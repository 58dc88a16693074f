"""Write latency, client call to committed-and-applied reply, median."""

UNIT = "ms"


def read(run):
    return run.ops["write"].p_ms(50) if "write" in run.ops else None
