"""Write latency, client call to committed-and-applied reply, 95th
percentile of all writes acknowledged inside the window."""

UNIT = "ms"


def read(run):
    return run.ops["write"].p_ms(95) if "write" in run.ops else None
