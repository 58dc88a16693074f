"""``kv_get`` latency (consistent query + log fetch), 95th percentile."""

UNIT = "ms"


def read(run):
    return run.ops["read"].p_ms(95) if "read" in run.ops else None
