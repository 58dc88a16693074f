"""The full-width step's share of its memory roofline: the bytes one
full-width step must read and write (``roofline.step_bytes``, from the
``GroupState``, mailbox and egress shapes), over the device's peak
memory bandwidth (``peaks.json``), over the traced device time of a
full-width step. Memory-bound by construction: the step is integer
compares and selects, no matrix unit work."""

UNIT = "%"
LAYER = "device programs"
MOVES = "ops_s"


def read(run):
    t = run.trace
    if not t or t["full_step_count"] <= 0 or not run.step_bytes:
        return None
    from benchmark import roofline

    per_step_s = t["full_step_seconds"] / t["full_step_count"]
    least_s = run.step_bytes / roofline.peak(run.device["kind"], "hbm_bytes_per_s")
    return 100.0 * least_s / per_step_s
