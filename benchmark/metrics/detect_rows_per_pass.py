"""Group rows the detector's per-row Python ran for, per pass: the
counters ``detector_rows_walked`` / ``detector_passes``, the three
nodes' added (``coordinator._detect_pass`` and ``_lane_watchdog``: the
rows their masks over the role, contact, ack and pending arrays left,
the suspicion sweep, the lane watchdog and the tick's probes counted
alike). A poll of a healthy elected fleet leaves none; a tick leaves the
groups with commands in flight and the led ones with a silent peer, and
one pass in ``tick_interval_s`` / ``detector_poll_s`` holds a tick."""

UNIT = "rows/pass"
LAYER = "failure detection"
MOVES = "ops_s"

COUNTER = "detector_rows_walked"


def read(run):
    if run.deltas is None or COUNTER not in run.deltas.after["coordinator"]:
        return None  # a program without the account
    passes = run.deltas.counter("coordinator", "detector_passes")
    if passes <= 0:
        return None
    return run.deltas.counter("coordinator", COUNTER) / passes
