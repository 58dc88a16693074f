"""Deliveries the leaders' machines sent (``send_msg`` effects realised,
the coordinators' counter ``effects_send_msg``) per 1,000 acknowledged
operations: about 500 where every enqueue + settle pair causes one
delivery."""

UNIT = "1/kop"
LAYER = "apply + reply"
MOVES = "ops_s"


def read(run):
    if run.deltas is None or run.acked <= 0:
        return None
    if "effects_send_msg" not in run.deltas.after["coordinator"]:
        return None  # a program without the counter
    return 1000.0 * run.deltas.counter("coordinator", "effects_send_msg") \
        / run.acked
