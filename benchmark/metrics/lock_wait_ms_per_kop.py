"""What the wave threads wait for the coordinators' state lock, per
1,000 acknowledged operations: the totals of the wave sub-phases
``step_lock_wait`` (step thread: classified -> lock held) +
``egress_lock_wait`` (egress thread: egress synced -> lock held), the
three coordinators added."""

UNIT = "ms/kop"
LAYER = "wave loop"
MOVES = "ops_s"

PHASES = ("step_lock_wait", "egress_lock_wait")


def read(run):
    if run.deltas is None or run.acked <= 0:
        return None
    hists = [run.deltas.hist("wave", p) for p in PHASES]
    if any(h is None for h in hists):
        return None
    return sum(h.total_ns for h in hists) / 1e6 / (run.acked / 1000.0)
