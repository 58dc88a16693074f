"""Flight-recorder ``election`` events inside the window: nobody asked
for an election after set-up, so each is a leader lost under load."""

UNIT = "count"
LAYER = "failure detection"
MOVES = "commit_p95_ms"


def read(run):
    if run.deltas is None:
        return None
    return float(sum(e["kind"] == "election" for e in run.events))
