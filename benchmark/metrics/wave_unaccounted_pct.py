"""The share of the wave loop's host time that no leaf sub-phase names:
100 x (the totals of the wave phases ``ingress_drain`` + ``host_pack`` +
``host_egress`` + ``aer_fanout``, which ``host_ms_per_kop`` adds, less
the totals of their leaves) over those phases' totals, the three
coordinators added. The leaves: of ``ingress_drain``
``ingress_classify``, ``step_lock_wait``, ``ingress_route``,
``ingest_append`` and ``ingest_fanout``; of ``host_pack``
``scatter_dispatch``, ``mailbox_build`` and ``step_dispatch``; of
``host_egress`` ``egress_follow``, ``egress_mirror``, ``egress_apply``
and ``egress_rare``; ``aer_fanout`` is its own (``effects_realise`` lies
inside a leaf and is not one). What is left is the code between two
leaves' clock reads; over 5 % a leaf is missing."""

UNIT = "%"
LAYER = "wave loop"
MOVES = "ops_s"
PHASES = ("ingress_drain", "host_pack", "host_egress", "aer_fanout")
LEAVES = ("ingress_classify", "step_lock_wait", "ingress_route",
          "ingest_append", "ingest_fanout",
          "scatter_dispatch", "mailbox_build", "step_dispatch",
          "egress_follow", "egress_mirror", "egress_apply", "egress_rare",
          "aer_fanout")


def read(run):
    if run.deltas is None:
        return None
    phases = [run.deltas.hist("wave", p) for p in PHASES]
    leaves = [run.deltas.hist("wave", p) for p in LEAVES]
    if any(h is None for h in phases + leaves):
        return None  # a program without the accounts
    whole = sum(h.total_ns for h in phases)
    if whole <= 0:
        return None
    return 100.0 * (whole - sum(h.total_ns for h in leaves)) / whole
