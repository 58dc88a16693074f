"""The full-width step's share of its memory roofline, under the name the
builder's contract gives a kernel's share (``<kernel>_roofline``): the
reading of ``step_roofline_pct.py``, which stays for
``tests/data/unproven_cells.json`` until a ``benchmark`` PR moves the
formula here (ROADMAP D9)."""

from benchmark import harness

UNIT = "%"
LAYER = "device programs"
MOVES = "ops_s"


def read(run):
    return harness.load_module("metrics", "step_roofline_pct").read(run)
