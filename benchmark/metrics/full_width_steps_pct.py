"""Share of the window's device steps that ran the full-width program
(``consensus_step_packed_scat`` over every group's column) and not the
active-set one: 100 x (``steps`` - ``sub_steps``) / ``steps``, the three
coordinators added. Says whether a cell still works the full-width
hand-off (mailbox of ``capacity`` columns, ``_build_mailbox``)."""

UNIT = "%"
LAYER = "device programs"
MOVES = "ops_s"


def read(run):
    if run.deltas is None:
        return None
    steps = run.deltas.scalar("steps")
    if steps <= 0:
        return None
    return 100.0 * (steps - run.deltas.scalar("sub_steps")) / steps
