"""Active-set (activity-scaled) stepping parity.

The coordinator's sub-batch step gathers only groups with pending
device work, runs the fused step over the compact batch, and scatters
results back (``ra_tpu/ops/consensus.py`` ``consensus_step_packed_sub``).
It must be observationally identical to the full-width step — same
leaders, same commits, same machine states — across election, pipelined
commands, membership and failover. The reference analog is per-group
processes waking only on messages (src/ra_server_proc.erl:457-530).
"""

import time

import numpy as np
import pytest

from ra_tpu import api
from ra_tpu.machine import SimpleMachine
from ra_tpu.ops import consensus as C
from ra_tpu.protocol import Command, ElectionTimeout, USR
from ra_tpu.runtime.coordinator import BatchCoordinator


def await_(cond, timeout=30.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = cond()
        if v:
            return v
        time.sleep(0.01)
    raise AssertionError(f"timeout waiting for {what}")


def adder():
    return SimpleMachine(lambda c, s: s + c, 0)


def _run_cluster(mode, prefix, groups=6, cmds=17):
    """Elect leaders for `groups` groups across 3 coordinators, pipeline
    `cmds` commands to each, kill one coordinator mid-stream, and return
    the surviving machine states."""
    coords = [
        BatchCoordinator(f"{prefix}{i}", capacity=64, num_peers=3,
                         active_set=mode, election_timeout_s=0.05,
                         detector_poll_s=0.02)
        for i in range(3)
    ]
    try:
        for c in coords:
            c.start()
        members = lambda g: [(f"g{g}", f"{prefix}{i}") for i in range(3)]  # noqa: E731
        for i, c in enumerate(coords):
            c.add_groups(
                [(f"g{g}", f"cl{g}", members(g), adder()) for g in range(groups)]
            )
        for g in range(groups):
            coords[0].deliver((f"g{g}", f"{prefix}0"), ElectionTimeout(), None)
        await_(
            lambda: all(
                coords[0].by_name[f"g{g}"].role == C.R_LEADER
                for g in range(groups)
            ),
            what=f"leaders ({mode})",
        )
        futs = []
        for k in range(cmds):
            for g in range(groups):
                fut = api.Future()
                coords[0].deliver(
                    (f"g{g}", f"{prefix}0"),
                    Command(kind=USR, data=k + 1, reply_mode="await_consensus", from_ref=fut),
                    None,
                )
                futs.append(fut)
        for fut in futs:
            tag, val, _ = fut.result(timeout=30)
            assert tag == "ok"
        total = sum(range(1, cmds + 1))
        await_(
            lambda: all(
                coords[0].by_name[f"g{g}"].machine_state == total
                for g in range(groups)
            ),
            what=f"applied ({mode})",
        )
        # failover: stop the leader node; another member must take over
        # and serve a command
        coords[0].stop()
        fut = api.Future()

        def leader_elsewhere():
            for c in coords[1:]:
                g = c.by_name["g0"]
                if g.role == C.R_LEADER:
                    return c
            return None

        c = await_(leader_elsewhere, what=f"failover leader ({mode})")
        fut = api.Future()
        c.deliver((next(iter(c.by_name)), c.name),
                  Command(kind=USR, data=100, reply_mode="await_consensus", from_ref=fut), None)
        tag, val, _ = fut.result(timeout=30)
        assert tag == "ok"
        return {
            "g0_state": val,
            "total": total,
        }
    finally:
        for c in coords:
            c.stop()


@pytest.mark.parametrize("mode", ["always", "never", "auto"])
def test_cluster_parity_across_step_modes(mode):
    # "auto" — the shipped default — is in the matrix since round 6:
    # the round-5 wedge shipped precisely because no test ran it
    out = _run_cluster(mode, f"as_{mode[:2]}")
    assert out["g0_state"] == out["total"] + 100


def test_auto_mode_flip_soak_crosses_saturation_boundary():
    """Drive an "auto" cluster across the capacity/4 saturation
    boundary in BOTH directions: a hot set wider than capacity >> 2
    forces full-width steps, a narrow one re-engages the sub path, then
    wide again — the sub<->full transitions and the hot-set carryover
    across them must not lose or wedge any command
    (coordinator.py active-set selection; VERDICT r5 item 5)."""
    groups = 24  # capacity 32 -> threshold 8: 24 saturates, 3 does not
    coords = [
        BatchCoordinator(f"fs{i}", capacity=32, num_peers=3,
                         active_set="auto", election_timeout_s=0.05,
                         detector_poll_s=0.02)
        for i in range(3)
    ]
    try:
        for c in coords:
            c.start()
        members = lambda g: [(f"g{g}", f"fs{i}") for i in range(3)]  # noqa: E731
        for c in coords:
            c.add_groups(
                [(f"g{g}", f"cl{g}", members(g), adder()) for g in range(groups)]
            )
        for g in range(groups):
            coords[0].deliver((f"g{g}", "fs0"), ElectionTimeout(), None)
        await_(
            lambda: all(
                coords[0].by_name[f"g{g}"].role == C.R_LEADER
                for g in range(groups)
            ),
            what="leaders (flip soak)",
        )

        def burst(gids, k):
            futs = []
            for _ in range(k):
                for g in gids:
                    fut = api.Future()
                    coords[0].deliver(
                        (f"g{g}", "fs0"),
                        Command(kind=USR, data=1,
                                reply_mode="await_consensus", from_ref=fut),
                        None,
                    )
                    futs.append(fut)
            for fut in futs:
                tag, _val, _ = fut.result(timeout=30)
                assert tag == "ok"

        expect = [0] * groups
        for phase, gids in enumerate(
            [range(groups), range(3), range(groups), range(4, 7),
             range(groups)]
        ):
            burst(list(gids), 5)
            for g in gids:
                expect[g] += 5
        await_(
            lambda: all(
                coords[0].by_name[f"g{g}"].machine_state == expect[g]
                for g in range(groups)
            ),
            what="all applied after mode flips",
        )
        # both step paths actually ran on the leader coordinator
        assert coords[0].sub_steps > 0, "sub path never engaged"
        assert coords[0].steps > coords[0].sub_steps, "full path never engaged"
    finally:
        for c in coords:
            c.stop()


@pytest.mark.parametrize("full_step,sub_step,scat", [
    ("consensus_step_packed", "consensus_step_packed_sub", False),
    ("consensus_step_packed_scat", "consensus_step_packed_sub_scat", True),
])
def test_active_set_sub_step_matches_full_step_kernel(full_step, sub_step,
                                                      scat):
    """Kernel-level parity: the same mailbox applied via the sub-batch
    gather/scatter path and via the full-width path must produce
    identical state and egress rows for the active groups. The
    sub-batch step reads its gather index from the packed buffer's last
    row, and takes the numpy buffer as the wave loop hands it over; the
    ``_scat`` pair carries an appended run and a durable watermark for
    the active groups in the scatter rows of both buffers."""
    import jax.numpy as jnp

    G, P = 32, 3
    state_a = C.make_group_state(G, P)
    state_b = C.make_group_state(G, P)
    # give rows distinct tails so the quorum scan has structure
    li = jnp.arange(G, dtype=jnp.int32) % 7
    # donated buffers must be distinct per field
    state_a = state_a._replace(last_index=li + 0, written_index=li + 0)
    state_b = state_b._replace(last_index=li + 0, written_index=li + 0)

    act = [3, 11, 17]
    fields = list(C.MBOX_FIELDS) + (C.MBOX_SCAT_FIELDS if scat else [])
    Rm = {name: i for i, name in enumerate(fields)}
    # full-width mailbox: one AER per active row; the sub-batch buffer
    # has the index row more
    full = np.zeros((len(fields), G), np.int32)
    sub = np.zeros((len(fields) + 1, 4), np.int32)
    sub[-1] = G  # pads: dropped by the scatter back
    sub[-1, :3] = act
    for arr in (full, sub):
        arr[Rm["host_term_idx"]].fill(-1)
        arr[Rm["host_term_val"]].fill(-1)
        if scat:
            # scatter rows are a list, not per column: the same in both
            arr[Rm["a_gid"]].fill(G)
            arr[Rm["w_gid"]].fill(G)
            for r, g in enumerate(act):
                tail = int(li[g])
                arr[Rm["a_gid"], r] = arr[Rm["w_gid"], r] = g
                arr[Rm["a_lo"], r] = tail + 1
                arr[Rm["a_hi"], r] = arr[Rm["w_idx"], r] = tail + 2
                arr[Rm["a_term"], r] = 1
    for p, g in enumerate(act):
        for arr, col in ((full, g), (sub, p)):
            arr[Rm["msg_type"], col] = C.MSG_AER
            arr[Rm["term"], col] = 1
            arr[Rm["prev_idx"], col] = int(li[g])
            arr[Rm["prev_term"], col] = 0
            arr[Rm["num_entries"], col] = 2
            arr[Rm["entries_last_term"], col] = 1
            arr[Rm["leader_commit"], col] = int(li[g]) + 2

    new_a, eg_a = getattr(C, full_step)(state_a, full)
    new_b, eg_b = getattr(C, sub_step)(state_b, sub)
    eg_a = np.asarray(eg_a)
    eg_b = np.asarray(eg_b)
    for p, g in enumerate(act):
        np.testing.assert_array_equal(eg_a[:, g], eg_b[:, p])
    for fa, fb in zip(new_a, new_b):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
    if scat:
        assert [int(np.asarray(new_b.written_index)[g]) for g in act] == [
            int(li[g]) + 2 for g in act]
