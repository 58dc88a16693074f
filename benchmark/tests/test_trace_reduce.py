"""``trace_reduce`` against the small trace recorded on a TPU v5e (the
fleet cell at 8 groups, a 0.2 s window: 90 full-width steps and one
active-set step by the coordinators' own ``steps`` counters in that
run), and against planes written by hand."""

import os

import pytest

from benchmark import trace_reduce as T

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "v5e_fleet_8groups.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    return T.reduce_file(RECORDED)


def test_recorded_trace_programs_and_steps(recorded):
    assert recorded["devices"] == 1
    assert set(recorded["programs"]) == {
        "_consensus_step_packed_scat_impl",
        "_consensus_step_packed_sub_scat_impl"}
    assert recorded["step_count"] == 91 and recorded["full_step_count"] == 90
    assert recorded["step_seconds"] == pytest.approx(0.001249624, rel=1e-6)
    assert recorded["full_step_seconds"] == pytest.approx(0.001217185, rel=1e-6)


def test_recorded_trace_busy_time(recorded):
    # the operations' union: inside the programs' time, and most of it
    assert recorded["busy_s"] == pytest.approx(0.001188133, rel=1e-6)
    assert 0.9 * recorded["step_seconds"] < recorded["busy_s"] \
        <= recorded["step_seconds"]
    # a 0.2 s window: the device was idle for more than 99 % of it
    assert recorded["busy_s"] / 0.2 < 0.01


def test_recorded_trace_breakdown(recorded):
    ops, gaps = recorded["device_ops"], recorded["idle_gaps"]
    assert 1 <= len(ops) <= 10 and 1 <= len(gaps) <= 10
    assert all(name.startswith("_consensus_step_packed") and "/" in name
               and len(name) < 100 for name, _s in ops)
    assert ops == sorted(ops, key=lambda x: -x[1])
    assert sum(s for _n, s in ops) <= recorded["busy_s"]
    # the gaps between programs are the idle time inside the traced span
    assert 0.19 < sum(s for _n, s in gaps) < 0.22


def test_planes_by_hand():
    planes = [
        ("/device:TPU:0", {
            "XLA Ops": [("%fusion.1 = s32[8] fusion(...)", 0, 10),
                        ("%copy.2 = s32[8] copy(...)", 5, 20),
                        ("%fusion.1 = s32[8] fusion(...)", 100, 130)],
            "XLA Modules": [
                ("jit__consensus_step_packed_scat_impl(12)", 0, 20),
                ("jit_set_roles(3)", 50, 60),
                ("jit__consensus_step_packed_sub_scat_impl(1)", 100, 130)]}),
        ("/host:CPU", {"python3": [("np.asarray(jax.Array)", 0, 1000)]}),
    ]
    got = T.reduce_planes(planes)
    assert got["busy_s"] == pytest.approx(50e-9)  # [0,20) and [100,130)
    assert got["step_count"] == 2 and got["full_step_count"] == 1
    assert got["step_seconds"] == pytest.approx(50e-9)
    assert got["programs"]["set_roles"] == {"count": 1, "seconds": 1e-8}
    assert got["device_ops"][0] == [
        "_consensus_step_packed_sub_scat_impl/fusion.1", pytest.approx(30e-9)]
    assert ["_consensus_step_packed_scat_impl -> set_roles",
            pytest.approx(30e-9)] in got["idle_gaps"]


def test_no_device_operation_is_no_trace():
    assert T.reduce_planes([("/host:CPU", {"python3": [("x", 0, 5)]})]) is None
    assert T.reduce_planes([("/device:TPU:0", {"XLA Ops": []})]) is None


def test_union_and_names():
    assert T.union_seconds([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25
    assert T.union_seconds([]) == 0
    assert T.program_name("jit_copy(17647079920528058415)") == "copy"
    assert T.op_name("%fusion.28 = pred[256]{0} fusion(...)") == "fusion.28"
