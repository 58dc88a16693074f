"""chip_smoke.py's phase functions at a tiny size on the CPU.

The script itself only passes on the chip. What can be held to account
here: its checks pass on a healthy system and catch a planted fault,
``main()`` refuses to report a result without a TPU, and the
compile-cache helper leaves the directory to whoever placed it.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from ra_tpu.ops import consensus as C  # noqa: E402
from ra_tpu.utils import lib  # noqa: E402

G = 64


@pytest.fixture(scope="module")
def stats():
    return chip_smoke.CompileStats()


@pytest.mark.parametrize("seed", [0, 1])
def test_step_variants_hold_to_the_oracle(seed):
    counts = chip_smoke.check_step_variants(G, 3, seed)
    decided = counts["aer"] + counts["vote"] + counts["pre_vote"]
    assert decided > G // 4 and counts["quorum"] > 0
    assert counts["appended_runs"] > 0 and counts["written"] > 0


def test_oracle_catches_a_wrong_decision():
    rng = np.random.default_rng(7)
    st = chip_smoke.seeded_state(rng, G, 3)
    mb = chip_smoke.seeded_mailbox(rng, st)
    state = C.GroupState(**{f: jnp.asarray(st[f]) for f in C.GroupState._fields})
    mbox = C.Mailbox(**{f: jnp.asarray(mb[f]) for f in C.Mailbox._fields})
    _, eg = C.consensus_step(state, mbox)
    eg = {f: np.array(a) for f, a in zip(C.Egress._fields, eg)}
    chip_smoke.oracle_check(st, mb, eg)
    leader = int(np.flatnonzero(eg["role"] == C.R_LEADER)[0])
    eg["commit_advanced_to"][leader] += 1
    with pytest.raises(AssertionError, match="commit_advanced_to"):
        chip_smoke.oracle_check(st, mb, eg)


def test_scatter_reference_catches_a_dropped_run():
    rng = np.random.default_rng(3)
    st = chip_smoke.seeded_state(rng, G, 3)
    hi = int(st["last_index"][5]) + 2
    want = chip_smoke.scatter_reference(st, [(5, hi - 1, hi, 9)], {5: hi})
    assert want["last_index"][5] == hi and want["last_term"][5] == 9
    assert want["written_index"][5] == hi
    assert (want["last_index"][:5] == st["last_index"][:5]).all()


def test_pallas_check_never_interprets():
    """Off the chip there is no Mosaic: the compiled-kernel check raises
    rather than falling back to interpret mode."""
    with pytest.raises(ValueError, match="interpret mode"):
        chip_smoke.check_pallas(G, 0)


def test_cluster_phase_holds_replicas_to_the_fold(tmp_path, stats):
    rec = chip_smoke.phase_cluster(G, 2, 5, str(tmp_path), 4, stats)
    assert rec["groups"] == G and rec["coordinators"] == 3
    assert rec["started_loops"] and rec["wal_backed"]
    assert rec["acknowledged"] == 2 * G + 4 and rec["reads_checked"] == 4
    assert rec["equal_to_fold"] and rec["wal_fsyncs"] > 0
    assert rec["lane_wedges"] == rec["elections_after_first"] == 0
    # warm_steps() ran every program the traffic then dispatched
    assert rec["programs_warmed"] > 0
    assert rec["compilations_while_serving"] == 0
    assert rec["steps"] >= rec["sub_steps"] > 0
    assert rec["fs_type"] != "unknown" and rec["fsync_median_ms"] > 0
    assert os.path.isdir(tmp_path / "chip_smoke" / "smoke0" / "wal")


def test_warm_steps_under_a_mesh_leaves_the_state_alone():
    from jax.sharding import Mesh

    from ra_tpu.runtime.coordinator import BatchCoordinator
    from ra_tpu.runtime.transport import NodeRegistry

    mesh = Mesh(np.array(jax.devices()[:8]), ("groups",))
    c = BatchCoordinator("warm0", capacity=G, num_peers=3, mesh=mesh,
                         nodes=NodeRegistry())
    try:
        before = [np.asarray(a) for a in c.state]
        # the sharded step, then set_roles at 1, 2, ... 64 rows
        assert c.warm_steps() == 1 + 7
        assert all(np.array_equal(a, np.asarray(b))
                   for a, b in zip(before, c.state))
        assert c.steps == 0
    finally:
        c.stop()


@pytest.mark.parametrize("group,caught_by", [
    (G // 2, "consistent_query"),  # a group the reads sample: the read sees it
    (G // 2 + 1, "differ from the fold"),  # any other: the replica states do
])
def test_fold_catches_a_planted_lost_command(
        tmp_path, stats, monkeypatch, group, caught_by):
    """Every replica acknowledges and then drops one payload: only the
    comparison with the plain fold can see it."""
    lost = chip_smoke.make_payloads(5, G, 2)[1][group]
    monkeypatch.setattr(
        chip_smoke, "adder", lambda cmd, s: s if cmd == lost else s + cmd)
    with pytest.raises(AssertionError, match=caught_by):
        chip_smoke.phase_cluster(G, 2, 5, str(tmp_path), 4, stats)


def test_main_reports_nothing_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


@pytest.fixture
def cache_config():
    """The helper turns the persistent cache on for the process: put
    JAX's settings back so that no later test compiles through it."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_cache_dir_placed_from_outside_is_left_alone(
        monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert lib.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_cache_dir_defaults_to_a_fixed_path_in_the_checkout(
        monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(ROOT, ".jax_cache")
    assert lib.enable_compile_cache() == fixed
    assert lib.enable_compile_cache() == fixed  # never a pid, time or temp name
    assert jax.config.jax_compilation_cache_dir == fixed
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
