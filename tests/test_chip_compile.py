"""Compile the main path's device programs for the real chip.

The only test file that describes the chip: the TPU compiler is
installed in the sandbox and compiles for a v5e that is described, not
attached. Nothing here runs on a device, so nothing here is a result or
a time; what it guards is that every later PR's kernels are still
accepted by the chip's compiler at the width production runs
(G=10,240, P=3; chip_smoke.py runs the same programs on the chip).

The topology is described inside a module-scoped fixture: only the
xdist worker that is handed this file loads the TPU library, and every
worker collects the same tests.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from ra_tpu.ops import consensus as C
from ra_tpu.ops.pallas_quorum import agreed_commit_pallas
from ra_tpu.runtime.coordinator import BatchCoordinator

G = 10240
SUB = 256  # the active-set path's smallest sub-batch width
SCAT = 1024  # a power-of-two scatter batch, as BatchCoordinator._pad makes
NROWS = BatchCoordinator._NROWS
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — whatever the plugin raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without the chip: keep it off here
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _state(g, p, sharding):
    shapes = jax.eval_shape(lambda: C.make_group_state(g, p))
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        shapes,
    )


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _step_args(variant, p, sh):
    """Abstract arguments of one step variant at G x p on ``sh``."""
    state = _state(G, p, sh)
    if variant == "consensus_step":
        mbox = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            jax.eval_shape(lambda: C.empty_mailbox(G)),
        )
        return state, mbox
    if "_sub" in variant:
        # the gather index is the packed buffer's last row
        return state, _i32((NROWS + 1, SUB), sh)
    return state, _i32((NROWS, G), sh)


@pytest.mark.parametrize("variant,p", [
    ("consensus_step", 3),
    ("consensus_step_packed", 3),
    ("consensus_step_packed_sub", 3),
    ("consensus_step_packed_scat", 3),
    ("consensus_step_packed_sub_scat", 3),
    ("consensus_step_packed_scat", 5),
    ("consensus_step_packed_scat", 7),
])
def test_step_variant_compiles_for_v5e(one_chip, variant, p):
    # what the chip's compiler refuses, .compile() raises
    getattr(C, variant).lower(*_step_args(variant, p, one_chip)).compile()


@pytest.mark.parametrize("scatter,ncols", [
    ("record_appended_runs", 4),
    ("record_written", 2),
    ("set_roles", 2),
])
def test_scatter_compiles_for_v5e(one_chip, scatter, ncols):
    cols = [_i32((SCAT,), one_chip) for _ in range(ncols)]
    getattr(C, scatter).lower(_state(G, 3, one_chip), *cols).compile()


@pytest.mark.parametrize("p", [3, 5, 7])
def test_pallas_quorum_compiles_with_mosaic(one_chip, p):
    compiled = agreed_commit_pallas.lower(
        _i32((G, p), one_chip),
        jax.ShapeDtypeStruct((G, p), jnp.bool_, sharding=one_chip),
        _i32((G,), one_chip),
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_step_compiles_without_collectives(topo):
    """The group axis of 4 x 10,240 groups over the four-device mesh,
    under the shardings BatchCoordinator(mesh=...) uses: every group's
    decision is independent, so the partitioned step must need no
    cross-device traffic at all."""
    mesh = Mesh(np.array(topo.devices), ("groups",))
    axes = tuple(mesh.axis_names)
    shard_state = NamedSharding(mesh, PartitionSpec(axes))
    shard_mbox = NamedSharding(mesh, PartitionSpec(None, axes))
    g4 = 4 * G
    compiled = C.consensus_step_packed.lower(
        _state(g4, 3, shard_state), _i32((NROWS, g4), shard_mbox)
    ).compile()
    text = compiled.as_text()
    assert not [c for c in COLLECTIVES if c in text]
    new_state, egress = compiled.output_shardings
    assert all(
        len(s.device_set) == 4 for s in jax.tree.leaves(new_state)
    )
