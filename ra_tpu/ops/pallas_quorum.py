"""Pallas TPU kernel: masked quorum scan over the peer axis.

The per-step hot op of the batch backend is ``agreed_commit`` — for every
group, the majority-replicated index = the (nvoters//2)-th largest of the
voter-masked match vector (reference semantics: agreed_commit
src/ra_server.erl:3684-3688; scalar spec: ra_tpu.ops.decisions).

Layout: the peer axis (P <= 8) maps onto VPU sublanes and groups onto
lanes, so one (8, 128) register tile holds 128 groups' full match
vectors. A fixed odd-even transposition network (P passes of
compare-exchange between adjacent sublanes) sorts every lane
simultaneously — no data-dependent control flow, no cross-lane traffic.
The majority row is then selected per-lane by comparing a sublane iota
against ``P - 1 - nvoters // 2``.

``agreed_commit_pallas`` is numerically identical to the ``jnp.sort``
path used inside ``consensus_step``: tests/test_pallas_quorum.py checks
parity in interpret mode on the CPU, tests/test_chip_compile.py compiles
it with Mosaic for the v5e, and chip_smoke.py runs the compiled kernel
on the chip against ``agreed_commit_sort``. Swap it in with
``ra_tpu.ops.consensus.configure(quorum_backend="pallas")`` before the
first step. XLA already fuses the sort path well — this
kernel exists for the configurations where the sort's O(P log P)
generality loses to the fixed P-pass network and to keep the scan inside
one VMEM-resident fusion as P grows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MAX_P = 8


def _quorum_kernel(match_ref, nvoters_ref, out_ref):
    # tile: (MAX_P, LANES) — peers on sublanes, groups on lanes; the
    # wrapper has already folded the voter mask in (non-voters are -1).
    # Everything stays int32: Mosaic has no vector-register layout for
    # a rolled i1 mask, so the compare-exchange partners are picked
    # from the sublane iota instead of from a shifted boolean.
    m = match_ref[...]
    rows = jax.lax.broadcasted_iota(jnp.int32, m.shape, 0)
    # odd-even transposition sort along the sublane (peer) axis,
    # ascending: after MAX_P passes every lane is sorted
    for p in range(MAX_P):
        start = p % 2
        up = pltpu.roll(m, MAX_P - 1, 0)  # row r holds m[r + 1]
        down = pltpu.roll(m, 1, 0)  # row r holds m[r - 1]
        is_lo = (rows % 2 == start) & (rows < MAX_P - 1)
        is_hi = (rows % 2 != start) & (rows >= 1)
        m = jnp.where(
            is_lo, jnp.minimum(m, up),
            jnp.where(is_hi, jnp.maximum(m, down), m),
        )
    # majority row per lane: ascending position MAX_P - 1 - nvoters // 2
    pos = MAX_P - 1 - nvoters_ref[...] // 2  # (1, LANES) broadcast row
    out_ref[...] = jnp.max(
        jnp.where(rows == pos, m, -(2 ** 31 - 1)), axis=0, keepdims=True
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def agreed_commit_pallas(
    match: jax.Array,  # i32[G, P]
    voting: jax.Array,  # bool[G, P]
    nvoters: jax.Array,  # i32[G]
    interpret: bool = False,
) -> jax.Array:
    """Per-group agreed commit index (majority-replicated match)."""
    g, p = match.shape
    assert p <= MAX_P, f"peer width {p} exceeds {MAX_P}"
    gp = ((g + LANES - 1) // LANES) * LANES
    # transpose to (P, G): peers on sublanes, groups on lanes; pad peers
    # with -1 (never selected) and groups to a lane multiple
    mt = jnp.full((MAX_P, gp), -1, jnp.int32)
    mt = mt.at[:p, :g].set(jnp.where(voting, match, -1).T)
    nv = jnp.zeros((1, gp), jnp.int32).at[0, :g].set(nvoters)

    out = pl.pallas_call(
        _quorum_kernel,
        grid=(gp // LANES,),
        in_specs=[
            pl.BlockSpec((MAX_P, LANES), lambda i: (0, i)),
            pl.BlockSpec((1, LANES), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, LANES), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, gp), jnp.int32),
        interpret=interpret,
    )(mt, nv)
    return out[0, :g]


def agreed_commit_reference(match, voting, nvoters):
    """The exact formulation consensus_step's sort backend executes —
    shared, so parity tests cover the production path."""
    from ra_tpu.ops.consensus import agreed_commit_sort

    return agreed_commit_sort(match, voting, nvoters)
