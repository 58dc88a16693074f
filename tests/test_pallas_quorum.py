"""Pallas quorum-scan kernel parity (interpret mode on CPU) against both
the jnp.sort formulation and the scalar oracle."""

import numpy as np
import pytest

import jax.numpy as jnp

from ra_tpu.ops import decisions as dec
from ra_tpu.ops.pallas_quorum import (
    agreed_commit_pallas,
    agreed_commit_reference,
)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("p", [3, 5, 7])
def test_pallas_matches_sort_and_oracle(seed, p):
    rng = np.random.default_rng(seed)
    g = 300  # deliberately not a lane multiple
    match = rng.integers(0, 1000, (g, p)).astype(np.int32)
    voting = rng.random((g, p)) < 0.8
    voting[:, 0] = True  # at least one voter per group
    nvoters = voting.sum(axis=1).astype(np.int32)

    got = np.asarray(
        agreed_commit_pallas(
            jnp.asarray(match), jnp.asarray(voting), jnp.asarray(nvoters),
            interpret=True,
        )
    )
    ref = np.asarray(
        agreed_commit_reference(
            jnp.asarray(match), jnp.asarray(voting), jnp.asarray(nvoters)
        )
    )
    np.testing.assert_array_equal(got, ref)
    # and against the scalar oracle
    for i in range(g):
        voters = [int(match[i, s]) for s in range(p) if voting[i, s]]
        assert got[i] == dec.agreed_commit(voters), (i, voters)


def test_pallas_full_and_single_voter_edges():
    # all voters present; single-voter groups return their own match
    match = jnp.asarray([[5, 9, 7], [3, 0, 0]], jnp.int32)
    voting = jnp.asarray([[True, True, True], [True, False, False]])
    nvoters = jnp.asarray([3, 1], jnp.int32)
    got = np.asarray(agreed_commit_pallas(match, voting, nvoters, interpret=True))
    assert got[0] == 7  # median of {5,9,7}
    assert got[1] == 3


def test_configure_pallas_backend_in_full_step():
    """consensus_step with quorum_backend='pallas' must agree with the
    sort backend on random states."""
    from ra_tpu.ops import consensus as C

    rng = np.random.default_rng(5)
    g = 64
    st = C.make_group_state(g, 3)
    st = st._replace(
        role=jnp.full((g,), C.R_LEADER, jnp.int32),
        current_term=jnp.ones((g,), jnp.int32),
        written_index=jnp.asarray(rng.integers(0, 10, g), jnp.int32),
        match_index=jnp.asarray(rng.integers(0, 10, (g, 3)), jnp.int32),
        last_index=jnp.full((g,), 10, jnp.int32),
        last_term=jnp.ones((g,), jnp.int32),
        term_suffix=jnp.ones_like(st.term_suffix),
    )
    mb = C.empty_mailbox(g)
    import jax

    ref_st, _ = C.consensus_step(jax.tree.map(jnp.copy, st), mb)
    try:
        C.configure(quorum_backend="pallas", pallas_interpret=True)
        pal_st, _ = C.consensus_step(jax.tree.map(jnp.copy, st), mb)
    finally:
        C.configure(quorum_backend="sort")
    np.testing.assert_array_equal(
        np.asarray(ref_st.commit_index), np.asarray(pal_st.commit_index)
    )
    with pytest.raises(ValueError):
        C.configure(quorum_backend="nope")
