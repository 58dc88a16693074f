"""Vectorized consensus kernels over a raft-group batch axis.

The TPU execution backend for the consensus decision hot path: per-group
scalar state lives in HBM as int32 structure-of-arrays indexed by
group-id, and the three north-star decisions run as one fused, jitted
step over *all* groups at once:

- AppendEntries accept (term/prev-log matching) — mirrors
  ``decisions.aer_decision`` (reference behavior: src/ra_server.erl
  handle_follower :1283-1429);
- RequestVote / PreVote grant — mirrors ``decisions.vote_decision`` /
  ``decisions.pre_vote_decision`` (reference: :1489-1529, :2926-2984);
- match_index -> commit_index quorum scan — mirrors
  ``decisions.agreed_commit`` (reference: :3633-3688).

Log *contents* stay host-side; the device keeps a ring-buffer window of
recent entry terms (``term_suffix``, indexed by ``idx % K``) so prev-term
matching and commit-term gating run without host round-trips. Groups
whose lookup falls outside the window raise a ``needs_host`` flag and are
resolved by the scalar oracle on the host (rare: deep backfill).

TPU-first design notes:
- everything is fixed-shape int32/bool; no data-dependent control flow —
  each step processes "at most one message per group" mailboxes, masked
  by ``msg_type``;
- the group axis is embarrassingly parallel: shard it over a
  ``jax.sharding.Mesh`` axis ("groups") and every kernel runs without
  collectives; only host ingress/egress crosses the boundary;
- P (replica slots) is a small static width; quorum scan is a sort along
  that axis (lane-local, VPU-friendly).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

# message type tags for the per-group mailbox
MSG_NONE = 0
MSG_AER = 1  # AppendEntries request (follower path)
MSG_AER_REPLY = 2  # AppendEntries reply (leader path)
MSG_VOTE_REQ = 3
MSG_VOTE_REPLY = 4
MSG_PREVOTE_REQ = 5
MSG_PREVOTE_REPLY = 6

# quorum-scan backend: "sort" (jnp.sort; XLA fuses it well) or "pallas"
# (the fixed odd-even network kernel in ra_tpu.ops.pallas_quorum).
# Switch with configure(quorum_backend=...) BEFORE the first step — it
# clears the jit caches so the choice takes effect. The kernel compiles
# for the device the step runs on, or the step raises: interpret mode is
# only ever chosen by a test that passes ``pallas_interpret=True``.
_QUORUM_BACKEND = "sort"
_PALLAS_INTERPRET = False


def configure(quorum_backend: str = None, pallas_interpret: bool = False) -> None:
    global _QUORUM_BACKEND, _PALLAS_INTERPRET
    if quorum_backend is not None:
        if quorum_backend not in ("sort", "pallas"):
            raise ValueError(f"unknown quorum_backend {quorum_backend!r}")
        _QUORUM_BACKEND = quorum_backend
        _PALLAS_INTERPRET = pallas_interpret
        for step in (
            consensus_step, consensus_step_packed, consensus_step_packed_sub,
            consensus_step_packed_scat, consensus_step_packed_sub_scat,
        ):
            step.clear_cache()


# roles
R_FOLLOWER = 0
R_PRE_VOTE = 1
R_CANDIDATE = 2
R_LEADER = 3

# AER decision codes (must match ra_tpu.ops.decisions)
AER_STALE = 0
AER_OK = 1
AER_MISMATCH = 2
AER_BEHIND_SNAPSHOT = 3


class GroupState(NamedTuple):
    """Per-group consensus state, shape [G] or [G, P]. ``self_slot`` is
    this coordinator's slot in each group's member table."""

    current_term: jax.Array  # i32[G]
    voted_for: jax.Array  # i32[G], peer slot or -1
    commit_index: jax.Array  # i32[G]
    last_applied: jax.Array  # i32[G]
    last_index: jax.Array  # i32[G] last visible log index
    last_term: jax.Array  # i32[G]
    written_index: jax.Array  # i32[G] durable watermark
    snapshot_index: jax.Array  # i32[G]
    snapshot_term: jax.Array  # i32[G]
    role: jax.Array  # i32[G]
    leader_slot: jax.Array  # i32[G], -1 unknown
    self_slot: jax.Array  # i32[G]
    machine_version: jax.Array  # i32[G] effective machine version
    match_index: jax.Array  # i32[G, P]
    next_index: jax.Array  # i32[G, P]
    voting: jax.Array  # bool[G, P]
    active: jax.Array  # bool[G, P]
    votes: jax.Array  # bool[G, P]
    pre_votes: jax.Array  # bool[G, P]
    term_suffix: jax.Array  # i32[G, K] ring buffer of entry terms
    # inclusive interval of indexes whose ring slots are stale (multi-
    # entry accepts record only the tail term until the host reconciles
    # via record_appended); empty when lo > hi
    unknown_lo: jax.Array  # i32[G]
    unknown_hi: jax.Array  # i32[G]
    # pre-vote round counter: bumped on every pre-vote entry so stale
    # grants from an earlier round can't combine with the current one
    # (mirrors Server.pre_vote_token; reference: token ref in
    # src/ra_server.erl call_for_election :2900-2924)
    pre_vote_token: jax.Array  # i32[G]


class Mailbox(NamedTuple):
    """At most one inbound message per group per step (dense)."""

    msg_type: jax.Array  # i32[G]
    sender_slot: jax.Array  # i32[G]
    term: jax.Array  # i32[G]
    # AER request fields
    prev_idx: jax.Array  # i32[G]
    prev_term: jax.Array  # i32[G]
    num_entries: jax.Array  # i32[G]
    entries_last_term: jax.Array  # i32[G] term of last entry in the batch
    leader_commit: jax.Array  # i32[G]
    # reply fields (AER reply) / vote fields
    success: jax.Array  # bool[G] (AER reply / vote granted)
    reply_next_idx: jax.Array  # i32[G]
    reply_last_idx: jax.Array  # i32[G]
    reply_last_term: jax.Array  # i32[G]
    cand_last_idx: jax.Array  # i32[G]
    cand_last_term: jax.Array  # i32[G]
    cand_machine_version: jax.Array  # i32[G]
    # host-resolved term cache: when a previous step flagged needs_host,
    # the host re-submits the message with the term it read from its log
    # at host_term_idx (-1 = no override)
    host_term_idx: jax.Array  # i32[G]
    host_term_val: jax.Array  # i32[G]
    # pre-vote reply round token (must match state.pre_vote_token to count)
    token: jax.Array  # i32[G]


class Egress(NamedTuple):
    """Per-group outbound decision for the host to serialize."""

    send_reply: jax.Array  # bool[G] reply to sender?
    reply_type: jax.Array  # i32[G] echoes request type
    reply_to: jax.Array  # i32[G] sender slot
    term: jax.Array  # i32[G]
    success: jax.Array  # bool[G]
    next_index: jax.Array  # i32[G]
    last_index: jax.Array  # i32[G]
    last_term: jax.Array  # i32[G]
    aer_code: jax.Array  # i32[G] accept decision (write entries iff OK)
    became_leader: jax.Array  # bool[G]
    became_candidate: jax.Array  # bool[G]
    commit_advanced_to: jax.Array  # i32[G] new commit index (== old if not)
    needs_host: jax.Array  # bool[G] fall back to scalar oracle
    term_or_vote_changed: jax.Array  # bool[G] host must persist term/vote
    # post-step mirror for the host (role/leader/current term/agreed idx)
    role: jax.Array  # i32[G]
    leader_slot: jax.Array  # i32[G]
    agreed_idx: jax.Array  # i32[G] quorum match point (for host term lookup)
    voted_for: jax.Array  # i32[G] post-step vote (slot or -1) for persistence


def make_group_state(num_groups: int, num_peers: int, suffix_k: int = 32) -> GroupState:
    g, p, k = num_groups, num_peers, suffix_k
    zi = lambda *s: jnp.zeros(s, dtype=jnp.int32)  # noqa: E731
    zb = lambda *s: jnp.zeros(s, dtype=jnp.bool_)  # noqa: E731
    return GroupState(
        current_term=zi(g),
        voted_for=jnp.full((g,), -1, jnp.int32),
        commit_index=zi(g),
        last_applied=zi(g),
        last_index=zi(g),
        last_term=zi(g),
        written_index=zi(g),
        snapshot_index=zi(g),
        snapshot_term=zi(g),
        role=zi(g),
        leader_slot=jnp.full((g,), -1, jnp.int32),
        self_slot=zi(g),
        machine_version=zi(g),
        match_index=zi(g, p),
        next_index=jnp.ones((g, p), jnp.int32),
        voting=jnp.ones((g, p), jnp.bool_),
        active=jnp.ones((g, p), jnp.bool_),
        votes=zb(g, p),
        pre_votes=zb(g, p),
        term_suffix=zi(g, k),
        unknown_lo=jnp.ones((g,), jnp.int32),
        unknown_hi=zi(g),
        pre_vote_token=zi(g),
    )


def empty_mailbox(num_groups: int) -> Mailbox:
    g = num_groups
    zi = lambda: jnp.zeros((g,), jnp.int32)  # noqa: E731
    return Mailbox(
        msg_type=zi(),
        sender_slot=zi(),
        term=zi(),
        prev_idx=zi(),
        prev_term=zi(),
        num_entries=zi(),
        entries_last_term=zi(),
        leader_commit=zi(),
        success=jnp.zeros((g,), jnp.bool_),
        reply_next_idx=zi(),
        reply_last_idx=zi(),
        reply_last_term=zi(),
        cand_last_idx=zi(),
        cand_last_term=zi(),
        cand_machine_version=zi(),
        host_term_idx=jnp.full((g,), -1, jnp.int32),
        host_term_val=jnp.full((g,), -1, jnp.int32),
        token=zi(),
    )


# ---------------------------------------------------------------------------
# device-side term lookup


def agreed_commit_sort(
    match: jax.Array, voting: jax.Array, nvoters: jax.Array
) -> jax.Array:
    """Quorum scan, jnp.sort formulation — the single shared
    implementation (the pallas kernel's parity reference and the default
    in-step backend)."""
    p = match.shape[-1]
    eff = jnp.where(voting, match, -1)
    srt = jnp.sort(eff, axis=-1)  # ascending; non-voters (-1) first
    pos = jnp.clip(p - 1 - nvoters // 2, 0, p - 1)
    return jnp.take_along_axis(srt, pos[:, None], axis=-1).squeeze(-1)


def term_at(state: GroupState, idx: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(term, known) — term of the entry at ``idx`` from the ring-buffer
    window / snapshot boundary. known=False → host fallback needed."""
    k = state.term_suffix.shape[-1]
    in_window = (idx > jnp.maximum(state.last_index - k, state.snapshot_index)) & (
        idx <= state.last_index
    )
    ring = jnp.take_along_axis(
        state.term_suffix, (idx % k)[..., None], axis=-1
    ).squeeze(-1)
    is_snap = idx == state.snapshot_index
    is_zero = idx <= 0
    stale = (idx >= state.unknown_lo) & (idx <= state.unknown_hi)
    term = jnp.where(is_zero, 0, jnp.where(is_snap, state.snapshot_term, ring))
    known = is_zero | is_snap | (in_window & ~stale)
    return term.astype(jnp.int32), known


def _log_up_to_date(our_idx, our_term, cand_idx, cand_term):
    return (cand_term > our_term) | ((cand_term == our_term) & (cand_idx >= our_idx))


# ---------------------------------------------------------------------------
# the fused step


def consensus_step_impl(state: GroupState, mbox: Mailbox) -> Tuple[GroupState, Egress]:
    """One decision step over all groups: classify at most one inbound
    message per group, update consensus bookkeeping, run the quorum scan.
    Pure function of (state, mailbox) — host performs all I/O."""
    G, P = state.match_index.shape
    gids = jnp.arange(G)

    is_aer = mbox.msg_type == MSG_AER
    is_aer_reply = mbox.msg_type == MSG_AER_REPLY
    is_vote_req = mbox.msg_type == MSG_VOTE_REQ
    is_vote_reply = mbox.msg_type == MSG_VOTE_REPLY
    is_prevote_req = mbox.msg_type == MSG_PREVOTE_REQ
    is_prevote_reply = mbox.msg_type == MSG_PREVOTE_REPLY
    has_msg = mbox.msg_type != MSG_NONE

    term0 = state.current_term
    voted0 = state.voted_for
    role0 = state.role

    # -- universal higher-term handling (pre-vote requests excluded: they
    #    probe without dethroning; pre-vote *replies* carry real terms)
    bumps_term = has_msg & ~is_prevote_req & (mbox.term > term0)
    term1 = jnp.where(bumps_term, mbox.term, term0)
    voted1 = jnp.where(bumps_term, -1, voted0)
    role1 = jnp.where(bumps_term, R_FOLLOWER, role0)
    leader1 = jnp.where(bumps_term, -1, state.leader_slot)

    # ---------------- AER (follower accept path) ----------------
    local_prev_term, prev_known = term_at(state, mbox.prev_idx)
    # host-resolved override (deep backfill outside the device window)
    prev_override = (mbox.host_term_idx == mbox.prev_idx) & (mbox.host_term_val >= 0)
    local_prev_term = jnp.where(prev_override, mbox.host_term_val, local_prev_term)
    prev_known = prev_known | prev_override
    aer_stale = mbox.term < term1
    aer_behind = mbox.prev_idx < state.snapshot_index
    aer_match = prev_known & (local_prev_term == mbox.prev_term)
    aer_code = jnp.where(
        aer_stale,
        AER_STALE,
        jnp.where(
            aer_behind,
            AER_BEHIND_SNAPSHOT,
            jnp.where(aer_match, AER_OK, AER_MISMATCH),
        ),
    ).astype(jnp.int32)
    aer_ok = is_aer & (aer_code == AER_OK)
    aer_fail_next = jnp.where(
        aer_behind,
        state.snapshot_index + 1,
        jnp.where(
            state.last_index < mbox.prev_idx,
            state.last_index + 1,
            state.commit_index + 1,
        ),
    )
    # host fallback when prev-term unknown on device (deep backfill)
    aer_needs_host = is_aer & ~aer_stale & ~aer_behind & ~prev_known

    # accepting an AER names the sender leader and becomes follower
    role2 = jnp.where(aer_ok, R_FOLLOWER, role1)
    leader2 = jnp.where(aer_ok, mbox.sender_slot, leader1)

    # log tail bookkeeping for accepted entries (host writes the bytes;
    # device tracks the resulting tail). Overwrite of a divergent suffix
    # rewinds last_index to prev+n.
    new_last = mbox.prev_idx + mbox.num_entries
    takes_entries = aer_ok & (mbox.num_entries > 0)
    last_index2 = jnp.where(takes_entries, new_last, state.last_index)
    last_term2 = jnp.where(takes_entries, mbox.entries_last_term, state.last_term)
    # record the accepted tail term in the ring so back-to-back device
    # steps can prev-match without host reconciliation (exact for the
    # batch's last entry; the host's record_appended covers the rest of
    # a multi-entry batch)
    kk = state.term_suffix.shape[-1]
    tail_slot = (new_last % kk)[:, None]
    term_suffix2 = jnp.where(
        (jnp.arange(kk)[None, :] == tail_slot) & takes_entries[:, None],
        mbox.entries_last_term[:, None],
        state.term_suffix,
    )
    # only the batch tail's term is exact: mark intermediate indexes of a
    # multi-entry accept stale until the host record_appended reconciles
    multi = takes_entries & (mbox.num_entries > 1)
    had_inv = state.unknown_lo <= state.unknown_hi
    unknown_lo2 = jnp.where(
        multi,
        jnp.where(had_inv, jnp.minimum(state.unknown_lo, mbox.prev_idx + 1),
                  mbox.prev_idx + 1),
        state.unknown_lo,
    )
    unknown_hi2 = jnp.where(
        multi, jnp.maximum(state.unknown_hi, new_last - 1), state.unknown_hi
    )
    # followers' commit index: min(leader_commit, last entry index)
    commit2 = jnp.where(
        aer_ok,
        jnp.maximum(state.commit_index, jnp.minimum(mbox.leader_commit, new_last)),
        state.commit_index,
    )

    # ---------------- votes ----------------
    fresh_term = mbox.term > term0
    free_to_vote = fresh_term | (voted1 == -1) | (voted1 == mbox.sender_slot)
    up_to_date = _log_up_to_date(
        last_index2, last_term2, mbox.cand_last_idx, mbox.cand_last_term
    )
    vote_grant = is_vote_req & (mbox.term >= term1) & free_to_vote & up_to_date
    voted2 = jnp.where(vote_grant, mbox.sender_slot, voted1)
    leader3 = jnp.where(vote_grant, -1, leader2)

    prevote_grant = (
        is_prevote_req
        & (mbox.term >= term1)
        & (mbox.cand_machine_version >= state.machine_version)
        & up_to_date
    )

    # ---------------- vote replies (candidate/pre_vote path) ----------------
    count_vote = is_vote_reply & (role1 == R_CANDIDATE) & mbox.success & (mbox.term == term1)
    votes2 = jnp.where(
        (count_vote[:, None] & (jnp.arange(P)[None, :] == mbox.sender_slot[:, None]))
        | state.votes,
        True,
        False,
    )
    votes2 = jnp.where(role1[:, None] == R_CANDIDATE, votes2, False)
    count_prevote = (
        is_prevote_reply
        & (role1 == R_PRE_VOTE)
        & mbox.success
        & (mbox.term <= term1)
        & (mbox.token == state.pre_vote_token)
    )
    pre_votes2 = jnp.where(
        (count_prevote[:, None] & (jnp.arange(P)[None, :] == mbox.sender_slot[:, None]))
        | state.pre_votes,
        True,
        False,
    )
    pre_votes2 = jnp.where(role1[:, None] == R_PRE_VOTE, pre_votes2, False)

    n_voters = jnp.sum(state.voting & state.active, axis=-1)
    quorum = n_voters // 2 + 1
    self_vote = jnp.take_along_axis(
        state.voting & state.active, state.self_slot[:, None], axis=-1
    ).squeeze(-1)
    n_votes = jnp.sum(votes2 & state.voting & state.active, axis=-1) + jnp.where(
        self_vote & (role1 == R_CANDIDATE), 1, 0
    )
    n_prevotes = jnp.sum(pre_votes2 & state.voting & state.active, axis=-1) + jnp.where(
        self_vote & (role1 == R_PRE_VOTE), 1, 0
    )
    became_leader = (role1 == R_CANDIDATE) & (n_votes >= quorum)
    became_candidate = (role1 == R_PRE_VOTE) & (n_prevotes >= quorum)

    role3 = jnp.where(became_leader, R_LEADER, role2)
    role3 = jnp.where(became_candidate, R_CANDIDATE, role3)
    # candidate promotion bumps the term and votes for self
    term2 = jnp.where(became_candidate, term1 + 1, term1)
    voted3 = jnp.where(became_candidate, state.self_slot, voted2)
    leader4 = jnp.where(became_leader, state.self_slot, leader3)
    votes3 = jnp.where(became_candidate[:, None], False, votes2)
    pre_votes3 = jnp.where(became_candidate[:, None], False, pre_votes2)

    # new leader resets peer bookkeeping
    match2 = jnp.where(became_leader[:, None], 0, state.match_index)
    next2 = jnp.where(
        became_leader[:, None], (last_index2 + 1)[:, None], state.next_index
    )

    # ---------------- AER replies (leader path) ----------------
    lead_ok = is_aer_reply & (role3 == R_LEADER) & (mbox.term == term2)
    sender_onehot = jnp.arange(P)[None, :] == mbox.sender_slot[:, None]
    succ = (lead_ok & mbox.success)[:, None] & sender_onehot
    match3 = jnp.where(succ, jnp.maximum(match2, mbox.reply_last_idx[:, None]), match2)
    next3 = jnp.where(
        succ, jnp.maximum(next2, mbox.reply_last_idx[:, None] + 1), next2
    )
    fail = (lead_ok & ~mbox.success)[:, None] & sender_onehot
    fail_hint = jnp.maximum(
        jnp.minimum(mbox.reply_next_idx, mbox.reply_last_idx + 1)[:, None], match3 + 1
    )
    next4 = jnp.where(fail, jnp.maximum(fail_hint, 1), next3)

    # ---------------- quorum commit scan (leaders, every step) ----------------
    is_self = jnp.arange(P)[None, :] == state.self_slot[:, None]
    eff_match = jnp.where(is_self, state.written_index[:, None], match3)
    if _QUORUM_BACKEND == "pallas" and P <= 8:
        from ra_tpu.ops.pallas_quorum import agreed_commit_pallas

        agreed = agreed_commit_pallas(
            eff_match,
            state.voting & state.active,
            n_voters,
            interpret=_PALLAS_INTERPRET,
        )
    else:
        # P > 8 exceeds the pallas kernel's sublane width: sort fallback
        agreed = agreed_commit_sort(eff_match, state.voting & state.active, n_voters)
    agreed_term, agreed_known = term_at(
        state._replace(
            last_index=last_index2,
            last_term=last_term2,
            term_suffix=term_suffix2,
            unknown_lo=unknown_lo2,
            unknown_hi=unknown_hi2,
        ),
        agreed,
    )
    agreed_override = (mbox.host_term_idx == agreed) & (mbox.host_term_val >= 0)
    agreed_term = jnp.where(agreed_override, mbox.host_term_val, agreed_term)
    agreed_known = agreed_known | agreed_override
    can_commit = (
        (role3 == R_LEADER)
        & (agreed > commit2)
        & agreed_known
        & (agreed_term == term2)
    )
    commit3 = jnp.where(can_commit, agreed, commit2)
    quorum_needs_host = (role3 == R_LEADER) & (agreed > commit2) & ~agreed_known

    # ---------------- egress ----------------
    reply_success = jnp.where(
        is_aer,
        aer_code == AER_OK,
        jnp.where(is_vote_req, vote_grant, jnp.where(is_prevote_req, prevote_grant, False)),
    )
    # AER success replies report the durable watermark (host may defer the
    # actual send until fsync when entries were written)
    wi = jnp.where(aer_ok, state.written_index, last_index2)
    reply_next = jnp.where(
        is_aer & (aer_code != AER_OK), aer_fail_next, wi + 1
    )
    egress = Egress(
        # a needs_host AER is resolved entirely by the host oracle — the
        # device must not also emit its (bogus) mismatch rejection
        send_reply=has_msg & ((is_aer & ~aer_needs_host) | is_vote_req | is_prevote_req),
        reply_type=mbox.msg_type,
        reply_to=mbox.sender_slot,
        term=term2,
        success=reply_success,
        next_index=reply_next,
        last_index=jnp.where(is_aer & aer_ok, wi, last_index2),
        last_term=last_term2,
        aer_code=jnp.where(is_aer, aer_code, -1),
        became_leader=became_leader,
        became_candidate=became_candidate,
        commit_advanced_to=commit3,
        needs_host=aer_needs_host | quorum_needs_host,
        term_or_vote_changed=(term2 != term0) | (voted3 != voted0),
        role=role3,
        leader_slot=leader4,
        agreed_idx=agreed,
        voted_for=voted3,
    )
    new_state = state._replace(
        current_term=term2,
        voted_for=voted3,
        commit_index=commit3,
        last_index=last_index2,
        last_term=last_term2,
        role=role3,
        leader_slot=leader4,
        match_index=match3,
        next_index=next4,
        votes=votes3,
        pre_votes=pre_votes3,
        term_suffix=term_suffix2,
        unknown_lo=unknown_lo2,
        unknown_hi=unknown_hi2,
    )
    return new_state, egress


# The production entry point: jitted with the state buffers donated so the
# G-sized arrays update in place in HBM.
consensus_step = jax.jit(consensus_step_impl, donate_argnums=(0,))


# Packed interface: the host coordinator ships the whole mailbox as ONE
# (len(MBOX_FIELDS), G) int32 array and receives the egress as ONE
# (len(EGRESS_FIELDS), G) int32 array — a single transfer each way per
# step instead of ~35 small ones. reply_to is intentionally omitted from
# the egress pack (hosts address replies via the consumed message's
# sender).
MBOX_FIELDS = [
    "msg_type", "sender_slot", "term", "prev_idx", "prev_term",
    "num_entries", "entries_last_term", "leader_commit", "success",
    "reply_next_idx", "reply_last_idx", "reply_last_term", "cand_last_idx",
    "cand_last_term", "cand_machine_version", "host_term_idx",
    "host_term_val", "token",
]
EGRESS_FIELDS = [
    "send_reply", "reply_type", "term", "success", "next_index",
    "last_index", "last_term", "aer_code", "became_leader",
    "became_candidate", "commit_advanced_to", "needs_host",
    "term_or_vote_changed", "role", "leader_slot", "agreed_idx",
    "voted_for",
]


# packed lists must track the namedtuples: a drifted field name would be
# silently dropped on the host side
assert set(MBOX_FIELDS) == set(Mailbox._fields), (
    set(MBOX_FIELDS) ^ set(Mailbox._fields)
)
assert set(EGRESS_FIELDS) == set(Egress._fields) - {"reply_to"}, (
    set(EGRESS_FIELDS) ^ (set(Egress._fields) - {"reply_to"})
)


def _consensus_step_packed_impl(state: GroupState, packed: jax.Array):
    rows = {name: packed[i] for i, name in enumerate(MBOX_FIELDS)}
    rows["success"] = rows["success"] != 0
    mbox = Mailbox(**rows)
    new_state, eg = consensus_step_impl(state, mbox)
    out = jnp.stack(
        [
            getattr(eg, name).astype(jnp.int32)
            for name in EGRESS_FIELDS
        ]
    )
    return new_state, out


consensus_step_packed = jax.jit(_consensus_step_packed_impl, donate_argnums=(0,))


def _consensus_step_packed_sub_impl(state: GroupState, packed: jax.Array):
    """Active-set step: gather ONLY the rows named by the gather index,
    run the fused step over the compact sub-batch, scatter results
    back. The index rides the packed buffer as its LAST row (an i32
    vector padded to a power of two with out-of-range ids), after the
    mailbox rows and, in the ``_scat`` form, the scatter rows: the host
    hands the step one buffer in one call. Step cost scales with
    *activity*, not capacity — the batch backend's analog of the
    reference's per-group process waking only on messages (reference:
    src/ra_server_proc.erl:457-530). Pad rows gather a clamped row's
    state but their writes are dropped on the scatter, so they cannot
    perturb any real group."""
    gidx = packed[-1]
    sub = jax.tree.map(lambda a: a[gidx], state)
    rows = {name: packed[i] for i, name in enumerate(MBOX_FIELDS)}
    rows["success"] = rows["success"] != 0
    mbox = Mailbox(**rows)
    sub_new, eg = consensus_step_impl(sub, mbox)
    out = jnp.stack(
        [getattr(eg, name).astype(jnp.int32) for name in EGRESS_FIELDS]
    )
    new_state = jax.tree.map(
        lambda full, s: full.at[gidx].set(s, mode="drop"), state, sub_new
    )
    return new_state, out


consensus_step_packed_sub = jax.jit(
    _consensus_step_packed_sub_impl, donate_argnums=(0,)
)


# Scatter-fused packed interface (docs/INTERNALS.md §15): the host's
# queued log-tail updates ride the SAME packed array as the mailbox —
# six extra rows after MBOX_FIELDS — and are applied on-device at the
# START of the step, before the quorum scan. One transfer and one
# dispatch per step instead of separate record_appended_runs /
# record_written calls (each with its own column uploads): on a CPU
# host the per-call dispatch overhead was a top cost of the unloaded
# commit wave. Pad entries carry an out-of-range gid (>= capacity);
# scatters drop them. a_* rows are contiguous same-term appended runs
# (one per group, gids unique); w_* rows are durable watermarks.
# NOT for sharded state: the mailbox shards column-wise, which would
# split the scatter rows across devices — sharded coordinators keep
# the separate record_* calls.
MBOX_SCAT_FIELDS = ["a_gid", "a_lo", "a_hi", "a_term", "w_gid", "w_idx"]


def _apply_packed_scatters(state: GroupState, packed: jax.Array) -> GroupState:
    # row-space form of record_appended_runs + record_written: every
    # temporary is (rows, k)-shaped, never (G, ...)-shaped, so the
    # per-step cost scales with the mailbox width, not capacity (the
    # full-state jnp.where variant cost O(G*k) per step at 10k groups).
    # Semantics match record_appended_runs exactly: tails advance by
    # max, ring slots in [lo, hi] take the run term, last_term re-reads
    # the updated ring at the (possibly unmoved) tail, staleness
    # clears; pad rows (gid >= G) drop on every scatter.
    base = len(MBOX_FIELDS)
    gids = packed[base]
    los = packed[base + 1]
    his = packed[base + 2]
    terms = packed[base + 3]
    k = state.term_suffix.shape[-1]
    los_c = jnp.maximum(los, his - (k - 1))
    slots = jnp.arange(k)[None, :]
    # largest index i <= hi with i % k == slot
    idx_at_slot = his[:, None] - ((his[:, None] - slots) % k)
    mask = idx_at_slot >= los_c[:, None]
    cur = state.term_suffix.at[gids].get(mode="fill", fill_value=0)
    rows = jnp.where(mask, terms[:, None], cur)
    ts = state.term_suffix.at[gids].set(rows, mode="drop")
    old_last = state.last_index.at[gids].get(mode="fill", fill_value=0)
    new_last = jnp.maximum(old_last, his)
    last_index = state.last_index.at[gids].set(new_last, mode="drop")
    ring_at_tail = jnp.take_along_axis(
        rows, (new_last % k)[:, None], axis=-1
    ).squeeze(-1)
    last_term = state.last_term.at[gids].set(ring_at_tail, mode="drop")
    unknown_lo = state.unknown_lo.at[gids].set(
        jnp.ones_like(gids), mode="drop"
    )
    unknown_hi = state.unknown_hi.at[gids].set(
        jnp.zeros_like(gids), mode="drop"
    )
    return state._replace(
        term_suffix=ts,
        last_index=last_index,
        last_term=last_term,
        unknown_lo=unknown_lo,
        unknown_hi=unknown_hi,
        written_index=state.written_index.at[packed[base + 4]].max(
            packed[base + 5], mode="drop"
        ),
    )


def _consensus_step_packed_scat_impl(state: GroupState, packed: jax.Array):
    state = _apply_packed_scatters(state, packed)
    return _consensus_step_packed_impl(state, packed)


consensus_step_packed_scat = jax.jit(
    _consensus_step_packed_scat_impl, donate_argnums=(0,)
)


def _consensus_step_packed_sub_scat_impl(state: GroupState, packed: jax.Array):
    # scatters apply to the FULL state before the active-set gather
    # (every appended/written group is in the active set by
    # construction, so the gathered sub-batch sees the new tails)
    state = _apply_packed_scatters(state, packed)
    return _consensus_step_packed_sub_impl(state, packed)


consensus_step_packed_sub_scat = jax.jit(
    _consensus_step_packed_sub_scat_impl, donate_argnums=(0,)
)


# ---------------------------------------------------------------------------
# host-side helpers for log-tail maintenance


@jax.jit
def record_appended(
    state: GroupState, group_ids: jax.Array, idxs: jax.Array, terms: jax.Array
) -> GroupState:
    """Record host-appended entries (scatter into the term ring buffer and
    advance the tails of the named groups). A batch may carry several
    entries for one group; (group, idx) pairs must be unique."""
    k = state.term_suffix.shape[-1]
    ts = state.term_suffix.at[group_ids, idxs % k].set(terms)
    # .max is order-independent under duplicate group indices...
    last_index = state.last_index.at[group_ids].max(idxs)
    # ...and last_term is then read back from the ring at the new tail
    # (a duplicate-index .set of terms would have implementation-defined
    # order for multi-entry batches spanning a term change)
    touched = jnp.zeros_like(state.last_index, dtype=jnp.bool_).at[group_ids].set(True)
    ring_at_tail = jnp.take_along_axis(ts, (last_index % k)[:, None], axis=-1).squeeze(-1)
    last_term = jnp.where(touched, ring_at_tail, state.last_term)
    # the host has reconciled these groups' rings exactly: clear staleness
    unknown_lo = jnp.where(touched, 1, state.unknown_lo)
    unknown_hi = jnp.where(touched, 0, state.unknown_hi)
    return state._replace(
        term_suffix=ts,
        last_index=last_index,
        last_term=last_term,
        unknown_lo=unknown_lo,
        unknown_hi=unknown_hi,
    )


@jax.jit
def record_appended_runs(
    state: GroupState,
    group_ids: jax.Array,
    los: jax.Array,
    his: jax.Array,
    terms: jax.Array,
) -> GroupState:
    """Record contiguous same-term appended runs — ONE row per group
    instead of one per entry (steady-state leaders append whole command
    batches in their current term). ``group_ids`` must be unique within
    the call (pad with an out-of-range gid). Ring slots covered by
    [lo, hi] are filled with ``term``; tails/staleness update as in
    ``record_appended``."""
    k = state.term_suffix.shape[-1]
    los_c = jnp.maximum(los, his - (k - 1))
    slots = jnp.arange(k)[None, :]
    # largest index i <= hi with i % k == slot
    idx_at_slot = his[:, None] - ((his[:, None] - slots) % k)
    mask = idx_at_slot >= los_c[:, None]
    cur = state.term_suffix.at[group_ids].get(mode="fill", fill_value=0)
    rows = jnp.where(mask, terms[:, None], cur)
    ts = state.term_suffix.at[group_ids].set(rows, mode="drop")
    last_index = state.last_index.at[group_ids].max(his, mode="drop")
    touched = (
        jnp.zeros_like(state.last_index, dtype=jnp.bool_)
        .at[group_ids].set(True, mode="drop")
    )
    ring_at_tail = jnp.take_along_axis(
        ts, (last_index % k)[:, None], axis=-1
    ).squeeze(-1)
    last_term = jnp.where(touched, ring_at_tail, state.last_term)
    unknown_lo = jnp.where(touched, 1, state.unknown_lo)
    unknown_hi = jnp.where(touched, 0, state.unknown_hi)
    return state._replace(
        term_suffix=ts,
        last_index=last_index,
        last_term=last_term,
        unknown_lo=unknown_lo,
        unknown_hi=unknown_hi,
    )


@jax.jit
def record_written(state: GroupState, group_ids: jax.Array, idxs: jax.Array) -> GroupState:
    """Advance durable watermarks after WAL fsync."""
    return state._replace(written_index=state.written_index.at[group_ids].max(idxs))


@jax.jit
def record_snapshot(
    state: GroupState, group_ids: jax.Array, idxs: jax.Array, terms: jax.Array
) -> GroupState:
    """Host installed snapshots for the named groups: move the snapshot
    boundary, advance tails/watermarks/commit, clear ring staleness."""
    touched = jnp.zeros_like(state.role, dtype=jnp.bool_).at[group_ids].set(True)
    snap_idx = state.snapshot_index.at[group_ids].set(idxs)
    snap_term = state.snapshot_term.at[group_ids].set(terms)
    last_index = state.last_index.at[group_ids].max(idxs)
    at_snap = last_index == snap_idx
    last_term = jnp.where(touched & at_snap, snap_term, state.last_term)
    written = state.written_index.at[group_ids].max(idxs)
    commit = state.commit_index.at[group_ids].max(idxs)
    unknown_lo = jnp.where(touched, 1, state.unknown_lo)
    unknown_hi = jnp.where(touched, 0, state.unknown_hi)
    return state._replace(
        snapshot_index=snap_idx,
        snapshot_term=snap_term,
        last_index=last_index,
        last_term=last_term,
        written_index=written,
        commit_index=commit,
        unknown_lo=unknown_lo,
        unknown_hi=unknown_hi,
    )


@jax.jit
def force_elections(state: GroupState, group_ids: jax.Array) -> GroupState:
    """Leadership-transfer fast path: the named groups become candidates
    IMMEDIATELY — term+1, vote for self, tallies cleared — skipping the
    pre-vote round. A TimeoutNow recipient must start a real election at
    once (Raft §3.10; reference: leadership transfer sends
    #timeout_now{} and the recipient calls an election directly,
    src/ra_server.erl handle_follower timeout_now). The host persists
    the bumped term/self-vote before any vote request leaves."""
    touched = (
        jnp.zeros_like(state.role, dtype=jnp.bool_)
        .at[group_ids].set(True, mode="drop")
    )
    return state._replace(
        role=jnp.where(touched, R_CANDIDATE, state.role),
        current_term=jnp.where(
            touched, state.current_term + 1, state.current_term
        ),
        voted_for=jnp.where(touched, state.self_slot, state.voted_for),
        leader_slot=jnp.where(touched, -1, state.leader_slot),
        votes=jnp.where(touched[:, None], False, state.votes),
        pre_votes=jnp.where(touched[:, None], False, state.pre_votes),
    )


@jax.jit
def set_roles(state: GroupState, group_ids: jax.Array, roles: jax.Array) -> GroupState:
    """Host-driven role transitions (election initiation and similar rare
    paths): scatter new roles and clear election tallies for the named
    groups."""
    role = state.role.at[group_ids].set(roles)
    touched = jnp.zeros_like(state.role, dtype=jnp.bool_).at[group_ids].set(True)
    votes = jnp.where(touched[:, None], False, state.votes)
    pre_votes = jnp.where(touched[:, None], False, state.pre_votes)
    # entering pre-vote opens a new round: bump the token so replies from
    # earlier rounds are ignored (the host mirrors this in
    # GroupHost.pre_vote_token)
    tok = state.pre_vote_token.at[group_ids].add(
        jnp.where(roles == R_PRE_VOTE, 1, 0)
    )
    return state._replace(
        role=role, votes=votes, pre_votes=pre_votes, pre_vote_token=tok
    )
