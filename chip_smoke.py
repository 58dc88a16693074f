#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ra_tpu still starts on the chip.

Drives the batch backend's served path once on one TPU chip, in one
process, through the entry points a user calls, at the width
``BASELINE.json`` names (10,240 groups x 3 replicas), and checks what
comes out against plain references. No rate is claimed here: the
numbers printed are counts and set-up/serving seconds of a smoke run.

Phases, one JSON object per line on stdout:

- ``device``: JAX must find a TPU, or the script exits non-zero before
  anything of the repo is imported. Versions, native entry points, the
  compile-cache directory and whether it was warm.
- ``kernels``: the five step variants and the ``record_*``/``set_roles``
  scatters at 10,240 x 3 on the device against ``ops/decisions.py`` on a
  seeded mailbox; the Pallas quorum kernel compiled by Mosaic (never
  interpreted) against ``agreed_commit_sort``; ``bench_decisions`` as a
  count and a time.
- ``cluster``: three started ``BatchCoordinator``s sharing the chip,
  each on its own WAL + segment writer, 10,240 leaders elected, then
  client traffic with replies on; every acknowledged write must be on
  all three replicas and equal a plain fold of the acknowledged
  commands.

``--chips 4`` runs only the mesh path instead: 4 x 10,240 groups with
the group axis sharded over four chips, against an unsharded one-chip
run of the same seeded commands.

Any failed check raises: the exit code is then non-zero and the last
line, ``{"ok": true, "device": {...}}``, is absent.

Usage: python chip_smoke.py [--chips 1|4] [--seed N] [--workdir DIR]
"""

import argparse
import json
import os
import shutil
import statistics
import sys
import time

CHECKOUT = os.path.dirname(os.path.abspath(__file__))
GROUPS = 10240
PEERS = 3
PER_GROUP = 3  # acknowledged commands per group in the cluster phase
SAMPLE = 32  # groups also driven through api.process_command / consistent_query
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def adder(cmd, state):
    """The README's machine: state depends on every payload, so a lost
    or doubled command shows in the fold comparison."""
    return state + cmd


def make_payloads(seed: int, groups: int, per_group: int):
    """``per_group`` rows of one distinct positive integer per group."""
    import numpy as np

    rng = np.random.default_rng(seed)
    return rng.integers(1, 1 << 40, size=(per_group, groups)).tolist()


class CompileStats:
    """Compilations and compile seconds as JAX itself reports them (a
    persistent-cache hit counts as a compilation whose seconds are the
    retrieval time)."""

    def __init__(self):
        import jax

        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_kw):
        if event == COMPILE_EVENT:
            self.n += 1
            self.seconds += secs

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def since(self, mark=(0, 0.0)) -> dict:
        return {"compilations": self.n - mark[0],
                "compile_seconds": round(self.seconds - mark[1], 3)}

    def mark(self):
        return (self.n, self.seconds)


# ---------------------------------------------------------------------------
# phase: device


def phase_device(chips: int):
    """JAX must find ``chips`` TPU devices. Imports nothing of the repo:
    in a directory that holds only this file the failure is the missing
    accelerator or, on the chip, the missing package — never a pass."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"chip_smoke: needs {chips} TPU device(s); JAX found "
            f"{len(devs)} x {devs[0].platform} — no result"
        )
    return devs


def report_device(devs, cache_dir: str, cache_warm: bool) -> None:
    from importlib import metadata

    import jax
    import jaxlib

    from ra_tpu import native

    eps = native.entry_points()
    if shutil.which("g++") and not all(eps.values()):
        raise RuntimeError(f"g++ is present but native entry points are "
                           f"missing: {eps}")
    emit(
        "device", platform=devs[0].platform, kind=devs[0].device_kind,
        count=len(devs), jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=metadata.version("libtpu"), native_entry_points=eps,
        compile_cache_dir=cache_dir, compile_cache_warm=cache_warm,
    )


# ---------------------------------------------------------------------------
# phase: kernels — the device programs against plain references


def seeded_state(rng, g: int, p: int, k: int = 32) -> dict:
    """Random but internally consistent group states, as numpy arrays
    keyed by ``GroupState`` field."""
    import numpy as np

    snap = np.where(rng.random(g) < 0.1, rng.integers(1, 6, g), 0)
    snap_term = np.where(snap > 0, rng.integers(1, 3, g), 0)
    tail = rng.integers(0, k - 1, g)  # the whole tail stays in the ring
    last = snap + tail
    suffix = np.zeros((g, k), np.int32)
    last_term = snap_term.copy()
    for i in range(g):
        t = max(int(snap_term[i]), 1)
        for idx in range(snap[i] + 1, last[i] + 1):
            t += int(rng.random() < 0.2)
            suffix[i, idx % k] = t
        if tail[i]:
            last_term[i] = t
    self_slot = rng.integers(0, p, g)
    voting = np.ones((g, p), bool)
    # at most one non-voter, never self: two voters cannot self-elect,
    # so the oracle needs no vote-tally model
    drop = (self_slot + 1 + rng.integers(0, p - 1, g)) % p
    voting[np.arange(g), drop] = rng.random(g) < 0.8
    match = np.minimum(rng.integers(0, 40, (g, p)), last[:, None])
    i32 = lambda a: np.asarray(a, np.int32)  # noqa: E731
    return {
        "current_term": i32(last_term + rng.integers(0, 3, g)),
        "voted_for": i32(rng.integers(-1, p, g)),
        "commit_index": i32(np.clip(rng.integers(0, 40, g), snap, last)),
        "last_applied": i32(snap),
        "last_index": i32(last),
        "last_term": i32(last_term),
        "written_index": i32(np.maximum(last - rng.integers(0, 3, g), snap)),
        "snapshot_index": i32(snap),
        "snapshot_term": i32(snap_term),
        "role": i32(rng.integers(0, 4, g)),
        "leader_slot": i32(rng.integers(-1, p, g)),
        "self_slot": i32(self_slot),
        "machine_version": i32(rng.integers(0, 3, g)),
        "match_index": i32(match),
        "next_index": i32(match + 1),
        "voting": voting,
        "active": np.ones((g, p), bool),
        "votes": np.zeros((g, p), bool),
        "pre_votes": np.zeros((g, p), bool),
        "term_suffix": suffix,
        "unknown_lo": np.ones(g, np.int32),
        "unknown_hi": np.zeros(g, np.int32),
        "pre_vote_token": np.zeros(g, np.int32),
    }


def seeded_mailbox(rng, st: dict) -> dict:
    """One message per group of the four kinds ``ops/decisions.py``
    decides (or none), as numpy arrays keyed by ``Mailbox`` field."""
    import numpy as np

    from ra_tpu.ops import consensus as C

    g, p = st["match_index"].shape
    k = st["term_suffix"].shape[1]
    last, term = st["last_index"], st["current_term"]
    kinds = np.array([C.MSG_NONE, C.MSG_AER, C.MSG_AER_REPLY,
                      C.MSG_VOTE_REQ, C.MSG_PREVOTE_REQ])
    prev_idx = rng.integers(0, last + 2)
    local = np.where(
        prev_idx == st["snapshot_index"], st["snapshot_term"],
        st["term_suffix"][np.arange(g), prev_idx % k],
    )
    i32 = lambda a: np.asarray(a, np.int32)  # noqa: E731
    reply_last = rng.integers(0, last + 1)
    return {
        "msg_type": i32(rng.choice(kinds, g)),
        "sender_slot": i32(rng.integers(0, p, g)),
        "term": i32(np.maximum(term + rng.integers(-1, 2, g), 0)),
        "prev_idx": i32(prev_idx),
        # mostly the matching term, so that accepts happen
        "prev_term": i32(np.where(rng.random(g) < 0.7, local,
                                  rng.integers(0, 4, g))),
        "num_entries": i32(rng.integers(0, 4, g)),
        "entries_last_term": i32(term),
        "leader_commit": i32(rng.integers(0, last + 4)),
        "success": rng.random(g) < 0.7,
        "reply_next_idx": i32(reply_last + 1),
        "reply_last_idx": i32(reply_last),
        "reply_last_term": i32(st["last_term"]),
        "cand_last_idx": i32(rng.integers(0, last + 3)),
        "cand_last_term": i32(rng.integers(0, st["last_term"] + 2)),
        "cand_machine_version": i32(rng.integers(0, 3, g)),
        "host_term_idx": np.full(g, -1, np.int32),
        "host_term_val": np.full(g, -1, np.int32),
        "token": np.zeros(g, np.int32),
    }


def _term_at(st: dict, i: int, idx: int):
    """Scalar model of the device's term lookup: (term, known)."""
    if idx <= 0:
        return 0, True
    snap, last = int(st["snapshot_index"][i]), int(st["last_index"][i])
    if idx == snap:
        return int(st["snapshot_term"][i]), True
    k = st["term_suffix"].shape[1]
    if max(last - k, snap) < idx <= last:
        return int(st["term_suffix"][i, idx % k]), True
    return -1, False


def oracle_check(st: dict, mb: dict, eg: dict) -> dict:
    """Hold one step's egress to ``ops/decisions.py``, group by group.
    Returns how many decisions of each kind were compared."""
    from ra_tpu.ops import consensus as C
    from ra_tpu.ops import decisions as dec

    g, p = st["match_index"].shape
    n = {"aer": 0, "aer_needs_host": 0, "vote": 0, "pre_vote": 0,
         "quorum": 0}

    def same(i, field, want):
        got = int(eg[field][i])
        if got != int(want):
            raise AssertionError(
                f"group {i} {field}: device {got}, oracle {int(want)} "
                f"(msg_type {int(mb['msg_type'][i])})"
            )

    for i in range(g):
        s = {f: st[f][i] for f in ("current_term", "voted_for",
                                   "commit_index", "last_index", "last_term",
                                   "written_index", "snapshot_index",
                                   "role", "self_slot", "machine_version")}
        s = {f: int(v) for f, v in s.items()}
        m = {f: int(mb[f][i]) for f in mb}
        kind = m["msg_type"]
        bumps = (kind not in (C.MSG_NONE, C.MSG_PREVOTE_REQ)
                 and m["term"] > s["current_term"])
        term1 = m["term"] if bumps else s["current_term"]
        role = C.R_FOLLOWER if bumps else s["role"]
        commit = s["commit_index"]
        match = [int(x) for x in st["match_index"][i]]
        if kind == C.MSG_AER:
            local, known = _term_at(st, i, m["prev_idx"])
            code = dec.aer_decision(
                term1, m["term"], m["prev_idx"], m["prev_term"],
                local if known else -1, s["snapshot_index"],
            )
            if not known and code == dec.AER_MISMATCH:
                # outside the device's window: resolved by the host
                same(i, "needs_host", 1)
                n["aer_needs_host"] += 1
                continue
            same(i, "aer_code", code)
            same(i, "success", code == dec.AER_OK)
            if code == dec.AER_OK:
                role = C.R_FOLLOWER
                new_last = m["prev_idx"] + m["num_entries"]
                commit = max(commit, min(m["leader_commit"], new_last))
            else:
                same(i, "next_index", dec.aer_failure_next_index(
                    commit, s["last_index"], m["prev_idx"],
                    s["snapshot_index"]))
            n["aer"] += 1
        elif kind == C.MSG_VOTE_REQ:
            grant, term = dec.vote_decision(
                s["current_term"], s["voted_for"], m["sender_slot"],
                m["term"], m["cand_last_idx"], m["cand_last_term"],
                s["last_index"], s["last_term"],
            )
            same(i, "success", grant)
            same(i, "term", term)
            n["vote"] += 1
        elif kind == C.MSG_PREVOTE_REQ:
            same(i, "success", dec.pre_vote_decision(
                term1, m["term"], m["cand_machine_version"],
                s["machine_version"], m["cand_last_idx"],
                m["cand_last_term"], s["last_index"], s["last_term"],
            ))
            n["pre_vote"] += 1
        elif (kind == C.MSG_AER_REPLY and role == C.R_LEADER
              and m["term"] == term1 and m["success"]):
            j = m["sender_slot"]
            match[j] = max(match[j], m["reply_last_idx"])
        if role == C.R_LEADER:
            # every leader's quorum scan runs on every step
            voters = [
                s["written_index"] if j == s["self_slot"] else match[j]
                for j in range(p) if st["voting"][i, j]
            ]
            t, known = _term_at(st, i, dec.agreed_commit(voters))
            commit = dec.new_commit_index(
                voters, commit, t if known else -1, term1)
            n["quorum"] += 1
        same(i, "commit_advanced_to", commit)
        same(i, "role", role)
    return n


def scatter_reference(st: dict, app_rows, written) -> dict:
    """Plain model of ``record_appended_runs`` + ``record_written``:
    ``app_rows`` are (gid, lo, hi, term), ``written`` is gid -> idx."""
    out = {f: a.copy() for f, a in st.items()}
    k = st["term_suffix"].shape[1]
    for gid, lo, hi, term in app_rows:
        for idx in range(max(lo, hi - (k - 1)), hi + 1):
            out["term_suffix"][gid, idx % k] = term
        out["last_index"][gid] = max(out["last_index"][gid], hi)
        out["last_term"][gid] = out["term_suffix"][
            gid, out["last_index"][gid] % k]
        out["unknown_lo"][gid], out["unknown_hi"][gid] = 1, 0
    for gid, idx in written.items():
        out["written_index"][gid] = max(out["written_index"][gid], idx)
    return out


def check_step_variants(groups: int, peers: int, seed: int) -> dict:
    """Run the five step variants and the host-side scatters on the
    default device and hold them to the scalar oracle and to the plain
    scatter model. ``consensus_step`` is checked against
    ``ops/decisions.py``; the packed variants must then equal it on the
    same input, the ``_scat`` variants on the input the scatter model
    makes, and the ``_sub`` variants on the gathered rows while leaving
    every other row as it was."""
    import jax.numpy as jnp
    import numpy as np

    from ra_tpu.ops import consensus as C
    from ra_tpu.runtime.coordinator import BatchCoordinator

    rng = np.random.default_rng(seed)
    g = groups
    st = seeded_state(rng, g, peers)
    mb = seeded_mailbox(rng, st)
    nrows = BatchCoordinator._NROWS
    base = len(C.MBOX_FIELDS)

    def dev_state(d):
        return C.pack_state(C.GroupState(
            **{f: jnp.asarray(d[f]) for f in C.GroupState._fields}))

    def host_state(s):
        return {f: np.asarray(getattr(s, f)) for f in C.GroupState._fields}

    def packed_mbox(cols, app_rows=(), written=None, index_row=False):
        """The coordinator's packed mailbox over group columns ``cols``
        (pads: an out-of-range gid, which the scatters drop); with
        ``index_row`` the active-set form, whose last row is ``cols``
        itself, the gather index."""
        pk = np.zeros((nrows + index_row, len(cols)), np.int32)
        for r, f in enumerate(C.MBOX_FIELDS):
            pk[r] = np.where(cols < g, mb[f][np.minimum(cols, g - 1)],
                             -1 if f.startswith("host_term") else 0)
        pk[base + 0] = pk[base + 4] = g
        for r, row in enumerate(app_rows):
            pk[base:base + 4, r] = row
        for r, (gid, idx) in enumerate((written or {}).items()):
            pk[base + 4:base + 6, r] = gid, idx
        if index_row:
            pk[-1] = cols
        return jnp.asarray(pk)

    def egress_rows(out):
        return {f: np.asarray(out[r]) for r, f in enumerate(C.EGRESS_FIELDS)}

    def equal(what, got: dict, want: dict, rows=None):
        for f in want:
            a, b = np.asarray(got[f]), np.asarray(want[f])
            if rows is not None:
                a, b = a[rows], b[rows]
            if not np.array_equal(a.astype(np.int64), b.astype(np.int64)):
                bad = np.flatnonzero((a != b).reshape(len(a), -1).any(axis=1))
                raise AssertionError(
                    f"{what}: {f} differs in {len(bad)} rows, first {bad[:5]}")

    # 1. the unpacked step against ops/decisions.py
    mbox = C.Mailbox(**{f: jnp.asarray(mb[f]) for f in C.Mailbox._fields})
    st1, eg1 = C.consensus_step(dev_state(st), mbox)
    eg1 = {f: np.asarray(a) for f, a in zip(C.Egress._fields, eg1)}
    counts = oracle_check(st, mb, eg1)
    st1 = host_state(st1)
    eg1 = {f: eg1[f] for f in C.EGRESS_FIELDS}

    # 2. packed == unpacked
    full = np.arange(g)
    st2, out = C.consensus_step_packed(dev_state(st), packed_mbox(full))
    equal("consensus_step_packed egress", egress_rows(out), eg1)
    equal("consensus_step_packed state", host_state(st2), st1)

    # 3. the scatters against the plain model: appended runs and durable
    #    watermarks for a seeded sub-set of an active set of groups
    act = np.sort(rng.choice(g, size=min(200, g // 2), replace=False))
    app_gids = act[: len(act) // 2]
    app_rows = [
        (int(i), int(st["last_index"][i]) + 1,
         int(st["last_index"][i]) + 1 + int(rng.integers(0, 3)),
         int(st["current_term"][i]))
        for i in app_gids
    ]
    written = {int(i): int(st["last_index"][i]) for i in act[len(act) // 3:]}
    st_s = scatter_reference(st, app_rows, written)
    pad = lambda n: 1 << max(0, int(n) - 1).bit_length()  # noqa: E731

    def cols(rows_, width):
        arr = np.zeros((pad(len(rows_)), width), np.int32)
        arr[:, 0] = g
        arr[: len(rows_)] = rows_
        return [jnp.asarray(arr[:, c]) for c in range(width)]

    got = C.record_appended_runs(dev_state(st), *cols(app_rows, 4))
    got = C.record_written(got, *cols(list(written.items()), 2))
    equal("record_appended_runs + record_written", host_state(got), st_s)
    roles = [(int(i), int(rng.integers(0, 3))) for i in app_gids]
    want = {f: a.copy() for f, a in st.items()}
    for gid, role in roles:
        want["role"][gid] = role
        want["votes"][gid] = want["pre_votes"][gid] = False
        want["pre_vote_token"][gid] += role == C.R_PRE_VOTE
    got = C.set_roles(dev_state(st), *cols(roles, 2))
    equal("set_roles", host_state(got), want)

    # 4. in-step scatters == plain model, then the packed step
    st3w, out3w = C.consensus_step_packed(dev_state(st_s), packed_mbox(full))
    st3, out3 = C.consensus_step_packed_scat(
        dev_state(st), packed_mbox(full, app_rows, written))
    eg3 = egress_rows(out3w)
    st3w = host_state(st3w)
    equal("consensus_step_packed_scat egress", egress_rows(out3), eg3)
    equal("consensus_step_packed_scat state", host_state(st3), st3w)

    # 5. the active-set variants: the gathered rows as the full-width
    #    step decides them, every other row untouched
    gidx = np.full(pad(len(act)), g, np.int32)
    gidx[: len(act)] = act
    rest = np.setdiff1d(full, act)
    real = np.arange(len(act))
    for name, fn, want_st, want_eg, scat in (
        ("consensus_step_packed_sub", C.consensus_step_packed_sub,
         st1, eg1, ()),
        # (the scattered groups are all in the active set, as the
        # coordinator builds it)
        ("consensus_step_packed_sub_scat", C.consensus_step_packed_sub_scat,
         st3w, eg3, (app_rows, written)),
    ):
        st5, out5 = fn(dev_state(st),
                       packed_mbox(gidx, *scat, index_row=True))
        got_eg = {f: a[real] for f, a in egress_rows(out5).items()}
        equal(f"{name} egress", got_eg, {f: a[act] for f, a in want_eg.items()})
        st5 = host_state(st5)
        equal(f"{name} active rows", st5, want_st, rows=act)
        equal(f"{name} other rows", st5, st, rows=rest)
    counts.update(groups=g, peers=peers, active=len(act),
                  appended_runs=len(app_rows), written=len(written))
    return counts


def check_pallas(groups: int, seed: int) -> dict:
    """The Pallas quorum kernel, compiled for the device it runs on
    (``interpret=False``: on a backend without Mosaic this raises),
    against ``agreed_commit_sort``."""
    import jax.numpy as jnp
    import numpy as np

    from ra_tpu.ops.consensus import agreed_commit_sort
    from ra_tpu.ops.pallas_quorum import agreed_commit_pallas

    rng = np.random.default_rng(seed)
    for p in (3, 5, 7):
        match = jnp.asarray(rng.integers(0, 1000, (groups, p)), jnp.int32)
        voting = rng.random((groups, p)) < 0.8
        voting[:, 0] = True
        nvoters = jnp.asarray(voting.sum(axis=1), jnp.int32)
        voting = jnp.asarray(voting)
        got = agreed_commit_pallas(match, voting, nvoters, interpret=False)
        want = agreed_commit_sort(match, voting, nvoters)
        if not np.array_equal(np.asarray(got), np.asarray(want)):
            raise AssertionError(f"pallas quorum kernel != sort at P={p}")
    return {"groups": groups, "peers": [3, 5, 7]}


def bench_decisions(groups: int, steps: int) -> dict:
    """``steps`` fused decision steps in one ``lax.scan`` on the device,
    every group taking one AER a step: a count and the seconds the
    second (compiled) run took."""
    import jax
    import jax.numpy as jnp

    from ra_tpu.ops.consensus import (
        MSG_AER,
        consensus_step_impl,
        empty_mailbox,
        make_group_state,
        unpack_state,
    )

    G, T = groups, steps
    state = unpack_state(make_group_state(G, PEERS))
    mbox = empty_mailbox(G)._replace(
        msg_type=jnp.full((G,), MSG_AER, jnp.int32),
        term=jnp.ones((G,), jnp.int32),
        num_entries=jnp.ones((G,), jnp.int32),
        entries_last_term=jnp.ones((G,), jnp.int32),
    )

    def many_steps(state, mbox):
        def body(st, _):
            mb = mbox._replace(prev_idx=st.last_index, prev_term=st.last_term)
            st2, eg = consensus_step_impl(st, mb)
            return st2, eg.success.sum()

        return jax.lax.scan(body, state, None, length=T)

    run = jax.jit(many_steps, donate_argnums=(0,))
    st, sums = run(jax.tree.map(jnp.copy, state), mbox)
    jax.block_until_ready(sums)
    t0 = time.perf_counter()
    st, sums = run(jax.tree.map(jnp.copy, state), mbox)
    jax.block_until_ready(sums)
    dt = time.perf_counter() - t0
    return {"decisions": G * T, "seconds": round(dt, 6)}


def phase_kernels(groups: int, seed: int, stats: CompileStats) -> None:
    mark, t0 = stats.mark(), time.perf_counter()
    variants = check_step_variants(groups, PEERS, seed)
    pallas = check_pallas(groups, seed)
    dec = bench_decisions(groups, 200)
    emit(
        "kernels", step_variants=variants, pallas_compiled=pallas,
        bench_decisions=dec,
        seconds=round(time.perf_counter() - t0, 3), **stats.since(mark),
    )


# ---------------------------------------------------------------------------
# phase: cluster — the served path


def fs_type(path: str) -> str:
    """Filesystem type of the mount that holds ``path``."""
    real = os.path.realpath(path)
    best, kind = "", "unknown"
    with open("/proc/mounts") as f:
        for line in f:
            _dev, mnt, typ = line.split()[:3]
            if (real == mnt or real.startswith(mnt.rstrip("/") + "/")) \
                    and len(mnt) > len(best):
                best, kind = mnt, typ
    return kind


def fsync_median_ms(directory: str, n: int = 32) -> float:
    """Median of ``n`` 4 KiB append + fdatasync round trips there."""
    path = os.path.join(directory, "fsync_probe")
    took = []
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND)
    try:
        for _ in range(n):
            os.write(fd, b"\0" * 4096)
            t0 = time.perf_counter()
            os.fdatasync(fd)
            took.append((time.perf_counter() - t0) * 1e3)
    finally:
        os.close(fd)
        os.unlink(path)
    return statistics.median(took)


def wal_storage(coords, base: str):
    """Put every coordinator of ``coords`` on real storage under
    ``base/<coordinator name>``: one shared WAL + segment writer + table
    registry per coordinator, so every group's appends ride the same
    file and the same batched fsync (one gen_batch_server WAL per
    system, docs/internals/INTERNALS.md:16-19), written events handled
    on the WAL writer thread itself (docs/INTERNALS.md §15). Returns
    ``(storage, mk_log)``: ``storage`` rows are ``(tables, wal,
    segment_writer, dir)`` in ``coords`` order (hand them to
    ``close_storage``), and ``mk_log(i, uid)`` opens group ``uid``'s
    ``Log`` on coordinator ``i``'s WAL."""
    from ra_tpu.log.log import Log
    from ra_tpu.log.segment_writer import SegmentWriter
    from ra_tpu.log.tables import TableRegistry
    from ra_tpu.log.wal import Wal

    storage = []
    for c in coords:
        d = os.path.join(base, c.name)
        tables = TableRegistry()
        sw = SegmentWriter(os.path.join(d, "data"), tables, c.wal_notify)
        w = Wal(os.path.join(d, "wal"), tables, c.wal_notify,
                segment_writer=sw)
        # bulk written-event channel: one lock round per fsync batch
        w.notify_many = c.wal_notify_many
        storage.append((tables, w, sw, d))

    def mk_log(i, uid):
        tables, w, _sw, d = storage[i]
        return Log(uid, os.path.join(d, "data", uid), tables, w)

    return storage, mk_log


def close_storage(storage) -> None:
    for _tables, w, sw, _d in storage:
        w.close()
        sw.close()


def _wait(done, budget_s: float, what: str, poll_s: float = 0.02) -> float:
    """Poll ``done`` (sparingly: the poller shares the interpreter lock
    with the coordinators' threads) until it holds; seconds waited."""
    t0 = time.monotonic()
    while not done():
        if time.monotonic() - t0 > budget_s:
            raise TimeoutError(f"{what} not reached in {budget_s:.0f} s")
        time.sleep(poll_s)
    return time.monotonic() - t0


def phase_cluster(groups: int, per_group: int, seed: int, workdir: str,
                  sample: int, stats: CompileStats) -> dict:
    """Three started coordinators on one device, WAL-backed, driven the
    way clients drive them; every acknowledged command is held to the
    plain fold on all three replicas. Returns the phase record."""
    import numpy as np

    from ra_tpu import api, leaderboard, obs
    from ra_tpu.machine import SimpleMachine
    from ra_tpu.ops import consensus as C
    from ra_tpu.protocol import USR, Command, ElectionTimeout
    from ra_tpu.runtime.coordinator import BatchCoordinator

    base = os.path.join(workdir, "chip_smoke")
    shutil.rmtree(base, ignore_errors=True)
    os.makedirs(base)
    disk = {"wal_dir": base, "fs_type": fs_type(base),
            "fsync_median_ms": round(fsync_median_ms(base), 3)}

    t_phase = time.perf_counter()
    mark = stats.mark()
    names = [f"g{g}" for g in range(groups)]
    coords = [BatchCoordinator(f"smoke{i}", capacity=groups, num_peers=PEERS)
              for i in range(3)]
    storage, mk_log = wal_storage(coords, base)
    fold = [0] * groups  # the plain reference: one adder per group
    try:
        members = [[(n, c.name) for c in coords] for n in names]
        for i, c in enumerate(coords):
            c.add_groups([
                (n, f"smoke_{n}", members[g], SimpleMachine(adder, 0),
                 mk_log(i, n))
                for g, n in enumerate(names)
            ])
        # every width the phase dispatches compiles BEFORE the loops
        # start: a compile on a started coordinator stalls it under the
        # command watchdog and the live election timers
        warmed = sum(c.warm_steps() for c in coords)
        set_up = stats.since(mark)
        set_up["seconds"] = round(time.perf_counter() - t_phase, 3)
        for c in coords:
            c.start()

        # -- elect: group g's leader is coordinator g % 3 ---------------
        t_elect = time.perf_counter()
        lead = [coords[g % 3] for g in range(groups)]

        def to_leaders(make_msg):
            """One bulk delivery (one ring slot) per leader coordinator."""
            for i, c in enumerate(coords):
                c.deliver_many([((names[g], c.name), make_msg(g), None)
                                for g in range(i, groups, 3)])

        to_leaders(lambda g: ElectionTimeout())
        _wait(lambda: all(lead[g].by_name[names[g]].role == C.R_LEADER
                          for g in range(groups)),
              300, f"{groups} leaders")
        # the election noops commit and apply everywhere before traffic
        _wait(lambda: all(c._applied_np[:groups].min() >= 1 for c in coords),
              120, "election noops applied")
        elect_s = time.perf_counter() - t_elect
        terms0 = sum(c.by_name[n].term for c in coords for n in names)
        obs.flight_recorder().clear()
        mark_serve = stats.mark()

        # -- serve: acknowledged writes, a few per group ----------------
        t_serve = time.perf_counter()
        acked = 0
        for wave in make_payloads(seed, groups, per_group):
            futs = [api.Future() for _ in range(groups)]
            to_leaders(lambda g: Command(
                kind=USR, data=wave[g], reply_mode="await_consensus",
                from_ref=futs[g], ts=time.monotonic_ns()))
            for g, fut in enumerate(futs):
                reply = fut.result(timeout=60)
                if reply[0] != "ok":
                    raise RuntimeError(
                        f"group {names[g]}: command not acknowledged: "
                        f"{reply!r}")
                fold[g] += wave[g]
                acked += 1
        # -- the synchronous client calls on a sample of groups ---------
        picks = list(range(0, groups, max(1, groups // sample)))[:sample]
        extra = make_payloads(seed + 1, groups, 1)[0]
        for g in picks:
            sid = (names[g], coords[(g + 1) % 3].name)  # a follower: redirected
            _reply, leader = api.process_command(sid, extra[g], timeout=30)
            fold[g] += extra[g]
            acked += 1
            if leader != (names[g], lead[g].name):
                raise RuntimeError(f"{names[g]}: answered by {leader}")
        for g in picks:
            out = api.consistent_query(
                (names[g], lead[g].name), lambda s: s, timeout=30)
            if out[0] != "ok" or out[1] != fold[g]:
                raise AssertionError(
                    f"{names[g]}: consistent_query {out!r} != fold {fold[g]}")
        serve_s = time.perf_counter() - t_serve

        # -- every acknowledged write on all three replicas -------------
        # wait on apply progress (the election noop plus the group's
        # acknowledged commands), then hold the states to the fold once
        target = np.full(groups, 1 + per_group)
        target[picks] += 1
        settle_s = _wait(
            lambda: all((c._applied_np[:groups] >= target).all()
                        for c in coords),
            60, "every replica applying what was acknowledged")
        bad = [(c, g) for c in coords for g in range(groups)
               if c.by_name[names[g]].machine_state != fold[g]]
        if bad:
            c, g = bad[0]
            raise AssertionError(
                f"{len(bad)} replica states differ from the fold of the "
                f"acknowledged commands, e.g. {names[g]} on {c.name}: "
                f"{c.by_name[names[g]].machine_state} != {fold[g]}"
            )
        serving = stats.since(mark_serve)

        record = {
            "coordinators": len(coords), "groups": groups, "replicas": PEERS,
            "wal_backed": True, "started_loops": all(c._started for c in coords),
            "acknowledged": acked, "applied_on_replicas": PEERS,
            "equal_to_fold": True, "reads_checked": len(picks),
            "steps": sum(c.steps for c in coords),
            "sub_steps": sum(c.sub_steps for c in coords),
            "programs_warmed": warmed,
            "lane_wedges": sum(c.counters.get("lane_wedges") for c in coords),
            "elections_after_first": sum(
                e["kind"] == "election"
                for e in obs.flight_recorder().events()),
            "term_bumps_after_first": sum(
                c.by_name[n].term for c in coords for n in names) - terms0,
            "detector_errors": sum(c.detector_errors for c in coords),
            "wal_fsyncs": sum(w.counter.get("fsyncs") for _t, w, _s, _d in storage),
            "set_up": set_up,
            "election_seconds": round(elect_s, 3),
            "serving_seconds": round(serve_s, 3),
            "settle_seconds": round(settle_s, 3),
            "compilations_while_serving": serving["compilations"],
            **disk,
        }
    finally:
        for c in coords:
            c.stop()
        close_storage(storage)
        leaderboard.clear()
    alive = [t.name for c in coords
             for t in (c._step_thread, c._sender_thread, c._detector)
             if t is not None and t.is_alive()]
    if alive:
        raise RuntimeError(f"coordinator threads outlived stop(): {alive}")
    for key in ("lane_wedges", "elections_after_first",
                "term_bumps_after_first", "detector_errors"):
        if record[key]:
            raise AssertionError(f"cluster phase: {key} = {record[key]}, "
                                 f"must be 0")
    if not record["wal_fsyncs"] > 0:
        raise AssertionError("cluster phase: the WALs never fsynced")
    return record


# ---------------------------------------------------------------------------
# phase: mesh (--chips 4)


def phase_mesh(devs, seed: int, stats: CompileStats) -> None:
    """The group axis of 4 x 10,240 groups sharded over four chips,
    against the unsharded one-chip run of the same seeded commands."""
    import contextlib

    import numpy as np

    from __graft_entry__ import sharded_cluster_phases

    groups = 4 * GROUPS
    runs = {}
    for tag, devices in (("mesh4_", devs[:4]), ("mesh1_", devs[:1])):
        mark, t0 = stats.mark(), time.perf_counter()
        # (its progress lines are not phase records: off stdout)
        with contextlib.redirect_stdout(sys.stderr):
            out = sharded_cluster_phases(devices, groups, seed=seed,
                                         waves=PER_GROUP, tag=tag)
        out["seconds"] = round(time.perf_counter() - t0, 3)
        out.update(stats.since(mark))
        runs[tag] = out
    sharded, single = runs["mesh4_"], runs["mesh1_"]
    commits = list(sharded["commit_index"].values()) \
        + list(single["commit_index"].values())
    if not all(np.array_equal(c, commits[0]) for c in commits):
        raise AssertionError("commit_index differs between replicas or "
                             "between the sharded and the unsharded run")
    for out in runs.values():
        for name, states in out["machine_state"].items():
            if states != out["fold"]:
                raise AssertionError(f"{name}: machine state != fold")
    if sharded["fold"] != single["fold"]:
        raise AssertionError("the two runs folded different commands")
    emit(
        "mesh", groups=groups, devices=len(devs[:4]),
        state_on_devices=sharded["devices"],
        sharded={k: sharded[k] for k in
                 ("steps", "shard_moves", "seconds", "compilations",
                  "compile_seconds")},
        unsharded={k: single[k] for k in
                   ("steps", "seconds", "compilations", "compile_seconds")},
        replicas_agree=True, equal_to_fold=True,
    )


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the mesh path, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workdir", default=os.path.join(CHECKOUT, "ra_data"),
                    help="WAL data goes under WORKDIR/chip_smoke, which is "
                         "emptied first (default: ra_data in the checkout)")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    devs = phase_device(args.chips)

    from ra_tpu.utils.lib import enable_compile_cache

    cache_dir = enable_compile_cache()
    warm = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))
    stats = CompileStats()
    report_device(devs, cache_dir, warm)
    if args.chips == 4:
        phase_mesh(devs, args.seed, stats)
    else:
        phase_kernels(GROUPS, args.seed, stats)
        emit("cluster", **phase_cluster(
            GROUPS, PER_GROUP, args.seed, args.workdir, SAMPLE, stats))
    emit("total", seconds=round(time.perf_counter() - t0, 3),
         cache_hits=stats.cache_hits, cache_misses=stats.cache_misses,
         **stats.since())
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
