"""Byte-parity fuzz tests for the native hot-loop runtime (docs/
INTERNALS.md §18): rt_classify / rt_pack_mbox against
their Python reference paths, plus the fallback seams — .so missing,
armed failpoints, and the loader's negative build cache.

Extends the tests/test_pipeline.py WAL parity pattern: every native
entry point must be byte-identical to the Python path it replaces, in
both directions (native output checked against a from-scratch Python
reference, and the coordinator's native/off variants checked against
each other on identical seeded corpora).
"""

import os
import random
import shutil
import subprocess
from collections import Counter

import numpy as np
import pytest

from ra_tpu import faults, native
from ra_tpu.machine import SimpleMachine
from ra_tpu.ops import consensus as C
from ra_tpu.protocol import (
    RC_BATCH,
    RC_CMD,
    RC_CMD_LOW,
    RC_MSG,
    USR,
    AppendEntriesReply,
    AppendEntriesRpc,
    Command,
    Entry,
)
from ra_tpu.runtime.coordinator import BatchCoordinator, parse_native

needs_rt = pytest.mark.skipif(
    not native.entry_points()["classify"],
    reason="rt_native.so unavailable (no compiler)",
)


# -- build guard (satellite: scripts/build_native.sh contract) -------------


def test_native_builds_when_compiler_present():
    """CI guard: with a compiler on PATH, EVERY native entry point must
    build and load — a broken build must fail loudly here instead of
    every test silently taking the Python fallback (scripts/
    build_native.sh runs the same check first in CI)."""
    if shutil.which("g++") is None:
        pytest.skip("no g++ on PATH")
    eps = native.entry_points()
    assert eps == {"wal": True, "pack": True, "classify": True}
    # available() stays the WAL-only historical contract
    assert native.available() == eps["wal"]


def test_parse_native_specs():
    allp = frozenset(("pack", "classify"))
    assert parse_native("auto") == allp
    assert parse_native(True) == allp
    assert parse_native("on") == allp
    assert parse_native("all") == allp
    assert parse_native("off") == frozenset()
    assert parse_native("none") == frozenset()
    assert parse_native(False) == frozenset()
    assert parse_native("") == frozenset()
    assert parse_native("pack,classify") == allp
    assert parse_native(" classify ") == frozenset(("classify",))
    with pytest.raises(ValueError):
        parse_native("pack,warp")
    with pytest.raises(ValueError):  # the per-message sealer went (PR 33)
        parse_native("pack,egress")


# -- rt_classify vs Python reference ---------------------------------------


@needs_rt
def test_classify_fuzz_vs_python_reference():
    """The native partition must equal the obvious Python one — per
    class, the item indexes in arrival order — across random corpora."""
    rng = random.Random(0xC1A55)
    for trial in range(50):
        n = rng.randint(1, 2000)
        codes = bytes(rng.randrange(native.N_CLASSES) for _ in range(n))
        out = native.classify(codes, n)
        assert out is not None
        idx, counts = out
        ref = [
            [i for i, c in enumerate(codes) if c == k]
            for k in range(native.N_CLASSES)
        ]
        assert counts.tolist() == [len(r) for r in ref]
        o = 0
        for k in range(native.N_CLASSES):
            assert idx[o:o + counts[k]].tolist() == ref[k]
            o += counts[k]
        assert o == n


@needs_rt
def test_classify_bytearray_and_oversized_sidecar():
    """The coordinator hands a reusable bytearray scratch, possibly
    longer than the drained burst — only the first n codes count."""
    codes = bytearray([1, 0, 2, 3, 3, 1]) + bytearray(64)
    out = native.classify(codes, 6)
    assert out is not None
    idx, counts = out
    assert counts.tolist() == [1, 2, 1, 2]
    assert idx.tolist() == [1, 0, 5, 2, 3, 4]


@needs_rt
def test_classify_rejects_out_of_range_code():
    """A corrupt sidecar code must fail the whole call (caller falls
    back to the Python tag dispatch), not silently misroute."""
    assert native.classify(bytes([0, 1, 200]), 3) is None
    assert native.classify(bytes([native.N_CLASSES]), 1) is None
    assert native.classify(b"", 0) is None  # n == 0: nothing to do


# -- coordinator drain-classify parity -------------------------------------


def _mk_coord(name, native_spec):
    return BatchCoordinator(
        name, capacity=8, num_peers=1, idle_sleep_s=0, native=native_spec
    )


def _add_groups(c, tag, names=("g0", "g1", "g2")):
    for gname in names:
        c.add_group(
            gname, f"{tag}-{gname}", [(gname, c.name)],
            SimpleMachine(lambda cm, s: s + cm, 0),
        )


def _apply_ops(c, ops):
    ext = ("x", "ext")
    for op in ops:
        kind = op[0]
        if kind == "cmd":
            _, gname, data, prio = op
            c.deliver(
                (gname, c.name),
                Command(kind=USR, data=data, priority=prio), None,
            )
        elif kind == "msg":
            _, gname, payload = op
            c.deliver((gname, c.name), payload, ext)
        elif kind == "cmds":  # one command fanned to many groups
            _, gnames, data, prio = op
            cmd = Command(kind=USR, data=data, priority=prio)
            c.deliver_many([((gname, c.name), cmd, None) for gname in gnames])
        elif kind == "many":
            _, trips = op
            c.deliver_many(
                [((gname, c.name), msg, ext) for gname, msg in trips]
            )
        else:  # ingest: pre-normalized peer batch
            _, trips = op
            c.ingest_batch([(gname, ext, msg) for gname, msg in trips])


def _cmd_key(cmd):
    return (cmd.kind, cmd.data, cmd.priority)


def _summarize(pre):
    """Order-insensitive view of a _drain_classify result: the native
    path keeps order WITHIN each RC class but may interleave classes
    differently than the single Python loop."""
    _, n_items, cmd_q, routes, lows = pre
    cq = {
        name: Counter(_cmd_key(cm) for cm in lst)
        for name, lst in (cmd_q or {}).items()
    }
    rt = Counter((name, frm, msg) for name, frm, msg in (routes or []))
    lw = Counter((name, _cmd_key(cm)) for name, cm in (lows or []))
    return n_items, cq, rt, lw


@needs_rt
def test_drain_classify_parity_mixed_corpus():
    """Two coordinators — native classify on vs off — fed an identical
    randomized corpus through every real publish path must drain to the
    same routing decision (multiset equality across classes; exact
    order within each class is covered by the single-class test)."""
    rng = random.Random(7)
    known = ["g0", "g1", "g2"]
    pool = known + ["zz"]  # unknown names drop at drain, both paths
    ops = []
    for i in range(400):
        r = rng.random()
        prio = "low" if rng.random() < 0.3 else "normal"
        if r < 0.35:
            ops.append(("cmd", rng.choice(known), i, prio))
        elif r < 0.55:
            ops.append(("msg", rng.choice(pool), ("hb", i)))
        elif r < 0.7:
            k = rng.randint(1, len(pool))
            ops.append(("cmds", tuple(rng.sample(pool, k)), i, prio))
        else:
            trips = []
            for _ in range(rng.randint(1, 5)):
                gname = rng.choice(pool)
                if rng.random() < 0.5:
                    trips.append(
                        (gname,
                         Command(kind=USR, data=("b", i), priority=prio))
                    )
                else:
                    trips.append((gname, ("evt", i)))
            ops.append(("many" if r < 0.85 else "ingest", trips))

    c_nat = _mk_coord("ncl0", "classify")
    c_off = _mk_coord("ncl1", "off")
    try:
        _add_groups(c_nat, "ncl0")
        _add_groups(c_off, "ncl1")
        assert c_nat._nat_classify and not c_off._nat_classify
        _apply_ops(c_nat, ops)
        _apply_ops(c_off, ops)
        s_nat = _summarize(c_nat._drain_classify())
        s_off = _summarize(c_off._drain_classify())
        assert s_nat == s_off
        assert c_nat.counters.get("native_classify_batches") == 1
        assert c_nat.counters.get("native_classify_items") == s_nat[0]
        assert c_nat.counters.get("native_fallbacks") == 0
        assert c_off.counters.get("native_classify_batches") == 0
        # drained clean: the scratch and sidecar reset for the next pass
        assert not c_nat._drain_buf and not c_nat._drain_codes
    finally:
        c_nat.stop()
        c_off.stop()


@needs_rt
def test_drain_classify_exact_order_single_class():
    """Within one RC class the native path must preserve exact arrival
    order — same per-group command lists, element for element."""
    c_nat = _mk_coord("nso0", "classify")
    c_off = _mk_coord("nso1", "off")
    try:
        _add_groups(c_nat, "nso0")
        _add_groups(c_off, "nso1")
        rng = random.Random(11)
        ops = [("cmd", rng.choice(["g0", "g1", "g2"]), i, "normal")
               for i in range(200)]
        _apply_ops(c_nat, ops)
        _apply_ops(c_off, ops)
        (_, n_n, cq_n, _, _) = c_nat._drain_classify()
        (_, n_o, cq_o, _, _) = c_off._drain_classify()
        assert n_n == n_o == 200
        assert {k: [c.data for c in v] for k, v in cq_n.items()} == {
            k: [c.data for c in v] for k, v in cq_o.items()
        }
    finally:
        c_nat.stop()
        c_off.stop()


@needs_rt
def test_drain_classify_armed_failpoint_falls_back():
    """While ANY failpoint is armed the native classify routes around
    itself — the nemesis plane must always exercise the Python seam —
    and the result is still correct."""
    c = _mk_coord("naf0", "classify")
    try:
        _add_groups(c, "naf0")
        faults.arm("wal.write", ("raise", "eio"), ("always",))
        _apply_ops(c, [("cmd", "g0", i, "normal") for i in range(10)])
        pre = c._drain_classify()
        assert [cm.data for cm in pre[2]["g0"]] == list(range(10))
        assert c.counters.get("native_classify_batches") == 0
        assert c.counters.get("native_fallbacks") == 0  # routed around
    finally:
        faults.disarm_all()
        c.stop()


# -- coordinator mailbox pack parity ---------------------------------------


def _pack_corpus(rng, cap):
    """Random AER + AER-reply corpora over distinct mailbox columns."""
    k_aer = rng.randint(0, cap // 2)
    k_rep = rng.randint(0, cap - k_aer)
    cols = rng.sample(range(cap), k_aer + k_rep)
    aer_i, rep_i = cols[:k_aer], cols[k_aer:]
    aer_m = []
    for _ in range(k_aer):
        ents = tuple(
            Entry(j, rng.randint(1, 9), Command(USR, j))
            for j in range(rng.randint(0, 3))
        )
        aer_m.append(
            AppendEntriesRpc(
                term=rng.randint(1, 100), leader_id=("a", "n"),
                prev_log_index=rng.randint(0, 1 << 20),
                prev_log_term=rng.randint(0, 99),
                leader_commit=rng.randint(0, 1 << 20), entries=ents,
            )
        )
    rep_m = [
        AppendEntriesReply(
            term=rng.randint(1, 100), success=rng.random() < 0.5,
            next_index=rng.randint(0, 1 << 20),
            last_index=rng.randint(0, 1 << 20),
            last_term=rng.randint(0, 99),
        )
        for _ in range(k_rep)
    ]
    aer_s = [rng.randrange(1) for _ in range(k_aer)]
    rep_s = [rng.randrange(1) for _ in range(k_rep)]
    return aer_i, aer_m, aer_s, rep_i, rep_m, rep_s


@needs_rt
def test_pack_hot_parity_fuzz():
    """_pack_hot's native scatter must produce a byte-identical mailbox
    to the columnwise numpy stores across random AER/reply corpora."""
    cap = 8
    c_nat = _mk_coord("npk0", "pack")
    c_off = _mk_coord("npk1", "off")
    try:
        assert c_nat._nat_pack and not c_off._nat_pack
        rng = random.Random(0xBEEF)
        nrows = BatchCoordinator._NROWS
        for trial in range(30):
            corpus = _pack_corpus(rng, cap)
            p_nat = np.zeros((nrows, cap), np.int32)
            p_off = np.zeros((nrows, cap), np.int32)
            c_nat._pack_hot(p_nat, *corpus)
            c_off._pack_hot(p_off, *corpus)
            assert np.array_equal(p_nat, p_off), f"trial {trial}"
        assert c_nat.counters.get("native_pack_batches") > 0
        assert c_nat.counters.get("native_fallbacks") == 0
        assert c_off.counters.get("native_pack_batches") == 0
    finally:
        c_nat.stop()
        c_off.stop()


@needs_rt
def test_pack_hot_noncontiguous_buffer_falls_back():
    """A non-C-contiguous mailbox (never produced in-tree, but the ABI
    guard must hold) takes the Python stores and counts a fallback."""
    cap = 8
    c = _mk_coord("npf0", "pack")
    try:
        rng = random.Random(3)
        corpus = _pack_corpus(rng, cap)
        nrows = BatchCoordinator._NROWS
        p_f = np.asfortranarray(np.zeros((nrows, cap), np.int32))
        p_ref = np.zeros((nrows, cap), np.int32)
        c._pack_hot(p_f, *corpus)
        c_off = _mk_coord("npf1", "off")
        try:
            c_off._pack_hot(p_ref, *corpus)
        finally:
            c_off.stop()
        assert np.array_equal(np.ascontiguousarray(p_f), p_ref)
        if corpus[0] or corpus[3]:  # corpus non-empty -> native refused
            assert c.counters.get("native_fallbacks") == 1
            assert c.counters.get("native_pack_batches") == 0
    finally:
        c.stop()


@needs_rt
def test_pack_hot_armed_failpoint_falls_back():
    cap = 8
    c = _mk_coord("npa0", "pack")
    try:
        corpus = _pack_corpus(random.Random(5), cap)
        packed = np.zeros((BatchCoordinator._NROWS, cap), np.int32)
        faults.arm("tcp.send", ("raise", "eio"), ("always",))
        c._pack_hot(packed, *corpus)
        assert c.counters.get("native_pack_batches") == 0
        assert c.counters.get("native_fallbacks") == 0  # routed around
    finally:
        faults.disarm_all()
        c.stop()


# -- .so-missing fallbacks -------------------------------------------------


def test_rt_lib_missing_helpers_and_coordinator(monkeypatch):
    """With rt_native absent every helper reports unavailable, the
    coordinator resolves all native switches off, and the drain still
    routes through the Python loop."""
    monkeypatch.setattr(native, "_rt_lib", None)
    monkeypatch.setattr(native, "_rt_tried", True)
    assert native.classify(bytes([0, 1]), 2) is None
    assert native.pack_mbox(
        np.zeros((2, 2), np.int32), [0], [1, 2],
        np.asarray([0, 1], np.int32),
    ) is False
    eps = native.entry_points()
    assert not eps["pack"] and not eps["classify"]
    c = _mk_coord("nmh0", "auto")
    try:
        assert not (c._nat_pack or c._nat_classify)
        _add_groups(c, "nmh0")
        _apply_ops(c, [("cmd", "g0", i, "normal") for i in range(5)])
        pre = c._drain_classify()
        assert [cm.data for cm in pre[2]["g0"]] == list(range(5))
        assert c.counters.get("native_classify_batches") == 0
    finally:
        c.stop()


def test_rt_lib_vanishing_midflight_counts_fallback(monkeypatch):
    """A coordinator that resolved classify ON but loses the lib at
    call time (classify returns None) must take the Python loop and
    count ONE fallback — not misroute or raise."""
    if not native.entry_points()["classify"]:
        pytest.skip("rt_native.so unavailable")
    c = _mk_coord("nvf0", "classify")
    try:
        _add_groups(c, "nvf0")
        monkeypatch.setattr(native, "classify", lambda codes, n: None)
        _apply_ops(c, [("cmd", "g0", i, "normal") for i in range(5)])
        pre = c._drain_classify()
        assert [cm.data for cm in pre[2]["g0"]] == list(range(5))
        assert c.counters.get("native_fallbacks") == 1
        assert c.counters.get("native_classify_batches") == 0
    finally:
        c.stop()


# -- loader negative build cache (satellite 3) -----------------------------


def test_build_negative_cache_and_single_warning(tmp_path, monkeypatch,
                                                 capsys):
    """A failed build is cached per source digest: no rebuild storm on
    every import, exactly one stderr warning carrying the compiler
    error, and a CHANGED source retries."""
    src = tmp_path / "broken.cpp"
    so = tmp_path / "broken.so"
    src.write_text("int main( {")
    calls = []

    def fake_run(*a, **kw):
        calls.append(a)
        raise subprocess.CalledProcessError(
            1, a[0], stderr=b"broken.cpp:1:1: error: expected ')'"
        )

    monkeypatch.setattr(native.subprocess, "run", fake_run)
    assert native._build(str(src), str(so)) is None
    assert native._build(str(src), str(so)) is None
    assert len(calls) == 1  # second call served by the negative cache
    err = capsys.readouterr().err
    assert err.count("build of broken.cpp failed") == 1
    assert "expected ')'" in err
    # a newer mtime on the same bytes is the same source ...
    st = os.stat(src)
    os.utime(src, (st.st_atime, st.st_mtime + 10))
    assert native._build(str(src), str(so)) is None
    assert len(calls) == 1
    # ... a changed source invalidates the cached failure
    src.write_text("int main( { //")
    assert native._build(str(src), str(so)) is None
    assert len(calls) == 2
    # ... but warns only once per source
    assert "failed" not in capsys.readouterr().err


def test_build_keyed_on_source_content(tmp_path, monkeypatch):
    """Which library loads is decided by the bytes of the source, not by
    mtimes: the .so files are git-ignored and travel with a copied tree
    (the chip tool copies the disk), where an old library can be newer
    than the source it no longer matches."""
    src = tmp_path / "lib.cpp"
    so = tmp_path / "lib.so"
    src.write_text("// v1")
    so.write_bytes(b"built from some older source, but newer by mtime")
    calls = []

    def fake_gxx(cmd, **kw):
        calls.append(cmd)
        with open(cmd[cmd.index("-o") + 1], "wb") as f:
            f.write(b"\x7fELF")

    monkeypatch.setattr(native.subprocess, "run", fake_gxx)
    v1 = native._build(str(src), str(so))
    assert len(calls) == 1 and v1 != str(so) and os.path.exists(v1)
    # same bytes, any mtime: reused without a build
    os.utime(src, (0, 0))
    assert native._build(str(src), str(so)) == v1
    os.utime(src, None)
    assert native._build(str(src), str(so)) == v1
    assert len(calls) == 1
    # new bytes: a new library, and the old one goes
    src.write_text("// v2")
    v2 = native._build(str(src), str(so))
    assert len(calls) == 2 and v2 != v1
    assert os.path.exists(v2) and not os.path.exists(v1)
    assert sorted(os.listdir(tmp_path)) == sorted(
        ["lib.cpp", "lib.so", os.path.basename(v2)])


def test_build_missing_compiler_warns_gplusplus(tmp_path, monkeypatch,
                                                capsys):
    src = tmp_path / "x.cpp"
    src.write_text("// empty")

    def no_gxx(*a, **kw):
        raise FileNotFoundError("g++")

    monkeypatch.setattr(native.subprocess, "run", no_gxx)
    assert native._build(str(src), str(tmp_path / "x.so")) is None
    assert "g++ not found" in capsys.readouterr().err
