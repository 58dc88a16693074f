"""The WAL writer's bookkeeping against the accounting it replaced.

``ReferenceWriter`` is the writer's first pass as it stood before the
ranges went mutable: per entry and per uid, with a ``Seq`` built for
every union, limit and written event. Both are fed the same queue
items, batch by batch, and have to agree on everything a batch leaves
behind: the written events in order, the resends, ``_last_idx``, the
file's bytes, the seqs handed to the segment writer at rollover and the
seqs recovery reads back from the file.
"""

import pickle
import random
import struct
import zlib

import pytest

from ra_tpu.log.tables import TableRegistry
from ra_tpu.log.wal import K_ENTRY, K_SPARSE, K_TRUNC, K_UID, MAGIC, Wal
from ra_tpu.utils.seq import Seq


class ReferenceWriter:
    """Per-entry / per-uid ``Seq`` accounting of what one WAL file holds."""

    def __init__(self, tables):
        self.tables = tables
        self.file_seqs = {}  # uid -> {tid: Seq}
        self.last_idx = {}
        self.uid_refs = {}
        self.frames = bytearray()

    def roll(self):
        seqs, self.file_seqs, self.uid_refs = self.file_seqs, {}, {}
        self.frames = bytearray()
        return seqs

    def _ref(self, uid):
        ref = self.uid_refs.get(uid)
        if ref is None:
            ref = self.uid_refs[uid] = len(self.uid_refs) + 1
            ub = uid.encode()
            self.frames += struct.pack("<BHH", K_UID, ref, len(ub)) + ub
        return ref

    def _frame(self, kind, ref, idx, term, payload):
        crc = zlib.crc32(struct.pack("<QQ", idx, term) + payload)
        self.frames += struct.pack("<BHQQII", kind, ref, idx, term, crc,
                                   len(payload)) + payload

    def _entry(self, kind, uid, idx, term, payload, tid, written, resends):
        snap_idx = self.tables.snapshot_index(uid)
        if idx <= snap_idx and idx not in self.tables.live_indexes(uid):
            written.setdefault((uid, term), []).append(idx)
            self.last_idx[uid] = max(self.last_idx.get(uid, 0), idx)
            return
        if kind != "s":
            last = self.last_idx.get(uid)
            if last is not None and idx > max(last, snap_idx) + 1:
                resends.append((uid, ("resend_write", max(last, snap_idx) + 1)))
                return
        self._frame(K_SPARSE if kind == "s" else K_ENTRY, self._ref(uid),
                    idx, term, payload)
        per = self.file_seqs.setdefault(uid, {})
        if kind == "s":
            self.last_idx[uid] = max(self.last_idx.get(uid, 0), idx)
        else:
            self.last_idx[uid] = idx
            last_any = max((sq.last() or 0 for sq in per.values()), default=0)
            if idx <= last_any:
                for t in list(per):
                    per[t] = per[t].limit(idx - 1)
        per[tid] = per.get(tid, Seq.empty()).add(idx)
        written.setdefault((uid, term), []).append(idx)

    def batch(self, items):
        """-> the notifications of the batch, in the order they leave."""
        written, resends = {}, []
        for kind, uid, idx, term, payload, tid in items:
            if kind == "r":
                snap_idx = self.tables.snapshot_index(uid)
                last = self.last_idx.get(uid)
                if idx > snap_idx and last is not None \
                        and idx > max(last, snap_idx) + 1:
                    # a run above the floor is refused whole, once
                    resends.append(
                        (uid, ("resend_write", max(last, snap_idx) + 1)))
                    continue
                for k, p in enumerate(payload):
                    self._entry("w", uid, idx + k, term[k], p, tid,
                                written, resends)
            elif kind == "t":
                self.frames += struct.pack("<BHQ", K_TRUNC, self._ref(uid), idx)
                self.last_idx[uid] = idx - 1
                per = self.file_seqs.get(uid, {})
                for t in list(per):
                    per[t] = per[t].limit(idx - 1)
            else:
                self._entry(kind, uid, idx, term, payload, tid, written, resends)
        return [(uid, ("written", term, Seq.from_list(idxs)))
                for (uid, term), idxs in written.items()] + resends


class Handoff:
    """A segment writer that only keeps what it is handed."""

    def __init__(self):
        self.jobs = []

    def flush_mem_tables(self, seqs, wal_file=None):
        self.jobs.append({uid: list(ts) for uid, ts in seqs.items()})


def pay(uid, idx, term):
    return pickle.dumps((uid, idx, term))


def run(uid, first, terms, tid=0):
    return ("r", uid, first, list(terms),
            [pay(uid, first + k, t) for k, t in enumerate(terms)], tid)


def w(uid, idx, term, tid=0):
    return ("w", uid, idx, term, pay(uid, idx, term), tid)


def s(uid, idx, term, tid=0):
    return ("s", uid, idx, term, pay(uid, idx, term), tid)


def t(uid, idx):
    return ("t", uid, idx, 0, b"", 0)


def snap(uid, idx, live=()):
    return ("snap", uid, idx, live)


def _fuzz(seed):
    """Seeded, and bound by no protocol: whatever reaches the queue, the
    two writers have to make the same of it."""
    rng = random.Random(seed)
    uids = [f"f{k}" for k in range(6)]
    nxt = {u: 1 for u in uids}
    term = {u: 1 for u in uids}
    tid = {u: 0 for u in uids}
    steps = []
    for _ in range(12):
        items = []
        for _ in range(rng.randrange(1, 14)):
            u = rng.choice(uids)
            roll = rng.random()
            if roll < 0.6:  # the steady case
                m = rng.randrange(1, 5)
                if rng.random() < 0.15:
                    term[u] += 1
                terms = [term[u]] * m
                if m > 2 and rng.random() < 0.3:
                    term[u] += 1
                    terms[m // 2:] = [term[u]] * (m - m // 2)
                items.append(run(u, nxt[u], terms, tid[u]))
                nxt[u] += m
            elif roll < 0.7:  # a rewrite of the suffix, in a successor table
                back = rng.randrange(1, 4)
                nxt[u] = max(1, nxt[u] - back)
                term[u] += 1
                tid[u] += 1
                if rng.random() < 0.5:
                    items.append(t(u, nxt[u]))
                m = rng.randrange(1, 4)
                items.append(run(u, nxt[u], [term[u]] * m, tid[u]))
                nxt[u] += m
            elif roll < 0.8:
                items.append(w(u, nxt[u], term[u], tid[u]))
                nxt[u] += 1
            elif roll < 0.88:  # a hole
                items.append(run(u, nxt[u] + rng.randrange(1, 4), [term[u]], tid[u]))
            elif roll < 0.94:
                items.append(s(u, rng.randrange(1, nxt[u] + 6), term[u], tid[u]))
            else:
                items.append(t(u, max(1, nxt[u] - rng.randrange(0, 3))))
        steps.append(items)
        if rng.random() < 0.25:
            u = rng.choice(uids)
            floor = rng.randrange(0, nxt[u] + 2)
            live = sorted(rng.sample(range(1, floor + 1), min(floor, 2)))
            steps.append(snap(u, floor, live))
            nxt[u] = max(nxt[u], floor + 1)
        if rng.random() < 0.15:
            steps.append("roll")
    return steps


SCENARIOS = {
    "run_of_one": [[run("a", 1, [1])], [run("a", 2, [1])], [run("a", 3, [1])]],
    "runs_of_many": [
        [run("a", 1, [1] * 5), run("b", 1, [1] * 3), run("a", 6, [1] * 2)],
        [run("b", 4, [1] * 4), run("a", 8, [1]), run("c", 1, [2] * 6)],
        "roll",
        [run("a", 9, [1] * 3), run("c", 7, [2])],
    ],
    "two_tables": [
        [run("a", 1, [1] * 4, tid=0), run("a", 5, [1] * 3, tid=1)],
        [run("a", 8, [1] * 2, tid=1), run("b", 1, [1], tid=0)],
        [run("a", 10, [1], tid=2), run("a", 11, [1], tid=1)],
    ],
    "multi_term_run": [
        [run("a", 1, [1, 1, 2, 2, 3]), run("b", 1, [4, 5])],
        [run("a", 6, [3, 3, 4])],
    ],
    "overwrite_of_a_pending_suffix": [
        [run("a", 1, [1] * 6, tid=0), run("b", 1, [1] * 2),
         run("a", 4, [2] * 4, tid=1), run("a", 8, [2], tid=1)],
        [run("a", 7, [3] * 2, tid=2)],
        [w("a", 2, 4, tid=3)],
    ],
    "truncate_marker": [
        [run("a", 1, [1] * 6), t("a", 4), run("a", 4, [2] * 2, tid=1)],
        [t("a", 5), run("b", 1, [1])],
        [run("a", 5, [3], tid=1), t("c", 9), run("c", 9, [1])],
    ],
    "run_over_the_snapshot_floor": [
        [run("a", 1, [1] * 3)],
        snap("a", 5, live=(3,)),
        [run("a", 2, [1] * 7), run("b", 1, [1])],
        snap("b", 9),
        [run("b", 10, [2] * 2), run("a", 9, [1])],
    ],
    "gap": [
        [run("a", 1, [1] * 3)],
        [run("a", 6, [1] * 2), run("b", 1, [1]), w("a", 9, 1)],
        [run("a", 4, [1] * 4)],
    ],
    "sparse_writes": [
        [run("a", 1, [1] * 2)],
        snap("a", 12, live=(5, 7, 10)),
        [s("a", 10, 1), s("a", 5, 1), s("a", 7, 1), s("a", 4, 1), s("b", 3, 2)],
        [run("a", 13, [2] * 2), s("a", 6, 1)],
    ],
    "fuzz_1": _fuzz(1),
    "fuzz_2": _fuzz(2),
    "fuzz_3": _fuzz(3),
}


def _drive(tmp_path, steps, bulk):
    tables = TableRegistry()
    got, handoff = [], Handoff()
    wal = Wal(str(tmp_path / "wal"), tables, lambda uid, evt: got.append((uid, evt)),
              segment_writer=handoff, threaded=False)
    rows = []
    if bulk:
        wal.notify_many = rows.extend
    ref = ReferenceWriter(tables)
    for step in steps:
        if step == "roll":
            path = wal._file_path
            wal.force_rollover()
            _same_file(path, ref)
            assert handoff.jobs.pop() == Wal._flush_jobs(ref.roll())
            continue
        if step[0] == "snap":
            _, uid, idx, live = step
            tables.set_snapshot_state(uid, idx, Seq.from_list(live))
            continue
        for kind, uid, idx, term, payload, tid in step:
            if kind == "r":
                assert wal.write_run(uid, idx, term, payload, tid)
            elif kind == "t":
                assert wal.truncate_write(uid, idx)
            else:
                assert wal.write(uid, idx, term, payload, sparse=kind == "s",
                                 tid=tid)
        wal.flush()
        want = ref.batch(step)
        if rows:
            # the bulk hook's rows are the batch's written events, range
            # by range, in the order notify() carries them
            events = [(uid, evt) for uid, evt in want if evt[0] == "written"]
            assert len(events) > 1
            assert rows == [(uid, term, lo, hi) for uid, (_w, term, seq) in events
                            for lo, hi in seq.ranges()]
            assert got == [e for e in want if e[1][0] != "written"]
        else:
            assert got == want
        assert wal._last_idx == ref.last_idx
        del got[:], rows[:]
    return wal, ref, handoff, tables


def _same_file(path, ref):
    with open(path, "rb") as f:
        assert f.read() == MAGIC + bytes(ref.frames)


@pytest.mark.parametrize("bulk", [False, True], ids=["notify", "notify_many"])
@pytest.mark.parametrize("name", SCENARIOS)
def test_writer_leaves_what_the_seq_accounting_left(tmp_path, name, bulk):
    wal, ref, handoff, _tables = _drive(tmp_path, SCENARIOS[name], bulk)
    path = wal._file_path
    wal.force_rollover()
    _same_file(path, ref)
    handed = handoff.jobs.pop()
    assert handed == Wal._flush_jobs(ref.roll())
    assert not handoff.jobs
    counts = wal.counter.to_dict()
    assert 0 <= counts["runs_in_place"] <= counts["runs"]
    wal.close()


@pytest.mark.parametrize("name", [n for n in SCENARIOS if "roll" not in SCENARIOS[n]])
def test_recovery_reads_back_what_the_writer_handed_over(tmp_path, name):
    """What recovery makes of the file's bytes is what the writer's
    bookkeeping said the file held, less what has died under a floor
    that moved since (index by index: recovery re-inserts into fresh
    memtables, whose table ids are its own)."""
    steps = SCENARIOS[name]
    wal, _ref, handoff, tables = _drive(tmp_path, steps, False)
    wal.force_rollover()
    handed = handoff.jobs.pop()
    wal.close()
    # the floors the file was written under are registered before the
    # WAL is read back, as pre-init does
    tables2, back = TableRegistry(), Handoff()
    for step in steps:
        if step != "roll" and step[0] == "snap":
            tables2.set_snapshot_state(step[1], step[2], Seq.from_list(step[3]))
    wal2 = Wal(str(tmp_path / "wal"), tables2, lambda uid, evt: None,
               segment_writer=back, threaded=False)

    def indexes(jobs):
        return {uid: Seq([r for _t, sq in ts for r in sq.ranges()])
                for uid, ts in jobs.items()}

    merged = {}
    for jobs in back.jobs:
        for uid, sq in indexes(jobs).items():
            merged[uid] = merged.get(uid, Seq.empty()).union(sq)
    alive = {}
    for uid, sq in indexes(handed).items():
        kept = sq.floor(tables2.snapshot_index(uid) + 1).union(
            sq.intersect(tables2.live_indexes(uid)))
        if not kept.is_empty():
            alive[uid] = kept
    assert merged == alive
    wal2.close()


@pytest.mark.parametrize("name,runs,in_place", [
    ("run_of_one", 3, 3),
    ("runs_of_many", 8, 8),
    ("two_tables", 6, 6),
    ("multi_term_run", 3, 3),
    # the two overwriting runs and the overwriting single write rewind
    ("overwrite_of_a_pending_suffix", 6, 3),
    # a marker clips before the rewrite comes, so the rewrite extends
    ("truncate_marker", 5, 5),
    ("run_over_the_snapshot_floor", 5, 4),
    ("gap", 5, 3),
    ("sparse_writes", 2, 2),
])
def test_in_place_counts_the_steady_runs(tmp_path, name, runs, in_place):
    wal, _ref, _handoff, _tables = _drive(tmp_path, SCENARIOS[name], True)
    counts = wal.counter.to_dict()
    assert (counts["runs"], counts["runs_in_place"]) == (runs, in_place)
    wal.close()


def test_writer_cpu_is_booked_once_a_batch(tmp_path):
    wal, _ref, _handoff, _tables = _drive(tmp_path, SCENARIOS["runs_of_many"], True)
    counts = wal.counter.to_dict()
    assert counts["writer_cpu_ns"] > 0 and counts["entries"] == 25
    before = counts["writer_cpu_ns"]
    wal.flush()  # nothing queued: no batch, no booking
    assert wal.counter.get("writer_cpu_ns") == before
    wal.close()
