"""The real per-server log: memtable + shared WAL + segments + snapshots.

Implements ``LogApi`` over the storage engines, with the reference's
async write model (reference: ``src/ra_log.erl`` — append/write go to the
memtable then the WAL :484-591; ``("written", term, seq)`` events advance
the durable watermark with overwrite-staleness checks :895-1163;
``("segments", seq, refs)`` events shrink the memtable; release cursors
decide snapshots :1282-1436; ``resend`` protocol re-feeds the WAL after
gaps :1651).

Events arrive via ``handle_event`` from whatever thread the runtime
routes them on; the owning server must serialize calls (the server proc
event loop does).
"""

from __future__ import annotations

import os
import pickle
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple

from ra_tpu.log.api import LogApi
from ra_tpu.log.segments import SegmentSet
from ra_tpu.log.snapshot import CHECKPOINT, RECOVERY, SNAPSHOT, SnapshotStore
from ra_tpu.log.tables import TableRegistry
from ra_tpu.log.wal import Wal
from ra_tpu.protocol import Entry, SnapshotMeta, encode_cmd
from ra_tpu.utils.seq import Seq

MIN_SNAPSHOT_INTERVAL = 4096
MIN_CHECKPOINT_INTERVAL = 16384


class Log(LogApi):
    def __init__(
        self,
        uid: str,
        server_dir: str,
        tables: TableRegistry,
        wal: Wal,
        min_snapshot_interval: int = MIN_SNAPSHOT_INTERVAL,
        min_checkpoint_interval: int = MIN_CHECKPOINT_INTERVAL,
        snapshot_store: Optional[SnapshotStore] = None,
        major_every_minors: int = 2,
        bg_submit=None,
        segment_index_mode: str = "map",
        sync_pool=None,
    ):
        self.uid = uid
        self.server_dir = server_dir
        os.makedirs(server_dir, exist_ok=True)
        self.tables = tables
        self.wal = wal
        self.mt = tables.mem_table(uid)
        self.segs = SegmentSet(
            os.path.join(server_dir, "segments"), index_mode=segment_index_mode
        )
        self.snapshots = snapshot_store or SnapshotStore(server_dir, sync_pool=sync_pool)
        self.min_snapshot_interval = min_snapshot_interval
        self.min_checkpoint_interval = min_checkpoint_interval
        # major compaction policy: schedule a grouping pass every N
        # minor (snapshot-floor) compactions (the reference's
        # {num_minors, N} major strategy; cf. src/ra_kv.erl:80-103)
        self.major_every_minors = major_every_minors
        self.bg_submit = bg_submit  # None -> run major passes inline
        self._minors_since_major = 0
        self.resend_window_s = 20.0
        self._last_resend_t = float("-inf")

        # recover tail state
        self._snapshot_meta = self.snapshots.current()
        snap_idx = self._snapshot_meta.index if self._snapshot_meta else 0
        snap_term = self._snapshot_meta.term if self._snapshot_meta else 0
        if self._snapshot_meta is not None:
            self.tables.set_snapshot_state(
                uid, snap_idx, Seq.from_list(self._snapshot_meta.live_indexes)
            )
        mt_rng = self.mt.range()
        seg_rng = self.segs.range()
        last = max(
            snap_idx,
            mt_rng[1] if mt_rng else 0,
            seg_rng[1] if seg_rng else 0,
        )
        self._last_index = last
        t = self.fetch_term(last)
        self._last_term = t if t is not None else snap_term
        # everything already on disk is durable
        self._written_index = last
        self._written_term = self._last_term
        self._last_checkpoint_idx = snap_idx
        self._last_snapshot_candidate: Optional[Tuple[int, Any]] = None

    # ------------------------------------------------------------------
    # writes

    def append(self, entry: Entry) -> None:
        if entry.index != self._last_index + 1:
            raise ValueError(
                f"non-contiguous append {entry.index} after {self._last_index}"
            )
        tid = self.mt.insert(entry)
        self.wal.write(self.uid, entry.index, entry.term, encode_cmd(entry.cmd), tid=tid)
        self._last_index = entry.index
        self._last_term = entry.term

    def append_many(self, entries: Sequence[Entry]) -> None:
        """Leader bulk append: one memtable run insert, one WAL lock
        round, and one serialization per DISTINCT command object (a
        pipelined wave fans the same Command instance across entries —
        pickling it once per batch instead of once per entry)."""
        if not entries:
            return
        if entries[0].index != self._last_index + 1:
            raise ValueError(
                f"non-contiguous append {entries[0].index} after "
                f"{self._last_index}"
            )
        self._bulk_insert(entries)
        self._last_index = entries[-1].index
        self._last_term = entries[-1].term

    def _bulk_insert(self, entries: Sequence[Entry]) -> None:
        tid = self.mt.insert_run(entries)
        if tid is None:
            # overwrite/rotation inside the run: per-entry path
            for e in entries:
                t = self.mt.insert(e)
                self.wal.write(self.uid, e.index, e.term,
                               encode_cmd(e.cmd), tid=t)
            return
        memo: dict = {}
        payloads = []
        terms = []
        for e in entries:
            c = e.cmd
            enc = memo.get(id(c))
            if enc is None:
                memo[id(c)] = enc = encode_cmd(c)
            payloads.append(enc)
            terms.append(e.term)
        # ONE queue item + run-level writer bookkeeping for the whole
        # contiguous run (the WAL expands it to per-entry frames)
        self.wal.write_run(self.uid, entries[0].index, terms, payloads, tid)

    def write(self, entries: Sequence[Entry]) -> None:
        if not entries:
            return
        first = entries[0].index
        if first > self._last_index + 1:
            raise ValueError(f"gap: write at {first}, last is {self._last_index}")
        if first <= self._last_index:
            # divergent suffix rewrite: rewind the durable watermark too
            self.wal.truncate_write(self.uid, first)
            self.mt.truncate_from(first)
            self._rewind_to(first - 1)
        self._bulk_insert(entries)
        self._last_index = entries[-1].index
        self._last_term = entries[-1].term

    def write_sparse(self, entry: Entry) -> None:
        """Out-of-order live-entry write during snapshot install."""
        tid = self.mt.insert_sparse(entry)
        self.wal.write(
            self.uid, entry.index, entry.term, encode_cmd(entry.cmd),
            sparse=True, tid=tid,
        )

    def set_last_index(self, idx: int) -> None:
        self.wal.truncate_write(self.uid, idx + 1)
        self.mt.truncate_from(idx + 1)
        self._rewind_to(idx)
        self._last_index = idx
        t = self.fetch_term(idx)
        self._last_term = t if t is not None else 0

    def _rewind_to(self, idx: int) -> None:
        if self._written_index > idx:
            self._written_index = idx
            t = self.fetch_term(idx)
            self._written_term = t if t is not None else 0

    # ------------------------------------------------------------------
    # events

    def handle_event(self, evt: Any) -> List[Any]:
        if not isinstance(evt, tuple) or not evt:
            return []
        tag = evt[0]
        if tag == "written":
            _, term, seq = evt
            if seq is not None and not seq.is_empty():
                self.note_written(term, seq.last())
            return []
        if tag == "segments":
            _, tid_seqs, refs = evt
            for fname, rng in refs:
                self.segs.add_ref(fname, rng)
            for tid, seq in tid_seqs:
                self.mt.record_flushed(seq, tid=tid)
            return []
        if tag == "resend_write":
            # throttled: a flood of gap notifications must not re-queue
            # the same tail repeatedly (reference: resend_window_seconds,
            # src/ra_log.erl:65,1651)
            _, from_idx = evt
            self._resend(from_idx)
            return []
        if tag == "wal_up":
            # the WAL came back after a failure: resend everything past
            # the durable watermark (bypasses the throttle — this is the
            # recovery moment itself)
            self._resend(self._written_index + 1, force=True)
            return []
        return []

    def note_written(self, term: int, last: int) -> int:
        """The WAL holds this log's entries up to ``last``, written at
        ``term`` (what a ``written`` event says, without the event):
        advance the durable watermark, and return its index."""
        # stale-write check: the entry at `last` must still carry the
        # term that was written (it may have been overwritten since)
        if last > self._written_index and self.fetch_term(last) == term:
            self._written_index = min(last, self._last_index)
            self._written_term = term
        return self._written_index

    def _resend(self, from_idx: int, force: bool = False) -> None:
        now = time.monotonic()
        if not force and (now - self._last_resend_t) < self.resend_window_s:
            return
        self._last_resend_t = now
        if force:
            # post-failure resend: truncate markers issued while the WAL
            # was down were dropped, and the retained failed file may
            # hold a since-discarded suffix — re-establish the cut in
            # the fresh file before replaying the current tail
            self.wal.truncate_write(self.uid, from_idx)
        for i in range(from_idx, self._last_index + 1):
            got = self.mt.get_with_tid(i)
            if got is not None:
                e, tid = got
                # tag with the table that OWNS the entry: tagging an
                # older table's entry with the head tid would make the
                # eventual flush read get_from(head, i) -> None and
                # silently drop the only durable copy
                self.wal.write(self.uid, e.index, e.term, encode_cmd(e.cmd),
                               tid=tid)

    # ------------------------------------------------------------------
    # reads

    def last_index_term(self) -> Tuple[int, int]:
        return self._last_index, self._last_term

    def last_written(self) -> Tuple[int, int]:
        return self._written_index, self._written_term

    def fetch(self, idx: int) -> Optional[Entry]:
        e = self.mt.get(idx)
        if e is not None:
            return e
        return self.segs.fetch(idx)

    def fetch_term(self, idx: int) -> Optional[int]:
        if idx == 0:
            return 0
        e = self.mt.get(idx)
        if e is not None:
            return e.term
        t = self.segs.fetch_term(idx)
        if t is not None:
            return t
        if self._snapshot_meta is not None and idx == self._snapshot_meta.index:
            return self._snapshot_meta.term
        return None

    def fetch_range(self, lo: int, hi: int) -> List[Entry]:
        """Batched contiguous read (the AER-construction / apply hot
        path): ONE memtable chain pass for the whole range instead of a
        per-index table walk, segment fallback only for flushed holes.
        Stops at the first truly-missing index (base-class contract)."""
        if hi < lo:
            return []
        got = self.mt.get_range(lo, hi)
        out: List[Entry] = []
        segs_fetch = self.segs.fetch
        for k, e in enumerate(got):
            if e is None:
                e = segs_fetch(lo + k)
                if e is None:
                    break
            out.append(e)
        return out

    def fold(self, lo: int, hi: int, fn: Callable[[Entry, Any], Any], acc: Any) -> Any:
        for i in range(lo, hi + 1):
            e = self.fetch(i)
            if e is None:
                raise KeyError(f"missing log entry {i} (uid={self.uid})")
            acc = fn(e, acc)
        return acc

    def sparse_read(self, idxs: Sequence[int]) -> List[Entry]:
        out = []
        for i in idxs:
            e = self.fetch(i)
            if e is not None:
                out.append(e)
        return out

    # ------------------------------------------------------------------
    # snapshots

    def snapshot_index_term(self) -> Optional[Tuple[int, int]]:
        m = self._snapshot_meta
        return (m.index, m.term) if m else None

    def snapshot_meta(self) -> Optional[SnapshotMeta]:
        return self._snapshot_meta

    def read_snapshot(self) -> Optional[Tuple[SnapshotMeta, Any]]:
        return self.snapshots.read(SNAPSHOT)

    def install_snapshot(self, meta: SnapshotMeta, machine_state: Any) -> List[Any]:
        self.snapshots.write(meta, machine_state, kind=SNAPSHOT)
        self._post_install(meta)
        return []

    def _post_install(self, meta: SnapshotMeta) -> None:
        self._post_snapshot(meta)
        if self._last_index < meta.index:
            self._last_index = meta.index
            self._last_term = meta.term
        if self._written_index < meta.index:
            self._written_index = meta.index
            self._written_term = meta.term

    # -- streaming transfer (reference: src/ra_snapshot.erl:135-210,
    # 742-860) -------------------------------------------------------------

    def begin_snapshot_read(self, chunk_size: int):
        return self.snapshots.begin_read_stream(chunk_size)

    def begin_accept_snapshot(self, meta: SnapshotMeta):
        return self.snapshots.begin_accept(meta)

    def complete_accept_snapshot(self, accept) -> Any:
        state = accept.complete()  # decodes from disk, promotes the dir
        self._post_install(accept.meta)
        return state

    def _post_snapshot(self, meta: SnapshotMeta) -> None:
        live = Seq.from_list(meta.live_indexes)
        self._snapshot_meta = meta
        self.tables.set_snapshot_state(self.uid, meta.index, live)
        self.mt.set_first(meta.index + 1, live=live)
        self.segs.truncate_below(meta.index, live)
        self._minors_since_major += 1
        if self._minors_since_major >= self.major_every_minors:
            self._minors_since_major = 0
            if self.bg_submit is not None:
                self.bg_submit(lambda: self.segs.major_compact(meta.index, live))
            else:
                self.segs.major_compact(meta.index, live)

    def major_compaction(self):
        """Explicit major compaction pass (grouping + merge + symlink
        protocol); normally scheduled automatically every
        ``major_every_minors`` snapshots."""
        meta = self._snapshot_meta
        if meta is None:
            return {"unreferenced": [], "linked": [], "compacted": []}
        return self.segs.major_compact(
            meta.index, Seq.from_list(meta.live_indexes)
        )

    def update_release_cursor(
        self, idx: int, cluster, machine_version: int, machine_state: Any,
        live_indexes=(),
    ) -> List[Any]:
        cur = self._snapshot_meta.index if self._snapshot_meta else 0
        if idx <= cur or (idx - cur) < self.min_snapshot_interval:
            return []
        return self._take_snapshot(
            idx, cluster, machine_version, machine_state,
            live_indexes=tuple(i for i in live_indexes if i <= idx),
        )

    def force_snapshot(
        self, idx, cluster, machine_version, machine_state, live_indexes=()
    ) -> List[Any]:
        return self._take_snapshot(
            idx, cluster, machine_version, machine_state,
            live_indexes=tuple(i for i in live_indexes if i <= idx),
        )

    def _take_snapshot(self, idx, cluster, machine_version, machine_state,
                       live_indexes: Tuple[int, ...] = ()) -> List[Any]:
        t = self.fetch_term(idx)
        if t is None:
            return []
        meta = SnapshotMeta(
            index=idx,
            term=t,
            cluster=tuple(cluster),
            machine_version=machine_version,
            live_indexes=tuple(live_indexes),
        )
        self.snapshots.write(meta, machine_state, kind=SNAPSHOT)
        self._post_snapshot(meta)
        return []

    def checkpoint(
        self, idx, cluster, machine_version, machine_state, live_indexes=()
    ) -> List[Any]:
        if (idx - self._last_checkpoint_idx) < self.min_checkpoint_interval:
            return []
        t = self.fetch_term(idx)
        if t is None:
            return []
        # live indexes are carried in the checkpoint meta: a later
        # promotion installs it as a snapshot and must retain them
        meta = SnapshotMeta(
            index=idx, term=t, cluster=tuple(cluster),
            machine_version=machine_version,
            live_indexes=tuple(i for i in live_indexes if i <= idx),
        )
        self.snapshots.write(meta, machine_state, kind=CHECKPOINT)
        self._last_checkpoint_idx = idx
        return []

    def promote_checkpoint(self, idx: int) -> List[Any]:
        meta = self.snapshots.promote_checkpoint(idx)
        if meta is not None:
            self._post_snapshot(meta)
        return []

    def write_recovery_checkpoint(self, meta: SnapshotMeta, machine_state: Any) -> None:
        """Orderly-shutdown capture to skip replay on restart."""
        self.snapshots.write(meta, machine_state, kind=RECOVERY)

    def read_recovery_checkpoint(self) -> Optional[Tuple[SnapshotMeta, Any]]:
        return self.snapshots.read(RECOVERY)

    def discard_recovery_checkpoint(self) -> None:
        """Recovery checkpoints are single-use (consumed at boot)."""
        self.snapshots.delete_kind(RECOVERY)

    # ------------------------------------------------------------------

    def close(self) -> None:
        self.segs.close()

    def overview(self) -> dict:
        ov = super().overview()
        ov.update(
            {
                "uid": self.uid,
                "mem_table_size": len(self.mt),
                "num_segments": self.segs.num_segments(),
                "wal_last_seq": self.wal.last_writer_seq(self.uid),
            }
        )
        return ov
