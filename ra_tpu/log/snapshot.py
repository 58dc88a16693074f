"""Snapshot subsystem: durable machine-state captures + chunked transfer.

Capability parity with the reference's ``ra_snapshot`` (``src/
ra_snapshot.erl``): a pluggable codec behaviour; three capture kinds —
``snapshot`` (replicated, truncates the log), ``checkpoint`` (local
only, promotable), ``recovery`` (orderly-shutdown state to skip replay);
directory layout ``<server_dir>/{snapshots,checkpoints,recovery}/
<Term>_<Index>/``; chunked read/accept protocol for remote installs;
CRC-validated recovery that skips corrupt captures.
"""

from __future__ import annotations

import os
import pickle
import shutil
import struct
import zlib
from typing import Any, Iterator, List, Optional, Tuple

from ra_tpu import faults
from ra_tpu.protocol import SnapshotMeta
from ra_tpu.utils.lib import make_dir, retry, sync_dir
from ra_tpu.utils.seq import Seq

SNAPSHOT = "snapshots"
CHECKPOINT = "checkpoints"
RECOVERY = "recovery"

_TRAILER = struct.Struct("<I")


def decode_snapshot_chunks(chunks) -> Any:
    """Reassemble a transferred snapshot body. In-proc transfers may ship
    the machine state as one direct object chunk; remote transfers ship
    pickled byte chunks. Single wire-format rule for both backends."""
    if len(chunks) == 1 and not isinstance(chunks[0], (bytes, bytearray)):
        return chunks[0]
    # chunk bodies arrive over snapshot TRANSFER (untrusted bytes from a
    # peer): resolve through the wire allowlist, never plain pickle
    from ra_tpu.utils.wire import wire_loads

    return wire_loads(b"".join(chunks))


class SnapshotCodec:
    """Pluggable serialization behaviour (cf. the reference's snapshot
    behaviour callbacks: prepare/write/begin_read/read_chunk/
    begin_accept/accept_chunk/complete_accept/recover/validate)."""

    name = "pickle"

    def write(self, dir: str, meta: SnapshotMeta, machine_state: Any,
              sync_pool=None) -> None:
        """Write the capture under ``dir``. When a SyncPool is given the
        codec routes its fsyncs through it (serialized across servers,
        reference: ra_log_sync); durability on return is unchanged."""
        raise NotImplementedError

    def read(self, dir: str) -> Tuple[SnapshotMeta, Any]:
        raise NotImplementedError

    def read_meta(self, dir: str) -> SnapshotMeta:
        raise NotImplementedError

    def validate(self, dir: str) -> bool:
        raise NotImplementedError


class PickleCodec(SnapshotCodec):
    """Default codec: CRC-trailered pickle files (``meta.dat`` +
    ``snapshot.dat``)."""

    @staticmethod
    def _write_file(path: str, obj: Any, sync_pool=None) -> None:
        payload = pickle.dumps(obj)
        with open(path, "wb") as f:
            # a torn write here leaves a short body or a missing/torn
            # CRC trailer, neither of which validates — recovery falls
            # back to the previous capture generation. Two writes (not
            # one concatenation): the body can be hundreds of MB and
            # must not be copied just to append 4 trailer bytes
            faults.checked_write("snapshot.write", f, payload)
            faults.checked_write("snapshot.write", f,
                                 _TRAILER.pack(zlib.crc32(payload)))
            f.flush()
            if sync_pool is None:
                os.fsync(f.fileno())
        if sync_pool is not None:
            sync_pool.sync_path(path)

    @staticmethod
    def _read_file(path: str) -> Any:
        data = open(path, "rb").read()
        if len(data) < _TRAILER.size:
            raise IOError(f"snapshot file too short: {path}")
        payload, (crc,) = data[: -_TRAILER.size], _TRAILER.unpack(data[-_TRAILER.size :])
        if crc and zlib.crc32(payload) != crc:
            raise IOError(f"snapshot crc mismatch: {path}")
        return pickle.loads(payload)

    def write(self, dir: str, meta: SnapshotMeta, machine_state: Any,
              sync_pool=None) -> None:
        self._write_file(os.path.join(dir, "meta.dat"), meta, sync_pool)
        self._write_file(os.path.join(dir, "snapshot.dat"), machine_state, sync_pool)

    def read(self, dir: str) -> Tuple[SnapshotMeta, Any]:
        return (
            self._read_file(os.path.join(dir, "meta.dat")),
            self._read_file(os.path.join(dir, "snapshot.dat")),
        )

    def read_meta(self, dir: str) -> SnapshotMeta:
        return self._read_file(os.path.join(dir, "meta.dat"))

    def validate(self, dir: str) -> bool:
        try:
            self.read(dir)
            return True
        except Exception:
            return False


class ChunkAccept:
    """Incremental accept of a transferred snapshot body: every chunk is
    appended straight to a spool file on disk — peak extra memory is
    O(chunk), never O(snapshot) (reference: begin_accept/accept_chunk/
    complete_accept stream to disk, src/ra_snapshot.erl:742-860). On
    ``complete`` the body gets the CRC trailer, the machine state is
    decoded by a STREAMING restricted unpickle from the file, and the
    directory is promoted with the same crash-safe rename protocol as a
    local snapshot write."""

    def __init__(self, store: "SnapshotStore", meta: SnapshotMeta):
        self.store = store
        self.meta = meta
        d = store._kind_dir(SNAPSHOT)
        self.tmp = os.path.join(d, store._dirname(meta) + ".accepting")
        if os.path.exists(self.tmp):
            shutil.rmtree(self.tmp)
        os.makedirs(self.tmp)
        self.path = os.path.join(self.tmp, "snapshot.dat")
        self._f = open(self.path, "wb")
        self._crc = 0
        self.chunks_accepted = 0
        self.done = False

    def accept_chunk(self, data: bytes) -> None:
        # a torn/failed spool write leaves an .accepting dir that boot
        # clears; the in-flight accept aborts (sender restarts transfer)
        faults.checked_write("snapshot.chunk", self._f, data)
        self._crc = zlib.crc32(data, self._crc)
        self.chunks_accepted += 1

    def abort(self) -> None:
        self.done = True
        try:
            self._f.close()
        except Exception:  # noqa: BLE001
            pass
        shutil.rmtree(self.tmp, ignore_errors=True)

    def complete(self) -> Any:
        store = self.store
        self._f.write(_TRAILER.pack(self._crc))
        self._f.flush()
        if store.sync_pool is None:
            os.fsync(self._f.fileno())
        self._f.close()
        if store.sync_pool is not None:
            store.sync_pool.sync_path(self.path)
        # decode BEFORE promoting: an undecodable body (wire-allowlist
        # miss, truncation) must never become the current snapshot.
        # Streaming unpickle: the blob is never materialized as bytes.
        from ra_tpu.utils.wire import wire_load_file

        try:
            with open(self.path, "rb") as rf:
                state = wire_load_file(rf)
        except Exception:
            self.abort()
            raise
        PickleCodec._write_file(
            os.path.join(self.tmp, "meta.dat"), self.meta, store.sync_pool
        )
        d = store._kind_dir(SNAPSHOT)
        final = os.path.join(d, store._dirname(self.meta))
        if os.path.exists(final):
            shutil.rmtree(final)

        def _promote():
            faults.fire("snapshot.promote")
            os.replace(self.tmp, final)

        try:
            retry(_promote, attempts=3, delay_s=0.02)
        except OSError:
            self.abort()
            raise
        sync_dir(d)
        store._prune_count(SNAPSHOT, 2)
        store._prune_older(CHECKPOINT, self.meta.index + 1)
        self.done = True
        return state


class SnapshotStore:
    """Per-server snapshot/checkpoint directory manager."""

    def __init__(self, server_dir: str, codec: Optional[SnapshotCodec] = None,
                 max_checkpoints: int = 10, sync_pool=None):
        self.server_dir = server_dir
        self.codec = codec or PickleCodec()
        self.max_checkpoints = max_checkpoints
        self.sync_pool = sync_pool
        # True when this constructor made snapshot/: it holds no capture
        self.fresh = False
        for kind in (SNAPSHOT, CHECKPOINT, RECOVERY):
            d = os.path.join(server_dir, kind)
            if make_dir(d):
                # just made, so empty: no spool to clear
                if kind == SNAPSHOT:
                    self.fresh = True
                continue
            # a crash mid-write/mid-accept leaves .writing/.accepting
            # (or legacy .partial) spool dirs; they are not valid
            # captures — clear them. Orphaned accept spools also count
            # against the disk watermark budget (docs/INTERNALS.md
            # §21), so boot reclaims the bytes, durably.
            cleared = False
            for name in os.listdir(d):
                if name.endswith((".writing", ".accepting", ".partial")):
                    shutil.rmtree(os.path.join(d, name), ignore_errors=True)
                    cleared = True
            if cleared:
                sync_dir(d)

    # -- naming -------------------------------------------------------------

    @staticmethod
    def _dirname(meta: SnapshotMeta) -> str:
        return f"{meta.term:016X}_{meta.index:016X}"

    @staticmethod
    def _parse(dirname: str) -> Optional[Tuple[int, int]]:
        try:
            t, i = dirname.split("_")
            return int(t, 16), int(i, 16)
        except ValueError:
            return None

    def _kind_dir(self, kind: str) -> str:
        return os.path.join(self.server_dir, kind)

    def _list(self, kind: str) -> List[Tuple[int, int, str]]:
        """[(index, term, path)] ascending by index."""
        out = []
        d = self._kind_dir(kind)
        for name in os.listdir(d):
            p = self._parse(name)
            if p is None:
                continue
            term, idx = p
            out.append((idx, term, os.path.join(d, name)))
        return sorted(out)

    # -- writes -------------------------------------------------------------

    def write(self, meta: SnapshotMeta, machine_state: Any, kind: str = SNAPSHOT) -> str:
        """Durably write a capture; crash-safe via tmp dir + rename."""
        d = self._kind_dir(kind)
        final = os.path.join(d, self._dirname(meta))
        tmp = final + ".writing"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        self.codec.write(tmp, meta, machine_state, sync_pool=self.sync_pool)

        def _promote():
            faults.fire("snapshot.promote")
            os.replace(tmp, final)

        retry(_promote, attempts=3, delay_s=0.02)
        sync_dir(d)
        if kind == SNAPSHOT:
            # keep the previous generation as a corruption safety net
            self._prune_count(SNAPSHOT, 2)
            self._prune_older(CHECKPOINT, meta.index + 1)
        elif kind == CHECKPOINT:
            self._prune_count(CHECKPOINT, self.max_checkpoints)
        return final

    def _prune_older(self, kind: str, below_idx: int) -> None:
        pruned = False
        for idx, term, path in self._list(kind):
            if idx < below_idx:
                shutil.rmtree(path, ignore_errors=True)
                pruned = True
        if pruned:
            # make the unlink durable: an un-fsynced directory entry can
            # resurrect the pruned capture after a crash, silently
            # re-consuming the bytes emergency reclamation just freed
            sync_dir(self._kind_dir(kind))

    def _prune_count(self, kind: str, max_n: int) -> None:
        entries = self._list(kind)
        pruned = False
        while len(entries) > max_n:
            idx, term, path = entries.pop(0)
            shutil.rmtree(path, ignore_errors=True)
            pruned = True
        if pruned:
            sync_dir(self._kind_dir(kind))

    # -- reads ---------------------------------------------------------------

    def current(self, kind: str = SNAPSHOT) -> Optional[SnapshotMeta]:
        for idx, term, path in reversed(self._list(kind)):
            try:
                return self.codec.read_meta(path)
            except Exception:
                continue
        return None

    def read(self, kind: str = SNAPSHOT) -> Optional[Tuple[SnapshotMeta, Any]]:
        for idx, term, path in reversed(self._list(kind)):
            try:
                return self.codec.read(path)
            except Exception:
                continue  # corrupt capture: fall back to the previous one
        return None

    def latest_checkpoint_at_or_below(self, idx: int) -> Optional[Tuple[SnapshotMeta, Any]]:
        for cidx, term, path in reversed(self._list(CHECKPOINT)):
            if cidx > idx:
                continue
            try:
                return self.codec.read(path)
            except Exception:
                continue
        return None

    def promote_checkpoint(self, idx: int) -> Optional[SnapshotMeta]:
        got = self.latest_checkpoint_at_or_below(idx)
        if got is None:
            return None
        meta, state = got
        self.write(meta, state, kind=SNAPSHOT)
        return meta

    # -- chunked transfer ------------------------------------------------------

    def begin_read(self, chunk_size: int) -> Iterator[bytes]:
        got = self.read(SNAPSHOT)
        if got is None:
            return iter(())
        meta, state = got
        blob = pickle.dumps(state)

        def chunks():
            for off in range(0, max(len(blob), 1), chunk_size):
                yield blob[off : off + chunk_size]

        return chunks()

    def begin_read_stream(
        self, chunk_size: int
    ) -> Optional[Tuple[SnapshotMeta, Iterator[bytes]]]:
        """Open the current snapshot body for chunked sending straight
        FROM DISK — the state object is never decoded and the blob never
        materialized (reference: begin_read/read_chunk,
        src/ra_snapshot.erl:135-210). The fd is opened here, on the
        owning thread; the iterator may then be drained from a sender
        thread (an open fd survives pruning of the directory). The CRC
        trailer is verified as the stream drains — a corrupt body raises
        before the last chunk is yielded. Returns None when no valid
        snapshot exists or the codec's file layout is not the default."""
        if type(self.codec) is not PickleCodec:
            return None  # unknown on-disk layout: caller falls back
        for idx, term, path in reversed(self._list(SNAPSHOT)):
            try:
                meta = self.codec.read_meta(path)
            except Exception:
                continue
            try:
                f = open(os.path.join(path, "snapshot.dat"), "rb")
            except OSError:
                continue
            size = os.fstat(f.fileno()).st_size - _TRAILER.size
            if size < 0:
                f.close()
                continue
            f.seek(size)
            (crc_stored,) = _TRAILER.unpack(f.read(_TRAILER.size))
            f.seek(0)

            def chunks(f=f, size=size, crc_stored=crc_stored):
                try:
                    crc = 0
                    left = size
                    pending = None  # one-chunk buffer so CRC checks
                    while left > 0:  # before the final chunk is yielded
                        buf = f.read(min(chunk_size, left))
                        if not buf:
                            raise IOError("short read streaming snapshot")
                        left -= len(buf)
                        crc = zlib.crc32(buf, crc)
                        if pending is not None:
                            yield pending
                        pending = buf
                    if crc_stored and crc != crc_stored:
                        raise IOError("snapshot crc mismatch while streaming")
                    yield pending if pending is not None else b""
                finally:
                    f.close()

            return meta, chunks()
        return None

    def begin_accept(self, meta: SnapshotMeta) -> Optional[ChunkAccept]:
        """Start an incremental disk-spooled accept (None when the codec
        is not the default — caller falls back to in-RAM accumulation)."""
        if type(self.codec) is not PickleCodec:
            return None
        return ChunkAccept(self, meta)

    def accept_chunks(self, meta: SnapshotMeta, chunks: List[bytes]) -> Any:
        state = decode_snapshot_chunks(chunks)  # untrusted transfer bytes
        self.write(meta, state, kind=SNAPSHOT)
        return state

    def delete_kind(self, kind: str) -> None:
        shutil.rmtree(self._kind_dir(kind), ignore_errors=True)
        os.makedirs(self._kind_dir(kind), exist_ok=True)

    def delete_all(self) -> None:
        for kind in (SNAPSHOT, CHECKPOINT, RECOVERY):
            self.delete_kind(kind)
