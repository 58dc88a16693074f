"""Native (C++) acceleration for the storage and hot-loop runtime paths.

Two libraries, built with g++ on first use (cached next to the source
as ``<name>.<digest of the source>.so``) and exposed through ctypes
bindings:

- ``wal_native``: WAL batch framing + write + fsync (PR 5);
- ``rt_native``: the hot-loop runtime (docs/INTERNALS.md §18) — ring
  drain classification and mailbox pack scatter.

Everything here has a pure-Python fallback. ``available()`` reports the
WAL library (the historical contract); ``entry_points()`` reports every
loaded symbol so bench artifacts are self-describing. A failed build is
cached per source digest (a missing compiler does not re-attempt the
build on every import) and surfaces the compiler stderr in ONE warning
instead of a silent fallback.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import sys
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "wal_native.cpp")
_SO = os.path.join(_HERE, "wal_native.so")
_RT_SRC = os.path.join(_HERE, "rt_native.cpp")
_RT_SO = os.path.join(_HERE, "rt_native.so")

_lib = None
_lock = threading.Lock()
_tried = False
_rt_lib = None
_rt_tried = False

# negative build cache: src path -> source digest the failure was seen
# at (a changed source retries; an unchanged one never rebuilds), and
# whether the one-shot warning for it was already emitted
_build_failed: Dict[str, str] = {}
_warned: set = set()


def _build(src: str = _SRC, so: str = _SO) -> Optional[str]:
    """Path of the library built from ``src`` as it reads now, building
    it if need be. The file is ``<so stem>.<source digest>.so``: the
    libraries are git-ignored and travel with a copied tree, where
    mtimes say nothing about which source a ``.so`` was built from."""
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    stem = so[:-len(".so")]
    built = f"{stem}.{digest}.so"
    if os.path.exists(built):
        return built
    if _build_failed.get(src) == digest:
        return None  # cached negative result for this exact source
    tmp = f"{built}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-o", tmp, src],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, built)  # atomic: a parallel builder never loads half a file
    except Exception as e:  # noqa: BLE001
        _build_failed[src] = digest
        if src not in _warned:
            _warned.add(src)
            detail = ""
            if isinstance(e, subprocess.CalledProcessError) and e.stderr:
                detail = e.stderr.decode("utf-8", "replace").strip()
            elif isinstance(e, FileNotFoundError):
                detail = "g++ not found"
            else:
                detail = repr(e)
            print(
                f"ra_tpu.native: build of {os.path.basename(src)} failed; "
                f"falling back to the Python paths "
                f"({detail[:2000]})",
                file=sys.stderr,
            )
        return None
    for old in glob.glob(f"{glob.escape(stem)}.*.so"):
        if old != built:
            os.unlink(old)  # libraries of sources that no longer exist
    return built


def _load():
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        so = _build(_SRC, _SO)
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            return None
        lib.wal_frame_batch.restype = ctypes.c_long
        lib.wal_frame_batch.argtypes = [
            ctypes.c_char_p,  # kinds u8*
            ctypes.c_void_p,  # refs u16*
            ctypes.c_void_p,  # idxs u64*
            ctypes.c_void_p,  # terms u64*
            ctypes.c_void_p,  # offs u64*
            ctypes.c_void_p,  # lens u32*
            ctypes.c_long,
            ctypes.c_char_p,  # blob
            ctypes.c_int,
            ctypes.c_void_p,  # out
            ctypes.c_long,
        ]
        lib.wal_frame_bound.restype = ctypes.c_long
        lib.wal_frame_bound.argtypes = [ctypes.c_char_p, ctypes.c_void_p, ctypes.c_long]
        lib.wal_crc32.restype = ctypes.c_uint32
        lib.wal_crc32.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.wal_write_batch.restype = ctypes.c_long
        lib.wal_write_batch.argtypes = [
            ctypes.c_char_p,  # kinds u8*
            ctypes.c_void_p,  # refs u16*
            ctypes.c_void_p,  # idxs u64*
            ctypes.c_void_p,  # terms u64*
            ctypes.c_void_p,  # offs u64*
            ctypes.c_void_p,  # lens u32*
            ctypes.c_long,
            ctypes.c_char_p,  # blob
            ctypes.c_int,     # compute_crc
            ctypes.c_int,     # fd
            ctypes.c_int,     # sync_mode
            ctypes.c_void_p,  # fsync_ns out
        ]
        _lib = lib
        return _lib


def _load_rt():
    global _rt_lib, _rt_tried
    with _lock:
        if _rt_tried:
            return _rt_lib
        _rt_tried = True
        so = _build(_RT_SRC, _RT_SO)
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            return None
        lib.rt_classify.restype = ctypes.c_long
        lib.rt_classify.argtypes = [
            ctypes.c_char_p,  # codes u8*
            ctypes.c_long,    # n
            ctypes.c_long,    # n_classes
            ctypes.c_void_p,  # out_idx i32*
            ctypes.c_void_p,  # counts i32*
        ]
        lib.rt_pack_mbox.restype = ctypes.c_long
        lib.rt_pack_mbox.argtypes = [
            ctypes.c_void_p,  # vals i64*
            ctypes.c_void_p,  # cols i32*
            ctypes.c_long,    # n
            ctypes.c_void_p,  # rows i32*
            ctypes.c_long,    # nf
            ctypes.c_void_p,  # out i32*
            ctypes.c_long,    # nrows
            ctypes.c_long,    # width
        ]
        _rt_lib = lib
        return _rt_lib


def available() -> bool:
    """Whether the native WAL library is loaded (historical contract —
    the Wal's construction-time gate). The runtime entry points report
    through ``entry_points()``."""
    return _load() is not None


def entry_points() -> Dict[str, bool]:
    """Which native entry points actually loaded, keyed by the seam
    they serve — recorded into bench JSON so artifacts are
    self-describing, and consulted by the coordinator's per-path
    switches."""
    wal = _load() is not None
    rt = _load_rt() is not None
    return {
        "wal": wal,
        "pack": rt,
        "classify": rt,
    }


# record: (kind:int, ref:int, idx:int, term:int, payload:bytes), or a
# contiguous run (K_RUN, ref, first_idx, terms_list, payloads_list) that
# expands to per-entry K_ENTRY frames (mirrors ra_tpu.log.wal.K_RUN)
Record = Tuple[int, int, int, int, bytes]
K_RUN = 100
_K_ENTRY = 2


def _pack_arrays(records: List[Record]):
    """Expand records (runs widened) into the parallel column arrays +
    payload blob the native entry points consume. The columns collect
    in lists and become arrays once: a 10k-group batch is thousands of
    runs of ONE entry, and five array stores a run cost ten times what
    five appends do."""
    kinds: List[int] = []
    refs: List[int] = []
    idxs: List[int] = []
    terms: List[int] = []
    parts: List[bytes] = []
    for rec in records:
        kind = rec[0]
        if kind == K_RUN:
            _, ref, first, run_terms, payloads = rec
            m = len(payloads)
            if m == 1:
                kinds.append(_K_ENTRY)
                refs.append(ref)
                idxs.append(first)
                terms.append(run_terms[0])
                parts.append(payloads[0])
            else:
                kinds.extend([_K_ENTRY] * m)
                refs.extend([ref] * m)
                idxs.extend(range(first, first + m))
                terms.extend(run_terms)
                parts.extend(payloads)
        else:
            _, ref, idx, term, payload = rec
            kinds.append(kind)
            refs.append(ref)
            idxs.append(idx)
            terms.append(term)
            parts.append(payload)
    n = len(kinds)
    lens = np.fromiter(map(len, parts), np.uint32, n)
    offs = np.empty(n, np.uint64)
    if n:
        offs[0] = 0
        np.cumsum(lens[:-1], dtype=np.uint64, out=offs[1:])
    return (n, np.array(kinds, np.uint8), np.array(refs, np.uint16),
            np.array(idxs, np.uint64), np.array(terms, np.uint64),
            offs, lens, b"".join(parts))


def frame_batch(records: List[Record], compute_crc: bool = True) -> Optional[bytes]:
    """Frame a WAL batch natively; None when the native lib is absent."""
    lib = _load()
    if lib is None or not records:
        return None if lib is None else b""
    n, kinds, refs, idxs, terms, offs, lens, blob = _pack_arrays(records)
    bound = lib.wal_frame_bound(
        kinds.ctypes.data_as(ctypes.c_char_p), lens.ctypes.data, n
    )
    out = ctypes.create_string_buffer(bound)
    w = lib.wal_frame_batch(
        kinds.ctypes.data_as(ctypes.c_char_p),
        refs.ctypes.data,
        idxs.ctypes.data,
        terms.ctypes.data,
        offs.ctypes.data,
        lens.ctypes.data,
        n,
        blob,
        1 if compute_crc else 0,
        ctypes.cast(out, ctypes.c_void_p),
        bound,
    )
    if w < 0:
        return None
    return out.raw[:w]


_SYNC_MODES = {"none": 0, "datasync": 1, "sync": 2}


def write_batch(
    records: List[Record], fd: int, sync_method: str,
    compute_crc: bool = True,
) -> Optional[Tuple[int, int]]:
    """Frame + write + fsync a whole WAL batch natively against ``fd``
    (one call, no Python-side byte assembly; the GIL is released for
    the duration). Returns ``(bytes_written, fsync_wait_ns)``; None
    when the native lib is absent, the batch is malformed, or the sync
    method is unknown (callers fall back to the Python path). Raises
    OSError (errno preserved) on write/fsync failure — fsync failure
    poisons the file exactly as the Python path's rule demands."""
    lib = _load()
    mode = _SYNC_MODES.get(sync_method)
    if lib is None or mode is None:
        return None
    if not records:
        return (0, 0)
    n, kinds, refs, idxs, terms, offs, lens, blob = _pack_arrays(records)
    fsync_ns = ctypes.c_longlong(0)
    w = lib.wal_write_batch(
        kinds.ctypes.data_as(ctypes.c_char_p),
        refs.ctypes.data,
        idxs.ctypes.data,
        terms.ctypes.data,
        offs.ctypes.data,
        lens.ctypes.data,
        n,
        blob,
        1 if compute_crc else 0,
        fd,
        mode,
        ctypes.byref(fsync_ns),
    )
    if w <= -1000:
        err = -(w + 1000)
        raise OSError(err, os.strerror(err))
    if w < 0:
        return None
    return int(w), int(fsync_ns.value)


def crc32(data: bytes) -> Optional[int]:
    lib = _load()
    if lib is None:
        return None
    return int(lib.wal_crc32(data, len(data)))


# -- hot-loop runtime bindings (rt_native.so) -------------------------------

# number of ring item classes (ra_tpu.protocol RC_* codes)
N_CLASSES = 4


def classify(codes, n: int) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Partition ``n`` drained ring items by their class-code sidecar
    (``codes``: a bytes/bytearray of length >= n). Returns ``(idx,
    counts)`` — ``idx`` holds the item indexes grouped by class in
    arrival order, class k occupying ``idx[counts[:k].sum() :
    +counts[k]]`` — or None when the native lib is absent or a code is
    out of range (caller falls back to the Python loop)."""
    lib = _load_rt()
    if lib is None or n <= 0:
        return None
    idx = np.empty(n, np.int32)
    counts = np.empty(N_CLASSES, np.int32)
    rc = lib.rt_classify(
        codes if isinstance(codes, bytes) else bytes(codes[:n]),
        n,
        N_CLASSES,
        idx.ctypes.data,
        counts.ctypes.data,
    )
    if rc < 0:
        return None
    return idx, counts


def pack_mbox(packed: np.ndarray, cols, vals, rows: np.ndarray) -> bool:
    """Scatter per-message field values into the packed int32 mailbox:
    ``packed[rows[f], cols[k]] = vals[k * len(rows) + f]`` — one
    GIL-released call for the whole message class. ``vals`` is the
    flat row-major int64 value list (len(cols) * len(rows)); ``rows``
    the int32 mailbox row indexes. Returns False when the native lib
    is absent or the scatter is out of bounds (caller falls back to
    the columnwise numpy stores)."""
    lib = _load_rt()
    if lib is None:
        return False
    cols_a = np.asarray(cols, np.int32)
    vals_a = np.asarray(vals, np.int64)
    n = len(cols_a)
    if n == 0:
        return True
    if len(vals_a) != n * len(rows) or not packed.flags.c_contiguous:
        return False
    rc = lib.rt_pack_mbox(
        vals_a.ctypes.data,
        cols_a.ctypes.data,
        n,
        rows.ctypes.data,
        len(rows),
        packed.ctypes.data,
        packed.shape[0],
        packed.shape[1],
    )
    return rc == 0
