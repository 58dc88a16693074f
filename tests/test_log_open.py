"""Opening a server log (docs/INTERNALS.md §3, "Opening a log").

A ``Log`` whose constructor made its server directory finds it empty, so
nothing is listed, recovered or synced: five ``mkdir``s and no other
metadata call. A directory that was already there takes the whole
recovery: compaction debris cleared, spools swept, segments indexed, the
segment directory synced.
"""

from __future__ import annotations

import builtins
import collections
import os
import pickle
import threading

import pytest

from ra_tpu.log.log import Log
from ra_tpu.log.segment import SegmentWriterHandle
from ra_tpu.log.segments import SegmentSet
from ra_tpu.log.snapshot import CHECKPOINT, RECOVERY, SNAPSHOT
from ra_tpu.log.tables import TableRegistry
from ra_tpu.log.wal import Wal

OS_CALLS = ("mkdir", "makedirs", "listdir", "scandir", "stat", "lstat",
            "open", "fsync", "unlink", "rmdir", "symlink")
SEGMENT = "0000000000000001.segment"


class CountingOs:
    """Counts the metadata calls the calling thread makes through ``os``
    (and file opens through ``open``) while it is entered; the directories
    opened with ``os.open`` are the ones ``sync_dir`` fsyncs."""

    def __init__(self, monkeypatch):
        self.mp = monkeypatch
        self.calls = collections.Counter()
        self.opened = []

    def _wrap(self, name, fn, record=False):
        me = threading.get_ident()

        def counted(*a, **k):
            if threading.get_ident() == me:
                self.calls[name] += 1
                if record:
                    self.opened.append(a[0])
            return fn(*a, **k)

        return counted

    def __enter__(self):
        for name in OS_CALLS:
            self.mp.setattr(os, name, self._wrap(name, getattr(os, name),
                                                 record=name == "open"))
        self.mp.setattr(builtins, "open", self._wrap("open", builtins.open))
        return self

    def __exit__(self, *exc):
        self.mp.undo()


def _wal(tmp_path):
    return Wal(str(tmp_path / "wal"), TableRegistry(), lambda *a: None,
               threaded=False, sync_method="none")


def _write_segment(segdir):
    w = SegmentWriterHandle(os.path.join(segdir, SEGMENT), max_count=8)
    for i in range(1, 5):
        w.append(i, 1 if i < 3 else 2, pickle.dumps(i))
    w.sync()
    w.close()


def _leave_debris(server_dir):
    """A crashed log's directories with its leftovers: a minor-compaction
    temp, a major pass's marker with its temp (a pre-rename crash), a
    snapshot write spool, beside one real segment."""
    segdir = os.path.join(server_dir, "segments")
    os.makedirs(segdir)
    for kind in (SNAPSHOT, CHECKPOINT, RECOVERY):
        os.makedirs(os.path.join(server_dir, kind))
    _write_segment(segdir)
    with open(os.path.join(segdir, SEGMENT + ".compacting"), "wb") as f:
        f.write(b"partial")
    with open(os.path.join(segdir, "0000000000000002.compaction_group"),
              "wb") as f:
        pickle.dump(["0000000000000002.segment",
                     "0000000000000003.segment"], f)
    with open(os.path.join(segdir, "0000000000000002.compacting"), "wb") as f:
        f.write(b"partial")
    os.makedirs(os.path.join(server_dir, SNAPSHOT,
                             "0000000000000001_0000000000000004.writing"))


@pytest.mark.parametrize("state", ["fresh", "existing_empty", "existing_debris"])
def test_log_open(tmp_path, monkeypatch, state):
    server_dir = str(tmp_path / "data" / "u1")
    os.makedirs(tmp_path / "data")
    if state == "existing_empty":
        os.makedirs(server_dir)
    elif state == "existing_debris":
        _leave_debris(server_dir)
    wal = _wal(tmp_path)
    with CountingOs(monkeypatch) as os_calls:
        log = Log("u1", server_dir, wal.tables, wal)
    segdir = os.path.join(server_dir, "segments")
    snapdir = os.path.join(server_dir, SNAPSHOT)
    if state == "existing_debris":
        # the whole recovery: every listing, the segment directory
        # synced whether or not recovery found anything to finish, the
        # swept spool's directory synced
        assert os_calls.calls["mkdir"] == 5
        assert os_calls.calls["listdir"] == 6
        assert {segdir, snapdir} <= set(os_calls.opened)
        assert os.listdir(segdir) == [SEGMENT]
        assert os.listdir(snapdir) == []
        assert log.segs.refs == {SEGMENT: (1, 4)}
        assert log.last_index_term() == (4, 2)
        assert log.fetch(3).cmd == 3
    else:
        # every directory below server_dir is made by this open, so none
        # is listed, recovered or synced
        assert dict(os_calls.calls) == {"mkdir": 5}
        assert log.last_index_term() == (0, 0)
        assert log.snapshot_meta() is None
        assert log.segs.refs == {}
    fresh = state == "fresh"
    assert wal.counter.get("log_opens_fresh") == int(fresh)
    assert wal.counter.get("log_opens_recovered") == int(not fresh)
    assert sorted(os.listdir(server_dir)) == sorted(
        ["segments", SNAPSHOT, CHECKPOINT, RECOVERY])
    log.close()


def test_readonly_segment_set_over_existing_dir_changes_nothing(tmp_path):
    server_dir = str(tmp_path / "u1")
    _leave_debris(server_dir)
    segdir = os.path.join(server_dir, "segments")
    before = sorted(os.listdir(segdir))
    segs = SegmentSet(segdir, index_mode="binary", readonly=True)
    try:
        assert segs.refs == {SEGMENT: (1, 4)}
        assert segs.fetch(2).cmd == 2
    finally:
        segs.close()
    # a reader's view never runs the owner's crash recovery
    assert sorted(os.listdir(segdir)) == before


def test_log_opens_count_each_kind_once(tmp_path):
    wal = _wal(tmp_path)
    server_dir = str(tmp_path / "data" / "u1")
    Log("u1", server_dir, wal.tables, wal).close()
    assert (wal.counter.get("log_opens_fresh"),
            wal.counter.get("log_opens_recovered")) == (1, 0)
    Log("u1", server_dir, wal.tables, wal).close()
    assert (wal.counter.get("log_opens_fresh"),
            wal.counter.get("log_opens_recovered")) == (1, 1)


def test_log_open_without_wal_counter(tmp_path):
    class BareWal:
        """A test double with no counter row."""

    log = Log("u1", str(tmp_path / "u1"), TableRegistry(), BareWal())
    assert log.last_index_term() == (0, 0)
    log.close()
