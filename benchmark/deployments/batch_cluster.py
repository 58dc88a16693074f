"""A 3-node cluster on one chip: three started ``BatchCoordinator``s in
one process (a chip belongs to one process), each with its own ``Wal`` +
``SegmentWriter`` + ``TableRegistry``, two-stage loops, ``warm_steps()``
before ``start()``, leaders spread ``g mod nodes``, in-process transport
with no injected delay.

The recipe is a copy of ``chip_smoke.phase_cluster`` and
``bench.wal_storage`` (the originals stay where they are; PERF.md lists
them for deletion), with program defaults everywhere: none of
``bench.py``'s constants is carried over. What differs from the copy:
the logs are opened from a few threads, because on a network filesystem
the four directory operations each ``Log`` makes are latency and not
work, and the data directory is removed on close.
"""

import concurrent.futures
import os
import shutil
import statistics
import subprocess
import time

import numpy as np

MEMORY_FS = ("tmpfs", "ramfs", "devtmpfs")
LOG_OPEN_THREADS = 16
REMOVE_PROCESSES = 16


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


def pick_data_base(candidates) -> str:
    """The first writable candidate that is not memory-backed; the first
    writable one if all are (the run then says so: its fsyncs prove
    nothing)."""
    writable = [c for c in candidates
                if c and os.path.isdir(c) and os.access(c, os.W_OK)]
    if not writable:
        raise RuntimeError(f"no writable data directory among {candidates}")
    for c in writable:
        if fs_type(c) not in MEMORY_FS:
            return c
    return writable[0]


def wait_for(done, budget_s: float, what: str, poll_s: float = 0.02) -> float:
    """Poll ``done`` (sparingly: the poller shares the interpreter lock
    with the coordinators' threads) until it holds; seconds waited."""
    t0 = time.monotonic()
    while not done():
        if time.monotonic() - t0 > budget_s:
            raise TimeoutError(f"{what} not reached in {budget_s:.0f} s")
        time.sleep(poll_s)
    return time.monotonic() - t0


class Cluster:
    """The deployment as generators, references and metric readers see
    it: group names, coordinators, where each group's leader is, and
    snapshots of every counter and histogram the program keeps."""

    def __init__(self, config: dict, machine_factory, data_bases, say):
        from ra_tpu import leaderboard, obs
        from ra_tpu.log.log import Log
        from ra_tpu.log.segment_writer import SegmentWriter
        from ra_tpu.log.tables import TableRegistry
        from ra_tpu.log.wal import Wal
        from ra_tpu.ops import consensus as C
        from ra_tpu.protocol import ElectionTimeout
        from ra_tpu.runtime.coordinator import BatchCoordinator

        self._leaderboard = leaderboard
        self._obs = obs
        self.groups = groups = int(config["groups"])
        self.replicas = int(config["replicas"])
        nodes = int(config["nodes"])
        if nodes != self.replicas:
            raise ValueError("batch_cluster places one replica of every "
                             "group on every node: nodes must equal replicas")
        self.names = [f"g{g}" for g in range(groups)]
        self.cluster_names = [f"bench_{n}" for n in self.names]
        self.coords = []
        self.storage = []
        self.data_dir = None
        timing = {}
        try:
            base = pick_data_base(data_bases)
            os.makedirs(base, exist_ok=True)
            # (a fixed name would let a crashed run's logs be recovered
            # into this one's groups)
            self.data_dir = os.path.join(
                base, f"ra_benchmark_data.{os.getpid()}")
            shutil.rmtree(self.data_dir, ignore_errors=True)
            os.makedirs(self.data_dir)
            self.disk = {"data_dir": self.data_dir,
                         "fs_type": fs_type(self.data_dir),
                         "fsync_median_ms": fsync_median_ms(self.data_dir)}
            say("disk", memory_backed=self.disk["fs_type"] in MEMORY_FS,
                **self.disk)

            t0 = time.perf_counter()
            lease = bool(config.get("lease", False))
            self.coords = [
                BatchCoordinator(f"bench{i}", capacity=groups,
                                 num_peers=self.replicas, lease=lease)
                for i in range(nodes)
            ]
            self._by_node = {c.name: c for c in self.coords}
            for c in self.coords:
                d = os.path.join(self.data_dir, c.name)
                tables = TableRegistry()
                sw = SegmentWriter(os.path.join(d, "data"), tables,
                                   c.wal_notify)
                w = Wal(os.path.join(d, "wal"), tables, c.wal_notify,
                        segment_writer=sw)
                # the coordinator's bulk channel for written events: one
                # lock round per fsync batch (as bench.wal_storage wires it)
                w.notify_many = c.wal_notify_many
                self.storage.append((tables, w, sw, d))
            members = [[(n, c.name) for c in self.coords] for n in self.names]
            with concurrent.futures.ThreadPoolExecutor(LOG_OPEN_THREADS) as pool:
                for (tables, w, _sw, d), c in zip(self.storage, self.coords):
                    logs = list(pool.map(
                        lambda n, d=d, tables=tables, w=w: Log(
                            n, os.path.join(d, "data", n), tables, w),
                        self.names))
                    c.add_groups([
                        (n, self.cluster_names[g], members[g],
                         machine_factory(), logs[g])
                        for g, n in enumerate(self.names)
                    ])
            timing["logs_s"] = time.perf_counter() - t0
            # every width a started loop dispatches compiles BEFORE the
            # loops start: a compile on a started coordinator stalls it
            # under the command watchdog and the live election timers
            t0 = time.perf_counter()
            self.programs_warmed = sum(c.warm_steps() for c in self.coords)
            timing["warm_s"] = time.perf_counter() - t0
            for c in self.coords:
                c.start()

            t0 = time.perf_counter()
            for i, c in enumerate(self.coords):
                c.deliver_many([((self.names[g], c.name), ElectionTimeout(),
                                 None) for g in range(i, groups, nodes)])
            lead = [self.coords[g % nodes] for g in range(groups)]
            wait_for(lambda: all(lead[g].by_name[self.names[g]].role
                                 == C.R_LEADER for g in range(groups)),
                     300, f"{groups} leaders")
            # the election noops commit and apply everywhere before traffic
            wait_for(lambda: all(c._applied_np[:groups].min() >= 1
                                 for c in self.coords),
                     120, "election noops applied")
            timing["election_s"] = time.perf_counter() - t0
            say("cluster", coordinators=nodes, groups=groups,
                replicas=self.replicas, lease=lease,
                programs_warmed=self.programs_warmed, **timing)
        except BaseException:
            self.close()
            raise

    # -- what clients and references read -----------------------------------

    def node_names(self):
        return [c.name for c in self.coords]

    def coord(self, node_name: str):
        return self._by_node[node_name]

    def leader_node(self, g: int) -> str:
        """The node clients send group ``g``'s traffic to: the
        leaderboard's entry, as ``api`` routes."""
        sid = self._leaderboard.lookup_leader(self.cluster_names[g])
        return sid[1] if sid else self.coords[g % len(self.coords)].name

    def replica_states(self, g: int):
        """Machine state of group ``g`` on every node, in node order."""
        return [c.by_name[self.names[g]].machine_state for c in self.coords]

    def applied(self) -> np.ndarray:
        """(nodes, groups) last-applied indexes."""
        return np.stack([c._applied_np[:self.groups].copy()
                         for c in self.coords])

    def settle(self, budget_s: float) -> bool:
        """Replicas apply on the commit index their next AER or heartbeat
        carries: wait until all nodes have applied the same (or the
        budget ends). True if they agree."""
        deadline = time.monotonic() + budget_s
        while True:
            a = self.applied()
            if (a == a[0]).all():
                return True
            if time.monotonic() > deadline:
                return False
            time.sleep(0.1)

    def term_sum(self) -> int:
        return sum(c.by_name[n].term for c in self.coords for n in self.names)

    # -- counters and spans, as snapshots ------------------------------------

    def snapshot(self) -> dict:
        """Every counter and histogram the per-layer readers use, summed
        over the coordinators, at one instant."""
        obs = self._obs
        snap = {
            "t": time.monotonic(),
            "steps": sum(c.steps for c in self.coords),
            "sub_steps": sum(c.sub_steps for c in self.coords),
            "detector_errors": sum(c.detector_errors for c in self.coords),
            "coordinator": {}, "wal": {}, "wave": {}, "commit": {},
        }
        for c in self.coords:
            for k, v in c.counters.to_dict().items():
                snap["coordinator"][k] = snap["coordinator"].get(k, 0) + v
        for _t, w, _sw, _d in self.storage:
            for k, v in w.counter.to_dict().items():
                snap["wal"][k] = snap["wal"].get(k, 0) + v
        for family, phases in (("wave", obs.WAVE_PHASES),
                               ("commit", obs.COMMIT_STAGES)):
            for name, _help in phases:
                arr, n, total = None, 0, 0
                for c in self.coords:
                    h = obs.histograms().fetch((family, c.name, name))
                    if h is None:
                        continue
                    arr = h.arr.copy() if arr is None else arr + h.arr
                    n += h.n
                    total += h.total
                if arr is not None:
                    snap[family][name] = (arr, n, total)
        return snap

    def events_between(self, t0: float, t1: float):
        """Flight-recorder events stamped inside [t0, t1) on
        ``time.monotonic()``, and whether the ring wrapped inside it."""
        evs = self._obs.flight_recorder().events()
        inside = [e for e in evs if t0 <= e["ts"] < t1]
        wrapped = bool(evs) and evs[0]["ts"] > t0 and \
            len(evs) >= self._obs.flight_recorder().capacity
        return inside, wrapped

    # -- teardown --------------------------------------------------------------

    def close(self) -> list:
        """Stop the loops, close the storage, remove the data directory.
        Returns the names of coordinator threads that outlived
        ``stop()``."""
        t0 = time.perf_counter()
        # the three nodes stop together, as at a process's end: stopped
        # one after the other, each survivor's detector sees a node go
        # down and arms one timer thread per group that node led
        for c in self.coords:
            c.running = False
        for c in self.coords:
            c.stop()
        t1 = time.perf_counter()
        for _tables, w, sw, _d in self.storage:
            w.close()
            sw.close()
        self._leaderboard.clear()
        threads = [t for c in self.coords
                   for t in (c._step_thread, c._egress_thread,
                             c._sender_thread, c._detector) if t is not None]
        deadline = time.monotonic() + 30  # stop() joins each for 5 s only
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))
        alive = [t.name for t in threads if t.is_alive()]
        t2 = time.perf_counter()
        self.coords, self.storage = [], []
        if self.data_dir:
            _remove_tree(self.data_dir)
            self.data_dir = None
        self.close_seconds = {"stop_s": t1 - t0, "storage_s": t2 - t1,
                              "remove_s": time.perf_counter() - t2}
        return alive


def _remove_tree(path: str) -> None:
    """Remove the data directory with a few ``rm -rf`` processes, each
    given a share of the groups' directories: 30,720 logs are 180,000
    directories, a minute of ``shutil.rmtree`` under the interpreter
    lock, and on a network filesystem every unlink is latency."""
    subs = []
    for node in os.listdir(path):
        data = os.path.join(path, node, "data")
        if os.path.isdir(data):
            subs += [os.path.join(data, n) for n in os.listdir(data)]
    procs = [subprocess.Popen(["rm", "-rf", "--", *subs[i::REMOVE_PROCESSES]])
             for i in range(REMOVE_PROCESSES) if subs[i::REMOVE_PROCESSES]]
    for p in procs:
        p.wait()
    shutil.rmtree(path, ignore_errors=True)
