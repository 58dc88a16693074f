"""A 3-node cluster on one chip whose nodes talk over loopback TCP:
``batch_cluster``'s recipe (three started ``BatchCoordinator``s in one
process, each with its own ``Wal`` + ``SegmentWriter`` +
``TableRegistry``, ``warm_steps()`` before ``start()``, leaders spread
``g mod nodes``, program defaults everywhere) with one difference: each
coordinator is built with ``tcp=True`` on ``127.0.0.1:<free port>`` and
has a ``NodeRegistry`` of its own, so that no node finds a peer in its
process and every protocol message leaves through a ``TcpTransport``:
one authenticated batch frame a destination a wave, six directed
connections, liveness by the transports' pings. No delay is injected.

What clients, references and metric readers see is ``batch_cluster``'s
``Cluster`` (this one inherits it): the same ``snapshot()`` keys, so a
per-layer metric reads here as it does in the in-process twin.
"""

import concurrent.futures
import errno
import inspect
import os
import shutil
import socket
import time

from benchmark import harness

_bc = harness.load_module("deployments", "batch_cluster")


def free_port(host: str) -> int:
    with socket.socket() as s:
        s.bind((host, 0))
        return s.getsockname()[1]


class Cluster(_bc.Cluster):
    HOST = "127.0.0.1"

    def __init__(self, config: dict, machine_factory, data_bases, say):
        from ra_tpu import leaderboard, obs
        from ra_tpu.log.log import Log
        from ra_tpu.log.segment_writer import SegmentWriter
        from ra_tpu.log.tables import TableRegistry
        from ra_tpu.log.wal import Wal
        from ra_tpu.ops import consensus as C
        from ra_tpu.protocol import ElectionTimeout
        from ra_tpu.runtime.coordinator import BatchCoordinator
        from ra_tpu.runtime.transport import NodeRegistry

        if "tcp" not in inspect.signature(BatchCoordinator.__init__).parameters:
            # (before anything is built: a coordinator that cannot leave
            # its process would wait for an election that never comes)
            raise SystemExit(
                "benchmark: wired_cluster needs a BatchCoordinator that "
                "takes a wire transport (tcp=True); this program's does "
                "not - no result")
        self._leaderboard = leaderboard
        self._obs = obs
        self.groups = groups = int(config["groups"])
        self.replicas = int(config["replicas"])
        nodes = int(config["nodes"])
        if nodes != self.replicas:
            raise ValueError("wired_cluster places one replica of every "
                             "group on every node: nodes must equal replicas")
        self.names = [f"g{g}" for g in range(groups)]
        self.cluster_names = [f"bench_{n}" for n in self.names]
        self.coords = []
        self.storage = []
        self.data_dir = None
        timing = {}
        try:
            base = _bc.pick_data_base(data_bases)
            os.makedirs(base, exist_ok=True)
            self.data_dir = os.path.join(
                base, f"ra_benchmark_data.{os.getpid()}")
            shutil.rmtree(self.data_dir, ignore_errors=True)
            os.makedirs(self.data_dir)
            self.disk = {"data_dir": self.data_dir,
                         "fs_type": _bc.fs_type(self.data_dir),
                         "fsync_median_ms": _bc.fsync_median_ms(self.data_dir)}
            say("disk", memory_backed=self.disk["fs_type"] in _bc.MEMORY_FS,
                **self.disk)

            t0 = time.perf_counter()
            lease = bool(config.get("lease", False))
            ports, taken = [], 0
            while len(ports) < nodes:
                port = free_port(self.HOST)
                try:
                    self.coords.append(BatchCoordinator(
                        f"{self.HOST}:{port}", capacity=groups,
                        num_peers=self.replicas, lease=lease,
                        nodes=NodeRegistry(), tcp=True))
                    ports.append(port)
                except OSError as e:
                    # (taken between the look and the bind, by a
                    # connection's own end: look again)
                    taken += 1
                    if e.errno != errno.EADDRINUSE or taken > 8:
                        raise
            self._by_node = {c.name: c for c in self.coords}
            for i, c in enumerate(self.coords):
                d = os.path.join(self.data_dir, f"node{i}")
                tables = TableRegistry()
                sw = SegmentWriter(os.path.join(d, "data"), tables,
                                   c.wal_notify)
                w = Wal(os.path.join(d, "wal"), tables, c.wal_notify,
                        segment_writer=sw)
                w.notify_many = c.wal_notify_many
                self.storage.append((tables, w, sw, d))
            members = [[(n, c.name) for c in self.coords] for n in self.names]
            with concurrent.futures.ThreadPoolExecutor(
                    _bc.LOG_OPEN_THREADS) as pool:
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
            _bc.wait_for(lambda: all(lead[g].by_name[self.names[g]].role
                                     == C.R_LEADER for g in range(groups)),
                         300, f"{groups} leaders")
            _bc.wait_for(lambda: all(c._applied_np[:groups].min() >= 1
                                     for c in self.coords),
                         120, "election noops applied")
            timing["election_s"] = time.perf_counter() - t0
            say("cluster", coordinators=nodes, groups=groups,
                replicas=self.replicas, lease=lease, transport="tcp",
                ports=ports,
                connections=sum(
                    c.transport.node_alive(o.name)
                    for c in self.coords for o in self.coords if o is not c),
                programs_warmed=self.programs_warmed, **timing)
        except BaseException:
            self.close()
            raise

    def close(self) -> list:
        """As ``batch_cluster``'s, and the names of the transports'
        threads that outlived ``stop()`` (which closes a coordinator's
        transport)."""
        transports = [c.transport for c in self.coords]
        alive = super().close()
        return alive + [t.name for tr in transports for t in tr.threads()]
