"""YCSB's core workload as a closed loop: ``clients`` synchronous
threads, each doing a read or an update with the proportions of the
traffic file, keys drawn from ``request_distribution`` over the
configuration's ``records`` (``zipfian`` with YCSB's constant 0.99, the
popular items scattered over the key space as YCSB's scrambled Zipfian
does, here by a seeded permutation; or ``uniform``), values fresh
records of ``field_count`` x ``field_length`` bytes.

An update is ``api.process_command((group, node), ("put", key, value))``;
a read is ``models.kv.kv_get(api, (group, leader's node), key)`` (a
consistent query, then the log fetch). Key -> group by a seeded hash.
The records are loaded during set-up in pipelined waves.

Every value starts with its writer and sequence number, so that the
reference (``ra_kv``) can tell which put a read returned. History: one
row per put (key, writer, seq, send and reply time, the raft index the
reply gave) and per get (key, send and reply time, the writer and seq of
the value that came back).
"""

import struct
import threading
import time
import zlib

import numpy as np

HEAD = struct.Struct("<IQ")  # writer, sequence number
LOADER = 0  # the writer id of the load phase; clients are 1..clients
CHUNK = 4096  # operations drawn at a time, per client
LOAD_WAVE = 2048


class Generator:
    def __init__(self, cluster, config: dict, params: dict, seed: int, say):
        self.cluster = cluster
        self.say = say
        self.seed = seed
        self.clients = int(params["clients"])
        self.read_share = float(params["read_proportion"])
        if abs(self.read_share + float(params["update_proportion"]) - 1.0) > 1e-9:
            raise ValueError("ycsb: only reads and updates are generated; "
                             "their proportions must add up to 1")
        self.timeout_s = float(params["op_timeout_s"])
        self.value_bytes = int(params["field_count"]) * int(params["field_length"])
        n = self.records = int(config["records"])
        G = cluster.groups
        self.keys = [f"user{i:010d}" for i in range(n)]
        self.group_of = [zlib.crc32(f"{seed}:{k}".encode()) % G
                         for k in self.keys]
        rng = np.random.default_rng(seed)
        self.scatter = rng.permutation(n)  # popularity rank -> key
        dist = params["request_distribution"]
        if dist == "zipfian":
            w = 1.0 / np.arange(1, n + 1) ** float(params["zipfian_constant"])
        elif dist == "uniform":
            w = np.ones(n)
        else:
            raise ValueError(f"ycsb: unknown request_distribution {dist!r}")
        self.cdf = np.cumsum(w / w.sum())
        self._stop = threading.Event()
        self._threads = []
        self._puts = [[] for _ in range(self.clients + 1)]  # [LOADER] = load
        self._gets = [[] for _ in range(self.clients + 1)]
        self.errors = {}

    def _value(self, writer: int, seq: int, rng) -> bytes:
        return HEAD.pack(writer, seq) + rng.bytes(self.value_bytes - HEAD.size)

    # -- set-up: the load phase ----------------------------------------------

    def load(self) -> None:
        """Every record once, in waves of ``LOAD_WAVE`` puts handed in
        bulk per leader node; a wave ends when all its replies are in."""
        from ra_tpu.protocol import USR, Command

        rng = np.random.default_rng(self.seed + 1)
        rows = self._puts[LOADER]
        t0 = time.perf_counter()
        for lo in range(0, self.records, LOAD_WAVE):
            todo = list(range(lo, min(lo + LOAD_WAVE, self.records)))
            for _attempt in range(20):
                got = {}
                sent = {}
                by_node = {}
                for i in todo:
                    g = self.group_of[i]
                    node = self.cluster.leader_node(g)
                    sent[i] = time.monotonic_ns()
                    by_node.setdefault(node, []).append((
                        (self.cluster.names[g], node),
                        Command(kind=USR,
                                data=("put", self.keys[i],
                                      self._value(LOADER, i, rng)),
                                reply_mode="await_consensus",
                                from_ref=lambda r, i=i: got.__setitem__(
                                    i, (r, time.monotonic_ns())),
                                ts=sent[i]),
                        None))
                for node, batch in by_node.items():
                    self.cluster.coord(node).deliver_many(batch)
                deadline = time.monotonic() + 60
                while len(got) < len(todo) and time.monotonic() < deadline:
                    time.sleep(0.01)
                again = []
                for i in todo:
                    reply, t_done = got.get(i, (("timeout",), 0))
                    if reply[0] == "ok" and reply[1][0] == "ok":
                        rows.append((i, LOADER, i, sent[i], t_done,
                                     reply[1][1], True))
                    elif reply[0] in ("redirect", "reject"):
                        again.append(i)  # never appended: safe to send again
                    else:
                        raise RuntimeError(f"ycsb load: {self.keys[i]}: "
                                           f"{reply!r}")
                todo = again
                if not todo:
                    break
                time.sleep(0.05)
            if todo:
                raise RuntimeError(f"ycsb load: {len(todo)} records never "
                                   f"accepted")
        self.say("load", records=self.records, value_bytes=self.value_bytes,
                 seconds=time.perf_counter() - t0)

    # -- the clients -------------------------------------------------------------

    def start(self) -> None:
        for w in range(1, self.clients + 1):
            t = threading.Thread(target=self._client, args=(w,),
                                 name=f"ycsb-{w}", daemon=True)
            self._threads.append(t)
            t.start()

    def _client(self, writer: int) -> None:
        from ra_tpu import api
        from ra_tpu.models.kv import kv_get

        rng = np.random.default_rng([self.seed, writer])
        cluster, names, keys, group_of = (self.cluster, self.cluster.names,
                                          self.keys, self.group_of)
        puts, gets = self._puts[writer], self._gets[writer]
        clock = time.monotonic_ns
        seq = 0
        while not self._stop.is_set():
            reads = rng.random(CHUNK) < self.read_share
            picks = self.scatter[np.searchsorted(self.cdf, rng.random(CHUNK))]
            for is_read, i in zip(reads.tolist(), picks.tolist()):
                if self._stop.is_set():
                    return
                g = group_of[i]
                sid = (names[g], cluster.leader_node(g))
                if is_read:
                    t0 = clock()
                    try:
                        value = kv_get(api, sid, keys[i], timeout=self.timeout_s)
                    except Exception as e:  # noqa: BLE001 — a failed operation
                        value = None
                        self._error(e)
                    t1 = clock()
                    if value is None:
                        gets.append((i, t0, t1, -1, -1, False))
                    else:
                        w, s = HEAD.unpack_from(value)
                        gets.append((i, t0, t1, w, s, True))
                else:
                    seq += 1
                    value = self._value(writer, seq, rng)
                    t0 = clock()
                    try:
                        reply, _leader = api.process_command(
                            sid, ("put", keys[i], value), timeout=self.timeout_s)
                        index = reply[1] if reply[0] == "ok" else -1
                    except Exception as e:  # noqa: BLE001 — a failed operation
                        index = -1
                        self._error(e)
                    puts.append((i, writer, seq, t0, clock(), index, index >= 0))

    def _error(self, e: Exception) -> None:
        key = f"{type(e).__name__}: {e}"[:120]
        self.errors[key] = self.errors.get(key, 0) + 1

    # -- the end -------------------------------------------------------------------

    def stop(self, drain_budget_s: float) -> None:
        """Each client ends after the call it is in (a call is bounded by
        ``op_timeout_s``)."""
        self._stop.set()
        for t in self._threads:
            t.join(drain_budget_s + self.timeout_s)
        left = [t.name for t in self._threads if t.is_alive()]
        if left:
            raise RuntimeError(f"ycsb clients did not end: {left}")
        if self.errors:
            self.say("generator", errors=self.errors)

    def issued(self, t0_ns: int, t1_ns: int) -> dict:
        """Consistent reads that ended inside the window."""
        n = sum(1 for rows in self._gets for r in rows if t0_ns <= r[2] < t1_ns)
        return {"consistent_reads": n}

    def history(self) -> dict:
        puts = [r for rows in self._puts for r in rows]
        gets = [r for rows in self._gets for r in rows]

        def cols(rows, names):
            return {n: [r[k] for r in rows] for k, n in enumerate(names)}

        p = cols(puts, ("key", "writer", "seq", "t_send", "t_done", "index", "ok"))
        g = cols(gets, ("key", "t_send", "t_done", "writer", "seq", "ok"))
        return {
            "ops": {"write": {k: p[k] for k in ("t_send", "t_done", "ok")},
                    "read": {k: g[k] for k in ("t_send", "t_done", "ok")}},
            "keys": self.keys, "group_of": self.group_of,
            "puts": p, "gets": g,
        }
