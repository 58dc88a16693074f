"""A closed loop with a fixed window of commands in flight per group
(``ra_bench``'s pipelining clients, src/ra_bench.erl:18-19,39-40): each
group's next command is sent when its reply arrives.

Parameters (the traffic file): ``shards`` client threads, each owning
the groups ``g mod shards``; ``in_flight_per_group``; ``payload_bytes``
per command, the first 8 a header drawn from the seed, group and
sequence number (distinct per command), the rest filler;
``reply_timeout_s`` after which a command with no reply fails and its
group is retired; ``max_resends`` of one command after redirects and
rejects.

A command is the message ``api.process_command`` builds
(``Command(kind=USR, reply_mode="await_consensus", from_ref=<callable>,
ts=...)``), handed in bulk per leader node (``deliver_many``), rerouted
by the reply's hint or the leaderboard on a ``redirect``.

History for the reference (``ra_bench``): per group the count and the
header sum of acknowledged commands, and the headers whose outcome is
unknown (a ``maybe`` reply, a timeout).
"""

import threading
import time
from collections import deque

MASK64 = (1 << 64) - 1
HEADER_BITS = 48
RESEND_DELAY_S = 0.01


def header_of(seed: int, g: int, seq: int) -> int:
    """A 48-bit odd number, distinct per (group, sequence) under a seed
    (splitmix64's finalizer over a linear mix)."""
    x = (seed * 0x9E3779B97F4A7C15 + g * 0xBF58476D1CE4E5B9
         + seq * 0x94D049BB133111EB) & MASK64
    x ^= x >> 30
    x = (x * 0xBF58476D1CE4E5B9) & MASK64
    x ^= x >> 27
    return (x >> (64 - HEADER_BITS)) | 1


class Generator:
    def __init__(self, cluster, config: dict, params: dict, seed: int, say):
        import numpy as np

        from ra_tpu.protocol import USR, Command

        self._Command, self._USR = Command, USR
        self.cluster = cluster
        self.seed = seed
        self.say = say
        self.shards = int(params["shards"])
        self.window = int(params["in_flight_per_group"])
        self.timeout_ns = int(float(params["reply_timeout_s"]) * 1e9)
        self.max_resends = int(params["max_resends"])
        self.filler = np.random.default_rng(seed).bytes(
            int(params["payload_bytes"]) - 8)
        G = cluster.groups
        self.G = G
        self.names = cluster.names
        self.node_of = [cluster.leader_node(g) for g in range(G)]
        self.nodes = {n: cluster.coord(n) for n in cluster.node_names()}
        # per group: the reference's fold, and the command(s) in flight
        self.count = [0] * G
        self.sum = [0] * G
        self.unknown = {}  # g -> [header, ...] of unknown outcome
        self.next_seq = [0] * G
        self.retired = set()
        # in flight per shard, keyed (g, slot): [header, t_send_ns, resends]
        self.flight = [{} for _ in range(self.shards)]
        self._done = [deque() for _ in range(self.shards)]
        self._wake = [threading.Event() for _ in range(self.shards)]
        self._ops = [([], [], []) for _ in range(self.shards)]  # send, done, ok
        self.redirects = [0] * self.shards
        self.rejects = [0] * self.shards
        self._stopping = False
        self._drain_by_ns = 0
        self._threads = []
        self._cbs = {}
        for g in range(G):
            for slot in range(self.window):
                self._cbs[(g, slot)] = self._callback(g, slot)

    def _callback(self, g: int, slot: int):
        done, wake = self._done[g % self.shards], self._wake[g % self.shards]
        clock = time.monotonic_ns

        def reply_to(reply):
            # (runs on a coordinator's thread: note the time, hand over)
            done.append((g, slot, reply, clock()))
            if not wake.is_set():
                wake.set()

        return reply_to

    # -- set-up -------------------------------------------------------------

    def load(self) -> None:
        """Nothing to load: a ``ra_bench`` group starts empty."""

    def start(self) -> None:
        for k in range(self.shards):
            t = threading.Thread(target=self._shard, args=(k,),
                                 name=f"bench-gen-{k}", daemon=True)
            self._threads.append(t)
            t.start()

    # -- one shard ------------------------------------------------------------

    def _command(self, g: int, slot: int, header: int, resends: int):
        now = time.monotonic_ns()
        flight = self.flight[g % self.shards]
        prev = flight.get((g, slot))
        # a resend keeps the first send's time: the client has waited since
        flight[(g, slot)] = [header, prev[1] if prev else now, resends]
        return ((self.names[g], self.node_of[g]),
                self._Command(kind=self._USR,
                              data=header.to_bytes(8, "little") + self.filler,
                              reply_mode="await_consensus",
                              from_ref=self._cbs[(g, slot)], ts=now),
                None)

    def _fresh(self, g: int, slot: int):
        seq = self.next_seq[g]
        self.next_seq[g] = seq + 1
        self.flight[g % self.shards].pop((g, slot), None)
        return self._command(g, slot, header_of(self.seed, g, seq), 0)

    def _send(self, msgs) -> None:
        by_node = {}
        for m in msgs:
            by_node.setdefault(m[0][1], []).append(m)
        for node, batch in by_node.items():
            self.nodes[node].deliver_many(batch)

    def _fail(self, k: int, g: int, slot: int, t_ns: int, why: str) -> None:
        header, t_send, _r = self.flight[k].pop((g, slot))
        self.unknown.setdefault(g, []).append(header)
        send, done, ok = self._ops[k]
        send.append(t_send)
        done.append(t_ns)
        ok.append(False)
        if why != "maybe":
            self.retired.add(g)
            self.say("generator", failed_group=self.names[g], why=why)

    def _shard(self, k: int) -> None:
        done_q, wake = self._done[k], self._wake[k]
        send, done, ok = self._ops[k]
        flight = self.flight[k]
        mine = range(k, self.G, self.shards)
        self._send([self._fresh(g, s) for g in mine
                    for s in range(self.window)])
        later = []  # (due_ns, g, slot): resends after a reject or a blind redirect
        last_scan = time.monotonic_ns()
        while True:
            wake.wait(0.02 if later else 0.25)
            wake.clear()
            out = []
            while done_q:
                g, slot, reply, t_ns = done_q.popleft()
                fl = flight.get((g, slot))
                if fl is None or g in self.retired:
                    continue  # a reply to a command already given up
                tag = reply[0]
                if tag == "ok":
                    self.count[g] += 1
                    self.sum[g] += fl[0]
                    send.append(fl[1])
                    done.append(t_ns)
                    ok.append(True)
                    if self._stopping:
                        del flight[(g, slot)]
                    else:
                        out.append(self._fresh(g, slot))
                elif tag == "maybe":
                    # deposed with the entry in its log: it may still commit
                    self._fail(k, g, slot, t_ns, "maybe")
                    if not self._stopping:
                        out.append(self._fresh(g, slot))
                elif tag in ("redirect", "reject"):
                    if fl[2] >= self.max_resends:
                        self._fail(k, g, slot, t_ns, f"{tag} x{fl[2]}")
                        continue
                    if tag == "redirect":
                        self.redirects[k] += 1
                        hint = reply[1]
                        if hint is not None:
                            self.node_of[g] = hint[1]
                            out.append(self._command(g, slot, fl[0], fl[2] + 1))
                            continue
                    else:
                        self.rejects[k] += 1
                    later.append((t_ns + int(RESEND_DELAY_S * 1e9), g, slot))
                else:
                    self._fail(k, g, slot, t_ns, f"reply {reply!r}")
            now = time.monotonic_ns()
            if later:
                due = [x for x in later if x[0] <= now]
                later = [x for x in later if x[0] > now]
                for _due, g, slot in due:
                    fl = flight.get((g, slot))
                    if fl is None:
                        continue
                    self.node_of[g] = self.cluster.leader_node(g)
                    out.append(self._command(g, slot, fl[0], fl[2] + 1))
            if out:
                self._send(out)
            if now - last_scan > 1_000_000_000:
                last_scan = now
                for (g, slot), fl in list(flight.items()):
                    if now - fl[1] > self.timeout_ns:
                        self._fail(k, g, slot, now, "no reply in "
                                   f"{self.timeout_ns / 1e9:.0f} s")
            if self._stopping:
                if now > self._drain_by_ns:
                    for g, slot in list(flight):
                        self._fail(k, g, slot, now, "no reply by the end "
                                   "of the drain")
                if not flight:
                    return

    # -- the end ----------------------------------------------------------------

    def stop(self, drain_budget_s: float) -> None:
        """Send nothing new; wait for the replies still owed, and give
        up those that have not come inside the budget (unknown outcome)."""
        self._drain_by_ns = time.monotonic_ns() + int(drain_budget_s * 1e9)
        self._stopping = True
        for w in self._wake:
            w.set()
        for t in self._threads:
            t.join(drain_budget_s + 10)
        left = [t.name for t in self._threads if t.is_alive()]
        if left:
            raise RuntimeError(f"generator threads did not end: {left}")

    def issued(self, t0_ns: int, t1_ns: int) -> dict:
        """Resends since the start (warm-up and drain included)."""
        return {"redirects": sum(self.redirects), "rejects": sum(self.rejects)}

    def history(self) -> dict:
        send = [t for s, _d, _o in self._ops for t in s]
        done = [t for _s, d, _o in self._ops for t in d]
        ok = [x for _s, _d, o in self._ops for x in o]
        return {
            "ops": {"write": {"t_send": send, "t_done": done, "ok": ok}},
            "groups": self.G,
            "count": list(self.count),
            "sum": list(self.sum),
            "unknown": {g: list(h) for g, h in self.unknown.items()},
            "retired": sorted(self.retired),
        }
