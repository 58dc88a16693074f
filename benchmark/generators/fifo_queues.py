"""Publishers with a confirm window and credit-windowed consumers on a
fleet of fifo queues (upstream's ``test/ra_fifo.erl`` clients, the
precursor of RabbitMQ's quorum queues): every queue has one consumer
attached, a few are hot.

Parameters (the traffic file): ``shards`` client threads, each owning
the queues ``g mod shards``, their publishers and their consumers;
``hot_queues`` drawn from the seed (``draw_hot``), the others idle;
``confirm_window`` enqueues a publisher keeps unconfirmed, the next sent
on each confirm;
``prefetch``, the consumer's credit at ``checkout``; ``reply_timeout_s``
after which an operation with no reply fails and its queue is retired;
``max_resends`` of one command after redirects and rejects. The
configuration gives ``body_bytes``: a body is its publisher (the queue's
number) and sequence number, then filler, so distinct per message.

``load`` attaches the consumer of EVERY queue (``("checkout", consumer,
prefetch)``, outside the window) and sets the coordinators'
``send_msg_cb`` to a sink that only appends the delivery to the owning
shard's inbox (it runs on the egress thread under the state lock). In
the window a consumer sends ``("settle", consumer, msg_id)`` for every
delivery as it arrives. Commands are the messages ``api.process_command``
builds, handed in bulk per leader node (``deliver_many``), rerouted by
the reply's hint or the leaderboard on a ``redirect``. An enqueue of
unknown outcome (a ``maybe`` reply, a timeout) is never sent again; a
settle is idempotent and is.

Operation kinds: ``write`` = enqueue, call -> confirm; ``settle`` =
settle, send -> acknowledged. History for the reference (``ra_fifo``),
per hot queue: the confirmed (sequence, message id) pairs, the enqueues
of unknown outcome, every delivery in arrival order (consumer, message
id, publisher, sequence, time), the acknowledged settles (message id,
time) and the settles of unknown outcome.
"""

import struct
import threading
import time
from collections import deque

import numpy as np

HEAD = struct.Struct("<IQ")  # publisher (the queue's number), sequence number
RESEND_DELAY_S = 0.01
ATTACH_WAVE = 2048
DELIVERY, CONFIRM, SETTLED = 0, 1, 2


def draw_hot(rng, groups: int, n_hot: int) -> list:
    """``n_hot`` queues drawn from the seed, spread evenly over
    ``g mod 64``, residue 0 first: the program samples its commit stages
    on every 64th group, and a uniform draw of 256 from 10,240 would
    leave all of them idle in one run of fifty-five (whose traced line
    then lacks the four commit-stage metrics)."""
    classes = [rng.permutation(np.arange(r, groups, 64)).tolist()
               for r in range(min(64, groups))]
    order = [0] + (1 + rng.permutation(len(classes) - 1)).tolist()
    hot = []
    while len(hot) < n_hot:
        for r in order:
            if classes[r] and len(hot) < n_hot:
                hot.append(classes[r].pop())
    return sorted(hot)


class Generator:
    def __init__(self, cluster, config: dict, params: dict, seed: int, say):
        from ra_tpu.protocol import USR, Command

        self._Command, self._USR = Command, USR
        self.cluster = cluster
        self.seed = seed
        self.say = say
        self.shards = int(params["shards"])
        self.window = int(params["confirm_window"])
        self.prefetch = int(params["prefetch"])
        self.timeout_ns = int(float(params["reply_timeout_s"]) * 1e9)
        self.max_resends = int(params["max_resends"])
        G = self.G = cluster.groups
        rng = np.random.default_rng(seed)
        self.hot = draw_hot(rng, G, min(int(params["hot_queues"]), G))
        self.filler = rng.bytes(int(config["body_bytes"]) - HEAD.size)
        self.names = cluster.names
        self.consumer = [("consumer", g) for g in range(G)]
        self.node_of = [cluster.leader_node(g) for g in range(G)]
        self.nodes = {n: cluster.coord(n) for n in cluster.node_names()}
        # per hot queue, what the reference is given
        self.confirmed = {g: [] for g in self.hot}  # (seq, msg_id)
        self.unknown = {}  # g -> [seq, ...]: enqueues of unknown outcome
        self.deliveries = {g: [] for g in self.hot}  # (consumer, id, writer, seq, t)
        self.settled = {g: [] for g in self.hot}  # (msg_id, t_done)
        self.settle_unknown = {}  # g -> [msg_id, ...]
        self.retired = set()
        self.sent_at = {g: [] for g in self.hot}  # seq -> first send (ns)
        self.seen = {g: set() for g in self.hot}  # message ids delivered
        self.open_msgs = {g: 0 for g in self.hot}  # sent - settled
        self.deepest = 0
        self.redeliveries = 0
        # per shard: enqueues in flight (g, slot) -> [seq, t_send, resends],
        # settles in flight (g, msg_id) -> [t_send, resends], and the inbox
        # the coordinators' threads append to
        self.flight = [{} for _ in range(self.shards)]
        self.settling = [{} for _ in range(self.shards)]
        self._inbox = [deque() for _ in range(self.shards)]
        self._wake = [threading.Event() for _ in range(self.shards)]
        # (send, done, ok) of writes and of settles, publish -> delivery
        self._writes = [([], [], []) for _ in range(self.shards)]
        self._settles = [([], [], []) for _ in range(self.shards)]
        self._pub_to_dlv = [([], []) for _ in range(self.shards)]  # t, ns
        self.redirects = [0] * self.shards
        self.rejects = [0] * self.shards
        self._stopping = False
        self._drain_by_ns = 0
        self._threads = []
        self._confirm_cb = {(g, s): self._callback(CONFIRM, g, s)
                            for g in self.hot for s in range(self.window)}

    def _callback(self, kind: int, g: int, key: int):
        inbox, wake = self._inbox[g % self.shards], self._wake[g % self.shards]
        clock = time.monotonic_ns

        def reply_to(reply):
            # (runs on a coordinator's thread: note the time, hand over)
            inbox.append((kind, g, key, reply, clock()))
            if not wake.is_set():
                wake.set()

        return reply_to

    def _sink(self):
        inboxes, wakes, shards = self._inbox, self._wake, self.shards
        clock = time.monotonic_ns

        def send_msg(to, msg, _options):
            # (the leader's egress thread, under its state lock)
            k = to[1] % shards
            inboxes[k].append((DELIVERY, to, msg, None, clock()))
            if not wakes[k].is_set():
                wakes[k].set()

        return send_msg

    # -- set-up: a consumer on every queue --------------------------------------

    def load(self) -> None:
        """Set the sink, then ``checkout`` for the one consumer of every
        queue, in waves handed in bulk per leader node; a wave ends when
        all its replies are in (a checkout is idempotent: sent again
        after a redirect or a reject)."""
        sink = self._sink()
        for coord in self.nodes.values():
            coord.send_msg_cb = sink
        t0 = time.perf_counter()
        for lo in range(0, self.G, ATTACH_WAVE):
            todo = list(range(lo, min(lo + ATTACH_WAVE, self.G)))
            for _attempt in range(20):
                got = {}
                by_node = {}
                for g in todo:
                    node = self.cluster.leader_node(g)
                    by_node.setdefault(node, []).append((
                        (self.names[g], node),
                        self._Command(
                            kind=self._USR,
                            data=("checkout", self.consumer[g], self.prefetch),
                            reply_mode="await_consensus",
                            from_ref=lambda r, g=g: got.__setitem__(g, r),
                            ts=time.monotonic_ns()),
                        None))
                for node, batch in by_node.items():
                    self.nodes[node].deliver_many(batch)
                deadline = time.monotonic() + 60
                while len(got) < len(todo) and time.monotonic() < deadline:
                    time.sleep(0.01)
                again = []
                for g in todo:
                    reply = got.get(g, ("timeout",))
                    if reply[0] in ("redirect", "reject"):
                        again.append(g)
                    elif reply[0] != "ok" or reply[1][0] != "ok":
                        raise RuntimeError(f"fifo_queues load: checkout on "
                                           f"{self.names[g]}: {reply!r}")
                todo = again
                if not todo:
                    break
                time.sleep(0.05)
            if todo:
                raise RuntimeError(f"fifo_queues load: {len(todo)} consumers "
                                   f"never attached")
        self.node_of = [self.cluster.leader_node(g) for g in range(self.G)]
        self.say("load", consumers=self.G, prefetch=self.prefetch,
                 hot_queues=len(self.hot),
                 seconds=time.perf_counter() - t0)

    def start(self) -> None:
        for k in range(self.shards):
            t = threading.Thread(target=self._shard, args=(k,),
                                 name=f"fifo-gen-{k}", daemon=True)
            self._threads.append(t)
            t.start()

    # -- one shard: its queues' publishers and consumers ---------------------------

    def _message(self, g: int, data, from_ref, now: int):
        return ((self.names[g], self.node_of[g]),
                self._Command(kind=self._USR, data=data,
                              reply_mode="await_consensus",
                              from_ref=from_ref, ts=now),
                None)

    def _enqueue(self, g: int, slot: int, seq: int, resends: int):
        now = time.monotonic_ns()
        flight = self.flight[g % self.shards]
        prev = flight.get((g, slot))
        # a resend keeps the first send's time: the publisher has waited since
        flight[(g, slot)] = [seq, prev[1] if prev else now, resends]
        return self._message(
            g, ("enqueue", HEAD.pack(g, seq) + self.filler),
            self._confirm_cb[(g, slot)], now)

    def _fresh(self, g: int, slot: int):
        sent = self.sent_at[g]
        self.flight[g % self.shards].pop((g, slot), None)
        msg = self._enqueue(g, slot, len(sent), 0)
        sent.append(msg[1].ts)
        depth = self.open_msgs[g] = self.open_msgs[g] + 1
        if depth > self.deepest:
            self.deepest = depth
        return msg

    def _settle(self, g: int, msg_id: int, resends: int):
        now = time.monotonic_ns()
        settling = self.settling[g % self.shards]
        prev = settling.get((g, msg_id))
        settling[(g, msg_id)] = [prev[0] if prev else now, resends]
        return self._message(g, ("settle", self.consumer[g], msg_id),
                             self._callback(SETTLED, g, msg_id), now)

    def _send(self, msgs) -> None:
        by_node = {}
        for m in msgs:
            by_node.setdefault(m[0][1], []).append(m)
        for node, batch in by_node.items():
            self.nodes[node].deliver_many(batch)

    def _retire(self, g: int, why: str) -> None:
        if g not in self.retired:
            self.retired.add(g)
            self.say("generator", failed_group=self.names[g], why=why)

    def _fail_enqueue(self, k: int, g: int, slot: int, t_ns: int, why: str):
        seq, t_send, _r = self.flight[k].pop((g, slot))
        self.unknown.setdefault(g, []).append(seq)
        send, done, ok = self._writes[k]
        send.append(t_send)
        done.append(t_ns)
        ok.append(False)
        if why != "maybe":
            self._retire(g, why)

    def _fail_settle(self, k: int, g: int, msg_id: int, t_ns: int, why: str):
        t_send, _r = self.settling[k].pop((g, msg_id))
        self.settle_unknown.setdefault(g, []).append(msg_id)
        send, done, ok = self._settles[k]
        send.append(t_send)
        done.append(t_ns)
        ok.append(False)
        self._retire(g, why)

    def _resend(self, kind: int, g: int, key: int, fl):
        """The command in flight under ``(g, key)`` once more."""
        if kind == CONFIRM:
            return self._enqueue(g, key, fl[0], fl[2] + 1)
        return self._settle(g, key, fl[1] + 1)

    def _fail(self, kind: int, k: int, g: int, key: int, t_ns: int, why: str):
        if kind == CONFIRM:
            self._fail_enqueue(k, g, key, t_ns, why)
        else:
            self._fail_settle(k, g, key, t_ns, why)

    def _idle(self, k: int) -> bool:
        """Nothing of shard ``k`` is in flight, and every message its
        publishers had confirmed has reached its consumer."""
        if self.flight[k] or self.settling[k]:
            return False
        return all(len(self.seen[g]) >= len(self.confirmed[g])
                   for g in self.hot
                   if g % self.shards == k and g not in self.retired)

    def _shard(self, k: int) -> None:
        inbox, wake = self._inbox[k], self._wake[k]
        flight, settling = self.flight[k], self.settling[k]
        w_send, w_done, w_ok = self._writes[k]
        s_send, s_done, s_ok = self._settles[k]
        lat_t, lat_ns = self._pub_to_dlv[k]
        retired = self.retired
        mine = [g for g in self.hot if g % self.shards == k]
        self._send([self._fresh(g, s) for g in mine
                    for s in range(self.window)])
        later = []  # (due_ns, kind, g, key): resends after a reject or a blind redirect
        last_scan = time.monotonic_ns()
        while True:
            wake.wait(0.02 if later else 0.25)
            wake.clear()
            out = []
            while inbox:
                kind, g, key, reply, t_ns = inbox.popleft()
                if kind == DELIVERY:
                    consumer, (_tag, msg_id, body) = g, key
                    g = consumer[1]
                    if g in retired or g not in self.seen:
                        continue
                    writer, seq = HEAD.unpack_from(body)
                    self.deliveries[g].append(
                        (consumer, msg_id, writer, seq, t_ns))
                    seen = self.seen[g]
                    if msg_id in seen:
                        self.redeliveries += 1
                    else:
                        seen.add(msg_id)
                        if writer == g and seq < len(self.sent_at[g]):
                            lat_t.append(t_ns)
                            lat_ns.append(t_ns - self.sent_at[g][seq])
                    out.append(self._settle(g, msg_id, 0))
                    continue
                if g in retired:
                    continue
                fl = (flight if kind == CONFIRM else settling).get((g, key))
                if fl is None:
                    continue  # a reply to a command already given up
                tag = reply[0]
                if tag == "ok":
                    if kind == CONFIRM:
                        self.confirmed[g].append((fl[0], reply[1][1]))
                        w_send.append(fl[1])
                        w_done.append(t_ns)
                        w_ok.append(True)
                        if self._stopping:
                            del flight[(g, key)]
                        else:
                            out.append(self._fresh(g, key))
                    else:
                        del settling[(g, key)]
                        self.settled[g].append((key, t_ns))
                        self.open_msgs[g] -= 1
                        s_send.append(fl[0])
                        s_done.append(t_ns)
                        s_ok.append(True)
                elif tag == "maybe" and kind == CONFIRM:
                    # deposed with the entry in its log: it may still
                    # commit, so the enqueue is never sent again
                    self._fail_enqueue(k, g, key, t_ns, "maybe")
                    if not self._stopping:
                        out.append(self._fresh(g, key))
                elif tag in ("redirect", "reject", "maybe"):
                    if fl[-1] >= self.max_resends:
                        self._fail(kind, k, g, key, t_ns, f"{tag} x{fl[-1]}")
                        continue
                    if tag == "redirect":
                        self.redirects[k] += 1
                        hint = reply[1]
                        if hint is not None:
                            self.node_of[g] = hint[1]
                            out.append(self._resend(kind, g, key, fl))
                            continue
                    elif tag == "reject":
                        self.rejects[k] += 1
                    later.append((t_ns + int(RESEND_DELAY_S * 1e9),
                                  kind, g, key))
                else:
                    self._fail(kind, k, g, key, t_ns, f"reply {reply!r}")
            now = time.monotonic_ns()
            if later:
                due = [x for x in later if x[0] <= now]
                later = [x for x in later if x[0] > now]
                for _due, kind, g, key in due:
                    fl = (flight if kind == CONFIRM else settling).get((g, key))
                    if fl is None:
                        continue
                    self.node_of[g] = self.cluster.leader_node(g)
                    out.append(self._resend(kind, g, key, fl))
            if out:
                self._send(out)
            if now - last_scan > 1_000_000_000:
                last_scan = now
                why = f"no reply in {self.timeout_ns / 1e9:.0f} s"
                for (g, slot), fl in list(flight.items()):
                    if now - fl[1] > self.timeout_ns:
                        self._fail_enqueue(k, g, slot, now, why)
                for (g, msg_id), fl in list(settling.items()):
                    if now - fl[0] > self.timeout_ns:
                        self._fail_settle(k, g, msg_id, now, why)
            if self._stopping:
                if now > self._drain_by_ns:
                    why = "no reply by the end of the drain"
                    for g, slot in list(flight):
                        self._fail_enqueue(k, g, slot, now, why)
                    for g, msg_id in list(settling):
                        self._fail_settle(k, g, msg_id, now, why)
                    return
                if not inbox and self._idle(k):
                    return

    # -- the end ----------------------------------------------------------------

    def stop(self, drain_budget_s: float) -> None:
        """Publish nothing new; go on settling what is delivered until
        every confirmed message has been delivered and its settle
        acknowledged, and give up what has not come inside the budget
        (unknown outcome)."""
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
        """Deliveries and acknowledged settles that ended inside the
        window; resends, redeliveries and the deepest queue (enqueues
        sent and not yet settled) since the start. Publish -> delivery
        latency of the window goes on a ``generator`` line."""
        t = np.asarray([x for ts, _ns in self._pub_to_dlv for x in ts], np.int64)
        ns = np.asarray([x for _ts, nss in self._pub_to_dlv for x in nss],
                        np.int64)
        inside = ns[(t >= t0_ns) & (t < t1_ns)]
        if len(inside):
            self.say("generator", publish_to_delivery_ms={
                "samples": int(len(inside)),
                "p50": float(np.percentile(inside, 50)) / 1e6,
                "p95": float(np.percentile(inside, 95)) / 1e6})
        settles = sum(1 for _s, done, ok in self._settles
                      for d, o in zip(done, ok) if o and t0_ns <= d < t1_ns)
        return {"redirects": sum(self.redirects), "rejects": sum(self.rejects),
                "deliveries": int(((t >= t0_ns) & (t < t1_ns)).sum()),
                "redeliveries": self.redeliveries, "settles": settles,
                "deepest_queue": self.deepest}

    def history(self) -> dict:
        def ops(shards):
            return {"t_send": [t for s, _d, _o in shards for t in s],
                    "t_done": [t for _s, d, _o in shards for t in d],
                    "ok": [x for _s, _d, o in shards for x in o]}

        return {
            "ops": {"write": ops(self._writes), "settle": ops(self._settles)},
            "groups": self.G,
            "hot": list(self.hot),
            "consumer": {g: self.consumer[g] for g in self.hot},
            "confirmed": {g: list(v) for g, v in self.confirmed.items()},
            "unknown": {g: list(v) for g, v in self.unknown.items()},
            "deliveries": {g: list(v) for g, v in self.deliveries.items()},
            "settled": {g: list(v) for g, v in self.settled.items()},
            "settle_unknown": {g: list(v)
                               for g, v in self.settle_unknown.items()},
            "retired": sorted(self.retired),
        }
