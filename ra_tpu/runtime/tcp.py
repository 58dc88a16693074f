"""TCP transport: real multi-process/multi-host clusters.

The distributed communication backend (counterpart of the reference's
use of Erlang distribution: async casts with noconnect/nosuspend
semantics and backpressure-aware peer status, reference:
src/ra_server_proc.erl:1875-1881, 2094-2110):

- node names are ``host:port`` strings; each node runs one
  ``TcpTransport`` that accepts inbound connections and lazily dials
  outbound ones;
- wire format: length-framed ``HMAC-SHA256(cookie) || payload``. A
  payload opens with the pickle of a head ``(to_name, from_sid, msg)``:
  one protocol message to the server ``to_name``, or a control frame
  (``__ping__`` / ``__pong__`` / ``__mgmt__`` / ``__proc_down__``). A
  BATCH frame (``send_batch``: everything one coordinator has for one
  destination node in one ``_send_batch``) has the head ``("__batch__",
  sender_node, n)`` and, behind it in the same payload, the pickle of
  the list of ``n`` ``(name, from_sid, msg)`` triples: one encode, one
  MAC, one outbox element and one socket write on this side; one MAC
  check, one restricted decode and one call of the owner's
  ``deliver_batch`` on the other (docs/INTERNALS.md section 18). Every
  frame is authenticated with a shared-secret cookie before it is
  unpickled (the counterpart of the Erlang distribution cookie): a
  frame with a bad MAC kills the connection without touching pickle
  and delivers none of its messages. **Trust model**: inbound frames
  deserialize through a RESTRICTED unpickler — only the protocol/effect
  vocabulary, plain containers, and application-registered payload
  types resolve (``register_wire_type``); a cookie holder cannot smuggle
  os/subprocess/functools gadget chains. Still set a secret cookie
  (``RA_TPU_COOKIE`` env or the ``cookie=`` arg): authenticated peers
  can of course drive the full management plane;
- sends are async and never block the caller: each peer has a bounded
  outbox drained by a writer thread — when the outbox overflows, sends
  report failure (the peer status flips, exactly like distribution
  buffer backpressure in the reference). While nothing is queued for a
  peer and its writer is idle the caller's thread offers the frame to
  the socket itself (``MSG_DONTWAIT``: the socket takes what it can
  at once), and the writer gets what is left;
- a client's call to a server on another node (``send_call``, from
  ``api``) and the replies to it (``reply_batch``, from the replying
  node's wave) are batch frames of their own, matched by a correlation
  id in this transport's table of pending calls (docs/INTERNALS.md
  section 24);
- at-most-once delivery; reconnection is lazy on next send;
- liveness: pings every ``ping_interval_s``; any authenticated frame
  from a node within ``pong_timeout_s`` proves it alive, and past that
  an attached phi-accrual detector (fed by the pongs) may still vouch
  for a link whose pongs are known to jitter.

The owner is a ``RaNode`` or a ``BatchCoordinator`` built with
``tcp=True``: it passes its ``deliver`` and sets the callbacks it has
(``deliver_batch``: the coordinator's ``ingest_batch``; ``counters``:
where the ``wire_*`` fields are booked, once a frame; ``detector``;
``on_proc_down_cb``; ``on_mgmt_cb``). ``close()`` ends every thread the
transport started.
"""

from __future__ import annotations

import hashlib
import hmac
import io
import logging
import os
import pickle
import socket
import struct
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from ra_tpu import counters as _counters
from ra_tpu import faults
from ra_tpu import obs as _obs
from ra_tpu.protocol import (
    LogRead, ServerId, sanitize_for_wire, strip_entry_refs)

logger = logging.getLogger("ra_tpu")

_LEN = struct.Struct("<I")
MAX_FRAME = 64 * 1024 * 1024
_MAC_LEN = 16  # truncated HMAC-SHA256 prefix on every frame
_BATCH = "__batch__"
# a batch frame whose list names one of these in its first triple
# carries a client's calls, or their replies, and nothing else
# (send_call, reply_batch): triples (CLIENT_CALL, None, (corr, server
# name, msg, slot, t_sent)) and (CLIENT_REPLY, None, (corr, value,
# t_issued))
CLIENT_CALL = "__call__"
CLIENT_REPLY = "__reply__"
_RECV = 1 << 20  # one recv takes what the socket holds, up to this
# hashlib lets go of the interpreter lock around an update of 2,048
# bytes or more, and under a dozen busy threads getting it back costs a
# thread more than hashing a frame does: a frame is fed in pieces below
_MAC_PIECE = 2047

# restricted wire deserialization: see ra_tpu.utils.wire (inbound
# frames resolve classes through an allowlist — a cookie holder cannot
# smuggle gadget chains). Re-exported here for discoverability.
from ra_tpu.utils.wire import (  # noqa: E402,F401 (re-export)
    register_wire_type,
    unregister_wire_type,
    wire_load_file as _wire_load_file,
    wire_loads as _wire_loads,
)


class RemoteRef:
    """The reply handle of a call that came over the wire: where a local
    caller's future would be (a ``Command``'s ``from_ref``, a query's
    future), the caller's node and the call's correlation id. The batch
    coordinator's ``_reply`` gathers these per caller node and sends a
    wave's replies as one frame; anyone else replies at once through
    ``set_result``. ``t_born`` is when the call was handed to this
    node's ingress (``time.monotonic_ns()``), where a read's accounts
    start. It never crosses a process boundary itself: the log and the
    replication strip it as they strip a local future."""

    __slots__ = ("transport", "node", "corr", "t_born")

    def __init__(self, transport: "TcpTransport", node: str, corr: int,
                 t_born: int):
        self.transport = transport
        self.node = node
        self.corr = corr
        self.t_born = t_born

    def set_result(self, value: Any) -> None:
        self.transport.send_replies(self.node, [(self.corr, value)])

    def __reduce__(self):
        raise TypeError("a RemoteRef does not leave its process")


def _wire_reply(value: Any) -> Any:
    """A reply as it can cross: a reject's gate waiter (an event of this
    process) is left out, so the caller backs off by its own clock, and
    the log entry a ``LogRead`` answer carries loses the leader's reply
    handle and stamp, as replication strips them."""
    if type(value) is tuple and value:
        if value[0] == "reject":
            return value[:2]
        if len(value) > 1 and type(value[1]) is LogRead \
                and value[1].entry is not None:
            read = value[1]
            (entry,) = strip_entry_refs((read.entry,))
            if entry is not read.entry:
                return (value[0], read._replace(entry=entry)) + value[2:]
    return value


class _Peer:
    def __init__(self, name: str, addr: Tuple[str, int], outbox_cap: int):
        self.name = name
        self.addr = addr
        # elements are (wire_bytes, message_count): wire_bytes is already
        # length-prefixed, so the writer joins and sends without any
        # per-frame work; a batch frame is ONE element carrying the
        # number of messages in it, for exact drop accounting
        self.outbox: deque = deque()
        self.cap = outbox_cap
        self.cv = threading.Condition()
        self.sock: Optional[socket.socket] = None
        self.writing = False  # the writer holds popped elements
        self.thread: Optional[threading.Thread] = None
        self.closed = False


class TcpTransport:
    """Duck-type compatible with InProcTransport (send / node_alive /
    proc_alive / blocked set for fault injection).

    The ``blocked`` set holds DIRECTED ``(from, to)`` node pairs checked
    on the sender's side only, so the nemesis plane's one-way partitions
    (``testing.partition_oneway`` / the soak's ``oneway`` dimension) work
    identically over TCP: arming ``(a, b)`` on a's transport drops a's
    sends to b while b's sends to a still flow — the stale-leader
    scenario (acks lost, AppendEntries delivered) needs exactly that
    asymmetry. A symmetric partition arms both directions, each on its
    own side's transport."""

    def __init__(
        self,
        node_name: str,
        deliver,  # fn(to_sid, msg, from_sid) -> bool
        bind: Optional[Tuple[str, int]] = None,
        outbox_cap: int = 10_000,
        cookie: Optional[str] = None,
    ):
        host, port = node_name.rsplit(":", 1)
        self.node_name = node_name
        self.deliver = deliver
        # set by an owner that takes a batch frame's list whole:
        # fn([(name, from_sid, msg), ...]) -> messages it shed. Without
        # it the list is fed to ``deliver`` one by one
        self.deliver_batch = None
        # set by an owner that keeps the ``wire_*`` fields of
        # ``counters.COORDINATOR_FIELDS``: booked once a frame
        self.counters = None
        self._book_lock = threading.Lock()  # two readers, one sender
        self.outbox_cap = outbox_cap
        self._cookie = (
            cookie or os.environ.get("RA_TPU_COOKIE") or "ra_tpu_default_cookie"
        ).encode()
        self.blocked: set = set()
        self.drop_fn = None
        self.dropped = 0
        self._peers: Dict[str, _Peer] = {}
        self._lock = threading.Lock()
        self._closed = False
        self._stop = threading.Event()  # what the ping loop sleeps on
        # inbound connections and their reader threads, for close()
        self._inbound: Dict[socket.socket, threading.Thread] = {}

        bind_addr = bind or (host, int(port))
        self._server = socket.create_server(bind_addr, reuse_port=False)
        self._server.settimeout(0.5)
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name=f"ra-tcp-accept-{node_name}", daemon=True
        )
        self._accept_thread.start()
        # liveness: ping every known peer. A node is alive while
        # anything authenticated came from it within ``pong_timeout_s``
        # (under load its frames come many times a ping, and a pong
        # that queues behind a wave's worth of them is late, not lost).
        # Past that, with a ``detector`` (ra_tpu.detector.
        # PhiAccrualDetector) attached, the pongs' ARRIVALS have fed it
        # and its adaptive phi window decides — a link known to jitter
        # is given longer (the aten role; both backends share this
        # transport, so liveness semantics stay uniform)
        self.ping_interval_s = 0.2
        self.pong_timeout_s = 1.0
        self.detector = None
        self._last_heard: Dict[str, float] = {}
        # set by the owning node: called with a ServerId when a remote
        # peer announces one of its procs died
        self.on_proc_down_cb = None
        # management plane (reference: rpc:call start/restart/delete on
        # remote nodes, src/ra_server_sup_sup.erl:33-50): the owning
        # node sets on_mgmt_cb(op, kwargs) -> result; mgmt_call() is the
        # client side
        self.on_mgmt_cb = None
        self._mgmt_futs: Dict[int, Tuple[threading.Event, dict]] = {}
        self._mgmt_seq = 0
        self._mgmt_lock = threading.Lock()
        # the client path (send_call / reply_batch; docs/INTERNALS.md,
        # "Clients on another node"): calls this node made and not yet
        # answered, by correlation id, and the path's counters, all
        # under one lock. The two hops are stamped on
        # ``time.monotonic_ns()`` at each end, so they read true only
        # where both nodes share a host's clock (the benchmark's nodes
        # share a process)
        self._calls: Dict[int, Any] = {}
        self._call_seq = 0
        self._client_lock = threading.Lock()
        self.client = _counters.new(("client", node_name),
                                    _counters.CLIENT_FIELDS)
        self._client_hop = _obs.histogram(
            ("client", node_name, "client_hop"),
            "a call's stamp on the caller's node before encode -> handed "
            "to this node's ingress", locked=True)
        self._reply_hop = _obs.histogram(
            ("client", node_name, "reply_hop"),
            "a reply's stamp on the replying node as its wave hands it "
            "to the send path -> taken in here, before the caller's "
            "future is set", locked=True)
        self._ping_thread = threading.Thread(
            target=self._ping_loop, name=f"ra-tcp-ping-{node_name}", daemon=True
        )
        self._ping_thread.start()

    # ------------------------------------------------------------------

    def _book(self, **fields: int) -> None:
        """Add to the owner's ``wire_<field>`` counters: once a frame,
        or once a loss."""
        c = self.counters
        if c is not None:
            with self._book_lock:
                for k, v in fields.items():
                    c.incr("wire_" + k, v)

    def _drop(self, n: int = 1) -> None:
        self.dropped += n
        self._book(dropped=n)

    def send(self, to: ServerId, msg: Any, from_sid: Optional[ServerId] = None) -> bool:
        node_name = to[1]
        if node_name == self.node_name:
            return self.deliver(to, msg, from_sid)
        if (self.node_name, node_name) in self.blocked or self._closed:
            self._drop()
            return False
        if self.drop_fn is not None and self.drop_fn(to, msg):
            self._drop()
            return False
        try:
            # injected send fault: raise -> reported undeliverable (the
            # caller's resend machinery covers it); latency just delays
            faults.fire("tcp.send", self.node_name)
        except OSError:
            self._drop()
            return False
        peer = self._peer(node_name)
        if peer is None:
            self._drop()
            return False
        try:
            frame = self._seal(
                pickle.dumps((to[0], from_sid, sanitize_for_wire(msg)))
            )
        except Exception:  # noqa: BLE001 — unpicklable payload
            self._drop()
            return False
        if len(frame) > MAX_FRAME:
            # the receiver would kill the connection (and every queued
            # frame behind this one); report failure to the caller instead
            self._drop()
            return False
        return self._enqueue(peer, _LEN.pack(len(frame)) + frame, 1)

    def send_batch(self, node_name: str, msgs) -> int:
        """Everything the caller has for ONE node, ``(to_sid, msg,
        from_sid)`` triples, as ONE batch frame: one encode of the list,
        one MAC, one length prefix, one outbox element whose count is
        the number of messages, one socket write (docs/INTERNALS.md
        section 18). ``blocked``, ``drop_fn`` and the drop accounts keep
        their meaning per message. A batch whose encoding would pass
        ``MAX_FRAME`` leaves as several frames. Returns the number of
        messages enqueued, or -1 when the destination is this node or a
        tcp failpoint is armed — the caller then sends message by
        message, so fire/mangle fault semantics stay per frame."""
        if node_name == self.node_name or faults.any_armed(
                "tcp.send", "tcp.frame"):
            return -1
        if self._closed or (self.node_name, node_name) in self.blocked:
            self._drop(len(msgs))
            return 0
        peer = self._peer(node_name)
        if peer is None:
            self._drop(len(msgs))
            return 0
        t0 = time.perf_counter_ns()
        drop = self.drop_fn
        if drop is None:
            triples = [(to[0], frm, sanitize_for_wire(msg))
                       for to, msg, frm in msgs]
        else:
            triples = [(to[0], frm, sanitize_for_wire(msg))
                       for to, msg, frm in msgs if not drop(to, msg)]
            if len(triples) < len(msgs):
                self._drop(len(msgs) - len(triples))
        sent = frames = size = 0
        for wire, n in self._batch_frames(triples):
            if self._enqueue(peer, wire, n):
                sent += n
                frames += 1
                size += len(wire)
        if triples and triples[0][0] == CLIENT_REPLY:
            self._count(client_replies_out=sent, client_frames_out=frames)
        else:
            self._book(frames_out=frames, msgs_out=sent, bytes_out=size,
                       encode_ns=time.perf_counter_ns() - t0)
        return sent

    def _batch_frames(self, triples: List[tuple]):
        """``triples`` as sealed, length-prefixed batch frames, each with
        its number of messages: one, unless the encoding would pass
        ``MAX_FRAME`` (then the list is halved until its parts fit) or
        a message cannot be encoded (it is dropped, the others go)."""
        if not triples:
            return
        try:
            body = pickle.dumps(triples)
        except Exception:  # noqa: BLE001 — some message is unpicklable
            good = []
            for t in triples:
                try:
                    pickle.dumps(t)
                    good.append(t)
                except Exception:  # noqa: BLE001
                    self._drop()
            if len(good) < len(triples):
                yield from self._batch_frames(good)
            else:
                self._drop(len(triples))  # (each encodes, the list not)
            return
        wire = self._batch_frame(len(triples), body)
        if wire is None:
            if len(triples) == 1:
                self._drop()  # one message larger than any frame
                return
            half = len(triples) // 2
            yield from self._batch_frames(triples[:half])
            yield from self._batch_frames(triples[half:])
            return
        yield wire, len(triples)

    def _batch_frame(self, n: int, body: bytes) -> Optional[bytes]:
        """The sealed, length-prefixed batch frame of ``n`` messages
        whose list encodes to ``body``; None past ``MAX_FRAME``."""
        head = pickle.dumps((_BATCH, self.node_name, n))
        size = _MAC_LEN + len(head) + len(body)
        if size > MAX_FRAME:
            return None
        return b"".join((_LEN.pack(size), self._mac(head, body), head, body))

    def _count(self, **fields: int) -> None:
        """Add to this node's client-path counters (``client_*``)."""
        c = self.client
        with self._client_lock:
            for k, v in fields.items():
                c.incr(k, v)

    # -- the client path: calls to a server on another node ----------------

    def send_call(self, to: ServerId, msg: Any, slot: int, fut) -> Optional[int]:
        """Send one client call to the server ``to`` on another node,
        as a batch frame of its own, from the caller's thread: ``msg``
        is what ``api`` would deliver locally with its reply handle
        taken out; ``slot`` says where the handle goes back (-1: a
        ``Command``'s ``from_ref``, else the tuple's position). ``fut``
        (``set_result``) is held in the table of pending calls until its
        reply comes. Returns the correlation id, which
        ``forget_call`` takes when the caller gives up; None when
        ``to``'s node cannot be addressed. A frame lost on the way
        (a blocked pair, a full outbox, a broken connection) is a call
        whose caller times out. Raises ``TypeError`` before anything is
        sent when the call does not encode."""
        node_name = to[1]
        peer = self._peer(node_name)
        if peer is None:
            return None
        t_sent = time.monotonic_ns()
        with self._client_lock:
            self._call_seq += 1
            corr = self._call_seq
            self._calls[corr] = fut
        tr = _obs.tracing()
        if tr:
            sp = _obs.begin("ra/client/call", node=self.node_name,
                            peer=node_name)
        try:
            try:
                body = pickle.dumps([(CLIENT_CALL, None, (
                    corr, to[0], sanitize_for_wire(msg), slot, t_sent))])
            except Exception as e:  # noqa: BLE001 — what will not encode
                with self._client_lock:
                    self._calls.pop(corr, None)
                raise TypeError(f"the call does not encode: {e}") from e
            wire = self._batch_frame(1, body)
            if wire is None:
                with self._client_lock:
                    self._calls.pop(corr, None)
                raise TypeError("the call is larger than any frame")
            if self._closed or (self.node_name, node_name) in self.blocked:
                self._drop()
            elif self._enqueue(peer, wire, 1):
                self._count(client_calls_out=1, client_frames_out=1)
        finally:
            if tr:
                _obs.end(sp)
        return corr

    def forget_call(self, corr: int) -> None:
        """The caller of ``corr`` timed out: its entry leaves the table,
        and a reply that comes later is counted late and dropped."""
        with self._client_lock:
            if self._calls.pop(corr, None) is not None:
                self.client.incr("client_timeouts")

    def reply_batch(self, node_name: str, replies) -> List[tuple]:
        """``(corr, value)`` replies to callers on ``node_name`` as the
        ``(to, msg, from)`` triples of one ``send_batch``: one clock read
        for all of them (their ``reply_hop`` starts here)."""
        t = time.monotonic_ns()
        to = (CLIENT_REPLY, node_name)
        return [(to, (corr, _wire_reply(value), t), None)
                for corr, value in replies]

    def send_replies(self, node_name: str, replies) -> None:
        """``(corr, value)`` replies to ``node_name`` now, as one frame
        (message by message while a tcp failpoint is armed)."""
        msgs = self.reply_batch(node_name, replies)
        if self.send_batch(node_name, msgs) < 0:
            for to, m, frm in msgs:
                self.send(to, m, frm)

    def _on_calls(self, sender: str, triples) -> None:
        """A frame of calls from ``sender``: each handed to the owner's
        ingress with a ``RemoteRef`` where the caller's future was (a
        command takes its submit stamp here, on this node's clock). A
        call to a server this node does not have is answered
        ``("redirect", None)`` at once: never appended, try another."""
        now = time.monotonic_ns()
        hop = self._client_hop.record
        deliver, me = self.deliver, self.node_name
        refused = []
        for _name, _frm, (corr, name, msg, slot, t_sent) in triples:
            hop(now - t_sent)
            ref = RemoteRef(self, sender, corr, now)
            if slot < 0:
                msg = msg._replace(from_ref=ref, ts=now)
            else:
                msg = msg[:slot] + (ref,) + msg[slot + 1:]
            if not deliver((name, me), msg, None):
                refused.append((corr, ("redirect", None)))
        self._count(client_calls_in=len(triples))
        if refused:
            self.send_replies(sender, refused)

    def _on_replies(self, triples) -> None:
        """A frame of replies to calls this node made: each sets its
        caller's future; one that finds no pending call is late."""
        now = time.monotonic_ns()
        hop = self._reply_hop.record
        found, late, redirects = [], 0, 0
        with self._client_lock:
            calls = self._calls
            for _name, _frm, (corr, value, t_issued) in triples:
                fut = calls.pop(corr, None)
                if fut is None:
                    late += 1
                    continue
                found.append((fut, value))
                hop(now - t_issued)
                if type(value) is tuple and value and value[0] == "redirect":
                    redirects += 1
            c = self.client
            c.incr("client_replies_in", len(triples))
            if late:
                c.incr("client_late_replies", late)
            if redirects:
                c.incr("client_redirects", redirects)
        for fut, value in found:
            fut.set_result(value)

    def _mac(self, *parts) -> bytes:
        """The truncated HMAC-SHA256 of ``parts`` under the cookie, fed
        in pieces small enough that the interpreter lock is kept."""
        mac = hmac.new(self._cookie, None, hashlib.sha256)
        for part in parts:
            with memoryview(part) as mv:
                for i in range(0, len(mv), _MAC_PIECE):
                    mac.update(mv[i:i + _MAC_PIECE])
        return mac.digest()[:_MAC_LEN]

    def _enqueue(self, peer: _Peer, wire: bytes, n: int) -> bool:
        with peer.cv:
            if len(peer.outbox) >= peer.cap:
                # backpressure: report undeliverable, do not block
                self._drop(n)
                return False
            sock = peer.sock
            if sock is not None and not peer.outbox and not peer.writing:
                # nothing queued and the writer idle: the socket takes
                # the bytes from this thread, if it takes them at once
                # (one turn at the interpreter lock less on the way to
                # the peer); what it does not take is the writer's
                tr = _obs.tracing()
                if tr:
                    sp = _obs.begin("ra/tcp/send", node=self.node_name,
                                    peer=peer.name, frames=1, bytes=len(wire))
                try:
                    sent = sock.send(wire, socket.MSG_DONTWAIT)
                except OSError:  # full, or broken: the writer finds out
                    sent = 0
                if tr:
                    _obs.end(sp)
                if sent == len(wire):
                    return True
                if sent:
                    wire = memoryview(wire)[sent:]
            peer.outbox.append((wire, n))
            peer.cv.notify()
        return True

    def node_alive(self, node_name: str) -> bool:
        if node_name == self.node_name:
            return not self._closed
        if (self.node_name, node_name) in self.blocked:
            return False
        peer = self._peers.get(node_name)
        if peer is None or peer.sock is None:
            return False
        last = self._last_heard.get(node_name)
        if last is None:
            return False
        if time.monotonic() - last < self.pong_timeout_s:
            return True
        d = self.detector
        return d is not None and not d.suspect(node_name)

    def proc_alive(self, sid: ServerId) -> bool:
        # remote proc liveness is not observable over TCP; approximate
        # with connection liveness (documented contract in transport.py)
        return self.node_alive(sid[1])

    def known_nodes(self):
        return [self.node_name] + list(self._peers.keys())

    def block(self, a: str, b: str) -> None:
        self.blocked.add((a, b))

    def unblock_all(self) -> None:
        self.blocked.clear()

    def close(self) -> None:
        """Close the sockets and end every thread this transport
        started (a caller on one of them is not joined)."""
        self._closed = True
        self._stop.set()
        _counters.delete(("client", self.node_name))
        for hop in ("client_hop", "reply_hop"):
            _obs.histograms().delete(("client", self.node_name, hop))
        try:
            self._server.close()
        except OSError:
            pass
        with self._lock:
            peers = list(self._peers.values())
            inbound = list(self._inbound)
        for p in peers:
            with p.cv:
                p.closed = True
                p.cv.notify_all()
        # wake the readers out of recv, a writer out of a send that the
        # other side does not take
        for sock in [*(p.sock for p in peers), *inbound]:
            try:
                if sock is not None:
                    sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        me = threading.current_thread()
        deadline = time.monotonic() + 5
        for t in self.threads():
            if t is not me:
                t.join(max(0.0, deadline - time.monotonic()))

    def threads(self) -> List[threading.Thread]:
        """Every thread of this transport that still runs."""
        with self._lock:
            ts = [self._accept_thread, self._ping_thread,
                  *(p.thread for p in self._peers.values()),
                  *self._inbound.values()]
        return [t for t in ts if t is not None and t.is_alive()]

    # ------------------------------------------------------------------

    def _seal(self, payload: bytes) -> bytes:
        mac = self._mac(payload)
        # injected frame corruption (torn -> truncated, raise -> bit
        # flip): the receiver's MAC check kills the connection, the
        # sender reconnects lazily — the wire-corruption drill
        return faults.mangle("tcp.frame", mac + payload, self.node_name)

    def _peer(self, node_name: str) -> Optional[_Peer]:
        with self._lock:
            if self._closed:
                # close() already swept the peer table: a late send
                # must not spawn a writer that would park (untimed)
                # with nobody left to close it
                return None
            p = self._peers.get(node_name)
            if p is not None:
                return p
            try:
                host, port = node_name.rsplit(":", 1)
                p = _Peer(node_name, (host, int(port)), self.outbox_cap)
            except ValueError:
                return None
            self._peers[node_name] = p
            p.thread = threading.Thread(
                target=self._writer_loop, args=(p,),
                name=f"ra-tcp-out-{node_name}", daemon=True,
            )
            p.thread.start()
            return p

    def _writer_loop(self, peer: _Peer) -> None:
        try:
            while not self._closed and not peer.closed:
                with peer.cv:
                    while not peer.outbox and not peer.closed and not self._closed:
                        # event-driven idle: every enqueue notifies the
                        # peer cv and close() marks peer.closed under it —
                        # an idle sender consumes zero CPU
                        # (docs/INTERNALS.md §16)
                        peer.cv.wait()
                    if peer.closed or self._closed:
                        break
                    frames = []
                    nf = 0
                    while peer.outbox and len(frames) < 512:
                        chunk, n = peer.outbox.popleft()
                        frames.append(chunk)
                        nf += n
                    peer.writing = True  # until these are on the socket
                if peer.sock is None:
                    try:
                        sock = socket.create_connection(peer.addr, timeout=2)
                        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                        # blocking from here on: a send either takes the
                        # bytes or (MSG_DONTWAIT) says at once that it
                        # cannot; close() shuts a stuck one down
                        sock.settimeout(None)
                        peer.sock = sock
                    except OSError:
                        self._drop(nf)
                        peer.writing = False
                        continue
                # elements are pre-framed at enqueue: the writer is a
                # pure join + sendall, no per-frame length packing
                data = frames[0] if len(frames) == 1 else b"".join(frames)
                tr = _obs.tracing()
                if tr:
                    sp = _obs.begin("ra/tcp/send", node=self.node_name,
                                    peer=peer.name, frames=len(frames),
                                    bytes=len(data))
                try:
                    peer.sock.sendall(data)
                except OSError:
                    self._drop(nf)
                    try:
                        peer.sock.close()
                    except OSError:
                        pass
                    peer.sock = None  # reconnect lazily on next batch
                finally:
                    peer.writing = False
                    if tr:
                        _obs.end(sp)
        finally:
            sock, peer.sock = peer.sock, None
            if sock is not None:
                try:
                    sock.close()
                except OSError:
                    pass

    def _ping_loop(self) -> None:
        while not self._closed:
            with self._lock:
                peers = list(self._peers.keys())
            for name in peers:
                self._enqueue_control(name, "__ping__")
            self._stop.wait(self.ping_interval_s)

    def _enqueue_control(self, node_name: str, kind: str, payload=None) -> bool:
        peer = self._peer(node_name)
        if peer is None:
            return False  # unaddressable node name
        frame = self._seal(pickle.dumps((kind, self.node_name, payload)))
        # (a control frame is no message: a full outbox drops it uncounted)
        return self._enqueue(peer, _LEN.pack(len(frame)) + frame, 0)

    def mgmt_call(self, node_name: str, op: str, kwargs: dict, timeout: float = 10.0):
        """Synchronous management RPC against a remote node (start /
        restart / stop / delete server, overview). Raises on timeout or
        remote error."""
        with self._mgmt_lock:
            self._mgmt_seq += 1
            corr = self._mgmt_seq
            ev, slot = threading.Event(), {}
            self._mgmt_futs[corr] = (ev, slot)
        try:
            if not self._enqueue_control(node_name, "__mgmt__", (corr, op, kwargs)):
                raise RuntimeError(
                    f"mgmt {op}: node {node_name!r} unaddressable or outbox full"
                )
            if not ev.wait(timeout):
                raise TimeoutError(f"mgmt {op} on {node_name} timed out")
        finally:
            with self._mgmt_lock:
                self._mgmt_futs.pop(corr, None)
        status, value = slot["r"]
        if status != "ok":
            raise RuntimeError(f"mgmt {op} on {node_name} failed: {value}")
        return value

    def broadcast_proc_down(self, sid: ServerId) -> None:
        """Tell every connected peer that a local server proc died (the
        TCP stand-in for remote process monitors)."""
        with self._lock:
            peers = list(self._peers.keys())
        for name in peers:
            self._enqueue_control(name, "__proc_down__", sid)

    def _accept_loop(self) -> None:
        while not self._closed:
            try:
                conn, _addr = self._server.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            t = threading.Thread(
                target=self._reader_loop, args=(conn,),
                name=f"ra-tcp-in-{self.node_name}", daemon=True,
            )
            with self._lock:
                if self._closed:
                    conn.close()
                    return
                self._inbound[conn] = t
            t.start()

    def _reader_loop(self, conn: socket.socket) -> None:
        conn.settimeout(None)
        # what has arrived and is not yet a whole frame: received into
        # ``chunk`` and appended (amortised), cut once a recv at the
        # last whole frame's end, never once a frame
        buf = bytearray()
        chunk = memoryview(bytearray(_RECV))
        try:
            while not self._closed:
                n = conn.recv_into(chunk)
                if not n:
                    return
                buf += chunk[:n]
                pos, have = 0, len(buf)
                while have - pos >= _LEN.size:
                    (ln,) = _LEN.unpack_from(buf, pos)
                    if ln > MAX_FRAME:
                        return  # corrupt/hostile stream: drop connection
                    end = pos + _LEN.size + ln
                    if end > have:
                        break
                    with memoryview(buf) as mv:
                        ok = self._on_frame(mv[pos + _LEN.size:end])
                    if not ok:
                        return
                    pos = end
                if pos:
                    del buf[:pos]
        except OSError:
            return
        finally:
            try:
                conn.close()
            except OSError:
                pass
            with self._lock:
                self._inbound.pop(conn, None)

    def _on_frame(self, frame: memoryview) -> bool:
        """One inbound frame: MAC, then decode, then what its head says.
        False ends the connection (and delivers nothing of the frame).
        ``frame`` is released on return."""
        t0 = time.perf_counter_ns()
        size = len(frame)
        try:
            if size < _MAC_LEN:
                return False
            with frame[_MAC_LEN:] as payload:
                if not hmac.compare_digest(bytes(frame[:_MAC_LEN]),
                                           self._mac(payload)):
                    return False  # unauthenticated frame: drop connection
                f = io.BytesIO(payload)  # the one copy of the frame
        finally:
            frame.release()
        sp = None
        try:
            to_name, from_sid, msg = _wire_load_file(f)
            if to_name == _BATCH:
                if _obs.tracing():
                    sp = _obs.begin("ra/tcp/recv", node=self.node_name,
                                    msgs=msg, bytes=size)
                triples = _wire_load_file(f)
                if type(triples) is not list or len(triples) != msg:
                    raise ValueError("batch frame: count and list differ")
        except Exception:  # noqa: BLE001
            # with the wire allowlist this is the primary failure mode
            # for LEGITIMATE traffic carrying an unregistered payload
            # type — never drop silently (the peer would reconnect and
            # loop forever)
            logger.exception(
                "tcp %s: dropping connection on frame decode failure "
                "(unregistered wire type? see "
                "ra_tpu.utils.wire.register_wire_type)", self.node_name)
            if sp is not None:
                _obs.end(sp)
            return False
        if to_name == _BATCH:
            kind = triples[0][0] if triples else None
            if kind == CLIENT_CALL or kind == CLIENT_REPLY:
                # a client's calls or their replies: no protocol message
                # rides such a frame, and the wire_* accounts leave it out
                try:
                    self._last_heard[from_sid] = time.monotonic()
                    if kind == CLIENT_CALL:
                        self._on_calls(from_sid, triples)
                    elif sp is not None:
                        with _obs.span("ra/client/reply", node=self.node_name,
                                       peer=from_sid, msgs=msg):
                            self._on_replies(triples)
                    else:
                        self._on_replies(triples)
                finally:
                    if sp is not None:
                        _obs.end(sp)
                return True
            try:
                self._last_heard[from_sid] = time.monotonic()
                cb = self.deliver_batch
                if cb is not None:
                    shed = cb(triples)
                else:
                    shed, me, deliver = 0, self.node_name, self.deliver
                    for name, frm, m in triples:
                        deliver((name, me), m, frm)
            finally:
                if sp is not None:
                    _obs.end(sp)
            self.dropped += shed
            self._book(frames_in=1, msgs_in=msg, bytes_in=size + _LEN.size,
                       decode_ns=time.perf_counter_ns() - t0, dropped=shed)
            return True
        if to_name == "__ping__":
            self._last_heard[from_sid] = time.monotonic()
            self._enqueue_control(from_sid, "__pong__")
        elif to_name == "__pong__":
            self._last_heard[from_sid] = time.monotonic()
            d = self.detector
            if d is not None:
                d.heartbeat(from_sid)
        elif to_name == "__mgmt__":
            corr, op, kwargs = msg
            cb = self.on_mgmt_cb

            # off the receive thread: start/restart do WAL recovery +
            # disk I/O, which must not stall the peer's Raft traffic on
            # this connection
            def run_mgmt(corr=corr, op=op, kwargs=kwargs, frm=from_sid):
                try:
                    r = (
                        ("ok", cb(op, kwargs))
                        if cb is not None
                        else ("error", "management not supported")
                    )
                except Exception as e:  # noqa: BLE001
                    r = ("error", repr(e))
                self._enqueue_control(frm, "__mgmt_reply__", (corr, r))

            threading.Thread(
                target=run_mgmt, name="ra-tcp-mgmt", daemon=True
            ).start()
        elif to_name == "__mgmt_reply__":
            corr, r = msg
            with self._mgmt_lock:
                fut = self._mgmt_futs.get(corr)
            if fut is not None:
                fut[1]["r"] = r
                fut[0].set()
        elif to_name == CLIENT_REPLY:  # sent message by message (a failpoint)
            self._on_replies([(to_name, from_sid, msg)])
        elif to_name == "__proc_down__":
            cb = self.on_proc_down_cb
            if cb is not None and msg is not None:
                try:
                    cb(tuple(msg))
                except Exception:  # noqa: BLE001
                    pass
        else:
            self.deliver((to_name, self.node_name), msg, from_sid)
        return True
