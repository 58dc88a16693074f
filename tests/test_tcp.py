"""TCP transport tests: consensus over real sockets.

Three RaNodes in this process, each with its own TcpTransport bound to a
localhost port — every inter-node protocol message crosses a real TCP
connection (no shared in-proc registry shortcut). Plus a true
multi-process smoke test.
"""

import socket
import subprocess
import sys
import time

import pytest

from ra_tpu import api, leaderboard
from ra_tpu.machine import SimpleMachine
from ra_tpu.system import SystemConfig
from ra_tpu.utils.wire import unregister_wire_type


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.fixture
def tcp_cluster(tmp_path):
    leaderboard.clear()
    names = [f"127.0.0.1:{free_port()}" for _ in range(3)]
    for n in names:
        cfg = SystemConfig(name="tcp", data_dir=str(tmp_path))
        api.start_node(n, cfg, election_timeout_s=0.15, tick_interval_s=0.1,
                       detector_poll_s=0.05, tcp=True)
    ids = [(f"t{i}", names[i]) for i in range(3)]
    yield ids, names
    for n in names:
        try:
            api.stop_node(n)
        except Exception:
            pass
    leaderboard.clear()


@pytest.mark.parametrize("lease", [False, True], ids=["lease-off", "lease-on"])
def test_consensus_over_tcp(tcp_cluster, lease):
    ids, names = tcp_cluster
    started, failed = api.start_cluster(
        "tcpc", lambda: SimpleMachine(lambda c, s: s + c, 0), ids, timeout=15,
        extra_cfg={"lease": True} if lease else None,
    )
    assert failed == []
    reply, leader = api.process_command(ids[0], 5, timeout=10)
    assert reply == 5
    reply, _ = api.process_command(ids[1], 7, timeout=10)
    assert reply == 12
    # all replicas converge over sockets
    deadline = time.monotonic() + 8
    while time.monotonic() < deadline:
        vals = [api.local_query(sid, lambda s: s)[1] for sid in ids]
        if vals == [12, 12, 12]:
            break
        time.sleep(0.05)
    assert vals == [12, 12, 12]
    assert api.consistent_query(ids[0], lambda s: s, timeout=10)[1] == 12


def test_tcp_failover(tcp_cluster):
    ids, names = tcp_cluster
    api.start_cluster("tcpf", lambda: SimpleMachine(lambda c, s: s + c, 0),
                      ids, timeout=15)
    api.process_command(ids[0], 1, timeout=10)
    leader = api.wait_for_leader("tcpf")
    api.stop_node(leader[1])  # whole node down: sockets drop
    deadline = time.monotonic() + 15
    new_leader = None
    while time.monotonic() < deadline:
        cand = leaderboard.lookup_leader("tcpf")
        if cand is not None and cand != leader and api._is_running(cand):
            new_leader = cand
            break
        time.sleep(0.05)
    assert new_leader is not None, "no TCP failover"
    reply, _ = api.process_command(new_leader, 9, timeout=10)
    assert reply == 10


_WORKER = """
import sys, time
sys.path.insert(0, {repo!r})
from ra_tpu import api
from ra_tpu.machine import SimpleMachine
from ra_tpu.system import SystemConfig

me, port, peers, data = sys.argv[1], sys.argv[2], sys.argv[3], sys.argv[4]
name = f"127.0.0.1:{{port}}"
cfg = SystemConfig(name="mp", data_dir=data)
api.start_node(name, cfg, election_timeout_s=0.2, tick_interval_s=0.1,
               detector_poll_s=0.05, tcp=True)
members = [(f"m{{i}}", p) for i, p in enumerate(peers.split(","))]
sid = next(s for s in members if s[1] == name)
api.start_server(sid, "mpc", SimpleMachine(lambda c, s: s + c, 0), members)
print("READY", flush=True)
if me == "driver":
    time.sleep(1.0)  # let peers come up
    # under full-suite load peers may take many seconds to import jax
    # and bind; keep triggering until a leader exists
    deadline = time.time() + 120
    while time.time() < deadline:
        api.trigger_election(sid)
        try:
            api.wait_for_leader("mpc", timeout=10)
            break
        except Exception:
            pass
    total = 0
    for i in range(1, 6):
        r, _ = api.process_command(sid, i, timeout=15, retry_on_timeout=True)
        total = r
    print("RESULT", total, flush=True)
    time.sleep(0.5)
else:
    deadline = time.time() + 30
    while time.time() < deadline:
        v = api.local_query(sid, lambda s: s, timeout=5)[1]
        if v == 15:
            print("CONVERGED", v, flush=True)
            break
        time.sleep(0.1)
"""


def test_multiprocess_cluster(tmp_path):
    """Three real OS processes, one member each, consensus over TCP."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ports = [free_port() for _ in range(3)]
    peers = ",".join(f"127.0.0.1:{p}" for p in ports)
    script = _WORKER.format(repo=repo)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    procs = []
    try:
        for i, port in enumerate(ports):
            role = "driver" if i == 0 else "follower"
            procs.append(
                subprocess.Popen(
                    [sys.executable, "-c", script, role, str(port), peers,
                     str(tmp_path / f"p{i}")],
                    stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                    env=env,
                )
            )
        # generous: three jax imports + elections on a contended 1-core
        # box (full-suite runs) need far more than the idle ~3s
        out0, err0 = procs[0].communicate(timeout=240)
        assert "RESULT 15" in out0, (out0, err0)
        out1, _ = procs[1].communicate(timeout=90)
        out2, _ = procs[2].communicate(timeout=90)
        assert "CONVERGED 15" in out1
        assert "CONVERGED 15" in out2
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


def test_tcp_rejects_unauthenticated_frames():
    """Frames without a valid cookie MAC must be dropped before pickle
    ever sees them (ADVICE r1: arbitrary unpickling from any peer)."""
    import pickle
    import struct
    import threading

    from ra_tpu.runtime.tcp import TcpTransport, _LEN

    got = []
    port = free_port()
    t = TcpTransport(
        f"127.0.0.1:{port}",
        lambda to, msg, frm: got.append((to, msg)) or True,
        cookie="secret-a",
    )
    try:
        # raw attacker frame: valid pickle, no/garbage MAC
        evil = pickle.dumps(("t0", None, ("pwn",)))
        s = socket.create_connection(("127.0.0.1", port), timeout=2)
        s.sendall(_LEN.pack(len(evil)) + evil)
        time.sleep(0.3)
        assert got == []
        # the connection was killed: a subsequent good-looking send fails
        # eventually (send buffer may absorb one write)
        dead = False
        try:
            for _ in range(20):
                s.sendall(_LEN.pack(len(evil)) + evil)
                time.sleep(0.02)
        except OSError:
            dead = True
        assert dead
        s.close()

        # frames sealed with the right cookie ARE delivered
        t2 = TcpTransport(
            f"127.0.0.1:{free_port()}",
            lambda to, msg, frm: True,
            cookie="secret-a",
        )
        try:
            assert t2.send(("t0", f"127.0.0.1:{port}"), ("hello",), None)
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and not got:
                time.sleep(0.02)
            assert got and got[0][1] == ("hello",)
        finally:
            t2.close()

        # ...but a transport with the WRONG cookie is rejected
        got.clear()
        t3 = TcpTransport(
            f"127.0.0.1:{free_port()}",
            lambda to, msg, frm: True,
            cookie="wrong-cookie",
        )
        try:
            t3.send(("t0", f"127.0.0.1:{port}"), ("intruder",), None)
            time.sleep(0.3)
            assert got == []
        finally:
            t3.close()
    finally:
        t.close()


def _mgmt_counter_factory(config):
    from ra_tpu.machine import SimpleMachine

    return SimpleMachine(lambda c, s: s + c, 0)


_MGMT_WORKER = '''
import sys, time
sys.path.insert(0, {repo!r})
sys.path.insert(0, {tests!r})
from ra_tpu import api
from ra_tpu.system import SystemConfig

port, data_dir = sys.argv[1], sys.argv[2]
name = "127.0.0.1:" + port
api.start_node(name, SystemConfig(name="mg", data_dir=data_dir),
               election_timeout_s=0.15, tick_interval_s=0.1,
               detector_poll_s=0.05, tcp=True)
print("READY", flush=True)
# idle until the parent is done managing us; report our server state
from ra_tpu.runtime.transport import registry
node = registry().get(name)
deadline = time.time() + 60
while time.time() < deadline:
    p = node.procs.get("m0")
    if p is not None and p.server.machine_state == 6:
        print("REMOTE_STATE", p.server.machine_state, flush=True)
        break
    time.sleep(0.1)
api.stop_node(name)
'''


def test_remote_management_over_tcp(tmp_path):
    """A cluster on a REMOTE process is assembled and operated entirely
    from this process via management RPCs (reference: rpc:call
    start/restart/delete, src/ra_server_sup_sup.erl:33-50)."""
    import os

    from ra_tpu import api
    from ra_tpu.system import SystemConfig

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tests = os.path.join(repo, "tests")
    remote_port = free_port()
    remote_name = f"127.0.0.1:{remote_port}"
    local_name = f"127.0.0.1:{free_port()}"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    child = subprocess.Popen(
        [sys.executable, "-c",
         _MGMT_WORKER.format(repo=repo, tests=tests),
         str(remote_port), str(tmp_path / "remote")],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
    )
    try:
        assert child.stdout.readline().strip() == "READY"
        api.start_node(local_name, SystemConfig(name="mg", data_dir=str(tmp_path / "local")),
                       election_timeout_s=0.15, tick_interval_s=0.1,
                       detector_poll_s=0.05, tcp=True)
        ids = [("m0", remote_name), ("m1", local_name)]
        # start the REMOTE member first — purely via the management RPC
        sid_remote = api.start_server(
            ids[0], "mgc", None, ids,
            machine_factory="test_tcp:_mgmt_counter_factory",
        )
        assert tuple(sid_remote) == ids[0]
        api.start_server(ids[1], "mgc", None, ids,
                         machine_factory="test_tcp:_mgmt_counter_factory")
        api.trigger_election(ids[1])
        # commands replicate across both processes
        r, _ = api.process_command(ids[1], 1, timeout=20, retry_on_timeout=True)
        r, _ = api.process_command(ids[1], 2, timeout=20, retry_on_timeout=True)
        assert r == 3
        # remote restart + overview over the management plane (before the
        # final command: the child exits once it observes state 6)
        restarted = api.restart_server(ids[0])
        assert tuple(restarted) == ids[0]
        ov = api.overview(remote_name)
        assert ov["node"] == remote_name
        r, _ = api.process_command(ids[1], 3, timeout=20, retry_on_timeout=True)
        assert r == 6
        out, err = child.communicate(timeout=60)
        assert "REMOTE_STATE 6" in out, (out, err)
    finally:
        if child.poll() is None:
            child.kill()
        try:
            api.stop_node(local_name)
        except Exception:
            pass


def test_tcp_node_alive_uses_phi_detector():
    """With a detector attached, pong arrivals drive an adaptive
    liveness window instead of the fixed pong timeout."""
    from ra_tpu.detector import PhiAccrualDetector
    from ra_tpu.runtime.tcp import TcpTransport

    a_port, b_port = free_port(), free_port()
    a = TcpTransport(f"127.0.0.1:{a_port}", lambda t, m, f: True)
    b = TcpTransport(f"127.0.0.1:{b_port}", lambda t, m, f: True)
    a.detector = PhiAccrualDetector(threshold=8.0)
    try:
        b_name = f"127.0.0.1:{b_port}"
        a.send(("x", b_name), ("hi",), None)  # dial: starts ping/pong
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not a.node_alive(b_name):
            time.sleep(0.05)
        assert a.node_alive(b_name)
        # detector has been fed by pong arrivals
        assert a.detector.phi(b_name) >= 0.0
        time.sleep(1.0)  # steady pongs keep phi low
        assert a.node_alive(b_name)
        b.close()  # pongs stop: adaptive suspicion flips liveness
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and a.node_alive(b_name):
            time.sleep(0.1)
        assert not a.node_alive(b_name)
    finally:
        a.close()
        try:
            b.close()
        except Exception:
            pass


def test_wire_unpickler_blocks_gadget_classes():
    """VERDICT r2 weak 7: a cookie holder must not get arbitrary code
    execution through pickle — only allowlisted protocol/payload types
    resolve on the wire."""
    import pickle as _p

    from ra_tpu.runtime import tcp as tcpmod
    from ra_tpu.protocol import AppendEntriesRpc, Command, Entry, USR

    # the protocol vocabulary round-trips
    rpc = AppendEntriesRpc(term=1, leader_id=("a", "n"), prev_log_index=0,
                           prev_log_term=0, leader_commit=0,
                           entries=(Entry(1, 1, Command(USR, ("put", "k", 1))),))
    out = tcpmod._wire_loads(_p.dumps(("a", ("b", "n"), rpc)))
    assert out[2].entries[0].cmd.data == ("put", "k", 1)
    # containers round-trip
    assert tcpmod._wire_loads(_p.dumps({1, 2})) == {1, 2}
    # a classic RCE gadget is rejected at find_class, never executed
    class Evil:
        def __reduce__(self):
            import os
            return (os.system, ("true",))

    with pytest.raises(Exception):
        tcpmod._wire_loads(_p.dumps(Evil()))
    # STACK_GLOBAL dotted-name traversal (protocol-4) must not tunnel
    # through an allowlisted module to arbitrary callables
    dotted = (b"\x80\x04" + b"\x8c\x0fra_tpu.protocol"
              + b"\x8c\x16dataclasses.sys.intern" + b"\x93"
              + b"\x8c\x03abc" + b"\x85" + b"R" + b".")
    with pytest.raises(_p.UnpicklingError, match="not allowlisted"):
        tcpmod._wire_loads(dotted)
    # module-level FUNCTIONS in allowlisted packages are not resolvable
    # (REDUCE could invoke them with attacker args)
    fnref = (b"\x80\x04" + b"\x8c\x0fra_tpu.protocol"
             + b"\x8c\x11sanitize_for_wire" + b"\x93"
             + b"\x8c\x03abc" + b"\x85" + b"R" + b".")
    with pytest.raises(_p.UnpicklingError, match="not allowlisted"):
        tcpmod._wire_loads(fnref)
    # snapshot-transfer bodies decode through the same allowlist
    from ra_tpu.log.snapshot import decode_snapshot_chunks

    with pytest.raises(Exception):
        decode_snapshot_chunks([_p.dumps(Evil())])
    assert decode_snapshot_chunks([_p.dumps({"k": 1})]) == {"k": 1}
    # registration opens the gate for application payload types
    blob = _p.dumps(_WirePayload(7))
    with pytest.raises(Exception):
        tcpmod._wire_loads(blob)
    tcpmod.register_wire_type(_WirePayload)
    try:
        assert tcpmod._wire_loads(blob).v == 7
    finally:
        unregister_wire_type(_WirePayload)


class _WirePayload:
    """Module-level so pickle can resolve it by reference."""

    def __init__(self, v):
        self.v = v


# -- the batch frame (docs/INTERNALS.md section 18; ISSUE 33) ----------------


class _Sink:
    """What a transport's owner gives it for its ``wire_*`` counters."""

    def __init__(self):
        self.v = {}

    def incr(self, field, n=1):
        self.v[field] = self.v.get(field, 0) + n


def _pair(batch_cb=True, **kw):
    """Two transports; ``got`` is what ``b`` delivered, as (name, msg,
    from_sid), and ``calls`` how many deliveries that took."""
    from ra_tpu.runtime.tcp import TcpTransport

    got, calls = [], []

    def deliver(to, msg, frm):
        got.append((to[0], msg, frm))
        calls.append(1)
        return True

    def deliver_batch(triples):
        got.extend((n, m, f) for n, f, m in triples)
        calls.append(len(triples))
        return 0

    def bound(deliver, **kw):
        for _ in range(8):  # a port taken between look and bind: again
            try:
                return TcpTransport(f"127.0.0.1:{free_port()}", deliver, **kw)
            except OSError:
                continue
        raise AssertionError("no free port")

    a = bound(lambda t, m, f: True, **kw)
    b = bound(deliver)
    if batch_cb:
        b.deliver_batch = deliver_batch
    a.counters, b.counters = _Sink(), _Sink()
    return a, b, got, calls


def _await(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline and not cond():
        time.sleep(0.01)
    return cond()


def _protocol_batch(a, b):
    from ra_tpu.protocol import (AppendEntriesReply, AppendEntriesRpc, Command,
                                 Entry, HeartbeatRpc, USR)

    body = bytes(range(256)) * 4  # a 1 KB body, as the fifo cell's
    aer = AppendEntriesRpc(
        term=3, leader_id=("q0", a.node_name), prev_log_index=7,
        prev_log_term=3, leader_commit=7,
        entries=(Entry(8, 3, Command(USR, ("enqueue", body),
                                     from_ref=lambda r: None, ts=123)),
                 Entry(9, 3, Command(USR, ("settle", ("consumer", 4), [8])))))
    ack = AppendEntriesReply(term=3, success=True, next_index=10,
                             last_index=9, last_term=3)
    hb = HeartbeatRpc(term=3, leader_id=("q2", a.node_name), query_index=5)
    return [
        (("q0", b.node_name), aer, ("q0", a.node_name)),
        (("q1", b.node_name), ack, ("q1", a.node_name)),
        (("q2", b.node_name), hb, None),  # from_sid of None
        (("q3", b.node_name), ("resync", [1, 2]), ("q3", a.node_name)),
    ]


@pytest.mark.parametrize("batch_cb", [True, False],
                         ids=["ingest_batch", "per_message_deliver"])
def test_batch_frame_round_trips_in_order(batch_cb):
    """One ``send_batch`` is one frame, one MAC, one outbox element; the
    receiver hands the decoded list to the batch callback in one call
    (a coordinator's ``ingest_batch``) or, for an owner without one (a
    ``RaNode``), feeds ``deliver`` from the same list, in order."""
    a, b, got, calls = _pair(batch_cb)
    try:
        msgs = _protocol_batch(a, b)
        assert a.send_batch(b.node_name, msgs) == 4
        assert _await(lambda: len(got) == 4)
        assert [n for n, _m, _f in got] == ["q0", "q1", "q2", "q3"]
        assert [f for _n, _m, f in got] == [
            ("q0", a.node_name), ("q1", a.node_name), None,
            ("q3", a.node_name)]
        aer = got[0][1]
        # reply handles and submit stamps do not cross (sanitize_for_wire)
        assert aer.entries[0].cmd.from_ref is None
        assert aer.entries[0].cmd.ts is None
        assert aer.entries[0].cmd.data == ("enqueue", bytes(range(256)) * 4)
        assert aer.entries[1].cmd.data == ("settle", ("consumer", 4), [8])
        assert got[1][1] == msgs[1][1] and got[2][1] == msgs[2][1]
        assert got[3][1] == ("resync", [1, 2])
        assert calls == ([4] if batch_cb else [1, 1, 1, 1])
        assert _await(lambda: b.counters.v.get("wire_frames_in") == 1)
        out, inn = a.counters.v, b.counters.v
        assert (out["wire_frames_out"], out["wire_msgs_out"]) == (1, 4)
        assert (inn["wire_frames_in"], inn["wire_msgs_in"]) == (1, 4)
        assert out["wire_bytes_out"] == inn["wire_bytes_in"] > 1024
        assert out["wire_encode_ns"] > 0 and inn["wire_decode_ns"] > 0
        assert inn.get("wire_dropped", 0) == 0 and a.dropped == b.dropped == 0
        # any authenticated frame is evidence of life
        assert a.node_name in b._last_heard
    finally:
        a.close()
        b.close()
    assert a.threads() == [] and b.threads() == []


def test_what_the_batch_callback_sheds_is_counted_as_dropped():
    a, b, got, _calls = _pair()
    b.deliver_batch = lambda triples: 3  # a full ingress lane sheds three
    try:
        assert a.send_batch(b.node_name, _protocol_batch(a, b)) == 4
        assert _await(lambda: b.dropped == 3)
        assert b.counters.v["wire_dropped"] == 3
        assert b.counters.v["wire_msgs_in"] == 4
    finally:
        a.close()
        b.close()


def _raw_batch_frame(a, b):
    from ra_tpu.protocol import sanitize_for_wire

    (wire, n), = a._batch_frames(
        [(to[0], frm, sanitize_for_wire(msg))
         for to, msg, frm in _protocol_batch(a, b)])
    assert n == 4
    return wire


@pytest.mark.parametrize("where", ["mac", "head", "body", "last_byte"])
def test_flipped_bit_in_a_batch_frame_delivers_none_of_it(where):
    """The MAC is checked before anything is decoded: a frame with one
    bit wrong closes the connection and not one of its messages is
    delivered."""
    from ra_tpu.runtime.tcp import _LEN, _MAC_LEN

    a, b, got, _calls = _pair()
    try:
        wire = bytearray(_raw_batch_frame(a, b))
        at = {"mac": _LEN.size + 3, "head": _LEN.size + _MAC_LEN + 5,
              "body": len(wire) // 2, "last_byte": len(wire) - 1}[where]
        wire[at] ^= 0x10
        host, port = b.node_name.rsplit(":", 1)
        s = socket.create_connection((host, int(port)), timeout=5)
        s.sendall(bytes(wire))
        s.settimeout(5)
        assert s.recv(1) == b""  # closed by the receiver
        s.close()
        assert got == [] and b.counters.v.get("wire_frames_in", 0) == 0
        # the untouched frame on a new connection is delivered whole
        s = socket.create_connection((host, int(port)), timeout=5)
        s.sendall(_raw_batch_frame(a, b))
        assert _await(lambda: len(got) == 4)
        s.close()
    finally:
        a.close()
        b.close()


def test_unregistered_type_in_a_batch_closes_the_connection_and_is_logged(
        caplog):
    a, b, got, _calls = _pair()
    try:
        msgs = _protocol_batch(a, b)
        msgs[2] = (msgs[2][0], _WirePayload(7), None)  # not allowlisted
        with caplog.at_level("ERROR", logger="ra_tpu"):
            assert a.send_batch(b.node_name, msgs) == 4
            assert _await(lambda: any(
                "register_wire_type" in r.getMessage() for r in caplog.records))
        assert got == []  # none of the frame's messages
        # the sender reconnects lazily; a clean batch then arrives
        assert _await(lambda: a.send_batch(b.node_name, _protocol_batch(a, b))
                      == 4 and _await(lambda: len(got) >= 4, 1.0))
    finally:
        a.close()
        b.close()


def test_a_batch_over_max_frame_is_split_and_arrives_whole(monkeypatch):
    from ra_tpu.runtime import tcp as tcpmod

    monkeypatch.setattr(tcpmod, "MAX_FRAME", 8192)
    a, b, got, calls = _pair()
    try:
        msgs = [((f"q{i}", b.node_name), ("blob", i, bytes(500)), None)
                for i in range(64)]
        msgs[10] = (("q10", b.node_name), ("blob", 10, bytes(9000)), None)
        assert a.send_batch(b.node_name, msgs) == 63  # one fits no frame
        assert _await(lambda: len(got) == 63)
        assert [m[1] for _n, m, _f in got] == [i for i in range(64) if i != 10]
        assert len(calls) == a.counters.v["wire_frames_out"] > 4
        assert a.counters.v["wire_msgs_out"] == 63
        assert a.dropped == 1 and a.counters.v["wire_dropped"] == 1
    finally:
        a.close()
        b.close()


def test_send_batch_declines_while_a_tcp_failpoint_is_armed():
    """With ``tcp.send`` / ``tcp.frame`` armed the per-message path runs,
    so fire and mangle keep their meaning frame by frame: the transport
    declines (-1) and a wired coordinator falls back to ``send``."""
    from ra_tpu import faults
    from ra_tpu.runtime.coordinator import BatchCoordinator
    from ra_tpu.runtime.transport import NodeRegistry

    a, b, got, calls = _pair()
    c = BatchCoordinator(f"127.0.0.1:{free_port()}", capacity=8,
                         nodes=NodeRegistry(), tcp=True)
    assert c.transport.threads()  # bound first of all, before any registry
    try:
        msgs = _protocol_batch(a, b)
        faults.arm("tcp.frame", ("torn", 0.5), ("always",))
        assert a.send_batch(b.node_name, msgs) == -1
        faults.disarm_all()
        faults.arm("tcp.send", ("raise", "eio"), ("always",))
        assert a.send_batch(b.node_name, msgs) == -1
        assert a.counters.v.get("wire_frames_out", 0) == 0
        # the coordinator's fan-out: every message through send(), where
        # the armed failpoint drops it and counts it
        c._send_batch_inline(b.node_name, msgs)
        assert c.transport.dropped == 4
        assert c.counters.get("wire_dropped") == 4
        assert c.counters.get("wire_frames_out") == 0
        faults.disarm_all()
        c._send_batch_inline(b.node_name, msgs)
        assert _await(lambda: len(got) == 4) and calls == [4]
        assert c.counters.get("wire_frames_out") == 1
    finally:
        faults.disarm_all()
        c.stop()
        a.close()
        b.close()


def test_an_outbox_at_its_cap_drops_the_batch_and_counts_its_messages():
    a, b, got, _calls = _pair(outbox_cap=0)  # never room for an element
    try:
        assert a.send_batch(b.node_name, _protocol_batch(a, b)) == 0
        assert a.dropped == 4 and a.counters.v["wire_dropped"] == 4
        assert a.counters.v.get("wire_frames_out", 0) == 0
        a.block(a.node_name, b.node_name)  # a blocked pair: per message too
        assert a.send_batch(b.node_name, _protocol_batch(a, b)) == 0
        assert a.dropped == 8 and a.counters.v["wire_dropped"] == 8
        time.sleep(0.1)
        assert got == []
    finally:
        a.close()
        b.close()


def test_a_large_frame_in_small_pieces_is_read_once(monkeypatch):
    """The reader appends what arrives and cuts its buffer once a recv,
    never once a frame: a 2 MB frame that trickles in, with small frames
    behind it, arrives whole and in order."""
    a, b, got, calls = _pair()
    try:
        big = [(("q0", b.node_name), ("blob", 0, bytes(2 << 20)), None)]
        small = [((f"q{i}", b.node_name), ("blob", i, b"x"), None)
                 for i in range(1, 4)]
        wire = b"".join(w for w, _n in a._batch_frames(
            [(to[0], frm, msg) for to, msg, frm in big]))
        for m in small:
            wire += b"".join(w for w, _n in a._batch_frames(
                [(m[0][0], m[2], m[1])]))
        host, port = b.node_name.rsplit(":", 1)
        s = socket.create_connection((host, int(port)), timeout=5)
        for i in range(0, len(wire), 100_000):
            s.sendall(wire[i:i + 100_000])
        assert _await(lambda: len(got) == 4)
        assert [m[1] for _n, m, _f in got] == [0, 1, 2, 3]
        assert len(got[0][1][2]) == 2 << 20 and calls == [1, 1, 1, 1]
        s.close()
    finally:
        a.close()
        b.close()
