"""Batch coordinator: many raft groups stepped together on the device.

The framework's north-star execution backend (``server_impl =
"tpu_batch"``): instead of one actor per group, one coordinator owns the
consensus decision state of *all* its groups as device arrays
(``ra_tpu.ops.consensus.GroupState``) and advances them in fused steps —
one ``consensus_step`` call classifies up to one inbound message per
group and runs every group's quorum scan at once.

Division of labor (keeps host<->device traffic to one egress struct per
step):

- **device (authoritative)**: current_term, voted_for, role, votes,
  match_index, commit_index, log-tail bookkeeping + recent-term ring;
- **host (authoritative)**: log *contents* (WAL/memtable/segments),
  machine apply, client replies, outbound AER construction with its own
  ``next_index`` bookkeeping (host routes every inbound reply anyway, so
  both sides update their own variables from the same messages — no
  gathers needed);
- **rare paths** (election initiation, deep-backfill term lookups) run
  host-side against the post-step egress mirror, re-entering the device
  via scatters (``set_roles``/``record_appended``) and mailbox term
  overrides.

The coordinator registers in the node registry and speaks the same
transport/protocol as per-group ServerProcs, so batch-backed and
actor-backed members interoperate in one cluster. Replies leaving a step
are batched per destination node — thousands of groups' traffic rides
single transport hops.

Snapshot install/send for batch-backed groups is fully implemented:
``_receive_snapshot_chunk`` runs the 4-phase chunked accept (init/pre/
next/last) host-side and scatters the new floor to the device;
``_start_snapshot_sender`` spools + streams outbound transfers through
the shared ``SnapshotSender`` (see ``ra_tpu/runtime/proc.py``); batch-
and actor-backed members interoperate in either direction.
"""

from __future__ import annotations

import logging
import operator
import pickle
import random
import threading
import time
from bisect import bisect_left
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ra_tpu import effects as fx
from ra_tpu import faults
from ra_tpu import leaderboard
from ra_tpu import native as _native
from ra_tpu import obs as _obs
from ra_tpu.log.api import LogApi
from ra_tpu.log.memory import MemoryLog
from ra_tpu.machine import Machine, normalize_apply_result
from ra_tpu.ops import consensus as C
from ra_tpu.protocol import (
    AppendEntriesReply,
    AppendEntriesRpc,
    CHUNK_INIT,
    CHUNK_LAST,
    CHUNK_NEXT,
    CHUNK_PRE,
    Command,
    ElectionTimeout,
    TimeoutNow,
    Entry,
    FromPeer,
    HeartbeatReply,
    HeartbeatRpc,
    InstallSnapshotAck,
    InstallSnapshotResult,
    InstallSnapshotRpc,
    LOSSY_PROTOCOL_TYPES,
    LogRead,
    NOOP,
    RC_BATCH,
    RC_CMD,
    RC_CMD_LOW,
    RC_MSG,
    REJECT_NOSPACE,
    REJECT_OVERLOADED,
    PreVoteResult,
    PreVoteRpc,
    RA_CLUSTER_CHANGE,
    RA_JOIN,
    RA_LEAVE,
    RequestVoteResult,
    RequestVoteRpc,
    ServerId,
    USR,
)
from ra_tpu.runtime import heap as _heap
from ra_tpu.runtime.monitors import Monitors
from ra_tpu.runtime.tcp import CLIENT_REPLY, RemoteRef
from ra_tpu.runtime.transport import InProcTransport, NodeRegistry, registry as node_registry

logger = logging.getLogger("ra_tpu")

MSG_OF_TYPE = {
    AppendEntriesRpc: C.MSG_AER,
    AppendEntriesReply: C.MSG_AER_REPLY,
    RequestVoteRpc: C.MSG_VOTE_REQ,
    RequestVoteResult: C.MSG_VOTE_REPLY,
    PreVoteRpc: C.MSG_PREVOTE_REQ,
    PreVoteResult: C.MSG_PREVOTE_REPLY,
}

_NATIVE_PATHS = frozenset(("pack", "classify"))


def parse_native(spec) -> frozenset:
    """Parse a ``--native`` spec into the set of enabled native
    hot-loop paths: ``"auto"``/``"on"``/``True`` enable both,
    ``"off"``/``"none"``/``False`` none, anything else a comma list
    over {pack, classify} (docs/INTERNALS.md §18)."""
    if spec is True or spec in ("auto", "on", "all"):
        return _NATIVE_PATHS
    if not spec or spec in ("off", "none"):
        return frozenset()
    parts = frozenset(p.strip() for p in str(spec).split(",") if p.strip())
    unknown = parts - _NATIVE_PATHS
    if unknown:
        raise ValueError(f"unknown native paths {sorted(unknown)}")
    return parts


@jax.jit
def _health_copies(st):
    """Fresh device copies of the carry's rows ``_health_scan`` reads,
    in one dispatch (the detector thread pays for each entry into JAX
    with a wait for the interpreter lock): the first 6 + P int rows and
    the P ``active`` rows, one slice a block (``C.STATE_I32_ROWS``)."""
    p = st.flags.shape[0] // len(C.STATE_BOOL_ROWS)
    return st.ints[:6 + p], st.flags[:p]


class GroupMirrors:
    """Per-group facts as flat ``(capacity,)`` / ``(capacity, P)``
    arrays, one row a group id: what the detector thread judges every
    group by, read as masks so that its Python runs only for the rows a
    mask leaves (docs/INTERNALS.md §14). Each has one writer:
    ``contact`` and ``role`` the ``GroupHost`` properties, ``last_ack``
    the host's own row view, ``peers`` ``_sync_peer_row``, ``pending``
    ``_handle_commands`` (set) and the lane watchdog (cleared)."""

    __slots__ = ("contact", "role", "last_ack", "pending", "peers")

    FREE = -1  # the role of a row no group holds: no mask passes it

    def __init__(self, capacity: int, num_peers: int):
        self.contact = np.zeros(capacity, np.float64)  # last_contact
        self.role = np.full(capacity, self.FREE, np.int8)
        # last AER ack per slot, 0.0 for never (a leader's silent peers)
        self.last_ack = np.zeros((capacity, num_peers), np.float64)
        # 1 while pending_replies may hold client futures
        self.pending = np.zeros(capacity, np.uint8)
        # slots that hold a member other than this node's own
        self.peers = np.zeros((capacity, num_peers), bool)


class GroupHost:
    """Host-side companion of one device-resident group."""

    __slots__ = (
        "gid", "name", "cluster_name", "members", "self_slot", "log",
        "machine", "machine_state", "last_applied", "_role", "term",
        "leader_slot", "next_index", "commit_sent", "pending_replies",
        "inbox", "host_term_hint", "election_ref", "effective_machine_version",
        "pending_ack", "snap_accept", "snap_senders", "pre_vote_token",
        "voter_status", "cluster_change_permitted", "cluster_index",
        "pending_queries", "machine_timers", "has_tick", "snap_floor",
        "noop_index", "noop_committed", "query_seq", "cluster_history",
        "last_ack", "aux_state", "aux_inited", "low_q",
        "specials", "last_ok_sent", "fresh_tail", "match_hint", "lat",
        "_clock", "fresh_anchor", "fresh_ts", "lease_contact", "_mirrors",
    )

    def __init__(self, gid, name, cluster_name, members, self_slot, log, machine,
                 mirrors, clock=None):
        from ra_tpu.runtime.clock import WALL

        self._clock = clock or WALL
        self.gid = gid
        # this row of the coordinator's per-group arrays (the detector
        # reads them as masks): a new occupant starts them afresh
        self._mirrors = mirrors
        mirrors.pending[gid] = 0
        self.name = name
        self.cluster_name = cluster_name
        self.members: List[ServerId] = list(members)
        self.self_slot = self_slot
        self.log: LogApi = log
        self.machine: Machine = machine
        self.machine_state = machine.init({"name": cluster_name})
        self.effective_machine_version = 0
        self.last_applied = 0
        self.role = C.R_FOLLOWER
        self.term = 0
        self.leader_slot = -1
        self.next_index = [1] * len(self.members)
        self.commit_sent = [0] * len(self.members)
        self.pending_replies: Dict[int, Any] = {}
        self.inbox: deque = deque()
        self.host_term_hint: Optional[Tuple[int, int]] = None
        self.election_ref = None
        # deferred AER ack awaiting WAL durability: (leader_sid, up_to_idx)
        self.pending_ack: Optional[Tuple[ServerId, int]] = None
        # inbound snapshot transfer state / outbound senders per peer
        self.snap_accept: Optional[Dict[str, Any]] = None
        self.snap_senders: Dict[ServerId, Any] = {}
        # host mirror of the device pre-vote round token (incremented in
        # lockstep with every set_roles(R_PRE_VOTE) scatter)
        self.pre_vote_token = 0
        # membership: voter status per slot ("voter" | ("nonvoter", tgt));
        # tombstoned slots hold None in self.members. One cluster change
        # in flight at a time (Raft one-at-a-time rule).
        self.voter_status: Dict[int, Any] = {
            i: "voter" for i in range(len(self.members))
        }
        self.cluster_change_permitted = True
        self.cluster_index = 0  # log index of the latest cluster change
        # consistent queries awaiting a leadership-confirmation quorum:
        # [{"qi": idx, "fn": fn, "fut": fut, "acks": set()}]
        self.pending_queries: List[Dict[str, Any]] = []
        self.machine_timers: Dict[Any, Any] = {}
        # a versioned container may delegate tick to its modules: check
        # the effective module as well as the container itself
        self.has_tick = (
            type(machine).tick is not Machine.tick
            or type(machine.which_module(machine.version())).tick
            is not Machine.tick
        )
        self.snap_floor = 0  # device-known snapshot floor (host mirror)
        # current-term-commit gate: a new leader may neither change
        # membership nor serve linearizable reads until its own noop has
        # committed (Raft read-index rule; reference: post_election
        # noop + cluster_change_permitted, src/ra_server.erl:4028-4064)
        self.noop_index = 0
        self.noop_committed = True  # groups start pre-election
        self.query_seq = 0
        # rollback snapshots for write-time cluster adoption: an
        # uncommitted change adopted from a dead leader must be undone
        # when a new leader truncates that suffix.
        # [(entry_index, members_copy, voter_status_copy), ...]
        self.cluster_history: List[Tuple[int, List, Dict[int, Any]]] = []
        # per-slot monotonic time of the last AER ack (leader-side), 0.0
        # for "never": this group's row of the coordinator's (capacity,
        # P) table, the only copy. Drives the periodic resync of silent
        # peers
        self.last_ack = mirrors.last_ack[gid]
        self.last_ack[:] = 0.0
        # aux machine state (initialized lazily on first aux message)
        self.aux_state: Any = None
        self.aux_inited = False
        # monotonic time of the last leader contact (AER / heartbeat /
        # snapshot chunk). The leader's silent-peer resync probe runs
        # every 2 ticks, so on this backend "no contact for several
        # ticks" is a reliable leaderless signal — the detector uses it
        # to retry elections after partition heals (a stalled pre-vote
        # or a deposed-leader cluster would otherwise wedge forever)
        self.last_contact = self._clock.monotonic()
        # buffered low-priority commands, drained in bounded slices
        # after normal traffic (reference: ra_ets_queue lane,
        # src/ra_server_proc.erl:507-530)
        self.low_q: deque = deque()
        # ascending log indexes holding non-USR commands (noops, cluster
        # changes). Tracked at append/write time so the apply loop can
        # take the batched fast path without scanning every entry; kept
        # exhaustive by the truncation/snapshot paths.
        self.specials: List[int] = []
        # last success ack shipped to a leader: (sid, term, last_index,
        # monotonic time). An identical re-ack within one tick interval
        # is suppressed — the pipeline's commit-sync AER round otherwise
        # triggers a reply that tells the leader nothing new. The time
        # bound keeps the leader's silent-peer resync probe honest: a
        # probe after 2 quiet ticks always gets a fresh ack.
        self.last_ok_sent: Optional[Tuple[ServerId, int, int, float]] = None
        # entries appended by THIS step's _handle_commands, passed
        # through to _send_aers so the steady-state AER build skips the
        # log re-read: (first_idx, prev_term, term, [Entry, ...]).
        # Valid only within one step; _send_aers always clears it.
        self.fresh_tail: Optional[Tuple[int, int, int, list]] = None
        # leader-side CONFIRMED replication point per slot (from AER
        # success replies) — the host mirror the pipeline window is
        # enforced against (next_index advances optimistically at send
        # time; match_hint only on acks, mirroring the reference's
        # match_index in its Next - Match <= ?MAX_PIPELINE_COUNT gate,
        # src/ra_server.erl:2308-2329)
        self.match_hint: List[int] = [0] * len(self.members)
        # in-flight commit-latency sample (obs.COMMIT_STAGES): at most
        # one per group, [idx, t_submit, t_append, t_durable, t_commit]
        # in monotonic ns. Only sampled groups (gid & lat_mask == 0)
        # for commands carrying a submit ts ever allocate one.
        self.lat: Optional[list] = None
        # staleness-bounded follower reads (docs/INTERNALS.md §20):
        # fresh_ts is the newest leader wall-clock stamp whose commit
        # point this replica has fully applied; fresh_anchor holds a
        # (leader_commit, commit_ts) pair still waiting for apply to
        # catch up. lease_contact is the leader-contact stamp backing
        # the stickiness promise (AER/heartbeat/snapshot only — NOT
        # the election-suspicion last_contact, which also restarts on
        # role changes and vote grants).
        self.fresh_anchor: Tuple[int, float] = (0, 0.0)
        self.fresh_ts = 0.0
        self.lease_contact = 0.0

    # ``role`` keeps its scalar for the wave threads' many reads; the
    # setter is the one writer of both it and the detector's mirror.
    # ``last_contact`` lives in the mirror alone.
    role = property(operator.attrgetter("_role"))

    @role.setter
    def role(self, role: int) -> None:
        self._role = role
        self._mirrors.role[self.gid] = role

    @property
    def last_contact(self) -> float:
        return float(self._mirrors.contact[self.gid])

    @last_contact.setter
    def last_contact(self, t: float) -> None:
        self._mirrors.contact[self.gid] = t

    def slot_of(self, sid: ServerId) -> int:
        try:
            return self.members.index(sid)
        except ValueError:
            return -1

    def sid_of(self, slot: int) -> Optional[ServerId]:
        if 0 <= slot < len(self.members):
            return self.members[slot]
        return None


class _SpanLock:
    """The coordinator's state lock as a wave thread takes it: the wait
    is a span in the profiler's trace, and ``t_held`` is the
    ``perf_counter_ns`` at which the lock was got (the end of the wave
    sub-phase ``step_lock_wait``)."""

    __slots__ = ("_lock", "_span", "_node", "t_held")

    def __init__(self, lock, span_name: str, node: str):
        self._lock = lock
        self._span = span_name
        self._node = node
        self.t_held = 0

    def __enter__(self):
        if _obs.tracing():
            with _obs.span(self._span, node=self._node):
                self._lock.acquire()
        else:
            self._lock.acquire()
        self.t_held = time.perf_counter_ns()

    def __exit__(self, *exc):
        self._lock.release()


class BatchCoordinator:
    """Hosts up to ``capacity`` groups on one node, device-stepped."""

    # the thread-CPU accounts (counters ``cpu_ns_*``) read
    # ``time.thread_time_ns()`` on one turn in 2**_CPU_SAMPLE_SHIFT and
    # book that turn's readings times as much: the clock is a system
    # call, 16 us a read beside the wave loop's work on the v5e's host
    # (PERF.md section 6, PR 24)
    _CPU_SAMPLE_SHIFT = 4
    # the lane watchdog's deadline follows the coordinator's own wave
    # (drain start -> step realised, smoothed) where waves are long: a
    # command is committed by the leader's pass, a follower's and the
    # leader's again, with a WAL batch and a sender's turn between, and
    # at each hand-off it can miss a whole wave. Measured in the
    # saturated 10,240 x 3 fleet (PERF.md section 6, PR 26: eleven 40 s
    # runs, gauges ``lane_stall_max_ms`` / ``lane_deadline_ms``): the
    # smoothed wave is 0.75-0.93 s and dips to 0.54 s between the
    # bursts of the closed loop; the median commit takes 4.7 s; the
    # watchdog sees the slowest lane of a run still for 3.2-4.8 s, and
    # in two runs of eleven for 5.2 and 6.2 s, lanes that then moved by
    # themselves: slow, not wedged, yet past ``command_deadline_s`` =
    # 5 s (four such were struck in one earlier run in thirteen). 6.2 s
    # at the dip is 11.5 waves; 16 leaves a third of room. The stretch
    # ends at ``_WEDGE_STRETCH_MAX`` configured deadlines: strike 2, the
    # client redirect, then comes at four, inside a client's reply
    # time-out, and a coordinator whose waves grow cannot talk its
    # detector out of detecting. At light load a wave is milliseconds
    # and the deadline is the configured one
    _WEDGE_WAVES = 16
    _WEDGE_STRETCH_MAX = 2.0

    def __init__(
        self,
        node_name: str,
        capacity: int = 1024,
        num_peers: int = 3,
        nodes: Optional[NodeRegistry] = None,
        aer_batch_size: int = 128,
        election_timeout_s: float = 0.15,
        detector_poll_s: float = 0.1,
        meta=None,
        idle_sleep_s: float = 0.0005,
        tick_interval_s: float = 1.0,
        send_msg_cb=None,
        mesh=None,
        active_set: str = "auto",
        max_pipeline_count: int = 4096,
        max_command_backlog: int = 4096,
        command_deadline_s: float = 5.0,
        ingress_ring_slots: int = 8192,
        native: str = "auto",
        clock=None,
        lease: bool = False,
        lease_safety_factor: float = 0.8,
        lease_drift_epsilon_s: float = 0.002,
        tcp: bool = False,
    ):
        from ra_tpu.runtime.clock import WALL

        # behavioral clock seam (docs/INTERNALS.md §19): election/resync
        # windows, contact stamps and the tick cadence read this clock;
        # the monotonic_ns() latency-histogram stamps below intentionally
        # stay on the wall clock (they measure real host time and the
        # simulation plane never drives this backend)
        self.clock = clock or WALL
        self.name = node_name
        if tcp:
            # first of all: a port that is taken raises here, before
            # anything of this node is registered anywhere
            from ra_tpu.runtime.tcp import TcpTransport

            self.transport = TcpTransport(node_name, self.deliver)
        self.capacity = capacity
        self.P = num_peers
        self.aer_batch_size = aer_batch_size
        self.election_timeout_s = election_timeout_s
        self.meta = meta
        self.idle_sleep_s = idle_sleep_s
        self.tick_interval_s = tick_interval_s
        self.send_msg_cb = send_msg_cb
        # flow control: per-peer AER pipeline window (reference:
        # ?MAX_PIPELINE_COUNT, src/ra_server.hrl:8), per-group client
        # admission window against apply progress, and the command-lane
        # watchdog deadline (accepted command with no commit progress
        # for this long -> detected wedge, recovery, bounded failure)
        self.max_pipeline_count = max_pipeline_count
        self.max_command_backlog = max_command_backlog
        self.command_deadline_s = command_deadline_s
        from ra_tpu import counters as _counters
        from ra_tpu import health as _health
        from ra_tpu.li import LeakyIntegrator

        self.counters = _counters.new(
            ("coordinator", node_name), _counters.COORDINATOR_FIELDS
        )
        # wave-phase + commit-stage histograms (docs/INTERNALS.md §13)
        # and the flight recorder; per-node histogram names so batch-
        # and actor-backed members on one node share a commit family
        self._wave_h = _obs.wave_hists(node_name)
        self._commit_h = _obs.commit_hists(node_name)
        self._obs_rec = _obs.flight_recorder()
        # per-group health scanner (docs/INTERNALS.md §14): fed once
        # per tick from the detector thread with ONE device fetch over
        # the existing mirrors — never from the step loop
        self._health = _health.register(
            node_name, backend="tpu_batch", capacity=max(64, capacity)
        )
        # storage-pressure plane (docs/INTERNALS.md §21): the harness /
        # embedding application drives enter/exit from its WAL-failure
        # classification and watermark accounting; the coordinator
        # consults it at admission and when granting snapshot credits
        from ra_tpu.pressure import StoragePressure

        self.pressure = StoragePressure(node_name)
        self.snapshot_credit_window = 4
        self._hslots: List[int] = []  # gid -> scanner slot
        # commit-latency sampling mask: groups with gid & mask == 0 are
        # eligible (bounds hot-path cost to ~1/64 of groups); _lat_gids
        # tracks the gids with a sample in flight so per-step sweeps
        # (the durable-watermark check) cost nothing when none is
        self._lat_mask = 63
        self._lat_gids: set = set()
        # aggregate commit-rate gauge over all groups (the batch-backend
        # analog of the per-proc ra_li integrator), sampled per tick
        self._commit_li = LeakyIntegrator()
        self._commit_li_prev: Optional[Tuple[float, int]] = None
        # activity-scaled stepping: "auto" runs the fused step over a
        # compact gather of just the groups with pending device work
        # whenever they number at most capacity/4 (power-of-two padded
        # sub-batches), falling back to the full-width step at
        # saturation; "always"/"never" pin a path (tests). Step
        # cost then scales with ACTIVITY, not capacity — a lone commit
        # round trip at 10k-group capacity no longer pays ~10 full-width
        # steps (the reference's per-group process wakes only on
        # messages: src/ra_server_proc.erl:457-530).
        if active_set not in ("auto", "always", "never"):
            raise ValueError(f"unknown active_set mode {active_set!r}")
        self.active_set = active_set

        self.state = C.make_group_state(capacity, num_peers)
        # groups not yet registered must never act: mark inactive
        self.state = self.state._replace(
            active=jnp.zeros((capacity, num_peers), dtype=jnp.bool_),
            voting=jnp.zeros((capacity, num_peers), dtype=jnp.bool_),
        )
        # multi-chip: shard the GROUP axis of all consensus state over
        # the mesh (the carry's rows ride along unsharded). Every group's
        # decision math is independent, so the fused step partitions
        # with zero cross-device communication; host scatters address
        # groups by id and GSPMD routes them. The state is re-pinned to
        # the sharding before each fused step (host-side single-row
        # updates may produce replicated layouts).
        self._shard_state = self._shard_mbox = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            n_dev = mesh.devices.size
            if capacity % n_dev:
                raise ValueError(
                    f"capacity {capacity} not divisible by mesh size {n_dev}"
                )
            # the group axis shards over EVERY mesh axis — a 2-D mesh
            # (e.g. ici x dcn) still engages all devices instead of
            # silently replicating over the unnamed axes
            axes = tuple(mesh.axis_names)
            # the carry's blocks, like the mailbox, hold groups on
            # their last axis
            self._shard_state = self._shard_mbox = NamedSharding(
                mesh, PartitionSpec(None, axes))
            self.state = jax.device_put(self.state, self._shard_state)
        self.groups: List[Optional[GroupHost]] = [None] * capacity
        self.by_name: Dict[str, GroupHost] = {}
        self.n_groups = 0

        # async command plane (docs/INTERNALS.md §16): per-producer-
        # thread lock-free SPSC ingress rings, drained in one batched
        # multi-lane pass by the step thread. No sender ever contends
        # with the step loop; the step thread blocks on _wake (an
        # Event set by every publish / WAL notify / egress realisation)
        # instead of 50 ms timed polls.
        from ra_tpu.rings import IngressRings, WaitGate

        self._wake = threading.Event()
        self._rings = IngressRings(lane_slots=ingress_ring_slots,
                                   wake=self._wake)
        # ring-full backpressure gate: opened on every drain that freed
        # space; ring-full-rejected clients wait on it instead of
        # sleeping (the ingress analog of the per-group admission gate)
        self._ring_gate = WaitGate()
        # admission-window gate: opened whenever apply progress releases
        # window room; admission-rejected clients park a waiter on it
        # (api.process_command) instead of a fixed 10 ms sleep poll
        self._adm_gate = WaitGate()
        # idents of the threads that DRAIN the rings (the step thread,
        # or whichever thread is inside a ``step_once`` call): a
        # full-ring publish from one of these must divert to
        # _internal_q — gate-waiting would deadlock on itself
        self._drainer_idents: set = set()
        # replies to callers on other nodes (RemoteRef) issued by the
        # drainer thread this turn, by caller node: [(corr, value)]
        self._client_out: Dict[str, List] = {}
        # reusable drain scratch (step-thread only)
        self._drain_buf: List = []
        # must-deliver self-publishes from the coordinator's own step
        # thread (machine Append/Aux effects): a blocking ring
        # publish from the drainer thread would deadlock, so they ride
        # this state-lock-guarded queue into the next drain instead
        self._internal_q: deque = deque()
        # machine monitors of the groups led here (Monitor / Demonitor
        # effects, ``process_down``): watcher = the group's server id
        self.monitors = Monitors()
        # effects realised since the last flush, under the state lock:
        # [clock ns inside _realise_effects, send_msg, other non-log
        # effects, release cursors seen, of those that cut a snapshot,
        # monitors armed, effects handed in]; _finish_ticket books them
        # once a step
        self._fx_acc = [0, 0, 0, 0, 0, 0, 0]
        # under a profiler session (_fx_tr) the step's effect stretch is
        # one span, opened at its first effect
        self._fx_tr = False
        self._fx_span = None
        # must-deliver overflow from FOREIGN threads (peer coordinator
        # step/egress/WAL threads, detector timers) whose publish hit a
        # full lane: never dropped, and never gate-waited either — a
        # peer's drainer thread parked on OUR ring gate while we park
        # on ITS gate is a distributed deadlock. Tiny leaf lock (never
        # nested inside any other), folded first by _drain_classify so
        # overflow items keep their arrival seniority.
        self._overflow_q: deque = deque()
        self._overflow_codes: deque = deque()  # RC_* sidecar, in step
        self._overflow_lock = threading.Lock()
        # native hot-loop runtime switches (docs/INTERNALS.md §18):
        # requested paths resolved against what actually loaded. Every
        # native path keeps the byte-identical Python fallback and
        # routes around itself while ANY failpoint is armed, so the
        # nemesis plane always exercises the Python fault seams.
        paths = parse_native(native)
        eps = _native.entry_points() if paths else {}
        self.native = native
        self._nat_pack = "pack" in paths and eps.get("pack", False)
        self._nat_classify = "classify" in paths and eps.get("classify", False)
        self._drain_codes = bytearray()  # classify sidecar scratch
        self._low_dirty: set = set()  # gids with buffered low-priority cmds
        # staged device scatters, coalesced until the next dispatch:
        # appended runs per gid as [[lo, hi, term], ...] chronological,
        # durable watermarks as gid -> max idx. The drain and the WAL
        # writers fold straight into these; the dispatch consumes them
        # with zero re-merging.
        self._staged_app: Dict[int, List[List[int]]] = {}
        self._staged_written: Dict[int, int] = {}
        # pre-zeroed full-width mailbox buffer, zeroed while the device
        # computes a step (the next dispatch packs into it with no
        # take/zero cost)
        self._spare_mbox: Optional[np.ndarray] = None
        # prezero only while full-width steps are the live shape (the
        # active-set sub path zeroes tiny buffers — not worth staging)
        self._prezero_useful = False
        # dedicated egress sender thread (started loop only; a
        # coordinator driven by ``step_once`` sends inline): AER/ack
        # fan-out hands (node, msgs) batches to a bounded ring consumed
        # off the step loop; overflow falls back to inline send
        self._egress_on = False
        self._egress_wake = threading.Event()
        self._egress_rings = IngressRings(lane_slots=4096,
                                          wake=self._egress_wake)
        self._sender_thread: Optional[threading.Thread] = None
        # clock-bound leader leases, vectorized over the group axis
        # (docs/INTERNALS.md §20): per-slot oldest-outstanding-send
        # stamps and credited ack bases, folded into a (G,) expiry
        # column by _lease_refresh over just the dirty gids. Off by
        # default — leader stickiness changes election behavior.
        from ra_tpu.lease import LeaseConfig

        self.lease_cfg = LeaseConfig(
            enabled=lease, election_timeout_s=election_timeout_s,
            safety_factor=lease_safety_factor,
            drift_epsilon_s=lease_drift_epsilon_s,
        )
        self._lease_sent = np.zeros((capacity, num_peers), np.float64)
        self._lease_basis = np.zeros((capacity, num_peers), np.float64)
        self._lease_expiry = np.zeros(capacity, np.float64)
        self._lease_voters = np.zeros((capacity, num_peers), bool)
        self._lease_quorum = np.zeros(capacity, np.int64)
        self._lease_self = np.zeros(capacity, np.int64)
        self._lease_renew_t = np.zeros(capacity, np.float64)
        self._lease_dirty: set = set()
        self._stale_h = None  # lazy follower_read_staleness histogram
        # role transitions queued by rare paths, applied as ONE scatter
        # at the start of the next step (an election storm over many
        # groups must not pay one jitted scatter per group)
        self._pending_roles: List[Tuple[int, int]] = []
        self._hot: set = set()  # gids with queued inbox msgs / term hints
        self._applied_np = np.zeros(capacity, np.int64)  # last_applied mirror
        # what the detector reads as masks (GroupMirrors), under the
        # names its passes use; node names any group's members live on
        # (a dead leader's node need not be one the registry still has)
        self._mirrors = GroupMirrors(capacity, num_peers)
        self._contact_np = self._mirrors.contact
        self._role_np = self._mirrors.role
        self._last_ack_np = self._mirrors.last_ack
        self._pending_np = self._mirrors.pending
        self._peer_np = self._mirrors.peers
        self._peer_nodes: set = set()
        self._tick_gids: List[int] = []  # groups whose machine has a tick
        # mailbox pack buffers, pooled (docs/INTERNALS.md §15): a build
        # hands back the numpy buffer itself and the jitted step takes
        # it as its argument; the buffer returns to the pool only after
        # that step's egress sync (np.asarray) proves the program ran,
        # which also holds where the backend aliases it. ``step_once``
        # cycles one buffer; the started loop at full width two, the
        # step's and the spare it zeroes while the device computes.
        self._mbox_pool: List[np.ndarray] = []
        # tickets that _drain_and_dispatch made and _realise has not
        # finished (step thread only). Both drivers realise a turn's
        # ticket before the next ingest, so the host mirrors an ingest
        # reads are the device state; a read's rare messages are
        # handled there only while this is 0 (docs/INTERNALS.md §13)
        self._unrealised = 0
        # the answers those early rare messages give callers in this
        # process, held while _holding until the turn ends, where every
        # other reply of the turn is set (_turn_replies)
        self._holding = False
        self._held: List = []
        # guards self.state (donated buffers!) between the step thread and
        # add_group callers
        self._state_lock = threading.Lock()
        # the same lock as the wave threads take it: the wait is a span
        self._step_lock = _SpanLock(
            self._state_lock, "ra/step/lock_wait", node_name)
        self._egress_lock = _SpanLock(
            self._state_lock, "ra/egress/lock_wait", node_name)
        self._wal_lock = _SpanLock(
            self._state_lock, "ra/wal/batch/notify/lock_wait", node_name)
        # turns of the step loop, and which of them read the thread clock
        self._cpu_turn = 0
        self._cpu_mask = (1 << self._CPU_SAMPLE_SHIFT) - 1
        # a wave's duration on ``self.clock`` (the watchdog's clock),
        # smoothed over eight: one float, written by the step thread
        # and read by the detector's
        self._wave_s = 0.0

        self.registry = nodes or node_registry()
        if tcp:
            # real sockets, as RaNode's ``tcp``: the name is
            # "host:port", peers are other processes (or coordinators
            # with a registry of their own) and are NOT found in
            # ``self.registry``; a batch frame's list comes in through
            # ``ingest_batch``, one ring slot a frame
            from ra_tpu.detector import PhiAccrualDetector

            self.transport.deliver_batch = self.ingest_batch
            self.transport.counters = self.counters
            # (a pong that is a detector tick late is no evidence of
            # death: the threads that carry it wait for the interpreter
            # lock behind the waves'; a dead process shows at once, as a
            # failed write)
            self.transport.detector = PhiAccrualDetector(
                owner=node_name, min_std=max(0.1, tick_interval_s / 2))
            self.transport.on_proc_down_cb = self.process_down
        else:
            self.transport = InProcTransport(node_name, self.registry)
        self._wired = tcp
        self.running = True
        self.registry.register(node_name, self)
        self.steps = 0
        self.sub_steps = 0  # steps taken on the active-set (sub) path
        # sharded steps that found a state array off the mesh layout and
        # had to move it back first (0 while the scatters keep it)
        self.shard_moves = 0
        self.msgs_processed = 0

        # the wave loop (docs/INTERNALS.md §15): one turn, ``_turn``,
        # dispatches a step and realises it on the same thread. The
        # started loop (``_run``) and ``step_once`` (the tests' driver)
        # both call it; callers must not mix the two on one coordinator.
        # No thread realises steps apart from the step thread: the
        # attribute stays, None, for callers that join what is there
        self._egress_thread: Optional[threading.Thread] = None
        self._step_thread = threading.Thread(
            target=self._run, name=f"ra-batch-{node_name}", daemon=True
        )
        self._node_status: Dict[str, bool] = {}
        self._detector_poll_s = detector_poll_s
        self._detector = threading.Thread(
            target=self._detect_loop, name=f"ra-batch-det-{node_name}", daemon=True
        )
        # detector passes that raised (the loop keeps running; the first
        # one is logged with its traceback)
        self.detector_errors = 0
        self._started = False

    # -- node-registry interface (same duck type as RaNode) ---------------

    @property
    def procs(self) -> Dict[str, Any]:
        return self.by_name

    # ring item tags: generic message | single command | per-node
    # batch of (name, from_sid, msg) triples
    _R_MSG, _R_CMD, _R_BATCH = 0, 1, 2

    def deliver(self, to: ServerId, msg: Any, from_sid: Optional[ServerId]) -> bool:
        """Lock-free ingress: publish onto this thread's SPSC lane. A
        full lane backpressures explicitly (docs/INTERNALS.md §16):
        client commands owing a reply reject through the admission
        path with a gate waiter, ack-free commands drop counted
        (at-most-once contract), lossy peer protocol traffic drops
        counted (transport contract), and must-deliver control
        messages (log events, internal commands, queries) ride the
        overflow queue — never a silent drop, and never a block (the
        caller may be a peer coordinator's drainer thread; parking it
        on our gate while we park on its gate would deadlock)."""
        name = to[0]
        if name not in self.by_name:
            return False
        if type(msg) is Command:
            # the RC_* class code rides a sidecar slot next to the item
            # (the flat tagged-item layout): the priority split is paid
            # once at the producer so the native drain-classify never
            # touches the object
            code = RC_CMD_LOW if msg.priority == "low" else RC_CMD
            if msg.internal and self._overflow_q:
                # older must-deliver work is parked on the overflow
                # queue: a lane publish would overtake it (the queue
                # folds after the lane drain) — keep arrival order
                return self._publish_overflow((self._R_CMD, name, msg), code)
            if self._rings.publish((self._R_CMD, name, msg), code):
                return True
            return self._ring_full_cmd(name, msg, code)
        if type(msg) not in LOSSY_PROTOCOL_TYPES and self._overflow_q:
            return self._publish_overflow(
                (self._R_MSG, name, from_sid, msg), RC_MSG)
        if self._rings.publish((self._R_MSG, name, from_sid, msg), RC_MSG):
            return True
        self.counters.incr("ingress_ring_full")
        if type(msg) in LOSSY_PROTOCOL_TYPES:
            return False  # lossy peer traffic: counted drop
        return self._publish_overflow((self._R_MSG, name, from_sid, msg), RC_MSG)

    def _ring_full_cmd(self, name: str, msg: Command,
                       code: int = RC_CMD) -> bool:
        self.counters.incr("ingress_ring_full")
        if msg.internal:
            # machine-internal must-deliver (timer fires, Append
            # effects): overflow queue, never shed
            return self._publish_overflow((self._R_CMD, name, msg), code)
        if msg.from_ref is not None:
            # explicit backpressure: the command was NEVER enqueued, so
            # a retry is exactly-once safe; the gate waiter wakes the
            # client on the next drain instead of a sleep loop
            self.counters.incr("commands_rejected")
            self._reply(
                msg.from_ref,
                REJECT_OVERLOADED + (self._ring_gate.waiter(),),
            )
            return True
        self.counters.incr("commands_dropped_overload")
        return False

    def _publish_blocking(self, item, code: int = RC_MSG) -> bool:
        """Bounded-wait publish for must-deliver BULK CLIENT traffic
        (deliver_many — the producers there are client/driver threads,
        where waiting IS the backpressure): wait on the ring gate
        (opened by every space-freeing drain) and retry. A drainer
        thread (step/egress loop, or a ``step_once`` call) must never
        gate-wait on itself — its must-deliver traffic rides
        ``_internal_q`` into its own next drain instead.
        Never used for traffic that may originate on ANOTHER
        coordinator's drainer thread (see _publish_overflow)."""
        if threading.get_ident() in self._drainer_idents:
            # caller holds the state lock (every drainer publish comes
            # from inside a locked stage/realise half)
            self._internal_q.append(item)
            return True
        for _ in range(4):
            if not self.running:
                return False
            if self._rings.publish(item, code):
                return True
            self._ring_gate.waiter().wait(0.05)
        # still full after the bounded wait: in cooperative (non-
        # started) mode the only drainer may be THIS thread between
        # ``step_once`` calls — spinning here would livelock until an
        # external stop(). Fall back to the overflow queue: delivered
        # on the next drain, never spun on, never shed.
        return self._publish_overflow(item, code)

    def _publish_overflow(self, item, code: int = RC_MSG) -> bool:
        """Non-blocking must-deliver fallback for a full lane: park the
        item on the overflow queue the next _drain_classify folds FIRST
        (arrival seniority kept). Used for traffic whose producer may
        be a peer coordinator's drainer thread or a timer — blocking
        those risks distributed deadlock, dropping violates the
        must-deliver contract. Unbounded, but only ever fed by the
        low-rate control/ack trickle that outlived a full lane."""
        if threading.get_ident() in self._drainer_idents:
            self._internal_q.append(item)
            return True
        with self._overflow_lock:
            self._overflow_q.append(item)
            self._overflow_codes.append(code)
        self.counters.incr("ingress_overflow_msgs")
        if not self._wake.is_set():
            self._wake.set()
        return True

    def _deliver_internal(self, name: str, msg) -> None:
        """Self-delivery from the step thread (machine effects
        re-entering the command queue). Caller holds the state lock;
        the queue is drained by the next _drain_and_dispatch."""
        if type(msg) is Command:
            self._internal_q.append((self._R_CMD, name, msg))
        else:
            self._internal_q.append((self._R_MSG, name, None, msg))

    def process_down(self, target, info="noproc") -> int:
        """A monitored process went away (the caller is whatever on
        this node learns it: a connection closed, a consumer's owner
        saying so): every group led here that watches ``target`` gets
        the builtin ``("down", target, info)`` command, appended and
        replicated like any other (an ``aux`` watch gets the aux cast).
        A monitor fires once, as an Erlang monitor does: the machine
        arms it again when the target checks in again. Any thread but
        one inside ``send_msg_cb`` (that one holds the state lock).
        Returns the number of groups told."""
        told = 0
        with self._state_lock:
            for watcher, component in self.monitors.watchers("process", target):
                self.monitors.remove(watcher, "process", target)
                g = self.by_name.get(watcher[0])
                if g is None or g.role != C.R_LEADER:
                    continue
                if component == "aux":
                    self._deliver_internal(
                        g.name, ("aux", "cast", ("down", target, info), None))
                else:
                    self._deliver_internal(
                        g.name, Command(kind=USR, data=("down", target, info),
                                        internal=True))
                told += 1
            if told:
                self.counters.incr("monitor_downs", told)
        if told and not self._wake.is_set():
            self._wake.set()
        return told

    def wal_notify(self, uid: str, evt) -> None:
        """Log-event entry point for WAL / segment-writer notify
        callbacks. ``written`` events take the decoupled durable-ack
        path — handled on the CALLING (WAL writer) thread so a durable
        batch advances watermarks, releases deferred AER acks, and
        queues the device written-scatter without waiting for a step-
        loop pass. Everything else rides normal ingress ordering."""
        if type(evt) is tuple and evt and evt[0] == "written":
            _, term, seq = evt
            if seq is not None and not seq.is_empty():
                self.wal_notify_many(
                    [(uid, term, lo, hi) for lo, hi in seq.ranges()])
        else:
            self.deliver((uid, self.name), ("log_event", evt), None)

    def wal_notify_many(self, rows) -> None:
        """Bulk durable-watermark delivery from one WAL flush (wire as
        ``wal.notify_many``): rows ``(uid, term, lo, hi)``, one
        state-lock round for the whole batch's written events. The
        durable-ack decoupling invariant (docs/INTERNALS.md §15):
        everything this touches — the log's written watermark,
        ``pending_ack``, ``last_ok_sent``, the pending-scatter queue —
        is guarded by the state lock, and the ack it emits is exactly
        the ack the step-loop path would have emitted one wave later."""
        route_out: Dict[str, List] = {}
        n_written = 0
        t_ask = time.perf_counter_ns()
        with self._wal_lock:
            by_get = self.by_name.get
            sw = self._staged_written
            for uid, term, _lo, hi in rows:
                g = by_get(uid)
                if g is None:
                    continue
                n_written += 1
                log = g.log
                wi = log.note_written(term, hi)
                # the device learns the durable watermark at the next
                # dispatch (the staged written scatter drives the
                # quorum scan)
                if sw.get(g.gid, 0) < wi:
                    sw[g.gid] = wi
                if g.pending_ack is not None and wi >= g.pending_ack[1]:
                    leader_sid, cover = g.pending_ack
                    g.pending_ack = None
                    ack = min(wi, cover)
                    at = log.fetch_term(ack)
                    if at is None:
                        at = log.last_written()[1]
                    out = route_out.get(leader_sid[1])
                    if out is None:
                        route_out[leader_sid[1]] = out = []
                    out.append(
                        (leader_sid,
                         AppendEntriesReply(g.term, True, ack + 1, ack, at),
                         (g.name, self.name))
                    )
            # the round's accounts, written under the lock every writer
            # of them holds
            cnt = self.counters
            t_held = self._wal_lock.t_held
            cnt.incr("wal_notify_batches")
            cnt.incr("wal_notify_events", n_written)
            cnt.incr("wal_notify_wait_ns", t_held - t_ask)
            cnt.incr("wal_notify_hold_ns", time.perf_counter_ns() - t_held)
        for node_name, msgs in route_out.items():
            self._send_batch(node_name, msgs)
        # wake the step thread only when the staged watermark is still
        # there to step: a turn that took it up while this batch sent
        # its acks leaves nothing, and a set then would wake the loop
        # for nothing (the ``step_spurious_wakeups`` counter)
        if n_written and self._have_work() and not self._wake.is_set():
            self._wake.set()

    def deliver_many(self, msgs) -> None:
        """Batch ingress: ONE ring slot for many ``(to_sid, msg,
        from_sid)`` triples (unknown group names are dropped at drain,
        as in ``deliver``). Blocks gate-paced when the lane is full.
        Keeps arrival order (never overtakes parked overflow work — the
        overflow queue folds after the lane drain) WITHOUT giving up
        pacing: while overflow is pending, gate-wait a bounded window
        for the drain to clear it; only if it persists does the wave
        park on the overflow queue too — producers stay paced at the
        gate cadence instead of appending unbounded waves at line rate
        (the failure mode an unconditional divert would reintroduce
        under exactly the overload the bounded rings exist for)."""
        item = (self._R_BATCH, [(to[0], frm, m) for to, m, frm in msgs])
        if self._overflow_q:
            ident = threading.get_ident()
            for _ in range(4):
                if ident in self._drainer_idents or not self.running:
                    break
                self._ring_gate.waiter().wait(0.05)
                if not self._overflow_q:
                    break
            if self._overflow_q:
                self._publish_overflow(item, RC_BATCH)
                return
        if not self._rings.publish(item, RC_BATCH):
            self.counters.incr("ingress_ring_full")
            self._publish_blocking(item, RC_BATCH)

    def ingest_batch(self, triples) -> int:
        """Peer-coordinator bulk ingress (the _send_batch fast path):
        pre-normalized ``(name, from_sid, msg)`` triples, one ring slot
        per per-node batch. On a full lane the batch SPLITS by the
        backpressure table: lossy protocol traffic is shed (returns the
        shed count for the sender's drop accounting), everything else —
        snapshot chunks/acks, TimeoutNow, client commands, log events —
        rides the overflow queue (must-deliver: a batch-level drop
        would stall a snapshot transfer for its whole ack timeout and
        silently swallow leadership transfers). Returns the number of
        messages dropped (0 = everything delivered)."""
        if not self._overflow_q:
            # (while older must-deliver work is parked on the overflow
            # queue, a lane publish would overtake it — divert below)
            if self._rings.publish((self._R_BATCH, triples), RC_BATCH):
                return 0
            self.counters.incr("ingress_ring_full")
        must = [t for t in triples if type(t[2]) not in LOSSY_PROTOCOL_TYPES]
        if must:
            self._publish_overflow((self._R_BATCH, must), RC_BATCH)
        if len(must) == len(triples):
            return 0
        # lossy remainder is order-insensitive (sender-retried): it may
        # still ride the lane; shed only what the lane cannot take
        lossy = [t for t in triples if type(t[2]) in LOSSY_PROTOCOL_TYPES]
        if self._rings.publish((self._R_BATCH, lossy), RC_BATCH):
            return 0
        return len(lossy)

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        if not self._started:
            self._started = True
            # the collector's policy of a serving process: what is
            # built is frozen, the young generations fit a wave
            _heap.enter(self)
            self._step_thread.start()
            self._detector.start()

    def warm_steps(self) -> int:
        """Run every step program this coordinator dispatches in steady
        service once, on a scratch state of its own shape, so that none
        of them compiles in the middle of traffic: on a started
        coordinator each new active-set width or election batch size is
        otherwise a compile under the state lock, with the command
        watchdog and the election timers running. At 10,240 x 3 on the
        chip, cold, that doubled the election (12.2 s against 6.2 s)
        and put 5 compilations into the serving window (19.2 s against
        13.2 s), though it struck no watchdog (chip_smoke.py, PR 21).
        Covers the full-width fused step, the active-set step at each
        power-of-two sub-batch width (both on a numpy buffer of the
        wave loop's shape, as the loop hands it over), the role scatter
        at each padded batch size and the health scan's copies. Rare paths (snapshot
        install, forced elections, mixed-term appends) still compile on
        first use. Returns the number of programs run."""
        cap = self.capacity
        scratch = C.make_group_state(
            cap, self.P, self.state.term_suffix.shape[1]
        )

        def pads(n):
            return jnp.full((n,), cap, jnp.int32)  # dropped by scatters

        def mbox(width=None):
            # numpy, as the wave loop hands it over: the argument's kind
            # is part of what the jitted call keys its fast path on
            packed = self._mbox_take(width)
            self._fill_scat(packed, None, None)
            if width is not None:
                packed[-1].fill(cap)
            return packed

        ran = 1
        if self._shard_state is not None:
            scratch = jax.device_put(scratch, self._shard_state)
            scratch, eg = C.consensus_step_packed(
                scratch, jax.device_put(mbox(), self._shard_mbox)
            )
        else:
            scratch, eg = C.consensus_step_packed_scat(scratch, mbox())
            if self.active_set != "never":
                most = cap if self.active_set == "always" else cap >> 2
                width = min(256, cap)
                while True:
                    scratch, eg = C.consensus_step_packed_sub_scat(
                        scratch, mbox(width)
                    )
                    ran += 1
                    if width >= most:
                        break
                    width <<= 1
        width = 1
        while True:
            scratch = C.set_roles(scratch, pads(width), pads(width))
            ran += 1
            if width >= cap:
                break
            width <<= 1
        _health_copies(scratch)
        np.asarray(eg)  # dispatch is async: wait for the last program
        return ran

    def stop(self) -> None:
        self.running = False
        self._egress_on = False  # late sends go inline, not to a dead ring
        if self._started:
            self._wake.set()
            self._egress_wake.set()
            self._step_thread.join(timeout=5)
            if self._sender_thread is not None:
                self._sender_thread.join(timeout=5)
                # a publisher that read _egress_on before stop() flipped
                # it can land a batch AFTER the sender's final drain:
                # ship the residue inline so queued acks still leave
                out: List = []
                if self._egress_rings.drain(out):
                    for node_name, msgs, _t_pub in out:
                        try:
                            self._send_batch_inline(node_name, msgs)
                        except Exception:  # noqa: BLE001 — best effort
                            pass
            # join the detector too: a straggling health scan sitting in
            # a device fetch at interpreter exit can crash the XLA
            # runtime's C++ teardown
            self._detector.join(timeout=5)
        _heap.leave(self)  # the last coordinator out gives it back
        from ra_tpu import counters as _counters
        from ra_tpu import health as _health

        _counters.delete(("coordinator", self.name))
        _health.unregister(self.name)
        self.pressure.delete()
        for g in self.groups:
            if g is not None:
                for t in g.machine_timers.values():
                    t.cancel()
                g.machine_timers.clear()
        self.registry.unregister(self.name)
        if self._wired:
            self.transport.close()  # sockets, and the threads on them
            self.transport.detector.close()

    def add_group(
        self,
        name: str,
        cluster_name: str,
        members: List[ServerId],
        machine: Machine,
        log: Optional[LogApi] = None,
    ) -> ServerId:
        return self.add_groups([(name, cluster_name, members, machine, log)])[0]

    def add_groups(self, specs) -> List[ServerId]:
        """Bulk group registration: ONE set of device scatters for the
        whole batch. ``specs`` rows are ``(name, cluster_name, members,
        machine[, log])``. Registering 10k groups one scatter-set at a
        time was minutes of un-jitted dispatch; this is 5 scatters
        total."""
        specs = list(specs)
        # validate EVERYTHING before mutating: a mid-batch error must
        # not leave half-registered groups with inactive device rows
        if self.n_groups + len(specs) > self.capacity:
            raise RuntimeError("coordinator at capacity")
        for spec in specs:
            name, _cl, members = spec[0], spec[1], spec[2]
            if len(members) > self.P:
                raise ValueError(
                    f"group has {len(members)} members; capacity is {self.P}"
                )
            if (name, self.name) not in members:
                raise ValueError(
                    "members must include this coordinator's server id"
                )
        sids: List[ServerId] = []
        hosts: List[Tuple[str, GroupHost]] = []
        rows: List[Tuple[int, np.ndarray, int, int, int]] = []
        for k, spec in enumerate(specs):
            name, cluster_name, members, machine = spec[:4]
            log = spec[4] if len(spec) > 4 else None
            sid = (name, self.name)
            gid = self.n_groups + k
            g = GroupHost(
                gid, name, cluster_name, members, members.index(sid),
                log or MemoryLog(auto_written=True), machine,
                self._mirrors, clock=self.clock,
            )
            # restart safety: reload the durable term/vote so this
            # member cannot re-vote in a term it already voted in
            term0, voted_slot = 0, -1
            if self.meta is not None:
                uid = f"{cluster_name}_{name}"
                term0 = int(self.meta.fetch(uid, "current_term", 0))
                voted_sid = self.meta.fetch(uid, "voted_for", None)
                if voted_sid is not None:
                    voted_slot = g.slot_of(tuple(voted_sid))
                    if voted_slot < 0:
                        # we voted this term for a sid not in the
                        # current member table (e.g. removed since):
                        # seed an out-of-range slot so free_to_vote
                        # stays False for the rest of the term — never
                        # degrade to "never voted" (-1), which would
                        # allow a second grant
                        voted_slot = self.P
                g.term = term0
            active = np.zeros(self.P, dtype=bool)
            active[: len(members)] = True
            li, lt = g.log.last_index_term()
            snap0 = g.log.snapshot_index_term()
            sidx, sterm = snap0 if snap0 else (0, 0)
            if snap0 is not None:
                # cold restart onto a snapshot-bearing log: entries at or
                # below the floor are gone, so the machine state MUST be
                # restored from the capture (replay-from-1 would raise on
                # the missing prefix); apply resumes above the floor
                got = g.log.read_snapshot()
                if got is not None:
                    meta0, state_obj = got
                    g.machine_state = state_obj
                    g.effective_machine_version = meta0.machine_version
                    g.last_applied = meta0.index
                    g.snap_floor = meta0.index
                    self._applied_np[gid] = meta0.index
            fi = sidx + 1
            if li >= fi:
                # a pre-populated log (cold restart with a persistent
                # log): seed the specials index so the batched apply
                # fast path stays sound
                g.specials = [
                    e.index for e in g.log.fetch_range(fi, li)
                    if type(e.cmd) is not Command or e.cmd.kind != USR
                ]
            rows.append((gid, active, g.self_slot, term0, voted_slot,
                         li, lt, sidx, sterm))
            hosts.append((name, g))
            sids.append(sid)
            if self.lease_cfg.enabled:
                self._lease_sync(g)
        if rows:
            gids = jnp.asarray(np.array([r[0] for r in rows], np.int32))
            act = jnp.asarray(np.stack([r[1] for r in rows]))
            slots = jnp.asarray(np.array([r[2] for r in rows], np.int32))
            terms = jnp.asarray(np.array([r[3] for r in rows], np.int32))
            voted = jnp.asarray(np.array([r[4] for r in rows], np.int32))
            lis_np = np.array([r[5] for r in rows], np.int32)
            lts_np = np.array([r[6] for r in rows], np.int32)
            sidx_np = np.array([r[7] for r in rows], np.int32)
            sterm_np = np.array([r[8] for r in rows], np.int32)
            lis = jnp.asarray(lis_np)
            lts = jnp.asarray(lts_np)
            sidxs = jnp.asarray(sidx_np)
            sterms = jnp.asarray(sterm_np)
            # recovered tails: the device learns last/written/snapshot
            # rows, with the whole (snap, li] interval marked
            # term-unknown — prev-term lookups fall back to the host log
            # (needs_host) until traffic reconciles the ring. Everything
            # already on disk is durable, so written == last.
            unk_lo = jnp.asarray(
                np.where(lis_np > sidx_np, sidx_np + 1, 1).astype(np.int32)
            )
            unk_hi = jnp.asarray(
                np.where(lis_np > sidx_np, lis_np, 0).astype(np.int32)
            )
            with self._state_lock:
                self.state = C.set_groups(self.state, gids, dict(
                    active=act, voting=act, self_slot=slots,
                    current_term=terms, voted_for=voted, last_index=lis,
                    last_term=lts, written_index=lis, commit_index=sidxs,
                    last_applied=sidxs, snapshot_index=sidxs,
                    snapshot_term=sterms, unknown_lo=unk_lo,
                    unknown_hi=unk_hi,
                ))
        # publish only after the device rows are live: deliver() must
        # never accept traffic for a group with inactive rows
        for name, g in hosts:
            self.groups[g.gid] = g
            self.by_name[name] = g
            self._hslots.append(self._health.ensure(name, g.cluster_name))
            self._sync_peer_row(g)
            if g.has_tick:
                self._tick_gids.append(g.gid)
        self.n_groups += len(hosts)
        return sids

    # -- the step loop -----------------------------------------------------

    def _have_work(self) -> bool:
        """Is there anything for a turn to do right now? Fresh ingress
        (ring items, internal self-deliveries, buffered lows) and
        deferred device work (hot gids, staged scatters, queued
        roles)."""
        return bool(
            self._rings.pending() or self._internal_q
            or self._overflow_q or self._low_dirty
            or self._hot or self._staged_app or self._staged_written
            or self._pending_roles
        )

    def _idle_wait(self) -> None:
        """Event-driven idle block (docs/INTERNALS.md §16): clear the
        wake event, re-check for work (a publish between the last drain
        and the clear must not be lost — publish stores the item BEFORE
        setting the event, so either the re-check sees the item or the
        wait sees the set), then block until a ring publish, WAL
        notify, timer delivery, or stop wakes us.
        No timed polls: an idle coordinator consumes zero CPU, and the
        ``step_spurious_wakeups`` counter proves every wake found
        work."""
        wake = self._wake
        wake.clear()
        if self._have_work() or not self.running:
            return
        tr = _obs.tracing()
        if tr:
            sp = _obs.begin("ra/step/idle", node=self.name)
        wake.wait()
        if tr:
            _obs.end(sp)
        self.counters.incr("step_wakeups")
        if self.running and not self._have_work():
            self.counters.incr("step_spurious_wakeups")

    def _run(self) -> None:
        """The started wave loop (docs/INTERNALS.md §15): turn after
        turn, each dispatching a step and realising it on this thread;
        an idle wait where a turn finds nothing to step. The sender
        thread ships what the turns hand it."""
        self._drainer_idents.add(threading.get_ident())
        self._sender_thread = threading.Thread(
            target=self._sender_loop, name=f"ra-batch-snd-{self.name}",
            daemon=True,
        )
        self._sender_thread.start()
        self._egress_on = True
        while self.running:
            if not self._turn(served=True):
                self._idle_wait()
        self._egress_wake.set()

    def _turn(self, served: bool) -> bool:
        """One turn of the wave loop, for both drivers: classify the
        ingress off the state lock; drain, ingest, fan out and dispatch
        under it; realise the step. ``served`` (the started loop): the
        lock is let go across the egress sync, so that the WAL writers'
        ``wal_notify_many`` can take it while the device computes, and
        realisation takes it again as ``_egress_lock``; ``step_once``
        holds it throughout. Returns False when there was nothing to
        step and no rare message to hand over."""
        pre = self._drain_classify()  # heavy half, off the state lock
        with self._step_lock:
            ticket = self._drain_and_dispatch(pre)
            if ticket is None:
                self._turn_replies()
                return False
            if not served:
                self._realise(ticket)
                self._turn_replies()
                return True
        cnt = self.counters
        cnt.incr("pipeline_steps")
        if self._prezero_useful and self._spare_mbox is None:
            # the next full-width dispatch packs into a buffer zeroed
            # here, while the device computes (the pool is this
            # thread's alone: only the turns take and release buffers)
            full = (self._NROWS, self.capacity)
            pool = self._mbox_pool
            buf = None
            for k, b in enumerate(pool):
                # (an active-set buffer of the same width has the index
                # row more)
                if b.shape == full:
                    buf = pool.pop(k)
                    buf.fill(0)
                    break
            self._spare_mbox = np.zeros(full, np.int32) if buf is None else buf
            cnt.incr("staging_prezeroed")
        eg = ticket.eg_packed
        if eg is not None and eg.is_ready():
            cnt.incr("sync_found_ready")
        self._realise(ticket, self._egress_lock)
        self._turn_replies()
        return True

    def _sender_loop(self) -> None:
        """Dedicated egress fan-out thread: per-destination message
        batches handed off through a bounded ring by the step/egress/
        WAL threads are shipped here, off every latency-critical loop.
        Drains outstanding batches on stop so queued acks still leave."""
        wake = self._egress_wake
        rings = self._egress_rings
        send_queue = self._wave_h["send_queue"].record
        out: List = []
        while True:
            n = rings.drain(out)
            if not n:
                wake.clear()
                if rings.pending():
                    continue
                if not self.running:
                    # straggler window: a publisher that read _egress_on
                    # just before stop() flipped it may land a batch
                    # after this empty check — give it one short beat,
                    # re-drain, and let stop()'s post-join residual
                    # drain catch anything even later
                    time.sleep(0.01)
                    if rings.pending():
                        continue
                    return
                wake.wait()
                continue
            msgs_n = 0
            # sub-phase send_queue: published -> drained, a sample a batch
            now = time.perf_counter_ns()
            for _n, _msgs, t_pub in out:
                send_queue(now - t_pub)
            tr = _obs.tracing()
            if tr:
                sp = _obs.begin("ra/send/batch", node=self.name,
                                msgs=sum(len(item[1]) for item in out))
            batches = out
            if self._wired and n > 1:
                # across a wire a batch is a frame, and a frame costs
                # the writer and the peer's reader a turn each at the
                # interpreter lock: what queued for one destination
                # while this thread waited its own turn leaves as one
                # (the slower the turns, the larger the frames)
                # (a batch of client replies keeps a frame of its own)
                by_node: Dict[Tuple[str, bool], List] = {}
                for node_name, msgs, _t_pub in out:
                    by_node.setdefault(
                        (node_name, bool(msgs)
                         and msgs[0][0][0] == CLIENT_REPLY), []
                    ).extend(msgs)
                batches = [(key[0], msgs) for key, msgs in by_node.items()]
            for item in batches:
                node_name, msgs = item[0], item[1]
                try:
                    self._send_batch_inline(node_name, msgs)
                except Exception:  # noqa: BLE001
                    logger.exception(
                        "coordinator %s: egress sender batch to %s failed",
                        self.name, node_name,
                    )
                msgs_n += len(msgs)
            if tr:
                _obs.end(sp)
            self.counters.incr("egress_thread_batches", n)
            self.counters.incr("egress_thread_msgs", msgs_n)
            out.clear()

    def _coop_drainer(self):
        """Register the calling thread as a drainer for the span of one
        ``step_once`` call (its self-publishes divert to
        ``_internal_q`` instead of gate-waiting on a ring it is itself
        responsible for draining). Returns a token for ``_coop_done``."""
        ident = threading.get_ident()
        if ident in self._drainer_idents:
            return 0
        self._drainer_idents.add(ident)
        return ident

    def _coop_done(self, token: int) -> None:
        if token:
            self._drainer_idents.discard(token)

    def step_once(self) -> bool:
        """One turn of the wave loop from the caller's thread, the
        state lock held throughout: drain ingress, scatter host log
        updates, run the fused device step, realise egress. Returns
        False when there was nothing to do. The deterministic driver of
        the tests — never call it on a started coordinator (two threads
        would turn one loop)."""
        token = self._coop_drainer()
        try:
            return self._turn(served=False)
        finally:
            self._coop_done(token)

    def _realise(self, ticket, lock=None) -> None:
        """Realise one dispatched step: sync its egress off the device
        (``np.asarray``: the host's one true wait for the device), then
        finish it under the state lock. The started loop passes its
        ``_egress_lock`` and syncs outside it; ``step_once`` holds the
        lock already."""
        tr = _obs.tracing()
        t_pop = time.perf_counter_ns()
        eg_np = None
        if ticket.eg_packed is not None:
            if tr:
                sp = _obs.begin("ra/egress/sync", node=self.name)
            eg_np = np.asarray(ticket.eg_packed)
            if tr:
                _obs.end(sp)
        t_sync = time.perf_counter_ns()
        if lock is None:
            self._finish_ticket(ticket, eg_np, t_pop, t_sync, tr)
        else:
            with lock:
                self._finish_ticket(ticket, eg_np, t_pop, t_sync, tr)
        self._unrealised -= 1

    class _StepTicket:
        """One dispatched-but-unrealised step: the device egress handle
        plus everything realisation needs (who was consumed, the
        position->gid map, rares, the staging timestamps, and the wall
        and thread-CPU ns of the pass's own AER fan-out, which
        realisation books beside its own, so that each account is
        written in one place).
        ``cpu``: this turn is one whose thread-CPU time is read."""

        __slots__ = ("eg_packed", "consumed", "act", "aer_dirty", "rare",
                     "mbox_buf", "t_in", "t_drain", "t_pack", "stepped",
                     "aer0_ns", "aer0_cpu_ns", "cpu", "wave0")

        def __init__(self, **kw):
            for k in self.__slots__:
                setattr(self, k, kw.get(k))

    def _drain_classify(self):
        """Lock-FREE half of the drain (docs/INTERNALS.md §16): pop
        every ingress lane into the reusable scratch and classify in
        one pass — commands regroup per target, generic messages
        collect into a route list, low-priority commands set aside.
        Runs on the step/driver thread WITHOUT the state lock: the
        classification of a deep-pipelined burst is O(items) pure
        Python, and holding the state lock through it starved the WAL
        writer's ``wal_notify_many`` (measured: 4x total fsync time,
        p99 8 ms -> 150 ms at 10240x96 — the writer blocked behind the
        lock, its queue grew, and every later batch paid the backlog).
        Only ``by_name`` reads happen here (GIL-safe dict reads; a
        concurrently added group at worst misses one pass, the same
        contract ``deliver`` already has). Returns the pre-drain
        ``(stamps, n_items, cmd_q, routes, lows)`` consumed by
        ``_drain_and_dispatch`` under the lock; ``stamps`` is ``(tr,
        t_in, cpu_in, t_classified)``: whether a profiler session takes
        this turn's spans, where the wave phase ``ingress_drain`` and
        its thread-CPU account start (``cpu_in`` is None on the turns
        whose CPU time is not read), and where the sub-phase
        ``step_lock_wait`` starts."""
        tr = _obs.tracing()
        _t_in = time.perf_counter_ns()
        self._cpu_turn = turn = self._cpu_turn + 1
        _c_in = None if turn & self._cpu_mask else time.thread_time_ns()
        if tr:
            sp = _obs.begin("ra/step/classify", node=self.name)
        got = self._classify()
        if tr:
            _obs.end(sp)
        return ((tr, _t_in, _c_in, time.perf_counter_ns()),) + got

    def _classify(self):
        """``_drain_classify``'s work: ``(n_items, cmd_q, routes,
        lows)``."""
        buf = self._drain_buf
        # native classify (docs/INTERNALS.md §18): drain the RC_* code
        # sidecar alongside the items and let rt_classify partition the
        # burst with the GIL released; Python keeps the routing half.
        # Routes around itself while ANY failpoint is armed so nemesis
        # runs always exercise the Python classification seam.
        nat = self._nat_classify and not faults.anything_armed()
        codes = self._drain_codes
        n_items = self._rings.drain(buf, codes if nat else None)
        if self._overflow_q:
            # overflow items are NEWER than the ring contents drained
            # above (a publish only overflows while the lane is full of
            # its own earlier items), so they fold AFTER the lane
            # drain; cross-pass order is kept by the producer-side
            # divert (a must-deliver publish goes straight to overflow
            # while older overflow is still parked — see deliver/
            # ingest_batch)
            with self._overflow_lock:
                n_items += len(self._overflow_q)
                buf.extend(self._overflow_q)
                if nat:
                    codes.extend(self._overflow_codes)
                self._overflow_q.clear()
                self._overflow_codes.clear()
        cmd_q: Optional[Dict[str, List[Command]]] = None
        routes: Optional[List] = None
        lows: Optional[List] = None
        if buf:
            cmd_q = {}
            routes = []
            lows = []
            if nat and len(codes) == len(buf):
                t0 = time.perf_counter_ns()
                part = _native.classify(codes, len(buf))
                if part is not None:
                    self._route_classified(buf, part, cmd_q, routes, lows)
                    self._wave_h["classify_native"].record(
                        time.perf_counter_ns() - t0)
                    self.counters.incr("native_classify_batches")
                    self.counters.incr("native_classify_items", len(buf))
                    buf.clear()
                    codes.clear()
                    self.counters.incr("ingress_ring_msgs", n_items)
                    self.counters.incr("ingress_ring_drains")
                    self._ring_gate.open()
                    return (n_items, cmd_q, routes, lows)
                self.counters.incr("native_fallbacks")
            radd = routes.append
            by = self.by_name
            cq_get = cmd_q.get
            R_MSG, R_CMD = self._R_MSG, self._R_CMD
            for item in buf:
                tag = item[0]
                if tag == R_CMD:
                    _, name, cmd = item
                    if name not in by:
                        continue
                    if cmd.priority == "low":
                        lows.append((name, cmd))
                        continue
                    q = cq_get(name)
                    if q is None:
                        cmd_q[name] = [cmd]
                    else:
                        q.append(cmd)
                elif tag == R_MSG:
                    _, name, from_sid, msg = item
                    if name in by:
                        radd((name, from_sid, msg))
                else:  # R_BATCH: pre-normalized (name, from_sid, msg)
                    for trip in item[1]:
                        name = trip[0]
                        msg = trip[2]
                        if type(msg) is Command:
                            if msg.priority == "low":
                                if name in by:
                                    lows.append((name, msg))
                                continue
                            q = cq_get(name)
                            if q is None:
                                if name not in by:
                                    continue
                                cmd_q[name] = [msg]
                            else:
                                q.append(msg)
                        elif name in by:
                            radd(trip)
            buf.clear()
            if codes:
                codes.clear()
        if n_items:
            self.counters.incr("ingress_ring_msgs", n_items)
            self.counters.incr("ingress_ring_drains")
            # space was freed on every lane: wake ring-full waiters
            self._ring_gate.open()
        return (n_items, cmd_q, routes, lows)

    def _route_classified(self, buf, part, cmd_q, routes, lows) -> None:
        """Python routing half of the native drain-classify: walk the
        per-class index partitions ``rt_classify`` returned (arrival
        order kept within each class) and run each class's straight-
        line routing loop — no per-item tag dispatch, no priority
        checks (the producer stamped those into the RC_* code).

        Ordering contract (docs/INTERNALS.md §18): order is preserved
        WITHIN each class; classes may reorder against each other.
        That is safe because any producer's causally-ordered commands
        ride a single class (clients publish R_CMD, peer forwards and
        bulk senders R_BATCH) and protocol traffic is
        reorder-tolerant by the transport contract."""
        idx, counts = part
        ilist = idx.tolist()
        c_msg, c_cmd, c_cmd_low, c_batch = counts.tolist()
        by = self.by_name
        cq_get = cmd_q.get
        radd = routes.append
        ladd = lows.append
        o = 0
        for k in ilist[o:o + c_msg]:
            item = buf[k]
            name = item[1]
            if name in by:
                radd((name, item[2], item[3]))
        o += c_msg
        for k in ilist[o:o + c_cmd]:
            _, name, cmd = buf[k]
            if name not in by:
                continue
            q = cq_get(name)
            if q is None:
                cmd_q[name] = [cmd]
            else:
                q.append(cmd)
        o += c_cmd
        for k in ilist[o:o + c_cmd_low]:
            _, name, cmd = buf[k]
            if name in by:
                ladd((name, cmd))
        o += c_cmd_low
        for k in ilist[o:o + c_batch]:
            for trip in buf[k][1]:
                name = trip[0]
                msg = trip[2]
                if type(msg) is Command:
                    if msg.priority == "low":
                        if name in by:
                            ladd((name, msg))
                        continue
                    q = cq_get(name)
                    if q is None:
                        if name not in by:
                            continue
                        cmd_q[name] = [msg]
                    else:
                        q.append(msg)
                elif name in by:
                    radd(trip)

    def _ingest(self, n_items, cmd_q, routes, lows, tr=False, t_held=0):
        """Route one classified burst under the state lock: messages to
        their handlers, commands into the logs and the WAL queue, the
        appended runs and durable watermarks into the staged scatter
        dicts. Returns ``(n_items, rare, aer_dirty)``. ``tr``: a
        profiler session takes the spans; ``t_held``: when the caller
        got the state lock (``perf_counter_ns``), where the sub-phase
        ``ingress_route`` starts."""
        # fold the step thread's own must-deliver self-publishes
        # (machine Append/Aux effects realized under the state lock —
        # including by the prev-ticket finish that just ran): they are
        # few, and folding here keeps their same-pass ordering
        if self._internal_q:
            iq = self._internal_q
            R_CMD = self._R_CMD
            if cmd_q is None:
                cmd_q, routes, lows = {}, [], []
            by = self.by_name
            n_internal = 0
            while iq:
                item = iq.popleft()
                n_internal += 1
                if item[0] == R_CMD:
                    _, name, cmd = item
                    if name not in by:
                        continue
                    if cmd.priority == "low":
                        lows.append((name, cmd))
                        continue
                    q = cmd_q.get(name)
                    if q is None:
                        cmd_q[name] = [cmd]
                    else:
                        q.append(cmd)
                else:
                    _, name, from_sid, msg = item
                    if name in by:
                        routes.append((name, from_sid, msg))
            # counted in n_items (pass-has-work accounting) but NOT in
            # ingress_ring_msgs — these never touched a ring
            n_items += n_internal
        rare: List[Tuple[GroupHost, Any, Optional[ServerId]]] = []
        aer_dirty: set = set()
        # appended runs: gid -> [[lo, hi, term], ...] (contiguous,
        # same-term); written: gid -> max durable idx. Run-based so the
        # device scatter is one row per touched GROUP, not per entry.
        # These ARE the staged scatter dicts: the dispatch consumes them
        # with zero re-merging (the WAL writer thread stages durable
        # watermarks into _staged_written directly, between turns).
        appended = self._staged_app
        written = self._staged_written
        # replies produced during routing (deferred durable acks): one
        # transport hop per destination per step, not one per group
        route_out: Dict[str, List] = {}

        by_get = self.by_name.get
        route = self._route_one
        if lows:
            low_dirty = self._low_dirty
            for name, cmd in lows:
                g = by_get(name)
                if g is not None:
                    g.low_q.append(cmd)
                    low_dirty.add(g.gid)
        if routes:
            # every drained protocol message to its handler, and the
            # replies that produced to the sender: sub-phase
            # ingress_route, one record a pass that had messages, from
            # the lock's own stamp (so that the way here is inside it)
            if tr:
                sp = _obs.begin("ra/step/ingress_drain/route",
                                node=self.name, msgs=len(routes))
            _t_route = t_held or time.perf_counter_ns()
            now_mono = self.clock.monotonic()
            for name, from_sid, msg in routes:
                g = by_get(name)
                if g is not None:
                    route(g, from_sid, msg, rare, appended, written,
                          aer_dirty, route_out, now_mono)
            if rare and not self._unrealised:
                rare = self._rares_at_ingest(rare, route_out, tr)
            for node_name, msgs in route_out.items():
                self._send_batch(node_name, msgs)
            self._wave_h["ingress_route"].record(
                time.perf_counter_ns() - _t_route)
            self.counters.incr("routed_msgs", len(routes))
            if tr:
                _obs.end(sp)
        if cmd_q or self._low_dirty:
            # the pass's client commands into the logs and the WAL
            # queue: sub-phase ingest_append, one record for all groups
            if tr:
                sp = _obs.begin("ra/step/ingress_drain/ingest_append",
                                node=self.name)
            _t_app = time.perf_counter_ns()
            if cmd_q:
                for name, cmds in cmd_q.items():
                    g = by_get(name)
                    if g is not None:
                        self._handle_commands(g, cmds, appended, written,
                                              aer_dirty)
            if self._low_dirty:
                self._drain_low_lane(appended, written, aer_dirty)
            self._wave_h["ingest_append"].record(
                time.perf_counter_ns() - _t_app)
            if tr:
                _obs.end(sp)
        return n_items, rare, aer_dirty

    def _drain_and_dispatch(
        self, pre
    ) -> Optional["BatchCoordinator._StepTicket"]:
        # caller holds the state lock (taken through ``_step_lock``);
        # ``pre`` is _drain_classify()'s output taken BEFORE the lock
        # (drivers pre-classify so the heavy classification never
        # blocks the WAL writer)
        (tr, _t_in, _c_in, _t_cls), n_items, cmd_q, routes, lows = pre
        wave0 = self.clock.monotonic()
        node = self.name
        wh = self._wave_h
        cnt = self.counters
        cpu = _c_in is not None  # a turn whose thread-CPU time is read
        shift = self._CPU_SAMPLE_SHIFT
        if tr:
            sp = _obs.begin("ra/step/ingress_drain", node=node)
        t_held = self._step_lock.t_held
        n_items, rare, aer_dirty = self._ingest(n_items, cmd_q, routes, lows,
                                                tr, t_held)
        if tr:
            _obs.end(sp)
        appended = self._staged_app
        written = self._staged_written
        if not (
            n_items or self._hot or rare or appended or written
            or self._pending_roles
        ):
            return None
        if cpu:
            _c_ing = _c_drain = time.thread_time_ns()
        _t_ing = _t_drain = time.perf_counter_ns()
        # Host work first: drain-produced AERs (fresh appends, ack-driven
        # next_index moves) leave BEFORE the pack and the device
        # hand-off. _send_aers reads logs and host mirrors only, so the
        # order is free, and the followers' round starts a whole
        # host_pack earlier. Its wall and CPU time are neither
        # ingress_drain's nor host_pack's (which starts after it): they
        # ride the ticket into aer_fanout, which realisation alone
        # writes. Egress-produced AERs (commit advances) ride the ticket
        # too.
        aer0_ns = aer0_cpu_ns = None
        if aer_dirty:
            if tr:
                sp = _obs.begin("ra/step/aer_fanout", node=node)
            cnt.incr("aer_groups_before_pack", self._send_aers(aer_dirty))
            if tr:
                _obs.end(sp)
            aer_dirty = set()
            _t_drain = time.perf_counter_ns()
            aer0_ns = _t_drain - _t_ing
            if cpu:
                _c_drain = time.thread_time_ns()
                aer0_cpu_ns = _c_drain - _c_ing

        stepped = False
        eg_packed = consumed = act_np = mbox_buf = None
        if tr:
            sp_pack = _obs.begin("ra/step/host_pack", node=node)
            sp = _obs.begin("ra/step/host_pack/scatter_dispatch", node=node)
        app_rows, act = self._scatter_staged(appended, written)
        if tr:
            _obs.end(sp)
        _t_scat = time.perf_counter_ns()
        if act is None or act:
            if tr:
                sp = _obs.begin("ra/step/host_pack/mailbox_build", node=node)
            # the builders hand back the numpy buffer itself; the jitted
            # step takes it as its argument and transfers it inside the
            # call: one entry into JAX per wave on the unsharded path
            if act is not None:
                variant = "sub_scat"
                step = C.consensus_step_packed_sub_scat
                mbox_buf, act_np, consumed = self._build_mailbox_sub(
                    act, app_rows, written)
            elif self._shard_state is not None:
                # the log-tail scatters went as separate calls
                # (_scatter_staged)
                variant = "packed"
                step = C.consensus_step_packed
                mbox_buf, consumed = self._build_mailbox(None, None)
            else:
                variant = "scat"
                step = C.consensus_step_packed_scat
                mbox_buf, consumed = self._build_mailbox(app_rows, written)
            packed = mbox_buf
            _t_build = time.perf_counter_ns()
            if tr:
                _obs.end(sp)
                sp = _obs.begin("ra/step/host_pack/step_dispatch", node=node,
                                width=int(packed.shape[1]), variant=variant)
            if variant == "packed":
                # the jitted scatters keep the mesh layout; an eager
                # host-side row update (membership, snapshot install)
                # may hand back another one — move the state back only
                # then, and count it: a move on every wave would be the
                # whole state crossing the interconnect per step
                if not all(
                    a.sharding.is_equivalent_to(self._shard_state, a.ndim)
                    for a in self.state
                ):
                    self.shard_moves += 1
                    self.state = jax.device_put(self.state, self._shard_state)
                packed = jax.device_put(packed, self._shard_mbox)
            state, eg_packed = step(self.state, packed)
            # the old state, donated to the call, goes here: its arrays
            # are released as the new ones take their place
            if tr:
                sp_rel = _obs.begin(
                    "ra/step/host_pack/step_dispatch/release", node=node)
            self.state = state
            if tr:
                _obs.end(sp_rel)
                _obs.end(sp)
            stepped = True
            self.steps += 1
            if act is not None:
                self.sub_steps += 1
            self.msgs_processed += len(consumed)
        if tr:
            _obs.end(sp_pack)
        # full-width steps are the shape worth pre-zeroing a spare
        # mailbox for while the device computes (sub-batch buffers are
        # tiny; zeroing them inline is already free)
        self._prezero_useful = stepped and act is None
        if cpu:
            _c_pack = time.thread_time_ns()
        _t_pack = time.perf_counter_ns()
        # dispatch is ASYNC: eg_packed is an in-flight device value; the
        # turn's realisation half syncs it (np.asarray) and processes
        # the egress.
        wh["ingress_drain"].record(_t_ing - _t_in)
        wh["ingress_classify"].record(_t_cls - _t_in)
        wh["step_lock_wait"].record(t_held - _t_cls)
        if stepped:
            wh["host_pack"].record(_t_pack - _t_drain)
            wh["scatter_dispatch"].record(_t_scat - _t_drain)
            wh["mailbox_build"].record(_t_build - _t_scat)
            wh["step_dispatch"].record(_t_pack - _t_build)
        if cpu:
            cnt.incr("cpu_ns_ingress_drain", (_c_ing - _c_in) << shift)
            if stepped:
                cnt.incr("cpu_ns_host_pack", (_c_pack - _c_drain) << shift)
        self._unrealised += 1
        return self._StepTicket(
            eg_packed=eg_packed, consumed=consumed, act=act_np,
            aer_dirty=aer_dirty, rare=rare, mbox_buf=mbox_buf, t_in=_t_in,
            t_drain=_t_drain, t_pack=_t_pack, stepped=stepped,
            aer0_ns=aer0_ns, aer0_cpu_ns=aer0_cpu_ns, cpu=cpu, wave0=wave0,
        )

    def _scatter_staged(self, appended, written):
        """The head of ``host_pack``: apply the queued role scatter,
        detach the staged runs and watermarks, send what the mailbox
        cannot carry as scatters of its own, and choose the step's
        path. Returns ``(app_rows, act)``: the newest appended run per
        group for the mailbox, and the sorted active set (None for a
        full-width step)."""
        if self._pending_roles:
            gids, roles, _ = self._pad3(
                [(gid, role, 0) for gid, role in self._pending_roles]
            )
            self._pending_roles = []
            self.state = C.set_roles(self.state, gids, roles)

        # consume the staged halves: detach so the WAL writer threads,
        # staging while the device computes, start a fresh buffer for
        # the NEXT dispatch
        self._staged_app = {}
        self._staged_written = {}

        app_rows: List[Tuple[int, int, int, int]] = []
        if appended:
            legacy: List[Tuple[int, int, int]] = []  # older runs, per entry
            for gid, runs in appended.items():
                for lo, hi, term in runs[:-1]:
                    legacy.extend((gid, i, term) for i in range(lo, hi + 1))
                lo, hi, term = runs[-1]
                app_rows.append((gid, lo, hi, term))
            if legacy:
                # rare (mixed-term batches): scatter older runs first so
                # the newest run's ring slots win
                gids, idxs, terms = self._pad3(legacy)
                self.state = C.record_appended(self.state, gids, idxs, terms)
        if written and self._lat_gids:
            now_w = time.monotonic_ns()
            for gid_w in self._lat_gids:
                idx_w = written.get(gid_w)
                gw = self.groups[gid_w] if idx_w is not None else None
                if gw is None:
                    continue
                lat = gw.lat
                if lat is not None and lat[3] == 0 and idx_w >= lat[0]:
                    lat[3] = now_w
                    self._commit_h["append_durable"].record(now_w - lat[2])

        # activity-scaled path selection: groups with device-relevant
        # work this step are exactly the hot set (queued messages/term
        # hints) plus those whose log tail or durable watermark moved
        # (the quorum scan can advance their commit). Everything else
        # is provably unchanged by an empty-mailbox step.
        # The newest appended runs and the durable watermarks ride the
        # packed mailbox itself (C.MBOX_SCAT_FIELDS rows) and apply
        # inside the fused step — one transfer + one dispatch per step.
        act: Optional[list] = None
        if self._shard_state is None:
            if self.active_set != "never":
                cand = self._hot | appended.keys() | written.keys()
                if (self.active_set == "always"
                        or len(cand) <= (self.capacity >> 2)):
                    act = sorted(cand)
        else:
            # sharded state: the mailbox shards column-wise, which
            # would split scatter rows across devices — apply the
            # log-tail scatters as separate (replicated-index) calls
            if app_rows:
                gids, los, his, terms = self._pad4(app_rows)
                self.state = C.record_appended_runs(
                    self.state, gids, los, his, terms
                )
            if written:
                gids, idxs, _ = self._pad3(
                    [(g, i, 0) for g, i in written.items()]
                )
                self.state = C.record_written(self.state, gids, idxs)
        return app_rows, act

    def _finish_ticket(self, ticket, eg_np: Optional[np.ndarray],
                       t_pop: int, t_sync: int, tr: bool) -> None:
        """Realise one dispatched step: process the synced egress, run
        the rare paths, fan out AERs (caller holds the state lock and
        has already synced ``eg_np`` — ideally outside the lock).
        ``t_pop`` / ``t_sync``: when ``_realise`` took the ticket up and
        when its sync ended (``perf_counter_ns``); ``tr``: a profiler
        session takes the spans."""
        node = self.name
        aer_dirty = ticket.aer_dirty
        cpu = ticket.cpu
        _t_dev = time.perf_counter_ns()
        if cpu:
            _c_dev = time.thread_time_ns()
        self._fx_tr = tr
        if eg_np is not None:
            if tr:
                sp = _obs.begin("ra/egress/host_egress", node=node)
            # egress is host-synced: the device has fully consumed the
            # mailbox view, so the pack buffer may be reused
            self._mbox_release(ticket.mbox_buf)
            eg = {name: eg_np[i] for i, name in enumerate(C.EGRESS_FIELDS)}
            _t_rare = self._process_egress(eg, ticket.consumed, aer_dirty,
                                           act=ticket.act, tr=tr, t0=_t_dev)
            if tr:
                _obs.end(sp)
        else:
            _t_rare = _t_dev
        rare_ns = 0
        if ticket.rare:
            # the rare messages the ingest left to the ticket
            # (elections, snapshots, membership, probes): sub-phase
            # egress_rare, from where egress_mirror ended (booked
            # below, beside the host_egress it is a part of)
            if tr:
                sp = _obs.begin("ra/egress/rare", node=node)
            self._handle_rares(ticket.rare)
            rare_ns = (time.perf_counter_ns() - _t_rare) or 1
            self.counters.incr("rares_handled", len(ticket.rare))
            if tr:
                self._end_effects_span()
                _obs.end(sp)
        if self._fx_acc[0]:
            self._book_effects()
        if cpu:
            _c_eg = time.thread_time_ns()
        _t_eg = time.perf_counter_ns()
        if tr:
            sp = _obs.begin("ra/egress/aer_fanout", node=node)
        self._send_aers(aer_dirty)
        if tr:
            _obs.end(sp)
        if cpu:
            cpu_aer = time.thread_time_ns() - _c_eg
        _t_aer = time.perf_counter_ns()
        # apply progress may have released admission-window room: wake
        # parked rejected clients (no-op attribute check when none)
        self._adm_gate.open()
        # per-step wave-phase breakdown (obs.WAVE_PHASES). host_pack
        # covered queued-scatter application + mailbox build + dispatch
        # (recorded at dispatch time); device_step runs from the
        # dispatch to here, and its three sub-phases add up to it;
        # host_egress includes the rare paths, apply and client replies
        # (its leaves: egress_follow, egress_mirror and egress_apply
        # from _process_egress, egress_rare). The dispatching pass's
        # own AER fan-out is booked here too, so that aer_fanout and
        # its CPU account have one writer.
        wh = self._wave_h
        if eg_np is not None:
            self._wave_s += (
                self.clock.monotonic() - ticket.wave0 - self._wave_s) / 8
            wh["ticket_queue"].record(t_pop - ticket.t_pack)
            wh["egress_sync"].record(t_sync - t_pop)
            wh["egress_lock_wait"].record(_t_dev - t_sync)
            wh["device_step"].record(_t_dev - ticket.t_pack)
        # (a pass that found nothing to step still hands its rare
        # messages over on a ticket: a probe of a quiet group. Their
        # time is host_egress too, or no phase holds it)
        egressed = eg_np is not None or rare_ns
        if egressed:
            wh["host_egress"].record(_t_eg - _t_dev)
            if rare_ns:
                wh["egress_rare"].record(rare_ns)
        if ticket.aer0_ns is not None:
            wh["aer_fanout"].record(ticket.aer0_ns)
        wh["aer_fanout"].record(_t_aer - _t_eg)
        if cpu:
            cnt = self.counters
            shift = self._CPU_SAMPLE_SHIFT
            if ticket.aer0_cpu_ns is not None:
                cpu_aer += ticket.aer0_cpu_ns
            cnt.incr("cpu_ns_aer_fanout", cpu_aer << shift)
            if egressed:
                cnt.incr("cpu_ns_host_egress", (_c_eg - _c_dev) << shift)

    def _rares_at_ingest(self, rare, out, tr):
        """A consistent read's three legs, handled by the pass that
        routed them (docs/INTERNALS.md §13): the leader's query, the
        follower's ``HeartbeatRpc`` and the leader's ``HeartbeatReply``
        read host mirrors only, so they need not wait behind the pack
        and the device step. Caller holds the state lock with no ticket
        unrealised; their sends join ``out``, their answers to callers
        leave as the turn ends (``_turn_replies``). Returns the other
        rare messages, in their order, for the ticket."""
        early, late = [], []
        for r in rare:
            m = r[1]
            t = type(m)
            if (t is HeartbeatRpc or t is HeartbeatReply
                    or (t is tuple and m and m[0] == "consistent_query")):
                early.append(r)
            else:
                late.append(r)
        if early:
            self._holding = True
            try:
                self._handle_rares(early, out)
            finally:
                self._holding = False
            self.counters.incr("rares_early", len(early))
            if tr:
                self._end_effects_span()
        return late

    def _handle_rares(self, rares, rare_out=None) -> None:
        # rare-path outbound batches per destination ACROSS the whole
        # rare loop: an election storm over 10k groups must land on a
        # peer as a handful of ring items, not one per group — per-group
        # sends overflowed the peer's bounded ingress lane and the
        # overflow was shed as lossy traffic, wedging the un-retried
        # tail of the storm (caught by the 10240-group bench election).
        # ``rare_out``: a caller's own outbound, which it ships
        send = rare_out is None
        if send:
            rare_out = {}
        for g, msg, from_sid in rares:
            # crash isolation for the slow paths (snapshot transfer
            # decode of untrusted bytes, membership, queries): a
            # poisoned message must not kill the step thread — every
            # group on this coordinator would freeze (the actor backend
            # gets the same guarantee from scheduler crash isolation)
            try:
                self._handle_rare(g, msg, from_sid, rare_out)
            except Exception:  # noqa: BLE001
                logger.exception(
                    "coordinator %s: dropping rare message %r for group "
                    "%s after handler crash", self.name, type(msg).__name__,
                    g.name,
                )
        if send:
            for node_name, msgs in rare_out.items():
                self._send_batch(node_name, msgs)

    def _stage_app(self, gid: int, lo: int, hi: int, term: int) -> None:
        """Stage an appended run for the next dispatching pass's device
        scatter (caller holds the state lock). Contiguous same-term runs
        merge in place — the staging half of the double buffer."""
        runs = self._staged_app.get(gid)
        if runs is None:
            self._staged_app[gid] = [[lo, hi, term]]
        elif runs[-1][1] + 1 == lo and runs[-1][2] == term:
            runs[-1][1] = hi
        else:
            runs.append([lo, hi, term])

    def _stage_written(self, gid: int, idx: int) -> None:
        """Stage a durable watermark (caller holds the state lock)."""
        if self._staged_written.get(gid, 0) < idx:
            self._staged_written[gid] = idx

    def _pad(self, rows, width: int):
        """Pad scatter batches to power-of-two buckets so XLA compiles a
        handful of shapes instead of one per batch length. Pads use an
        out-of-bounds group id, which jitted scatters drop. Returns one
        jnp column per input column."""
        n = len(rows)
        cap = 1
        while cap < n:
            cap <<= 1
        arr = np.zeros((cap, width), np.int32)
        arr[n:, 0] = self.capacity
        if n:
            arr[:n] = rows
        return tuple(jnp.asarray(arr[:, c]) for c in range(width))

    def _pad3(self, triples):
        return self._pad(triples, 3)

    def _pad4(self, rows):
        return self._pad(rows, 4)

    # -- ingress routing ---------------------------------------------------

    def _route_one(self, g: GroupHost, from_sid, msg, rare, appended,
                   written, aer_dirty, route_out, now_mono=None):
        if now_mono is None:
            now_mono = self.clock.monotonic()
        if type(msg) is FromPeer:
            from_sid, msg = msg.peer, msg.msg
        t = type(msg)
        if t in MSG_OF_TYPE:
            if t is AppendEntriesRpc and msg.term >= g.term:
                g.last_contact = now_mono
                if self.lease_cfg.enabled:
                    # leader contact backing the stickiness promise,
                    # plus the follower freshness anchor for bounded
                    # local reads (docs/INTERNALS.md §20)
                    g.lease_contact = now_mono
                    if msg.commit_ts > g.fresh_anchor[1]:
                        if g.last_applied >= msg.leader_commit:
                            if msg.commit_ts > g.fresh_ts:
                                g.fresh_ts = msg.commit_ts
                        else:
                            g.fresh_anchor = (
                                msg.leader_commit, msg.commit_ts
                            )
            # host-side next_index bookkeeping rides on the same replies
            # the device will process
            elif t is AppendEntriesReply and g.role == C.R_LEADER:
                slot = g.slot_of(from_sid)
                if slot >= 0:
                    g.last_ack[slot] = now_mono
                    if self.lease_cfg.enabled and msg.term == g.term:
                        # any same-term reply (success or reject)
                        # proves contact: credit the send basis
                        self._lease_credit(g, slot)
                    if msg.success:
                        g.next_index[slot] = max(g.next_index[slot], msg.last_index + 1)
                        if slot < len(g.match_hint):
                            g.match_hint[slot] = max(
                                g.match_hint[slot], msg.last_index
                            )
                        vs = g.voter_status.get(slot)
                        if (
                            isinstance(vs, tuple)
                            and vs[0] == "nonvoter"
                            and msg.last_index >= vs[1]
                            and g.cluster_change_permitted
                        ):
                            # caught-up nonvoter: promote via a cluster
                            # change (reference: maybe_promote_peer,
                            # src/ra_server.erl:3977-3995)
                            self._handle_command(
                                g,
                                Command(kind=RA_CLUSTER_CHANGE,
                                        data=((from_sid, "voter"),)),
                                appended, written, aer_dirty,
                            )
                    else:
                        hint = max(1, min(msg.next_index, msg.last_index + 1))
                        g.next_index[slot] = min(g.next_index[slot], hint)
                    aer_dirty.add(g.gid)
                    if msg.success and g.inbox and self._supersede_ack(
                        g.inbox, from_sid, msg
                    ):
                        return
            elif (
                self.lease_cfg.enabled
                and (t is PreVoteRpc or t is RequestVoteRpc)
                and not (t is RequestVoteRpc and msg.force)
                and g.slot_of(msg.candidate_id) != g.leader_slot
                and not self._stickiness_lapsed(g, now_mono)
            ):
                # leader stickiness (§20): within one election timeout
                # of leader contact, (pre-)votes for other candidates
                # are disregarded — denied at OUR term, without letting
                # the device adopt the higher term (the term echo would
                # depose the live leader the lease depends on).
                # TimeoutNow-forced candidacies bypass: the old leader
                # revoked its lease before soliciting the vote.
                deny = (
                    PreVoteResult(g.term, msg.token, False)
                    if t is PreVoteRpc
                    else RequestVoteResult(g.term, False)
                )
                out = route_out.get(msg.candidate_id[1])
                if out is None:
                    route_out[msg.candidate_id[1]] = out = []
                out.append((msg.candidate_id, deny, (g.name, self.name)))
                return
            g.inbox.append((from_sid, msg))
            self._hot.add(g.gid)
            return
        if isinstance(msg, Command):
            self._handle_command(g, msg, appended, written, aer_dirty)
            return
        if isinstance(msg, tuple) and msg and msg[0] == "log_event":
            _, evt = msg
            g.log.handle_event(evt)
            wi, wt = g.log.last_written()
            if written.get(g.gid, 0) < wi:
                written[g.gid] = wi
            aer_dirty.add(g.gid)
            if g.pending_ack is not None and wi >= g.pending_ack[1]:
                leader_sid, cover = g.pending_ack
                g.pending_ack = None
                ack = min(wi, cover)
                at = g.log.fetch_term(ack)
                out = route_out.get(leader_sid[1])
                if out is None:
                    route_out[leader_sid[1]] = out = []
                out.append(
                    (leader_sid,
                     AppendEntriesReply(g.term, True, ack + 1, ack,
                                        at if at is not None else wt),
                     (g.name, self.name))
                )
            return
        rare.append((g, msg, from_sid))

    @staticmethod
    def _supersede_ack(inbox, from_sid, msg: AppendEntriesReply) -> bool:
        """Acks are cumulative: a success reply says of its sender all
        that an earlier success reply of the same term said. The device
        takes ONE message a group a step, so under a hot key a leader's
        lane fills with its followers' acks, two a write, and the
        newest, the one that commits, waits behind the stale ones. Where
        ``inbox`` still holds the sender's previous success of this
        term, with nothing else of the sender's after it, the new one
        takes its place (as if the old had been lost and the new had
        come sooner, which the protocol allows) and True comes back."""
        for k in range(len(inbox) - 1, -1, -1):
            fs, m = inbox[k]
            if fs == from_sid:
                if (
                    type(m) is AppendEntriesReply and m.success
                    and m.term == msg.term
                    and m.last_index <= msg.last_index
                ):
                    inbox[k] = (from_sid, msg)
                    return True
                return False
        return False

    def _handle_command(self, g: GroupHost, cmd: Command, appended, written, aer_dirty):
        self._handle_commands(g, (cmd,), appended, written, aer_dirty)

    # max low-priority commands appended per group per step (reference:
    # ?FLUSH_COMMANDS_SIZE, src/ra_server.hrl:34)
    FLUSH_COMMANDS_SIZE = 16

    def _drain_low_lane(self, appended, written, aer_dirty) -> None:
        """Bounded per-step drain of buffered low-priority commands —
        normal ingest always goes first; lows trickle in slices so a
        low-priority firehose cannot starve interactive traffic
        (reference: ra_ets_queue lane, src/ra_server_proc.erl:507-530).
        Non-leaders redirect buffered lows instead of dropping futures.
        Low-priority routing now happens at ring-drain time on the step
        thread (under the state lock), so ``low_q``/``_low_dirty`` have
        a single writer and need no extra lock."""
        dirty = self._low_dirty
        self._low_dirty = set()
        still: set = set()
        for gid in dirty:
            g = self.groups[gid]
            if g is None or not g.low_q:
                continue
            if g.role != C.R_LEADER:
                red = ("redirect", g.sid_of(g.leader_slot))
                for cmd in g.low_q:
                    if cmd.from_ref is not None:
                        self._reply(cmd.from_ref, red)
                g.low_q.clear()
                continue
            take = [
                g.low_q.popleft()
                for _ in range(min(self.FLUSH_COMMANDS_SIZE, len(g.low_q)))
            ]
            if g.low_q:
                still.add(gid)
            self._handle_commands(g, take, appended, written, aer_dirty)
        if still:
            self._low_dirty |= still

    def _handle_commands(self, g: GroupHost, cmds, appended, written, aer_dirty):
        """Append a batch of client commands for one group: one pass of
        log/run/reply bookkeeping instead of per-command."""
        if g.role != C.R_LEADER:
            red = ("redirect", g.sid_of(g.leader_slot))
            for cmd in cmds:
                if cmd.from_ref is not None:
                    self._reply(cmd.from_ref, red)
            return
        log = g.log
        term = g.term
        gid = g.gid
        pending = g.pending_replies
        me = (g.name, self.name)
        idx = log.next_index()
        first = idx
        # admission window: bound the group's appended-but-unapplied
        # backlog so a client cannot queue unbounded work ahead of apply
        # progress (the client analog of the reference's per-peer
        # pipeline window, src/ra_server.hrl:8). Commands past the
        # window are rejected with backoff (from_ref callers see
        # ("reject", "overloaded") and retry) or dropped and counted:
        # noreply commands owe no ack, and notify-mode pipelined
        # commands are at-most-once by contract (clients resend on a
        # missing applied notification — reference pipeline_command
        # semantics). Machine-INTERNAL commands (timer fires, Append
        # effects) fire exactly once with no retry path: never shed.
        if self.pressure.blocked():
            # storage-degraded pre-emption (docs/INTERNALS.md §21):
            # space-class WAL failure or hard disk watermark. Client
            # commands reject typed ("reject", "nospace") with the
            # pressure gate's waiter (opens when the probe write
            # succeeds); machine-internal commands still admit — they
            # fire exactly once with no retry path.
            admit2 = [c for c in cmds if c.internal]
            shed2 = [c for c in cmds if not c.internal]
            n_rej2 = 0
            for cmd in shed2:
                if cmd.from_ref is not None:
                    n_rej2 += 1
                    self._reply(
                        cmd.from_ref,
                        REJECT_NOSPACE + (self.pressure.waiter(),),
                    )
            if n_rej2:
                self.counters.incr("commands_rejected_nospace", n_rej2)
            if len(shed2) > n_rej2:
                self.counters.incr(
                    "commands_dropped_overload", len(shed2) - n_rej2
                )
            if shed2:
                self._obs_rec.record(
                    "admission_reject", node=self.name, group=g.name,
                    term=term,
                    detail=(f"nospace rejected={n_rej2} "
                            f"dropped={len(shed2) - n_rej2}"),
                )
            cmds = admit2
            if not cmds:
                return
        room = self.max_command_backlog - (first - 1 - g.last_applied)
        if room < len(cmds):
            admit: List[Command] = []
            shed: List[Command] = []
            for cmd in cmds:
                if cmd.internal or len(admit) < room:
                    admit.append(cmd)
                else:
                    shed.append(cmd)
            cmds = admit
            n_rej = 0
            for cmd in shed:
                if cmd.from_ref is not None:
                    n_rej += 1
                    # the reject carries an admission-gate waiter:
                    # api.process_command parks on it and is WOKEN on
                    # window release (apply progress) instead of
                    # sleeping a fixed backoff (docs/INTERNALS.md §16)
                    self._reply(
                        cmd.from_ref,
                        REJECT_OVERLOADED + (self._adm_gate.waiter(),),
                    )
            if n_rej:
                self.counters.incr("commands_rejected", n_rej)
            if len(shed) > n_rej:
                self.counters.incr(
                    "commands_dropped_overload", len(shed) - n_rej
                )
            if shed:
                self._obs_rec.record(
                    "admission_reject", node=self.name, group=g.name,
                    term=term,
                    detail=f"rejected={n_rej} dropped={len(shed) - n_rej}",
                )
            if not cmds:
                return
        # commit-stage sampling: bounded to groups on the sample mask,
        # and only for commands stamped with a submit ts
        sampled = (gid & self._lat_mask) == 0
        # fast path: plain user commands owing no replies (the pipeline
        # shape) — build the run in one pass and bulk-append it
        simple = True
        for cmd in cmds:
            if cmd.kind != USR or cmd.from_ref is not None:
                simple = False
                break
        if simple:
            entries = [Entry(first + k, term, cmd) for k, cmd in enumerate(cmds)]
            _li, prev_term = log.last_index_term()
            log.append_many(entries)
            idx = first + len(cmds)
            ft = g.fresh_tail
            if ft is not None and ft[0] + len(ft[3]) == first and ft[2] == term:
                ft[3].extend(entries)  # second batch this step: one run
            else:
                g.fresh_tail = (first, prev_term, term, entries)
        else:
            for cmd in cmds:
                if cmd.kind in (RA_JOIN, RA_LEAVE, RA_CLUSTER_CHANGE):
                    if not self._prepare_cluster_cmd(g, cmd):
                        continue
                ref = cmd.from_ref
                if ref is not None or cmd.ts is not None:
                    # the log keeps neither the reply handle (the
                    # pending-reply table does, until the reply is out)
                    # nor the submit stamp (read below, from ``cmds``):
                    # an entry lives until its log is cut, and a handle
                    # held that long is a caller's closure the collector
                    # walks at every young collection (PERF.md section
                    # 6, PR 33). What replication and the WAL would
                    # strip anyway is stripped once, here
                    log.append(Entry(
                        idx, term, cmd._replace(from_ref=None, ts=None)))
                else:
                    log.append(Entry(idx, term, cmd))
                if cmd.kind != USR:
                    g.specials.append(idx)
                if ref is not None:
                    if cmd.reply_mode == "after_log_append":
                        self._reply(ref, ("ok", (idx, term), me))
                    elif cmd.reply_mode == "await_consensus":
                        pending[idx] = ref
                idx += 1
            if pending:
                # the lane watchdog's mask: set here, after the futures
                # are in, once a batch; the watchdog clears it when it
                # finds the table empty
                self._pending_np[gid] = 1
        if idx == first:
            return  # every command was rejected
        last = idx - 1
        if sampled:
            now_ns = time.monotonic_ns()
            ts0 = cmds[0].ts
            lat = g.lat
            if ts0 is not None and (
                lat is None or now_ns - lat[1] > 10_000_000_000
            ):
                # one in-flight sample per group; a sample stranded >10s
                # (leadership churn) is abandoned and replaced
                g.lat = [last, ts0, now_ns, 0, 0]
                self._lat_gids.add(gid)
                self._commit_h["submit_append"].record(now_ns - ts0)
        runs = appended.get(gid)
        if runs is None:
            appended[gid] = [[first, last, term]]
        else:
            tail = runs[-1]
            if tail[1] + 1 == first and tail[2] == term:
                tail[1] = last
            else:
                runs.append([first, last, term])
        if log.last_written()[0] >= last and written.get(gid, 0) < last:
            written[gid] = last
        aer_dirty.add(gid)

    # -- membership (reference: $ra_join/$ra_leave handling,
    # src/ra_server.erl:3491-3542; one change in flight at a time) --------

    def _prepare_cluster_cmd(self, g: GroupHost, cmd: Command) -> bool:
        """Leader-side cluster change: apply to the host member table
        immediately (Raft new-config-on-append rule), gate one change at
        a time. Returns False when rejected (caller must not append)."""
        if not g.cluster_change_permitted:
            if cmd.from_ref is not None:
                self._reply(cmd.from_ref, ("error", "cluster_change_not_permitted"))
            return False
        # rollback point: the leader's own uncommitted change must be
        # undoable if it is deposed and a new leader truncates this
        # suffix — same protocol as follower-side _adopt_cluster_cmd
        # (the truncation rollback in _host_write_entries covers both)
        history = (g.log.next_index(), list(g.members), dict(g.voter_status))
        if cmd.kind == RA_JOIN:
            member, voter = cmd.data
            member = tuple(member)
            if member in g.members:
                if cmd.from_ref is not None:
                    self._reply(cmd.from_ref, ("ok", "already_member"))
                return False
            slot = self._alloc_slot(g)
            if slot is None:
                if cmd.from_ref is not None:
                    self._reply(cmd.from_ref, ("error", "group_at_peer_capacity"))
                return False
            li = g.log.last_index_term()[0]
            g.members[slot] = member
            g.voter_status[slot] = "voter" if voter else ("nonvoter", li)
            g.next_index[slot] = li + 1
            g.commit_sent[slot] = 0
        elif cmd.kind == RA_LEAVE:
            member = tuple(cmd.data)
            slot = g.slot_of(member)
            if slot < 0:
                if cmd.from_ref is not None:
                    self._reply(cmd.from_ref, ("ok", "not_member"))
                return False
            g.members[slot] = None
            g.voter_status[slot] = None
        else:  # RA_CLUSTER_CHANGE: explicit voter-status updates
            for member, vs in cmd.data:
                slot = g.slot_of(tuple(member))
                if slot >= 0:
                    g.voter_status[slot] = vs
        g.cluster_history.append(history)
        del g.cluster_history[:-8]
        g.cluster_change_permitted = False
        g.cluster_index = g.log.next_index()
        self._sync_member_rows(g)
        return True

    def _alloc_slot(self, g: GroupHost) -> Optional[int]:
        for i, m in enumerate(g.members):
            if m is None:
                g.last_ack[i] = 0.0  # fresh occupant, fresh liveness
                g.match_hint[i] = 0  # nothing confirmed for the newcomer
                return i  # reuse a tombstoned slot
        if len(g.members) < self.P:
            g.members.append(None)
            g.next_index.append(1)
            g.commit_sent.append(0)
            g.match_hint.append(0)
            return len(g.members) - 1
        return None

    def _set_self_slot(self, g: GroupHost) -> None:
        """The host's new slot for this node in ``g``'s member table,
        to the device (under the state lock)."""
        self.state = C.set_groups(
            self.state, np.array([g.gid], np.int32),
            {"self_slot": np.array([g.self_slot], np.int32)})

    def _follow_on_device(self, g: GroupHost, term: int,
                          clear_vote: bool) -> None:
        """The device side of a term learnt outside the mailbox (a
        heartbeat reply, a snapshot): ``g`` follows ``g.leader_slot``
        at the larger of its term and ``term``, its vote cleared where
        the term moved (under the state lock). A role queued for the
        next step's scatter before this (a pre-vote round the group's
        election timeout started) is superseded, not applied after."""
        if self._pending_roles:
            self._pending_roles = [
                pr for pr in self._pending_roles if pr[0] != g.gid]
        one = np.array([g.gid], np.int32)
        fields = {"leader_slot": np.array([g.leader_slot], np.int32),
                  "role": np.array([C.R_FOLLOWER], np.int32)}
        if clear_vote:
            fields["voted_for"] = np.array([-1], np.int32)
        self.state = C.set_groups(
            self.state, one, fields,
            {"current_term": np.array([term], np.int32)})

    def _sync_member_rows(self, g: GroupHost) -> None:
        """Scatter the host member table's active/voting view to the
        device (call sites all run under the state lock)."""
        active = np.zeros(self.P, dtype=bool)
        voting = np.zeros(self.P, dtype=bool)
        for i, m in enumerate(g.members):
            if m is not None:
                active[i] = True
                voting[i] = g.voter_status.get(i) == "voter"
        self.state = C.set_groups(self.state, np.array([g.gid], np.int32), {
            "active": active[None], "voting": voting[None],
        })
        self._sync_peer_row(g)
        if self.lease_cfg.enabled:
            if g.role == C.R_LEADER:
                self._lease_revoke(g, "membership change")
            self._lease_sync(g)

    def _sync_peer_row(self, g: GroupHost) -> None:
        """The member table as the detector's masks see it: which slots
        hold a peer, and the nodes any member lives on."""
        row = self._peer_np[g.gid]
        row[:] = False
        for s, m in enumerate(g.members):
            if m is not None:
                row[s] = s != g.self_slot
                self._peer_nodes.add(m[1])

    def _adopt_cluster_cmd(self, g: GroupHost, cmd: Command, entry_index: int = 0) -> None:
        """Follower-side adoption of a replicated cluster change (slot
        coordinates are node-local; only the member set must agree)."""
        g.cluster_history.append(
            (entry_index, list(g.members), dict(g.voter_status))
        )
        del g.cluster_history[:-8]
        if cmd.kind == RA_JOIN:
            member, voter = cmd.data
            member = tuple(member)
            slot = g.slot_of(member)
            if slot < 0:
                slot = self._alloc_slot(g)
                if slot is not None:
                    g.members[slot] = member
            if slot is not None and slot >= 0:
                # also covers the joining member itself learning its own
                # (non)voter status from the replicated entry; the join
                # entry's index is the catch-up target should this node
                # lead later (never 0 — that would promote a lagging
                # learner on its first ack)
                g.voter_status[slot] = (
                    "voter" if voter else ("nonvoter", entry_index)
                )
        elif cmd.kind == RA_LEAVE:
            slot = g.slot_of(tuple(cmd.data))
            if slot >= 0:
                g.members[slot] = None
                g.voter_status[slot] = None
        else:
            if cmd.data and cmd.data[0] == "replace":
                # force-shrink style replacement
                new = [tuple(m) for m, _vs in cmd.data[1]]
                me = (g.name, self.name)
                if me in new:
                    g.members = list(new)
                    g.self_slot = new.index(me)
                    g.voter_status = {i: "voter" for i in range(len(new))}
                    g.next_index = [1] * len(new)
                    g.commit_sent = [0] * len(new)
                    g.match_hint = [0] * len(new)
                    self._set_self_slot(g)
            else:
                for member, vs in cmd.data:
                    slot = g.slot_of(tuple(member))
                    if slot >= 0:
                        g.voter_status[slot] = vs
        self._sync_member_rows(g)

    # -- mailbox build -----------------------------------------------------

    # packed mailbox row indexes (see C.MBOX_FIELDS), plus the fused
    # scatter rows that ride the same buffer (C.MBOX_SCAT_FIELDS)
    _R = {
        name: i
        for i, name in enumerate(list(C.MBOX_FIELDS) + C.MBOX_SCAT_FIELDS)
    }
    _NROWS = len(C.MBOX_FIELDS) + len(C.MBOX_SCAT_FIELDS)

    # mailbox row-index vectors for the two hot message types, in the
    # flat value order _pack_hot builds (the native rt_pack_mbox ABI)
    _REP_ROWS = np.asarray(
        [_R["msg_type"], _R["sender_slot"], _R["term"], _R["success"],
         _R["reply_next_idx"], _R["reply_last_idx"],
         _R["reply_last_term"]],
        np.int32,
    )
    _AER_ROWS = np.asarray(
        [_R["msg_type"], _R["sender_slot"], _R["term"], _R["prev_idx"],
         _R["prev_term"], _R["num_entries"], _R["entries_last_term"],
         _R["leader_commit"]],
        np.int32,
    )

    def _pack_hot(self, packed, aer_i, aer_m, aer_s, rep_i, rep_m,
                  rep_s) -> None:
        """Columnwise encode of the two hot message types into the
        packed mailbox. With the native pack path on, each class is one
        flat int64 value pass + one GIL-released scatter
        (rt_pack_mbox); otherwise (or while any failpoint is armed, or
        on a scatter bounds failure) the original per-field numpy
        column stores run — both produce byte-identical buffers."""
        if (
            (rep_i or aer_i)
            and self._nat_pack
            and not faults.anything_armed()
        ):
            t0 = time.perf_counter_ns()
            ok = True
            if rep_i:
                vals: List[int] = []
                ext = vals.extend
                for s, m in zip(rep_s, rep_m):
                    ext((C.MSG_AER_REPLY, s, m.term,
                         1 if m.success else 0, m.next_index,
                         m.last_index, m.last_term))
                ok = _native.pack_mbox(packed, rep_i, vals, self._REP_ROWS)
            if ok and aer_i:
                vals = []
                ext = vals.extend
                for s, m in zip(aer_s, aer_m):
                    ext((C.MSG_AER, s, m.term, m.prev_log_index,
                         m.prev_log_term, len(m.entries),
                         m.entries[-1].term if m.entries else 0,
                         m.leader_commit))
                ok = _native.pack_mbox(packed, aer_i, vals, self._AER_ROWS)
            if ok:
                self._wave_h["pack_native"].record(
                    time.perf_counter_ns() - t0)
                self.counters.incr("native_pack_batches")
                self.counters.incr("native_pack_msgs",
                                   len(rep_i) + len(aer_i))
                return
            # partial native success is harmless: the Python stores
            # below rewrite the same cells with the same values
            self.counters.incr("native_fallbacks")
        R = self._R
        if rep_i:
            ii = np.asarray(rep_i, np.int64)
            packed[R["msg_type"], ii] = C.MSG_AER_REPLY
            packed[R["sender_slot"], ii] = rep_s
            packed[R["term"], ii] = [m.term for m in rep_m]
            packed[R["success"], ii] = [1 if m.success else 0 for m in rep_m]
            packed[R["reply_next_idx"], ii] = [m.next_index for m in rep_m]
            packed[R["reply_last_idx"], ii] = [m.last_index for m in rep_m]
            packed[R["reply_last_term"], ii] = [m.last_term for m in rep_m]
        if aer_i:
            ii = np.asarray(aer_i, np.int64)
            packed[R["msg_type"], ii] = C.MSG_AER
            packed[R["sender_slot"], ii] = aer_s
            packed[R["term"], ii] = [m.term for m in aer_m]
            packed[R["prev_idx"], ii] = [m.prev_log_index for m in aer_m]
            packed[R["prev_term"], ii] = [m.prev_log_term for m in aer_m]
            packed[R["num_entries"], ii] = [len(m.entries) for m in aer_m]
            packed[R["entries_last_term"], ii] = [
                m.entries[-1].term if m.entries else 0 for m in aer_m
            ]
            packed[R["leader_commit"], ii] = [m.leader_commit for m in aer_m]

    def _fill_scat(self, packed: np.ndarray, app_rows, written) -> None:
        """Write the fused log-tail scatter rows: the newest appended
        run per group and the durable watermarks, pad gid = capacity
        (device scatters drop out-of-range rows)."""
        R = self._R
        packed[R["a_gid"]].fill(self.capacity)
        packed[R["w_gid"]].fill(self.capacity)
        if app_rows:
            ar = np.asarray(app_rows, np.int64)
            n = len(app_rows)
            packed[R["a_gid"], :n] = ar[:, 0]
            packed[R["a_lo"], :n] = ar[:, 1]
            packed[R["a_hi"], :n] = ar[:, 2]
            packed[R["a_term"], :n] = ar[:, 3]
        if written:
            n = len(written)
            packed[R["w_gid"], :n] = np.fromiter(written.keys(), np.int64, n)
            packed[R["w_idx"], :n] = np.fromiter(written.values(), np.int64, n)

    def _mbox_take(self, width: Optional[int] = None) -> np.ndarray:
        """Pop a zeroed pack buffer from the pool: the full-width
        ``(_NROWS, capacity)`` mailbox by default, or an active-set
        buffer of a power-of-two sub-batch ``width``, which has one row
        more for the gather index (``_NROWS + 1`` rows; its builder
        fills that row). Allocates when empty — pool size is bounded by
        the tickets in flight."""
        if width is None:
            shape = (self._NROWS, self.capacity)
            spare = self._spare_mbox
            if spare is not None:
                # the spare was pre-zeroed while the last step computed
                # — no take/zero cost here
                self._spare_mbox = None
                return spare
        else:
            shape = (self._NROWS + 1, width)
        pool = self._mbox_pool
        for k, buf in enumerate(pool):
            if buf.shape == shape:
                del pool[k]
                buf.fill(0)
                return buf
        return np.zeros(shape, np.int32)

    def _mbox_release(self, buf: Optional[np.ndarray]) -> None:
        """Return a pack buffer to the pool. The jitted step took the
        numpy array as its argument: where the backend copies it (the
        TPU's transfer) the host buffer is free once the call returns,
        where it may alias host memory (the CPU backend) only once the
        program has run. The callers hold to the stricter: they release
        after the step's egress sync."""
        if buf is not None and len(self._mbox_pool) < 6:
            self._mbox_pool.append(buf)

    def _build_mailbox(self, app_rows=None, written=None):
        packed = self._mbox_take()
        self._fill_scat(packed, app_rows, written)
        R = self._R
        packed[R["host_term_idx"]].fill(-1)
        packed[R["host_term_val"]].fill(-1)
        consumed: Dict[int, Tuple[Any, Any]] = {}
        hot = self._hot
        self._hot = set()
        groups = self.groups
        # the two hot message types are encoded COLUMNWISE after the pop
        # loop (numpy scalar stores per field per message were a top
        # cost); everything else goes through the scalar _encode
        aer_i: List[int] = []
        aer_m: List[AppendEntriesRpc] = []
        aer_s: List[int] = []
        rep_i: List[int] = []
        rep_m: List[AppendEntriesReply] = []
        rep_s: List[int] = []
        for i in hot:
            g = groups[i]
            if g is None:
                continue
            if g.host_term_hint is not None:
                packed[R["host_term_idx"], i] = g.host_term_hint[0]
                packed[R["host_term_val"], i] = g.host_term_hint[1]
                g.host_term_hint = None
            if not g.inbox:
                continue
            from_sid, msg = g.inbox.popleft()
            consumed[i] = (from_sid, msg)
            t = type(msg)
            if t is AppendEntriesRpc:
                aer_i.append(i)
                aer_m.append(msg)
                aer_s.append(g.slot_of(from_sid) if from_sid else 0)
            elif t is AppendEntriesReply:
                rep_i.append(i)
                rep_m.append(msg)
                rep_s.append(g.slot_of(from_sid) if from_sid else 0)
            else:
                self._encode(g, from_sid, msg, packed, i)
            if g.inbox:
                self._hot.add(i)  # more queued: stay hot for next step
        self._pack_hot(packed, aer_i, aer_m, aer_s, rep_i, rep_m, rep_s)
        return packed, consumed

    def _build_mailbox_sub(self, act, app_rows=None, written=None):
        """Compact mailbox for the active-set step: one COLUMN PER
        ACTIVE GROUP (power-of-two padded), with the gather index
        (column -> group id) as the buffer's last row. ``consumed`` is
        keyed by column position (the egress arrays come back in the
        same position space). Same pop-one-message-per-group semantics
        as the full-width builder. Returns ``(packed, act_np,
        consumed)``; like ``_build_mailbox`` it hands back the numpy
        buffer itself, which the jitted step takes as it is."""
        n = len(act)
        # pad floor bounds the number of compiled shapes (straggler
        # tails would otherwise walk every power of two down to 1)
        cap = min(256, self.capacity)
        while cap < n:
            cap <<= 1
        packed = self._mbox_take(cap)
        self._fill_scat(packed, app_rows, written)
        R = self._R
        packed[R["host_term_idx"]].fill(-1)
        packed[R["host_term_val"]].fill(-1)
        gidx = packed[-1]
        gidx.fill(self.capacity)  # pads dropped on scatter
        gidx[:n] = act
        self._hot = set()
        consumed: Dict[int, Tuple[Any, Any]] = {}
        groups = self.groups
        aer_i: List[int] = []
        aer_m: List[AppendEntriesRpc] = []
        aer_s: List[int] = []
        rep_i: List[int] = []
        rep_m: List[AppendEntriesReply] = []
        rep_s: List[int] = []
        for p, i in enumerate(act):
            g = groups[i]
            if g is None:
                continue
            if g.host_term_hint is not None:
                packed[R["host_term_idx"], p] = g.host_term_hint[0]
                packed[R["host_term_val"], p] = g.host_term_hint[1]
                g.host_term_hint = None
            if not g.inbox:
                continue
            from_sid, msg = g.inbox.popleft()
            consumed[p] = (from_sid, msg)
            t = type(msg)
            if t is AppendEntriesRpc:
                aer_i.append(p)
                aer_m.append(msg)
                aer_s.append(g.slot_of(from_sid) if from_sid else 0)
            elif t is AppendEntriesReply:
                rep_i.append(p)
                rep_m.append(msg)
                rep_s.append(g.slot_of(from_sid) if from_sid else 0)
            else:
                self._encode(g, from_sid, msg, packed, p)
            if g.inbox:
                self._hot.add(i)  # more queued: stay hot for next step
        self._pack_hot(packed, aer_i, aer_m, aer_s, rep_i, rep_m, rep_s)
        return packed, np.asarray(act, np.int64), consumed

    def _encode(self, g: GroupHost, from_sid, msg, p, i) -> None:
        R = self._R
        p[R["sender_slot"], i] = g.slot_of(from_sid) if from_sid else 0
        if isinstance(msg, AppendEntriesRpc):
            p[R["msg_type"], i] = C.MSG_AER
            p[R["term"], i] = msg.term
            p[R["prev_idx"], i] = msg.prev_log_index
            p[R["prev_term"], i] = msg.prev_log_term
            p[R["num_entries"], i] = len(msg.entries)
            p[R["entries_last_term"], i] = msg.entries[-1].term if msg.entries else 0
            p[R["leader_commit"], i] = msg.leader_commit
        elif isinstance(msg, AppendEntriesReply):
            p[R["msg_type"], i] = C.MSG_AER_REPLY
            p[R["term"], i] = msg.term
            p[R["success"], i] = 1 if msg.success else 0
            p[R["reply_next_idx"], i] = msg.next_index
            p[R["reply_last_idx"], i] = msg.last_index
            p[R["reply_last_term"], i] = msg.last_term
        elif isinstance(msg, RequestVoteRpc):
            p[R["msg_type"], i] = C.MSG_VOTE_REQ
            p[R["term"], i] = msg.term
            p[R["sender_slot"], i] = g.slot_of(msg.candidate_id)
            p[R["cand_last_idx"], i] = msg.last_log_index
            p[R["cand_last_term"], i] = msg.last_log_term
        elif isinstance(msg, RequestVoteResult):
            p[R["msg_type"], i] = C.MSG_VOTE_REPLY
            p[R["term"], i] = msg.term
            p[R["success"], i] = 1 if msg.vote_granted else 0
        elif isinstance(msg, PreVoteRpc):
            p[R["msg_type"], i] = C.MSG_PREVOTE_REQ
            p[R["term"], i] = msg.term
            p[R["sender_slot"], i] = g.slot_of(msg.candidate_id)
            p[R["cand_last_idx"], i] = msg.last_log_index
            p[R["cand_last_term"], i] = msg.last_log_term
            p[R["cand_machine_version"], i] = msg.machine_version
        elif isinstance(msg, PreVoteResult):
            p[R["msg_type"], i] = C.MSG_PREVOTE_REPLY
            p[R["term"], i] = msg.term
            p[R["success"], i] = 1 if msg.vote_granted else 0
            p[R["token"], i] = msg.token

    # -- egress ------------------------------------------------------------

    def _process_egress(self, eg, consumed, aer_dirty, act=None,
                        tr=False, t0=0) -> int:
        """Realise one step's egress. ``act`` is None for the full-width
        step (egress row == group id) or the i64 position->gid map of an
        active-set step (egress row == position in ``act``); ``consumed``
        is keyed in the same space as the egress rows. ``tr``: a
        profiler session takes the spans; ``t0``: where the wave phase
        ``host_egress`` started (``perf_counter_ns``). Two clock reads
        split the time since ``t0`` into the sub-phases
        ``egress_follow`` (up to the end of the loop over the consumed
        messages) and ``egress_mirror`` (all after it, less the applies,
        which are ``egress_apply``). Returns the second."""
        outbound: Dict[str, List[Tuple[ServerId, Any, ServerId]]] = {}

        def queue_send(to: ServerId, msg: Any, frm: ServerId):
            out = outbound.get(to[1])
            if out is None:
                outbound[to[1]] = out = []
            out.append((to, msg, frm))

        groups = self.groups
        needs_host = eg["needs_host"]
        # numpy scalar indexing (plus int()/bool() coercion) in a
        # per-message loop is slow; gather each needed field for exactly
        # the consumed rows in one vector op, then read python ints
        clock_ns = time.perf_counter_ns
        t_mark = t0 or clock_ns()
        if consumed:
            if tr:
                sp = _obs.begin("ra/egress/host_egress/follow",
                                node=self.name, msgs=len(consumed))
            n_faer = n_fent = 0
            items = list(consumed.items())
            ci = np.fromiter((i for i, _ in items), np.int64, len(items))
            nh_l = needs_host[ci].tolist()
            code_l = eg["aer_code"][ci].tolist()
            sr_l = eg["send_reply"][ci].tolist()
            term_l = eg["term"][ci].tolist()
            succ_l = eg["success"][ci].tolist()
            nxt_l = eg["next_index"][ci].tolist()
            li_l = eg["last_index"][ci].tolist()
            lt_l = eg["last_term"][ci].tolist()
            for p, (i, (from_sid, msg)) in enumerate(items):
                g = groups[i if act is None else act[i]]
                if g is None:
                    continue
                t = type(msg)
                if t is AppendEntriesRpc:
                    if nh_l[p]:
                        self._host_resolve_aer(g, from_sid, msg, queue_send)
                    elif code_l[p] == C.AER_OK:
                        # the host performs the write and owns the
                        # durable watermark, so it builds the success
                        # ack (possibly deferred until WAL fsync)
                        if msg.entries:
                            n_faer += 1
                            n_fent += len(msg.entries)
                        self._host_write_entries(g, msg)
                        self._ack_aer(g, from_sid, msg, term_l[p], outbound)
                    elif sr_l[p] and from_sid is not None:
                        queue_send(
                            from_sid,
                            AppendEntriesReply(
                                term_l[p], bool(succ_l[p]), nxt_l[p],
                                li_l[p], lt_l[p],
                            ),
                            (g.name, self.name),
                        )
                elif sr_l[p] and from_sid is not None:
                    if t is RequestVoteRpc:
                        if succ_l[p]:
                            # granting a vote resets the election timer
                            # (Raft §3.4): the granter must give its
                            # candidate a full round before campaigning
                            # itself, or dueling candidacies ping-pong
                            g.last_contact = self.clock.monotonic()
                        queue_send(
                            from_sid,
                            RequestVoteResult(term_l[p], bool(succ_l[p])),
                            (g.name, self.name),
                        )
                    elif t is PreVoteRpc:
                        if succ_l[p]:
                            g.last_contact = self.clock.monotonic()
                        queue_send(
                            from_sid,
                            PreVoteResult(term_l[p], msg.token, bool(succ_l[p])),
                            (g.name, self.name),
                        )
            t_follow = clock_ns()
            self._wave_h["egress_follow"].record(t_follow - t_mark)
            t_mark = t_follow
            if n_faer:
                cnt = self.counters
                cnt.incr("follower_aers", n_faer)
                cnt.incr("follower_entries", n_fent)
            if tr:
                _obs.end(sp)
        if tr:
            sp = _obs.begin("ra/egress/host_egress/mirror", node=self.name)
        apply_ns = 0

        # vectorized change detection: only touched groups pay Python cost
        n = self.n_groups if act is None else len(act)
        applied = (
            self._applied_np[:n] if act is None else self._applied_np[act]
        )
        interesting = np.flatnonzero(
            eg["became_candidate"][:n]
            | eg["became_leader"][:n]
            | eg["term_or_vote_changed"][:n]
            | (eg["commit_advanced_to"][:n] > applied)
            | needs_host[:n]
        )
        touched = (
            interesting.tolist() if len(consumed) == 0
            else list(set(consumed) | set(interesting.tolist()))
        )
        if touched:
            ti = np.asarray(touched, np.int64)
            role_l = eg["role"][ti].tolist()
            gterm_l = eg["term"][ti].tolist()
            leader_l = eg["leader_slot"][ti].tolist()
            tvc_l = eg["term_or_vote_changed"][ti].tolist()
            voted_l = eg["voted_for"][ti].tolist()
            bc_l = eg["became_candidate"][ti].tolist()
            bl_l = eg["became_leader"][ti].tolist()
            ca_l = eg["commit_advanced_to"][ti].tolist()
            nh2_l = needs_host[ti].tolist()
            ag_l = eg["agreed_idx"][ti].tolist()
            now_roles = self.clock.monotonic()
            # machine apply and client replies of every group this step
            # committed: each in its place, their clock pairs added up
            for p, pos in enumerate(touched):
                i = pos if act is None else int(act[pos])
                g = groups[i]
                if g is None:
                    continue
                new_role = role_l[p]
                old_role = g.role
                if new_role != old_role:
                    self._obs_rec.record(
                        "role_change", node=self.name, group=g.name,
                        term=gterm_l[p],
                        detail=f"{self._ROLE_NAMES.get(old_role, old_role)}->"
                               f"{self._ROLE_NAMES.get(new_role, new_role)}",
                    )
                    # role transitions restart the leaderless-suspicion
                    # window (a just-deposed leader must give the new
                    # one a chance to make contact before suspecting)
                    g.last_contact = now_roles
                if old_role == C.R_LEADER and new_role != C.R_LEADER:
                    # deposed: in-flight linearizable reads must not be
                    # answered from this replica's state, and pending
                    # command futures must redirect rather than hang
                    # their clients until timeout
                    self._lease_revoke(g, "left leader")
                    for q in g.pending_queries:
                        self._reply(q["fut"], ("redirect", None))
                    g.pending_queries = []
                    g.leader_slot = leader_l[p]  # hint before the sweep
                    self._fail_pending(g)
                entered = (new_role == C.R_LEADER) != (old_role == C.R_LEADER)
                if new_role != old_role:
                    g.role = new_role  # (a store into the mirror too)
                g.term = gterm_l[p]
                g.leader_slot = leader_l[p]
                if tvc_l[p] and self.meta is not None:
                    # Raft safety: term AND vote must both be durable
                    # before any message leaves this step, or a
                    # restarted member could vote twice in one term
                    uid = f"{g.cluster_name}_{g.name}"
                    self.meta.store(uid, "current_term", g.term)
                    self.meta.store_sync(uid, "voted_for", g.sid_of(voted_l[p]))
                if bc_l[p]:
                    self._hot.add(i)  # keep stepping (single-member self-election)
                    self._broadcast_vote_req(g, queue_send, pre=False)
                if bl_l[p]:
                    self._on_became_leader(g, aer_dirty)
                if entered:
                    self._state_enter(g)
                ci2 = ca_l[p]
                if ci2 > g.last_applied:
                    _t_app = clock_ns()
                    self._apply_group(g, ci2)
                    apply_ns += clock_ns() - _t_app
                    aer_dirty.add(i)
                if nh2_l[p] and g.host_term_hint is None:
                    # quorum term lookup outside the device window (the
                    # AER branch may already have claimed the hint slot;
                    # that one retries first and the quorum resolves
                    # next step)
                    agreed = ag_l[p]
                    t2 = g.log.fetch_term(agreed)
                    if t2 is not None:
                        g.host_term_hint = (agreed, t2)
                        self._hot.add(i)
            if apply_ns:
                # sub-phase egress_apply: the step's applies, one record
                self._wave_h["egress_apply"].record(apply_ns)
            if self._fx_span is not None:
                self._end_effects_span()

        for node_name, msgs in outbound.items():
            self._send_batch(node_name, msgs)
        # sub-phase egress_mirror: all since the consumed loop, the
        # applies taken out (they are egress_apply's)
        t_end = clock_ns()
        self._wave_h["egress_mirror"].record(t_end - t_mark - apply_ns)
        if tr:
            _obs.end(sp)
        return t_end

    def _host_resolve_aer(self, g: GroupHost, from_sid, msg: AppendEntriesRpc, queue_send):
        """Deep backfill: resolve the prev term from the host log and
        re-enqueue with an override (or reject directly when absent)."""
        t = g.log.fetch_term(msg.prev_log_index)
        if t is None:
            li, lt = g.log.last_index_term()
            snap = g.log.snapshot_index_term()
            from ra_tpu.ops import decisions as dec

            nid = dec.aer_failure_next_index(
                g.last_applied, li, msg.prev_log_index, snap[0] if snap else 0
            )
            queue_send(
                from_sid,
                AppendEntriesReply(g.term, False, nid, li, lt),
                (g.name, self.name),
            )
            return
        g.host_term_hint = (msg.prev_log_index, t)
        g.inbox.appendleft((from_sid, msg))  # retry next step with override
        self._hot.add(g.gid)

    def _host_write_entries(self, g: GroupHost, msg: AppendEntriesRpc) -> None:
        if not msg.entries:
            return
        li, _ = g.log.last_index_term()
        if msg.entries[0].index == li + 1:
            # fast path (steady-state pipeline): strictly-new suffix
            to_write = msg.entries
        else:
            to_write = []
            for e in msg.entries:
                if e.index <= li and g.log.fetch_term(e.index) == e.term:
                    continue
                to_write = [x for x in msg.entries if x.index >= e.index]
                break
            if not to_write and msg.entries[-1].index > li:
                to_write = [e for e in msg.entries if e.index > li]
        if to_write:
            first_idx = to_write[0].index
            if first_idx <= li:
                # overwriting a divergent suffix: truncated specials are
                # gone, and any cluster adoption that rode on them must
                # be rolled back. The ack-suppression key is also
                # invalidated — its (sid, term, ack) invariant only
                # holds while acked entries are never truncated
                g.last_ok_sent = None
                # pending futures for truncated indexes are provably
                # dead (the entries are being overwritten): redirect
                # their clients to the new leader now — a clean
                # "redirect" verdict, safe to retry exactly-once
                self._fail_pending(g, from_idx=first_idx, verdict="redirect")
                if g.specials and g.specials[-1] >= first_idx:
                    g.specials = [s for s in g.specials if s < first_idx]
                if g.cluster_history:
                    keep = [h for h in g.cluster_history if h[0] < first_idx]
                    undone = [h for h in g.cluster_history if h[0] >= first_idx]
                    if undone:
                        _, members, voter = undone[0]
                        g.members = list(members)
                        g.voter_status = dict(voter)
                        g.cluster_history = keep
                        self._sync_member_rows(g)
            g.log.write(to_write)
            # followers adopt replicated cluster changes at write time
            # (reference: cluster scan on follower writes,
            # src/ra_server.erl:1005-1040) and index every non-USR
            # entry for the apply fast path. A leader-stamped plain_usr
            # batch skips the scan (the hot pipeline shape).
            if not msg.plain_usr:
                specials = g.specials
                for e in to_write:
                    c = e.cmd
                    if type(c) is not Command:
                        specials.append(e.index)
                        continue
                    k = c.kind
                    if k != USR:
                        specials.append(e.index)
                        if k in (RA_JOIN, RA_LEAVE, RA_CLUSTER_CHANGE):
                            self._adopt_cluster_cmd(g, c, e.index)
            # reconcile the device term ring exactly (clears the
            # multi-entry unknown interval next step). Raft log terms
            # are monotonic, so equal first/last terms mean ONE run —
            # the per-entry split loop only runs for term-crossing
            # batches (rare: a new leader resending mixed history)
            first = to_write[0]
            last = to_write[-1]
            if first.term == last.term:
                self._stage_app(g.gid, first.index, last.index, first.term)
            else:
                lo = prev = first.index
                term = first.term
                for e in to_write[1:]:
                    if e.term != term:
                        self._stage_app(g.gid, lo, prev, term)
                        lo, term = e.index, e.term
                    prev = e.index
                self._stage_app(g.gid, lo, prev, term)
            wi, _ = g.log.last_written()
            if wi >= to_write[-1].index:
                self._stage_written(g.gid, wi)

    def _ack_aer(self, g: GroupHost, from_sid, msg: AppendEntriesRpc, term, outbound):
        """Success ack with the host's durable watermark, anchored to
        what THIS AER covered (a shorter-logged new leader must not see
        acks above its own prev — mirrors the scalar backend); deferred
        until the WAL confirms when the write is still in flight.
        Appends into the caller's per-destination ``outbound`` map (hot
        path: one ack per follower group per step)."""
        last_entry = msg.entries[-1].index if msg.entries else msg.prev_log_index
        wi, wt = g.log.last_written()
        if wi >= last_entry:
            ack = min(wi, last_entry)
            prev = g.last_ok_sent
            now = self.clock.monotonic()
            if (
                prev is not None
                and prev[0] == from_sid
                and prev[1] == term
                and prev[2] == ack
                and now - prev[3] < self.tick_interval_s
            ):
                return  # identical ack just sent: nothing new for the leader
            g.last_ok_sent = (from_sid, term, ack, now)
            # steady state acks exactly at the watermark: reuse its term
            at = wt if ack == wi else g.log.fetch_term(ack)
            out = outbound.get(from_sid[1])
            if out is None:
                outbound[from_sid[1]] = out = []
            out.append((
                from_sid,
                AppendEntriesReply(term, True, ack + 1, ack,
                                   at if at is not None else wt),
                (g.name, self.name),
            ))
        else:
            g.pending_ack = (from_sid, last_entry)

    def _on_became_leader(self, g: GroupHost, aer_dirty) -> None:
        if self.lease_cfg.enabled:
            # fresh leadership starts bare: the lease is earned by this
            # term's own acks, never inherited from stale stamps
            gid = g.gid
            self._lease_expiry[gid] = 0.0
            self._lease_sent[gid, :] = 0.0
            self._lease_basis[gid, :] = 0.0
            self._lease_renew_t[gid] = 0.0
            self._lease_dirty.discard(gid)
        li, _ = g.log.last_index_term()
        g.next_index = [li + 1] * len(g.members)
        g.commit_sent = [0] * len(g.members)
        g.match_hint = [0] * len(g.members)
        g.last_ack[:] = 0.0
        g.leader_slot = g.self_slot
        leaderboard.record(g.cluster_name, (g.name, self.name), tuple(g.members))
        # the new term's noop (commit gate + version carrier)
        idx = g.log.next_index()
        g.log.append(Entry(index=idx, term=g.term, cmd=Command(kind=NOOP)))
        g.specials.append(idx)
        g.noop_index = idx
        g.noop_committed = False
        g.cluster_change_permitted = False
        self._stage_app(g.gid, idx, idx, g.term)
        wi, _ = g.log.last_written()
        if wi >= idx:
            self._stage_written(g.gid, wi)
        aer_dirty.add(g.gid)

    def _apply_group(self, g: GroupHost, commit_index: int) -> None:
        li, _ = g.log.last_index_term()
        hi = min(commit_index, li)
        if hi <= g.last_applied:
            return
        # commit-stage sample: the tracked entry commits (and applies)
        # in THIS call iff it is durable and within hi; ``lat`` stays a
        # local None otherwise so the hot loop pays one check per entry
        lat = g.lat
        if lat is not None:
            if lat[3] == 0 or lat[0] > hi:
                lat = None  # not durable yet / commits in a later round
            elif lat[4] == 0:
                lat[4] = time.monotonic_ns()
                self._commit_h["durable_commit"].record(lat[4] - lat[3])
        # hot loop: locals bound once, apply-result normalization inlined
        # (machines return (state, reply) or (state, reply, effects))
        entries = g.log.fetch_range(g.last_applied + 1, hi)
        if len(entries) != hi - g.last_applied:
            # fail fast like fold(): a gap below the commit index is a
            # log integrity violation, never something to skip silently
            raise KeyError(
                f"missing log entries applying ({g.last_applied}, {hi}] "
                f"in group {g.name}: got {len(entries)}"
            )
        pending = g.pending_replies
        machine = g.machine
        mver = g.effective_machine_version
        state = g.machine_state
        is_leader = g.role == C.R_LEADER
        specials = g.specials
        if specials and specials[0] <= g.last_applied:
            # stale entries (already applied or compacted away)
            g.specials = specials = [s for s in specials if s > g.last_applied]
        if (
            not pending
            and len(entries) > 1
            and (not specials or specials[0] > hi)
        ):
            # plain user-command run with no replies owed (the specials
            # index proves it without scanning): offer the machine the
            # whole payload batch at once (apply_many hook)
            batched = machine.which_module(mver).apply_many(
                {"index": hi, "term": entries[-1].term,
                 "machine_version": mver},
                [e.cmd.data for e in entries], state,
            )
            if batched is not None:
                g.machine_state = batched
                g.last_applied = hi
                self._applied_np[g.gid] = hi
                if self.lease_cfg.enabled:
                    self._lease_applied(g, hi)
                if lat is not None:
                    # noreply pipeline shape: the reply stage is the
                    # post-apply bookkeeping fan-out (no future owed)
                    now2 = time.monotonic_ns()
                    self._commit_h["commit_apply"].record(now2 - lat[4])
                    self._commit_gates(g, hi, is_leader)
                    self._commit_h["apply_reply"].record(
                        time.monotonic_ns() - now2
                    )
                    g.lat = None
                    self._lat_gids.discard(g.gid)
                else:
                    self._commit_gates(g, hi, is_leader)
                return
        mac = machine.which_module(mver)
        apply_fn = mac.apply
        me = (g.name, self.name)
        for entry in entries:
            cmd = entry.cmd
            if not isinstance(cmd, Command):
                continue
            kind = cmd.kind
            if kind == USR:
                res = apply_fn(
                    {"index": entry.index, "term": entry.term,
                     "machine_version": mver},
                    cmd.data, state,
                )
                state = res[0]
                if len(res) > 2 and res[2]:
                    g.machine_state = state  # effects may read/snapshot it
                    self._realise_effects(g, res[2], is_leader)
                if lat is not None and entry.index == lat[0]:
                    t_ap = time.monotonic_ns()
                    self._commit_h["commit_apply"].record(t_ap - lat[4])
                    if pending:
                        fut = pending.pop(entry.index, None)
                        if fut is not None and is_leader:
                            self._reply(fut, ("ok", res[1], me))
                    self._commit_h["apply_reply"].record(
                        time.monotonic_ns() - t_ap
                    )
                    g.lat = lat = None
                    self._lat_gids.discard(g.gid)
                    continue
                if pending:
                    fut = pending.pop(entry.index, None)
                    if fut is not None and is_leader:
                        self._reply(fut, ("ok", res[1], me))
                continue
            if kind == NOOP:
                if cmd.machine_version > g.effective_machine_version:
                    # machine-version bump rides the term noop
                    # (reference: src/ra_server.erl:3357-3417)
                    old_v = g.effective_machine_version
                    g.effective_machine_version = mver = cmd.machine_version
                    mac = machine.which_module(mver)
                    apply_fn = mac.apply
                    res = apply_fn(
                        {"index": entry.index, "term": entry.term,
                         "machine_version": mver},
                        ("machine_version", old_v, mver), state,
                    )
                    state = res[0]
                if is_leader and entry.index >= g.noop_index:
                    # the new leader's own entry committed: unlock
                    # membership changes and linearizable reads
                    g.noop_committed = True
                    if entry.index >= g.cluster_index:
                        g.cluster_change_permitted = True
            elif kind in (RA_JOIN, RA_LEAVE, RA_CLUSTER_CHANGE):
                if entry.index >= g.cluster_index:
                    # change committed: the next one may proceed
                    g.cluster_change_permitted = is_leader and g.noop_committed
            if pending and is_leader:
                fut = pending.pop(entry.index, None)
                if fut is not None:
                    self._reply(fut, ("ok", None, me))
        g.machine_state = state
        g.last_applied = hi
        self._applied_np[g.gid] = hi
        if self.lease_cfg.enabled:
            self._lease_applied(g, hi)
        if lat is not None:
            # tracked entry was non-USR (rare): close the sample here
            now2 = time.monotonic_ns()
            self._commit_h["commit_apply"].record(now2 - lat[4])
            self._commit_h["apply_reply"].record(time.monotonic_ns() - now2)
            g.lat = None
            self._lat_gids.discard(g.gid)

    def _commit_gates(self, g: GroupHost, hi: int, is_leader: bool) -> None:
        """Noop-commit gate for apply paths that skip the per-entry loop
        (cluster entries always force the per-entry path, so reaching
        ``hi >= noop_index`` here means the noop itself committed)."""
        if is_leader and not g.noop_committed and hi >= g.noop_index:
            g.noop_committed = True
            if g.cluster_index <= hi:
                g.cluster_change_permitted = True

    # -- machine effects (batch-backend executor; reference vocabulary:
    # src/ra_machine.erl:131-159, realised per src/ra_server_proc.erl
    # handle_effects) -----------------------------------------------------

    def _state_enter(self, g: GroupHost) -> None:
        """The group's role changed to leader or away from it (caller
        holds the state lock, ``g.role`` is the new role): a replica that
        left leadership forgets its watches (monitors are leader-local
        runtime state), and the machine's ``state_enter`` says what the
        new role arms (reference: ra_machine state_enter effects; a
        quorum-queue machine re-issues a monitor per consumer)."""
        is_leader = g.role == C.R_LEADER
        if not is_leader:
            self.monitors.forget((g.name, self.name))
        mac = g.machine.which_module(g.effective_machine_version)
        effs = mac.state_enter(self._ROLE_NAMES.get(g.role, g.role),
                               g.machine_state)
        if effs:
            self._realise_effects(g, effs, is_leader)

    def _realise_effects(self, g: GroupHost, effs, is_leader: bool = True) -> None:
        """Machine effects, in apply order, on the thread that applied
        (the step thread's realisation; ticks and aux too), under
        the state lock. Log effects (release_cursor / checkpoint) are
        realised on EVERY replica (followers must truncate too); the
        rest (send_msg, monitor, demonitor, mod_call, timer, log read,
        reply, aux) on the leader only. One clock pair a call, none per
        effect; the accounts are booked once a step (_finish_ticket)."""
        if self._fx_tr and self._fx_span is None:
            self._fx_span = _obs.begin("ra/egress/effects", node=self.name)
        sent = other = cursors = snaps = armed = 0
        t0 = time.perf_counter_ns()
        for eff in effs:
            if not is_leader and not isinstance(
                eff, (fx.ReleaseCursor, fx.Checkpoint, fx.TryAppend)
            ):
                continue
            if isinstance(eff, fx.ReleaseCursor):
                cursors += 1
                mac = g.machine.which_module(g.effective_machine_version)
                g.log.update_release_cursor(
                    eff.index,
                    tuple(m for m in g.members if m is not None),
                    g.effective_machine_version,
                    eff.machine_state,
                    live_indexes=tuple(mac.live_indexes(eff.machine_state)),
                )
                if self._sync_snapshot_floor(g):
                    snaps += 1
            elif isinstance(eff, fx.Checkpoint):
                mac = g.machine.which_module(g.effective_machine_version)
                g.log.checkpoint(
                    eff.index,
                    tuple(m for m in g.members if m is not None),
                    g.effective_machine_version,
                    eff.machine_state,
                    live_indexes=tuple(mac.live_indexes(eff.machine_state)),
                )
            elif isinstance(eff, fx.SendMsg):
                sent += 1
                cb = self.send_msg_cb
                if cb is not None:
                    try:
                        cb(eff.to, eff.msg, eff.options)
                    except Exception:  # noqa: BLE001
                        pass
                elif callable(getattr(eff.to, "set_result", None)) or callable(eff.to):
                    self._reply(eff.to, eff.msg)
                elif isinstance(eff.to, tuple) and len(eff.to) == 2:
                    self.transport.send(eff.to, eff.msg, from_sid=(g.name, self.name))
            elif isinstance(eff, fx.Monitor):
                armed += 1
                self.monitors.add((g.name, self.name), eff.kind, eff.target,
                                  eff.component)
            elif isinstance(eff, fx.Demonitor):
                other += 1
                self.monitors.remove((g.name, self.name), eff.kind, eff.target)
            elif isinstance(eff, fx.ModCall):
                other += 1
                try:
                    eff.fn(*eff.args)
                except Exception:  # noqa: BLE001
                    pass
            elif isinstance(eff, fx.Timer):
                other += 1
                self._machine_timer(g, eff)
            elif isinstance(eff, fx.LogRead):
                other += 1
                entries = g.log.sparse_read(list(eff.indexes))
                out = eff.fn(entries)
                if out is not None:
                    # apply runs on a drainer thread under the state
                    # lock: self-deliveries ride the internal queue
                    # straight into the next drain (never the rings —
                    # a full lane must not block the drainer on itself)
                    self._deliver_internal(g.name, out)
            elif isinstance(eff, fx.Reply):
                other += 1
                self._reply(eff.from_ref, eff.reply)
            elif isinstance(eff, fx.Aux):
                other += 1
                self._deliver_internal(g.name, ("aux", "cast", eff.cmd, None))
            elif isinstance(eff, (fx.Append, fx.TryAppend)):
                other += 1
                # machine-originated command re-enters via the command
                # queue: the next step's drain appends it on the leader;
                # a TryAppend on a non-leader redirects per command
                # routing (reference: src/ra_server_proc.erl:1604-1615).
                # Only the leader's copy carries the reply ref — every
                # replica realises a TryAppend, and a follower's
                # redirect must not race the leader's ok on one future
                self._deliver_internal(
                    g.name,
                    Command(kind=USR, data=eff.cmd,
                            reply_mode=eff.reply_mode,
                            from_ref=eff.from_ref if is_leader else None,
                            internal=True),
                )
        acc = self._fx_acc
        # (never 0: a step that realised effects books them)
        acc[0] += (time.perf_counter_ns() - t0) or 1
        acc[1] += sent
        acc[2] += other + armed
        acc[3] += cursors
        acc[4] += snaps
        acc[5] += armed
        acc[6] += len(effs)

    def _end_effects_span(self) -> None:
        """Close the step's ``ra/egress/effects`` span (first effect ->
        here) inside the span that holds it, with the effects so far."""
        sp = self._fx_span
        if sp is not None:
            self._fx_span = None
            sp.set_metadata(effects=self._fx_acc[6])
            _obs.end(sp)

    def _book_effects(self) -> None:
        """The step's effect accounts, once a step that realised any:
        the sub-phase ``effects_realise`` and the effect counters."""
        acc = self._fx_acc
        ns, sent, other, cursors, snaps, armed, _n = acc
        acc[:] = (0, 0, 0, 0, 0, 0, 0)
        self._wave_h["effects_realise"].record(ns)
        cnt = self.counters
        if sent:
            cnt.incr("effects_send_msg", sent)
        if other:
            cnt.incr("effects_other", other)
        if cursors:
            cnt.incr("release_cursors", cursors)
        if snaps:
            cnt.incr("release_cursor_snapshots", snaps)
        if armed:
            cnt.incr("monitors_armed", armed)

    def _sync_snapshot_floor(self, g: GroupHost) -> bool:
        """Tell the device a snapshot the log took; whether it took one."""
        snap = g.log.snapshot_index_term()
        if snap is not None and snap[0] > g.snap_floor:
            g.snap_floor = snap[0]
            gid = jnp.asarray([g.gid], jnp.int32)
            self.state = C.record_snapshot(
                self.state, gid,
                jnp.asarray([snap[0]], jnp.int32),
                jnp.asarray([snap[1]], jnp.int32),
            )
            return True
        return False

    def _machine_timer(self, g: GroupHost, eff: fx.Timer) -> None:
        old = g.machine_timers.pop(eff.name, None)
        if old is not None:
            old.cancel()
        if eff.ms is None:
            return

        def fire():
            g.machine_timers.pop(eff.name, None)
            if self.running and g.role == C.R_LEADER:
                self.deliver(
                    (g.name, self.name),
                    Command(kind=USR, data=("timeout", eff.name),
                            internal=True),
                    None,
                )

        t = threading.Timer(eff.ms / 1000.0, fire)
        t.daemon = True
        t.start()
        g.machine_timers[eff.name] = t

    def _fail_pending(self, g: GroupHost, counter: str = "pending_redirected",
                      from_idx: int = 0, verdict: str = "maybe") -> None:
        """Answer pending await_consensus futures instead of silently
        dropping them (root cause of the round-5 command wedge: a leader
        deposed between append and commit popped its pending futures on
        apply without replying, hanging every waiting client for its
        full timeout).

        The verdict matters for exactly-once semantics:
        - ``"redirect"`` — the entry is provably DEAD (truncated away):
          clients may retry with no duplicate risk;
        - ``"maybe"`` (default) — deposed with the entry still in the
          log: it MAY commit under the new leader. process_command
          surfaces this as an immediate error unless the caller opted
          into at-least-once retries — a transparent retry here is how
          the overload harness caught a double-applied incr.

        ``from_idx`` limits the sweep to truncated indexes; 0 fails
        all."""
        pending = g.pending_replies
        if not pending:
            return
        leader = g.sid_of(g.leader_slot)
        if leader == (g.name, self.name):
            leader = None  # never redirect a caller back to ourselves
        doomed = (
            list(pending) if from_idx <= 0
            else [i for i in pending if i >= from_idx]
        )
        for i in doomed:
            self._reply(pending.pop(i), (verdict, leader))
        if doomed:
            self.counters.incr(counter, len(doomed))
            self._obs_rec.record(
                "deposition", node=self.name, group=g.name, term=g.term,
                detail=f"{len(doomed)} pending futures answered "
                       f"{verdict!r} ({counter})",
            )

    # -- outbound ----------------------------------------------------------

    def _reply(self, fut, value) -> None:
        if type(fut) is RemoteRef and \
                threading.get_ident() in self._drainer_idents:
            # a caller on another node: the turn's replies to its node
            # leave together when the turn ends (_flush_client_replies)
            out = self._client_out.get(fut.node)
            if out is None:
                self._client_out[fut.node] = out = []
            out.append((fut.corr, value))
            return
        if self._holding:
            self._held.append((fut, value))
            return
        setter = getattr(fut, "set_result", None)
        if setter is not None:
            setter(value)
        elif callable(fut):
            fut(value)

    def _turn_replies(self) -> None:
        """Where a turn's replies to its callers leave, once its step is
        realised (drainer thread only): the answers its ingest gave to
        callers in this process, held until here so that a caller's next
        operation meets the next turn's ingest, as it does after a
        commit's reply, and not the middle of this turn's pack; then
        the replies to callers on other nodes, a batch a node."""
        if self._held:
            held, self._held = self._held, []
            for fut, value in held:
                self._reply(fut, value)
        if self._client_out:
            self._flush_client_replies()

    def _flush_client_replies(self) -> None:
        """The turn's replies to callers on other nodes, one batch a
        caller node, through ``_send_batch`` (drainer thread only)."""
        out, self._client_out = self._client_out, {}
        reply_batch = self.transport.reply_batch
        for node_name, replies in out.items():
            self._send_batch(node_name, reply_batch(node_name, replies))

    def _send_batch(self, node_name: str, msgs) -> None:
        """Per-destination batch send. With the started loop,
        the fan-out hands off to the dedicated sender thread through a
        bounded ring — the step/egress/WAL threads never pay transport
        cost; a full handoff ring falls back to an inline send (bounded
        handoff never drops)."""
        if self._egress_on:
            # (the stamp rides the item: sub-phase send_queue, which the
            # sender thread records when it drains the batch)
            if self._egress_rings.publish(
                    (node_name, msgs, time.perf_counter_ns())):
                return
            self.counters.incr("egress_thread_ring_full")
        self._send_batch_inline(node_name, msgs)

    def _send_batch_inline(self, node_name: str, msgs) -> None:
        node = self.registry.get(node_name)
        if isinstance(node, BatchCoordinator) and node is not self:
            # one hop for the whole batch; honor the same fault-injection
            # and liveness rules as InProcTransport.send
            if not node.running or (self.name, node_name) in self.transport.blocked:
                self.transport.dropped += len(msgs)
                return
            drop = self.transport.drop_fn
            if drop is None:
                triples = [(to[0], frm, msg) for to, msg, frm in msgs]
            else:
                triples = []
                for to, msg, frm in msgs:
                    if drop(to, msg):
                        self.transport.dropped += 1
                    else:
                        triples.append((to[0], frm, msg))
            if triples:
                # peer's ingress lane full: the peer sheds only the
                # lossy subset (counted here) and overflow-queues the
                # must-deliver remainder — never a batch-level drop
                self.transport.dropped += node.ingest_batch(triples)
            return
        if node is None:
            if not self._wired:
                return
            # a node across the wire: the batch as ONE frame. -1 = a tcp
            # failpoint is armed: fall through to per-message send so
            # fire/mangle semantics apply frame by frame.
            if self.transport.send_batch(node_name, msgs) >= 0:
                return
        for to, msg, frm in msgs:
            self.transport.send(to, msg, from_sid=frm)

    # -- leases (docs/INTERNALS.md §20) ------------------------------------

    def _lease_sync(self, g: GroupHost) -> None:
        """Mirror the group's voter set into the lease arrays. Runs at
        registration and on every membership scatter; a membership
        change while leading revokes (the old lease quorum may not
        intersect the new vote quorum)."""
        voting = np.zeros(self.P, dtype=bool)
        for i, m in enumerate(g.members):
            if m is not None and g.voter_status.get(i) == "voter":
                voting[i] = True
        self._lease_voters[g.gid] = voting
        self._lease_quorum[g.gid] = int(voting.sum()) // 2 + 1
        self._lease_self[g.gid] = g.self_slot

    def _lease_stamp_send(self, gid: int, slot: int, now: float) -> None:
        """Oldest-outstanding-send stamp for one peer slot (later sends
        before an ack keep the older, more conservative stamp)."""
        if self._lease_sent[gid, slot] == 0.0:
            self._lease_sent[gid, slot] = now

    def _lease_credit(self, g: GroupHost, slot: int) -> None:
        """Fold a same-term response from ``slot`` into its ack basis
        (send-basis rule — never the receive time)."""
        gid = g.gid
        t0 = self._lease_sent[gid, slot]
        if t0 == 0.0:
            return
        self._lease_sent[gid, slot] = 0.0
        if t0 > self._lease_basis[gid, slot]:
            self._lease_basis[gid, slot] = t0
            self._lease_dirty.add(gid)

    def _lease_refresh(self) -> None:
        """Recompute expiries for groups with newly credited bases: one
        vectorized k-th-largest pass over the dirty set (the (G,)-array
        analog of LeaseTracker.refresh). Expiry only ever advances."""
        d = self._lease_dirty
        if not d:
            return
        from ra_tpu.lease import lease_expiry, quorum_bases

        gids = np.fromiter(d, np.int64, len(d))
        d.clear()
        now = self.clock.monotonic()
        bases = self._lease_basis[gids].copy()
        # the leader's own slot always counts as an ack at ``now``
        bases[np.arange(len(gids)), self._lease_self[gids]] = now
        qb = quorum_bases(bases, self._lease_voters[gids],
                          self._lease_quorum[gids])
        cfg = self.lease_cfg
        exp = np.where(qb > 0.0, lease_expiry(
            qb, cfg.election_timeout_s, cfg.safety_factor,
            cfg.drift_epsilon_s), 0.0)
        cur = self._lease_expiry[gids]
        fresh = (exp > now) & (cur <= now) & (exp > cur)
        self._lease_expiry[gids] = np.maximum(cur, exp)
        if fresh.any():
            for gid in gids[fresh].tolist():
                g = self.groups[gid]
                if g is not None:
                    self._obs_rec.record(
                        "lease_acquired", node=self.name, group=g.name,
                        term=g.term,
                        detail=f"expires in "
                               f"{self._lease_expiry[gid] - now:.3f}s",
                    )

    def _lease_revoke(self, g: GroupHost, why: str) -> None:
        """Eager revocation: clears the expiry AND the stamp/basis rows
        so acks already in flight cannot resurrect a lease for a
        leadership this group no longer holds."""
        if not self.lease_cfg.enabled:
            return
        gid = g.gid
        had = self._lease_expiry[gid] > self.clock.monotonic()
        self._lease_expiry[gid] = 0.0
        self._lease_sent[gid, :] = 0.0
        self._lease_basis[gid, :] = 0.0
        self._lease_dirty.discard(gid)
        if had:
            self.counters.incr("read_lease_revocations")
            self._obs_rec.record(
                "lease_lost", node=self.name, group=g.name, term=g.term,
                detail=why,
            )

    def _stickiness_lapsed(self, g: GroupHost, now: float) -> bool:
        """False while this replica's promise to its current leader
        still stands: (pre-)votes for OTHER candidates are disregarded
        for one election timeout after the last leader contact."""
        if g.role == C.R_LEADER:
            return False
        if g.leader_slot < 0:
            return True
        return now - g.lease_contact >= self.election_timeout_s

    def _read_staleness(self, g: GroupHost) -> float:
        """Upper bound on this replica's staleness vs the leader's
        wall clock (inf until a leader stamp has been applied)."""
        if g.fresh_ts <= 0.0:
            return float("inf")
        return max(0.0, self.clock.time() - g.fresh_ts) \
            + self.lease_cfg.drift_epsilon_s

    def _staleness_hist(self):
        if self._stale_h is None:
            self._stale_h = _obs.staleness_hist(self.name)
        return self._stale_h

    def _lease_applied(self, g: GroupHost, hi: int) -> None:
        """Freshness-floor upkeep after apply reached ``hi``: leaders
        stamp their own wall clock once fully caught up; followers
        promote a pending (leader_commit, commit_ts) anchor whose
        commit point is now applied."""
        if g.role == C.R_LEADER:
            # host mirror: applied == committed, so the leader is
            # always fully caught up here
            g.fresh_ts = self.clock.time()
            return
        anchor_idx, anchor_ts = g.fresh_anchor
        if anchor_ts > 0.0 and anchor_idx <= hi:
            if anchor_ts > g.fresh_ts:
                g.fresh_ts = anchor_ts
            g.fresh_anchor = (0, 0.0)

    def _broadcast_vote_req(self, g: GroupHost, queue_send, pre: bool,
                            force: bool = False) -> None:
        li, lt = g.log.last_index_term()
        sid = (g.name, self.name)
        if pre:
            rpc = PreVoteRpc(
                term=g.term, token=g.pre_vote_token, candidate_id=sid, version=1,
                machine_version=g.machine.version(), last_log_index=li,
                last_log_term=lt,
            )
        else:
            rpc = RequestVoteRpc(
                term=g.term, candidate_id=sid, last_log_index=li,
                last_log_term=lt, force=force,
            )
        for s, member in enumerate(g.members):
            if s != g.self_slot and member is not None:
                queue_send(member, rpc, sid)

    _NEEDS_SNAPSHOT = object()  # rpc-cache sentinel

    def _send_aers(self, aer_dirty) -> int:
        """Build and hand off the AppendEntries of the dirty groups this
        node leads, one batch per destination. Reads the logs and the
        host mirrors only (never ``self.state``), so a pass may run it
        before or after its device hand-off. Returns the number of
        groups for which an AER left."""
        outbound: Dict[str, List] = {}
        now = self.clock.monotonic()
        shipped = 0
        for gid in aer_dirty:
            g = self.groups[gid]
            if g is None:
                continue
            ft = g.fresh_tail  # valid for THIS step only, whoever we are
            g.fresh_tail = None
            if g.role != C.R_LEADER:
                continue
            li, _ = g.log.last_index_term()
            commit = g.last_applied  # host mirror of commit (applied == committed here)
            sid = (g.name, self.name)
            # lease (§20): every AER is a quorum-bearing send — stamp
            # the oldest outstanding send per peer, and carry the wall
            # clock the commit point was current at (follower
            # freshness). 0.0 when lease-off: receivers then never
            # advance their freshness floor.
            lease_on = self.lease_cfg.enabled
            cts = self.clock.time() if lease_on else 0.0
            # peers at the same next_index (the steady-state pipeline)
            # share ONE immutable rpc: one entry fetch, one object
            rpc_cache: Dict[int, Any] = {}
            left = False
            for s, member in enumerate(g.members):
                if s == g.self_slot or member is None:
                    continue
                nxt = g.next_index[s]
                if nxt > li and commit <= g.commit_sent[s]:
                    continue  # nothing new to say
                if nxt <= li:
                    # per-peer pipeline window: never run more than
                    # max_pipeline_count entries ahead of the peer's
                    # CONFIRMED match (reference: Next - Match <=
                    # ?MAX_PIPELINE_COUNT, src/ra_server.erl:2308-2329).
                    # An actively-acking peer reopens the window by
                    # itself; a silent one gets an EMPTY probe at the
                    # current next point (the actor backend's
                    # empty-probe shape): its success ack rebuilds
                    # match_hint at the peer's true tail, its reject
                    # hint rewinds next_index — either resynchronizes
                    # without blindly re-sending the whole log (a fresh
                    # leader starts at match_hint 0, so a rewind-to-
                    # match here would re-replicate or snapshot-stream
                    # to every caught-up peer).
                    mh = g.match_hint[s] if s < len(g.match_hint) else 0
                    if nxt - mh > self.max_pipeline_count:
                        la = g.last_ack[s]
                        if la and now - la <= self.tick_interval_s:
                            continue  # window full but acks are flowing
                        g.last_ack[s] = now  # one probe per tick per peer
                        self.counters.incr("stale_peer_resends")
                        prev_idx = nxt - 1
                        prev_term = g.log.fetch_term(prev_idx)
                        snap = g.log.snapshot_index_term()
                        if prev_term is None or (
                            snap is not None and prev_idx < snap[0]
                        ):
                            self._start_snapshot_sender(g, member)
                            continue
                        if lease_on:
                            self._lease_stamp_send(gid, s, now)
                        outbound.setdefault(member[1], []).append((
                            member,
                            AppendEntriesRpc(
                                term=g.term, leader_id=sid,
                                prev_log_index=prev_idx,
                                prev_log_term=prev_term,
                                leader_commit=commit, entries=(),
                                commit_ts=cts,
                            ),
                            sid,
                        ))
                        g.commit_sent[s] = commit
                        left = True
                        continue
                rpc = rpc_cache.get(nxt)
                if rpc is None and ft is not None and nxt >= ft[0]:
                    # steady state: the entries were appended by THIS
                    # step's _handle_commands — ship them straight
                    # through (no log re-read; all plain USR, one term)
                    first_f, prev_f, term_f, ents_f = ft
                    k = nxt - first_f
                    if k < len(ents_f):
                        rpc = AppendEntriesRpc(
                            term=g.term, leader_id=sid, prev_log_index=nxt - 1,
                            prev_log_term=prev_f if k == 0 else term_f,
                            leader_commit=commit,
                            entries=tuple(ents_f[k:k + self.aer_batch_size]),
                            plain_usr=True, commit_ts=cts,
                        )
                        rpc_cache[nxt] = rpc
                if rpc is None:
                    entries: List[Entry] = []
                    if nxt <= li:
                        entries = g.log.fetch_range(
                            nxt, min(li, nxt + self.aer_batch_size - 1)
                        )
                    prev_idx = nxt - 1
                    prev_term = g.log.fetch_term(prev_idx)
                    snap = g.log.snapshot_index_term()
                    if prev_term is None or (
                        snap is not None and prev_idx < snap[0]
                    ):
                        rpc = self._NEEDS_SNAPSHOT
                    else:
                        # stamp plain-USR batches so the receiver skips
                        # its per-entry specials scan. g.specials is
                        # only exhaustive ABOVE last_applied (older
                        # rows are pruned), so lagging-peer backfills
                        # below the applied floor never get the stamp.
                        plain = False
                        if entries and nxt > g.last_applied:
                            sp = g.specials
                            if not sp:
                                plain = True
                            else:
                                i = bisect_left(sp, nxt)
                                plain = (
                                    i >= len(sp)
                                    or sp[i] > entries[-1].index
                                )
                        rpc = AppendEntriesRpc(
                            term=g.term, leader_id=sid, prev_log_index=prev_idx,
                            prev_log_term=prev_term, leader_commit=commit,
                            entries=tuple(entries), plain_usr=plain,
                            commit_ts=cts,
                        )
                    rpc_cache[nxt] = rpc
                if rpc is self._NEEDS_SNAPSHOT:
                    # peer is behind our compacted floor: stream a snapshot
                    self._start_snapshot_sender(g, member)
                    continue
                if lease_on:
                    self._lease_stamp_send(gid, s, now)
                outbound.setdefault(member[1], []).append((member, rpc, sid))
                if rpc.entries:
                    g.next_index[s] = rpc.entries[-1].index + 1
                g.commit_sent[s] = commit
                left = True
            if left:
                shipped += 1
        for node_name, msgs in outbound.items():
            self._send_batch(node_name, msgs)
        return shipped

    # -- rare paths --------------------------------------------------------

    def _handle_rare(self, g: GroupHost, msg, from_sid,
                     rare_out: Optional[Dict[str, List]] = None) -> None:
        """``rare_out``: the realisation pass's shared per-destination
        outbound — fan-outs append into it and the caller ships ONE
        batch per destination after the whole rare loop (a per-group
        send per election would overflow a peer's bounded ingress lane
        under a 10k-group storm). A None caller (direct invocations in
        tests) ships inline."""
        if isinstance(msg, ElectionTimeout):
            if g.role == C.R_LEADER:
                return
            if msg.armed_at and g.last_contact > msg.armed_at:
                # stale detector trigger: the group has seen contact (or
                # restarted its own election window) since the suspicion
                # was confirmed — a trigger delayed behind a stall (jit
                # compile, long egress) must not pile a second election
                # onto a round that is already resolving
                return
            if g.voter_status.get(g.self_slot) != "voter":
                return  # nonvoters never start elections
            if self.lease_cfg.enabled and not self._stickiness_lapsed(
                g, self.clock.monotonic()
            ):
                # standing is stickiness-gated too (§20): a candidate
                # grants its own vote, and could be the one quorum-
                # intersection voter a live leader's lease counts on
                return
            self._obs_rec.record(
                "election", node=self.name, group=g.name, term=g.term,
                detail="pre_vote round started",
            )
            # start pre-vote host-side: queue the role scatter (batched
            # across groups at the next step), broadcast the rpc
            self._pending_roles.append((g.gid, C.R_PRE_VOTE))
            g.role = C.R_PRE_VOTE
            g.pre_vote_token += 1
            g.last_contact = self.clock.monotonic()  # election-retry window restarts
            self._hot.add(g.gid)  # force steps so the election progresses
            if len(g.members) == 1:
                return  # the next device steps self-elect
            outbound: Dict[str, List] = (
                {} if rare_out is None else rare_out
            )

            def queue_send(to, m, frm):
                outbound.setdefault(to[1], []).append((to, m, frm))

            self._broadcast_vote_req(g, queue_send, pre=True)
            if rare_out is None:
                for node_name, msgs in outbound.items():
                    self._send_batch(node_name, msgs)
            return
        if isinstance(msg, tuple) and msg and msg[0] == "local_query":
            # ("local_query", fn, fut) or a 4-tuple carrying the
            # caller's max_staleness_s bound (docs/INTERNALS.md §20):
            # the bounded form only answers when the leader-stamped
            # freshness floor proves local state is recent enough
            fn, fut = msg[1], msg[2]
            if len(msg) > 3 and msg[3] is not None:
                staleness = self._read_staleness(g)
                self._staleness_hist().record_seconds(
                    min(staleness, 3600.0)
                )
                if staleness > msg[3]:
                    self.counters.incr("read_stale_rejected")
                    self._reply(
                        fut, ("stale", staleness, g.sid_of(g.leader_slot))
                    )
                    return
                self.counters.incr("read_local_bounded")
            self._reply(fut, ("ok", fn(g.machine_state), g.sid_of(g.leader_slot)))
            return
        if isinstance(msg, TimeoutNow):
            # leadership-transfer trigger from any backend's leader: a
            # FORCED election, no pre-vote round (Raft §3.10; matches
            # the scalar backend's _call_for_election on TimeoutNow) —
            # one round trip to leadership, and correct independent of
            # any leader-stickiness in the pre-vote grant.
            if g.role == C.R_LEADER or g.voter_status.get(g.self_slot) != "voter":
                return
            g.role = C.R_CANDIDATE
            g.term += 1
            g.leader_slot = -1
            g.last_contact = self.clock.monotonic()
            if self.meta is not None:
                # term AND self-vote must be durable before any vote
                # request leaves this node (restart double-vote safety)
                uid = f"{g.cluster_name}_{g.name}"
                self.meta.store(uid, "current_term", g.term)
                self.meta.store_sync(uid, "voted_for", (g.name, self.name))
            self.state = C.force_elections(
                self.state, jnp.asarray([g.gid], jnp.int32)
            )
            self._hot.add(g.gid)  # keep stepping (single-member self-election)
            outbound2: Dict[str, List] = (
                {} if rare_out is None else rare_out
            )

            def queue_send2(to, m, frm):
                outbound2.setdefault(to[1], []).append((to, m, frm))

            # forced candidacy (§20): the transferring leader revoked
            # its lease before sending TimeoutNow, so voters may skip
            # stickiness for this request
            self._broadcast_vote_req(g, queue_send2, pre=False, force=True)
            if rare_out is None:
                for node_name, msgs in outbound2.items():
                    self._send_batch(node_name, msgs)
            return
        if isinstance(msg, tuple) and msg and msg[0] == "transfer_leadership":
            _, target, fut = msg
            me = (g.name, self.name)
            if g.role != C.R_LEADER:
                self._reply(fut, ("redirect", g.sid_of(g.leader_slot)))
                return
            target = tuple(target)
            if target == me:
                self._reply(fut, ("ok", "already_leader"))
                return
            slot = g.slot_of(target)
            if slot < 0:
                self._reply(fut, ("error", "unknown_member"))
                return
            if g.voter_status.get(slot) != "voter":
                self._reply(fut, ("error", "non_voter"))
                return
            li, _ = g.log.last_index_term()
            # gate on the device's CONFIRMED match for the slot — the
            # host next_index advances optimistically at send time, so
            # a pipelined-to-but-unacked peer must not pass (mirrors
            # the scalar backend's match_index gate). One device read;
            # transfers are rare.
            confirmed = int(np.asarray(self.state.match_index)[g.gid, slot])
            if confirmed != li:
                self._reply(fut, ("error", "not_up_to_date"))
                return
            self._reply(fut, ("ok", None))
            # revoke BEFORE the transfer trigger leaves this node: the
            # target's forced (stickiness-bypassing) election is only
            # safe because no lease-holding leader remains (§20)
            self._lease_revoke(g, "leadership transfer")
            self._send_batch(target[1], [(target, TimeoutNow(), me)])
            return
        if isinstance(msg, tuple) and msg and msg[0] == "lane_recover":
            # watchdog strike 1: force a device re-step (fresh quorum
            # scan over current match/written state) and probe every
            # peer — their acks or reject hints resynchronize
            # replication from the confirmed point
            self.counters.incr("lane_recoveries")
            self._hot.add(g.gid)
            if g.role == C.R_LEADER:
                now = self.clock.monotonic()
                for s, m in enumerate(g.members):
                    if (
                        m is not None and s != g.self_slot
                        and s < len(g.commit_sent)
                    ):
                        g.commit_sent[s] = -1
                        if not g.last_ack[s]:
                            g.last_ack[s] = now
                self._send_aers({g.gid})
            return
        if isinstance(msg, tuple) and msg and msg[0] == "lane_fail":
            # watchdog second strike: recovery did not move the lane —
            # bound the failure so clients retry elsewhere instead of
            # hanging until their timeout
            self._fail_pending(g, counter="lane_redirects")
            return
        if isinstance(msg, tuple) and msg and msg[0] == "resync":
            if g.role == C.R_LEADER:
                now = self.clock.monotonic()
                for s in msg[1]:
                    if s < len(g.commit_sent):
                        # -1 sentinel: the probe must fire even at
                        # commit 0 (a fresh leader's lost noop AER)
                        g.commit_sent[s] = -1
                        if not g.last_ack[s]:
                            g.last_ack[s] = now
                self._send_aers({g.gid})
            return
        if isinstance(msg, tuple) and msg and msg[0] == "machine_tick":
            mac = g.machine.which_module(g.effective_machine_version)
            effs = mac.tick(msg[1], g.machine_state)
            if effs and g.role == C.R_LEADER:
                self._realise_effects(g, effs)
            return
        if isinstance(msg, tuple) and msg and msg[0] == "consistent_query":
            self._handle_consistent_query(g, msg[1], msg[2], rare_out)
            return
        if isinstance(msg, HeartbeatRpc):
            # follower side of the query-index leadership confirmation.
            # A higher term is adopted before acking (the scalar backend
            # goes through _update_term, server.py; an ack from a member
            # that never acknowledged the term would be meaningless).
            if from_sid is not None:
                if msg.term >= g.term:
                    g.last_contact = self.clock.monotonic()
                    if self.lease_cfg.enabled:
                        g.lease_contact = g.last_contact
                    if msg.term > g.term or g.role != C.R_FOLLOWER:
                        self._adopt_term(g, msg.term, leader_sid=from_sid)
                    elif g.leader_slot < 0:
                        g.leader_slot = g.slot_of(from_sid)
                    reply = HeartbeatReply(term=msg.term, query_index=msg.query_index)
                else:
                    reply = HeartbeatReply(term=g.term, query_index=-1)
                item = (from_sid, reply, (g.name, self.name))
                if rare_out is None:
                    self._send_batch(from_sid[1], [item])
                else:
                    rare_out.setdefault(from_sid[1], []).append(item)
            return
        if isinstance(msg, HeartbeatReply):
            self._handle_heartbeat_reply(g, msg, from_sid)
            return
        if isinstance(msg, tuple) and msg and msg[0] == "aux":
            self._handle_aux(g, msg[1], msg[2], msg[3])
            return
        if isinstance(msg, tuple) and msg and msg[0] == "state_query":
            _, fn, fut = msg
            self._reply(fut, ("ok", fn(g), g.sid_of(g.leader_slot)))
            born = getattr(fut, "t_born", None)
            if born is not None:
                self.counters.incr("state_queries")
                self.counters.incr(
                    "state_query_ns", time.monotonic_ns() - born)
            return
        if isinstance(msg, tuple) and msg and msg[0] == "force_shrink":
            # disaster recovery: restrict the cluster to this member and
            # elect. Mirrors the Server path: membership shrinks, a
            # durable 'replace' marker is appended (meaningful when the
            # group's log is persistent), and an election follows.
            me = (g.name, self.name)
            self._lease_revoke(g, "force_shrink")
            idx = g.log.next_index()
            g.log.append(Entry(index=idx, term=g.term, cmd=Command(
                kind="ra_cluster_change", data=("replace", ((me, "voter"),)))))
            g.specials.append(idx)
            self._stage_app(g.gid, idx, idx, g.term)
            g.members = [me]
            g.self_slot = 0
            g.next_index = [idx + 1]
            g.commit_sent = [0]
            g.match_hint = [0]
            g.voter_status = {0: "voter"}
            g.last_ack[:] = 0.0
            self._sync_peer_row(g)
            g.cluster_change_permitted = True
            onehot = np.zeros(self.P, dtype=bool)
            onehot[0] = True
            self.state = C.set_groups(
                self.state, np.array([g.gid], np.int32), {
                    "voting": onehot[None], "active": onehot[None],
                    "self_slot": np.zeros(1, np.int32),
                })
            self.state = C.set_roles(
                self.state,
                jnp.asarray([g.gid], jnp.int32),
                jnp.asarray([C.R_PRE_VOTE], jnp.int32),
            )
            g.role = C.R_PRE_VOTE
            g.pre_vote_token += 1
            self._hot.add(g.gid)
            if len(msg) > 1 and msg[1] is not None:
                self._reply(msg[1], ("ok", None))
            return
        if isinstance(msg, InstallSnapshotRpc):
            self._receive_snapshot_chunk(g, msg, from_sid)
            return
        if isinstance(msg, (InstallSnapshotAck, InstallSnapshotResult)):
            sender = g.snap_senders.get(from_sid)
            if sender is not None:
                if isinstance(msg, InstallSnapshotAck):
                    sender.on_ack(msg)
                else:
                    sender.on_result(msg)
            return
        if isinstance(msg, tuple) and msg and msg[0] == "snap_send_done":
            _, to, result = msg
            g.snap_senders.pop(to, None)
            if result is not None and g.role == C.R_LEADER:
                slot = g.slot_of(to)
                if slot >= 0:
                    g.next_index[slot] = max(g.next_index[slot], result.last_index + 1)
                    if slot < len(g.match_hint):
                        g.match_hint[slot] = max(
                            g.match_hint[slot], result.last_index
                        )
                    # feed the result through the device path for match
                    g.inbox.append((to, AppendEntriesReply(
                        result.term, True, result.last_index + 1,
                        result.last_index, result.last_term)))
                    self._hot.add(g.gid)
                    # resume pipelining the post-snapshot tail right away
                    self._send_aers({g.gid})
            return

    _ROLE_NAMES = {0: "follower", 1: "pre_vote", 2: "candidate", 3: "leader"}

    class _AuxServerShim:
        """Duck-types the Server surface AuxContext reads, over a
        GroupHost (machine state, membership, indexes, log)."""

        def __init__(self, coord: "BatchCoordinator", g: GroupHost):
            self.machine_state = g.machine_state
            self.leader_id = g.sid_of(g.leader_slot)
            self.current_term = g.term
            self.commit_index = g.last_applied
            self.last_applied = g.last_applied
            self.log = g.log
            self._g = g
            self._coord = coord

        def members(self):
            return [m for m in self._g.members if m is not None]

        def overview(self):
            g = self._g
            return {
                "id": (g.name, self._coord.name),
                "backend": "tpu_batch",
                "role": BatchCoordinator._ROLE_NAMES.get(g.role, g.role),
                "term": g.term,
                "last_applied": g.last_applied,
                "machine": g.machine.overview(g.machine_state),
            }

    def _handle_aux(self, g: GroupHost, kind: str, cmd, from_ref) -> None:
        """Aux machine plumbing for batch-backed groups (reference:
        ra_aux surface, src/ra_aux.erl:8-23)."""
        from ra_tpu.aux import AuxContext

        if not g.aux_inited:
            g.aux_state = g.machine.init_aux(g.cluster_name)
            g.aux_inited = True
        from ra_tpu.machine import normalize_aux_result

        res = g.machine.handle_aux(
            self._ROLE_NAMES.get(g.role, "follower"), kind, cmd, g.aux_state,
            AuxContext(self._AuxServerShim(self, g)),
        )
        reply, g.aux_state, effs = normalize_aux_result(res, g.aux_state)
        if effs:
            # aux effects are realized regardless of role (matching the
            # proc backend, which executes them ungated)
            self._realise_effects(g, effs, True)
        if kind == "call" and from_ref is not None:
            self._reply(from_ref, ("ok", reply, (g.name, self.name)))

    def _voter_count(self, g: GroupHost) -> int:
        return sum(
            1 for i, m in enumerate(g.members)
            if m is not None and g.voter_status.get(i) == "voter"
        )

    def _answer_query(self, g: GroupHost, fn):
        """A consistent query's answer, at every site that issues one
        (single voter, lease, quorum round; call sites hold the state
        lock): ``fn`` of the applied state and, where that names a log
        entry (``LogRead``), the entry read from this replica's log
        there and then, so a ``kv_get`` is one request and one reply
        (docs/INTERNALS.md §13). The log read is booked where the
        second message's turn used to be."""
        res = fn(g.machine_state)
        if type(res) is LogRead:
            t0 = time.monotonic_ns()
            res = res.read_from(g.log)
            cnt = self.counters
            cnt.incr("state_queries")
            cnt.incr("state_query_ns", time.monotonic_ns() - t0)
            if res.entry is None:
                cnt.incr("read_log_misses")
        return res

    def _handle_consistent_query(self, g: GroupHost, fn, fut,
                                 out: Optional[Dict[str, List]] = None
                                 ) -> None:
        """Linearizable read: confirm leadership with a voter heartbeat
        quorum round before answering, gated on the leader's own noop
        having committed (Raft read-index; reference: query_index
        heartbeat protocol, src/ra_server.erl consistent queries).
        ``out``: the caller's per-destination outbound, which it ships;
        None sends the heartbeats here."""
        if g.role != C.R_LEADER:
            self._reply(fut, ("redirect", g.sid_of(g.leader_slot)))
            return
        if not g.noop_committed:
            # a fresh leader may hold committed-but-unapplied entries
            # from the previous term; ask the caller to retry
            self._reply(fut, ("redirect", None))
            return
        me = (g.name, self.name)
        # read accounts (docs/INTERNALS.md §13): every read, from the
        # caller's future's birth; stamps only, they touch no decision
        born = getattr(fut, "t_born", None)
        cnt = self.counters
        if self._voter_count(g) <= 1:
            self._reply(fut, ("ok", self._answer_query(g, fn), me))
            if born is not None:
                cnt.incr("read_registers")
                cnt.incr("read_register_ns", time.monotonic_ns() - born)
            return
        now = self.clock.monotonic()
        if self.lease_cfg.enabled:
            # lease fast path (§20): within a quorum-earned lease the
            # read is served locally at read_index = commit (== applied
            # on this backend) with zero quorum traffic. Demand-driven
            # renewal: reads in the back half of the window trigger a
            # stamped heartbeat round (throttled to one per quarter-
            # window) so a read-only workload renews at an amortized
            # one round per window instead of one per read.
            self._lease_refresh()
            gid = g.gid
            exp = self._lease_expiry[gid]
            if exp > now:
                self.counters.incr("read_lease_served")
                self._reply(fut, ("ok", self._answer_query(g, fn), me))
                if born is not None:
                    cnt.incr("read_registers")
                    cnt.incr("read_register_ns", time.monotonic_ns() - born)
                if (
                    exp - now < self.lease_cfg.window_s / 2.0
                    and now - self._lease_renew_t[gid]
                    >= self.lease_cfg.window_s / 4.0
                ):
                    self._lease_renew_t[gid] = now
                    hb0 = HeartbeatRpc(
                        term=g.term, leader_id=me,
                        query_index=g.query_seq,
                    )
                    ob0: Dict[str, List] = {} if out is None else out
                    for s0, m0 in enumerate(g.members):
                        if (
                            m0 is None or s0 == g.self_slot
                            or g.voter_status.get(s0) != "voter"
                        ):
                            continue
                        self._lease_stamp_send(gid, s0, now)
                        ob0.setdefault(m0[1], []).append((m0, hb0, me))
                    if out is None:
                        for nn0, msgs0 in ob0.items():
                            self._send_batch(nn0, msgs0)
                return
            if exp > 0.0:
                # held a lease, lapsed: count the expiry once. Bases
                # stay — they are still honest promises and the
                # fallback round's acks re-earn the lease.
                self.counters.incr("read_lease_expirations")
                self._obs_rec.record(
                    "lease_lost", node=self.name, group=g.name,
                    term=g.term, detail="expired",
                )
                self._lease_expiry[gid] = 0.0
            self.counters.incr("read_quorum_fallback")
        fresh = []
        for q in g.pending_queries:
            if now - q["t"] < 10.0:
                fresh.append(q)
            else:
                # quorum never arrived (lost heartbeat, shrunk voter
                # set): tell the caller to retry instead of hanging
                self._reply(q["fut"], ("redirect", None))
        g.pending_queries = fresh
        g.query_seq += 1
        qid = g.query_seq
        query = {"qi": g.last_applied, "qid": qid, "fn": fn, "fut": fut,
                 "acks": set(), "t": now}
        g.pending_queries.append(query)
        hb = HeartbeatRpc(term=g.term, leader_id=me, query_index=qid)
        outbound: Dict[str, List] = {} if out is None else out
        for s, member in enumerate(g.members):
            if (
                member is None
                or s == g.self_slot
                or g.voter_status.get(s) != "voter"
            ):
                continue  # only voter acks may confirm leadership
            if self.lease_cfg.enabled:
                # the fallback round's acks re-earn the lease
                self._lease_stamp_send(g.gid, s, now)
            outbound.setdefault(member[1], []).append((member, hb, me))
        if out is None:
            for node_name, msgs in outbound.items():
                self._send_batch(node_name, msgs)
        if born is not None:
            query["t_reg"] = t_reg = time.monotonic_ns()
            cnt.incr("read_registers")
            cnt.incr("read_register_ns", t_reg - born)

    def _adopt_term(self, g: GroupHost, term: int, leader_sid=None) -> None:
        """Adopt a higher term seen outside the device mailbox (call
        sites hold the state lock): revert to follower on host AND
        device, persist the term, drop in-flight linearizable reads."""
        if g.role == C.R_LEADER:
            self._lease_revoke(g, "deposed by higher term")
            for q in g.pending_queries:
                self._reply(q["fut"], ("redirect", None))
            g.pending_queries = []
        bumped = term > g.term
        g.term = max(g.term, term)
        was_leader = g.role == C.R_LEADER
        g.role = C.R_FOLLOWER
        g.last_contact = self.clock.monotonic()
        g.leader_slot = g.slot_of(leader_sid) if leader_sid is not None else -1
        if was_leader:
            # deposed outside the device mailbox: same redirect contract
            # as the egress role-transition path
            self._fail_pending(g)
            self._state_enter(g)
        if bumped and self.meta is not None:
            # entering a new term clears the durable vote (the device
            # mailbox path resets voted_for on term bumps identically)
            uid = f"{g.cluster_name}_{g.name}"
            self.meta.store(uid, "current_term", g.term)
            self.meta.store_sync(uid, "voted_for", None)
        self._follow_on_device(g, term, clear_vote=bumped)

    def _handle_heartbeat_reply(self, g: GroupHost, msg: HeartbeatReply, from_sid) -> None:
        if msg.term > g.term:
            # a deposed leader must step down now, not wait for AER
            # traffic while its pending queries ride the redirect timeout
            self._adopt_term(g, msg.term)
            return
        if g.role != C.R_LEADER or from_sid is None or msg.term != g.term:
            return
        slot = g.slot_of(from_sid)
        if slot < 0 or g.voter_status.get(slot) != "voter":
            return
        if self.lease_cfg.enabled:
            self._lease_credit(g, slot)
        quorum = self._voter_count(g) // 2 + 1
        me = (g.name, self.name)
        done = []
        for q in g.pending_queries:
            if msg.query_index >= q["qid"]:
                q["acks"].add(from_sid)
                if len(q["acks"]) + 1 >= quorum and g.last_applied >= q["qi"]:
                    self._reply(
                        q["fut"], ("ok", self._answer_query(g, q["fn"]), me))
                    done.append(q)
                    t_reg = q.get("t_reg")
                    if t_reg is not None:
                        self.counters.incr("read_quorum_rounds")
                        self.counters.incr(
                            "read_quorum_ns", time.monotonic_ns() - t_reg)
        for q in done:
            g.pending_queries.remove(q)

    # -- snapshot transfer (batch-backed groups) ---------------------------

    def _snap_ack(self, g: GroupHost, chunk_no: int) -> InstallSnapshotAck:
        """Chunk ack with receiver-paced credits (docs/INTERNALS.md
        §21): 0 while this node is storage-blocked, so the sender parks
        instead of streaming chunks at a disk that cannot spool them."""
        window = max(1, self.snapshot_credit_window)
        credits = self.pressure.snapshot_credits(window)
        if credits:
            self.counters.incr("snapshot_credits_granted", credits)
        else:
            self.counters.incr("snapshot_credit_waits")
        self.counters.put("snapshot_credit_window", credits)
        return InstallSnapshotAck(g.term, chunk_no, credits)

    def _receive_snapshot_chunk(self, g: GroupHost, msg: InstallSnapshotRpc, from_sid):
        """Host-side 4-phase chunked install; the device learns the new
        floor via a record_snapshot scatter on completion."""
        me = (g.name, self.name)

        def send_one(m):
            self._send_batch(from_sid[1], [(from_sid, m, me)])

        if msg.term < g.term:
            li, lt = g.log.last_index_term()
            send_one(InstallSnapshotResult(g.term, li, lt))
            return
        g.last_contact = self.clock.monotonic()
        if self.lease_cfg.enabled:
            g.lease_contact = g.last_contact
        if msg.chunk_phase == CHUNK_INIT:
            # INIT always starts a fresh accumulator — a retried transfer
            # at the same index must not append onto stale chunks. Chunk
            # bodies spool straight to disk when the group's log store
            # supports it ("accept" is None on memory logs: RAM fallback)
            old = g.snap_accept
            if old is not None:
                oa = old.get("accept")
                if oa is not None and not oa.done:
                    oa.abort()
            g.snap_accept = {
                "meta": msg.meta, "chunks": [], "next": 1,
                "accept": g.log.begin_accept_snapshot(msg.meta),
            }
            send_one(self._snap_ack(g, msg.chunk_no))
            return
        acc = g.snap_accept
        if acc is None or acc["meta"].index != msg.meta.index:
            return  # no transfer in progress for this snapshot: ignore
        if msg.chunk_phase == CHUNK_PRE:
            acc["next"] = max(acc["next"], msg.chunk_no + 1)
            for e in msg.data:
                if g.log.fetch_term(e.index) is None:
                    g.log.write_sparse(e)
            send_one(self._snap_ack(g, msg.chunk_no))
            return
        if msg.chunk_no < acc["next"]:
            send_one(self._snap_ack(g, msg.chunk_no))
            return
        if msg.chunk_no > acc["next"]:
            return
        a = acc.get("accept")
        if a is not None and isinstance(msg.data, (bytes, bytearray)):
            a.accept_chunk(msg.data)  # straight to the disk spool
        else:
            if a is not None:
                # non-byte chunk (in-proc direct-object transfer): falls
                # back to RAM — always the first chunk, nothing is lost
                a.abort()
                acc["accept"] = a = None
            acc["chunks"].append(msg.data)
        acc["next"] += 1
        if msg.chunk_phase != CHUNK_LAST:
            send_one(self._snap_ack(g, msg.chunk_no))
            return
        # complete: install host-side, then scatter the floor to device
        from ra_tpu.log.snapshot import decode_snapshot_chunks

        meta = acc["meta"]
        try:
            if a is not None:
                # seal + streaming-decode + promote: the spool dir IS
                # the new snapshot; no second serialization
                state_obj = g.log.complete_accept_snapshot(a)
            else:
                state_obj = decode_snapshot_chunks(acc["chunks"])
                g.log.install_snapshot(meta, state_obj)
        except Exception:
            # undecodable body (e.g. a machine-state type the wire
            # allowlist does not know here): abort THIS transfer so a
            # retry restarts from INIT; never poison the step thread
            g.snap_accept = None
            logger.exception(
                "coordinator %s: snapshot body for group %s failed wire "
                "decode; transfer aborted (register_wire_type missing?)",
                self.name, g.name,
            )
            return
        g.machine_state = state_obj
        g.effective_machine_version = meta.machine_version
        g.last_applied = max(g.last_applied, meta.index)
        g.snap_floor = max(g.snap_floor, meta.index)
        g.last_ok_sent = None  # log identity changed under the ack key
        # installing a snapshot forces follower: any leftover pending
        # command futures must redirect, not hang
        self._fail_pending(g)
        if g.specials:
            g.specials = [s for s in g.specials if s > meta.index]
        # adopt the snapshot's member set (node-local slot coordinates)
        if meta.cluster:
            new = [tuple(m) for m in meta.cluster]
            me = (g.name, self.name)
            if me in new and set(new) != {m for m in g.members if m is not None}:
                g.members = list(new)
                g.self_slot = new.index(me)
                g.voter_status = {i: "voter" for i in range(len(new))}
                g.next_index = [meta.index + 1] * len(new)
                g.commit_sent = [0] * len(new)
                g.match_hint = [0] * len(new)
                g.last_ack[:] = 0.0
                self._set_self_slot(g)
                self._sync_member_rows(g)
        self._applied_np[g.gid] = g.last_applied
        g.term = max(g.term, msg.term)
        g.leader_slot = g.slot_of(msg.leader_id)
        g.snap_accept = None
        self._obs_rec.record(
            "snapshot_install", node=self.name, group=g.name, term=g.term,
            detail=f"installed at index {meta.index} (term {meta.term})",
        )
        gid = jnp.asarray([g.gid], jnp.int32)
        self.state = C.record_snapshot(
            self.state, gid, jnp.asarray([meta.index], jnp.int32),
            jnp.asarray([meta.term], jnp.int32),
        )
        self._follow_on_device(g, msg.term, clear_vote=False)
        send_one(InstallSnapshotResult(g.term, meta.index, meta.term))

    class _SenderShim:
        """Adapts a coordinator group to the interface proc.SnapshotSender
        expects (transport / server.id / enqueue / ack timeout)."""

        def __init__(self, coord: "BatchCoordinator", g: GroupHost):
            self._coord = coord
            self._g = g
            self.transport = coord.transport
            self.snapshot_ack_timeout_s = 60.0
            self.server = type(
                "S", (),
                {"id": (g.name, coord.name),
                 # the sender counts credit starvation through the
                 # server surface; route it to coordinator counters
                 "_c": staticmethod(
                     lambda field, n=1: coord.counters.incr(field, n)
                 )},
            )()

        def enqueue(self, msg, front: bool = False):
            tag = msg[0]
            to = msg[1]
            result = msg[2] if tag == "snapshot_send_done" else None
            self._coord.deliver(
                (self._g.name, self._coord.name), ("snap_send_done", to, result), None
            )

    def _start_snapshot_sender(self, g: GroupHost, to: ServerId) -> None:
        if to in g.snap_senders:
            return
        # prefer the disk-streaming reader (no decode, no blob in RAM);
        # memory-backed group logs fall back to the whole-state capture
        chunk_size = 1024 * 1024
        state_obj = chunk_iter = None
        stream = g.log.begin_snapshot_read(chunk_size)
        if stream is not None:
            meta, chunk_iter = stream
        else:
            got = g.log.read_snapshot()
            if got is None:
                return
            meta, state_obj = got
        live_entries = (
            g.log.sparse_read(list(meta.live_indexes)) if meta.live_indexes else []
        )
        from ra_tpu.runtime.proc import SnapshotSender

        sender = SnapshotSender(
            self._SenderShim(self, g), to, meta, state_obj, live_entries, g.term,
            chunk_size, chunk_iter=chunk_iter,
        )
        g.snap_senders[to] = sender
        sender.start()

    # -- failure detection -------------------------------------------------

    def _detect_loop(self) -> None:
        cooldown: Dict[int, float] = {}
        # suspicion arming: first sighting arms a randomized deadline
        # (the textbook randomized election timeout); the election only
        # fires if the group is STILL suspicious at the deadline. Breaks
        # dueling candidacies: the node whose trigger lands first gets a
        # full round before rivals pile in.
        armed: Dict[int, float] = {}
        # command-lane watchdog state per gid:
        # (applied_seen, oldest_pending_idx, since, strikes)
        lane_watch: Dict[int, Tuple[int, int, float, int]] = {}
        self._detect_last_tick = self.clock.monotonic()
        cnt = self.counters
        while self.running:
            # this thread's CPU inside its passes: one clock pair a pass
            # (ten to thirty a second; the clock ticks in 10 ms on some
            # hosts, so only the sum over many passes means anything)
            cpu0 = time.thread_time_ns()
            try:
                with _obs.span("ra/detect/scan", node=self.name):
                    self._detect_pass(cooldown, armed, lane_watch)
            except Exception:  # noqa: BLE001 — the detector must keep running
                self.detector_errors += 1
                if self.detector_errors == 1:
                    logger.exception(
                        "coordinator %s: detector pass failed", self.name
                    )
            cnt.incr("detector_passes")
            cnt.incr("detector_cpu_ns", time.thread_time_ns() - cpu0)
            time.sleep(self._detector_poll_s)

    def _detect_pass(self, cooldown, armed, lane_watch) -> None:
        """One pass of the detector thread: the per-tick work (lane
        watchdog, commit rate, health scan, machine ticks, resync
        probes), node liveness, and the suspicion sweep."""
        now0 = self.clock.monotonic()
        if now0 - self._detect_last_tick >= self.tick_interval_s:
            self._detect_last_tick = now0
            self._lane_watchdog(lane_watch, now0)
            # aggregate commit rate across all groups (the
            # batch-backend ra_li feed for system_overview /
            # placement decisions)
            applied_total = int(
                self._applied_np[: self.n_groups].sum()
            )
            prev = self._commit_li_prev
            self._commit_li_prev = (now0, applied_total)
            if prev is not None:
                rate = self._commit_li.sample(
                    max(0, applied_total - prev[1]), now0 - prev[0]
                )
                self.counters.put("commit_rate", int(round(rate)))
            # reclaim lanes of exited producer threads, then
            # publish the registered-lane gauge (one lane per
            # live producer; off the hot drain path)
            self._rings.prune_dead()
            self.counters.put(
                "ingress_ring_lanes", self._rings.lanes()
            )
            self._health_scan(now0)
            ms = int(self.clock.time() * 1000)
            me = self.name
            out = [
                ((self.groups[i].name, me), ("machine_tick", ms), None)
                for i in self._tick_gids
            ]
            # peers silent for two ticks may have missed AERs
            # (drops/partitions advance next_index optimistically):
            # probe them so their reject hints rewind replication (zero
            # cost while acks flow). The led rows with such a peer, as a
            # mask over the ack and member tables; the row's own member
            # table decides which slots
            n = self.n_groups
            old = now0 - self._last_ack_np[:n] > 2 * self.tick_interval_s
            rows = np.flatnonzero(
                (old & self._peer_np[:n]).any(axis=1)
                & (self._role_np[:n] == C.R_LEADER)
            )
            self.counters.incr(
                "detector_rows_walked", len(out) + len(rows))
            for i, old_i in zip(rows.tolist(), old[rows].tolist()):
                g = self.groups[i]
                if g is None or g.role != C.R_LEADER:
                    continue
                own = g.self_slot
                stale = [
                    s for s, m in enumerate(g.members)
                    if m is not None and s != own and old_i[s]
                ]
                if stale:
                    out.append(((g.name, me), ("resync", stale), None))
            if out:
                self.deliver_many(out)  # one ring slot a tick
        # a stopped node unregisters: include previously-seen
        # names so disappearance reads as death; and the nodes members
        # live on, which across a wire are in no registry of this process
        known = (set(self.registry.names()) | set(self._node_status)
                 | self._peer_nodes)
        all_alive = True  # every node a leader could be on, this pass
        for other in known:
            if other == self.name:
                continue
            alive = self.transport.node_alive(other)
            all_alive &= alive
            prev = self._node_status.get(other)
            self._node_status[other] = alive
            if prev is True and not alive:
                self._on_node_down(other)
        # suspicion sweep. Three leaderless shapes need retry —
        # without it a partition heal can wedge a group forever
        # (nobody re-elects once every node is "alive" again):
        #   1. a stalled election (pre-vote/candidate whose
        #      messages were lost) — mirror the actor backend's
        #      state-enter election timer;
        #   2. a follower with a known leader: a dead leader
        #      node counts once the follower has ALSO been
        #      without contact for one election timeout (vote
        #      grants refresh contact, so a member that just
        #      endorsed a campaigning rival holds off); an
        #      alive-but-silent leader (deposed, never re-won)
        #      times out on lost contact — the resync probe
        #      guarantees a live leader contacts every peer
        #      within ~2 ticks;
        #   3. a follower with NO known leader (term bumped by a
        #      failed election) — contact timeout, gated on
        #      term > 0 so fresh clusters still boot quiet until
        #      explicitly triggered (reference: ra:start_cluster
        #      calls trigger_election; no idle heartbeats).
        # window >> the 2-tick probe cadence: device pre-vote
        # grants have no leader-stickiness, so a trigger-happy
        # sweep could dethrone a healthy but loaded leader
        now = self.clock.monotonic()
        contact_window = max(
            5 * self.tick_interval_s, 6 * self.election_timeout_s
        )
        # The rows worth the walk, as a mask over the role and contact
        # mirrors: a row's contact older than its role's shortest
        # threshold, which every branch below implies (branch 2's
        # dead-leader clause only while some node is not alive, so
        # liveness is read once a pass for the mask and once a row only
        # for its rows). A healthy fleet leaves no row. Kept to four
        # numpy calls: under a busy interpreter lock each one over
        # 10,240 rows hands the lock over and waits to get it back
        # (PERF.md section 6, PR 31)
        n = self.n_groups
        et = self.election_timeout_s
        follower = contact_window if all_alive else min(et, contact_window)
        campaigning = min(2 * et, follower)
        # by role value: follower, pre-vote, candidate, leader (never),
        # and last, where GroupMirrors.FREE indexes, a free row (never)
        limits = np.array([follower, campaigning, campaigning, np.inf, np.inf])
        hits = now - self._contact_np[:n] > limits[self._role_np[:n]]
        for i in [k for k in armed if not hits[k]]:
            del armed[i]  # no longer suspicious
        rows = np.flatnonzero(hits).tolist()
        self.counters.incr("detector_rows_walked", len(rows))
        for i in rows:
            g = self.groups[i]
            if g is None or g.role == C.R_LEADER:
                continue
            if g.voter_status.get(g.self_slot) != "voter":
                continue
            leader = g.sid_of(g.leader_slot)
            last_contact = g.last_contact
            if g.role in (C.R_PRE_VOTE, C.R_CANDIDATE):
                suspicious = (
                    now - last_contact > 2 * self.election_timeout_s
                )
            elif leader is not None and leader[1] != self.name:
                # a dead leader node is suspicious only once it
                # has also been SILENT for an election timeout:
                # last_contact refreshes on vote grants, so a
                # member that just endorsed a campaigning rival
                # holds off instead of racing it (the round-5
                # takeover duel)
                suspicious = (
                    not self.transport.node_alive(leader[1])
                    and now - last_contact > self.election_timeout_s
                ) or now - last_contact > contact_window
            else:
                suspicious = (
                    g.term > 0
                    and now - last_contact > contact_window
                )
            if not suspicious:
                armed.pop(i, None)
            elif now >= cooldown.get(i, 0.0):
                dl = armed.get(i)
                if dl is None:
                    armed[i] = now + self.election_timeout_s * (
                        0.1 + random.random()
                    )
                elif now >= dl:
                    armed.pop(i, None)
                    cooldown[i] = (
                        now + 2 * self.election_timeout_s
                        + random.random() * 2 * self.election_timeout_s
                    )
                    self.deliver(
                        (g.name, self.name), ElectionTimeout(now),
                        None,
                    )

    def _lane_watchdog(
        self, lane_watch: Dict[int, Tuple[int, int, float, int]], now0: float
    ) -> None:
        """Per-command-deadline lane watchdog (runs on the detector
        thread, once per tick): a group holding pending client futures
        whose apply floor AND oldest pending index both sat still for
        ``command_deadline_s`` (or ``_WEDGE_WAVES`` of this
        coordinator's waves if that is longer, up to
        ``_WEDGE_STRETCH_MAX`` deadlines) is a wedged lane.
        Strike 1 recovers
        (device re-step + peer resync probe); a further strike bounds
        the failure by redirecting the stuck clients. Turns the round-5
        class of bug (accepted command, no commit, silent 10 s client
        hang) into a detected, counted, bounded event. Gauges
        ``lane_deadline_ms`` and ``lane_stall_max_ms`` say how near to
        being struck the lanes have come."""
        deadline = min(
            max(self.command_deadline_s, self._WEDGE_WAVES * self._wave_s),
            self._WEDGE_STRETCH_MAX * self.command_deadline_s)
        stall_max = 0.0
        # the rows that may hold client futures (``_pending_np``: set by
        # the append that put one in); the rest have nothing to watch.
        # A copy: numpy's nonzero raises when the step thread marks a
        # row between its counting and its filling
        live = self._pending_np
        marked = live[: self.n_groups].copy()
        for i in [k for k in lane_watch if not marked[k]]:
            del lane_watch[i]
        rows = np.flatnonzero(marked).tolist()
        self.counters.incr("detector_rows_walked", len(rows))
        for i in rows:
            g = self.groups[i]
            if g is None:
                continue
            pending = g.pending_replies
            if not pending:
                # emptied since (applied, or failed): unmark first, look
                # again after, so that an append racing this keeps its
                # mark whichever side stores last
                live[i] = 0
                if g.pending_replies:
                    live[i] = 1
                lane_watch.pop(i, None)
                continue
            try:
                oldest = min(pending)
            except (ValueError, RuntimeError):
                continue  # raced the step thread's mutation: next tick
            st = lane_watch.get(i)
            if st is None or st[0] != g.last_applied or st[1] != oldest:
                lane_watch[i] = (g.last_applied, oldest, now0, 0)
                continue
            still = now0 - st[2]
            if still > stall_max:
                stall_max = still
            if still <= deadline:
                continue
            strikes = st[3] + 1
            lane_watch[i] = (g.last_applied, oldest, now0, strikes)
            self.counters.incr("lane_wedges")
            self._obs_rec.record(
                "watchdog_strike", node=self.name, group=g.name,
                term=g.term,
                detail=f"strike {strikes}: oldest pending {oldest}, "
                       f"applied {g.last_applied}",
            )
            logger.warning(
                "coordinator %s: command lane wedged for group %s "
                "(oldest pending idx %d, applied %d, role %d, strike %d)",
                self.name, g.name, oldest, g.last_applied, g.role, strikes,
            )
            self.deliver(
                (g.name, self.name),
                ("lane_recover",) if strikes == 1 else ("lane_fail",),
                None,
            )
        cnt = self.counters
        cnt.put("lane_deadline_ms", round(deadline * 1e3))
        if stall_max * 1e3 > cnt.get("lane_stall_max_ms"):
            cnt.put("lane_stall_max_ms", round(stall_max * 1e3))

    def _health_scan(self, now: float) -> None:
        """Per-group health pass (docs/INTERNALS.md §14), once per tick
        on the detector thread: ONE device fetch over the existing
        consensus mirrors (proven by the scans==fetches counter
        invariant), then a fully vectorized gauge/anomaly update in
        ra_tpu.health — no per-group Python loop, so the cost scales
        with capacity at numpy speed, not with groups at Python speed."""
        from ra_tpu import health as H

        n = self.n_groups
        if n == 0:
            return
        with self._state_lock:
            st = self.state
            # the fused step DONATES the state buffers, so a reference
            # read outside the lock can die under us — but holding the
            # lock across the host transfer would stall the step thread
            # behind the async dispatch queue. Enqueue device-side
            # COPIES under the lock (dispatch only, microseconds; the
            # copies' buffers are fresh, never donated) ...
            snap = _health_copies(st)
        # ... and pay the transfer/queue wait OUTSIDE it: one
        # device_get per scan (the health_fetches == health_scans
        # counter invariant) with the step loop free to run
        dev = jax.device_get(snap)
        sc = self._health
        sc.counters.incr("health_fetches")
        rows, active = dev
        term, commit, last, role, leader_slot, self_slot = rows[:6, :n]
        match = rows[6:, :n].T
        active = active[:, :n].T
        applied = self._applied_np[:n]
        # follower match gap (leaders only): own tail minus the slowest
        # ACTIVE peer's confirmed match, self slot excluded
        cols = np.arange(match.shape[1])
        peers = active & (cols[None, :] != self_slot[:, None])
        slowest = np.where(
            peers, match.astype(np.int64), np.iinfo(np.int64).max
        ).min(axis=1)
        is_leader = role == C.R_LEADER
        has_peer = peers.any(axis=1)
        match_gap = np.where(
            is_leader & has_peer,
            np.maximum(last.astype(np.int64) - slowest, 0), 0,
        )
        leader_key = np.where(
            leader_slot >= 0, leader_slot.astype(np.int64), H.NO_LEADER_KEY
        )
        slots = np.asarray(self._hslots[:n], np.int64)
        sc.scan(now, slots, role, term, applied, commit, last, match_gap,
                leader_key)

    def _on_node_down(self, node_name: str) -> None:
        for i in range(self.n_groups):
            g = self.groups[i]
            if g is None or g.role == C.R_LEADER:
                continue
            leader = g.sid_of(g.leader_slot)
            if leader is not None and leader[1] == node_name:
                delay = self.election_timeout_s * (1 + random.random())
                # stamp the suspicion-confirmation time NOW: stamping at
                # fire time would make the staleness guard in
                # _handle_rare unable to drop the trigger when the
                # leader re-establishes contact during the delay
                armed = self.clock.monotonic()
                threading.Timer(
                    delay,
                    lambda gg=g, at=armed: self.deliver(
                        (gg.name, self.name),
                        ElectionTimeout(at), None,
                    ),
                ).start()

    def overview(self) -> dict:
        return {
            "node": self.name,
            "backend": "tpu_batch",
            "groups": self.n_groups,
            "steps": self.steps,
            "sub_steps": self.sub_steps,
            "shard_moves": self.shard_moves,
            "msgs": self.msgs_processed,
            "commit_rate": self.counters.get("commit_rate"),
            "counters": self.counters.to_dict(),
        }
