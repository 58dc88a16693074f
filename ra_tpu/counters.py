"""Named counter/gauge registry.

Capability parity with the reference's ``ra_counters`` facade over the
seshat dep (reference: ``src/ra_counters.erl:10-22``) and the per-server
counter taxonomy (reference: ``src/ra.hrl:266-438``): every server (and the
WAL / segment writer) registers a fixed-width array of int64 slots, updated
lock-free on the hot path and readable by observers at any time.

Implementation: one list of ints per registered object (a list, not a
numpy vector: ``v[i] += n`` on a list costs a third of what it costs on
an array, which boxes a scalar each way, and the wave loop increments
some thirty-five counters a step). CPython's GIL plus
single-writer-per-slot discipline (each slot is only incremented from its
owner's event loop) makes the plain ``v[i] += n`` safe here; readers may
see slightly stale values, matching the reference's semantics.
"""

from __future__ import annotations

import threading
from typing import Dict, Iterable, List, Optional, Sequence, Tuple


# (name, kind, help). Kind: "counter" (monotone) or "gauge".
FieldSpec = Tuple[str, str, str]

# Per-server counter fields — same information set as the reference's
# ra_server counter index definitions (src/ra.hrl:266-438).
RA_SERVER_FIELDS: List[FieldSpec] = [
    ("commands", "counter", "commands received by the leader"),
    ("commands_rejected", "counter",
     "client commands rejected with overloaded (admission window)"),
    ("commands_dropped_overload", "counter",
     "ack-free commands dropped past the admission window"),
    ("commands_rejected_nospace", "counter",
     "client commands rejected with the typed RA_NOSPACE reason while "
     "the node's storage plane was degraded or hard-watermarked "
     "(docs/INTERNALS.md §21)"),
    ("stale_peer_resends", "counter",
     "pipeline-window stalls resolved by rewinding to the peer match"),
    ("msgs_sent", "counter", "protocol messages sent"),
    ("dropped_sends", "counter", "sends dropped due to backpressure"),
    ("send_msg_effects_sent", "counter", "send_msg effects executed"),
    ("commit_index", "gauge", "current commit index"),
    ("last_applied", "gauge", "last applied index"),
    ("commit_latency", "gauge", "approx entry-write->commit latency ms"),
    ("term", "gauge", "current term"),
    ("last_index", "gauge", "last log index"),
    ("last_written_index", "gauge", "last durably written log index"),
    ("snapshot_index", "gauge", "current snapshot index"),
    ("snapshots_written", "counter", "snapshots written"),
    ("snapshot_installed", "counter", "snapshots installed (follower)"),
    ("snapshot_send_failures", "counter",
     "snapshot sender deaths (backoff retries armed)"),
    ("snapshot_credits_granted", "counter",
     "chunk credits granted to snapshot senders (receiver-paced flow "
     "control; docs/INTERNALS.md §21)"),
    ("snapshot_credit_waits", "counter",
     "sender backoffs taken on credit starvation (receiver granted 0)"),
    ("snapshot_credit_window", "gauge",
     "last credit window granted by / observed at this server"),
    ("checkpoints_written", "counter", "checkpoints written"),
    ("recovery_checkpoint_used", "counter", "boots that skipped replay"),
    ("checkpoints_promoted", "counter", "checkpoints promoted to snapshots"),
    ("checkpoint_index", "gauge", "latest checkpoint index"),
    ("aer_received", "counter", "append_entries RPCs received"),
    ("aer_received_followers", "counter", "AERs received while follower"),
    ("aer_replies_success", "counter", "successful AER replies sent"),
    ("aer_replies_failed", "counter", "failed AER replies sent"),
    ("elections", "counter", "elections started"),
    ("pre_vote_elections", "counter", "pre-vote rounds started"),
    ("force_elections", "counter", "forced elections"),
    ("applied", "counter", "entries applied to the machine"),
    ("releases", "counter", "release-cursor truncations"),
    ("check_quorum_stepdowns", "counter",
     "leader step-downs because a quorum of voters went silent past the "
     "check-quorum window (one-way partition protection: a leader that "
     "can send but not hear acks must not reign uselessly)"),
    ("num_segments", "gauge", "number of live segment files"),
    ("compactions", "counter", "compactions run"),
    ("local_queries", "counter", "local queries served"),
    ("leader_queries", "counter", "leader queries served"),
    ("consistent_queries", "counter", "consistent queries served"),
    # -- lease-based local reads (docs/INTERNALS.md §20) ----------------
    ("read_lease_served", "counter",
     "consistent queries served locally under a valid leader lease "
     "(zero quorum traffic)"),
    ("read_quorum_fallback", "counter",
     "consistent queries that fell back to a quorum heartbeat round "
     "(lease off, invalid, or not yet earned)"),
    ("read_lease_expirations", "counter",
     "leases found lapsed at read admission (each lapse counted once)"),
    ("read_lease_revocations", "counter",
     "leases revoked eagerly on deposition/stepdown/transfer/"
     "membership change"),
    ("read_stale_rejected", "counter",
     "bounded local queries rejected because the freshness floor "
     "exceeded the caller's max_staleness_s"),
    ("read_local_bounded", "counter",
     "local queries served under an explicit max_staleness_s bound"),
    ("read_issued", "counter", "log reads issued"),
    ("read_cache", "counter", "log reads served from memtable"),
    ("read_segment", "counter", "log reads served from segments"),
    ("open_segments", "gauge", "open segment fds"),
    ("commit_rate", "gauge", "commit rate (entries/sec, smoothed)"),
]

WAL_FIELDS: List[FieldSpec] = [
    ("wal_files", "counter", "WAL files opened"),
    ("batches", "counter", "write batches flushed"),
    ("writes", "counter", "write requests (queue items) flushed"),
    ("entries", "counter", "log entries written (runs expanded)"),
    ("bytes_written", "counter", "bytes written"),
    ("fsyncs", "counter", "fsync calls"),
    ("fsync_time_us", "counter", "cumulative fsync time (us)"),
    ("batch_size", "gauge", "last batch size"),
    ("out_of_seq", "counter", "out-of-sequence writes detected"),
    ("rollovers", "counter", "WAL file rollovers"),
    ("failures", "counter", "I/O failures (WAL entered failed state)"),
    ("space_failures", "counter",
     "failures classified space-class (ENOSPC/EDQUOT): the node "
     "degrades and probe-resumes instead of restarting from disk"),
    ("group_commit_waits", "counter",
     "flushes that held the batch open coalescing an arriving burst "
     "(adaptive group commit; docs/INTERNALS.md §15)"),
    ("group_commit_delay_us", "gauge",
     "coalescing delay of the last flush (us; 0 = flushed immediately)"),
    ("native_batches", "counter",
     "batches persisted via the native serialize+write+fsync path"),
    ("native_fallbacks", "counter",
     "permanent flips off the native path (lib lost or framing format "
     "mismatch after construction) — nonzero means the Python fallback "
     "took over mid-run"),
    ("writer_cpu_ns", "counter",
     "thread CPU of the writer inside its batches, bookkeeping, frame + "
     "write + fsync and the written hand-off included (one clock pair a "
     "batch)"),
    ("runs", "counter",
     "append requests flushed (a contiguous run or a single write; "
     "truncate markers and sparse writes are none)"),
    ("runs_in_place", "counter",
     "of those, the ones that lay above the snapshot floor and above "
     "all the file held of their writer, and so extended the file's "
     "index in place (the rest filtered, rewound or asked for a resend)"),
    ("log_opens_fresh", "counter",
     "server logs opened on a directory the open itself made: nothing "
     "listed, recovered or synced"),
    ("log_opens_recovered", "counter",
     "server logs opened on a directory that was already there: the "
     "full recovery ran"),
]

# Flow-control / liveness counters for a batch coordinator's command
# lane (one vector per coordinator, name ("coordinator", node_name)).
# These are the gauges an operator watches for overload: rejects and
# drops mean clients are past the admission window; lane_wedges firing
# means accepted commands stopped committing (the watchdog recovers or
# bounds them instead of hanging clients).
COORDINATOR_FIELDS: List[FieldSpec] = [
    ("commands_rejected", "counter",
     "client commands rejected with overloaded (reject-with-backoff)"),
    ("commands_dropped_overload", "counter",
     "ack-free (noreply) commands dropped past the admission window"),
    ("commands_rejected_nospace", "counter",
     "client commands rejected with the typed RA_NOSPACE reason while "
     "the coordinator's storage plane was degraded or hard-watermarked"),
    ("snapshot_credits_granted", "counter",
     "chunk credits granted to snapshot senders (receiver-paced flow "
     "control; docs/INTERNALS.md §21)"),
    ("snapshot_credit_waits", "counter",
     "sender backoffs taken on credit starvation (receiver granted 0)"),
    ("snapshot_credit_window", "gauge",
     "last credit window granted by this coordinator's accept path"),
    ("pending_redirected", "counter",
     "pending client futures answered with a redirect on deposition/"
     "truncation instead of being silently dropped"),
    ("lane_wedges", "counter",
     "watchdog detections of a wedged command lane (accepted command, "
     "no commit progress within the deadline)"),
    ("lane_deadline_ms", "gauge",
     "the watchdog's deadline at its last tick: command_deadline_s, "
     "stretched to _WEDGE_WAVES of the coordinator's waves where they "
     "are long, by at most _WEDGE_STRETCH_MAX"),
    ("lane_stall_max_ms", "gauge",
     "longest the watchdog has seen a lane with pending commands stand "
     "still (what it holds against the deadline at each tick; a lane "
     "past it was struck): how near healthy lanes come to a strike"),
    ("lane_recoveries", "counter",
     "watchdog recovery attempts (re-step + peer resync probe)"),
    ("lane_redirects", "counter",
     "watchdog second-strike bounded failures (pending futures "
     "redirected so clients retry elsewhere)"),
    ("stale_peer_resends", "counter",
     "pipeline-window stalls against a silent peer resolved by an "
     "empty probe AER (its ack/reject hint resynchronizes match/next)"),
    ("commit_rate", "gauge",
     "aggregate applied-entries/sec across this coordinator's groups "
     "(leaky-integrator smoothed, sampled per tick — the batch-backend "
     "feed for placement/leader-balancing decisions)"),
    # -- lease-based local reads, batch backend (§20) -------------------
    ("read_lease_served", "counter",
     "consistent queries served locally under a valid group lease "
     "(checked against the vectorized (G,) expiry array)"),
    ("read_quorum_fallback", "counter",
     "consistent queries that fell back to a quorum heartbeat round"),
    ("read_lease_expirations", "counter",
     "group leases found lapsed at read admission"),
    ("read_lease_revocations", "counter",
     "group leases revoked on deposition/term-adoption/transfer/"
     "membership change"),
    ("read_stale_rejected", "counter",
     "bounded local queries rejected past max_staleness_s"),
    ("read_local_bounded", "counter",
     "local queries served under an explicit max_staleness_s bound"),
    ("pipeline_steps", "counter",
     "tickets dispatched and realised by the started wave loop's turns "
     "(a ticket that carries only rare messages counts too); 0 under "
     "step_once (docs/INTERNALS.md §15)"),
    ("sync_found_ready", "counter",
     "started turns whose step program had already finished when the "
     "egress sync began (jax.Array.is_ready, read once before "
     "np.asarray): beside pipeline_steps, whether what egress_sync "
     "waits for is the device or the interpreter lock"),
    # -- async command plane (docs/INTERNALS.md §16) --------------------
    ("ingress_ring_msgs", "counter",
     "items drained from the lock-free ingress rings (a bulk fan-out "
     "or per-node batch counts as one item)"),
    ("ingress_ring_drains", "counter",
     "batched multi-lane ring drain passes run by the step thread"),
    ("ingress_ring_full", "counter",
     "publishes that hit a full ingress lane (backpressure: client "
     "commands reject through the admission path, lossy protocol "
     "traffic is counted and dropped, control messages gate-wait — "
     "never a silent drop)"),
    ("ingress_ring_lanes", "gauge",
     "ingress lanes registered (one per producer thread)"),
    ("ingress_overflow_msgs", "counter",
     "must-deliver items parked on the overflow queue after a full-"
     "lane publish (snapshot traffic, TimeoutNow, internal commands: "
     "never shed, never gate-waited — a foreign drainer thread parked "
     "on our gate while we park on its gate would deadlock)"),
    ("staging_prezeroed", "counter",
     "full-width mailbox pack buffers pre-zeroed between a dispatch "
     "and its egress sync, while the device computes (the next "
     "dispatch then packs into the spare buffer with no take/zero "
     "cost on the critical path)"),
    ("aer_groups_before_pack", "counter",
     "groups whose AppendEntries left from a dispatching pass ahead of "
     "its mailbox pack and device hand-off (docs/INTERNALS.md §15)"),
    # -- the WAL writer's durable hand-off (wal_notify_many: one state-
    # lock round per fsync batch; perf_counter_ns pairs per batch)
    ("wal_notify_batches", "counter",
     "state-lock rounds taken to deliver written events (one per WAL "
     "fsync batch, or one per event where the WAL has no bulk channel)"),
    ("wal_notify_events", "counter",
     "written events delivered to their groups under those rounds"),
    ("wal_notify_wait_ns", "counter",
     "the WAL writer's wait for the state lock, asked -> held, summed "
     "(inside every append_durable)"),
    ("wal_notify_hold_ns", "counter",
     "the state lock held for those rounds, held -> released, summed"),
    # -- the cyclic collector while the process serves (runtime/heap.py:
    # one collector hook, booked on ONE started coordinator of the
    # process, because a pause stops every node in it)
    ("gc_collections", "counter",
     "collections of the cyclic collector, any generation, while a "
     "coordinator of this process was started (on the first started "
     "one only: do not add the nodes of a process up twice)"),
    ("gc_full_collections", "counter",
     "those of the oldest generation (the unfrozen heap walked whole)"),
    ("gc_pause_ns", "counter",
     "time inside those collections, every Python thread stopped, "
     "summed (perf_counter_ns, one pair a collection)"),
    # -- the detector thread (_detect_loop): how often its masks leave
    # a row for its Python, and what the thread costs
    ("detector_passes", "counter",
     "passes of the detector thread (one a detector_poll_s; one in "
     "tick_interval_s / detector_poll_s of them holds the tick's work)"),
    ("detector_rows_walked", "counter",
     "group rows its per-row Python ran for, the suspicion sweep, the "
     "lane watchdog and the tick's probes added: the rows their masks "
     "over the role, contact, ack and pending arrays left (0 on a poll "
     "of a healthy elected fleet)"),
    ("detector_cpu_ns", "counter",
     "thread CPU inside those passes, summed (thread_time_ns, one pair "
     "a pass; the clock ticks in 10 ms on some hosts, so read sums)"),
    # -- the wire (runtime/tcp.py; a coordinator built with tcp=True):
    # booked by the transport once a frame, never once a message
    ("wire_frames_out", "counter",
     "batch frames handed to a peer's outbox (one a destination a "
     "_send_batch, more only past MAX_FRAME)"),
    ("wire_msgs_out", "counter", "protocol messages inside those frames"),
    ("wire_bytes_out", "counter",
     "bytes of those frames, length prefix and MAC included"),
    ("wire_frames_in", "counter",
     "batch frames authenticated, decoded and handed to ingest_batch"),
    ("wire_msgs_in", "counter", "protocol messages inside those frames"),
    ("wire_bytes_in", "counter",
     "bytes of those frames, length prefix and MAC included"),
    ("wire_encode_ns", "counter",
     "wall ns building a batch's list, encoding and sealing it (one "
     "clock pair a send_batch)"),
    ("wire_decode_ns", "counter",
     "wall ns from a batch frame's MAC check through its restricted "
     "decode to ingest_batch's return (one clock pair a frame)"),
    ("wire_dropped", "counter",
     "messages the wire lost: a full outbox, a blocked pair, a drop_fn, "
     "a message no frame holds, a failed connect or write, or shed by "
     "the receiving ingress (Raft resends what it needs)"),
    # -- machine effects on the batch backend (_realise_effects; booked
    # once a step that realised any)
    ("effects_send_msg", "counter",
     "send_msg effects realised (leader only): deliveries handed to "
     "send_msg_cb, a future or the transport"),
    ("effects_other", "counter",
     "other non-log effects taken up on the leader (monitor, demonitor, "
     "timer, mod_call, log read, reply, aux, append)"),
    ("release_cursors", "counter",
     "release_cursor effects seen on this node (every replica realises "
     "log effects)"),
    ("release_cursor_snapshots", "counter",
     "those after which the group's snapshot index moved (the log cut a "
     "snapshot; the others fell under min_snapshot_interval)"),
    ("monitors_armed", "counter",
     "monitor effects realised into this node's monitor table (first "
     "checkouts, and a new leader's state_enter re-arming)"),
    ("monitor_downs", "counter",
     "builtin down commands delivered by process_down to groups led "
     "here that watched the target"),
    ("egress_thread_batches", "counter",
     "per-destination message batches shipped by the dedicated egress "
     "sender thread (off the step loop)"),
    ("egress_thread_msgs", "counter",
     "messages shipped by the dedicated egress sender thread"),
    ("egress_thread_ring_full", "counter",
     "egress handoffs that overflowed the bounded sender ring and were "
     "sent inline instead (bounded handoff never drops)"),
    ("step_wakeups", "counter",
     "times the idle step thread was woken (ring publish, WAL notify, "
     "egress realisation, stop) — the event-driven replacement for the "
     "old 50 ms timed polls"),
    ("step_spurious_wakeups", "counter",
     "wakeups that found no work (must stay 0 while idle: the "
     "zero-spurious-wakeups invariant of the async command plane)"),
    # -- native hot-loop runtime (docs/INTERNALS.md §18) ----------------
    ("native_classify_batches", "counter",
     "drain passes whose class partition ran in the native GIL-released "
     "classifier (rt_classify) instead of the per-item Python loop"),
    ("native_classify_items", "counter",
     "ring items partitioned by the native classifier"),
    ("native_pack_batches", "counter",
     "mailbox builds whose columnwise AER/reply encode ran as one "
     "native GIL-released scatter (rt_pack_mbox)"),
    ("native_pack_msgs", "counter",
     "mailbox messages encoded by the native pack scatter"),
    ("native_fallbacks", "counter",
     "hot-loop iterations that took the byte-identical Python path "
     "while a native path was switched on (armed failpoints, "
     "out-of-range input, or a load failure after the switch)"),
    # -- thread-CPU accounts of the wave phases (docs/INTERNALS.md §13):
    # time.thread_time_ns() at the boundaries the wall phases have, read
    # on one turn in 16 and booked 16 times (the clock is a system
    # call); a phase's wall total less its CPU total is what its thread
    # spent off a core (interpreter lock, state lock, system calls)
    ("cpu_ns_ingress_drain", "counter",
     "thread CPU ns inside the wave phase ingress_drain (estimate: one "
     "turn in 16 is read)"),
    ("cpu_ns_host_pack", "counter",
     "thread CPU ns inside the wave phase host_pack (estimate: one "
     "turn in 16 is read)"),
    ("cpu_ns_host_egress", "counter",
     "thread CPU ns inside the wave phase host_egress (estimate: one "
     "turn in 16 is read)"),
    ("cpu_ns_aer_fanout", "counter",
     "thread CPU ns inside the wave phase aer_fanout (pre-pack "
     "and commit-driven fan-out, both added when the ticket realises; "
     "estimate: one turn in 16 is read)"),
    # -- read accounts (every read; time.monotonic_ns() stamps from the
    # caller's api.Future birth; docs/INTERNALS.md §13)
    ("read_registers", "counter",
     "consistent queries a leader accepted (quorum round started, or "
     "served under a lease / as the only voter)"),
    ("read_register_ns", "counter",
     "future born -> the query's heartbeats queued (or its reply, for "
     "a lease-served or single-voter read), summed"),
    ("read_quorum_rounds", "counter",
     "consistent queries answered after a heartbeat quorum round"),
    ("read_quorum_ns", "counter",
     "heartbeats queued -> the quorum's reply issued, summed"),
    ("state_queries", "counter",
     "log reads made for a consistent query's answer (a LogRead: "
     "kv_get's value, read by the replica that answers), plus "
     "state_query messages answered (members, overview, sparse_read)"),
    ("state_query_ns", "counter",
     "around the log fetch of a LogRead answer, on the answering "
     "thread; for a state_query message: future born -> reply issued; "
     "summed"),
    ("read_log_misses", "counter",
     "LogRead answers whose named index this replica's log no longer "
     "held (the entry comes back None and kv_get re-asks; 0 in a "
     "healthy window)"),
    # -- what turns a wave sub-phase's total into microseconds a message
    # (docs/INTERNALS.md §13): booked once a pass or step from a local
    # sum, never once a message
    ("routed_msgs", "counter",
     "protocol messages the step thread routed to their handlers "
     "(sub-phase ingress_route's work: AppendEntries, replies, votes, "
     "heartbeats, rare messages)"),
    ("follower_aers", "counter",
     "AppendEntries that carried entries and that this node, as a "
     "follower, wrote to its log and acknowledged at realisation "
     "(sub-phase egress_follow's per-message work)"),
    ("follower_entries", "counter",
     "log entries those AppendEntries carried"),
    ("rares_handled", "counter",
     "rare messages handled at realisation (sub-phase egress_rare's "
     "work: election timeouts, snapshot chunks, membership, probes; "
     "a read's queries and heartbeats where a ticket was unrealised "
     "at ingest)"),
    ("rares_early", "counter",
     "rare messages handled by the ingest pass that routed them (inside "
     "sub-phase ingress_route: consistent queries, heartbeats and their "
     "replies, where no ticket was unrealised)"),
]

# Per-node client-path vector (name ("client", node_name); kept by the
# node's TcpTransport under its own lock, booked once a frame): calls
# made through ``api`` to a server on another node, and their replies
# (docs/INTERNALS.md, "Clients on another node").
CLIENT_FIELDS: List[FieldSpec] = [
    ("client_calls_out", "counter",
     "calls this node sent to a server on another node"),
    ("client_calls_in", "counter",
     "calls from another node handed to this node's ingress"),
    ("client_replies_out", "counter",
     "replies this node sent to a caller on another node"),
    ("client_replies_in", "counter",
     "replies from another node taken in (late ones included)"),
    ("client_frames_out", "counter",
     "frames this node sent that carry at least one call or reply"),
    ("client_redirects", "counter",
     "replies taken in that redirect the caller to another member"),
    ("client_timeouts", "counter",
     "calls whose caller timed out first (removed from the table)"),
    ("client_late_replies", "counter",
     "replies that came after their call timed out (dropped)"),
]

# Per-node health-plane vector (name ("health", node_name); written
# only by the node's health scanner on its detector/tick thread). The
# scans==fetches invariant is the proof of the single-fetch-per-tick
# discipline the overhead guard relies on.
HEALTH_FIELDS: List[FieldSpec] = [
    ("health_scans", "counter", "health scans run (one per tick)"),
    ("health_fetches", "counter",
     "device/host mirror fetch operations (== health_scans proves the "
     "single-fetch-per-tick discipline)"),
    ("health_transitions", "counter", "anomaly state transitions"),
    ("health_stuck", "gauge", "groups currently classified stuck"),
    ("health_lagging", "gauge", "groups currently classified lagging"),
    ("health_flapping", "gauge", "groups currently classified flapping"),
    ("health_quiet", "gauge",
     "groups currently classified quiet (healthy)"),
    ("health_max_commit_gap", "gauge",
     "worst commit->apply gap across this node's groups"),
    ("health_max_match_gap", "gauge",
     "worst follower match gap across this node's led groups"),
    ("health_max_backlog", "gauge",
     "worst appended-but-unapplied admission backlog"),
    ("health_disk_pressure", "gauge",
     "node disk-pressure anomaly state (0=clear 1=soft 2=hard; "
     "hysteresis applied by the watermark controller, "
     "docs/INTERNALS.md §21)"),
    ("health_disk_transitions", "counter",
     "disk-pressure anomaly state transitions"),
]

# Per-watched-peer phi-accrual gauges (name ("phi", owner, target);
# written by the detector on whatever thread evaluates it). phi is a
# float: exported as phi * 1000 so the int64 slot keeps 3 decimals.
DETECTOR_FIELDS: List[FieldSpec] = [
    ("phi_milli", "gauge", "phi-accrual suspicion level x1000"),
    ("phi_suspect", "gauge", "1 while the peer is suspected, else 0"),
    ("phi_intervals", "gauge",
     "learned liveness-cadence samples in window"),
]

# Nemesis-plane vector (name ("nemesis", run_label); written by the
# nemesis Planner thread only). One inject/heal counter pair per fault
# dimension so a soak can prove every enabled dimension actually fired
# (a quiet schedule absorbing a dimension reads as injected == 0).
NEMESIS_FIELDS: List[FieldSpec] = [
    ("nemesis_partition_injected", "counter",
     "symmetric partitions injected"),
    ("nemesis_partition_healed", "counter", "symmetric partitions healed"),
    ("nemesis_oneway_injected", "counter",
     "one-way (asymmetric) partitions injected"),
    ("nemesis_oneway_healed", "counter", "one-way partitions healed"),
    ("nemesis_disk_injected", "counter",
     "disk failpoints armed (faults.py registry)"),
    ("nemesis_disk_healed", "counter", "disk failpoints disarmed"),
    ("nemesis_disk_full_injected", "counter",
     "ENOSPC/EDQUOT storms armed (storage-pressure survival plane)"),
    ("nemesis_disk_full_healed", "counter", "ENOSPC storms disarmed"),
    ("nemesis_slow_disk_injected", "counter",
     "fsync-latency brownout failpoints armed"),
    ("nemesis_slow_disk_healed", "counter",
     "fsync-latency failpoints disarmed"),
    ("nemesis_crash_injected", "counter",
     "node/coordinator crash-restarts injected"),
    ("nemesis_crash_healed", "counter",
     "crash-restart recoveries completed"),
    ("nemesis_membership_injected", "counter",
     "membership churn steps (remove+add cycles) injected"),
    ("nemesis_membership_healed", "counter",
     "membership churn steps completed (member rejoined)"),
    ("nemesis_overload_injected", "counter",
     "overload bursts (ack-free floods past the admission window)"),
    ("nemesis_overload_healed", "counter",
     "overload bursts drained (flood ended, lane live again)"),
    ("nemesis_modeflip_injected", "counter",
     "active-set step-mode flips injected (batch backend)"),
    ("nemesis_modeflip_healed", "counter",
     "active-set mode restored to its pre-fault value"),
    ("nemesis_heals_forced", "counter",
     "teardown heals forced on exit paths (0 unless a run exited with "
     "faults still armed — the heal-on-every-exit-path guarantee)"),
]

# Deterministic simulation plane (ra_tpu/sim, docs/INTERNALS.md §19):
# one vector per sweep label, accumulated across every schedule the
# sweep explores — the observability contract the sim lane is gated on
# (scripts/obs_smoke.py / scripts/sim_sweep.sh).
SIM_FIELDS: List[FieldSpec] = [
    ("sim_schedules_run", "counter", "simulation schedules executed"),
    ("sim_schedules_failed", "counter",
     "schedules whose oracle found a violation"),
    ("sim_steps_executed", "counter",
     "virtual-time events executed across all schedules"),
    ("sim_msgs_delivered", "counter", "network messages delivered"),
    ("sim_msgs_dropped", "counter",
     "messages dropped (blocked pairs + schedule drops)"),
    ("sim_msgs_duplicated", "counter", "duplicate deliveries injected"),
    ("sim_msgs_delayed", "counter", "deliveries given a schedule delay"),
    ("sim_shrink_iterations", "counter",
     "delta-debugging replays run while minimizing failures"),
    ("sim_minimized_ops", "counter",
     "ops in the last minimized repro schedule"),
    ("sim_virtual_ms", "counter", "virtual milliseconds simulated"),
    ("sim_disk_exhaustions", "counter",
     "simulated nodes that ran out of their disk byte budget"),
    ("sim_disk_parked_writes", "counter",
     "write confirmations parked while a sim node was space-degraded"),
]

# Session/lock-service machine (ra_tpu/models/session.py). The vector
# is owned by whoever constructs the machine (harness, sim world,
# smoke gate) — replicas constructed WITHOUT one stay silent, so a
# 3-replica fold does not triple-count.
SESSION_FIELDS: List[FieldSpec] = [
    ("session_opens", "counter", "sessions opened"),
    ("session_renews", "counter", "lease renewals"),
    ("session_closes", "counter", "clean session closes"),
    ("session_expiries_ttl", "counter",
     "sessions expired by TTL lapse (machine timer)"),
    ("session_expiries_down", "counter",
     "sessions expired by monitor DOWN"),
    ("session_lock_acquires", "counter", "lock grants (immediate)"),
    ("session_lock_waits", "counter", "lock requests queued behind a holder"),
    ("session_lock_releases", "counter", "explicit lock releases"),
    ("session_lock_steals", "counter", "locks stolen from a live holder"),
    ("session_lock_handoffs", "counter",
     "locks handed to a queued waiter after release/expiry"),
]

SEGMENT_WRITER_FIELDS: List[FieldSpec] = [
    ("mem_tables_flushed", "counter", "memtable flush jobs"),
    ("entries_flushed", "counter", "entries flushed to segments"),
    ("segments_created", "counter", "segment files created"),
    ("bytes_flushed", "counter", "bytes flushed"),
    ("flush_errors", "counter", "flush jobs that raised (retried/retained)"),
]


class Counters:
    """A fixed set of int64 slots addressed by field name."""

    __slots__ = ("name", "fields", "_idx", "_v")

    def __init__(self, name, fields: Sequence[FieldSpec]):
        self.name = name
        self.fields = list(fields)
        self._idx: Dict[str, int] = {f[0]: i for i, f in enumerate(self.fields)}
        self._v = [0] * len(self.fields)

    def incr(self, field: str, n: int = 1) -> None:
        self._v[self._idx[field]] += n

    def put(self, field: str, v: int) -> None:
        self._v[self._idx[field]] = v

    def get(self, field: str) -> int:
        return int(self._v[self._idx[field]])

    def to_dict(self) -> Dict[str, int]:
        return {f[0]: int(v) for f, v in zip(self.fields, self._v)}

    def describe(self) -> List[Dict[str, object]]:
        """Field metadata + current values: [{name, kind, help, value}]
        — the exposition shape (``overview()`` drops kind/help; scrape
        surfaces need them for TYPE/HELP lines)."""
        return [
            {"name": f[0], "kind": f[1], "help": f[2], "value": int(v)}
            for f, v in zip(self.fields, self._v)
        ]


class CounterRegistry:
    """Process-global registry: name -> Counters."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._tab: Dict[object, Counters] = {}

    def new(self, name, fields: Sequence[FieldSpec]) -> Counters:
        with self._lock:
            c = self._tab.get(name)
            if c is None:
                c = Counters(name, fields)
                self._tab[name] = c
            elif [f[0] for f in c.fields] != [f[0] for f in fields]:
                # replacing a live counters object would zero its values and
                # orphan existing holders — make the conflict loud instead
                raise ValueError(
                    f"counters {name!r} already registered with a different field set"
                )
            return c

    def fetch(self, name) -> Optional[Counters]:
        # take the lock like new()/delete(): a bare dict read can race a
        # concurrent resize (delete+new) and CPython only guarantees
        # atomicity for builtin-key gets — registry keys are tuples of
        # arbitrary objects
        with self._lock:
            return self._tab.get(name)

    def delete(self, name) -> None:
        with self._lock:
            self._tab.pop(name, None)

    def overview(self) -> Dict[object, Dict[str, int]]:
        return {k: v.to_dict() for k, v in list(self._tab.items())}

    def describe_overview(self) -> Dict[object, List[Dict[str, object]]]:
        """Exposition overview: every registered vector with field kind
        and help text alongside the values (what ``overview()`` drops)."""
        with self._lock:
            items = list(self._tab.items())
        return {k: v.describe() for k, v in items}

    def names(self) -> List[object]:
        return list(self._tab.keys())


_global = CounterRegistry()


def registry() -> CounterRegistry:
    return _global


def new(name, fields: Sequence[FieldSpec] = RA_SERVER_FIELDS) -> Counters:
    return _global.new(name, fields)


def fetch(name) -> Optional[Counters]:
    return _global.fetch(name)


def delete(name) -> None:
    _global.delete(name)


def overview() -> Dict[object, Dict[str, int]]:
    return _global.overview()
