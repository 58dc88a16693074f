"""Plain reference for the fifo-queue deployments: a queue is a list of
message ids waiting and a dict of message ids checked out, and a run is
held to what the configuration's guarantees say of it.

Shares no code with the program's machine (``ra_tpu/models/fifo.py``):
the generator keeps what its publishers and consumers saw
(``history``), ``observe`` reads each replica's state once after the
drain and writes it down as plain data, and ``judge`` walks every hot
queue's deliveries through a queue model of its own.

Held, per queue:

- a confirmed message was delivered to the consumer, and its settle was
  acknowledged, by the end of the drain (no lost message);
- no body (publisher, sequence) came under two message ids (no doubled
  enqueue), and a confirmed body came under the id its confirm gave;
- a consumer got its deliveries in message-id order, each id once
  (enqueue order while nothing is returned; an id comes again only to
  another consumer, after the first went down);
- no id was delivered after its settle had been acknowledged;
- ``next_msg_id - 1`` lies within [confirmed, confirmed + enqueues of
  unknown outcome], nothing is ready or checked out, and the three
  replicas are equal.

A queue with an operation of unknown outcome (a ``maybe`` reply, a
timeout) is held to the range it leaves open: an enqueue that may have
happened may have been delivered, a settle that may not have happened
may have left its message checked out. An idle queue must be empty with
its consumer attached, on every replica.
"""


def _plain(state) -> dict:
    """One replica's queue state as plain data."""
    return {
        "next_msg_id": int(state.next_msg_id),
        "ready": [int(i) for i, _body in state.queue],
        "checked_out": {c: sorted(int(i) for i in f)
                        for c, f in state.consumers.items() if f},
        "consumers": sorted(state.consumers, key=repr),
    }


def observe(cluster, history: dict, config: dict, seed: int) -> dict:
    cluster.settle(60)
    return {"states": [[_plain(s) for s in cluster.replica_states(g)]
                       for g in range(cluster.groups)]}


def _walk(g: int, deliveries, settled_at: dict, bad: list) -> dict:
    """The queue model: ``given`` is the ids the queue has given out so
    far, in the order it first did; ``checked_out`` maps an id to the
    consumer that holds it. Returns {(publisher, sequence): message id}
    of what was seen."""
    given = []
    checked_out = {}  # id -> consumer
    last_of = {}  # consumer -> the last id it got
    ids_of = {}  # body -> id
    for consumer, msg_id, writer, seq, t_ns in deliveries:
        body = (writer, seq)
        if ids_of.setdefault(body, msg_id) != msg_id:
            bad.append(f"g{g}: body {body} under message ids "
                       f"{ids_of[body]} and {msg_id} (doubled enqueue)")
        if msg_id <= last_of.get(consumer, 0):
            bad.append(f"g{g}: consumer {consumer!r} got id {msg_id} after "
                       f"id {last_of[consumer]} (out of order or doubled "
                       f"delivery)")
        last_of[consumer] = max(msg_id, last_of.get(consumer, 0))
        done = settled_at.get(msg_id)
        if done is not None and t_ns > done:
            bad.append(f"g{g}: id {msg_id} delivered {(t_ns - done) / 1e6:.3f}"
                       f" ms after its settle was acknowledged")
        if msg_id not in checked_out:
            if given and msg_id < given[-1]:
                bad.append(f"g{g}: id {msg_id} first given out after id "
                           f"{given[-1]} (out of order)")
            given.append(msg_id)
        checked_out[msg_id] = consumer
    return ids_of


def judge(history: dict, observed: dict, config: dict) -> list:
    bad = []
    hot = set(history["hot"])
    for g in range(history["groups"]):
        states = observed["states"][g]
        if any(s != states[0] for s in states[1:]):
            bad.append(f"g{g}: the replicas differ: {states}")
            continue
        state = states[0]
        if g not in hot:
            if state["next_msg_id"] != 1 or state["ready"] \
                    or state["checked_out"] or len(state["consumers"]) != 1:
                bad.append(f"g{g}: an idle queue holds {state}")
            continue
        confirmed = history["confirmed"][g]
        unknown = history["unknown"].get(g, [])
        settle_unknown = set(history["settle_unknown"].get(g, []))
        settled_at = {}
        for msg_id, t_done in history["settled"][g]:
            settled_at[msg_id] = min(t_done, settled_at.get(msg_id, t_done))
        ids_of = _walk(g, history["deliveries"][g], settled_at, bad)
        delivered = set(ids_of.values())
        if len({i for _s, i in confirmed}) != len(confirmed):
            bad.append(f"g{g}: two confirms gave one message id")
        for seq, msg_id in confirmed:
            got = ids_of.get((g, seq))
            if got is None:
                bad.append(f"g{g}: confirmed message {seq} (id {msg_id}) "
                           f"never delivered (lost message)")
            elif got != msg_id:
                bad.append(f"g{g}: confirmed message {seq} has id {msg_id} "
                           f"but was delivered as id {got}")
            elif msg_id not in settled_at and msg_id not in settle_unknown:
                bad.append(f"g{g}: confirmed message {seq} (id {msg_id}) "
                           f"delivered but its settle never acknowledged")
        known = {(g, seq) for seq, _i in confirmed} | {(g, s) for s in unknown}
        foreign = [b for b in ids_of if b not in known]
        if foreign:
            bad.append(f"g{g}: delivered bodies nobody sent: {foreign[:4]}")
        n = state["next_msg_id"] - 1
        if not len(confirmed) <= n <= len(confirmed) + len(unknown):
            bad.append(f"g{g}: {n} messages enqueued, {len(confirmed)} "
                       f"confirmed and {len(unknown)} of unknown outcome "
                       f"({'lost' if n < len(confirmed) else 'doubled'} "
                       f"enqueue)")
        # at rest: nothing waits; what is checked out is what the run
        # left open (a settle of unknown outcome, or a message of an
        # enqueue of unknown outcome that came after its consumer's
        # shard had ended)
        held = {i for f in state["checked_out"].values() for i in f}
        may_hold = settle_unknown | (set(range(1, n + 1)) - delivered
                                     if unknown else set())
        if state["ready"] and not unknown:
            bad.append(f"g{g}: ids {state['ready'][:8]} still ready after "
                       f"the drain")
        if not held <= may_hold:
            bad.append(f"g{g}: ids {sorted(held - may_hold)[:8]} still "
                       f"checked out after the drain")
    return bad
