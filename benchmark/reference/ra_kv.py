"""Plain reference for the ``ra_kv`` deployments: a key's value is the
put with the highest raft index.

Shares no code with the program. From the generator's rows alone
(``history``): every value carries its writer and sequence number, a
put's reply gave its raft index, and every row has its send and reply
time on one clock. ``observe`` reads each replica's key -> index map and
a sample of keys through ``kv_get`` once after the drain; ``judge``
compares plain data.

Held: (1) an acknowledged write is read back, never an older one — a
read's value must belong to a put of the same key whose index is at or
above the read's floor, the highest index acknowledged for that key
before the read was sent; (2) after the drain each replica's index map
equals the fold of the acknowledged puts (no lost write, on any
replica); (3) ``kv_get`` of a sample of keys returns the last
acknowledged value. A put without a reply (a timeout) has an unknown
outcome: its key is then held to "at or above the fold, and the
replicas agree".
"""

import bisect
import struct

import numpy as np

HEAD = struct.Struct("<IQ")
SAMPLE = 64


def observe(cluster, history: dict, config: dict, seed: int) -> dict:
    from ra_tpu import api
    from ra_tpu.models.kv import kv_get

    cluster.settle(60)
    maps = [dict() for _ in cluster.node_names()]
    for g in range(cluster.groups):
        for node, state in enumerate(cluster.replica_states(g)):
            for key, (index, _digest) in state.items():
                maps[node][key] = index
    keys, group_of = history["keys"], history["group_of"]
    rng = np.random.default_rng(seed)
    sample = {}
    for i in rng.choice(len(keys), size=min(SAMPLE, len(keys)), replace=False):
        i = int(i)
        g = group_of[i]
        value = kv_get(api, (cluster.names[g], cluster.leader_node(g)),
                       keys[i], timeout=30)
        sample[i] = None if value is None else HEAD.unpack_from(value)
    return {"maps": maps, "sample": sample}


def judge(history: dict, observed: dict, config: dict) -> list:
    bad = []
    keys = history["keys"]
    p, g = history["puts"], history["gets"]
    put_of = {}  # (writer, seq) -> row number
    acked = {}  # key -> [(t_done, index)], then sorted with a running max
    open_keys = set()  # keys with a put of unknown outcome
    for r in range(len(p["key"])):
        put_of[(p["writer"][r], p["seq"][r])] = r
        if p["ok"][r]:
            acked.setdefault(p["key"][r], []).append(
                (p["t_done"][r], p["index"][r]))
        else:
            open_keys.add(p["key"][r])
    fold = {}
    floors = {}
    for key, rows in acked.items():
        rows.sort()
        times, best, run = [], [], -1
        for t_done, index in rows:
            run = max(run, index)
            times.append(t_done)
            best.append(run)
        floors[key] = (times, best)
        fold[key] = run

    # (1) reads
    stale = 0
    for r in range(len(g["key"])):
        if not g["ok"][r]:
            continue
        key = g["key"][r]
        row = put_of.get((g["writer"][r], g["seq"][r]))
        if row is None or p["key"][row] != key:
            bad.append(f"read of {keys[key]} returned a value no put of "
                       f"that key wrote: writer {g['writer'][r]} seq "
                       f"{g['seq'][r]}")
            continue
        if not p["ok"][row]:
            continue  # a put of unknown outcome: cannot be placed
        times, best = floors.get(key, ([], []))
        k = bisect.bisect_left(times, g["t_send"][r])
        floor = best[k - 1] if k else -1
        if p["index"][row] < floor:
            stale += 1
            if stale <= 5:
                bad.append(f"stale read of {keys[key]}: got the put at index "
                           f"{p['index'][row]}, but index {floor} was "
                           f"acknowledged before the read was sent")
    if stale > 5:
        bad.append(f"... {stale} stale reads in all")

    # (2) every replica's index map against the fold
    for node, have in enumerate(observed["maps"]):
        wrong = 0
        for key, index in fold.items():
            got = have.get(keys[key])
            if key in open_keys:
                ok = got is not None and got >= index and all(
                    m.get(keys[key]) == got for m in observed["maps"])
            else:
                ok = got == index
            if not ok:
                wrong += 1
                if wrong <= 3:
                    what = ("lost write" if got is None or got < index
                            else "unacknowledged overwrite")
                    bad.append(f"node {node}: {keys[key]} at index {got}, the "
                               f"acknowledged puts fold to {index} ({what})")
        if wrong > 3:
            bad.append(f"node {node}: ... {wrong} keys differ in all")
        extra = len(have) - len(fold)
        if extra > 0 and not open_keys:
            bad.append(f"node {node}: {extra} keys nobody was acknowledged for")

    # (3) the sample read through the client's path
    for key, head in observed["sample"].items():
        if key in open_keys or key not in fold:
            continue
        row = put_of.get(tuple(head)) if head is not None else None
        if row is None or p["key"][row] != key or p["index"][row] != fold[key]:
            bad.append(f"kv_get({keys[key]}) after the drain returned {head}, "
                       f"not the last acknowledged put (index {fold[key]})")
    return bad
