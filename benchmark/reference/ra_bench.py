"""Plain reference for the ``ra_bench`` deployments: a group's state is
the count and the header sum of the commands it acknowledged.

Shares no code with the program: the fold is kept by the generator from
the replies it got (``history``), the replicas' states and a sample of
``consistent_query`` answers are read once after the drain
(``observe``), and ``judge`` compares the two as plain data.

Held: every acknowledged command is applied, once, on every replica
(no lost and no doubled command); a linearizable read after the drain
returns that state. Where a command's outcome is unknown (a ``maybe``
reply, a timeout) the group is held to the range the unknown commands
leave open, and the replicas must still agree.
"""

import itertools

SAMPLE = 32


def observe(cluster, history: dict, config: dict, seed: int) -> dict:
    from ra_tpu import api

    cluster.settle(60)
    G = cluster.groups
    states = [[tuple(s) for s in cluster.replica_states(g)] for g in range(G)]
    picks = list(range(0, G, max(1, G // SAMPLE)))[:SAMPLE]
    reads = {}
    for g in picks:
        out = api.consistent_query(
            (cluster.names[g], cluster.leader_node(g)), lambda s: s, timeout=30)
        reads[g] = tuple(out[1]) if out[0] == "ok" else ("error", repr(out))
    return {"states": states, "reads": reads}


def _allowed(count: int, total: int, unknown):
    """Every (count, sum) the unknown commands leave open."""
    out = set()
    for r in range(len(unknown) + 1):
        for combo in itertools.combinations(unknown, r):
            out.add((count + r, total + sum(combo)))
    return out


def judge(history: dict, observed: dict, config: dict) -> list:
    bad = []
    unknown = history["unknown"]
    for g in range(history["groups"]):
        want = (history["count"][g], history["sum"][g])
        states = observed["states"][g]
        if g in unknown:
            if len(unknown[g]) > 8:
                bad.append(f"g{g}: {len(unknown[g])} commands of unknown "
                           f"outcome, too many to compare as a range")
            elif not (set(states) <= _allowed(*want, unknown[g])
                      and len(set(states)) == 1):
                bad.append(f"g{g}: replicas {states} outside what "
                           f"{want} + unknown {unknown[g]} allows")
        elif any(s != want for s in states):
            lost = [s for s in states if s[0] < want[0]]
            bad.append(f"g{g}: replicas {states} != acknowledged {want} "
                       f"({'lost' if lost else 'doubled or foreign'} command)")
        read = observed["reads"].get(g)
        if read is not None and g not in unknown and read != want:
            bad.append(f"g{g}: consistent_query {read} != acknowledged {want}")
    return bad
