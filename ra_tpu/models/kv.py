"""Built-in KV machine demonstrating log-as-value-store.

Capability parity with the reference's ``ra_kv`` (``src/ra_kv.erl:44-103``):
the machine state holds only ``key -> (raft_index, digest)`` — values are
NOT kept in machine state; they live in the log and are fetched on demand
through the log read path. Old values become dead log entries; the
current ones are advertised via ``live_indexes`` so compaction retains
exactly the live set.

Commands: ("put", key, value) | ("delete", key). Reads go through
``kv_get`` (one consistent query that names the log entry), not apply.
"""

from __future__ import annotations

import hashlib
import pickle
from typing import Any, Dict, Optional, Tuple

from ra_tpu import obs
from ra_tpu.effects import ReleaseCursor
from ra_tpu.machine import Machine
from ra_tpu.protocol import LogRead


def _digest(value: Any) -> bytes:
    return hashlib.blake2b(pickle.dumps(value), digest_size=8).digest()


class KvMachine(Machine):
    """State: {key: (raft_index, digest)}. Values read from the log."""

    def __init__(self, snapshot_interval: int = 256):
        self.snapshot_interval = snapshot_interval

    def init(self, config) -> Dict[str, Tuple[int, bytes]]:
        return {}

    def apply(self, meta, cmd, state):
        if not isinstance(cmd, tuple) or not cmd:
            return state, None
        op = cmd[0]
        if op == "put":
            _, key, value = cmd
            state = dict(state)
            state[key] = (meta["index"], _digest(value))
            reply = ("ok", meta["index"])
        elif op == "delete":
            _, key = cmd
            state = dict(state)
            old = state.pop(key, None)
            reply = ("ok", old[0] if old else None)
        elif op == "keys":
            return state, sorted(state.keys())
        else:
            return state, ("error", "unknown_op")
        effects = []
        if meta["index"] % self.snapshot_interval == 0:
            # state is tiny (indexes only): snapshot aggressively; live
            # indexes keep the current values in the log
            effects.append(ReleaseCursor(meta["index"], state))
        return state, reply, effects

    def live_indexes(self, state):
        return sorted(idx for idx, _ in state.values())

    def overview(self, state):
        return {"type": "kv", "keys": len(state)}


def kv_get(api_mod, member, key, timeout: float = 5.0) -> Optional[Any]:
    """Read a value in one round: ONE consistent query whose function
    resolves the key in the index map and names the value's log entry
    (``LogRead``); the leader that answers reads that entry from its own
    log there and then (the reference gets index and read plan in one
    call; docs/INTERNALS.md §13). The digest is checked here. Re-asks,
    at most three times, when the answering replica's log no longer
    held the index it named."""
    if obs.tracing():
        with obs.span("ra/kv/get", node=member[1]):
            return _kv_get(api_mod, member, key, timeout)
    return _kv_get(api_mod, member, key, timeout)


class KvResolve:
    """``kv_get``'s consistent query, an object so that it crosses to a
    leader in another process (Ra's ``{M, F, A}``: the code is the
    class on the answering node, only ``key`` travels): the key's entry
    in the index map as the ``LogRead`` the answering replica fills in,
    or None for an absent key."""

    __slots__ = ("key",)

    def __init__(self, key):
        self.key = key

    def __call__(self, st):
        at = st.get(self.key)
        return None if at is None else LogRead(at[0], at[1])


def _kv_get(api_mod, member, key, timeout):
    resolve = KvResolve(key)
    for _attempt in range(3):
        out = api_mod.consistent_query(member, resolve, timeout=timeout)
        if out[0] != "ok" or out[1] is None:
            return None
        idx, digest, entry = out[1]
        if entry is None:
            continue  # cut under the answer: re-resolve the current index
        value = entry.cmd.data[2]
        if _digest(value) != digest:
            raise IOError(f"kv digest mismatch for {key!r} at idx {idx}")
        return value
    return None
