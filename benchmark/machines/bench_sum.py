"""``ra_bench``'s machine (no-op apply, a release cursor every 100,000
entries: src/ra_bench.erl:48-55) plus a running sum of the commands'
8-byte headers, so that a lost or doubled command shows in the state.

State: ``(count, header_sum)``. The reply is the count.
"""

from ra_tpu.effects import ReleaseCursor
from ra_tpu.machine import Machine

RELEASE_EVERY = 100_000
HEADER_BYTES = 8


def header_of(payload: bytes) -> int:
    return int.from_bytes(payload[:HEADER_BYTES], "little")


class BenchSumMachine(Machine):
    def init(self, config):
        return (0, 0)

    def apply(self, meta, cmd, state):
        if not isinstance(cmd, bytes):
            return state, None  # builtin commands (nodeup, timeout, ...)
        state = (state[0] + 1, state[1] + header_of(cmd))
        if meta["index"] % RELEASE_EVERY == 0:
            return state, state[0], [ReleaseCursor(meta["index"], state)]
        return state, state[0]

    def overview(self, state):
        return {"type": "bench_sum", "applied": state[0]}


def make(_args=None):
    return BenchSumMachine()
