"""The table of peaks, and the bytes a step must move.

``step_bytes`` counts what one full-width fused step has to read and
write whatever the implementation: every ``GroupState`` array once in
and once out (the step's state is donated and returned), the packed
mailbox in, the egress out. It takes the shapes from the program's own
step function by ``jax.eval_shape`` (nothing runs), so a change of the
state's layout changes the count with it.
"""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peak(device_kind: str, what: str) -> float:
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/peaks.json: add it with its source")
    return float(peaks[device_kind][what])


def _nbytes(tree) -> int:
    import jax
    import numpy as np

    return int(sum(np.prod(leaf.shape, dtype=np.int64) * leaf.dtype.itemsize
                   for leaf in jax.tree_util.tree_leaves(tree)))


def step_bytes(capacity: int, peers: int) -> int:
    import jax
    import jax.numpy as jnp

    from ra_tpu.ops import consensus as C

    state = jax.eval_shape(lambda: C.make_group_state(capacity, peers))
    rows = len(C.MBOX_FIELDS) + len(C.MBOX_SCAT_FIELDS)
    mbox = jax.ShapeDtypeStruct((rows, capacity), jnp.int32)
    out_state, egress = jax.eval_shape(C.consensus_step_packed_scat, state, mbox)
    return _nbytes(state) + _nbytes(mbox) + _nbytes(out_state) + _nbytes(egress)
