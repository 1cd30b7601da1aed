"""Public wrappers for tree_descend: dispatch between the Pallas kernels
(int32 device keys) and the dtype-generic jnp references.

The tree's host index uses int64 keys; the TPU kernels operate on int32
lanes.  ``descend_probe`` therefore routes int64 pools to the reference
implementation unless the caller asserts the keys AND values lie strictly
inside the int32 range (``narrow=True`` casts and uses the kernel — the
same contract as ``kernels/range_scan``'s narrow gate: the int32 max is
the device EMPTY sentinel, so a key/value at ±(2**31 - 1) would be
conflated with a free slot).

``frontier_compact`` operates on node *ids* (always int32), so both of its
paths are sort-free: the default jnp path compacts by exclusive-cumsum
rank + one batched scatter (replacing the per-level stable ``argsort`` of
the original frontier expansion), and the ``use_pallas`` path runs the
masked-select Pallas kernel, keeping the whole scan descent in VMEM.  The
argsort formulation survives only as ``ref.frontier_compact_ref``, the
test oracle.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.tree_descend.kernel import (
    INT32_MAX,
    descend_probe_pallas,
    frontier_compact_pallas,
)
from repro.kernels.tree_descend.ref import (
    descend_probe_ref,
    descend_ref,
    probe_ref,
)

# The largest pool (rows, scratch row included) whose packed record plane
# the v5e compiler accepts in VMEM: a 2**19-node pool packs into 64 MiB and
# compiles; 2**20 needs 128 MiB plus scratch and is refused ("Ran out of
# memory in memory space vmem").  Found by compiling for a described v5e
# and pinned by tests/test_chip_compile.py.  Larger pools take the ref path
# even under the narrow gate.
MAX_POOL_ROWS = (1 << 19) + 1


def descend_probe_uses_kernel(n_rows: int, key_dtype, narrow: bool) -> bool:
    """The dispatch gate of :func:`descend_probe`: the Pallas kernel runs
    iff the keys are int32 (or the caller's narrow gate asserts they fit)
    and the pool fits VMEM (``n_rows <= MAX_POOL_ROWS``)."""
    return (narrow or key_dtype == jnp.int32) and n_rows <= MAX_POOL_ROWS


def descend_probe(
    pool_keys: jax.Array,  # (N, b) EMPTY-padded keys/routers
    pool_vals: jax.Array,  # (N, b)
    children: jax.Array,  # (N, b) int32
    is_leaf: jax.Array,  # (N,) bool
    root,  # int32 scalar
    queries: jax.Array,  # (B,)
    *,
    max_height: int,
    notfound,
    narrow: bool = False,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
):
    """Fused search phase: root-to-leaf descent + unsorted-leaf probe.

    Returns ``(leaf_ids (B,) int32, found (B,) bool, slot (B,) int32,
    val (B,))`` with ``val == notfound`` where absent — exactly the
    ``descend_ref``/``probe_ref`` composition on every path.
    """
    if use_pallas and descend_probe_uses_kernel(
        pool_keys.shape[0], pool_keys.dtype, narrow
    ):
        empty = jnp.iinfo(pool_keys.dtype).max
        pk = jnp.where(pool_keys == empty, INT32_MAX, pool_keys).astype(jnp.int32)
        q = jnp.where(queries == empty, INT32_MAX, queries).astype(jnp.int32)
        leaf_ids, found, slot, val32 = descend_probe_pallas(
            pk,
            pool_vals.astype(jnp.int32),
            children.astype(jnp.int32),
            is_leaf,
            root,
            q,
            max_height=max_height,
            interpret=interpret,
        )
        val = jnp.where(found, val32.astype(pool_vals.dtype), notfound)
        return leaf_ids, found, slot, val
    return descend_probe_ref(
        pool_keys, pool_vals, children, is_leaf, root, queries,
        max_height=max_height, notfound=notfound,
    )


def frontier_compact(
    cand: jax.Array,  # (B, M) int32 candidate node ids
    valid: jax.Array,  # (B, M) bool
    f: int,  # static output frontier width
    *,
    scratch: int,
    use_pallas: bool = False,
    interpret: Optional[bool] = None,
):
    """Stable, sort-free compaction of each row's valid candidates into a
    width-``f`` frontier.  Returns ``(frontier (B, f) int32, valid (B, f)
    bool, overflow (B,))``; invalid output slots hold ``scratch``.
    Bit-identical to the argsort oracle (``ref.frontier_compact_ref``) on
    both paths."""
    if use_pallas:
        raw, fvalid, total = frontier_compact_pallas(
            cand, valid, f=f, interpret=interpret
        )
        return jnp.where(fvalid, raw, jnp.int32(scratch)), fvalid, total > f
    vi = valid.astype(jnp.int32)
    rank = jnp.cumsum(vi, axis=1, dtype=jnp.int32) - vi  # exclusive rank
    total = jnp.sum(vi, axis=1, dtype=jnp.int32)
    # one batched scatter: lane → its rank slot; invalid / overflow lanes
    # land in the dropped column f (duplicate writes there are discarded).
    idx = jnp.where(valid, jnp.minimum(rank, f), f)
    rows = jnp.broadcast_to(jnp.arange(cand.shape[0])[:, None], cand.shape)
    raw = (
        jnp.zeros((cand.shape[0], f + 1), jnp.int32)
        .at[rows, idx]
        .set(cand, mode="drop")[:, :f]
    )
    fvalid = jnp.arange(f, dtype=jnp.int32)[None, :] < total[:, None]
    return jnp.where(fvalid, raw, jnp.int32(scratch)), fvalid, total > f


__all__ = [
    "descend_probe",
    "descend_probe_pallas",
    "descend_probe_ref",
    "descend_ref",
    "probe_ref",
    "frontier_compact",
    "frontier_compact_pallas",
    "MAX_POOL_ROWS",
    "descend_probe_uses_kernel",
]
