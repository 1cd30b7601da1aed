"""jit'd public wrapper for leaf_probe: gathers leaf rows from the node pool
then runs the Pallas probe (or the jnp oracle when use_pallas=False).

64-bit host keys are probed as (hi, lo) int32 pairs: two compares + AND —
the TPU-native encoding of the paper's 8-byte keys (DESIGN.md §2)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.leaf_probe.kernel import leaf_probe_pallas
from repro.kernels.leaf_probe.ref import leaf_probe_ref


def leaf_probe(
    leaf_keys: jax.Array,
    leaf_vals: jax.Array,
    queries: jax.Array,
    *,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
):
    if use_pallas:
        return leaf_probe_pallas(leaf_keys, leaf_vals, queries, interpret=interpret)
    return leaf_probe_ref(leaf_keys, leaf_vals, queries)


def leaf_probe_i64(
    leaf_keys64: jax.Array,  # (B, b) int64
    leaf_vals32: jax.Array,  # (B, b) int32
    queries64: jax.Array,  # (B,) int64
    *,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
):
    """Probe 64-bit keys via hi/lo split: slot matches iff both halves match.
    Returns (slot, val) with slot = -1 when absent."""
    hi = (leaf_keys64 >> 32).astype(jnp.int32)
    lo = (leaf_keys64 & 0xFFFFFFFF).astype(jnp.int32)
    qhi = (queries64 >> 32).astype(jnp.int32)
    qlo = (queries64 & 0xFFFFFFFF).astype(jnp.int32)
    b = leaf_keys64.shape[1]
    # compare lo; verify hi at the matched slot.  Duplicated lo halves across
    # slots are possible, so match on a combined predicate instead: encode
    # slot-match as (hi match) & (lo match) with a two-plane probe.
    eq = (hi == qhi[:, None]) & (lo == qlo[:, None])
    # reuse the kernel on a synthesized 1/0 plane: probe for value 1
    plane = eq.astype(jnp.int32)
    slot, val = leaf_probe(
        plane, leaf_vals32, jnp.ones_like(qlo), use_pallas=use_pallas, interpret=interpret
    )
    del b
    return slot, val
