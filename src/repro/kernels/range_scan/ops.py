"""Public wrapper for range_scan: dispatches between the Pallas kernel
(int32 device keys) and the dtype-generic jnp reference.

The tree's host index uses int64 keys; the TPU kernel operates on int32
lanes (no int64 vector support).  ``range_scan`` therefore routes int64
candidates to the reference implementation unless the caller asserts the
keys lie strictly inside the int32 range (``narrow=True`` casts and uses
the kernel).  The round engine's scan phase (``core/rounds.py``, serving
both ``scan_round`` and fused mixed-op rounds) calls this wrapper from
inside its jitted gather: the tree's int64 host index takes the ref path,
while int32 device keys and bounded-key serving/benchmark paths take the
kernel.

Narrow-path key domain: user keys must satisfy ``-2**31 < k < 2**31 - 1``.
``INT32_MAX`` itself is the kernel's EMPTY sentinel (exactly as the tree
reserves the int64 max as its own EMPTY) — a key equal to 2**31 - 1 would
be conflated with an empty slot and silently dropped, so callers with an
unbounded key space must leave ``narrow=False``.  ``lo``/``hi`` bounds are
clipped into the int32 range, which under this contract excludes no valid
key.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.range_scan.kernel import INT32_MAX, range_scan_pallas
from repro.kernels.range_scan.ref import range_scan_ref


def range_scan(
    cand_keys: jax.Array,  # (B, n) EMPTY-padded gathered leaf slots
    cand_vals: jax.Array,  # (B, n)
    lo: jax.Array,  # (B,)
    hi: jax.Array,  # (B,)
    *,
    cap: int = 128,
    use_pallas: bool = True,
    narrow: bool = False,
    interpret: Optional[bool] = None,
):
    """Fixed-capacity ascending gather of candidate keys in [lo, hi).

    Returns ``(keys, vals, count, truncated)``; see ref.py for semantics.
    """
    if use_pallas and (narrow or cand_keys.dtype == jnp.int32):
        empty = jnp.iinfo(cand_keys.dtype).max
        ck = jnp.where(cand_keys == empty, INT32_MAX, cand_keys).astype(jnp.int32)
        keys, vals, count, trunc = range_scan_pallas(
            ck,
            cand_vals.astype(jnp.int32),
            jnp.clip(lo, -INT32_MAX, INT32_MAX).astype(jnp.int32),
            jnp.clip(hi, -INT32_MAX, INT32_MAX).astype(jnp.int32),
            cap=cap,
            interpret=interpret,
        )
        # widen back to the caller's dtypes, restoring the EMPTY sentinel
        out_keys = jnp.where(
            keys == INT32_MAX, empty, keys.astype(cand_keys.dtype)
        )
        return out_keys, vals.astype(cand_vals.dtype), count, trunc
    return range_scan_ref(cand_keys, cand_vals, lo, hi, cap)
