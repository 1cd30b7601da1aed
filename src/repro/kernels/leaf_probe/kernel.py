"""Pallas TPU kernel: batched unsorted-leaf probe.

The paper's key structural choice — *unsorted leaves* — maps directly onto
the TPU VPU: probing a leaf is a lane-parallel compare of the query key
against all b slots (one VREG op for b ≤ 128), followed by a masked
reduction.  A CPU implementation scans slot-by-slot; the TPU-native form
compares the whole leaf at once.  This kernel probes a *batch* of
(leaf row, key) pairs, the shape used by the round's search phase and by
the serving engine's page-table lookups.

Layout: leaf key rows are gathered (HBM → VMEM tiles of (TB, b)) by the
caller; the kernel is the compare/select hot loop.  Keys are int32 on
device (TPU has no int64 vector support; the host index uses int64 — 64-bit
keys are split hi/lo by ops.py when needed).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _probe_kernel(leaf_keys_ref, leaf_vals_ref, query_ref, slot_ref, val_ref, *, b: int):
    """One (TB, b) tile: lane-parallel compare + masked argmin reduction."""
    rows = leaf_keys_ref[...]  # (TB, b) int32
    vals = leaf_vals_ref[...]  # (TB, b) int32
    q = query_ref[...]  # (TB, 1) int32
    eq = rows == q  # broadcast compare across slots (VPU)
    # slot = first matching index; b+1 ⇒ not found
    iota = jax.lax.broadcasted_iota(jnp.int32, eq.shape, 1)
    slot = jnp.min(jnp.where(eq, iota, jnp.int32(b + 1)), axis=1, keepdims=True)
    found = slot < b + 1
    # select value at slot (masked sum avoids a gather)
    sel = iota == slot
    # dtype pinned: under jax_enable_x64 an un-pinned int32 sum promotes to
    # int64 and the store into the int32 output ref fails.
    val = jnp.sum(jnp.where(sel, vals, 0), axis=1, keepdims=True, dtype=jnp.int32)
    slot_ref[...] = jnp.where(found, slot, -1)
    val_ref[...] = jnp.where(found, val, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def leaf_probe_pallas(
    leaf_keys: jax.Array,  # (B, b) int32 — gathered leaf key rows
    leaf_vals: jax.Array,  # (B, b) int32
    queries: jax.Array,  # (B,) int32
    *,
    block_b: int = 256,
    interpret: Optional[bool] = None,
):
    bsz, b = leaf_keys.shape
    pad = (-bsz) % block_b
    if pad:
        leaf_keys = jnp.pad(leaf_keys, ((0, pad), (0, 0)), constant_values=0)
        leaf_vals = jnp.pad(leaf_vals, ((0, pad), (0, 0)))
        queries = jnp.pad(queries, (0, pad), constant_values=-1)
    n = leaf_keys.shape[0]
    grid = (n // block_b,)
    out_shape = [
        jax.ShapeDtypeStruct((n, 1), jnp.int32),  # slot
        jax.ShapeDtypeStruct((n, 1), jnp.int32),  # val
    ]
    slot, val = pl.pallas_call(
        functools.partial(_probe_kernel, b=b),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, b), lambda i: (i, 0)),
            pl.BlockSpec((block_b, b), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_shape=out_shape,
        interpret=interpret_mode(interpret),
    )(leaf_keys, leaf_vals, queries[:, None])
    return slot[:bsz, 0], val[:bsz, 0]
