"""Pallas TPU kernel: batched range-scan gather over unsorted leaf slots.

The tree's unsorted leaves make a range scan a *mask + compact* problem: the
leaf frontier for a query ``[lo, hi)`` is gathered by the caller and
flattened to ``n`` candidate slots per query; the kernel then

  1. lane-parallel compares every candidate against the interval,
  2. compacts the matches into a fixed-capacity, *ascending* output via
     rank-selection: the rank of a matching key is the number of smaller
     matching keys, and output row ``c`` selects the key of rank ``c`` by a
     masked sum — no scatter, no sort network, all VPU-friendly ops.

Layout: queries run along the 128 lanes and candidates along the
sublanes, so one grid step serves 128 queries and every value in the
kernel is a 2-D ``(rows, 128)`` plane (the TPU compiler refuses the 3-D
``(TB, n, n)`` broadcasts of a query-per-row layout).  The wrapper
transposes ``(B, n)`` candidates to ``(n, B)`` and the ``(cap, B)`` result
back.  The rank is a loop over candidate rows ``j``: row ``j`` (one
query-wide vector) is compared against the candidates being ranked and the
0/1 result accumulates into an int32 rank plane.

Two variants, bit-identical (integer partial ranks):

  * pairwise — one pass ranks all ``n`` candidates at once (an ``(n, 128)``
    live plane);
  * tiled (``tile_n``) — candidates are ranked and selected ``T`` rows at a
    time, so the live planes are ``(T, 128)`` whatever ``n`` is.

Keys are int32 on device (TPU has no int64 vector support — the tree's
64-bit keys take the pure-jnp ref path; see ops.py).

Dtype discipline: the host package enables jax_enable_x64, under which
integer reductions of int32 promote to int64 and Python-int constants and
loop bounds trace as int64 — every reduction pins ``dtype=jnp.int32`` and
every constant and ``fori_loop`` bound is built as ``jnp.int32`` inside the
kernel body.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

INT32_MAX = jnp.iinfo(jnp.int32).max  # EMPTY sentinel for device keys
LANES = 128  # queries per grid step


def _range_scan_kernel(
    keys_ref, vals_ref, lo_ref, hi_ref,
    okeys_ref, ovals_ref, count_ref, trunc_ref,
    km_ref, rank_ref,
    *, cap: int, tile: int,
):
    """One block of 128 queries (lanes) × ``n`` candidates (sublanes)."""
    empty = jnp.int32(INT32_MAX)
    zero = jnp.int32(0)
    n = keys_ref.shape[0]
    rows = keys_ref[...]  # (n, 128)
    match = (rows >= lo_ref[...]) & (rows < hi_ref[...]) & (rows != empty)
    # non-matches sit at INT32_MAX: never smaller than a match, never selected
    km_ref[...] = jnp.where(match, rows, empty)

    # rank of each matching key = #matching keys strictly smaller (keys are
    # unique within a tree), accumulated over candidate rows j.
    for t0 in range(0, n, tile):  # static: one tile (pairwise) or n/T
        ki = km_ref[t0 : t0 + tile, :]

        def rank_row(j, acc, ki=ki):
            kj = km_ref[pl.ds(j, 1), :]  # (1, 128)
            return acc + (kj < ki).astype(jnp.int32)

        rank_ref[t0 : t0 + tile, :] = jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(n), rank_row,
            jnp.zeros((tile, LANES), jnp.int32),
        )

    # output row c takes the key of rank c (masked sum over candidates).
    def select(c, carry):
        hit = jnp.zeros((1, LANES), jnp.int32)
        out_k = jnp.zeros((1, LANES), jnp.int32)
        out_v = jnp.zeros((1, LANES), jnp.int32)
        for t0 in range(0, n, tile):
            km = km_ref[t0 : t0 + tile, :]
            sel = (km != empty) & (rank_ref[t0 : t0 + tile, :] == c)
            hit += jnp.sum(sel.astype(jnp.int32), axis=0, keepdims=True, dtype=jnp.int32)
            out_k += jnp.sum(jnp.where(sel, km, zero), axis=0, keepdims=True, dtype=jnp.int32)
            out_v += jnp.sum(
                jnp.where(sel, vals_ref[t0 : t0 + tile, :], zero),
                axis=0, keepdims=True, dtype=jnp.int32,
            )
        okeys_ref[pl.ds(c, 1), :] = jnp.where(hit > 0, out_k, empty)
        ovals_ref[pl.ds(c, 1), :] = jnp.where(hit > 0, out_v, zero)
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(cap), select, jnp.int32(0))
    total = jnp.sum(match.astype(jnp.int32), axis=0, keepdims=True, dtype=jnp.int32)
    count_ref[...] = jnp.minimum(total, jnp.int32(cap))
    trunc_ref[...] = (total > jnp.int32(cap)).astype(jnp.int32)


# Candidate widths past this auto-route to the tiled variant, whose live
# planes stay (T, 128) however wide the leaf frontier grows.
TILE_AUTO_THRESHOLD = 256
_DEFAULT_TILE = 128


@functools.partial(jax.jit, static_argnames=("cap", "tile_n", "interpret"))
def range_scan_pallas(
    cand_keys: jax.Array,  # (B, n) int32 gathered leaf slots, INT32_MAX-padded
    cand_vals: jax.Array,  # (B, n) int32
    lo: jax.Array,  # (B,) int32 inclusive
    hi: jax.Array,  # (B,) int32 exclusive
    *,
    cap: int = 128,
    tile_n: int = 0,
    interpret: Optional[bool] = None,
):
    """Returns ``(keys (B,cap), vals (B,cap), count (B,), truncated (B,))``
    with keys ascending and INT32_MAX-padded.

    ``tile_n`` selects the rank-select variant: 0 (default) auto-routes —
    pairwise for n ≤ ``TILE_AUTO_THRESHOLD``, tiled otherwise; a positive
    value forces that tile width; -1 forces the pairwise kernel."""
    bsz, n = cand_keys.shape
    if tile_n == 0:
        tile_n = _DEFAULT_TILE if n > TILE_AUTO_THRESHOLD else -1
    # candidate rows pad to the sublane tile (8) and to the tile width; the
    # INT32_MAX pad never matches and never outranks a real key.
    step = 8 if tile_n <= 0 else max(8, tile_n + (-tile_n) % 8)
    n_pad = n + (-n) % step
    tile = n_pad if tile_n <= 0 else step
    b_pad = bsz + (-bsz) % LANES
    keys_t = jnp.pad(
        cand_keys.astype(jnp.int32), ((0, b_pad - bsz), (0, n_pad - n)),
        constant_values=INT32_MAX,
    ).T
    vals_t = jnp.pad(
        cand_vals.astype(jnp.int32), ((0, b_pad - bsz), (0, n_pad - n))
    ).T
    lo_t = jnp.pad(lo.astype(jnp.int32), (0, b_pad - bsz))[None, :]
    hi_t = jnp.pad(hi.astype(jnp.int32), (0, b_pad - bsz))[None, :]
    # index maps return int32 block indices (a bare 0 traces as int64 under
    # x64, which the TPU compiler refuses)
    col = lambda rows: pl.BlockSpec((rows, LANES), lambda i: (jnp.int32(0), i))
    out_shape = [
        jax.ShapeDtypeStruct((cap, b_pad), jnp.int32),  # keys
        jax.ShapeDtypeStruct((cap, b_pad), jnp.int32),  # vals
        jax.ShapeDtypeStruct((1, b_pad), jnp.int32),  # count
        jax.ShapeDtypeStruct((1, b_pad), jnp.int32),  # truncated
    ]
    keys, vals, count, trunc = pl.pallas_call(
        functools.partial(_range_scan_kernel, cap=cap, tile=tile),
        grid=(b_pad // LANES,),
        in_specs=[col(n_pad), col(n_pad), col(1), col(1)],
        out_specs=[col(cap), col(cap), col(1), col(1)],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((n_pad, LANES), jnp.int32),  # masked keys
            pltpu.VMEM((n_pad, LANES), jnp.int32),  # ranks
        ],
        interpret=interpret_mode(interpret),
    )(keys_t, vals_t, lo_t, hi_t)
    return (
        keys.T[:bsz],
        vals.T[:bsz],
        count[0, :bsz],
        trunc[0, :bsz].astype(bool),
    )
