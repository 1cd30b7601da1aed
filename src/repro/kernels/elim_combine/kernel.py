"""Pallas TPU kernel: publishing-elimination combine (segmented scan).

This is the device-side hot loop of the Elim-ABtree round (DESIGN.md §4,
core/elimination.py is the pure-jnp oracle).  Input ops are key-sorted; each
op is lifted to a transition of the {absent, present(v)} state machine and
the per-key fold is a *segmented inclusive scan* of transition composition.

TPU mapping:
  * within a tile: Hillis–Steele doubling scan (log2(TILE) vectorized
    compose steps — `jnp.roll` + select, no gathers),
  * across tiles: the TPU grid iterates sequentially, so a carry transition
    lives in VMEM scratch and is composed into each tile (the segmented-scan
    flag monoid makes the carry self-neutralizing across key boundaries).

The same kernel powers the EmbedElim sparse-update combine (optim/sparse.py)
where "insert/delete" become "accumulate/clear" on embedding rows.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

# op codes (match core.elimination)
OP_NOP, OP_FIND, OP_INSERT, OP_DELETE = 0, 1, 2, 3
# NOTE: kind selects below pin .astype(jnp.int32) — under jax_enable_x64 a
# jnp.where whose branches are both weak Python ints resolves to int64, and
# the resulting transition tuples then fail the int32 ref stores.
K_ABSENT, K_CONST, K_KEEP = 0, 1, 2


def _compose(f, g):
    """h = g∘f on transition 5-tuples of int32 arrays (see core/elimination).
    Inlined for the kernel: identical algebra, int32 kinds."""
    fa_k, fa_v, fp_k, fp_v, f_fl = f
    ga_k, ga_v, gp_k, gp_v, g_fl = g

    f_a_present = fa_k != K_ABSENT
    gp_on_fa_k = jnp.where(gp_k == K_KEEP, K_CONST, gp_k)
    gp_on_fa_v = jnp.where(gp_k == K_KEEP, fa_v, gp_v)
    h_a_k = jnp.where(f_a_present, gp_on_fa_k, ga_k)
    h_a_v = jnp.where(f_a_present, gp_on_fa_v, ga_v)

    f_p_present = fp_k != K_ABSENT
    g_keep = gp_k == K_KEEP
    hp_k_fp = jnp.where(
        g_keep, jnp.where(fp_k == K_KEEP, K_KEEP, K_CONST).astype(jnp.int32), gp_k
    )
    hp_v_fp = jnp.where(g_keep, fp_v, gp_v)
    h_p_k = jnp.where(f_p_present, hp_k_fp, ga_k)
    h_p_v = jnp.where(f_p_present, hp_v_fp, ga_v)

    return (
        jnp.where(g_fl == 1, ga_k, h_a_k),
        jnp.where(g_fl == 1, ga_v, h_a_v),
        jnp.where(g_fl == 1, gp_k, h_p_k),
        jnp.where(g_fl == 1, gp_v, h_p_v),
        jnp.maximum(f_fl, g_fl),
    )


def _apply(t, present0, val0):
    a_k, a_v, p_k, p_v, _ = t
    on_a_p = (a_k != K_ABSENT).astype(jnp.int32)
    on_a_v = jnp.where(a_k == K_CONST, a_v, val0)
    on_p_p = (p_k != K_ABSENT).astype(jnp.int32)
    on_p_v = jnp.where(p_k == K_CONST, p_v, val0)
    present = jnp.where(present0 == 1, on_p_p, on_a_p)
    val = jnp.where(present0 == 1, on_p_v, on_a_v)
    return present, val


def _identity_like(x):
    z = jnp.zeros_like(x)
    return (z + K_ABSENT, z, z + K_KEEP, z, z)


def _combine_kernel(
    ops_ref, vals_ref, head_ref, p0_ref, v0_ref,
    bp_ref, bv_ref, ap_ref, av_ref,
    carry_ref,
    *, tile: int,
):
    i = pl.program_id(0)

    ops = ops_ref[...]  # (TILE, 1) int32
    vals = vals_ref[...]
    head = head_ref[...]
    p0 = p0_ref[...]
    v0 = v0_ref[...]

    # lift ops → transitions
    is_ins = (ops == OP_INSERT).astype(jnp.int32)
    is_del = ops == OP_DELETE
    a_k = jnp.where(is_ins == 1, K_CONST, K_ABSENT).astype(jnp.int32)
    a_v = jnp.where(is_ins == 1, vals, 0)
    p_k = jnp.where(is_del, K_ABSENT, K_KEEP).astype(jnp.int32)
    p_v = jnp.zeros_like(vals)
    t = (a_k, a_v, p_k, p_v, head)

    # Hillis–Steele inclusive scan over the tile (axis 0), log2 steps.
    d = 1
    while d < tile:
        shifted = tuple(jnp.roll(x, d, axis=0) for x in t)
        idx = jax.lax.broadcasted_iota(jnp.int32, ops.shape, 0)
        ident = _identity_like(ops)
        left = tuple(jnp.where(idx >= d, s, ii) for s, ii in zip(shifted, ident))
        t = _compose(left, t)
        d *= 2

    # initialize / read tile carry (identity at tile 0)
    @pl.when(i == 0)
    def _():
        ident = _identity_like(carry_ref[...][:, 0:1])
        for j, x in enumerate(ident):
            carry_ref[..., j : j + 1] = x

    carry = tuple(carry_ref[...][:, j : j + 1] for j in range(5))
    inc = _compose(tuple(jnp.broadcast_to(c, x.shape) for c, x in zip(carry, t)), t)

    after_p, after_v = _apply(inc, p0, v0)

    # exclusive state = inclusive of previous element (carry for element 0);
    # at segment heads the observed state is simply (p0, v0).
    exc = tuple(jnp.roll(x, 1, axis=0) for x in inc)
    idx = jax.lax.broadcasted_iota(jnp.int32, ops.shape, 0)
    exc = tuple(
        jnp.where(idx >= 1, e, jnp.broadcast_to(c, e.shape))
        for e, c in zip(exc, carry)
    )
    exc_p, exc_v = _apply(exc, p0, v0)
    before_p = jnp.where(head == 1, p0, exc_p)
    before_v = jnp.where(head == 1, v0, exc_v)

    bp_ref[...] = before_p
    bv_ref[...] = before_v
    ap_ref[...] = after_p
    av_ref[...] = after_v

    # new carry = inclusive transition of the tile's last element
    last = tuple(x[tile - 1 : tile, :] for x in inc)
    for j, x in enumerate(last):
        carry_ref[..., j : j + 1] = x


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def elim_combine_pallas(
    ops: jax.Array,  # (B,) int32, key-sorted
    vals: jax.Array,  # (B,) int32
    seg_head: jax.Array,  # (B,) bool
    present0: jax.Array,  # (B,) bool  (valid everywhere, broadcast per segment)
    val0: jax.Array,  # (B,) int32
    *,
    tile: int = 256,
    interpret: Optional[bool] = None,
):
    b = ops.shape[0]
    pad = (-b) % tile
    if pad:
        ops = jnp.pad(ops, (0, pad))  # NOP
        vals = jnp.pad(vals, (0, pad))
        seg_head = jnp.pad(seg_head, (0, pad), constant_values=True)
        present0 = jnp.pad(present0, (0, pad))
        val0 = jnp.pad(val0, (0, pad))
    n = ops.shape[0]
    col = lambda x: x.astype(jnp.int32)[:, None]
    grid = (n // tile,)
    spec = pl.BlockSpec((tile, 1), lambda i: (i, 0))
    outs = pl.pallas_call(
        functools.partial(_combine_kernel, tile=tile),
        grid=grid,
        in_specs=[spec] * 5,
        out_specs=[spec] * 4,
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.int32)] * 4,
        scratch_shapes=[pltpu.VMEM((1, 5), jnp.int32)],
        interpret=interpret_mode(interpret),
    )(col(ops), col(vals), col(seg_head), col(present0), col(val0))
    bp, bv, ap, av = (o[:b, 0] for o in outs)
    return bp.astype(bool), bv, ap.astype(bool), av
