"""Pallas TPU kernel: tiled online-softmax attention (causal / GQA / SWA).

Standard TPU flash pattern: grid = (batch·q_heads, q_tiles, kv_tiles); the
kv dimension is innermost so the (m, l, acc) running-softmax state persists
in VMEM scratch across kv tiles; output is written once on the last kv tile.
Causal and sliding-window masks skip fully-masked tiles via `pl.when`.

GQA is expressed in the BlockSpec index maps: the k/v block row is
`(bh // H) * KH + (bh % H) // group`, so q heads sharing a kv head stream
the same K/V tiles (VMEM reuse, no HBM duplication).

Block sizes default to MXU-aligned (128, 128) tiles; D is kept whole per
block (≤ 256 for all assigned archs).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, block_q: int, block_k: int, nk: int, causal: bool, window: int, sm_scale: float,
    s_orig: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # tile-level skip: under causality a kv tile strictly above the diagonal
    # contributes nothing; under SWA a tile entirely left of the window does
    # not either.
    q_lo = qi * block_q
    k_lo = ki * block_k
    live = True
    if causal:
        live = k_lo <= q_lo + block_q - 1
    if window > 0:
        live = jnp.logical_and(live, k_lo + block_k - 1 > q_lo - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0].astype(jnp.float32)  # (block_k, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (block_q, block_k)

        rows = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = cols < s_orig  # padded key columns never receive mass
        if causal:
            mask &= cols <= rows
        if window > 0:
            mask &= cols > rows - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]  # (block_q, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        o = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = o.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "sm_scale", "block_q", "block_k", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, KH, S, D)
    v: jax.Array,  # (B, KH, S, D)
    *,
    causal: bool = True,
    window: int = 0,  # 0 = unbounded; >0 = sliding window (SWA)
    sm_scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
):
    b, h, s, d = q.shape
    kh = k.shape[1]
    assert h % kh == 0, "GQA requires H % KH == 0"
    group = h // kh
    if sm_scale is None:
        sm_scale = 1.0 / (d**0.5)

    pad_q = (-s) % block_q
    pad_k = (-s) % block_k
    sq, sk = s + pad_q, s + pad_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * kh, sk, d)
    vr = v.reshape(b * kh, sk, d)
    nq, nk = sq // block_q, sk // block_k

    def kv_row(bh):
        return (bh // h) * kh + (bh % h) // group

    # Padded kv columns (beyond original s) must be masked: padding keys are
    # zeros → scores 0, which would beat NEG_INF.  Under causal they are only
    # visible to padded q rows (discarded).  For non-causal use we mask via
    # window==0 & causal==False ⇒ disallow pad: handled by masking cols < s.
    grid = (b * h, nq, nk)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            block_q=block_q,
            block_k=block_k,
            nk=nk,
            causal=causal,
            window=window,
            sm_scale=sm_scale,
            s_orig=s,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (kv_row(bh), ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (kv_row(bh), ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(qr, kr, vr)
    return out.reshape(b, h, sq, d)[:, :, :s, :]
