"""Pallas TPU kernel: GQA decode attention (flash-decoding split over KV).

The decode hot spot is memory-bound: one query token must stream the whole
KV cache (S up to 512k).  Grid = (batch·kv_heads, kv_tiles): the kv axis is
innermost/sequential so the per-(batch, kv-head) online-softmax state for
the `group` query heads lives in VMEM scratch, and the KV cache is read
exactly once from HBM — the roofline-optimal schedule.  A `kv_len` scalar
masks the tail (ragged caches from the paging layer).

q is reshaped to (B·KH, G, D): all G query heads of one kv head are carried
in a single MXU-friendly (G, block_k) score tile.
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


def _decode_kernel(
    len_ref,  # scalar prefetch: (1,) int32 kv_len
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, block_k: int, nk: int, sm_scale: float,
):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_len = len_ref[0]
    live = ki * block_k < kv_len

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (G, D)
        k = k_ref[0].astype(jnp.float32)  # (block_k, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (G, block_k)
        cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < kv_len, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "block_k", "interpret")
)
def decode_attention_pallas(
    q: jax.Array,  # (B, H, D) — single decode token per sequence
    k: jax.Array,  # (B, KH, S, D) KV cache
    v: jax.Array,  # (B, KH, S, D)
    kv_len: jax.Array | int | None = None,  # valid cache length (≤ S)
    *,
    sm_scale: float | None = None,
    block_k: int = 256,
    interpret: Optional[bool] = None,
):
    b, h, d = q.shape
    kh, s = k.shape[1], k.shape[2]
    assert h % kh == 0
    group = h // kh
    if sm_scale is None:
        sm_scale = 1.0 / (d**0.5)
    if kv_len is None:
        kv_len = s
    kv_len = jnp.asarray([kv_len], jnp.int32)

    pad = (-s) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    sp = s + pad
    nk = sp // block_k

    # (B, H, D) → (B·KH, G, D): group q heads by their kv head.
    qr = q.reshape(b, kh, group, d).reshape(b * kh, group, d)
    kr = k.reshape(b * kh, sp, d)
    vr = v.reshape(b * kh, sp, d)

    grid = (b * kh, nk)
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, block_k=block_k, nk=nk, sm_scale=sm_scale
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, group, d), lambda bh, ki, lens: (bh, 0, 0)),
                pl.BlockSpec((1, block_k, d), lambda bh, ki, lens: (bh, ki, 0)),
                pl.BlockSpec((1, block_k, d), lambda bh, ki, lens: (bh, ki, 0)),
            ],
            out_specs=pl.BlockSpec((1, group, d), lambda bh, ki, lens: (bh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, d), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * kh, group, d), q.dtype),
        interpret=interpret_mode(interpret),
    )(kv_len, qr, kr, vr)
    return out.reshape(b, h, d)
