"""Pallas TPU kernels: device-resident search phase.

Two kernels make the whole root-to-leaf search path device-resident:

``descend_probe_pallas`` — fused descent + probe.  The node pool is packed
into one lane-dense record plane (per node: ``b`` keys, ``b`` children,
``b`` values and the leaf flag, padded to a power-of-two record width, so
four b=8 records share one 128-lane row) and mapped whole into VMEM, so
the hot upper levels of the tree stay on-chip for every query instead of
being re-gathered from HBM once per level per batch (the ``max_height``
separate batched gathers of the jnp path).  The descent walks eight
queries at a time, one per sublane: each query's node id is read out as a
scalar and addresses a dynamic row load of its record, the eight rows
stack into one ``(8, 128)`` tile, and one lane-parallel router count
(``#routers ≤ key``) plus a masked child pick advances all eight.  The
walk stops as soon as all eight stand on leaves (or after ``max_height``
levels, the jnp path's fixed trip count — leaves are fixed points, so the
results agree), and the unsorted-leaf probe runs on the final tile, so one
kernel launch returns ``(leaf, found, slot, val)``.

``frontier_compact_pallas`` — segmented frontier compaction.  The scan
descent expands each query's frontier level by level; compacting the valid
candidates used a per-level stable XLA ``argsort`` (the "24× sort" — one
per level per scan round).  The kernel replaces the sort network with an
exclusive rank — a strictly-lower-triangular 0/1 matmul on the MXU, since
Pallas TPU has no ``cumsum`` — and output slot ``c`` selects the
candidate with rank ``c`` by masked sum: stable and scatter-free.  Queries
run along the lanes and candidates along the sublanes, so every value is
a 2-D ``(rows, 128)`` plane.

Keys are int32 on device (TPU has no int64 vector support) — the tree's
64-bit host index takes the pure-jnp ref path; see ops.py for the narrow
gate.  VMEM contract: the packed pool must fit on-chip; the dispatcher
routes pools past ``ops.MAX_POOL_ROWS`` (the largest the v5e compile
accepts) to the ref path.

Dtype discipline: the host package enables jax_enable_x64, under which
integer reductions of int32 promote to int64 and Python-int constants,
loop bounds and index-map results trace as int64 — every reduction pins
``dtype=jnp.int32`` and every constant is built as ``jnp.int32`` (the
weak-typing trap that once bit leaf_probe and elim_combine).
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
LANES = 128
SUBLANES = 8  # queries walked together by the descent


def record_width(b: int) -> int:
    """Lanes per packed node record: b keys, b children, b values and the
    leaf flag, rounded up to a power of two that divides 128."""
    w = 1 << (3 * b).bit_length()
    if w > LANES:
        raise ValueError(f"b={b} leaves no room for a packed record")
    return w


def pack_pool(pool_keys, pool_vals, children, is_leaf):
    """``(N, b)`` int32 planes → the ``(rows, 128)`` record plane the
    descent kernel maps into VMEM (``128 // record_width(b)`` nodes per
    row, rows padded to the sublane tile)."""
    n, b = pool_keys.shape
    rec = record_width(b)
    npr = LANES // rec
    recs = jnp.concatenate(
        [
            pool_keys.astype(jnp.int32),
            children.astype(jnp.int32),
            pool_vals.astype(jnp.int32),
            is_leaf.astype(jnp.int32)[:, None],
            jnp.zeros((n, rec - 3 * b - 1), jnp.int32),
        ],
        axis=1,
    )
    recs = jnp.pad(recs, ((0, (-n) % (SUBLANES * npr)), (0, 0)))
    return recs.reshape(-1, LANES)


def pool_vmem_bytes(n_nodes: int, b: int) -> int:
    """VMEM bytes of the packed record plane for an ``n_nodes`` pool."""
    npr = LANES // record_width(b)
    rows = -(-n_nodes // (SUBLANES * npr)) * SUBLANES
    return rows * LANES * 4


# ----------------------------------------------------------------------------
# fused descent + probe
# ----------------------------------------------------------------------------


def _descend_probe_kernel(
    pool_ref, start_ref, q_ref, leaf_ref, found_ref, slot_ref, val_ref, rows_ref,
    *, b: int, n_nodes: int, max_height: int,
):
    """One (TB, 1) query block against the VMEM-resident packed pool."""
    rec = record_width(b)
    shift = (LANES // rec).bit_length() - 1  # log2(records per row)
    zero = jnp.int32(0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)

    def fetch(node):
        """(8, 1) node ids → their rows (8, 128) and per-lane record offsets
        (lane − record start; the record's fields sit at offsets 0..rec-1).
        Rows follow the jnp path's gather semantics: a negative (NULL) id
        wraps once — onto the scratch row, an empty pseudo-leaf — and an id
        past the pool clamps; the walk keeps the raw id, as that path does."""
        node = jnp.where(node < zero, node + jnp.int32(n_nodes), node)
        node = jnp.clip(node, zero, jnp.int32(n_nodes - 1))
        for k in range(SUBLANES):
            row = jax.lax.shift_right_logical(node[k, 0], jnp.int32(shift))
            rows_ref[pl.ds(k, 1), :] = pool_ref[pl.ds(row, 1), :]
        start = (node & jnp.int32((1 << shift) - 1)) * jnp.int32(rec)
        return rows_ref[...], lane - start

    def pick(rows, off, at):
        """Per-sublane value at record offset ``at`` ((8, 1) or scalar)."""
        return jnp.sum(
            jnp.where(off == at, rows, zero), axis=1, keepdims=True,
            dtype=jnp.int32,
        )

    def is_leaf(rows, off):
        return pick(rows, off, jnp.int32(3 * b)) > zero

    def group(g, carry):
        r0 = pl.multiple_of(g * SUBLANES, SUBLANES)
        q = q_ref[pl.ds(r0, SUBLANES), :]  # (8, 1)

        def walking(c):
            lvl, _, rows, off = c
            return (lvl < jnp.int32(max_height)) & (
                jnp.min(is_leaf(rows, off).astype(jnp.int32)) == zero
            )

        def level(c):
            lvl, node, rows, off = c
            router = (off >= zero) & (off < jnp.int32(b - 1)) & (rows <= q)
            idx = jnp.sum(
                router.astype(jnp.int32), axis=1, keepdims=True, dtype=jnp.int32
            )
            child = pick(rows, off, jnp.int32(b) + idx)
            node = jnp.where(is_leaf(rows, off), node, child)
            rows, off = fetch(node)
            return lvl + jnp.int32(1), node, rows, off

        node = start_ref[pl.ds(r0, SUBLANES), :]
        rows, off = fetch(node)
        _, node, rows, off = jax.lax.while_loop(
            walking, level, (zero, node, rows, off)
        )
        # fused unsorted-leaf probe on the final tile: first matching slot
        eq = (off >= zero) & (off < jnp.int32(b)) & (rows == q)
        slot = jnp.min(jnp.where(eq, off, jnp.int32(b)), axis=1, keepdims=True)
        found = slot < jnp.int32(b)
        val = pick(rows, off, jnp.int32(2 * b) + slot)
        leaf_ref[pl.ds(r0, SUBLANES), :] = node
        found_ref[pl.ds(r0, SUBLANES), :] = found.astype(jnp.int32)
        slot_ref[pl.ds(r0, SUBLANES), :] = jnp.where(found, slot, zero)
        val_ref[pl.ds(r0, SUBLANES), :] = jnp.where(found, val, zero)
        return carry

    n_groups = q_ref.shape[0] // SUBLANES
    jax.lax.fori_loop(zero, jnp.int32(n_groups), group, zero)


# VMEM left for everything but the resident pool (query/output blocks,
# the row tile, compiler scratch).
_VMEM_HEADROOM = 8 << 20


@functools.partial(
    jax.jit, static_argnames=("max_height", "block_b", "interpret")
)
def descend_probe_pallas(
    pool_keys: jax.Array,  # (N, b) int32, EMPTY = INT32_MAX
    pool_vals: jax.Array,  # (N, b) int32
    children: jax.Array,  # (N, b) int32
    is_leaf: jax.Array,  # (N,) bool
    root,  # int32 scalar
    queries: jax.Array,  # (B,) int32
    *,
    max_height: int,
    block_b: int = 512,
    interpret: Optional[bool] = None,
):
    """Returns ``(leaf_ids (B,), found (B,), slot (B,), val (B,))`` —
    exactly the jnp ``descend_probe_ref`` semantics on int32 keys (``val``
    raw int32; the dispatcher applies the NOTFOUND sentinel)."""
    bsz = queries.shape[0]
    n, b = pool_keys.shape
    m = max(SUBLANES, 1 << (max(bsz, 1) - 1).bit_length())  # pow2 pad
    block = min(block_b, m)
    m = m if m % block == 0 else m + (-m) % block
    if m != bsz:
        queries = jnp.pad(queries, (0, m - bsz), constant_values=INT32_MAX)
    start = jnp.full((m, 1), root, jnp.int32)
    pool = pack_pool(pool_keys, pool_vals, children, is_leaf)
    blk = pl.BlockSpec((block, 1), lambda i: (i, jnp.int32(0)))
    leaf, found, slot, val = pl.pallas_call(
        functools.partial(
            _descend_probe_kernel, b=b, n_nodes=n, max_height=max_height
        ),
        grid=(m // block,),
        in_specs=[
            # the whole packed pool, one buffer, resident across grid steps
            pl.BlockSpec(
                pool.shape, lambda i: (jnp.int32(0), jnp.int32(0)),
                pipeline_mode=pl.Buffered(1),
            ),
            blk,
            blk,
        ],
        out_specs=[blk for _ in range(4)],
        out_shape=[jax.ShapeDtypeStruct((m, 1), jnp.int32) for _ in range(4)],
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=pool_vmem_bytes(n, b) + _VMEM_HEADROOM
        ),
        interpret=interpret_mode(interpret),
    )(pool, start, queries.astype(jnp.int32)[:, None])
    return (
        leaf[:bsz, 0],
        found[:bsz, 0].astype(bool),
        slot[:bsz, 0],
        val[:bsz, 0],
    )


# ----------------------------------------------------------------------------
# segmented frontier compaction
# ----------------------------------------------------------------------------


def _frontier_compact_kernel(
    cand_ref, valid_ref, frontier_ref, fvalid_ref, total_ref, rank_ref, *, f: int
):
    """One block of 128 queries (lanes) × ``M`` candidates (sublanes):
    exclusive rank by a strictly-lower-triangular 0/1 matmul, then a
    masked-sum select per output slot."""
    m = cand_ref.shape[0]
    valid = valid_ref[...]  # (M, 128) int32 0/1
    # rank[i] = #valid candidates before i: L @ valid with L[i, j] = (j < i).
    # 0/1 operands are exact in bf16 and the MXU accumulates in f32, exact
    # for counts far beyond any frontier width.
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
        < jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
    ).astype(jnp.bfloat16)
    rank_ref[...] = jnp.dot(
        tri, valid.astype(jnp.bfloat16), preferred_element_type=jnp.float32
    ).astype(jnp.int32)
    zero = jnp.int32(0)

    def select(c, carry):
        sel = (valid_ref[...] > zero) & (rank_ref[...] == c)
        frontier_ref[pl.ds(c, 1), :] = jnp.sum(
            jnp.where(sel, cand_ref[...], zero), axis=0, keepdims=True,
            dtype=jnp.int32,
        )
        fvalid_ref[pl.ds(c, 1), :] = jnp.sum(
            sel.astype(jnp.int32), axis=0, keepdims=True, dtype=jnp.int32
        )
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(f), select, jnp.int32(0))
    total_ref[...] = jnp.sum(valid, axis=0, keepdims=True, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("f", "interpret"))
def frontier_compact_pallas(
    cand: jax.Array,  # (B, M) int32 candidate ids
    valid: jax.Array,  # (B, M) bool
    *,
    f: int,
    interpret: Optional[bool] = None,
):
    """Returns ``(frontier (B, f) int32, valid (B, f) bool, total (B,))``:
    row-stable compaction of the valid candidates (invalid output slots are
    0 — callers mask them via the returned valid plane)."""
    bsz, m = cand.shape
    b_pad = bsz + (-bsz) % LANES
    m_pad = m + (-m) % LANES  # MXU-aligned contraction; pads are invalid
    pad = ((0, b_pad - bsz), (0, m_pad - m))
    cand_t = jnp.pad(cand.astype(jnp.int32), pad).T
    valid_t = jnp.pad(valid.astype(jnp.int32), pad).T
    col = lambda rows: pl.BlockSpec((rows, LANES), lambda i: (jnp.int32(0), i))
    frontier, fvalid, total = pl.pallas_call(
        functools.partial(_frontier_compact_kernel, f=f),
        grid=(b_pad // LANES,),
        in_specs=[col(m_pad), col(m_pad)],
        out_specs=[col(f), col(f), col(1)],
        out_shape=[
            jax.ShapeDtypeStruct((f, b_pad), jnp.int32),  # frontier
            jax.ShapeDtypeStruct((f, b_pad), jnp.int32),  # valid
            jax.ShapeDtypeStruct((1, b_pad), jnp.int32),  # total
        ],
        scratch_shapes=[pltpu.VMEM((m_pad, LANES), jnp.int32)],  # ranks
        interpret=interpret_mode(interpret),
    )(cand_t, valid_t)
    return frontier.T[:bsz], fvalid.T[:bsz].astype(bool), total[0, :bsz]
