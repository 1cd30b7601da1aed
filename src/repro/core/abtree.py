"""Batched OCC-ABtree / Elim-ABtree on an array-backed node pool.

This is the TPU-native adaptation of the paper's concurrent relaxed
(a,b)-tree (see DESIGN.md §2/§4).  Concurrency is expressed as *rounds*: a
round applies a batch of dictionary operations that are all mutually
concurrent; per-key linearization order within a round is arrival order
(any order is legal per the paper's §4 argument — this is the freedom
publishing elimination exploits).

Two modes:

  * ``mode='elim'``   — Elim-ABtree: the elimination combine collapses all
    ops on a key to ≤ 1 physical slot write; eliminated ops compute their
    return values from the published per-key record (the combine), never
    touching tree arrays.
  * ``mode='occ'``    — OCC-ABtree baseline: every op executes physically.
    Duplicate keys force sub-rounds (duplicate-rank r executes in sub-round
    r), each with its own search + leaf write + version bump — mirroring the
    per-op work of the paper's OCC tree under contention.

Structure follows the paper:
  * unsorted leaves: insert writes the first free slot; delete blanks a slot
    (no shifting) — on TPU the probe is a lane-parallel compare (see
    kernels/leaf_probe).
  * per-node version counters (+2 per modifying round; record stamped with
    the odd intermediate) — used by the durable layer and by cross-round
    optimistic readers (serving).
  * per-leaf ElimRecord ⟨key, val, ver, op⟩ — the publishing record of the
    last modification, exposed to other engine replicas / later rounds.
  * relaxed rebalancing as independent-set *waves* of the Larsen–Fagerberg
    sub-operations (split / merge / distribute), each wave touching at most
    one violating child per parent.

NOTE on the paper's Figure 9 pseudocode: the distribute/merge branch
condition there is inverted relative to Larsen–Fagerberg (distributing two
nodes whose total is ≤ 2·MIN would leave one still underfull).  We implement
the standard relaxed-(a,b) rule: merge when total ≤ b, else distribute
evenly (each side ≥ a since total > b ≥ 2a).  See DESIGN.md §7.

This module holds the tree *state* and the device-level phase primitives
(descent, probe, net-op apply, structural waves, frontier expansion).
Round execution — lane classification, the ordered phase pipeline, and the
host orchestration of structural waves — lives in ``core/rounds.py``; the
``ABTree`` entry points below are thin wrappers over that engine.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import elimination as elim
from repro.kernels.tree_descend.ops import frontier_compact
from repro.kernels.tree_descend.ref import descend_ref, probe_ref
from repro.obs.metrics import (
    MetricsRegistry,
    RegistryBackedCounters,
    engine_collector,
)
from repro.obs.recorder import Recorder
from repro.obs.tracer import NULL_TRACER, pull

# ----------------------------------------------------------------------------
# Constants & state
# ----------------------------------------------------------------------------

KEY_DTYPE = jnp.int64
VAL_DTYPE = jnp.int64
EMPTY = jnp.iinfo(jnp.int64).max  # free-slot / unused-router sentinel (sorts last)
NOTFOUND = jnp.iinfo(jnp.int64).min  # ⊥ return value
NULL = jnp.int32(-1)  # null node id

OP_NOP = int(elim.OP_NOP)
OP_FIND = int(elim.OP_FIND)
OP_INSERT = int(elim.OP_INSERT)
OP_DELETE = int(elim.OP_DELETE)
# range scan [lo, lo+span) — served by the round engine's scan phase, which
# linearizes it before the round's net writes; never reaches the combine.
OP_RANGE = int(elim.OP_RANGE)

INT_MAX = np.int32(2**31 - 1)
KEY_MIN = jnp.iinfo(jnp.int64).min  # -inf bound for leftmost child ranges


class ScanConflictError(RuntimeError):
    """An optimistic range scan failed version validation repeatedly
    (concurrent update rounds kept touching the scanned subtree)."""


class TreeConfig(NamedTuple):
    capacity: int = 4096  # node pool size
    b: int = 8  # max keys per leaf == max children per internal
    a: int = 2  # min keys per leaf == min children per internal (a ≤ b/2)
    max_height: int = 24  # static bound for descent loops


class TreeStats(NamedTuple):
    slot_writes: jax.Array  # physical leaf slot writes (keys or vals)
    struct_ops: jax.Array  # split/merge/distribute sub-operations
    searches: jax.Array  # root-to-leaf descents (per lane)
    eliminated: jax.Array  # update ops eliminated (write avoided)
    rounds: jax.Array
    subrounds: jax.Array  # OCC sub-rounds executed
    scans: jax.Array  # range-scan ops served
    scan_retries: jax.Array  # scan rounds re-run after version conflicts


class TreeState(NamedTuple):
    # node pool (SoA) ---------------------------------------------------------
    keys: jax.Array  # (N, b) leaf keys (unsorted) | internal routers in [:, :b-1] (sorted)
    vals: jax.Array  # (N, b) leaf values
    children: jax.Array  # (N, b) int32 child ids (internal)
    parent: jax.Array  # (N,) int32
    pidx: jax.Array  # (N,) int32 index of node in parent.children
    is_leaf: jax.Array  # (N,) bool
    size: jax.Array  # (N,) int32: leaf → #keys; internal → #children
    level: jax.Array  # (N,) int32: leaf = 0
    ver: jax.Array  # (N,) int32: even ⇔ quiescent (paper's version discipline)
    alloc: jax.Array  # (N,) bool
    # per-leaf ElimRecord (paper §4.1) ---------------------------------------
    rec_key: jax.Array  # (N,)
    rec_val: jax.Array  # (N,)
    rec_ver: jax.Array  # (N,) int32 (odd when valid)
    rec_op: jax.Array  # (N,) int32
    # tree scalars ------------------------------------------------------------
    root: jax.Array  # int32
    height: jax.Array  # int32 (#levels; 1 = single leaf)
    dirty: jax.Array  # (N,) bool — touched since last durable commit
    stats: TreeStats


# Pool-row fill values per TreeState field (scalars root/height/stats are
# absent: they pass through pool growth untouched).  Shared by ABTree._grow
# (node axis 0) and ABForest._grow (node axis 1 of the stacked state).
_GROW_FILL = dict(
    keys=EMPTY, vals=0, children=NULL, parent=NULL, pidx=0, is_leaf=True,
    size=0, level=0, ver=0, alloc=False, rec_key=EMPTY, rec_val=0,
    rec_ver=0, rec_op=0, dirty=False,
)


def grow_pool(state: TreeState, pad_n: int, axis: int = 0) -> TreeState:
    """Append ``pad_n`` freshly-initialized node rows along ``axis`` of
    every per-node array (scalars untouched).  The old scratch row becomes
    an ordinary free node (it is kept all-initial by the masked-scatter
    discipline) and the new last row takes over as scratch."""
    out = {}
    for name, val in state._asdict().items():
        if name in _GROW_FILL:
            pad_shape = val.shape[:axis] + (pad_n,) + val.shape[axis + 1 :]
            out[name] = jnp.concatenate(
                [val, jnp.full(pad_shape, _GROW_FILL[name], val.dtype)], axis=axis
            )
        else:
            out[name] = val
    return TreeState(**out)


def wave_width(capacity: int) -> int:
    """Pad width of a structural (split / underfull) wave: 64 nodes, or
    1/256 of the pool once that is larger.  Every wave is a whole-pool
    program plus whole-pool pulls to the host, so at deployment size a
    round's thousands of splits must take a handful of waves, not
    hundreds; small pools keep 64.  The width changes only with the
    capacity, which recompiles every phase anyway, so it adds no program
    variants."""
    return max(64, capacity >> 8)


def make_tree(cfg: TreeConfig) -> TreeState:
    # Pool has capacity+1 rows: the last row is a write-off SCRATCH row that
    # absorbs all masked-out scatter lanes.  Routing inactive lanes to a
    # dedicated row (instead of row 0) avoids duplicate-index scatter races
    # with real writes (XLA scatter order for duplicates is unspecified).
    n, b = cfg.capacity + 1, cfg.b
    z64 = functools.partial(jnp.full, dtype=KEY_DTYPE)
    zi = functools.partial(jnp.zeros, dtype=jnp.int32)
    return TreeState(
        keys=z64((n, b), EMPTY),
        vals=z64((n, b), 0),
        children=jnp.full((n, b), NULL, jnp.int32),
        parent=jnp.full((n,), NULL, jnp.int32),
        pidx=zi((n,)),
        is_leaf=jnp.ones((n,), bool),
        size=zi((n,)),
        level=zi((n,)),
        ver=zi((n,)),
        alloc=jnp.zeros((n,), bool).at[0].set(True),  # node 0 = initial root leaf
        rec_key=z64((n,), EMPTY),
        rec_val=z64((n,), 0),
        rec_ver=zi((n,)),
        rec_op=zi((n,)),
        root=jnp.int32(0),
        height=jnp.int32(1),
        dirty=jnp.zeros((n,), bool).at[0].set(True),
        stats=TreeStats(*([jnp.int64(0)] * 8)),
    )


# ----------------------------------------------------------------------------
# Phase 1: vectorized descent + probe.  The implementations live in
# kernels/tree_descend/ref.py (the pure-jnp oracles of the fused Pallas
# descent+probe kernel); these wrappers bind them to the TreeState layout so
# the host path and the kernel oracle can never drift.
# ----------------------------------------------------------------------------


def descend(state: TreeState, keys: jax.Array, cfg: TreeConfig) -> jax.Array:
    """Root-to-leaf search for a batch of keys → leaf ids.  The per-level
    child choice mirrors the paper's ``search``: follow ptrs[#routers ≤ key]."""
    return descend_ref(
        state.keys, state.children, state.is_leaf, state.root, keys,
        max_height=cfg.max_height,
    )


def probe(state: TreeState, leaf_ids: jax.Array, keys: jax.Array):
    """Unsorted-leaf probe: lane-parallel compare across the b slots."""
    return probe_ref(state.keys, state.vals, leaf_ids, keys, notfound=NOTFOUND)


# ----------------------------------------------------------------------------
# Phase 3: in-place apply of net ops (the hot path the paper optimizes)
# ----------------------------------------------------------------------------


class ApplyOut(NamedTuple):
    state: TreeState
    deferred: jax.Array  # (B,) bool — net inserts that did not fit (leaf full)


def _segment_starts(x: jax.Array) -> jax.Array:
    return jnp.concatenate([jnp.ones((1,), bool), x[1:] != x[:-1]])


def _segmented_rank(mask: jax.Array, seg_id: jax.Array) -> jax.Array:
    """0-based rank of each True within its segment (junk elsewhere)."""
    c = jnp.cumsum(mask.astype(jnp.int32))
    seg_base = jnp.where(_segment_starts(seg_id), c - mask.astype(jnp.int32), 0)
    seg_base = jax.lax.associative_scan(jnp.maximum, seg_base)
    return c - 1 - seg_base


def apply_net_ops(
    state: TreeState,
    cfg: TreeConfig,
    leaf_ids: jax.Array,  # (B,) leaf per sorted op
    keys_sorted: jax.Array,
    slot_found: jax.Array,  # (B,) slot of key if present
    net_insert: jax.Array,  # (B,) bool (at segment heads)
    net_delete: jax.Array,
    net_overwrite: jax.Array,
    final_val: jax.Array,
    arrival_sorted: jax.Array,  # (B,) original position (for record priority)
) -> ApplyOut:
    """Apply per-key net effects.  All net flags are on distinct keys; keys
    are sorted, so ops on one leaf are contiguous (leaf key ranges partition
    the key space — invariants 1/7 of the paper)."""
    b = cfg.b
    scratch = state.keys.shape[0] - 1  # masked lanes write here (see make_tree)

    # --- deletes: blank the slot (unsorted leaves: no shifting — the paper's
    # fast delete), size -= 1.
    del_rows = jnp.where(net_delete, leaf_ids, scratch)
    del_slots = jnp.where(net_delete, slot_found, 0)
    keys_new = state.keys.at[del_rows, del_slots].set(
        jnp.where(net_delete, EMPTY, state.keys[del_rows, del_slots])
    )
    size_new = state.size.at[del_rows].add(jnp.where(net_delete, -1, 0))

    # --- overwrites: value-only write.
    ow_rows = jnp.where(net_overwrite, leaf_ids, scratch)
    ow_slots = jnp.where(net_overwrite, slot_found, 0)
    vals_new = state.vals.at[ow_rows, ow_slots].set(
        jnp.where(net_overwrite, final_val, state.vals[ow_rows, ow_slots])
    )

    # --- inserts: rank-th free slot of the leaf, ranking against the
    # *post-delete* keys (deletes in this round free slots first).
    ins = net_insert
    rank = _segmented_rank(ins, leaf_ids)
    leaf_rows = keys_new[leaf_ids]  # (B, b)
    free = leaf_rows == EMPTY
    # argsort(stable) of ~free puts free slots first, ascending slot order.
    free_order = jnp.argsort(~free, axis=1, stable=True).astype(jnp.int32)
    n_free = jnp.sum(free, axis=1).astype(jnp.int32)
    fits = ins & (rank < n_free)
    ins_slot = jnp.take_along_axis(
        free_order, jnp.clip(rank, 0, b - 1)[:, None], axis=1
    )[:, 0]

    ins_rows = jnp.where(fits, leaf_ids, scratch)
    ins_slots = jnp.where(fits, ins_slot, 0)
    keys_new = keys_new.at[ins_rows, ins_slots].set(
        jnp.where(fits, keys_sorted, keys_new[ins_rows, ins_slots])
    )
    vals_new = vals_new.at[ins_rows, ins_slots].set(
        jnp.where(fits, final_val, vals_new[ins_rows, ins_slots])
    )
    size_new = size_new.at[ins_rows].add(jnp.where(fits, 1, 0))

    deferred = ins & ~fits

    # --- version bump: +2 per modified leaf (even ⇔ quiescent, §3.1).
    modified = net_delete | net_overwrite | fits
    mod_rows = jnp.where(modified, leaf_ids, scratch)
    ver_bump = jnp.zeros_like(state.ver).at[mod_rows].max(
        jnp.where(modified, 1, 0).astype(jnp.int32)
    )
    ver_bump = ver_bump.at[scratch].set(0)
    ver_new = state.ver + 2 * ver_bump
    dirty_new = state.dirty | (ver_bump > 0)

    # --- publish ElimRecord: the net op with max arrival in each modified
    # leaf is the leaf's last modifier; rec_ver = new_ver - 1 (odd), §4.1.
    prio = jnp.where(modified, arrival_sorted.astype(jnp.int32), -1)
    best = jnp.full((state.keys.shape[0],), -1, jnp.int32).at[mod_rows].max(prio)
    is_best = modified & (prio == best[leaf_ids])
    rb_rows = jnp.where(is_best, leaf_ids, scratch)

    def publish(arr, values):
        return arr.at[rb_rows].set(jnp.where(is_best, values, arr[rb_rows]))

    rec_key = publish(state.rec_key, keys_sorted)
    rec_val = publish(state.rec_val, final_val)
    rec_op = publish(
        state.rec_op, jnp.where(net_delete, OP_DELETE, OP_INSERT).astype(jnp.int32)
    )
    rec_ver = publish(state.rec_ver, ver_new[leaf_ids] - 1)

    n_writes = (
        jnp.sum(net_delete) + jnp.sum(net_overwrite) + 2 * jnp.sum(fits)
    ).astype(jnp.int64)
    stats = state.stats._replace(slot_writes=state.stats.slot_writes + n_writes)

    return ApplyOut(
        state=state._replace(
            keys=keys_new,
            vals=vals_new,
            size=size_new,
            ver=ver_new,
            dirty=dirty_new,
            rec_key=rec_key,
            rec_val=rec_val,
            rec_op=rec_op,
            rec_ver=rec_ver,
            stats=stats,
        ),
        deferred=deferred,
    )


# ----------------------------------------------------------------------------
# Structural waves (relaxed-rebalancing sub-operations, batched)
# ----------------------------------------------------------------------------


def _alloc_ids(state: TreeState, k: int) -> jax.Array:
    """ids of k free nodes (deterministic: lowest ids first).  The last
    pool row (scratch) is never handed out; callers keep ≥ k rows free
    (``_ensure_capacity``), and any shortfall maps to scratch.  The j-th
    free id is where the running free count first reaches j + 1: one
    cumsum over the pool and k binary searches.  (A whole-pool argsort
    here cost every split wave a sort of the pool in compile and run time,
    and ``nonzero`` a scatter of the whole pool.)"""
    scratch = state.alloc.shape[0] - 1
    n_free = jnp.cumsum(~state.alloc[:-1], dtype=jnp.int32)
    ids = jnp.searchsorted(n_free, jnp.arange(1, k + 1, dtype=jnp.int32))
    return jnp.where(ids < scratch, ids, scratch).astype(jnp.int32)


def _refresh_child_links(state: TreeState, parents: jax.Array, cfg: TreeConfig) -> TreeState:
    """Recompute parent/pidx for all children of the given (allocated,
    internal) parent ids.  Safe to call with junk ids: guarded by alloc &
    ~is_leaf & size."""
    ch = state.children[parents]  # (W, b)
    ok = (
        state.alloc[parents][:, None]
        & ~state.is_leaf[parents][:, None]
        & (jnp.arange(cfg.b)[None, :] < state.size[parents][:, None])
        & (ch >= 0)
    )
    scratch = state.keys.shape[0] - 1
    rows = jnp.where(ok, ch, scratch).reshape(-1)
    okf = ok.reshape(-1)
    jj = jnp.broadcast_to(jnp.arange(cfg.b, dtype=jnp.int32)[None, :], ch.shape).reshape(-1)
    pp = jnp.broadcast_to(parents[:, None], ch.shape).reshape(-1).astype(jnp.int32)
    pidx_new = state.pidx.at[rows].set(jnp.where(okf, jj, state.pidx[rows]))
    parent_new = state.parent.at[rows].set(jnp.where(okf, pp, state.parent[rows]))
    return state._replace(pidx=pidx_new, parent=parent_new)


def split_wave(
    state: TreeState, cfg: TreeConfig, node_ids: jax.Array, active: jax.Array
) -> TreeState:
    """One wave of split sub-operations.  Preconditions (caller-enforced):
    every active node is full (size == b); its parent is NOT full (or the
    node is the root); at most one active node per parent.

    Batched analog of the paper's splitting insert + fixTagged chain: we
    split eagerly instead of publishing a TaggedInternal, because wave
    execution is already atomic w.r.t. readers (no intra-round readers);
    tagging existed only to keep each lock-protected step small (DESIGN §7).
    """
    w = node_ids.shape[0]
    b = cfg.b
    scratch = state.keys.shape[0] - 1
    node_ids = jnp.where(active, node_ids, scratch)

    new_ids = _alloc_ids(state, 2 * w)
    right_ids = jnp.where(active, new_ids[:w], scratch)
    is_root = active & (state.parent[node_ids] == NULL)
    newroot_ids = jnp.where(is_root, new_ids[w:], scratch)

    leaf = state.is_leaf[node_ids]  # (W,)
    lh = (b + 1) // 2
    rh = b - lh
    iota = jnp.arange(b)[None, :]

    # ---- sort node contents (leaves are unsorted; internals already sorted).
    krows = state.keys[node_ids]
    vrows = state.vals[node_ids]
    crows = state.children[node_ids]
    order = jnp.argsort(krows, axis=1, stable=True).astype(jnp.int32)
    order = jnp.where(leaf[:, None], order, iota.astype(jnp.int32))
    ks = jnp.take_along_axis(krows, order, axis=1)
    vs = jnp.take_along_axis(vrows, order, axis=1)

    # ---- leaves: left ks[:lh], right ks[lh:]; router = ks[lh] (= min right).
    leaf_lk = jnp.where(iota < lh, ks, EMPTY)
    leaf_rk = jnp.where(iota < rh, jnp.roll(ks, -lh, axis=1), EMPTY)
    leaf_lv = vs
    leaf_rv = jnp.roll(vs, -lh, axis=1)

    # ---- internals: left lh children + lh-1 routers; right rh children +
    # rh-1 routers; router krows[lh-1] moves up.
    int_lk = jnp.where(iota < lh - 1, krows, EMPTY)
    int_rk = jnp.where(iota < rh - 1, jnp.roll(krows, -lh, axis=1), EMPTY)
    int_lc = jnp.where(iota < lh, crows, NULL)
    int_rc = jnp.where(iota < rh, jnp.roll(crows, -lh, axis=1), NULL)

    router = jnp.where(leaf, ks[:, lh], krows[:, lh - 1])

    def masked_set(arr, rows, values, act):
        cur = arr[rows]
        m = act[:, None] if values.ndim == 2 else act
        return arr.at[rows].set(jnp.where(m, values, cur))

    keys_new = masked_set(state.keys, node_ids, jnp.where(leaf[:, None], leaf_lk, int_lk), active)
    keys_new = masked_set(keys_new, right_ids, jnp.where(leaf[:, None], leaf_rk, int_rk), active)
    vals_new = masked_set(state.vals, node_ids, leaf_lv, active & leaf)
    vals_new = masked_set(vals_new, right_ids, leaf_rv, active & leaf)
    ch_new = masked_set(state.children, node_ids, int_lc, active & ~leaf)
    ch_new = masked_set(ch_new, right_ids, int_rc, active & ~leaf)

    size_new = state.size.at[node_ids].set(jnp.where(active, lh, state.size[node_ids]))
    size_new = size_new.at[right_ids].set(jnp.where(active, rh, size_new[right_ids]))
    isleaf_new = state.is_leaf.at[right_ids].set(
        jnp.where(active, leaf, state.is_leaf[right_ids])
    )
    level_new = state.level.at[right_ids].set(
        jnp.where(active, state.level[node_ids], state.level[right_ids])
    )
    alloc_new = state.alloc.at[right_ids].set(state.alloc[right_ids] | active)
    ver_new = state.ver.at[node_ids].add(jnp.where(active, 2, 0))

    state = state._replace(
        keys=keys_new, vals=vals_new, children=ch_new, size=size_new,
        is_leaf=isleaf_new, level=level_new, alloc=alloc_new, ver=ver_new,
    )

    # ---- grow root where needed: fresh internal with single child = node.
    state = state._replace(
        keys=state.keys.at[newroot_ids].set(
            jnp.where(is_root[:, None], jnp.full((w, b), EMPTY, KEY_DTYPE), state.keys[newroot_ids])
        ),
        children=state.children.at[newroot_ids, 0].set(
            jnp.where(is_root, node_ids, state.children[newroot_ids, 0])
        ),
        size=state.size.at[newroot_ids].set(jnp.where(is_root, 1, state.size[newroot_ids])),
        is_leaf=state.is_leaf.at[newroot_ids].set(
            state.is_leaf[newroot_ids] & ~is_root
        ),
        level=state.level.at[newroot_ids].set(
            jnp.where(is_root, state.level[node_ids] + 1, state.level[newroot_ids])
        ),
        alloc=state.alloc.at[newroot_ids].set(state.alloc[newroot_ids] | is_root),
        parent=state.parent.at[node_ids].set(
            jnp.where(is_root, newroot_ids, state.parent[node_ids])
        ),
        pidx=state.pidx.at[node_ids].set(jnp.where(is_root, 0, state.pidx[node_ids])),
    )
    any_root = jnp.any(is_root)
    root_new = jnp.where(
        any_root, jnp.max(jnp.where(is_root, newroot_ids, -1)), state.root
    ).astype(jnp.int32)
    height_new = state.height + any_root.astype(jnp.int32)

    # ---- link right sibling into parent: insert router at slot `at`,
    # child at `at+1` (shift tail right by one).
    pids = jnp.where(is_root, newroot_ids, state.parent[node_ids])
    pids = jnp.where(active, pids, scratch)
    at = state.pidx[node_ids][:, None]  # (W,1)
    pk = state.keys[pids]
    pc = state.children[pids]
    shifted_k = jnp.where(iota > at, jnp.roll(pk, 1, axis=1), pk)
    shifted_k = jnp.where(iota == at, router[:, None], shifted_k)
    shifted_c = jnp.where(iota > at + 1, jnp.roll(pc, 1, axis=1), pc)
    shifted_c = jnp.where(iota == at + 1, right_ids[:, None], shifted_c)

    keys_new = state.keys.at[pids].set(jnp.where(active[:, None], shifted_k, state.keys[pids]))
    ch_new = state.children.at[pids].set(jnp.where(active[:, None], shifted_c, state.children[pids]))
    size_new = state.size.at[pids].add(jnp.where(active, 1, 0))

    dirty_new = state.dirty
    for rows, m in ((node_ids, active), (right_ids, active), (pids, active), (newroot_ids, is_root)):
        r = jnp.where(m, rows, scratch)
        dirty_new = dirty_new.at[r].set(dirty_new[r] | m)

    stats = state.stats._replace(
        struct_ops=state.stats.struct_ops + jnp.sum(active).astype(jnp.int64)
    )
    state = state._replace(
        keys=keys_new, children=ch_new, size=size_new, root=root_new,
        height=height_new, dirty=dirty_new, stats=stats,
    )
    # fix child links of: parents (children shifted), the split node and its
    # new right sibling (internal splits reassign grandchildren).
    state = _refresh_child_links(state, pids, cfg)
    state = _refresh_child_links(state, node_ids, cfg)
    state = _refresh_child_links(state, right_ids, cfg)
    return state


def underfull_wave(
    state: TreeState, cfg: TreeConfig, node_ids: jax.Array, active: jax.Array
) -> TreeState:
    """One wave of merge/distribute sub-operations (paper's fixUnderfull).
    Preconditions (caller-enforced): each active node is underfull, not the
    root, its parent has ≥ 2 children, ≤ 1 active node per parent."""
    w = node_ids.shape[0]
    b = cfg.b
    scratch = state.keys.shape[0] - 1
    node_ids = jnp.where(active, node_ids, scratch)
    parents = jnp.where(active, state.parent[node_ids], scratch)
    at = jnp.clip(state.pidx[node_ids], 0, b - 1)
    sib_at = jnp.where(at == 0, 1, at - 1)  # paper: right sibling iff leftmost
    sibs = state.children[parents, sib_at]
    sibs = jnp.where(active, sibs, scratch)
    left_at = jnp.minimum(at, sib_at)
    left_is_node = at < sib_at
    lid = jnp.where(active, jnp.where(left_is_node, node_ids, sibs), scratch)
    rid = jnp.where(active, jnp.where(left_is_node, sibs, node_ids), scratch)

    leaf = state.is_leaf[node_ids]
    lsz = state.size[lid]
    rsz = state.size[rid]
    total = lsz + rsz
    sep = state.keys[parents, left_at]  # router between the pair

    do_merge = active & (total <= b)
    do_dist = active & (total > b)

    # ---- build merged content, width 2b ------------------------------------
    lk, lv, lc = state.keys[lid], state.vals[lid], state.children[lid]
    rk, rv, rc = state.keys[rid], state.vals[rid], state.children[rid]
    j2 = jnp.arange(2 * b)[None, :]

    # Leaves: concat + stable sort (EMPTY last) compacts `total` sorted keys.
    cat_k = jnp.concatenate([lk, rk], axis=1)
    cat_v = jnp.concatenate([lv, rv], axis=1)
    ordr = jnp.argsort(cat_k, axis=1, stable=True).astype(jnp.int32)
    leaf_mk = jnp.take_along_axis(cat_k, ordr, axis=1)
    leaf_mv = jnp.take_along_axis(cat_v, ordr, axis=1)

    # Internals: children = lc[0:lsz] ++ rc[0:rsz];
    #            routers  = lk[0:lsz-1] ++ [sep] ++ rk[0:rsz-1].
    r_idx = jnp.clip(j2 - lsz[:, None], 0, b - 1)
    lc2 = jnp.concatenate([lc, jnp.full_like(lc, NULL)], axis=1)
    lk2 = jnp.concatenate([lk, jnp.full_like(lk, EMPTY)], axis=1)
    int_mc = jnp.where(j2 < lsz[:, None], lc2, jnp.take_along_axis(rc, r_idx, axis=1))
    int_mc = jnp.where(j2 < total[:, None], int_mc, NULL)
    int_mk = jnp.where(
        j2 < lsz[:, None] - 1,
        lk2,
        jnp.where(
            j2 == lsz[:, None] - 1, sep[:, None], jnp.take_along_axis(rk, r_idx, axis=1)
        ),
    )
    int_mk = jnp.where(j2 < total[:, None] - 1, int_mk, EMPTY)

    merged_k = jnp.where(leaf[:, None], leaf_mk, int_mk)  # (W, 2b)
    merged_v = leaf_mv
    merged_c = int_mc

    def sel(act):
        return act[:, None]

    # ---- MERGE: all content into lid; drop rid + separator from parent -----
    keys_new = state.keys.at[lid].set(jnp.where(sel(do_merge), merged_k[:, :b], state.keys[lid]))
    vals_new = state.vals.at[lid].set(jnp.where(sel(do_merge & leaf), merged_v[:, :b], state.vals[lid]))
    ch_new = state.children.at[lid].set(
        jnp.where(sel(do_merge & ~leaf), merged_c[:, :b], state.children[lid])
    )
    size_new = state.size.at[lid].set(jnp.where(do_merge, total, state.size[lid]))
    ver_new = state.ver.at[lid].add(jnp.where(do_merge, 2, 0))
    # free rid (the paper marks unlinked nodes; we deallocate post-wave).
    alloc_new = state.alloc.at[rid].set(state.alloc[rid] & ~do_merge)
    b_iota = jnp.arange(b)[None, :]
    keys_new = keys_new.at[rid].set(
        jnp.where(sel(do_merge), jnp.full((w, b), EMPTY, KEY_DTYPE), keys_new[rid])
    )
    size_new = size_new.at[rid].set(jnp.where(do_merge, 0, size_new[rid]))

    # parent: remove router at left_at and child at max(at, sib_at).
    rm_child = jnp.maximum(at, sib_at)
    pk = state.keys[parents]
    pc = state.children[parents]
    pk_shift = jnp.where(b_iota >= left_at[:, None], jnp.roll(pk, -1, axis=1), pk)
    pk_shift = pk_shift.at[:, b - 1].set(EMPTY)
    pc_shift = jnp.where(b_iota >= rm_child[:, None], jnp.roll(pc, -1, axis=1), pc)
    pc_shift = pc_shift.at[:, b - 1].set(NULL)
    keys_new = keys_new.at[parents].set(jnp.where(sel(do_merge), pk_shift, keys_new[parents]))
    ch_new = ch_new.at[parents].set(jnp.where(sel(do_merge), pc_shift, ch_new[parents]))
    size_new = size_new.at[parents].add(jnp.where(do_merge, -1, 0))

    # ---- DISTRIBUTE: split merged content evenly; new separator up ---------
    ln = (total + 1) // 2
    rn = total - ln
    shift_k = jnp.take_along_axis(merged_k, jnp.clip(j2 + ln[:, None], 0, 2 * b - 1), axis=1)
    shift_v = jnp.take_along_axis(merged_v, jnp.clip(j2 + ln[:, None], 0, 2 * b - 1), axis=1)
    shift_c = jnp.take_along_axis(merged_c, jnp.clip(j2 + ln[:, None], 0, 2 * b - 1), axis=1)

    # leaves: left ln keys, right rn keys; router = merged_k[ln].
    dl_k = jnp.where(j2 < ln[:, None], merged_k, EMPTY)[:, :b]
    dr_k = jnp.where(j2 < rn[:, None], shift_k, EMPTY)[:, :b]
    dl_v = merged_v[:, :b]
    dr_v = shift_v[:, :b]
    router_leaf = jnp.take_along_axis(merged_k, jnp.clip(ln, 0, 2 * b - 1)[:, None], axis=1)[:, 0]
    # internals: left ln children (ln-1 routers); router merged_k[ln-1] up;
    # right rn children (rn-1 routers) starting at child index ln.
    di_lk = jnp.where(j2 < ln[:, None] - 1, merged_k, EMPTY)[:, :b]
    di_lc = jnp.where(j2 < ln[:, None], merged_c, NULL)[:, :b]
    di_rk = jnp.where(j2 < rn[:, None] - 1, shift_k, EMPTY)[:, :b]
    di_rc = jnp.where(j2 < rn[:, None], shift_c, NULL)[:, :b]
    router_int = jnp.take_along_axis(merged_k, jnp.clip(ln - 1, 0, 2 * b - 1)[:, None], axis=1)[:, 0]

    keys_new = keys_new.at[lid].set(
        jnp.where(sel(do_dist), jnp.where(leaf[:, None], dl_k, di_lk), keys_new[lid])
    )
    keys_new = keys_new.at[rid].set(
        jnp.where(sel(do_dist), jnp.where(leaf[:, None], dr_k, di_rk), keys_new[rid])
    )
    vals_new = vals_new.at[lid].set(jnp.where(sel(do_dist & leaf), dl_v, vals_new[lid]))
    vals_new = vals_new.at[rid].set(jnp.where(sel(do_dist & leaf), dr_v, vals_new[rid]))
    ch_new = ch_new.at[lid].set(jnp.where(sel(do_dist & ~leaf), di_lc, ch_new[lid]))
    ch_new = ch_new.at[rid].set(jnp.where(sel(do_dist & ~leaf), di_rc, ch_new[rid]))
    size_new = size_new.at[lid].set(jnp.where(do_dist, ln, size_new[lid]))
    size_new = size_new.at[rid].set(jnp.where(do_dist, rn, size_new[rid]))
    ver_new = ver_new.at[lid].add(jnp.where(do_dist, 2, 0))
    ver_new = ver_new.at[rid].add(jnp.where(do_dist, 2, 0))
    router_new = jnp.where(leaf, router_leaf, router_int)
    keys_new = keys_new.at[parents, left_at].set(
        jnp.where(do_dist, router_new, keys_new[parents, left_at])
    )

    dirty_new = state.dirty
    for rows, m in ((node_ids, active), (sibs, active), (parents, active)):
        r = jnp.where(m, rows, scratch)
        dirty_new = dirty_new.at[r].set(dirty_new[r] | m)

    stats = state.stats._replace(
        struct_ops=state.stats.struct_ops + jnp.sum(active).astype(jnp.int64)
    )
    state = state._replace(
        keys=keys_new, vals=vals_new, children=ch_new, size=size_new,
        alloc=alloc_new, ver=ver_new, dirty=dirty_new, stats=stats,
    )
    # refresh links: parents (child list shifted), lid/rid (grandchildren
    # reassigned for internal merges/distributes).
    state = _refresh_child_links(state, parents, cfg)
    state = _refresh_child_links(state, lid, cfg)
    state = _refresh_child_links(state, rid, cfg)
    return state


def shrink_root(state: TreeState, cfg: TreeConfig) -> TreeState:
    """If the root is internal with a single child, that child becomes the
    root (paper: entry.ptrs[0] replacement in fixUnderfull)."""
    r = state.root
    can = (~state.is_leaf[r]) & (state.size[r] == 1)
    child = state.children[r, 0]
    child = jnp.where(can, child, r)
    return state._replace(
        root=child.astype(jnp.int32),
        height=state.height - can.astype(jnp.int32),
        alloc=state.alloc.at[r].set(state.alloc[r] & ~can),
        size=state.size.at[r].set(jnp.where(can, 0, state.size[r])),
        parent=state.parent.at[child].set(
            jnp.where(can, NULL, state.parent[child])
        ),
        keys=state.keys.at[r].set(
            jnp.where(can, jnp.full((cfg.b,), EMPTY, KEY_DTYPE), state.keys[r])
        ),
        dirty=state.dirty.at[r].set(True),
    )


# ----------------------------------------------------------------------------
# Round outputs (produced by the core/rounds.py engine)
# ----------------------------------------------------------------------------


class ScanOutput(NamedTuple):
    keys: jax.Array  # (B, cap) ascending matches, EMPTY-padded
    vals: jax.Array  # (B, cap) values (0 where key slot is EMPTY)
    count: jax.Array  # (B,) int32 — entries emitted (≤ cap)
    truncated: jax.Array  # (B,) bool — more matches existed than cap


class RoundOutput(NamedTuple):
    results: jax.Array  # (B,) per-op return value (NOTFOUND = ⊥; range: #matches)
    found: jax.Array  # (B,) bool (range lanes: any match)
    # Per-lane scan rows for fused mixed-op rounds, aligned to the batch
    # (non-range rows scan the empty interval).  None when the round had no
    # OP_RANGE lane.
    scan: Optional[ScanOutput] = None


# ----------------------------------------------------------------------------
# Range-scan phase: frontier expansion + lane-parallel gather
# ----------------------------------------------------------------------------


def frontier_expand(
    state: TreeState, cfg: TreeConfig, lo: jax.Array, hi: jax.Array,
    frontier_cap: int, *, narrow: bool = False,
):
    """Expand each query's root into its leaf frontier — the set of leaves
    whose key range intersects ``[lo, hi)`` — level by level, wholly on
    device.  Internal nodes expand to the children whose range intersects
    the interval (the batched form of ``range_query``'s host DFS); leaves
    self-propagate, so after ``max_height`` iterations every frontier slot
    is a leaf.  Per-level compaction of the surviving candidates goes
    through ``kernels/tree_descend``'s segmented cumsum-rank compaction
    (the Pallas kernel under the ``narrow`` gate, the scatter-based jnp
    form otherwise) — no sort network on either path.

    Returns ``(leaves (B,F), cand_keys (B,F·b), cand_vals (B,F·b),
    touched (L,B,F), overflow (B,))``.  ``touched`` records every node id
    whose routers/slots the expansion read (scratch-padded) — the read set
    the optimistic reader validates versions against.  ``overflow`` marks
    queries whose intersecting-node count exceeded F at some level: their
    results may be missing keys and the caller must re-run with a larger
    frontier."""
    bsz = lo.shape[0]
    f, b = frontier_cap, cfg.b
    scratch = state.keys.shape[0] - 1  # empty pseudo-leaf; ver never bumps

    frontier0 = jnp.full((bsz, f), scratch, jnp.int32).at[:, 0].set(state.root)
    valid0 = jnp.zeros((bsz, f), bool).at[:, 0].set(True)
    touched0 = jnp.full((cfg.max_height, bsz, f), scratch, jnp.int32)
    overflow0 = jnp.zeros((bsz,), bool)

    def body(level, carry):
        frontier, valid, touched, overflow = carry
        node = jnp.where(valid, frontier, scratch)
        touched = touched.at[level].set(node)
        leaf = state.is_leaf[node]  # (B,F); scratch is a leaf
        routers = state.keys[node][:, :, : b - 1]  # (B,F,b-1); unused = EMPTY
        sz = state.size[node]  # (B,F)
        # child j covers [clo_j, chi_j): clo_0 = -inf, chi_{sz-1} = +inf
        # (stale routers beyond sz-1 are EMPTY, which acts as +inf).
        pad_lo = jnp.full((bsz, f, 1), KEY_MIN, KEY_DTYPE)
        pad_hi = jnp.full((bsz, f, 1), EMPTY, KEY_DTYPE)
        clo = jnp.concatenate([pad_lo, routers], axis=2)  # (B,F,b)
        chi = jnp.concatenate([routers, pad_hi], axis=2)
        j = jnp.arange(b, dtype=jnp.int32)[None, None, :]
        isect = (
            (j < sz[:, :, None])
            & (chi > lo[:, None, None])
            & (clo < hi[:, None, None])
        )
        expand = (valid & ~leaf)[:, :, None] & isect  # (B,F,b)
        keep = valid & leaf  # leaves ride along unchanged
        cand = jnp.concatenate(
            [
                jnp.where(expand, state.children[node], scratch),
                jnp.where(keep, frontier, scratch)[:, :, None],
            ],
            axis=2,
        ).reshape(bsz, f * (b + 1))
        cand_valid = jnp.concatenate(
            [expand, keep[:, :, None]], axis=2
        ).reshape(bsz, f * (b + 1))
        frontier, valid, of = frontier_compact(
            cand, cand_valid, f, scratch=scratch, use_pallas=narrow
        )
        return frontier, valid, touched, overflow | of

    frontier, valid, touched, overflow = jax.lax.fori_loop(
        0, cfg.max_height, body, (frontier0, valid0, touched0, overflow0)
    )
    leaves = jnp.where(valid, frontier, scratch)
    cand_keys = jnp.where(valid[:, :, None], state.keys[leaves], EMPTY)
    cand_vals = state.vals[leaves]
    return (
        leaves,
        cand_keys.reshape(bsz, f * b),
        cand_vals.reshape(bsz, f * b),
        touched,
        overflow,
    )


def frontier_expand_sharded(
    state: TreeState, cfg: TreeConfig, sid: jax.Array, lo: jax.Array,
    hi: jax.Array, frontier_cap: int, *, narrow: bool = False,
):
    """Flat ragged form of :func:`frontier_expand` over a STACKED ``(S, …)``
    state: lane ``i`` expands inside shard ``sid[i]``, so one launch covers
    every shard's sub-lanes packed side by side — no per-shard row padding.
    Every state access is the per-shard gather generalized to two index
    axes (``state.X[sid[:, None], node]``); the per-level compaction and
    the downstream gather kernels are shard-agnostic and unchanged.

    Returns the same tuple as :func:`frontier_expand`; ``touched`` records
    per-LANE node ids (the caller groups lanes by ``sid`` to build each
    shard's validated read set).  Padding lanes (``lo = hi = EMPTY``)
    expand into nothing past level 0."""
    bsz = lo.shape[0]
    f, b = frontier_cap, cfg.b
    scratch = state.keys.shape[1] - 1  # node axis is 1 on the stacked state
    sid2 = sid[:, None]  # broadcasts against (B, F) node-id blocks

    frontier0 = jnp.full((bsz, f), scratch, jnp.int32).at[:, 0].set(
        state.root[sid]
    )
    valid0 = jnp.zeros((bsz, f), bool).at[:, 0].set(True)
    touched0 = jnp.full((cfg.max_height, bsz, f), scratch, jnp.int32)
    overflow0 = jnp.zeros((bsz,), bool)

    def body(level, carry):
        frontier, valid, touched, overflow = carry
        node = jnp.where(valid, frontier, scratch)
        touched = touched.at[level].set(node)
        leaf = state.is_leaf[sid2, node]  # (B,F); scratch is a leaf
        routers = state.keys[sid2, node][:, :, : b - 1]
        sz = state.size[sid2, node]  # (B,F)
        pad_lo = jnp.full((bsz, f, 1), KEY_MIN, KEY_DTYPE)
        pad_hi = jnp.full((bsz, f, 1), EMPTY, KEY_DTYPE)
        clo = jnp.concatenate([pad_lo, routers], axis=2)  # (B,F,b)
        chi = jnp.concatenate([routers, pad_hi], axis=2)
        j = jnp.arange(b, dtype=jnp.int32)[None, None, :]
        isect = (
            (j < sz[:, :, None])
            & (chi > lo[:, None, None])
            & (clo < hi[:, None, None])
        )
        expand = (valid & ~leaf)[:, :, None] & isect  # (B,F,b)
        keep = valid & leaf
        cand = jnp.concatenate(
            [
                jnp.where(expand, state.children[sid2, node], scratch),
                jnp.where(keep, frontier, scratch)[:, :, None],
            ],
            axis=2,
        ).reshape(bsz, f * (b + 1))
        cand_valid = jnp.concatenate(
            [expand, keep[:, :, None]], axis=2
        ).reshape(bsz, f * (b + 1))
        frontier, valid, of = frontier_compact(
            cand, cand_valid, f, scratch=scratch, use_pallas=narrow
        )
        return frontier, valid, touched, overflow | of

    frontier, valid, touched, overflow = jax.lax.fori_loop(
        0, cfg.max_height, body, (frontier0, valid0, touched0, overflow0)
    )
    leaves = jnp.where(valid, frontier, scratch)
    cand_keys = jnp.where(valid[:, :, None], state.keys[sid2, leaves], EMPTY)
    cand_vals = state.vals[sid2, leaves]
    return (
        leaves,
        cand_keys.reshape(bsz, f * b),
        cand_vals.reshape(bsz, f * b),
        touched,
        overflow,
    )


# ----------------------------------------------------------------------------
# Host-orchestrated tree (thin wrappers over the core/rounds.py engine)
# ----------------------------------------------------------------------------


class ABTree(RegistryBackedCounters):
    """Host-orchestrated batched (a,b)-tree — the S = 1 case of the unified
    sharded round engine.  Every entry point builds a round plan and runs
    the ``core/rounds.py`` (S, wave_w) phase pipeline (the ``stacked``
    property views this tree's state as a one-shard stack); heavy phases
    are jitted and the host loop only sequences structural waves (rare —
    the paper notes splits are infrequent) and reads tiny control scalars."""

    def __init__(
        self, cfg: TreeConfig = TreeConfig(), mode: str = "elim",
        *, narrow_scan: bool = False, narrow: bool = False,
    ):
        assert mode in ("elim", "occ")
        assert 2 <= cfg.a <= cfg.b // 2, "(a,b) requires 2 ≤ a ≤ b/2"
        self.cfg = cfg
        self.mode = mode
        self.state = make_tree(cfg)
        # unified-engine holder protocol: the single tree is a one-shard
        # forest with an unpartitioned key space (see core/rounds.py).
        self.n_shards = 1
        self._splits = np.empty((0,), np.int64)
        self._bounds = [int(KEY_MIN), int(EMPTY)]
        # telemetry: metrics registry (the one store behind the legacy
        # ``_rounds``/``_scans``/``_scan_retries`` counter properties) and
        # the host-side phase tracer (NULL_TRACER = strict no-op; install a
        # ``repro.obs.Tracer()`` to record spans).  The flight recorder is
        # always on (bounded ring; ``Recorder(enabled=False)`` to opt out).
        self.metrics = MetricsRegistry()
        self.metrics.add_collector(engine_collector(self))
        self.tracer = NULL_TRACER
        self.recorder = Recorder()
        self._rounds = 0
        self._scans = 0
        self._scan_retries = 0
        self._scan_active = 0
        # narrow_scan=True is the caller's assertion that every key AND value
        # fits strictly inside int32 (|x| < 2**31 - 1): the round engine's
        # scan phase then routes fused-round gathers through the
        # kernels/range_scan Pallas kernel instead of the int64 jnp ref.
        # Keys at/above 2**31 - 1 would be conflated with the kernel's EMPTY
        # sentinel — leave False for unbounded key spaces (e.g. hash keys).
        #
        # narrow=True extends the same int32 assertion to the whole search
        # path: point-op descents (search / retry / overfull phases) run the
        # fused kernels/tree_descend descent+probe kernel with the pool
        # pinned in VMEM, and scan-phase frontier compaction uses its Pallas
        # form.  Implies narrow_scan.
        self.narrow = narrow
        self.narrow_scan = narrow_scan or narrow
        self._wave_w = wave_width(cfg.capacity)  # structural-wave pad width
        # durable layer hook: OCC durability commits after EVERY sub-round
        # (each sub-round's returns causally follow the previous one — the
        # batched analog of the paper's per-update flush+fence); Elim
        # commits once per round.  See core/durable.py.
        self.subround_hook = None
        # optimistic-reader hook: called between a scan's gather and its
        # version validation.  Models update rounds from other engine
        # replicas interleaving with the scan (tests use it to force the
        # retry/conflict paths); production single-replica use leaves None.
        self.scan_hook = None
        self._scan_frontier = 8  # leaf-frontier pad width (doubles on overflow)

    # -- unified-engine holder protocol ---------------------------------------

    # ``state`` (bare) and ``stacked`` (leading axis 1 — the form every
    # ``core/rounds.py`` phase executes on) are lazy views of one another:
    # each setter just invalidates the other form, and each getter converts
    # only when its form is stale.  A round's phases touch ``stacked``
    # a dozen times; eagerly re-deriving the 25-leaf tree_map on every
    # access cost more host time than the phases' device calls.

    @property
    def state(self) -> TreeState:
        if self._state is None:
            self._state = jax.tree_util.tree_map(lambda x: x[0], self._stacked)
        return self._state

    @state.setter
    def state(self, st: TreeState):
        self._state = st
        self._stacked = None

    @property
    def stacked(self) -> TreeState:
        """This tree's state as a one-shard stack (leading axis 1 on every
        array) — the form every ``core/rounds.py`` phase executes on."""
        if self._stacked is None:
            self._stacked = jax.tree_util.tree_map(lambda x: x[None], self._state)
        return self._stacked

    @stacked.setter
    def stacked(self, st: TreeState):
        self._stacked = st
        self._state = None

    def _maybe_split_shards(self):
        """Shard-overflow policy: the single tree never splits shards."""

    def _maybe_repartition(self):
        """Load rebalancing is a forest concern; S = 1 has one partition."""

    def _note_shard_load(self, counts):
        """Hot-shard accounting is a forest concern; S = 1 has no skew."""

    # -- public API -----------------------------------------------------------

    def apply_round(self, ops, keys, vals=None, *, scan_cap: int = 128) -> RoundOutput:
        """Apply one round of concurrent ops (1-D arrays, equal length).
        Returns per-op results in arrival order.

        Batches may freely mix point ops with OP_RANGE lanes (key = lo,
        val = span → scan ``[lo, lo + span)``): the round engine runs the
        scan phase before the round's net writes, so every range lane
        observes the pre-round dictionary.  Range-lane results land in
        ``RoundOutput.scan`` (≤ ``scan_cap`` smallest matches per lane);
        their ``results`` entry is the match count.  Malformed range lanes
        (negative span, i.e. hi < lo) raise ``ValueError``."""
        from repro.core import rounds

        plan = rounds.build_plan(ops, keys, vals, scan_cap=scan_cap, holder=self)
        return rounds.execute_plan(self, plan)

    def scan_round(self, lo, hi, cap: int = 128, max_retries: int = 8) -> ScanOutput:
        """Apply one round of concurrent range scans: for each query i,
        return the ≤ ``cap`` smallest keys in ``[lo[i], hi[i])`` with their
        values, ascending (``truncated[i]`` marks clipped results).

        Scans follow the paper's optimistic-reader discipline: the gather
        runs against a state snapshot, recording every node it reads; the
        node versions are then re-validated against the live state, and the
        scan re-runs if an interleaved update round bumped any of them
        (``ScanConflictError`` after ``max_retries``).  Scan rounds
        interleave legally with elim/occ update rounds at round granularity
        — each scan linearizes at its validation point."""
        from repro.core import rounds

        return rounds.execute_scan(self, lo, hi, cap=cap, max_retries=max_retries)

    def scan_delete_round(self, lo, hi, cap: int = 128, max_retries: int = 8) -> ScanOutput:
        """ONE fused round that gathers every key in ``[lo_i, hi_i)``
        (≤ ``cap`` smallest per query) and deletes the gathered keys —
        the scan linearizes before the round's deletes, which target
        exactly the snapshot it observed.  Returns the pre-delete scan
        (the evicted keys/values); ``truncated`` marks queries with more
        matches left to sweep."""
        from repro.core import rounds

        return rounds.execute_scan_delete(self, lo, hi, cap=cap, max_retries=max_retries)

    def scan_stream(self, lo, hi, cap: int = 128):
        """Stream all (key, value) pairs in ``[lo, hi)`` in ascending key
        order as a generator, issuing successive ``cap``-bounded scan
        rounds that resume from the last emitted key (the cursor /
        continuation API over ``scan_round``'s fixed-capacity pages).

        Each underlying round is individually validated; entries observed
        by different rounds may straddle interleaved update rounds, as any
        cursor over a concurrent map does."""
        from repro.core import rounds

        return rounds.execute_scan_stream(self, lo, hi, cap)

    def _one(self, out) -> Optional[int]:
        """A one-lane round's answer (None where the lane found nothing)."""
        found = pull(self, "answers", out.found)[0]
        return int(pull(self, "answers", out.results)[0]) if found else None

    def find(self, key) -> Optional[int]:
        return self._one(self.apply_round([OP_FIND], [key]))

    def insert(self, key, val):
        return self._one(self.apply_round([OP_INSERT], [key], [val]))

    def delete(self, key):
        return self._one(self.apply_round([OP_DELETE], [key]))

    def items(self) -> dict:
        """Host-side snapshot of the dictionary contents (sorted by key)."""
        s = self.state
        leaf = pull(self, "items", s.is_leaf) & pull(self, "items", s.alloc)
        keys = pull(self, "items", s.keys)[leaf].ravel()
        vals = pull(self, "items", s.vals)[leaf].ravel()
        live = keys != int(EMPTY)
        keys, vals = keys[live], vals[live]
        order = np.argsort(keys, kind="stable")
        return dict(zip(keys[order].tolist(), vals[order].tolist()))

    def take_dirty(self) -> np.ndarray:
        """Node ids dirtied since the last durable commit (then reset)."""
        d = np.nonzero(pull(self, "commit.dirty", self.state.dirty))[0].astype(np.int32)
        self.state = self.state._replace(dirty=jnp.zeros_like(self.state.dirty))
        return d

    def stats(self) -> dict:
        """Device phase counters plus the engine's host-side round/scan
        counters (``rounds`` / ``scans`` / ``scan_retries`` are sequenced on
        the host by the unified engine; ``scan_retries`` counts retried
        *lanes* — ops re-gathered after a version conflict).  ``host_syncs``
        and ``d2h_bytes`` count every device→host pull so far, read before
        this call's own pull of the device counters."""
        syncs, d2h = self.metrics.value("host_syncs"), self.metrics.value("d2h_bytes")
        s = {
            k: int(pull(self, "stats", v).sum())
            for k, v in self.state.stats._asdict().items()
        }
        s["host_syncs"] = syncs
        s["d2h_bytes"] = d2h
        s["rounds"] = self._rounds
        s["scans"] = self._scans
        s["scan_retries"] = self._scan_retries
        return s

    # -- pool management --------------------------------------------------------

    def _ensure_capacity(self, need_nodes: int):
        """Grow the pool if fewer than `need + slack` nodes are free.  The
        2·wave_w term keeps the pool large enough for a full-width split
        wave's allocation (``_alloc_ids(state, 2w)`` slices 2w rows
        unconditionally), which tiny ``capacity`` configs would violate."""
        need = 2 * need_nodes + 4 * self.cfg.max_height + 2 * self._wave_w + 8
        # the stacked form is the one the engine keeps current; reading
        # ``state`` would slice an unstacked copy of every pool array
        n_alloc = int(pull(self, "capacity", jnp.sum(self.stacked.alloc)))
        cap = self.cfg.capacity
        if cap - n_alloc >= need:
            return
        self._grow(max(cap * 2, cap + need))

    def _grow(self, new_cap: int):
        self.state = grow_pool(self.state, new_cap - self.cfg.capacity, axis=0)
        self.cfg = self.cfg._replace(capacity=new_cap)
        self._wave_w = wave_width(new_cap)


# ----------------------------------------------------------------------------
# Range queries (paper §3: "could be added using the techniques of [5]").
# Optimistic double-collect over the touched subtree: capture versions,
# walk, re-validate — the multi-node generalization of searchLeaf.
# ----------------------------------------------------------------------------


def range_query(tree: "ABTree", lo: int, hi: int, max_retries: int = 8):
    """All (k, v) with lo ≤ k < hi, validated against node versions (the
    paper's optimistic-reader discipline, [5]-style epoch elided because
    rounds are quiescent between calls; retries guard against interleaved
    rounds from other engine threads sharing the state)."""
    cfg = tree.cfg
    for _ in range(max_retries):
        s = tree.state
        ver_before, keys, vals, children, is_leaf, size, root = (
            pull(tree, "range_query", x)
            for x in (s.ver, s.keys, s.vals, s.children, s.is_leaf, s.size, s.root)
        )
        root = int(root)
        out = []
        touched = []
        stack = [root]
        while stack:
            nid = stack.pop()
            touched.append(nid)
            if is_leaf[nid]:
                for j in range(cfg.b):
                    k = int(keys[nid, j])
                    if k != int(EMPTY) and lo <= k < hi:
                        out.append((k, int(vals[nid, j])))
                continue
            sz = int(size[nid])
            routers = keys[nid, : sz - 1]
            for j in range(sz):
                clo = -(2**63) if j == 0 else int(routers[j - 1])
                chi = int(EMPTY) if j == sz - 1 else int(routers[j])
                if chi > lo and clo < hi:  # child range intersects [lo, hi)
                    stack.append(int(children[nid, j]))
        ver_after = pull(tree, "range_query", tree.state.ver)
        if all(ver_before[t] == ver_after[t] for t in touched):
            return sorted(out)
    raise ScanConflictError("range_query: version validation failed repeatedly")
