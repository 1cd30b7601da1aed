"""Unified sharded round engine: ONE (S, wave_w) pipeline behind every round.

A *round* is a batch of mutually concurrent dictionary operations.  This
module owns the execution of rounds for the single tree and the forest
alike: there is exactly one host-sequencing implementation, written in the
leading-shard form — every phase kernel is a ``jax.vmap`` of the per-shard
kernel over a stacked ``TreeState`` (leading shard axis on every array),
every host loop masks its work per shard into shared ``(S, wave_w)`` /
``(S, W)`` blocks, and ``ABTree`` is simply the S = 1 case (its ``stacked``
property views the unstacked state as a one-shard stack).  ``ABForest``
contributes only routing (key-partition split points) and shard lifecycle
(overflow splits / restacks); the loops below never special-case either.

The public ``ABTree``/``ABForest`` entry points (``apply_round``,
``scan_round``, ``scan_delete_round``) are thin wrappers that build a
:class:`RoundPlan` (lane classification) and hand it to
:func:`execute_plan`, which sequences the ordered phase pipeline

    scan → search/combine → apply → retry → rebalance

Phase ↔ paper terminology (Elimination (a,b)-trees, §3–§4):

  ``scan``            the optimistic-reader discipline of ``searchLeaf``
                      generalized to a leaf frontier: gather against a state
                      snapshot, record every node read, re-validate versions
                      (retry on conflict).  Runs FIRST, so every scan in a
                      round linearizes *before* the round's net writes —
                      range lanes observe the pre-round dictionary.
                      Validation is per shard *component*: shards linked by
                      a cross-shard lane accept/retry against ONE snapshot;
                      independent shards validate independently.
  ``search/combine``  the paper's ``search`` (root-to-leaf descent + unsorted
                      leaf probe) followed by the publishing-elimination
                      combine (§4): all ops on one key fold to ≤ 1 net
                      physical write; eliminated ops compute their return
                      values from the published ElimRecord.
  ``apply``           the collapsed net writes — the paper's leaf slot
                      write + version bump (+2, odd intermediate stamped on
                      the ElimRecord, §4.1).
  ``retry``           deferred inserts (leaf full) re-descend after the
                      splits their overflow triggered — the batched analog
                      of a thread retrying after helping a split.
  ``rebalance``       relaxed-rebalancing waves of the Larsen–Fagerberg
                      sub-operations (split / merge / distribute), each wave
                      touching ≤ 1 violating child per parent per shard
                      (§3's fixTagged / fixUnderfull chains, batched).

Lane classes (``RoundPlan``):

  * **elim-combine / occ** — point ops (find/insert/delete).  In ``elim``
    mode the whole batch runs one combine; in ``occ`` mode duplicate keys
    force sub-rounds (duplicate-rank r executes in sub-round r; a shard
    whose own rank budget is exhausted is masked out of the tail).
  * **range** — OP_RANGE lanes ``[lo, lo+span)`` (key = lo, val = span),
    served by the scan phase.  Cross-shard lanes split into per-shard
    sub-lanes and stitch back in key order; mixed batches need no host-side
    splitting — one ``apply_round`` call executes every lane and returns
    per-lane results in one ``RoundOutput``.

Holder protocol (duck-typed; ``ABTree`` and ``ABForest`` both provide it):

  ``stacked``               get/set property: the (S, …) stacked TreeState
  ``cfg`` / ``mode``        TreeConfig, "elim" | "occ"
  ``n_shards``              S (1 for ABTree)
  ``narrow`` / ``narrow_scan``  int32 device-path gates (see ABTree)
  ``_splits`` / ``_bounds`` key-partition routing (empty / [-inf, +inf)
                            for the single tree)
  ``_wave_w``               structural-wave pad width
  ``_scan_frontier``        leaf-frontier pad width (doubles on overflow)
  ``_ensure_capacity(n)``   pool growth
  ``scan_hook`` / ``subround_hook``  optimistic-reader & durability hooks
  ``_rounds`` / ``_scans`` / ``_scan_retries``  host-side counters
  ``_scan_active``          in-flight-scan counter (defers shard splits)
  ``_maybe_split_shards()`` shard-overflow policy (no-op on ABTree)
  ``metrics`` / ``tracer``  telemetry (``repro.obs``): the registry backs
                            the legacy counters and counts every
                            device→host pull (``repro.obs.pull``); the
                            tracer wraps phase launches host-side
                            (NULL_TRACER = no-op)
  ``recorder``              flight recorder (``repro.obs.recorder``): one
                            semantic audit record per round, captured
                            host-side at round boundaries (NULL_RECORDER
                            = no-op)
  ``_note_shard_load(c)``   per-shard routed-lane counts → hot-shard
                            detection (no-op on ABTree)
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import elimination as elim
from repro.core.abtree import (
    EMPTY,
    INT_MAX,
    KEY_DTYPE,
    NOTFOUND,
    OP_DELETE,
    OP_FIND,
    OP_INSERT,
    OP_NOP,
    OP_RANGE,
    RoundOutput,
    ScanConflictError,
    ScanOutput,
    TreeConfig,
    TreeState,
    VAL_DTYPE,
    apply_net_ops,
    frontier_expand_sharded,
    shrink_root,
    split_wave,
    underfull_wave,
    _segment_starts,
)
from repro.kernels.range_scan.ops import range_scan
from repro.kernels.tree_descend.ops import descend_probe
from repro.obs.recorder import NULL_RECORDER
from repro.obs.tracer import NULL_TRACER, pull

# ----------------------------------------------------------------------------
# telemetry accessors (host-side only — spans/counters wrap the jitted
# phase launches and never enter them, so tracing cannot change HLO)
# ----------------------------------------------------------------------------


def _tr(holder):
    """The holder's installed tracer (NULL_TRACER when absent/None)."""
    t = getattr(holder, "tracer", None)
    return NULL_TRACER if t is None else t


def _metrics(holder):
    """The holder's metrics registry, or None for bare mock holders."""
    return getattr(holder, "metrics", None)


def _rec(holder):
    """The holder's installed flight recorder (NULL_RECORDER when
    absent/None).  Like the tracer, the recorder is host-side only —
    records are built from values the round already materialised on the
    host, after the jitted phases ran, so recording cannot change HLO."""
    r = getattr(holder, "recorder", None)
    return NULL_RECORDER if r is None else r


def _elim_note(holder, ops_sw, ks, arrival, res) -> dict:
    """Host summary of one combine's elimination decisions: per-shard
    eliminated-op counts plus every multi-update key segment (the
    annihilated insert/delete pairings) with its net physical action.
    Built only when a recorder is enabled."""
    ks_np = pull(holder, "recorder", ks)  # (S, W) key-sorted; EMPTY on NOP lanes
    arr_np = pull(holder, "recorder", arrival)  # sorted pos -> packed lane slot
    seg_np = pull(holder, "recorder", res.seg_head)
    ni = pull(holder, "recorder", res.net_insert)
    nd = pull(holder, "recorder", res.net_delete)
    no = pull(holder, "recorder", res.net_overwrite)
    nel = pull(holder, "recorder", res.n_eliminated).reshape(-1)
    ops_np = pull(holder, "recorder", ops_sw)
    segments = []
    for s in range(ks_np.shape[0]):
        ops_sorted = ops_np[s][arr_np[s]]
        upd = (ops_sorted == OP_INSERT) | (ops_sorted == OP_DELETE)
        if int(upd.sum()) < 2:
            continue
        seg_id = np.cumsum(seg_np[s]) - 1
        multi = np.nonzero(np.bincount(seg_id[upd]) >= 2)[0]
        heads = np.nonzero(seg_np[s])[0]
        for g in multi.tolist():
            head = int(heads[g])
            key = int(ks_np[s][head])
            if key == int(EMPTY):
                continue
            in_seg = (seg_id == g) & upd
            net = (
                "insert" if ni[s][head]
                else "delete" if nd[s][head]
                else "overwrite" if no[s][head]
                else "none"
            )
            segments.append(
                {
                    "shard": int(s),
                    "key": key,
                    "lanes": arr_np[s][in_seg].astype(np.int64).tolist(),
                    "net": net,
                }
            )
    return {"eliminated": nel.astype(np.int64).tolist(), "segments": segments}


def _note_load(holder, counts):
    """Feed per-shard routed-lane counts to the holder's hot-shard
    detector (a forest concern; ABTree's implementation is a no-op)."""
    note = getattr(holder, "_note_shard_load", None)
    if note is not None:
        note(counts)


def _note_keys(holder, keys):
    """Feed routed lane keys to the holder's key-sample reservoir (the
    forest's skew-aware repartitioner draws its weighted quantiles from
    it; ABTree has no reservoir)."""
    note = getattr(holder, "_note_key_sample", None)
    if note is not None:
        note(keys)


def _note_pack(holder, tr_span, width: int, n_real: int):
    """Record one lane-pack's width + pad waste: gauges in the metrics
    registry (``router_pack_width`` / ``pad_waste_frac``) and span args on
    the pack's trace span, so both the registry snapshot and
    ``repro.obs.report``'s pack table surface the padding the router
    actually shipped."""
    waste = (width - n_real) / width if width else 0.0
    m = _metrics(holder)
    if m is not None:
        m.set_gauge("router_pack_width", width)
        m.set_gauge("pad_waste_frac", waste)
        m.observe("pack_pad_waste", waste)
    tr_span.note(width=width, real=n_real, pad_waste=round(waste, 4))


# ----------------------------------------------------------------------------
# Round plans: lane classification
# ----------------------------------------------------------------------------


class RoundPlan(NamedTuple):
    """A classified round: which lanes take which pipeline, plus the derived
    per-lane scan intervals.  Built host-side once per round by
    :func:`build_plan`; the phase selection flags are host booleans so the
    engine only launches the phases the batch actually needs."""

    ops: jax.Array  # (B,) int32 — original lane opcodes
    point_ops: jax.Array  # (B,) int32 — OP_RANGE masked to OP_NOP
    keys: jax.Array  # (B,) KEY_DTYPE
    vals: jax.Array  # (B,) VAL_DTYPE (span on range lanes)
    lo: jax.Array  # (B,) scan lower bounds; EMPTY on non-range lanes
    hi: jax.Array  # (B,) scan upper bounds; EMPTY on non-range lanes
    is_range: jax.Array  # (B,) bool
    has_point: bool  # any find/insert/delete lane
    has_range: bool  # any OP_RANGE lane
    n_range: int
    scan_cap: int


def build_plan(ops, keys, vals=None, *, holder, scan_cap: int = 128) -> RoundPlan:
    """Classify one round's lanes and derive the range lanes' intervals.

    OP_RANGE lane encoding: ``key = lo``, ``val = span`` → the lane scans
    ``[lo, lo + span)`` (``span == 0`` is a legal empty scan).  Raises
    ``ValueError`` for malformed range lanes (``span < 0``, i.e. hi < lo)
    and for unknown op codes.  Device-array inputs are pulled through
    ``repro.obs.pull``, counted in ``holder``'s registry (``None``: no
    registry counts them).
    """
    ops_np = np.asarray(pull(holder, "plan", ops), np.int32)
    keys_np = np.asarray(pull(holder, "plan", keys), np.int64)
    vals_np = (
        np.zeros_like(keys_np) if vals is None
        else np.asarray(pull(holder, "plan", vals), np.int64)
    )
    if not (ops_np.shape == keys_np.shape == vals_np.shape and ops_np.ndim == 1):
        raise ValueError("apply_round expects equal-length 1-D ops/keys/vals")
    if ops_np.size and (ops_np.min() < int(OP_NOP) or ops_np.max() > int(OP_RANGE)):
        bad = ops_np[(ops_np < int(OP_NOP)) | (ops_np > int(OP_RANGE))][0]
        raise ValueError(f"unknown op code {int(bad)}")
    is_range_np = ops_np == OP_RANGE
    if np.any(is_range_np & (vals_np < 0)):
        lane = int(np.nonzero(is_range_np & (vals_np < 0))[0][0])
        raise ValueError(
            f"malformed OP_RANGE lane {lane}: negative span {int(vals_np[lane])} "
            f"(hi = lo + span < lo)"
        )
    n_range = int(is_range_np.sum())
    has_point = bool(np.any((ops_np > int(OP_NOP)) & ~is_range_np))

    ops_j = jnp.asarray(ops_np)
    keys_j = jnp.asarray(keys_np, KEY_DTYPE)
    vals_j = jnp.asarray(vals_np, VAL_DTYPE)
    is_range = jnp.asarray(is_range_np)
    # hi = lo + span, saturating at EMPTY: a span reaching past the top of
    # the key space must scan "everything ≥ lo" (matching the unbounded
    # oracle), not wrap to a negative int64 bound that scans nothing.
    with np.errstate(over="ignore"):
        hi_np = keys_np + vals_np
    hi_np = np.where(is_range_np & (hi_np < keys_np), int(EMPTY), hi_np)
    # Non-range lanes scan the empty interval [EMPTY, EMPTY): they expand
    # past the root into nothing and add no nodes to the validated read set.
    lo = jnp.where(is_range, keys_j, EMPTY)
    hi = jnp.where(is_range, jnp.asarray(hi_np, KEY_DTYPE), EMPTY)
    return RoundPlan(
        ops=ops_j,
        point_ops=elim.mask_range_lanes(ops_j),
        keys=keys_j,
        vals=vals_j,
        lo=lo,
        hi=hi,
        is_range=is_range,
        has_point=has_point,
        has_range=n_range > 0,
        n_range=n_range,
        scan_cap=scan_cap,
    )


# ----------------------------------------------------------------------------
# jitted per-shard phase kernels (device work; host code below only
# sequences their vmapped forms)
# ----------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(1, 5, 6, 7, 8))
def _phase_scan_flat(
    state: TreeState, cfg: TreeConfig, sid, lo, hi, frontier_cap: int,
    cap: int, narrow: bool = False, narrow_descent: bool = False,
):
    """jit: flat ragged frontier expansion + in-range gather over the
    STACKED state.  One launch covers every shard's scan sub-lanes packed
    side by side (lane ``i`` expands inside shard ``sid[i]``), so the
    device cost is proportional to the TRUE sub-lane count bucketed to one
    power of two — not ``S × pow2(max per-shard count)`` as the old
    per-shard row padding was.  The gather goes through
    ``kernels/range_scan``'s dispatching wrapper: int64 host-index keys
    take the jnp reference, int32 device keys the Pallas kernel.
    ``narrow`` (static, from ``tree.narrow_scan``) asserts the caller's
    keys/values fit in int32, routing the fused-round gather through the
    Pallas kernel even on the int64 host index (the ROADMAP "fused-round
    scan kernel" path).  ``narrow_descent`` (static, from ``tree.narrow``
    — the full device-path gate) additionally routes the per-level
    frontier compaction through its Pallas kernel; either way the jnp
    compaction is sort-free (cumsum rank + scatter)."""
    leaves, ck, cv, touched, overflow = frontier_expand_sharded(
        state, cfg, sid, lo, hi, frontier_cap, narrow=narrow_descent
    )
    keys, vals, count, truncated = range_scan(ck, cv, lo, hi, cap=cap, narrow=narrow)
    return ScanOutput(keys=keys, vals=vals, count=count, truncated=truncated), touched, overflow


def _search_leaves(state: TreeState, cfg: TreeConfig, ks, narrow: bool):
    """The search phase proper: fused root-to-leaf descent + unsorted-leaf
    probe via ``kernels/tree_descend`` — the Pallas kernel (pool pinned in
    VMEM, one launch instead of ``max_height`` batched HBM gathers) under
    the ``narrow`` gate, the jnp ref otherwise."""
    return descend_probe(
        state.keys, state.vals, state.children, state.is_leaf, state.root, ks,
        max_height=cfg.max_height, notfound=NOTFOUND, narrow=narrow,
    )


@functools.partial(jax.jit, static_argnums=(2, 3))
def _phase_search_combine(state: TreeState, batch, cfg: TreeConfig, narrow: bool = False):
    """jit: sort → descend → probe → eliminate.  Returns everything apply
    needs plus per-op results in original arrival order."""
    ops, keys, vals = batch
    bsz = ops.shape[0]
    sort_keys = jnp.where(ops == elim.OP_NOP, EMPTY, keys)
    # stable key sort carrying an int32 arrival index (argsort would carry
    # an int64 iota under x64, which doubles the TPU sort's compile time)
    ks, perm = jax.lax.sort(
        (sort_keys, jnp.arange(bsz, dtype=jnp.int32)), num_keys=1, is_stable=True
    )
    os_ = ops[perm]
    vs = vals[perm]
    arrival = perm

    seg_head = _segment_starts(ks)
    leaf_ids, found, slot, val0 = _search_leaves(state, cfg, ks, narrow)

    res = elim.eliminate_batch(os_, vs, seg_head, found, jnp.where(found, val0, 0))
    rets_sorted = elim.op_return_values(os_, res, NOTFOUND)
    # back to arrival order: a scatter through the permutation (inverting
    # it with a second sort costs a sort's compile and run time)
    results = jnp.zeros_like(rets_sorted).at[perm].set(
        rets_sorted, unique_indices=True
    )
    found_out = results != NOTFOUND

    stats = state.stats._replace(
        searches=state.stats.searches + jnp.int64(bsz),
        eliminated=state.stats.eliminated + res.n_eliminated.astype(jnp.int64),
    )
    state = state._replace(stats=stats)
    return state, (ks, arrival, leaf_ids, slot, res, results, found_out)


@functools.partial(jax.jit, static_argnums=(1,))
def _phase_apply(state: TreeState, cfg: TreeConfig, ks, arrival, leaf_ids, slot, res):
    out = apply_net_ops(
        state, cfg, leaf_ids, ks, slot,
        res.net_insert, res.net_delete, res.net_overwrite, res.final_val,
        arrival,
    )
    return out.state, out.deferred


@functools.partial(jax.jit, static_argnums=(1, 6))
def _phase_retry_insert(
    state: TreeState, cfg: TreeConfig, ks, vals, arrival, deferred,
    narrow: bool = False,
):
    """Re-descend deferred keys and retry the insert (post-split)."""
    leaf_ids, found, slot, _ = _search_leaves(state, cfg, ks, narrow)
    net_insert = deferred & ~found
    out = apply_net_ops(
        state, cfg, leaf_ids, ks, slot,
        net_insert,
        jnp.zeros_like(deferred),
        jnp.zeros_like(deferred),
        vals,
        arrival,
    )
    return out.state, out.deferred & deferred


@functools.partial(jax.jit, static_argnums=(1, 4))
def _phase_overfull_leaves(
    state: TreeState, cfg: TreeConfig, ks, deferred, narrow: bool = False
):
    """Unique (sentinel-padded, sorted) ids of full leaves holding deferred
    inserts."""
    leaf_ids, _, _, _ = _search_leaves(state, cfg, ks, narrow)
    full = deferred & (state.size[leaf_ids] >= cfg.b)
    ids = jnp.where(full, leaf_ids, INT_MAX)
    srt = jnp.sort(ids)
    first = _segment_starts(srt)
    return jnp.where(first, srt, INT_MAX)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _phase_split(state: TreeState, cfg: TreeConfig, w: int, node_ids, active):
    return split_wave(state, cfg, node_ids, active)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _phase_underfull(state: TreeState, cfg: TreeConfig, w: int, node_ids, active):
    return underfull_wave(state, cfg, node_ids, active)


@functools.partial(jax.jit, static_argnums=(1,))
def _phase_shrink(state: TreeState, cfg: TreeConfig):
    return shrink_root(state, cfg)


# ----------------------------------------------------------------------------
# vmapped phase kernels: one program, all shards (leading axis 0 everywhere).
# These are the ONLY call sites of the per-shard kernels above — the S = 1
# tree pays one trivially-mapped axis, the forest gets SPMD for free.
# ----------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(2, 3))
def _v_search_combine(state, batch, cfg: TreeConfig, narrow: bool = False):
    return jax.vmap(lambda st, b: _phase_search_combine(st, b, cfg, narrow))(
        state, batch
    )


@functools.partial(jax.jit, static_argnums=(1,))
def _v_apply(state, cfg: TreeConfig, ks, arrival, leaf_ids, slot, res):
    f = lambda st, a, b, c, d, e: _phase_apply(st, cfg, a, b, c, d, e)
    return jax.vmap(f)(state, ks, arrival, leaf_ids, slot, res)


@functools.partial(jax.jit, static_argnums=(1, 6))
def _v_retry_insert(state, cfg: TreeConfig, ks, vals, arrival, deferred, narrow=False):
    f = lambda st, a, b, c, d: _phase_retry_insert(st, cfg, a, b, c, d, narrow)
    return jax.vmap(f)(state, ks, vals, arrival, deferred)


@functools.partial(jax.jit, static_argnums=(1, 4))
def _v_overfull(state, cfg: TreeConfig, ks, deferred, narrow=False):
    return jax.vmap(lambda st, k, d: _phase_overfull_leaves(st, cfg, k, d, narrow))(
        state, ks, deferred
    )


@functools.partial(jax.jit, static_argnums=(1, 2))
def _v_split(state, cfg: TreeConfig, w: int, node_ids, active):
    return jax.vmap(lambda st, n, a: _phase_split(st, cfg, w, n, a))(
        state, node_ids, active
    )


@functools.partial(jax.jit, static_argnums=(1, 2))
def _v_underfull(state, cfg: TreeConfig, w: int, node_ids, active):
    return jax.vmap(lambda st, n, a: _phase_underfull(st, cfg, w, n, a))(
        state, node_ids, active
    )


@functools.partial(jax.jit, static_argnums=(1,))
def _v_shrink(state, cfg: TreeConfig):
    return jax.vmap(lambda st: _phase_shrink(st, cfg))(state)


# ----------------------------------------------------------------------------
# host helpers
# ----------------------------------------------------------------------------


def _pow2(n: int) -> int:
    """Shared pad width: power of two ≥ n, floor 8 (bounds jit recompiles)."""
    return max(8, 1 << (int(n) - 1).bit_length())


def _pack_slots(shard: np.ndarray, n_shards: int):
    """Vectorized per-shard slot assignment for lane packing: returns
    ``(shard_sorted, slot_sorted, order)`` where ``order`` stably sorts
    lanes by shard (preserving arrival order within each shard) and
    ``slot_sorted[j]`` is lane ``order[j]``'s slot in its shard's row."""
    order = np.argsort(shard, kind="stable")
    shard_sorted = shard[order]
    starts = np.searchsorted(shard_sorted, np.arange(n_shards))
    slot_sorted = np.arange(shard_sorted.size) - starts[shard_sorted]
    return shard_sorted, slot_sorted, order


def _independent_by_parent_np(parent_row: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Host-side: keep one node per parent (lowest id first).  ``parent_row``
    is one shard's parent array."""
    keep, seen = [], set()
    for nid in ids.tolist():
        p = int(parent_row[nid])
        if p not in seen:
            seen.add(p)
            keep.append(int(nid))
    return np.asarray(keep, np.int32)


def _duplicate_ranks(ops_np: np.ndarray, keys_np: np.ndarray) -> np.ndarray:
    """Per-lane duplicate rank of each key (OP_NOP lanes rank 0): rank r
    executes in OCC sub-round r.  Vectorized: a stable sort groups equal
    keys while preserving arrival order, so a lane's rank is its offset
    from its key-run's first occurrence."""
    rank = np.zeros(ops_np.shape[0], np.int32)
    idx = np.nonzero(ops_np != OP_NOP)[0]
    if idx.size == 0:
        return rank
    k = keys_np[idx]
    order = np.argsort(k, kind="stable")
    ks = k[order]
    run_start = np.searchsorted(ks, ks, side="left")
    rank[idx[order]] = (np.arange(ks.size) - run_start).astype(np.int32)
    return rank


# ----------------------------------------------------------------------------
# Phase: scan (optimistic reader; linearizes before the round's writes)
# ----------------------------------------------------------------------------


def gather_until_frontier_fits(holder, gather):
    """Run ``gather(frontier_cap) → (out, touched, overflow)``, doubling
    ``holder._scan_frontier`` until no query overflows its leaf frontier
    (powers of two keep the jit recompiles bounded).  The growth state lives
    on the holder, so later rounds start at the steady-state width.
    Returns (out, touched)."""
    guard = 0
    while True:
        out, touched, overflow = gather(holder._scan_frontier)
        if not bool(pull(holder, "scan.frontier", jnp.any(overflow))):
            return out, touched
        guard += 1
        assert guard < 32, "scan frontier growth diverged"
        holder._scan_frontier *= 2


def scan_lanes(holder, lo_np, hi_np, cap, *, n_scan_ops, max_retries: int = 8):
    """Split lanes ``[lo_i, hi_i)`` at shard boundaries, run one FLAT
    ragged scan phase over all sub-lanes (per-lane shard ids, one shared
    width bucketed to a power of two — no per-shard row padding), stitch
    sub-lane rows back per lane in key order (shards are key-ordered, rows
    within a shard ascending, so concatenation is globally sorted).  With
    S = 1 every lane is its own single sub-lane.  Routing is vectorized
    (two ``searchsorted`` calls over the whole batch; only the rare
    cross-shard lanes take a host loop) and computed ONCE per round — the
    retry loop re-gathers pending lanes without re-routing.  Returns numpy
    ``(keys (B,cap), vals, count, truncated)``."""
    n_shards = holder.n_shards
    bsz = int(lo_np.size)
    lo_np = np.asarray(lo_np, np.int64)
    hi_np = np.asarray(hi_np, np.int64)
    out_k = np.full((bsz, cap), int(EMPTY), np.int64)
    out_v = np.zeros((bsz, cap), np.int64)
    out_c = np.zeros((bsz,), np.int32)
    out_t = np.zeros((bsz,), bool)
    holder._scans += int(n_scan_ops)
    tr = _tr(holder)
    m = _metrics(holder)
    live = hi_np > lo_np
    comp = np.arange(n_shards)  # union-find over cross-shard-linked shards
    with tr.span("router_pack", lanes=bsz) as pack_sp:
        s0 = np.searchsorted(holder._splits, lo_np, side="right")
        s1 = np.searchsorted(
            holder._splits, np.maximum(hi_np - 1, lo_np), side="right"
        )
        multi = np.nonzero(live & (s0 < s1))[0]
        single = np.nonzero(live & (s0 == s1))[0]
        if multi.size == 0:
            lane_of = single
            sub_sid = s0[single]
            sub_lo = lo_np[single]
            sub_hi = hi_np[single]
        else:
            # Cross-shard lanes split at shard boundaries (host loop over
            # just those lanes); a stable lane-major sort then interleaves
            # them with the single-shard lanes, keeping each lane's
            # sub-lanes contiguous and shard-ascending.
            ln = [single]
            sd = [s0[single]]
            lo_l = [lo_np[single]]
            hi_l = [hi_np[single]]
            def _find(x):
                while comp[x] != x:
                    comp[x] = comp[comp[x]]
                    x = comp[x]
                return x
            for i in multi.tolist():
                for s in range(int(s0[i]), int(s1[i]) + 1):
                    slo = max(int(lo_np[i]), holder._bounds[s])
                    shi = min(int(hi_np[i]), holder._bounds[s + 1])
                    if shi <= slo:
                        continue
                    ln.append(np.array([i]))
                    sd.append(np.array([s]))
                    lo_l.append(np.array([slo]))
                    hi_l.append(np.array([shi]))
                    # all of a lane's shards validate against ONE snapshot
                    comp[_find(int(s0[i]))] = _find(s)
            lane_of = np.concatenate(ln).astype(np.int64)
            sub_sid = np.concatenate(sd).astype(np.int64)
            sub_lo = np.concatenate(lo_l).astype(np.int64)
            sub_hi = np.concatenate(hi_l).astype(np.int64)
            order = np.argsort(lane_of, kind="stable")
            lane_of = lane_of[order]
            sub_sid = sub_sid[order]
            sub_lo = sub_lo[order]
            sub_hi = sub_hi[order]
        n_sub = int(sub_sid.size)
        n_per = np.bincount(sub_sid, minlength=n_shards).astype(np.int64)
        if n_sub:
            _note_pack(holder, pack_sp, _pow2(n_sub), n_sub)
    tr.shard_marks("scan.sublanes", n_per)
    _note_load(holder, n_per)
    if live.any():
        _note_keys(holder, lo_np[live])
    if m is not None:
        for s in np.nonzero(n_per)[0]:
            m.inc_shard("scan_sublanes", int(n_per[s]), int(s))
        m.inc("scan_sublanes", int(n_per.sum()))
    if n_sub == 0:
        return out_k, out_v, out_c, out_t
    # Shards linked by a cross-shard lane form one validation component:
    # all of a lane's sub-lanes must be accepted against ONE snapshot
    # (else the stitched row could mix states that never coexisted).
    def _root(x):
        while comp[x] != x:
            comp[x] = comp[comp[x]]
            x = comp[x]
        return x

    groups = np.array([_root(s) for s in range(n_shards)])
    buf_k, buf_v, buf_c, buf_t = run_scan_phase(
        holder, sub_sid, sub_lo, sub_hi, cap, max_retries, groups
    )
    if multi.size == 0:
        # every lane is one sub-lane: the stitched output IS the buffer
        out_k[lane_of] = buf_k
        out_v[lane_of] = buf_v
        out_c[lane_of] = buf_c
        out_t[lane_of] = buf_t
        return out_k, out_v, out_c, out_t
    with tr.span("router_stitch", lanes=bsz):
        starts = np.searchsorted(lane_of, np.arange(bsz))
        ends = np.searchsorted(lane_of, np.arange(bsz) + 1)
        for i in np.unique(lane_of).tolist():
            a, e = int(starts[i]), int(ends[i])
            if e - a == 1:
                out_k[i] = buf_k[a]
                out_v[i] = buf_v[a]
                out_c[i] = buf_c[a]
                out_t[i] = buf_t[a]
                continue
            parts_k, parts_v, truncated = [], [], False
            for j in range(a, e):  # shards ascending ⇒ keys ascending
                c = int(buf_c[j])
                truncated = truncated or bool(buf_t[j])
                parts_k.append(buf_k[j, :c])
                parts_v.append(buf_v[j, :c])
            cat_k = np.concatenate(parts_k)
            cat_v = np.concatenate(parts_v)
            n = min(cat_k.size, cap)
            out_k[i, :n] = cat_k[:n]
            out_v[i, :n] = cat_v[:n]
            out_c[i] = n
            out_t[i] = truncated or cat_k.size > cap
    return out_k, out_v, out_c, out_t


def run_scan_phase(
    holder, sub_sid, sub_lo, sub_hi, cap, max_retries: int = 8, groups=None
):
    """One FLAT ragged gather over all sub-lanes + per-*component* version
    validation: shards linked by a cross-shard lane (``groups``) accept
    or retry TOGETHER, so every lane's stitched row comes from one
    snapshot (the single-tree linearization guarantee); independent
    shards validate independently, which is the conflict-window shrink
    sharding buys.  The flat block packs every shard's sub-lanes side by
    side at width ``pow2(n_sub)`` — device cost tracks the true lane
    count, not ``S × pow2(max per-shard count)`` — and a retry re-packs
    ONLY the pending components' lanes (an accepted component's rows are
    frozen; its scans linearized at that validation point), so per-shard
    validation's retry savings convert to wall-clock.  ``scan_retries``
    accrues the retried lane count.  Raises ``ScanConflictError`` after
    ``max_retries``; ``holder.scan_hook`` (modeling update rounds from
    other engine replicas) is called between each gather and its
    validation."""
    n_s = holder.n_shards
    sub_sid = np.asarray(sub_sid, np.int64)
    sub_lo = np.asarray(sub_lo, np.int64)
    sub_hi = np.asarray(sub_hi, np.int64)
    n_sub = int(sub_sid.size)
    if groups is None:
        groups = np.arange(n_s)
    buf_k = np.full((n_sub, cap), int(EMPTY), np.int64)
    buf_v = np.zeros((n_sub, cap), np.int64)
    buf_c = np.zeros((n_sub,), np.int32)
    buf_t = np.zeros((n_sub,), bool)
    n_per_shard = np.bincount(sub_sid, minlength=n_s).astype(np.int64)
    pending = n_per_shard > 0  # lane-less shards are trivially done
    cur = np.arange(n_sub)  # original sub-lane indices in the packed block
    retried = 0
    tr = _tr(holder)
    m = _metrics(holder)
    # a scan_hook writer may push a shard past max_keys_per_shard: the
    # split (which restacks to S+1 shards) must not fire under this
    # loop's flat lane routing — defer it to the next round boundary.
    holder._scan_active += 1
    try:
        with tr.span("scan", lanes=n_sub, shards=n_s) as scan_sp:
            for _attempt in range(max_retries):
                w = _pow2(cur.size)
                sid_w = np.zeros(w, np.int64)
                lo_w = np.full(w, int(EMPTY), np.int64)
                hi_w = np.full(w, int(EMPTY), np.int64)
                sid_w[: cur.size] = sub_sid[cur]
                lo_w[: cur.size] = sub_lo[cur]
                hi_w[: cur.size] = sub_hi[cur]
                snap = holder.stacked
                with tr.span("scan.gather", attempt=_attempt, width=w) as sp:
                    sid_j = jnp.asarray(sid_w, jnp.int32)
                    lo_j = jnp.asarray(lo_w, KEY_DTYPE)
                    hi_j = jnp.asarray(hi_w, KEY_DTYPE)
                    out, touched = gather_until_frontier_fits(
                        holder,
                        lambda fc: _phase_scan_flat(
                            snap, holder.cfg, sid_j, lo_j, hi_j, fc, cap,
                            holder.narrow_scan, holder.narrow,
                        ),
                    )
                    sp.fence((out, touched))
                if holder.scan_hook is not None:
                    holder.scan_hook()
                with tr.span("scan.validate", attempt=_attempt):
                    snap_ver = pull(holder, "scan.validate", snap.ver)
                    live_ver = pull(holder, "scan.validate", holder.stacked.ver)
                    # (L, w, F) per-lane ids
                    touched_np = pull(holder, "scan.validate", touched)
                    shard_ok = np.zeros(n_s, bool)
                    for s in np.nonzero(pending)[0]:
                        ids = np.unique(touched_np[:, sid_w == s, :])
                        shard_ok[s] = np.array_equal(
                            snap_ver[s][ids], live_ver[s][ids]
                        )
                    accept = np.zeros(n_s, bool)
                    for g in np.unique(groups[pending]):
                        members = pending & (groups == g)
                        if shard_ok[members].all():
                            accept |= members
                        else:  # whole component re-gathers next attempt
                            retried += int(n_per_shard[members].sum())
                            if m is not None:
                                for s in np.nonzero(members)[0]:
                                    m.inc_shard(
                                        "scan_retries",
                                        int(n_per_shard[s]), int(s),
                                    )
                            tr.shard_marks(
                                "scan.retry",
                                np.where(members, n_per_shard, 0),
                                attempt=_attempt,
                            )
                if accept.any():
                    take = accept[sub_sid[cur]]  # rows of accepted shards
                    rows = np.nonzero(take)[0]
                    buf_k[cur[rows]] = pull(holder, "scan.rows", out.keys)[rows]
                    buf_v[cur[rows]] = pull(holder, "scan.rows", out.vals)[rows]
                    buf_c[cur[rows]] = pull(holder, "scan.rows", out.count)[rows]
                    buf_t[cur[rows]] = pull(holder, "scan.rows", out.truncated)[rows]
                    pending &= ~accept
                if not pending.any():
                    holder._scan_retries += retried
                    scan_sp.note(retries=retried, attempts=_attempt + 1)
                    rec = _rec(holder)
                    if rec.enabled:
                        rec.note_scan_phase(
                            retries=retried, attempts=_attempt + 1
                        )
                    return buf_k, buf_v, buf_c, buf_t
                # only pending components' sub-lanes re-gather
                cur = cur[pending[sub_sid[cur]]]
            raise ScanConflictError(
                f"scan phase: version validation failed {max_retries} "
                f"times on shards {np.nonzero(pending)[0].tolist()}"
            )
    finally:
        holder._scan_active -= 1


def execute_scan(holder, lo, hi, cap: int = 128, max_retries: int = 8) -> ScanOutput:
    """One batched scan round: per query the ≤ ``cap`` smallest keys in
    ``[lo_i, hi_i)``, ascending, stitched across shards in key order.  The
    shared body behind ``ABTree.scan_round`` and ``ABForest.scan_round``."""
    lo = np.atleast_1d(np.asarray(pull(holder, "plan", lo), np.int64))
    hi = np.atleast_1d(np.asarray(pull(holder, "plan", hi), np.int64))
    assert lo.shape == hi.shape and lo.ndim == 1
    k_, v_, c_, t_ = scan_lanes(
        holder, lo, hi, cap, n_scan_ops=int(lo.size), max_retries=max_retries
    )
    rec = _rec(holder)
    if rec.enabled:
        rec.round(
            round_no=holder._rounds,
            mode=holder.mode,
            n_shards=holder.n_shards,
            ops=np.full((lo.size,), int(OP_RANGE), np.int32),
            keys=lo,
            vals=hi - lo,
            results=c_.astype(np.int64),
            found=c_ > 0,
            scans={
                i: list(zip(k_[i, : c_[i]].tolist(), v_[i, : c_[i]].tolist()))
                for i in range(lo.size)
            },
            scan_cap=cap,
            fused="scan",
        )
    # Scan rounds never run the shard-overflow split (pinned: splits defer
    # to the next update round), but load rebalancing may act here — read
    # skew is exactly what the hot-shard window observes on scan traffic.
    holder._maybe_repartition()
    return ScanOutput(
        keys=jnp.asarray(k_),
        vals=jnp.asarray(v_),
        count=jnp.asarray(c_),
        truncated=jnp.asarray(t_),
    )


def execute_scan_stream(holder, lo, hi, cap: int):
    """Validate eagerly (a generator body would not run until first
    ``next``), then stream ``[lo, hi)`` as cursor-chained pages."""
    if cap <= 0:
        raise ValueError(f"scan_stream: cap must be positive, got {cap}")
    return scan_stream_pages(holder, int(lo), int(hi), cap)


def scan_stream_pages(holder, cur: int, hi: int, cap: int):
    """Stream all (key, value) pairs in ``[cur, hi)`` ascending by chaining
    per-shard cursors: each page queries only the shard holding the cursor,
    so arbitrarily long cross-shard scans stay bounded at ``cap`` entries
    (and one shard's gather) per round."""
    while cur < hi:
        s = int(np.searchsorted(holder._splits, cur, side="right"))
        s_hi = min(hi, holder._bounds[s + 1])
        out = holder.scan_round([cur], [s_hi], cap=cap)
        n = int(pull(holder, "scan.rows", out.count)[0])
        ks = pull(holder, "scan.rows", out.keys)[0, :n]
        vs = pull(holder, "scan.rows", out.vals)[0, :n]
        for k, v in zip(ks.tolist(), vs.tolist()):
            yield int(k), int(v)
        if bool(pull(holder, "scan.rows", out.truncated)[0]):
            cur = int(ks[-1]) + 1
        else:
            cur = s_hi  # shard exhausted: jump to the next shard's range


# ----------------------------------------------------------------------------
# Phases: search/combine → apply → retry → rebalance (point lanes)
# ----------------------------------------------------------------------------


def run_point_phases(holder, ops_sw, keys_sw, vals_sw):
    """Execute the point-op pipeline in the holder's mode on one packed
    ``(S, W)`` lane block.  ``ops_sw`` must be free of OP_RANGE (the plan
    builder masks range lanes to OP_NOP)."""
    if holder.mode == "elim":
        return _combine_apply(holder, ops_sw, keys_sw, vals_sw)
    return _occ_round(holder, ops_sw, keys_sw, vals_sw)


def _combine_apply(holder, ops_sw, keys_sw, vals_sw):
    """Elim-ABtree: every shard's batch runs one combine; ≤ 1 net write per
    key per shard."""
    tr = _tr(holder)
    with tr.span("search_combine") as sp:
        holder.stacked, pack = _v_search_combine(
            holder.stacked, (ops_sw, keys_sw, vals_sw), holder.cfg,
            holder.narrow,
        )
        sp.fence(pack)
    ks, arrival, leaf_ids, slot, res, results, found = pack
    rec = _rec(holder)
    if rec.enabled:
        rec.note_elim(_elim_note(holder, ops_sw, ks, arrival, res))
    with tr.span("apply") as sp:
        holder.stacked, deferred = _v_apply(
            holder.stacked, holder.cfg, ks, arrival, leaf_ids, slot, res
        )
        sp.fence(holder.stacked)
    # retry and rebalance spans are emitted even when the phase has no
    # work: a trace of any round shows the full five-phase pipeline.
    with tr.span("retry") as sp:
        passes = _drain_deferred(holder, ks, res.final_val, arrival, deferred)
        sp.note(passes=passes)
    with tr.span("rebalance") as sp:
        waves, shrinks = _fix_underfull_all(holder)
        sp.note(waves=waves, shrinks=shrinks)
    return results, found


def _occ_round(holder, ops_sw, keys_sw, vals_sw):
    """OCC baseline: per-shard duplicate-rank sub-rounds, executed as
    max-over-shards vmapped sub-rounds.  A shard whose own duplicate
    rank is exhausted runs all-NOP lanes in the tail sub-rounds — those
    are *not* sub-rounds it executes: its lanes are masked out, its
    ``subrounds`` counter stays put, and its durable/validation cost is
    zero (the vmap itself still spans all shards, as any SPMD program
    must).  Sub-round lane masking is RAGGED: each sub-round re-packs
    only its live lanes (rank-r duplicates) into a block bucketed to
    ``pow2(max per-shard live count)``, so tail sub-rounds — typically a
    handful of duplicate keys — run at width 8 instead of the full round
    width, and already-satisfied lanes never re-enter the search phase.
    ``holder.subround_hook`` fires after every executed sub-round — the
    durable layer's per-update flush+fence discipline."""
    on = pull(holder, "plan", ops_sw)
    kn = pull(holder, "plan", keys_sw)
    vn = pull(holder, "plan", vals_sw)
    n_s, w = on.shape
    rank = np.stack([_duplicate_ranks(on[s], kn[s]) for s in range(n_s)])
    # per-shard sub-round budget: rank r of a real op executes in
    # sub-round r, so shard s is live only while r ≤ max(rank[s]).
    live = on != OP_NOP  # (S, w)
    shard_max = np.where(
        live.any(axis=1), np.where(live, rank, 0).max(axis=1), -1
    )
    n_sub = int(rank.max()) + 1
    results = np.full((n_s, w), int(NOTFOUND), np.int64)
    found = np.zeros((n_s, w), bool)
    tr = _tr(holder)
    reg = _metrics(holder)
    for r in range(n_sub):
        active = shard_max >= r  # (S,) host bools: shard executes r
        m = (rank == r) & live  # (S, w) this sub-round's live lanes
        counts_r = m.sum(axis=1)
        w_r = _pow2(int(counts_r.max()))
        s_idx, pos = np.nonzero(m)  # row-major ⇒ s_idx sorted
        starts = np.searchsorted(s_idx, np.arange(n_s))
        slot = np.arange(s_idx.size) - starts[s_idx]
        sub_ops = np.full((n_s, w_r), OP_NOP, np.int32)
        sub_keys = np.zeros((n_s, w_r), np.int64)
        sub_vals = np.zeros((n_s, w_r), np.int64)
        sub_ops[s_idx, slot] = on[s_idx, pos]
        sub_keys[s_idx, slot] = kn[s_idx, pos]
        sub_vals[s_idx, slot] = vn[s_idx, pos]
        with tr.span(
            "occ_subround", subround=r, active=int(active.sum()), width=w_r
        ) as sp:
            if reg is not None and w_r:
                waste = (n_s * w_r - int(s_idx.size)) / (n_s * w_r)
                reg.set_gauge("router_pack_width", w_r)
                reg.set_gauge("pad_waste_frac", waste)
            sp.note(width=w_r, real=int(s_idx.size))
            sub_res, sub_found = _combine_apply(
                holder,
                jnp.asarray(sub_ops),
                jnp.asarray(sub_keys, KEY_DTYPE),
                jnp.asarray(sub_vals, VAL_DTYPE),
            )
        results[s_idx, pos] = pull(holder, "answers", sub_res)[s_idx, slot]
        found[s_idx, pos] = pull(holder, "answers", sub_found)[s_idx, slot]
        if reg is not None:
            reg.inc("occ_subrounds", int(active.sum()))
        st = holder.stacked
        holder.stacked = st._replace(
            stats=st.stats._replace(
                subrounds=st.stats.subrounds + jnp.asarray(active, jnp.int64)
            )
        )
        if holder.subround_hook is not None:
            holder.subround_hook()
    rec = _rec(holder)
    if rec.enabled:
        rec.note_occ(
            subrounds=n_sub,
            active_per_subround=[
                int((shard_max >= r).sum()) for r in range(n_sub)
            ],
        )
    return jnp.asarray(results, VAL_DTYPE), jnp.asarray(found)


def _drain_deferred(holder, ks, final_vals, arrival, deferred):
    """Retry phase: split overflowing leaves and re-apply deferred inserts
    until none remain (all shards per wave).  Returns the pass count."""
    guard = 0
    reg = _metrics(holder)
    while bool(pull(holder, "deferred", jnp.any(deferred))):
        guard += 1
        assert guard < 512 * holder.cfg.max_height, "split loop diverged"
        if reg is not None:
            reg.inc("retry_passes")
        uniq = pull(
            holder, "overfull",
            _v_overfull(holder.stacked, holder.cfg, ks, deferred, holder.narrow),
        )
        per_shard = [row[row != INT_MAX].astype(np.int32) for row in uniq]
        if any(r.size for r in per_shard):
            _split_cascade(holder, per_shard)
        holder.stacked, deferred = _v_retry_insert(
            holder.stacked, holder.cfg, ks, final_vals, arrival, deferred,
            holder.narrow,
        )
    return guard


def _split_cascade(holder, ids_per_shard: List[np.ndarray]):
    """Split the given full nodes, all shards per wave.  A node whose parent
    is itself full is postponed until the parent has split (pre-splitting
    ancestors) — keeps every wave's parent-insert within capacity; ≤ 1
    active node per parent per wave."""
    n_s = holder.n_shards
    work = [set(int(i) for i in ids) for ids in ids_per_shard]
    guard = 0
    while any(work):
        guard += 1
        assert guard < 512 * holder.cfg.max_height * n_s, "split cascade diverged"
        st = holder.stacked
        size = pull(holder, "split", st.size)
        parent = pull(holder, "split", st.parent)
        alloc = pull(holder, "split", st.alloc)
        ready_rows: List[np.ndarray] = []
        blocked_rows: List[List[int]] = []
        for s in range(n_s):
            # prune: stale entries no longer full / no longer allocated
            ws = {n for n in work[s] if alloc[s, n] and size[s, n] >= holder.cfg.b}
            work[s] = ws
            ready, blocked = [], []
            for n in sorted(ws):
                p = int(parent[s, n])
                if p >= 0 and size[s, p] >= holder.cfg.b:
                    blocked.append(p)
                else:
                    ready.append(n)
            if not ready:
                # all blocked: queue the blocking parents for splitting
                work[s] |= set(blocked)
                ready_rows.append(np.zeros((0,), np.int32))
                blocked_rows.append([])
                continue
            rd = _independent_by_parent_np(
                parent[s], np.asarray(ready, np.int32)
            )[: holder._wave_w]  # per-wave node cap
            ready_rows.append(rd)
            blocked_rows.append(blocked)
        if not any(r.size for r in ready_rows):
            continue
        holder._ensure_capacity(2 * max(int(r.size) for r in ready_rows))
        # ragged wave width: typical waves touch a handful of nodes, so
        # the vmapped kernel runs at width 8 instead of the full cap.
        # Two buckets only ({8, cap}) — each wave kernel compiles at most
        # twice, and big waves are rare enough that padding them is fine.
        max_nodes = max(int(r.size) for r in ready_rows)
        w_wave = 8 if max_nodes <= 8 else holder._wave_w
        node_ids = np.zeros((n_s, w_wave), np.int32)
        active = np.zeros((n_s, w_wave), bool)
        for s, rd in enumerate(ready_rows):
            node_ids[s, : rd.size] = rd
            active[s, : rd.size] = True
        tr = _tr(holder)
        with tr.span("split_wave", wave=guard, width=w_wave) as sp:
            holder.stacked = _v_split(
                holder.stacked, holder.cfg, w_wave,
                jnp.asarray(node_ids), jnp.asarray(active),
            )
            sp.fence(holder.stacked)
        reg = _metrics(holder)
        if reg is not None:
            reg.inc("split_waves")
            for s, rd in enumerate(ready_rows):
                if rd.size:
                    reg.inc("split_nodes", int(rd.size), shard=s)
        tr.shard_marks("split_wave.nodes", [int(r.size) for r in ready_rows])
        for s, rd in enumerate(ready_rows):
            for n in rd.tolist():
                work[s].discard(int(n))
            work[s] |= set(blocked_rows[s])


def _fix_underfull_all(holder):
    """Rebalance phase: merge/distribute every shard's underfull non-root
    nodes, bottom-up vmapped waves; root shrink once a shard has no
    actionable wave.  Returns (wave count, shrink count)."""
    n_s = holder.n_shards
    tr = _tr(holder)
    reg = _metrics(holder)
    n_waves = n_shrinks = 0
    guard = 0
    while True:
        guard += 1
        assert guard < 512 * holder.cfg.max_height * n_s, (
            "underfull loop diverged"
        )
        st = holder.stacked
        alloc = pull(holder, "underfull", st.alloc)
        size = pull(holder, "underfull", st.size)
        parent = pull(holder, "underfull", st.parent)
        level = pull(holder, "underfull", st.level)
        is_leaf = pull(holder, "underfull", st.is_leaf)
        root = pull(holder, "underfull", st.root)
        sel_rows: List[np.ndarray] = []
        any_wave = False
        want_shrink = False
        for s in range(n_s):
            r = int(root[s])
            under = alloc[s] & (size[s] < holder.cfg.a) & (parent[s] >= 0)
            under[r] = False
            ids = np.nonzero(under)[0].astype(np.int32)
            actionable = ids[size[s][parent[s][ids]] >= 2] if ids.size else ids
            if actionable.size:
                lv = level[s][actionable].min()
                sel = actionable[level[s][actionable] == lv]
                sel = _independent_by_parent_np(parent[s], sel)[: holder._wave_w]
                sel_rows.append(sel)
                any_wave = True
            else:
                sel_rows.append(np.zeros((0,), np.int32))
                if (not is_leaf[s, r]) and int(size[s, r]) == 1:
                    want_shrink = True
        if any_wave:
            # ragged wave width, as in _split_cascade ({8, cap} buckets)
            max_nodes = max(int(r.size) for r in sel_rows)
            w_wave = 8 if max_nodes <= 8 else holder._wave_w
            node_ids = np.zeros((n_s, w_wave), np.int32)
            active = np.zeros((n_s, w_wave), bool)
            for s, sel in enumerate(sel_rows):
                node_ids[s, : sel.size] = sel
                active[s, : sel.size] = True
            with tr.span("underfull_wave", wave=guard, width=w_wave) as sp:
                holder.stacked = _v_underfull(
                    holder.stacked, holder.cfg, w_wave,
                    jnp.asarray(node_ids), jnp.asarray(active),
                )
                sp.fence(holder.stacked)
            n_waves += 1
            if reg is not None:
                reg.inc("underfull_waves")
            tr.shard_marks(
                "underfull_wave.nodes", [int(r.size) for r in sel_rows]
            )
            continue
        if want_shrink:
            # per-shard `can` guard inside shrink_root makes the vmapped
            # call exact: only single-child internal roots collapse.
            with tr.span("root_shrink"):
                holder.stacked = _v_shrink(holder.stacked, holder.cfg)
            n_shrinks += 1
            if reg is not None:
                reg.inc("root_shrinks")
            continue
        break
    return n_waves, n_shrinks


# ----------------------------------------------------------------------------
# Plan execution
# ----------------------------------------------------------------------------


def execute_plan(holder, plan: RoundPlan) -> RoundOutput:
    """Run one round through the phase pipeline: the router partitions
    lanes by key range (a no-op at S = 1), all shards execute as one
    vmapped round, and per-lane results come back batch-aligned.

    Phase order fixes the linearization: range lanes gather from the
    pre-round state (scan phase first; cross-shard lanes split into
    per-shard sub-lanes and stitch back in key order), point lanes then
    apply in arrival order per key (stable packing preserves arrival order
    within a shard, and all ops on one key land in one shard).  Returns
    per-lane results in one ``RoundOutput``: point lanes get the §3
    dictionary return values; range lanes get their match count in
    ``results`` (``found`` ⇔ non-empty) and their rows in
    ``RoundOutput.scan`` (batch-aligned; non-range rows are empty)."""
    bsz = int(plan.ops.shape[0])
    n_shards = holder.n_shards
    if bsz == 0:
        holder._rounds += 1
        return RoundOutput(
            results=jnp.full((0,), NOTFOUND, VAL_DTYPE),
            found=jnp.zeros((0,), bool),
            scan=None,
        )
    tr = _tr(holder)
    reg = _metrics(holder)
    with tr.span("round", lanes=bsz, shards=n_shards):
        ops_np = pull(holder, "plan", plan.ops)
        keys_np = pull(holder, "plan", plan.keys)
        vals_np = pull(holder, "plan", plan.vals)
        # host mirror of elimination.lane_masks: classifying 256 lanes is
        # a handful of numpy compares, not worth five op-by-op dispatches
        # on the round's critical path.
        is_range = ops_np == OP_RANGE
        is_point = (ops_np == OP_FIND) | (ops_np == OP_INSERT) | (ops_np == OP_DELETE)

        results = np.full((bsz,), int(NOTFOUND), np.int64)
        found = np.zeros((bsz,), bool)

        # --- scan phase first: range lanes linearize before the round's
        # writes.
        scan_out = None
        if plan.has_range:
            rl = np.nonzero(is_range)[0]
            lo_np = pull(holder, "plan", plan.lo)[rl]
            hi_np = pull(holder, "plan", plan.hi)[rl]
            k_, v_, c_, t_ = scan_lanes(
                holder, lo_np, hi_np, plan.scan_cap, n_scan_ops=plan.n_range
            )
            keys_full = np.full((bsz, plan.scan_cap), int(EMPTY), np.int64)
            vals_full = np.zeros((bsz, plan.scan_cap), np.int64)
            count_full = np.zeros((bsz,), np.int32)
            trunc_full = np.zeros((bsz,), bool)
            keys_full[rl] = k_
            vals_full[rl] = v_
            count_full[rl] = c_
            trunc_full[rl] = t_
            scan_out = ScanOutput(
                keys=jnp.asarray(keys_full),
                vals=jnp.asarray(vals_full),
                count=jnp.asarray(count_full),
                truncated=jnp.asarray(trunc_full),
            )
            results[rl] = c_.astype(np.int64)
            found[rl] = c_ > 0

        # --- point lanes: pack per shard (stable ⇒ arrival order kept).
        if plan.has_point:
            pl = np.nonzero(is_point)[0]
            with tr.span("router_pack", lanes=int(pl.size)) as pack_sp:
                shard = np.searchsorted(
                    holder._splits, keys_np[pl], side="right"
                )
                counts = np.bincount(shard, minlength=n_shards)
                w = _pow2(int(counts.max()))
                ops_sw = np.full((n_shards, w), OP_NOP, np.int32)
                keys_sw = np.zeros((n_shards, w), np.int64)
                vals_sw = np.zeros((n_shards, w), np.int64)
                shard_sorted, slot_sorted, order = _pack_slots(shard, n_shards)
                ops_sw[shard_sorted, slot_sorted] = ops_np[pl][order]
                keys_sw[shard_sorted, slot_sorted] = keys_np[pl][order]
                vals_sw[shard_sorted, slot_sorted] = vals_np[pl][order]
                slot = np.empty(pl.size, np.int64)
                slot[order] = slot_sorted
                _note_pack(holder, pack_sp, n_shards * w, int(pl.size))
            tr.shard_marks("point_lanes", counts)
            _note_load(holder, counts)
            _note_keys(holder, keys_np[pl])
            if reg is not None:
                reg.inc("point_lanes", int(pl.size))
                for s in np.nonzero(counts)[0]:
                    reg.inc_shard("point_lanes", int(counts[s]), int(s))
            holder._ensure_capacity(w)
            res_sw, fnd_sw = run_point_phases(
                holder,
                jnp.asarray(ops_sw),
                jnp.asarray(keys_sw, KEY_DTYPE),
                jnp.asarray(vals_sw, VAL_DTYPE),
            )
            results[pl] = pull(holder, "answers", res_sw)[shard, slot]
            found[pl] = pull(holder, "answers", fnd_sw)[shard, slot]

        rec = _rec(holder)
        if rec.enabled:
            scans_d = None
            if scan_out is not None:
                scans_d = {
                    int(i): list(zip(k_[j, : c_[j]].tolist(), v_[j, : c_[j]].tolist()))
                    for j, i in enumerate(rl.tolist())
                }
            rec.round(
                round_no=holder._rounds,
                mode=holder.mode,
                n_shards=n_shards,
                ops=ops_np,
                keys=keys_np,
                vals=vals_np,
                results=results,
                found=found,
                scans=scans_d,
                scan_cap=plan.scan_cap,
            )
        holder._rounds += 1
        out = RoundOutput(
            results=jnp.asarray(results, VAL_DTYPE),
            found=jnp.asarray(found),
            scan=scan_out,
        )
        holder._maybe_split_shards()
    return out


def execute_scan_delete(holder, lo, hi, cap: int = 128, max_retries: int = 8) -> ScanOutput:
    """ONE fused round that gathers every key in ``[lo_i, hi_i)`` (≤ ``cap``
    smallest per query, stitched across shards) and deletes exactly the
    *emitted* keys, in ONE round.  Legal because the scan linearizes before
    the round's writes: the deletes target exactly the snapshot the scan
    observed.  Keys a truncated page did not emit survive for the caller's
    next chunk (the one-fused-round-per-chunk sweep contract of
    ``SessionIndex``).  Returns the pre-delete ``ScanOutput`` (the evicted
    keys/values)."""
    lo = np.atleast_1d(np.asarray(pull(holder, "plan", lo), np.int64))
    hi = np.atleast_1d(np.asarray(pull(holder, "plan", hi), np.int64))
    assert lo.shape == hi.shape and lo.ndim == 1
    tr = _tr(holder)
    reg = _metrics(holder)
    rec = _rec(holder)
    del_res = del_fnd = None
    with tr.span("round", lanes=int(lo.size), fused="scan_delete"):
        k_, v_, c_, t_ = scan_lanes(
            holder, lo, hi, cap, n_scan_ops=int(lo.size),
            max_retries=max_retries,
        )
        del_keys = k_[k_ != int(EMPTY)]
        if del_keys.size:
            n_shards = holder.n_shards
            with tr.span("router_pack", lanes=int(del_keys.size)) as pack_sp:
                shard = np.searchsorted(holder._splits, del_keys, side="right")
                counts = np.bincount(shard, minlength=n_shards)
                w = _pow2(int(counts.max()))
                ops_sw = np.full((n_shards, w), OP_NOP, np.int32)
                keys_sw = np.zeros((n_shards, w), np.int64)
                shard_sorted, slot_sorted, order = _pack_slots(shard, n_shards)
                ops_sw[shard_sorted, slot_sorted] = OP_DELETE
                keys_sw[shard_sorted, slot_sorted] = del_keys[order]
                _note_pack(holder, pack_sp, n_shards * w, int(del_keys.size))
            tr.shard_marks("point_lanes", counts)
            _note_load(holder, counts)
            if reg is not None:
                reg.inc("point_lanes", int(del_keys.size))
                for s in np.nonzero(counts)[0]:
                    reg.inc_shard("point_lanes", int(counts[s]), int(s))
            holder._ensure_capacity(w)
            res_sw, fnd_sw = run_point_phases(
                holder,
                jnp.asarray(ops_sw),
                jnp.asarray(keys_sw, KEY_DTYPE),
                jnp.zeros((n_shards, w), VAL_DTYPE),
            )
            if rec.enabled:
                slot = np.empty(del_keys.size, np.int64)
                slot[order] = slot_sorted
                del_res = pull(holder, "answers", res_sw)[shard, slot]
                del_fnd = pull(holder, "answers", fnd_sw)[shard, slot]
        if rec.enabled:
            n_r = int(lo.size)
            n_d = int(del_keys.size)
            ops_rec = np.concatenate(
                [
                    np.full((n_r,), int(OP_RANGE), np.int64),
                    np.full((n_d,), int(OP_DELETE), np.int64),
                ]
            )
            keys_rec = np.concatenate([lo, del_keys.astype(np.int64)])
            vals_rec = np.concatenate([hi - lo, np.zeros(n_d, np.int64)])
            results_rec = np.concatenate(
                [
                    c_.astype(np.int64),
                    del_res if del_res is not None else np.zeros(0, np.int64),
                ]
            )
            found_rec = np.concatenate(
                [c_ > 0, del_fnd if del_fnd is not None else np.zeros(0, bool)]
            )
            rec.round(
                round_no=holder._rounds,
                mode=holder.mode,
                n_shards=holder.n_shards,
                ops=ops_rec,
                keys=keys_rec,
                vals=vals_rec,
                results=results_rec,
                found=found_rec,
                scans={
                    i: list(zip(k_[i, : c_[i]].tolist(), v_[i, : c_[i]].tolist()))
                    for i in range(n_r)
                },
                scan_cap=cap,
                fused="scan_delete",
            )
        holder._rounds += 1
        holder._maybe_split_shards()
    return ScanOutput(
        keys=jnp.asarray(k_),
        vals=jnp.asarray(v_),
        count=jnp.asarray(c_),
        truncated=jnp.asarray(t_),
    )
