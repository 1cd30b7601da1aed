"""ABForest: a key-partitioned forest of (a,b)-trees on the unified engine.

The round-based OCC/elimination design is embarrassingly shardable: lanes on
disjoint key ranges never conflict, so partitioning the key space by split
points turns one contended tree into ``n_shards`` independent ones — and the
SPMD formulation makes the partition *free* on device: every shard's round
is the same program, so all shards execute as ONE ``jax.vmap`` of the
round-engine phase kernels.

Since PR 5 this module contains NO round execution of its own: every host
loop (split cascade, rebalance waves, deferred-insert drain, occ
sub-rounds, the optimistic scan retry) lives in ``core/rounds.py`` in its
leading-shard ``(S, wave_w)`` form, shared verbatim with ``ABTree`` (the
S = 1 case).  What remains here is what is genuinely *forest*: the key
partition (split points → router bounds), the shard lifecycle (overflow
splits / restacks), the stacked-state representation, and the per-shard
durability surface (``shard_state`` / ``take_dirty``).

Representation
    All shard trees live in one stacked ``TreeState`` whose every leaf array
    carries a leading shard axis (``keys``: (S, N, b), ``root``: (S,), …).
    This is the layout every later scaling step wants: multi-device
    placement is ``shard_map`` over axis 0, per-shard durability
    (``core/durable.py``'s ``DurableForest``) journals slices of it.

Routing (host, per round — performed inside ``rounds.execute_plan``)
    ``elimination.lane_masks`` classifies the batch's lanes; point lanes go
    to ``shard = searchsorted(splits, key)``; OP_RANGE lanes are split at
    shard boundaries into per-shard sub-lanes.  Sub-lane scan rows are
    stitched back in key order (shards are ordered by key range, rows within
    a shard are ascending, so concatenation is globally sorted).

Ragged-width bucketing contract
    Scan lanes are flat-packed: all shards' sub-lanes concatenate into ONE
    1-D block whose width is pow2(true sub-lane count), and each lane
    gathers through its own shard id on the stacked state — no per-shard
    rectangle, no max-over-shards padding, and a retry re-packs only the
    lanes of still-conflicted shard components.  Point lanes keep the
    (S, W) rectangle (arrival-order packing needs per-shard slots) with
    W = pow2(max per-shard lane count); the repartition actions below
    exist precisely to keep that max — and hence the padding every later
    ``shard_map`` step would ship over the wire — low.  The occ mode's
    duplicate-rank passes re-pack only their live lanes the same way.
    Widths always bucket to powers of two (bounded recompiles), and pad
    waste is observable via the ``router_pack_width`` /
    ``pad_waste_frac`` gauges and per-pack tracer span args.

Load-aware repartitioning
    The router feeds two host-side signals: per-shard routed-lane counts
    (the windowed hot-shard detector behind ``hot_shard_hook``) and a ring
    buffer of recently routed keys (``_note_key_sample``).  With
    ``auto_repartition=True``, a window fire also queues ONE pending
    action; it is consumed at a round boundary when no scan is in flight
    and no restack is running.  The state machine:

        IDLE --window fire (hot frac ≥ max(hot_shard_frac, 1.5/S))--> PENDING
        PENDING --round boundary, quiescent--> MERGE | REBALANCE --> IDLE

    REBALANCE moves the boundary between the hot shard and its colder
    neighbor to the load-weighted quantile of the sampled keys (NOT the
    key-count median — skew lives in traffic, not population): the moved
    range is swept off with fused scan+delete rounds and re-inserted
    through the router, reusing the shard-overflow split machinery.
    MERGE instead retires the coldest shard (window share ≤
    ``cold_shard_frac``) into a neighbor the same way, shrinking S.
    Either way ``repartition_hook(kind, a, b)`` fires after the partition
    changes — the durable layer's journal re-keying point (mirrors
    ``split_hook``).  Overflow splits also prefer the sampled-load
    quantile as their split point, falling back to the key median when
    the sample is thin.  Uniform traffic never reaches PENDING: no shard
    dominates a window, so the partition stays put.

Semantics
    Identical to ``ABTree`` — they run the same engine: a forest round is
    one round, scans linearize before the round's net writes, point lanes
    apply in arrival order per key (stable packing preserves arrival order
    within a shard, and all ops on one key land in one shard).
    ``DictOracle`` remains the single reference: a forest with ANY shard
    count must be oracle-equivalent.

Conflict granularity
    Scan validation is per shard *component* (see the scan phase in
    ``core/rounds.py``): shards linked by a cross-shard lane validate
    jointly, independent shards independently, so a concurrent writer
    (``scan_hook``, modeling other engine replicas) invalidates only the
    components whose versions it bumped.  ``scan_retries`` counts retried
    *lanes* (ops), the honest per-op cost the sharding is buying down.

Shard overflow
    With ``max_keys_per_shard`` set, a shard growing past the threshold is
    split: the median key becomes a new split point, the upper half is swept
    off the hot shard with fused scan+delete rounds, a fresh shard is
    restacked in at the new position, and the swept keys re-insert through
    the normal router (which now targets the new shard).  ``split_hook``
    fires after the restack — the durable layer uses it to re-key its
    per-shard journals and force snapshots of the two affected shards.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rounds
from repro.core.abtree import (
    EMPTY,
    KEY_MIN,
    OP_DELETE,
    OP_FIND,
    OP_INSERT,
    RoundOutput,
    ScanOutput,
    TreeConfig,
    TreeState,
    grow_pool,
    make_tree,
    wave_width,
)
from repro.obs.metrics import (
    MetricsRegistry,
    RegistryBackedCounters,
    engine_collector,
)
from repro.obs.recorder import Recorder
from repro.obs.tracer import NULL_TRACER, pull


def _stack_states(states: List[TreeState]) -> TreeState:
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


class ABForest(RegistryBackedCounters):
    """Key-partitioned forest of batched (a,b)-trees; ``ABTree``-compatible
    round API (``apply_round`` / ``scan_round`` / ``scan_delete_round`` /
    ``scan_stream``), one vmapped round across all shards per call."""

    def __init__(
        self,
        n_shards: int = 2,
        cfg: TreeConfig = TreeConfig(),
        mode: str = "elim",
        *,
        splits=None,
        key_space: Optional[Tuple[int, int]] = None,
        narrow_scan: bool = False,
        narrow: bool = False,
        max_keys_per_shard: Optional[int] = None,
        hot_shard_frac: float = 0.5,
        hot_shard_window: int = 256,
        auto_repartition: bool = False,
        cold_shard_frac: float = 0.05,
    ):
        assert mode in ("elim", "occ")
        assert 2 <= cfg.a <= cfg.b // 2, "(a,b) requires 2 ≤ a ≤ b/2"
        assert n_shards >= 1
        self.cfg = cfg
        self.mode = mode
        self.n_shards = int(n_shards)
        # same contracts as ABTree: narrow_scan = int32 keys/values on the
        # scan gather; narrow = the whole search path (vmapped fused
        # descent+probe kernel + Pallas frontier compaction per shard).
        self.narrow = narrow
        self.narrow_scan = narrow_scan or narrow
        if splits is not None:
            splits = np.asarray(splits, np.int64).reshape(-1)
            assert splits.size == self.n_shards - 1, (
                f"need {self.n_shards - 1} split points, got {splits.size}"
            )
            assert np.all(np.diff(splits) > 0), "splits must be strictly ascending"
        else:
            lo, hi = key_space if key_space is not None else (0, 1 << 63)
            assert hi - lo >= self.n_shards, "key_space too small for n_shards"
            step = (hi - lo) // self.n_shards
            # Python ints: with one shard the step is the whole 63-bit
            # domain, which does not fit an int64 array operand
            splits = np.array(
                [lo + step * i for i in range(1, self.n_shards)], np.int64
            )
        self._splits = splits.astype(np.int64)
        self._rebuild_bounds()
        self.state: TreeState = _stack_states(
            [make_tree(cfg) for _ in range(self.n_shards)]
        )
        self.max_keys_per_shard = max_keys_per_shard
        self._in_split = False
        self._scan_active = 0  # defers shard splits while a scan is in flight
        self._wave_w = wave_width(cfg.capacity)  # structural-wave pad width
        self._scan_frontier = 8  # leaf-frontier pad width (doubles on overflow)
        # optimistic-reader hook, as on ABTree: called between a scan's
        # gather and its per-shard version validation (models update rounds
        # from other engine replicas).
        self.scan_hook = None
        # durability hook, as on ABTree: fires after every executed occ
        # sub-round (DurableForest commits per sub-round in occ mode).
        self.subround_hook = None
        # shard-lifecycle hook: split_hook(s) fires after shard s has been
        # split and the fresh shard restacked at s + 1 (before the swept
        # keys re-insert) — the durable layer's journal re-keying point.
        self.split_hook = None
        # telemetry: the registry is the one store behind the legacy
        # counter properties; the tracer defaults to the strict no-op.
        # The flight recorder is always on (bounded ring; install
        # ``Recorder(enabled=False)`` to opt out).
        self.metrics = MetricsRegistry()
        self.metrics.add_collector(engine_collector(self))
        self.tracer = NULL_TRACER
        self.recorder = Recorder()
        # forest-level counters (device stats stay per shard; see stats()).
        self._rounds = 0
        self._scans = 0
        self._scan_retries = 0
        # hot-shard detection (fed by the router via _note_shard_load):
        # over each window of ``hot_shard_window`` routed lanes, if one
        # shard received ≥ ``hot_shard_frac`` of them the hook fires with
        # (shard, info) — the detection primitive for load-aware
        # re-partitioning (ROADMAP item 2).
        self.hot_shard_hook = None
        self.hot_shard_frac = float(hot_shard_frac)
        self.hot_shard_window = int(hot_shard_window)
        self._shard_load = np.zeros(self.n_shards, np.int64)
        # load-aware repartitioning (see module docstring): a window fire
        # queues ONE pending action; consumed at a quiescent round boundary.
        self.auto_repartition = bool(auto_repartition)
        self.cold_shard_frac = float(cold_shard_frac)
        self._repartition_pending = None
        # shard-lifecycle hook: repartition_hook(kind, a, b) fires after a
        # boundary rebalance ("rebalance", hot, neighbor) or a cold-shard
        # merge ("merge", retired, survivor-after-restack) — the durable
        # layer's journal re-keying point, mirroring split_hook.
        self.repartition_hook = None
        # ring buffer of recently routed keys: the weighted-quantile sample
        # behind load-aware split points and boundary moves.
        self._key_sample = np.zeros(4096, np.int64)
        self._key_sample_n = 0

    # -- unified-engine holder protocol ---------------------------------------

    @property
    def stacked(self) -> TreeState:
        """The (S, …) stacked state the unified engine executes on — for the
        forest this IS the canonical representation."""
        return self.state

    @stacked.setter
    def stacked(self, st: TreeState):
        self.state = st

    # -- routing --------------------------------------------------------------

    def _rebuild_bounds(self):
        self._bounds = (
            [int(KEY_MIN)] + [int(x) for x in self._splits] + [int(EMPTY)]
        )

    def _shard_of(self, keys: np.ndarray) -> np.ndarray:
        return np.searchsorted(self._splits, keys, side="right")

    def _note_shard_load(self, counts):
        """Router callback: accumulate per-shard routed-lane counts and
        fire ``hot_shard_hook(shard, info)`` when one shard dominates the
        current window (see __init__).  The window resets either way once
        full, so sustained skew fires repeatedly and transient skew ages
        out.  With ``auto_repartition`` the fire also queues the pending
        repartition action (window snapshot included) for the next
        quiescent round boundary."""
        if self.hot_shard_hook is None and not self.auto_repartition:
            return
        if self._in_split:
            # sweep/re-insert lanes of a shard split or repartition in
            # progress are internal traffic, not offered load — counting
            # them would make every action look like a fresh hot spot.
            return
        counts = np.asarray(counts, np.int64)
        if counts.size != self._shard_load.size:
            # shard count changed mid-window (shard split): restart clean
            self._shard_load = np.zeros(self.n_shards, np.int64)
        self._shard_load[: counts.size] += counts
        total = int(self._shard_load.sum())
        if total < self.hot_shard_window:
            return
        s = int(np.argmax(self._shard_load))
        frac = float(self._shard_load[s]) / total
        lanes = int(self._shard_load[s])
        win = self._shard_load.copy()
        self._shard_load[:] = 0
        # "hot" is relative to fair share: a fixed fraction reads very
        # differently at S=2 (fair share 0.5) than at S=8 (0.125), so the
        # trip point is the larger of the configured frac and 1.5x fair
        # share — with a bare 0.5 frac a 2-shard forest fires on almost
        # every window and the boundary thrashes.
        thresh = max(self.hot_shard_frac, 1.5 / self.n_shards)
        if frac >= thresh and self.n_shards > 1:
            self.metrics.inc("hot_shard_events", shard=s)
            info = {
                "shard": s,
                "frac": frac,
                "lanes": lanes,
                "window": total,
                "bounds": (self._bounds[s], self._bounds[s + 1]),
                "window_loads": win,
            }
            if self.hot_shard_hook is not None:
                self.hot_shard_hook(s, info)
            if self.auto_repartition:
                self._repartition_pending = info
                if self.recorder.enabled:
                    self.recorder.transition(
                        "repartition_pending",
                        shard=s,
                        frac=round(float(frac), 4),
                        window_loads=[int(x) for x in win],
                    )

    def _note_key_sample(self, keys):
        """Router callback: fold routed keys (point keys and scan lower
        bounds) into the fixed-size ring sample behind ``_load_quantile``."""
        if self._in_split:
            return  # internal sweep/re-insert keys are not offered load
        keys = np.asarray(keys, np.int64).reshape(-1)
        if keys.size == 0:
            return
        cap = self._key_sample.size
        if keys.size >= cap:
            self._key_sample[:] = keys[-cap:]
        else:
            start = self._key_sample_n % cap
            end = start + keys.size
            if end <= cap:
                self._key_sample[start:end] = keys
            else:
                k = cap - start
                self._key_sample[start:] = keys[:k]
                self._key_sample[: end - cap] = keys[k:]
        self._key_sample_n += keys.size

    def _load_quantile(self, lo, hi, q, default=None):
        """q-quantile of the *observed* (routed) keys inside ``[lo, hi)`` —
        the load-weighted split point.  Falls back to ``default`` when the
        sample holds fewer than 32 in-range keys."""
        n = min(self._key_sample_n, self._key_sample.size)
        sel = self._key_sample[:n]
        sel = np.sort(sel[(sel >= lo) & (sel < hi)])
        if sel.size < 32:
            return default
        return int(sel[min(int(q * sel.size), sel.size - 1)])

    # -- public API -----------------------------------------------------------

    def apply_round(self, ops, keys, vals=None, *, scan_cap: int = 128) -> RoundOutput:
        """Apply one round of concurrent ops (semantics of
        ``ABTree.apply_round``, including fused OP_RANGE lanes): the router
        partitions lanes by key range, all shards execute as one vmapped
        round, and per-lane results come back batch-aligned.  Cross-shard
        range lanes are split into per-shard sub-lanes and their rows
        stitched back in key order."""
        plan = rounds.build_plan(ops, keys, vals, scan_cap=scan_cap, holder=self)
        return rounds.execute_plan(self, plan)

    def scan_round(self, lo, hi, cap: int = 128, max_retries: int = 8) -> ScanOutput:
        """Batched range scans (semantics of ``ABTree.scan_round``): per
        query the ≤ ``cap`` smallest keys in ``[lo_i, hi_i)``, ascending,
        stitched across shards in key order."""
        return rounds.execute_scan(self, lo, hi, cap=cap, max_retries=max_retries)

    def scan_delete_round(
        self, lo, hi, cap: int = 128, max_retries: int = 8
    ) -> ScanOutput:
        """ONE fused forest round that gathers every key in ``[lo_i, hi_i)``
        (≤ ``cap`` smallest per query, stitched across shards) and deletes
        exactly the *emitted* keys — keys a truncated page did not emit
        survive for the caller's next chunk, preserving the
        one-fused-round-per-chunk sweep contract of ``SessionIndex``."""
        return rounds.execute_scan_delete(self, lo, hi, cap=cap, max_retries=max_retries)

    def scan_stream(self, lo, hi, cap: int = 128):
        """Stream all (key, value) pairs in ``[lo, hi)`` ascending by
        chaining per-shard cursors: each page queries only the shard holding
        the cursor, so arbitrarily long cross-shard scans stay bounded at
        ``cap`` entries (and one shard's gather) per round."""
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
        """Host-side snapshot of the forest contents (sorted by key)."""
        st = self.state
        keys = pull(self, "items", st.keys)
        vals = pull(self, "items", st.vals)
        leaf = pull(self, "items", st.is_leaf) & pull(self, "items", st.alloc)
        out = {}
        for s in range(self.n_shards):
            for nid in np.nonzero(leaf[s])[0]:
                for j in range(self.cfg.b):
                    k = int(keys[s, nid, j])
                    if k != int(EMPTY):
                        out[k] = int(vals[s, nid, j])
        return dict(sorted(out.items()))

    def shard_state(self, s: int) -> TreeState:
        """One shard's (unstacked) TreeState — for invariant checks and the
        per-shard durability layer (``DurableForest`` journals these
        slices)."""
        return jax.tree_util.tree_map(lambda x: x[s], self.state)

    def take_dirty(self) -> List[np.ndarray]:
        """Per-shard node ids dirtied since the last durable commit (then
        reset) — each shard's journal segment is exactly one of these
        lists, so an untouched shard flushes nothing."""
        d = pull(self, "commit.dirty", self.state.dirty)
        self.state = self.state._replace(dirty=jnp.zeros_like(self.state.dirty))
        return [
            np.nonzero(d[s])[0].astype(np.int32) for s in range(self.n_shards)
        ]

    def stats(self) -> dict:
        """Forest-level stats: device counters summed over shards;
        ``rounds`` counts forest rounds (one vmapped round = 1, however many
        shards it spans) and ``scan_retries`` counts retried *lanes* — the
        per-op conflict cost per-shard validation buys down.  ``host_syncs``
        and ``d2h_bytes`` are read before this call's own pull."""
        syncs, d2h = self.metrics.value("host_syncs"), self.metrics.value("d2h_bytes")
        agg = {
            k: int(pull(self, "stats", v).sum())
            for k, v in self.state.stats._asdict().items()
        }
        agg["host_syncs"] = syncs
        agg["d2h_bytes"] = d2h
        agg["rounds"] = self._rounds
        agg["scans"] = self._scans
        agg["scan_retries"] = self._scan_retries
        return agg

    def stats_per_shard(self) -> List[dict]:
        stats = {k: pull(self, "stats", v) for k, v in self.state.stats._asdict().items()}
        return [
            {k: int(a[s]) for k, a in stats.items()}
            for s in range(self.n_shards)
        ]

    @property
    def splits(self) -> np.ndarray:
        return self._splits.copy()

    # -- shard-overflow splitting ---------------------------------------------

    def _live_key_counts(self) -> np.ndarray:
        st = self.state
        leaf = pull(self, "shard_split", st.is_leaf) & pull(self, "shard_split", st.alloc)
        return np.sum(np.where(leaf, pull(self, "shard_split", st.size), 0), axis=1)

    def _maybe_split_shards(self):
        self._maybe_repartition()
        if self.max_keys_per_shard is None or self._in_split or self._scan_active:
            return
        guard = 0
        while True:
            counts = self._live_key_counts()
            s = int(np.argmax(counts))
            if int(counts[s]) <= self.max_keys_per_shard:
                return
            guard += 1
            assert guard < 64, "shard split diverged"
            self._split_shard(s)

    def _sweep_range(self, lo: int, hi: int) -> Tuple[List[int], List[int]]:
        """Sweep every key in ``[lo, hi)`` off the forest with fused
        scan+delete rounds (the shared move primitive behind overflow
        splits, boundary rebalances and cold-shard merges); returns the
        evicted (keys, vals)."""
        moved_k: List[int] = []
        moved_v: List[int] = []
        cap = max(256, self.cfg.b)
        # The bulk sweep needs a far wider leaf frontier than steady-state
        # point scans; _scan_frontier is sticky, so restore it afterwards or
        # every later scan round pays the sweep's width forever (the wide
        # executable stays jit-cached for the next sweep regardless).
        frontier0 = self._scan_frontier
        try:
            while True:
                out = self.scan_delete_round([lo], [hi], cap=cap)
                n = int(pull(self, "shard_split", out.count)[0])
                moved_k.extend(int(k) for k in pull(self, "shard_split", out.keys)[0, :n])
                moved_v.extend(int(v) for v in pull(self, "shard_split", out.vals)[0, :n])
                if not bool(pull(self, "shard_split", out.truncated)[0]):
                    break
        finally:
            self._scan_frontier = frontier0
        return moved_k, moved_v

    def _reinsert(self, moved_k: List[int], moved_v: List[int]):
        bs = 1024
        for i in range(0, len(moved_k), bs):
            ck = moved_k[i : i + bs]
            cv = moved_v[i : i + bs]
            self.apply_round(np.full(len(ck), OP_INSERT, np.int32), ck, cv)

    def _split_shard(self, s: int):
        """Split shard ``s``: sweep the upper part off with fused
        scan+delete rounds, restack with a fresh shard at ``s + 1``, and
        re-insert the swept keys through the router (which now targets the
        new shard).  The split point prefers the load-weighted quantile of
        observed keys (skew-aware: balances *traffic*, not population) and
        falls back to the shard's key median when the sample is thin."""
        self._in_split = True
        try:
            st = self.state
            leaf = pull(self, "shard_split", st.is_leaf)[s] & pull(self, "shard_split", st.alloc)[s]
            krows = pull(self, "shard_split", st.keys)[s][leaf]
            ks = krows[krows != int(EMPTY)]
            if ks.size < 2:
                return
            ks.sort()
            m = int(ks[ks.size // 2])  # > ks[0] ≥ bounds[s]; < bounds[s+1]
            lm = self._load_quantile(self._bounds[s], self._bounds[s + 1], 0.5)
            if lm is not None and int(ks[0]) < lm <= int(ks[-1]):
                m = lm  # both sides stay non-empty
            hi_bound = self._bounds[s + 1]
            moved_k, moved_v = self._sweep_range(m, hi_bound)
            per = [self.shard_state(i) for i in range(self.n_shards)]
            per.insert(s + 1, make_tree(self.cfg))
            self.state = _stack_states(per)
            self.n_shards += 1
            self._splits = np.insert(self._splits, s, m)
            self._rebuild_bounds()
            # keep telemetry attribution aligned with the restack: shift
            # per-shard metric cells ≥ s+1 up one, reset the load window.
            self.metrics.inc("shard_splits", shard=s)
            self.metrics.insert_shard(s + 1)
            self._shard_load = np.zeros(self.n_shards, np.int64)
            if self.recorder.enabled:
                self.recorder.transition(
                    "split", shard=s, split_key=int(m),
                    n_shards=self.n_shards, moved=len(moved_k),
                )
            if self.split_hook is not None:
                self.split_hook(s)
            self._reinsert(moved_k, moved_v)
        finally:
            self._in_split = False

    # -- load-aware repartitioning (see module docstring) -----------------------

    def _maybe_repartition(self):
        """Consume the pending repartition action, if any, at a quiescent
        round boundary: prefer retiring a cold shard (window share ≤
        ``cold_shard_frac``), otherwise move the hot boundary."""
        info = self._repartition_pending
        if info is None or self._in_split or self._scan_active:
            return
        self._repartition_pending = None
        if self.n_shards < 2:
            return
        win = np.asarray(info.get("window_loads"), np.int64)
        if win.size != self.n_shards:
            return  # shard count changed since detection: signal is stale
        s = int(info["shard"])
        total = int(win.sum())
        c = int(np.argmin(win))
        # engine-track span (``shard=`` would route it onto the per-shard
        # attribution track): the hot shard rides as a plain arg instead.
        with self.tracer.span("repartition", hot_shard=s, hot_frac=info["frac"]) as sp:
            if (
                c != s
                and total > 0
                and float(win[c]) / total <= self.cold_shard_frac
                and self._merge_cold(c)
            ):
                sp.note(action="merge", cold=c)
                # the merge restacked the shards: the hot shard's cell is
                # s - 1 when the retired shard sat below it.
                self.metrics.inc("repartitions", shard=s if c > s else s - 1)
                if self.recorder.enabled:
                    self.recorder.transition(
                        "repartition", action="merge", cold=c, hot_shard=s,
                        n_shards=self.n_shards,
                    )
            elif self._rebalance_boundary(s, win):
                sp.note(action="rebalance")
                self.metrics.inc("repartitions", shard=s)
                if self.recorder.enabled:
                    self.recorder.transition(
                        "repartition", action="rebalance", hot_shard=s,
                        n_shards=self.n_shards,
                    )
            else:
                sp.note(action="noop")
                if self.recorder.enabled:
                    self.recorder.transition(
                        "repartition", action="noop", hot_shard=s,
                        n_shards=self.n_shards,
                    )

    def _rebalance_boundary(self, s: int, win: np.ndarray) -> bool:
        """Move the boundary between hot shard ``s`` and its colder
        neighbor ``t`` to the load-weighted quantile that would even their
        observed loads: sweep the moved range off ``s``, shift the split
        point, re-insert through the router (keys now land on ``t``)."""
        nbrs = [t for t in (s - 1, s + 1) if 0 <= t < self.n_shards]
        if not nbrs:
            return False
        t = min(nbrs, key=lambda i: int(win[i]))
        load_s, load_t = int(win[s]), int(win[t])
        if load_s <= load_t or load_s == 0:
            return False
        phi = (load_s - load_t) / (2.0 * load_s)  # load share to hand over
        lo_b, hi_b = self._bounds[s], self._bounds[s + 1]
        q = (1.0 - phi) if t == s + 1 else phi
        m = self._load_quantile(lo_b, hi_b, q)
        if m is None or not (lo_b < m < hi_b):
            return False
        self._in_split = True
        try:
            if t == s + 1:
                moved_k, moved_v = self._sweep_range(m, hi_b)
                self._splits[s] = m
            else:
                moved_k, moved_v = self._sweep_range(lo_b, m)
                self._splits[s - 1] = m
            self._rebuild_bounds()
            self.metrics.inc("boundary_moves", shard=s)
            self._shard_load = np.zeros(self.n_shards, np.int64)
            if self.repartition_hook is not None:
                self.repartition_hook("rebalance", s, t)
            self._reinsert(moved_k, moved_v)
        finally:
            self._in_split = False
        return True

    def _merge_cold(self, c: int) -> bool:
        """Retire cold shard ``c`` into a neighbor: sweep its whole range
        off, drop the shard from the stack and the boundary between the
        pair, re-insert through the router (keys land on the survivor)."""
        nbrs = [t for t in (c - 1, c + 1) if 0 <= t < self.n_shards]
        if not nbrs:
            return False
        t = nbrs[0] if len(nbrs) == 1 else min(
            nbrs, key=lambda i: int(self._live_key_counts()[i])
        )
        if self.max_keys_per_shard is not None:
            counts = self._live_key_counts()
            if int(counts[c]) + int(counts[t]) > self.max_keys_per_shard:
                return False  # survivor would overflow: not worth merging
        self._in_split = True
        try:
            moved_k, moved_v = self._sweep_range(
                self._bounds[c], self._bounds[c + 1]
            )
            per = [self.shard_state(i) for i in range(self.n_shards)]
            per.pop(c)
            self.state = _stack_states(per)
            self.n_shards -= 1
            self._splits = np.delete(self._splits, c - 1 if t == c - 1 else c)
            self._rebuild_bounds()
            # re-key BEFORE attributing: remove_shard(c) pops cell c and
            # shifts the cells above it down, so incrementing the survivor
            # first would land on cell c when t == c + 1 (the survivor's
            # post-restack index equals the retired index) and be orphaned
            # by the pop.  Mirror of insert_shard's re-keying on splits.
            self.metrics.remove_shard(c)
            self.metrics.inc("shard_merges", shard=t if t < c else t - 1)
            self._shard_load = np.zeros(self.n_shards, np.int64)
            if self.repartition_hook is not None:
                self.repartition_hook("merge", c, t if t < c else t - 1)
            self._reinsert(moved_k, moved_v)
        finally:
            self._in_split = False
        return True

    # -- pool management --------------------------------------------------------

    def _ensure_capacity(self, need_nodes: int):
        """Grow every shard's pool when the *fullest* shard has fewer than
        ``need + slack`` free nodes (stacked pools share one capacity).
        The 2·wave_w term keeps each pool large enough for a full-width
        split wave's allocation (see ``ABTree._ensure_capacity``)."""
        need = 2 * need_nodes + 4 * self.cfg.max_height + 2 * self._wave_w + 8
        n_alloc = int(pull(self, "capacity", jnp.max(jnp.sum(self.state.alloc, axis=1))))
        cap = self.cfg.capacity
        if cap - n_alloc >= need:
            return
        self._grow(max(cap * 2, cap + need))

    def _grow(self, new_cap: int):
        # node axis is 1 on the stacked state (axis 0 is the shard axis)
        self.state = grow_pool(self.state, new_cap - self.cfg.capacity, axis=1)
        self.cfg = self.cfg._replace(capacity=new_cap)
        self._wave_w = wave_width(new_cap)


def check_forest_invariants(forest: ABForest) -> None:
    """Per-shard structural invariants plus the forest's own: every key in
    shard ``s`` lies within ``[bounds[s], bounds[s+1])``."""
    from repro.core.oracle import check_invariants, tree_contents

    for s in range(forest.n_shards):
        st = forest.shard_state(s)
        check_invariants(st, forest.cfg)
        lo, hi = forest._bounds[s], forest._bounds[s + 1]
        for k in tree_contents(st, forest.cfg):
            assert lo <= k < hi, (
                f"shard {s}: key {k} outside shard range [{lo}, {hi})"
            )
