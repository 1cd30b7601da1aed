"""YCSB workload analogs on the batched tree index.

  A (paper Fig 16): 50% reads / 50% writes where a "write" reads the row
    pointer from the index then mutates the row payload (NOT the index) —
    index traffic is find-dominated, Zipf 0.5.
  E: 95% short range scans / 5% inserts (Zipf start keys) — the scan-heavy
    mix.  Runs FUSED by default: each mixed batch is ONE ``apply_round``
    call (scans linearized before the round's writes by the round engine).
    ``--scan-path split`` selects the legacy baseline (host-side
    ``split_scan_round`` → one scan round + one point round per batch, 2×
    the round count); ``--scan-path both`` (the default) A/Bs the two and
    reports the round counts side by side.

``--shards K`` (K ≥ 1) switches the index to the key-partitioned
``ABForest`` and A/Bs it against the 1-shard forest baseline:

  A: reads execute as *validated optimistic point-reads* (the paper's
     ``searchLeaf`` version discipline, batched) while a concurrent writer
     replica — modeled by the forest's ``scan_hook`` — churns Zipf-hot keys
     between each round's gather and validation.  The single tree
     validates the whole batch's touched set, so one hot write retries
     every lane; the forest validates per shard, so only the conflicted
     shards' lanes retry.  ``conflict_retries`` counts retried lanes; with
     K > 1 the run fails unless retries/op is strictly below the 1-shard
     baseline on the skewed workload.
  E: the same fused mixed rounds, with cross-shard OP_RANGE lanes split at
     shard boundaries and executed as one vmapped round.

``--narrow`` asserts the workload's keys/values fit int32 (true for every
YCSB config here) and routes the whole search path through the
``kernels/tree_descend`` + ``kernels/range_scan`` device kernels (fused
descent+probe, Pallas frontier compaction, kernel rank-select) instead of
the int64 jnp references — the A/B for the device-resident search path.

``python benchmarks/ycsb.py [--workload A|E] [--scan-path fused|split|both]
[--shards K] [--narrow] [--trace PATH] [--quick]``

``--trace PATH`` installs a phase ``Tracer`` on every holder the section
builds and writes Chrome trace-event JSON (Perfetto-loadable; or render a
phase/shard table with ``python -m repro.obs.report PATH``).

``--audit PATH`` re-runs the workload's forest leg with the flight
recorder installed, writes the semantic audit log (JSONL) to PATH, and
replays it through the linearizability witness
(``python -m repro.obs.witness PATH``); workload A also gates the
recorder's measured overhead at ≤ 5% ops/s vs a disabled-recorder twin.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

if __package__ in (None, ""):  # `python benchmarks/ycsb.py` (not -m)
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from repro.configs.abtree import TPU8
from repro.core import ABForest, ABTree, OP_DELETE, OP_FIND, OP_INSERT
from repro.data.workloads import (
    WorkloadConfig,
    prefill_tree,
    split_scan_round,
    ycsb_e_stream,
    zipf_keys,
)

from benchmarks.common import emit

# set by main(trace=...): every holder the section builds gets this tracer
# installed, so one --trace run captures all of the section's rounds.
_TRACER = None
# set by _run_audit: the audit leg's flight recorder.  Unlike the tracer
# this is only ever installed for ONE holder at a time — the witness
# replays the ring as a single sequential history, so interleaving rounds
# from two different trees would be an (incorrectly) rejected history.
_RECORDER = None


def _instrument(holder):
    if _TRACER is not None:
        holder.tracer = _TRACER
    if _RECORDER is not None:
        holder.recorder = _RECORDER
    return holder


def _run_a(quick=False, narrow=False):
    key_range = 4096
    batch = 512
    rounds = 10 if quick else 30
    rows = np.zeros(key_range, np.int64)
    rng = np.random.default_rng(3)
    for mode in ("elim", "occ"):
        tree = _instrument(
            ABTree(TPU8._replace(capacity=4 * key_range), mode=mode, narrow=narrow)
        )
        prefill_tree(tree, WorkloadConfig(key_range=key_range, seed=1))
        keys = zipf_keys(rng, batch * rounds, key_range, 0.5)
        is_write = rng.random(batch * rounds) < 0.5
        tree.apply_round([OP_FIND] * batch, keys[:batch], [0] * batch)  # warm
        t0 = time.perf_counter()
        for r in range(rounds):
            k = keys[r * batch : (r + 1) * batch]
            w = is_write[r * batch : (r + 1) * batch]
            out = tree.apply_round(np.full(batch, OP_FIND, np.int32), k, np.zeros(batch, np.int64))
            # writes mutate the ROW (host payload), not the index
            res = np.asarray(out.results)
            hit = np.asarray(out.found) & w
            rows[k[hit] % key_range] += res[hit] % 7
        dt = time.perf_counter() - t0
        n_ops = batch * rounds
        emit(
            f"ycsb_a.{mode}{'.narrow' if narrow else ''}",
            dt / n_ops * 1e6,
            f"tx/s={n_ops/dt:.0f}",
            ops_per_s=n_ops / dt,
            rounds=rounds,
        )


def run_a_forest(shards, quick=False, key_range=4096, batch=256, narrow=False,
                 dist="zipf", repartition=False):
    """YCSB-A on an ``ABForest``: reads as validated optimistic point-reads
    under a concurrent writer replica (the ``scan_hook``).  Returns metrics
    incl. ``conflict_retries`` = retried lanes (per-shard validation only
    retries the shards the writer actually touched).

    ``dist`` picks the read-key distribution: "zipf" (s=0.5, the skewed
    leg) or "uniform" (the scaling leg — per-shard lane groups stay even,
    so s4 ≥ s1 ops/s is the ragged-batching gate).  ``repartition`` turns
    on the forest's load-aware boundary moves (the zipf leg's fix)."""
    rounds_n = 10 if quick else 30
    n_warm = 8  # adaptive warm budget (see below)
    n_total = rounds_n + n_warm
    wl = WorkloadConfig(key_range=key_range, seed=1)
    forest = _instrument(ABForest(
        n_shards=shards,
        cfg=TPU8._replace(capacity=4 * key_range),
        mode="elim",
        key_space=(0, key_range),
        narrow=narrow,
        auto_repartition=repartition,
    ))
    prefill_tree(forest, wl)
    rng = np.random.default_rng(3)
    n_w = 8  # hot-key writes per round (the contended fraction)
    if dist == "uniform":
        reads = rng.integers(0, key_range, batch * n_total).astype(np.int64)
    else:
        reads = zipf_keys(rng, batch * n_total, key_range, 0.5)
    writes = zipf_keys(rng, n_w * n_total, key_range, 1.2)
    wvals = rng.integers(0, 1 << 30, n_w * n_total).astype(np.int64)
    # writer round: delete+insert per hot key collapses to ONE net leaf
    # write (overwrite / insert) that always bumps the leaf version.
    w_ops = np.concatenate(
        [np.full(n_w, OP_DELETE, np.int32), np.full(n_w, OP_INSERT, np.int32)]
    )
    pending = {}

    def writer_replica():
        w = pending.pop("w", None)
        if w is not None:
            wk, wv = w
            forest.apply_round(
                w_ops,
                np.concatenate([wk, wk]),
                np.concatenate([np.zeros(n_w, np.int64), wv]),
            )

    forest.scan_hook = writer_replica

    def one_round(r):
        k = reads[r * batch : (r + 1) * batch]
        pending["w"] = (
            writes[r * n_w : (r + 1) * n_w],
            wvals[r * n_w : (r + 1) * n_w],
        )
        forest.scan_round(k, k + 1, cap=1)

    # warm adaptively: the ragged round widths (retry re-gathers, writer
    # point blocks, structural waves) each jit-compile on first sight, so
    # run real rounds until one executes without a compile spike — then
    # every width the steady state touches is cached outside the timed
    # region.  Pre-compile the common retry scan widths explicitly too.
    forest.scan_hook = None
    for w_ in (32, 64, 128):
        kw = reads[:w_]
        forest.scan_round(kw, kw + 1, cap=1)
    forest.scan_hook = writer_replica
    t_best = None
    for w_r in range(rounds_n, n_total):
        t0 = time.perf_counter()
        one_round(w_r)
        t_r = time.perf_counter() - t0
        if t_best is not None and t_r <= 1.5 * t_best:
            break  # no compile landed in this round: warmed up
        t_best = t_r if t_best is None else min(t_best, t_r)
    base_retries = forest.stats()["scan_retries"]
    t0 = time.perf_counter()
    for r in range(rounds_n):
        one_round(r)
    dt = time.perf_counter() - t0
    forest.scan_hook = None
    retries = forest.stats()["scan_retries"] - base_retries
    n_ops = batch * rounds_n
    return {
        "shards": shards,
        "dist": dist,
        "ops_per_s": n_ops / dt,
        "us_per_op": dt / n_ops * 1e6,
        "conflict_retries": retries,
        "retries_per_op": retries / n_ops,
        "rounds": rounds_n,
        "repartitions": int(forest.metrics.snapshot()["counters"].get("repartitions", 0)),
    }


def run_e_forest(shards, quick=False, key_range=4096, batch=256, cap=128, narrow=False):
    """YCSB-E fused mixed rounds on an ``ABForest`` (cross-shard OP_RANGE
    lanes split at shard boundaries, one vmapped round per batch)."""
    rounds_n = 6 if quick else 20
    wl = WorkloadConfig(
        key_range=key_range, dist="zipf", zipf_s=1.0, batch=batch, seed=5
    )
    forest = _instrument(ABForest(
        n_shards=shards,
        cfg=TPU8._replace(capacity=4 * key_range),
        mode="elim",
        key_space=(0, key_range),
        narrow=narrow,
    ))
    prefill_tree(forest, wl)
    # Warm adaptively on a prefix of the stream, then time its
    # CONTINUATION — replaying warm batches on the (now mutated) forest
    # shifts round widths and lands fresh compiles inside the timed
    # region, which is where this leg's run-to-run 10x swings came from.
    n_warm = 8
    batches = list(ycsb_e_stream(wl, n_warm + rounds_n))
    t_best = None
    for ops, keys, vals in batches[:n_warm]:
        t0 = time.perf_counter()
        forest.apply_round(ops, keys, vals, scan_cap=cap)
        t_r = time.perf_counter() - t0
        if t_best is not None and t_r <= 1.5 * t_best:
            break  # no compile landed in this round: warmed up
        t_best = t_r if t_best is None else min(t_best, t_r)
    n_ops = n_items = 0
    dts = []
    for ops, keys, vals in batches[n_warm:]:
        t0 = time.perf_counter()
        out = forest.apply_round(ops, keys, vals, scan_cap=cap)
        dts.append(time.perf_counter() - t0)
        n_items += int(np.sum(np.asarray(out.scan.count)))
        n_ops += len(ops)
    # median x count: one straggler round (late compile, scheduler
    # spike) must not own the section's committed ops/s record.
    dt = float(np.median(dts)) * len(dts)
    st = forest.stats()
    return {
        "shards": shards,
        "ops_per_s": n_ops / dt,
        "items_per_s": n_items / dt,
        "us_per_op": dt / n_ops * 1e6,
        "rounds": rounds_n,
        "conflict_retries": st["scan_retries"],
    }


def _run_a_sharded(shards, quick=False, narrow=False, dist="zipf",
                   repartition=False):
    per = {}
    sfx = ".narrow" if narrow else ""
    if dist != "zipf":
        sfx += f".{dist}"
    for k in sorted({1, shards}):
        m = run_a_forest(k, quick=quick, narrow=narrow, dist=dist,
                         repartition=repartition)
        per[k] = m
        emit(
            f"ycsb_a.forest.s{k}{sfx}",
            m["us_per_op"],
            f"tx/s={m['ops_per_s']:.0f};conflict_retries={m['conflict_retries']};"
            f"retries/op={m['retries_per_op']:.3f}",
            **m,
        )
    if shards > 1:
        r1, rk = per[1]["retries_per_op"], per[shards]["retries_per_op"]
        if rk >= r1:  # hard error, not assert: must survive python -O
            raise RuntimeError(
                f"forest({shards}) retries/op {rk:.3f} not strictly below "
                f"1-shard baseline {r1:.3f}"
            )
        o1, ok = per[1]["ops_per_s"], per[shards]["ops_per_s"]
        if dist == "uniform" and shards >= 4 and ok < o1:
            # the ragged-batching gate: sharding must pay in wall-clock,
            # not just in retries (the s1→s4 cliff can never return).
            raise RuntimeError(
                f"forest({shards}) uniform ops/s {ok:.0f} below 1-shard "
                f"baseline {o1:.0f} — sharding lost throughput"
            )
        emit(
            f"ycsb_a.forest.s{shards}_vs_s1{sfx}",
            0.0,
            f"retries/op={rk:.3f} vs {r1:.3f} ({r1 / max(rk, 1e-9):.2f}x fewer);"
            f"ops/s={ok:.0f} vs {o1:.0f} ({ok / max(o1, 1e-9):.2f}x)",
            retries_per_op_sharded=rk,
            retries_per_op_single=r1,
            ops_per_s_sharded=ok,
            ops_per_s_single=o1,
        )


def _run_e_sharded(shards, quick=False, narrow=False):
    per = {}
    sfx = ".narrow" if narrow else ""
    for k in sorted({1, shards}):
        m = run_e_forest(k, quick=quick, narrow=narrow)
        per[k] = m
        emit(
            f"ycsb_e.forest.s{k}{sfx}",
            m["us_per_op"],
            f"tx/s={m['ops_per_s']:.0f};items/s={m['items_per_s']:.0f};"
            f"conflict_retries={m['conflict_retries']}",
            **m,
        )
    if shards > 1:
        emit(
            f"ycsb_e.forest.s{shards}_vs_s1{sfx}",
            0.0,
            f"speedup={per[1]['us_per_op'] / per[shards]['us_per_op']:.2f}x",
            us_per_op_sharded=per[shards]["us_per_op"],
            us_per_op_single=per[1]["us_per_op"],
        )


def _run_e_path(mode, paths, wl, rounds, cap, narrow=False):
    """Run YCSB-E in one tree mode across ``paths``, batch-INTERLEAVED on
    one tree per path; returns ``{path: metrics}``.

    fused: one ``apply_round`` per mixed batch (the round engine's fused
    scan+update pipeline).  split: the legacy host-split baseline — one
    ``scan_round`` + one ``apply_round`` per batch (2 rounds/batch).

    The paths are timed interleaved (batch i on every path before batch
    i+1) and aggregated as median-of-batches × batches: the fused/split
    gate compares estimates whose true ratio sits a few percent above
    1.0, so sequential timing — where heap growth, GC epochs and CPU
    clocks drift between the two passes — made the ratio a coin flip."""
    key_range = wl.key_range
    trees = {
        path: _instrument(
            ABTree(
                TPU8._replace(capacity=4 * key_range), mode=mode,
                narrow=narrow,
            )
        )
        for path in paths
    }
    stats = {
        path: {"dts": [], "ops": 0, "items": 0, "rounds": 0}
        for path in paths
    }

    def _one(path, ops, keys, vals, timed):
        tree = trees[path]
        st = stats[path]
        t0 = time.perf_counter()
        if path == "fused":
            out = tree.apply_round(ops, keys, vals, scan_cap=cap)
            dt = time.perf_counter() - t0
            items = int(np.sum(np.asarray(out.scan.count)))
            n_rounds = 1
        else:
            (lo, hi), point = split_scan_round(ops, keys, vals)
            out = tree.scan_round(lo, hi, cap=cap)
            tree.apply_round(*point)
            dt = time.perf_counter() - t0
            items = int(np.sum(np.asarray(out.count)))
            n_rounds = 2
        if timed:
            st["dts"].append(dt)
            st["ops"] += len(ops)
            st["items"] += items
            st["rounds"] += n_rounds

    # Timed rounds CONTINUE the stream past the warm prefix rather than
    # replaying it: a replay re-runs the same batches against a larger
    # tree, so the ragged widths shift and fresh jit compiles land in the
    # timed region.  Advancing the stream keeps the width mix evolving
    # continuously out of the warm state.
    n_warm = 10
    batches = list(ycsb_e_stream(wl, n_warm + rounds))
    for path in paths:
        prefill_tree(trees[path], wl)
        # pre-compile the small point-block widths the mixed rounds bucket
        # to (the ~5% insert fraction flaps across pow2 buckets round to
        # round); FIND-only rounds hit the compiled pipeline w/o mutating.
        for w_ in (8, 16, 32):
            trees[path].apply_round(
                np.full(w_, OP_FIND, np.int32),
                np.arange(w_, dtype=np.int64),
                np.zeros(w_, np.int64),
            )
        for ops, keys, vals in batches[:n_warm]:
            _one(path, ops, keys, vals, timed=False)
    for ops, keys, vals in batches[n_warm:]:
        for path in paths:
            _one(path, ops, keys, vals, timed=True)
    out = {}
    for path in paths:
        st = stats[path]
        dt = float(np.median(st["dts"])) * len(st["dts"])
        out[path] = {
            "ops_per_s": st["ops"] / dt,
            "items_per_s": st["items"] / dt,
            "rounds": st["rounds"],
            "scan_retries": trees[path].stats()["scan_retries"],
            "us_per_op": dt / st["ops"] * 1e6,
            "batch_dts": st["dts"],
        }
    return out


def _run_e(quick=False, scan_path="both", narrow=False):
    key_range = 4096
    batch = 256
    # quick still times 16 batches: the occ fused-vs-split gate compares
    # two median-of-batches estimates whose true ratio sits only a few
    # percent above 1.0 — 6 batches left it a coin flip.
    rounds = 16 if quick else 20
    cap = 128
    wl = WorkloadConfig(key_range=key_range, dist="zipf", zipf_s=1.0, batch=batch, seed=5)
    paths = ("fused", "split") if scan_path == "both" else (scan_path,)
    for mode in ("elim", "occ"):
        per_path = _run_e_path(mode, paths, wl, rounds, cap, narrow=narrow)
        for path in paths:
            m = per_path[path]
            emit(
                f"ycsb_e.{mode}.{path}{'.narrow' if narrow else ''}",
                m["us_per_op"],
                f"tx/s={m['ops_per_s']:.0f};items/s={m['items_per_s']:.0f};"
                f"rounds={m['rounds']};scan_retries={m['scan_retries']}",
                ops_per_s=m["ops_per_s"],
                rounds=m["rounds"],
                conflict_retries=m["scan_retries"],
            )
        if scan_path == "both":
            rf, rs = per_path["fused"]["rounds"], per_path["split"]["rounds"]
            if rf >= rs:  # hard error, not assert: must survive python -O
                raise RuntimeError(
                    f"fused rounds {rf} not below split baseline {rs}"
                )
            # Paired estimator: batch i ran on both trees back to back, so
            # the per-pair ratio cancels batch difficulty (subround count,
            # scan spans) and the median cancels scheduler spikes.
            speedup = float(np.median(
                np.asarray(per_path["split"]["batch_dts"])
                / np.asarray(per_path["fused"]["batch_dts"])
            ))
            if mode == "occ" and speedup < 0.9:
                # the ragged duplicate-rank gate: with already-satisfied
                # lanes masked out of each occ sub-pass, fusing runs at
                # parity-or-better with the 2-rounds-per-batch host split
                # (measured ~1.0x; the old full-width sub-pass penalty
                # this guards against costs well over 10%).  The floor
                # sits below the ±5% noise of a shared host; the committed
                # BENCH_ycsb_e.json speedup_x record is the ≥ 1.0x anchor
                # the --check gate compares against.
                raise RuntimeError(
                    f"occ fused {speedup:.2f}x vs split — full-width "
                    f"sub-pass padding regressed the fused occ path"
                )
            emit(
                f"ycsb_e.{mode}.fused_vs_split",
                0.0,
                f"rounds_fused={rf};rounds_split={rs};speedup={speedup:.2f}x",
                rounds_fused=rf,
                rounds_split=rs,
                speedup_x=speedup,
            )


def _recorder_overhead_ratio(shards, narrow=False, rounds=24):
    """Paired in-bench recorder-overhead estimate on the YCSB-A round mix
    (validated scan-reads + a hot-key writer block): one warmed forest,
    each iteration runs the SAME batch recorder-off then recorder-on, and
    the estimate is the median of the per-pair time ratios (on/off).
    Pairing cancels the host drift that makes sequential whole-leg A/Bs a
    coin flip — the same estimator ``_run_e_path`` uses for fused/split."""
    from repro.obs.recorder import Recorder

    key_range, batch, n_w = 4096, 256, 8
    forest = ABForest(
        n_shards=shards,
        cfg=TPU8._replace(capacity=4 * key_range),
        mode="elim",
        key_space=(0, key_range),
        narrow=narrow,
    )
    prefill_tree(forest, WorkloadConfig(key_range=key_range, seed=1))
    rng = np.random.default_rng(7)
    n_total = rounds + 8
    reads = zipf_keys(rng, batch * n_total, key_range, 0.5)
    writes = zipf_keys(rng, n_w * n_total, key_range, 1.2)
    wvals = rng.integers(0, 1 << 30, n_w * n_total).astype(np.int64)
    w_ops = np.concatenate(
        [np.full(n_w, OP_DELETE, np.int32), np.full(n_w, OP_INSERT, np.int32)]
    )

    def one(r):
        kr = reads[r * batch : (r + 1) * batch]
        wk = writes[r * n_w : (r + 1) * n_w]
        wv = wvals[r * n_w : (r + 1) * n_w]
        forest.scan_round(kr, kr + 1, cap=1)
        forest.apply_round(
            w_ops,
            np.concatenate([wk, wk]),
            np.concatenate([np.zeros(n_w, np.int64), wv]),
        )

    for r in range(8):  # warm every width the mix touches
        one(r)
    on_rec = Recorder(capacity=1_000_000)
    off_rec = Recorder(enabled=False)
    dts = {False: [], True: []}
    for r in range(8, n_total):
        # off-then-on with identical inputs: delete+insert of the same hot
        # keys nets to the same state, so the pair stays like-for-like
        for enabled in (False, True):
            forest.recorder = off_rec if not enabled else on_rec
            t0 = time.perf_counter()
            one(r)
            dts[enabled].append(time.perf_counter() - t0)
    return float(np.median(np.asarray(dts[True]) / np.asarray(dts[False])))


def _run_audit(path, workload="A", shards=4, quick=False, narrow=False):
    """``--audit PATH`` leg: re-run the workload's forest leg with a
    high-capacity flight recorder installed from construction (the witness
    replays from the EMPTY tree, so prefill must be on the ring too),
    export the audit log to ``path``, and replay it through the
    linearizability witness — a ``WitnessError`` fails the run non-zero.

    Workload A additionally gates the recorder's cost at ≤ 5%: the paired
    on/off estimator ``_recorder_overhead_ratio`` must report ≤ 1.05x."""
    global _RECORDER
    from repro.obs.recorder import Recorder
    from repro.obs.witness import check_file

    runner = run_a_forest if workload.upper() == "A" else run_e_forest
    k = max(shards, 1)
    rec = Recorder(capacity=1_000_000)
    _RECORDER = rec
    try:
        runner(k, quick=quick, narrow=narrow)
    finally:
        _RECORDER = None
    rec.export(path)
    rep = check_file(path)  # raises WitnessError on an illegal history
    gate = workload.upper() == "A"
    ratio = _recorder_overhead_ratio(k, narrow=narrow) if gate else None
    emit(
        f"ycsb_audit.{workload.lower()}.s{k}{'.narrow' if narrow else ''}",
        0.0,
        f"witness_rounds={rep.rounds};lanes={rep.lanes};"
        f"eliminated={rep.eliminated}"
        + (f";recorder_overhead_x={ratio:.3f}" if gate else ""),
        witness_rounds=rep.rounds,
        witness_lanes=rep.lanes,
        witness_eliminated=rep.eliminated,
        **({"recorder_overhead_x": ratio} if gate else {}),
    )
    print(f"# wrote audit: {path} — {rep.summary()}")
    if gate and ratio > 1.05:  # hard error: must survive python -O
        raise RuntimeError(
            f"recorder overhead gate: paired on/off round-time ratio "
            f"{ratio:.3f}x above the 1.05x ceiling"
        )


def main(quick=False, workload="A", scan_path="both", shards=0, narrow=False,
         trace=None, dist="zipf", repartition=False, audit=None):
    global _TRACER
    if trace:
        from repro.obs.tracer import Tracer

        _TRACER = Tracer()
    try:
        if workload.upper() == "A":
            if shards:
                _run_a_sharded(shards, quick=quick, narrow=narrow, dist=dist,
                               repartition=repartition)
            else:
                _run_a(quick=quick, narrow=narrow)
        elif workload.upper() == "E":
            if shards:
                _run_e_sharded(shards, quick=quick, narrow=narrow)
            else:
                _run_e(quick=quick, scan_path=scan_path, narrow=narrow)
        else:
            raise ValueError(f"unknown YCSB workload {workload!r} (A or E)")
        if audit:
            _run_audit(audit, workload=workload, shards=shards or 1,
                       quick=quick, narrow=narrow)
    finally:
        if trace:
            from repro.obs.trace_export import write_chrome_trace

            write_chrome_trace(trace, _TRACER)
            print(f"# wrote trace: {trace} ({len(_TRACER.events)} events)")
            _TRACER = None


if __name__ == "__main__":
    from repro.compile_cache import use_persistent_cache

    use_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="A", choices=["A", "E", "a", "e"])
    ap.add_argument(
        "--scan-path",
        default="both",
        choices=["fused", "split", "both"],
        help="workload E execution: 'fused' (mixed rounds, the engine's "
        "default path), 'split' (legacy 2-rounds-per-batch baseline), or "
        "'both' (default) — runs fused then split and reports the A/B "
        "round-count comparison",
    )
    ap.add_argument(
        "--shards",
        type=int,
        default=0,
        choices=[0, 1, 2, 4, 8],
        help="run the workload on a key-partitioned ABForest with this many "
        "shards, A/B'd against the 1-shard forest baseline (0 = legacy "
        "single-tree path).  Workload A fails unless the sharded run has "
        "strictly fewer conflict retries per op than the baseline",
    )
    ap.add_argument(
        "--narrow",
        action="store_true",
        help="route the search path through the int32 device kernels "
        "(fused descent+probe, Pallas frontier compaction, kernel "
        "rank-select) — the device-resident A/B against the jnp refs",
    )
    ap.add_argument(
        "--trace",
        default=None,
        metavar="PATH",
        help="record a phase trace of the whole section (every holder the "
        "section builds) and write Chrome trace-event JSON to PATH — "
        "load it in Perfetto, or render a table with "
        "`python -m repro.obs.report PATH`",
    )
    ap.add_argument(
        "--audit",
        default=None,
        metavar="PATH",
        help="after the section, re-run the workload's forest leg with the "
        "flight recorder installed, write the audit log (JSONL) to PATH, "
        "and replay it through the linearizability witness — a witness "
        "violation (or, on workload A, recorder overhead above 5% ops/s) "
        "fails the run",
    )
    ap.add_argument(
        "--dist",
        default="zipf",
        choices=["zipf", "uniform"],
        help="workload A read-key distribution (sharded path only): 'zipf' "
        "(s=0.5, the skewed leg) or 'uniform' (the scaling leg — with "
        "--shards ≥ 4 the run fails unless sharded ops/s ≥ the 1-shard "
        "baseline)",
    )
    ap.add_argument(
        "--repartition",
        action="store_true",
        help="enable the forest's load-aware repartitioning (boundary "
        "rebalance / cold-shard merge driven by the hot-shard window)",
    )
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    main(
        quick=args.quick,
        workload=args.workload,
        scan_path=args.scan_path,
        shards=args.shards,
        narrow=args.narrow,
        trace=args.trace,
        dist=args.dist,
        repartition=args.repartition,
        audit=args.audit,
    )
