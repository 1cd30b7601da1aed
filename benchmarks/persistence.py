"""Persistence overhead (paper Table 1 analog), sharded: throughput change
from enabling durable commits and the flush-traffic gap between p-Elim and
p-OCC (elimination ⇒ fewer dirty nodes ⇒ fewer flushed bytes), measured on
the per-shard-journaled ``DurableForest`` at shard counts {1, 4}.

Emits structured metrics (``flush_bytes`` / ``fsyncs`` / ``commits`` /
``flush_bytes_per_op``) into ``results/BENCH_persistence.json`` via the run
aggregator; ``commits`` and ``fsyncs`` are deterministic for a given seeded
workload, so ``benchmarks/run.py --check`` gates them exactly.  The section
FAILS (raises) unless elim flushes strictly fewer bytes/op than occ at
every shard count — the paper's durability headline, published per shard
count."""
from __future__ import annotations

import shutil
import tempfile
import time

from repro.configs.abtree import TPU8
from repro.core import ABForest
from repro.core.durable import DurableForest, DurableStats
from repro.data.workloads import WorkloadConfig, op_stream, prefill_tree

from benchmarks.common import emit


WARM = 4
SHARD_COUNTS = (1, 4)


def _run(tree, stream):
    for r in stream[:WARM]:
        tree.apply_round(*r)
    t0 = time.perf_counter()
    for ops, keys, vals in stream[WARM:]:
        tree.apply_round(ops, keys, vals)
    return time.perf_counter() - t0


def main(quick=False):
    key_range, batch = 2048, 256
    rounds = 8 if quick else 20
    n_ops = batch * (rounds - WARM)
    cfg = WorkloadConfig(
        key_range=key_range, update_frac=1.0, dist="zipf", zipf_s=1.0,
        batch=batch, seed=11,
    )
    stream = list(op_stream(cfg, rounds))
    tree_cfg = TPU8._replace(capacity=4 * key_range)
    for shards in SHARD_COUNTS:
        bytes_per_op = {}
        for mode in ("elim", "occ"):
            vol = ABForest(
                n_shards=shards, cfg=tree_cfg, mode=mode,
                key_space=(0, key_range),
            )
            prefill_tree(vol, cfg)
            t_vol = _run(vol, stream)

            d = tempfile.mkdtemp(prefix=f"ptree_{mode}_s{shards}_")
            dur = DurableForest(
                d, n_shards=shards, cfg=tree_cfg, mode=mode,
                key_space=(0, key_range), snapshot_every=10**9,
            )
            prefill_tree(dur.forest, cfg)  # prefill outside timed commits
            dur._commit(force_snapshot=True)  # journal the prefilled state
            dur.dstats = DurableStats()  # count the timed stream only
            t_dur = _run(dur, stream)
            overhead = (t_dur - t_vol) / t_vol * 100
            s = dur.stats()
            bytes_per_op[mode] = s["flush_bytes"] / n_ops
            emit(
                f"persistence.zipf.{mode}.s{shards}",
                t_dur / n_ops * 1e6,
                f"overhead_vs_volatile={overhead:.0f}%;"
                f"flush_bytes={s['flush_bytes']};fsyncs={s['fsyncs']};"
                f"commits={s['commits']}",
                ops_per_s=n_ops / t_dur,
                flush_bytes=s["flush_bytes"],
                flush_bytes_per_op=s["flush_bytes"] / n_ops,
                fsyncs=s["fsyncs"],
                commits=s["commits"],
                nodes_flushed=s["nodes_flushed"],
                gc_removed=s["gc_removed"],
            )
            shutil.rmtree(d, ignore_errors=True)
        ratio = bytes_per_op["occ"] / max(bytes_per_op["elim"], 1e-9)
        emit(
            f"persistence.zipf.flush_reduction.s{shards}",
            0.0,
            f"elim_vs_occ_bytes_per_op={ratio:.2f}x",
            flush_reduction=ratio,
        )
        if bytes_per_op["elim"] >= bytes_per_op["occ"]:
            raise RuntimeError(
                f"persistence: elim must flush fewer bytes/op than occ at "
                f"shards={shards} (elim={bytes_per_op['elim']:.1f}, "
                f"occ={bytes_per_op['occ']:.1f})"
            )

    # Snapshot-churn leg, delta vs full: with ``incremental_snapshots`` the
    # periodic snapshot writes only the rows dirtied since the last FULL
    # image (a ``_delta_`` file that replaces the segment chain) instead of
    # re-serializing every node.  HARD gate: the delta path must flush
    # strictly fewer bytes/op than the full-snapshot path on the identical
    # stream — otherwise incremental snapshots are dead weight.
    churn_bytes_per_op = {}
    for variant, incremental in (("full", False), ("delta", True)):
        d = tempfile.mkdtemp(prefix=f"ptree_churn_{variant}_")
        dur = DurableForest(
            d, n_shards=2, cfg=tree_cfg, mode="elim",
            key_space=(0, key_range), snapshot_every=4,
            incremental_snapshots=incremental,
        )
        prefill_tree(dur.forest, cfg)
        dur._commit(force_snapshot=True)
        dur.dstats = DurableStats()
        t_churn = _run(dur, stream)
        s = dur.stats()
        churn_bytes_per_op[variant] = s["flush_bytes"] / n_ops
        emit(
            f"persistence.snapshot_churn.{variant}.s2",
            t_churn / n_ops * 1e6,
            f"flush_bytes_per_op={s['flush_bytes'] / n_ops:.1f};"
            f"commits={s['commits']};fsyncs={s['fsyncs']}",
            ops_per_s=n_ops / t_churn,
            flush_bytes=s["flush_bytes"],
            flush_bytes_per_op=s["flush_bytes"] / n_ops,
            commits=s["commits"],
            fsyncs=s["fsyncs"],
        )
        shutil.rmtree(d, ignore_errors=True)
    if churn_bytes_per_op["delta"] >= churn_bytes_per_op["full"]:
        raise RuntimeError(
            f"persistence.snapshot_churn: delta snapshots must flush fewer "
            f"bytes/op than full snapshots "
            f"(delta={churn_bytes_per_op['delta']:.1f}, "
            f"full={churn_bytes_per_op['full']:.1f})"
        )

    # Group-commit leg: G rounds per manifest rename (count-based
    # boundaries — the wall-clock bound is pinned huge so the commit
    # schedule is deterministic and exact-gated).  HARD gate: grouping must
    # strictly reduce both commits and fsyncs vs the serial journal on the
    # identical stream.
    group_counts = {}
    for variant, G in (("serial", 1), ("g4", 4)):
        d = tempfile.mkdtemp(prefix=f"ptree_grp_{variant}_")
        dur = DurableForest(
            d, n_shards=2, cfg=tree_cfg, mode="elim",
            key_space=(0, key_range), snapshot_every=10**9,
            group_commit_every=G, group_commit_max_wait_s=1e9,
            commit_async=(G > 1),
        )
        prefill_tree(dur.forest, cfg)
        dur._commit(force_snapshot=True)
        dur.drain()
        dur.dstats = DurableStats()
        t0 = time.perf_counter()
        for r in stream[WARM:]:
            dur.apply_round(*r)
        dur.drain()  # the persist fence is part of the measured cost
        t_grp = time.perf_counter() - t0
        s = dur.stats()
        group_counts[variant] = (s["commits"], s["fsyncs"])
        rpc = dur.metrics.histogram_summary("rounds_per_commit")
        emit(
            f"persistence.group_commit.{variant}.s2",
            t_grp / n_ops * 1e6,
            f"commits={s['commits']};fsyncs={s['fsyncs']};"
            f"rounds_per_commit_max={rpc['max']:.0f}",
            ops_per_s=n_ops / t_grp,
            commits=s["commits"],
            fsyncs=s["fsyncs"],
            flush_bytes=s["flush_bytes"],
            rounds_per_commit_max=rpc["max"],
        )
        shutil.rmtree(d, ignore_errors=True)
    if not (
        group_counts["g4"][0] < group_counts["serial"][0]
        and group_counts["g4"][1] < group_counts["serial"][1]
    ):
        raise RuntimeError(
            f"persistence.group_commit: grouping must reduce commits AND "
            f"fsyncs (serial={group_counts['serial']}, g4={group_counts['g4']})"
        )

    # GC churn leg: frequent snapshots supersede earlier journal files, so
    # the post-commit GC must actually collect them (gc_removed > 0 —
    # guards against the journal directory growing without bound; the
    # main legs never snapshot, so they never exercise GC).  Also the one
    # leg that publishes fsync latency percentiles, from the registry
    # histogram the durable layer feeds.
    d = tempfile.mkdtemp(prefix="ptree_gc_")
    dur = DurableForest(
        d, n_shards=2, cfg=tree_cfg, mode="elim",
        key_space=(0, key_range), snapshot_every=2,
    )
    prefill_tree(dur.forest, cfg)
    t_gc = _run(dur, stream)
    s = dur.stats()
    fs = dur.metrics.histogram_summary("fsync_latency_s")
    if s["gc_removed"] <= 0:
        raise RuntimeError(
            "persistence.gc: snapshot churn must GC superseded journal "
            f"files (gc_removed={s['gc_removed']})"
        )
    emit(
        "persistence.zipf.gc_churn.s2",
        t_gc / n_ops * 1e6,
        f"gc_removed={s['gc_removed']};fsync_p99_us={fs['p99'] * 1e6:.0f}",
        ops_per_s=n_ops / t_gc,
        gc_removed=s["gc_removed"],
        commits=s["commits"],
        fsyncs=s["fsyncs"],
        fsync_p50_us=fs["p50"] * 1e6,
        fsync_p99_us=fs["p99"] * 1e6,
    )
    shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    from repro.compile_cache import use_persistent_cache

    use_persistent_cache()
    main()
