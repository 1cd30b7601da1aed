"""SetBench-style microbenchmark (paper Figs 12–15 analog).

Grid: {uniform, zipf-1.0} × update rate {5%, 50%, 100%} × key range,
comparing Elim-ABtree vs OCC-ABtree (and a Python-dict control for
sanity).  Throughput is ops/s over batched rounds; `derived` reports the
paper's headline effect: the Elim/OCC speedup and the physical-write
collapse under skew.

CPU note: batch-parallel rounds play the role of hardware threads; the
relative Elim/OCC ratio is the reproduced claim (paper: up to 2.5× on
Zipf update-heavy), absolute ops/µs are CPU-backend numbers.
"""
from __future__ import annotations

import time

from repro.configs.abtree import TPU8
from repro.core import ABTree, DictOracle
from repro.data.workloads import WorkloadConfig, op_stream, prefill_tree

from benchmarks.common import emit


def run_case(dist, update_frac, key_range=4096, batch=512, rounds=32, zipf_s=1.0, warm=10):
    results = {}
    for mode in ("elim", "occ"):
        cfg = WorkloadConfig(
            key_range=key_range,
            update_frac=update_frac,
            dist=dist,
            zipf_s=zipf_s,
            batch=batch,
            seed=7,
        )
        tree = ABTree(TPU8._replace(capacity=4 * key_range), mode=mode)
        prefill_tree(tree, cfg)
        stream = list(op_stream(cfg, rounds))
        # warmup: cover split/merge/retry phase compiles (steady-state is
        # what the paper's 10-second runs measure)
        for r in stream[:warm]:
            tree.apply_round(*r)
        t0 = time.perf_counter()
        for ops, keys, vals in stream[warm:]:
            tree.apply_round(ops, keys, vals)
        dt = time.perf_counter() - t0
        n_ops = batch * (rounds - warm)
        results[mode] = {
            "ops_per_s": n_ops / dt,
            "us_per_op": dt / n_ops * 1e6,
            **tree.stats(),
        }
    return results


def main(quick=False):
    grid = [
        ("uniform", 0.05),
        ("uniform", 0.5),
        ("uniform", 1.0),
        ("zipf", 0.05),
        ("zipf", 0.5),
        ("zipf", 1.0),
    ]
    if quick:
        grid = [("uniform", 1.0), ("zipf", 1.0)]
    for dist, uf in grid:
        r = run_case(dist, uf)
        speedup = r["elim"]["ops_per_s"] / r["occ"]["ops_per_s"]
        writes_ratio = r["occ"]["slot_writes"] / max(r["elim"]["slot_writes"], 1)
        emit(
            f"microbench.{dist}.upd{int(uf*100)}.elim",
            r["elim"]["us_per_op"],
            f"ops/s={r['elim']['ops_per_s']:.0f};eliminated={r['elim']['eliminated']}",
        )
        emit(
            f"microbench.{dist}.upd{int(uf*100)}.occ",
            r["occ"]["us_per_op"],
            f"ops/s={r['occ']['ops_per_s']:.0f};subrounds={r['occ']['subrounds']}",
        )
        emit(
            f"microbench.{dist}.upd{int(uf*100)}.ratio",
            0.0,
            f"elim_vs_occ_speedup={speedup:.2f}x;write_reduction={writes_ratio:.2f}x",
        )


if __name__ == "__main__":
    from repro.compile_cache import use_persistent_cache

    use_persistent_cache()
    main()
