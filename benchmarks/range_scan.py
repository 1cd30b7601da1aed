"""Range-scan microbenchmark: batched ``scan_round`` throughput vs span and
batch size, plus the ``kernels/range_scan`` Pallas kernel vs its jnp ref on
the gather hot loop (int32 device keys; interpret mode on the CPU only)."""
from __future__ import annotations

import os
import sys

import numpy as np

import jax.numpy as jnp

if __package__ in (None, ""):  # `python benchmarks/range_scan.py` (not -m)
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from repro.configs.abtree import TPU8
from repro.core import ABTree
from repro.data.workloads import WorkloadConfig, prefill_tree
from repro.kernels.range_scan import range_scan_pallas, range_scan_ref

from benchmarks.common import emit, timeit


def _bench_scan_round(quick=False):
    key_range = 1 << 14
    batch = 64 if quick else 256
    iters = 2 if quick else 5
    tree = ABTree(TPU8._replace(capacity=4 * key_range), mode="elim")
    prefill_tree(tree, WorkloadConfig(key_range=key_range, seed=11))
    rng = np.random.default_rng(17)
    for span in (16, 256) if quick else (16, 64, 256, 1024):
        lo = rng.integers(0, key_range - span, batch).astype(np.int64)
        hi = lo + span
        cap = min(2 * span, 1024)
        tree.scan_round(lo, hi, cap=cap)  # warm / compile
        dt = timeit(lambda: tree.scan_round(lo, hi, cap=cap), warmup=1, iters=iters)
        emit(
            f"range_scan.round.span{span}",
            dt / batch * 1e6,
            f"scans/s={batch/dt:.0f}",
        )


def _bench_kernel(quick=False):
    rng = np.random.default_rng(23)
    bsz, n, cap = (64, 128, 32) if quick else (256, 256, 64)
    keys = np.sort(rng.choice(1 << 20, size=(bsz, n), replace=False, axis=None).reshape(bsz, n), axis=1)
    keys = keys.astype(np.int32)
    vals = rng.integers(0, 1 << 20, (bsz, n)).astype(np.int32)
    lo = keys[:, n // 4].astype(np.int32)
    hi = keys[:, 3 * n // 4].astype(np.int32)
    args = tuple(jnp.asarray(x) for x in (keys, vals, lo, hi))
    for name, fn in (
        ("pallas", lambda: range_scan_pallas(*args, cap=cap)[0].block_until_ready()),
        ("ref", lambda: range_scan_ref(*args, cap)[0].block_until_ready()),
    ):
        dt = timeit(fn, warmup=1, iters=2 if quick else 5)
        emit(f"range_scan.kernel.{name}", dt / bsz * 1e6, f"rows/s={bsz/dt:.0f}")


def main(quick=False):
    _bench_scan_round(quick=quick)
    _bench_kernel(quick=quick)


if __name__ == "__main__":
    from repro.compile_cache import use_persistent_cache

    use_persistent_cache()
    main()
