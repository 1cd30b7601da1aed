"""Kernel microbenchmarks: Pallas (interpret on CPU — structural check)
vs the pure-jnp oracles (XLA-compiled, the actual CPU fast path).

The ``search_phase.hlo`` records report host-visible XLA sort/gather op
counts lowered from the round engine's search and scan-descent phases —
the structural metric the device-resident search path (kernels/
tree_descend) is buying down: zero sorts in the scan descent on every
path, and the narrow point-op search collapsing to one fused kernel."""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import decode_attention_ref
from repro.kernels.flash_attention import attention_ref
from repro.kernels.leaf_probe import leaf_probe_pallas, leaf_probe_ref

from benchmarks.common import emit, timeit


def _hlo_op_counts():
    """Lower the search/scan phases both ways and count sort/gather ops
    (the reusable audit in :mod:`repro.obs.hlo_audit`; the no-sort trace
    tests assert on the same programs)."""
    from repro.obs.hlo_audit import audit_search_phases

    for name, counts in audit_search_phases().items():
        sorts = counts["stablehlo.sort"]
        gathers = counts["stablehlo.gather"]
        emit(
            f"kernel.search_phase.hlo.{name}", 0.0,
            f"sorts={sorts};gathers={gathers}",
            hlo_sorts=sorts, hlo_gathers=gathers,
        )


def main(quick=False):
    rng = np.random.default_rng(0)

    # fused descent + probe (tree_descend): jnp ref path vs Pallas interpret
    from repro.core import ABTree, OP_INSERT, TreeConfig
    from repro.core.rounds import _search_leaves

    t = ABTree(TreeConfig(capacity=4096, b=8, a=2, max_height=16))
    tkeys = rng.choice(1 << 30, size=1500, replace=False).astype(np.int64)
    t.apply_round(np.full(1500, OP_INSERT, np.int32), tkeys, tkeys)
    q = jnp.asarray(rng.choice(tkeys, 1024).astype(np.int64))
    for narrow, tag in ((False, "ref_xla"), (True, "pallas_interp")):
        fn = jax.jit(
            functools.partial(_search_leaves, narrow=narrow), static_argnums=(1,)
        )
        jax.block_until_ready(fn(t.state, t.cfg, q))
        dt = timeit(lambda: jax.block_until_ready(fn(t.state, t.cfg, q)))
        emit(f"kernel.tree_descend.{tag}", dt * 1e6, "batch=1024;pool=4096")

    # segmented frontier compaction: argsort oracle vs scatter jnp vs Pallas
    from repro.kernels.tree_descend import (
        frontier_compact,
        frontier_compact_ref,
    )

    bsz, m, f = 64, 288, 32
    cand = jnp.asarray(rng.integers(0, 4096, (bsz, m)), jnp.int32)
    valid = jnp.asarray(rng.random((bsz, m)) < 0.15)
    ref = jax.jit(lambda c, v: frontier_compact_ref(c, v, f, scratch=0))
    jnp_path = jax.jit(lambda c, v: frontier_compact(c, v, f, scratch=0))
    jax.block_until_ready(ref(cand, valid))
    jax.block_until_ready(jnp_path(cand, valid))
    dt = timeit(lambda: jax.block_until_ready(ref(cand, valid)))
    emit("kernel.frontier_compact.argsort_ref", dt * 1e6, f"m={m};f={f}")
    dt = timeit(lambda: jax.block_until_ready(jnp_path(cand, valid)))
    emit("kernel.frontier_compact.cumsum_xla", dt * 1e6, f"m={m};f={f}")
    pallas_path = lambda: jax.block_until_ready(
        frontier_compact(cand, valid, f, scratch=0, use_pallas=True)
    )
    pallas_path()  # warm: trace/lower outside the timed region
    dt = timeit(pallas_path)  # iters=3: single-shot interpret timings are noisy
    emit("kernel.frontier_compact.pallas_interp", dt * 1e6, "interpret-mode")

    # rank-select: pairwise vs tiled at a large frontier
    from repro.kernels.range_scan.kernel import range_scan_pallas

    n = 512 if quick else 1024
    sk = np.stack([rng.choice(10**7, size=n, replace=False) for _ in range(8)])
    sk = sk.astype(np.int32)
    sv = rng.integers(0, 10**6, (8, n)).astype(np.int32)
    slo = np.zeros(8, np.int32)
    shi = np.full(8, 10**7, np.int32)
    a = (jnp.asarray(sk), jnp.asarray(sv), jnp.asarray(slo), jnp.asarray(shi))
    for tile, tag in ((-1, "pairwise"), (128, "tiled128")):
        run = lambda: jax.block_until_ready(
            range_scan_pallas(*a, cap=128, tile_n=tile)
        )
        run()  # warm: trace/lower outside the timed region
        dt = timeit(run)  # iters=3: single-shot interpret timings are noisy
        emit(f"kernel.rank_select.{tag}", dt * 1e6, f"n={n};cap=128")

    _hlo_op_counts()

    # leaf probe
    bsz, b = 4096, 8
    keys = jnp.asarray(rng.integers(0, 1 << 30, (bsz, b)), jnp.int32)
    vals = jnp.asarray(rng.integers(0, 1 << 30, (bsz, b)), jnp.int32)
    qs = keys[:, 3]
    ref = jax.jit(leaf_probe_ref)
    jax.block_until_ready(ref(keys, vals, qs))
    t = timeit(lambda: jax.block_until_ready(ref(keys, vals, qs)))
    emit("kernel.leaf_probe.ref_xla", t * 1e6, f"batch={bsz}")
    t = timeit(
        lambda: jax.block_until_ready(leaf_probe_pallas(keys, vals, qs)),
    )
    emit("kernel.leaf_probe.pallas_interp", t * 1e6, "interpret-mode (structural)")

    # attention (train shape, small)
    bq, h, s, d = 1, 8, 512 if quick else 1024, 64
    q = jnp.asarray(rng.standard_normal((bq, h, s, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((bq, h // 4, s, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((bq, h // 4, s, d)), jnp.bfloat16)
    ref_attn = jax.jit(lambda a, b_, c: attention_ref(a, b_, c, causal=True))
    jax.block_until_ready(ref_attn(q, k, v))
    t = timeit(lambda: jax.block_until_ready(ref_attn(q, k, v)))
    emit("kernel.flash_attention.ref_xla", t * 1e6, f"s={s},gqa4")

    # decode attention
    bd, hd, kh, sd, dd = 8, 16, 4, 8192, 64
    qd = jnp.asarray(rng.standard_normal((bd, hd, dd)), jnp.bfloat16)
    kd = jnp.asarray(rng.standard_normal((bd, kh, sd, dd)), jnp.bfloat16)
    vd = jnp.asarray(rng.standard_normal((bd, kh, sd, dd)), jnp.bfloat16)
    refd = jax.jit(lambda a, b_, c: decode_attention_ref(a, b_, c, sd))
    jax.block_until_ready(refd(qd, kd, vd))
    t = timeit(lambda: jax.block_until_ready(refd(qd, kd, vd)))
    kv_bytes = bd * kh * sd * dd * 2 * 2
    emit(
        "kernel.decode_attention.ref_xla", t * 1e6,
        f"kv_bytes={kv_bytes};GBps={kv_bytes/t/1e9:.1f}",
    )


if __name__ == "__main__":
    from repro.compile_cache import use_persistent_cache

    use_persistent_cache()
    main()
