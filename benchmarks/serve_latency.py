"""Latency under load: p50/p99 ``ServeEngine.tick`` at N concurrent
sessions — volatile vs durable-serial vs durable-pipelined backends.

The serving engine's tick latency is the paper claim that matters at the
system level: the batched index rounds (admit lookups, prefix publishes,
session-range sweeps) ride the scheduler tick, so index-side regressions
surface here as tail latency.  Each leg submits N seeded sessions against
a 2-shard forest index (durable legs journal both indexes to a temp
directory) and reads p50/p99 from the engine's ``tick_latency_s``
histogram — compile time is excluded by warming the engine on a couple of
throwaway sessions and then swapping in a fresh registry.

The ``durable_pipelined`` leg is the PR-10 configuration: double-buffered
ticks (admit overlapped under the in-flight decode) + group commit
(``group_commit_every`` rounds per manifest rename, committed
asynchronously off the tick thread).  Two HARD gates ride the bench:

  * durable-pipelined p99 must be STRICTLY below durable-serial p99 at
    every load (else the pipeline bought nothing — RuntimeError);
  * the pipelined legs must report ``tick_overlap_frac`` > 0 (the admit
    work really ran under a decode in flight).

Gating (``run.py --check results/BENCH_serve_latency.json``):
``ops_per_s`` (ticks/s of measured wall time) is floor-gated; ``rounds``
(the measured tick count — deterministic for seeded prompts under greedy
decode; grouping is count-based, ``group_commit_max_wait_s`` is pinned
huge) is exact-gated.

CI smoke: ``python -m benchmarks.serve_latency --quick
--group-commit-every 4`` runs the same legs with the chosen group depth.
"""
from __future__ import annotations

import argparse
import tempfile

import numpy as np

from benchmarks.common import emit


def _run_leg(cfg, n_sessions: int, durable: bool, *, pipelined: bool = False,
             group_commit_every: int = 1, seed: int = 0):
    from repro.obs.metrics import MetricsRegistry
    from repro.serve import Request, ServeEngine

    ddir = tempfile.mkdtemp(prefix="bench_serve_lat_") if durable else None
    eng = ServeEngine(
        cfg,
        max_batch=4,
        s_max=64,
        n_pages=128,
        index_shards=2,
        index_durable_dir=ddir,
        pipelined=pipelined,
        group_commit_every=group_commit_every,
        # count-based boundaries only: wall-clock boundaries would make the
        # commit schedule (and the exact-gated counters) machine-dependent
        group_commit_max_wait_s=1e9,
    )
    rng = np.random.default_rng(seed)
    # warm: compile the decode step + round kernels outside the window
    for rid in range(2):
        eng.submit(
            Request(rid=rid, prompt=list(rng.integers(0, cfg.vocab, 8)), max_new=2)
        )
    eng.run_until_done(max_ticks=200)
    eng.metrics = MetricsRegistry()  # drop warm-up ticks from the histogram
    for rid in range(100, 100 + n_sessions):
        eng.submit(
            Request(rid=rid, prompt=list(rng.integers(0, cfg.vocab, 8)), max_new=4)
        )
    eng.run_until_done(max_ticks=2000)  # drains pending commit groups at exit
    hist = eng.metrics.histogram_summary("tick_latency_s")
    overlap = eng.metrics.histogram_summary("tick_overlap_frac")
    return hist, int(eng.metrics.value("ticks")), overlap


def main(quick: bool = False, group_commit_every: int = 4):
    from repro.configs import get_config
    from repro.models import reduced

    cfg = reduced(get_config("qwen2-0.5b"), n_layers=1)
    loads = (2, 8) if quick else (2, 8, 16)
    legs = (
        ("volatile", dict(durable=False)),
        ("durable", dict(durable=True)),
        (
            "durable_pipelined",
            dict(durable=True, pipelined=True,
                 group_commit_every=group_commit_every),
        ),
    )
    for n in loads:
        p99 = {}
        for mode, kw in legs:
            hist, ticks, overlap = _run_leg(cfg, n, **kw)
            p99[mode] = hist["p99"]
            total_s = hist["sum"] or 1e-9
            extra = {}
            derived = f"p99_us={hist['p99'] * 1e6:.1f};ticks={ticks}"
            if kw.get("pipelined"):
                if not overlap["max"] > 0.0:
                    raise RuntimeError(
                        f"serve_latency.n{n}.{mode}: tick_overlap_frac never "
                        "positive — the pipelined tick overlapped nothing"
                    )
                extra["overlap_frac_p50"] = overlap["p50"]
                extra["overlap_frac_max"] = overlap["max"]
                derived += f";overlap_max={overlap['max']:.2f}"
            emit(
                f"serve_latency.n{n}.{mode}",
                hist["p50"] * 1e6,
                derived,
                ops_per_s=ticks / total_s,
                rounds=ticks,
                p50_us=hist["p50"] * 1e6,
                p99_us=hist["p99"] * 1e6,
                **extra,
            )
        if p99["durable_pipelined"] >= p99["durable"]:
            raise RuntimeError(
                f"serve_latency: pipelined durable p99 must beat serial "
                f"durable p99 at n={n} "
                f"(pipelined={p99['durable_pipelined'] * 1e6:.1f}us, "
                f"serial={p99['durable'] * 1e6:.1f}us)"
            )


if __name__ == "__main__":
    from repro.compile_cache import use_persistent_cache

    use_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--group-commit-every", type=int, default=4,
                    help="journal rounds per manifest rename on the "
                    "pipelined leg (CI smoke runs 4)")
    args = ap.parse_args()
    main(quick=args.quick, group_commit_every=args.group_commit_every)
