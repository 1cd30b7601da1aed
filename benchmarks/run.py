"""Benchmark aggregator — one section per paper table/figure.
Prints ``name,us_per_call,derived`` CSV and writes each section's records
to ``results/BENCH_<section>.json`` (machine-readable: ops/s, round
counts, conflict retries, …) so the perf trajectory accumulates.

  microbench    — Figs 12–15 (uniform/zipf × update-rate grid, Elim vs OCC)
  ycsb          — Fig 16 (YCSB-A analog)
  ycsb_e        — YCSB-E analog (95% range scans / 5% inserts)
  forest        — ABForest shard-count sweep (ops/s + conflict retries
                  per shard count, YCSB A/E; 4 shards must strictly beat
                  1 shard on retries/op)
  range_scan    — scan_round throughput + kernels/range_scan hot loop
  persistence   — Table 1 (durable overhead + flush traffic + GC churn)
  fault_soak    — crash-under-load soak: YCSB-A through a firing
                  FaultPlan (EIO / ENOSPC / torn / rename / kill ×
                  seeds), recovery witnessed against the committed
                  prefix + degraded-serving gate (tick never raises)
  serve_latency — p50/p99 ServeEngine.tick at N sessions, durable vs
                  volatile index backends (latency under load)
  elim_rate     — §4 mechanism (elimination fraction vs skew)
  embed_elim    — framework integration (sparse-update write collapse)
  kernels       — per-kernel timings
  roofline      — §Roofline terms from results/dryrun.json (if present)

``python -m benchmarks.run [--quick] [--only SECTION]
[--check BASELINE.json ...] [--check-tol T]``

``--check`` turns the run into a regression gate: each given committed
baseline (a prior ``results/BENCH_<section>.json``) is loaded *before* the
run overwrites it, the matching section's fresh records are compared
record-by-record — ``ops_per_s``-style throughput metrics must reach
``(1 - T)`` of the baseline and round counts must match exactly (rounds are
deterministic for a given workload + flags) — and the process exits
non-zero on any regression.  Compare runs with the same ``--quick`` setting
as the baseline; the default tolerance is generous because the gate is a
cliff detector, not a microbenchmark.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import traceback

from repro.compile_cache import use_persistent_cache

# metrics compared under the relative tolerance (higher is better);
# integral metrics compared exactly (deterministic for a seeded workload:
# round counts, and the durable layer's commit/fsync counts).
_THROUGHPUT_KEYS = ("ops_per_s", "items_per_s", "speedup_x")
_EXACT_KEYS = ("rounds", "rounds_fused", "rounds_split", "commits", "fsyncs")


def check_against_baseline(records, baseline: dict, tol: float):
    """Compare one section's fresh ``records`` against a loaded baseline
    dict (``{"workload": ..., "results": [...]}``).  Returns a list of
    failure strings (empty = pass)."""
    fresh = {r["name"]: r for r in records}
    failures = []
    compared = 0
    for base in baseline.get("results", []):
        got = fresh.get(base["name"])
        if got is None:
            failures.append(f"{base['name']}: missing from fresh run")
            continue
        for k in _THROUGHPUT_KEYS:
            if k in base and k in got:
                compared += 1
                floor = (1.0 - tol) * float(base[k])
                if float(got[k]) < floor:
                    failures.append(
                        f"{base['name']}.{k}: {float(got[k]):.1f} < "
                        f"{floor:.1f} (= (1-{tol})·baseline {float(base[k]):.1f})"
                    )
        for k in _EXACT_KEYS:
            if k in base and k in got:
                compared += 1
                if int(got[k]) != int(base[k]):
                    failures.append(
                        f"{base['name']}.{k}: {int(got[k])} != baseline {int(base[k])}"
                    )
    if compared == 0:
        failures.append(
            f"baseline {baseline.get('workload')!r}: nothing comparable "
            f"(section not run, or records renamed)"
        )
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument(
        "--check",
        nargs="+",
        default=None,
        metavar="BASELINE.json",
        help="committed BENCH_<section>.json files to gate the fresh run "
        "against (loaded before the run overwrites them)",
    )
    ap.add_argument(
        "--check-tol",
        type=float,
        default=0.6,
        help="allowed fractional throughput drop vs baseline (default 0.6: "
        "fresh ops/s must reach 40%% of baseline — a cliff detector that "
        "tolerates machine variance; tighten locally for perf work)",
    )
    args = ap.parse_args()
    use_persistent_cache()

    baselines = []
    for path in args.check or []:
        if not os.path.exists(path):
            sys.exit(
                f"--check baseline {path!r} not found — baselines must be "
                f"committed (results/ is gitignored: use `git add -f`)"
            )
        with open(path) as f:  # load BEFORE the run overwrites results/
            baselines.append((path, json.load(f)))

    from benchmarks import (
        elim_rate,
        embed_elim,
        fault_soak,
        forest,
        kernels_bench,
        microbench,
        persistence,
        range_scan,
        serve_latency,
        ycsb,
    )

    sections = {
        "microbench": microbench.main,
        "ycsb": ycsb.main,
        "ycsb_e": functools.partial(ycsb.main, workload="E"),
        "forest": forest.main,
        "range_scan": range_scan.main,
        "persistence": persistence.main,
        "fault_soak": fault_soak.main,
        "serve_latency": serve_latency.main,
        "elim_rate": elim_rate.main,
        "embed_elim": embed_elim.main,
        "kernels": kernels_bench.main,
    }
    from benchmarks.common import drain_records, write_bench_json

    print("name,us_per_call,derived")
    section_records = {}
    raised = []
    for name, fn in sections.items():
        if args.only and name != args.only:
            continue
        print(f"# --- {name} ---")
        try:
            fn(quick=args.quick)
        except Exception as e:  # noqa: BLE001 — report, run the rest, fail at exit
            print(f"{name}.ERROR,0.0,{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
            raised.append(name)
        records = drain_records()
        if records:
            section_records[name] = records
            path = write_bench_json(name, records)
            print(f"# wrote {path}")

    failures = []
    for path, baseline in baselines:
        section = baseline.get("workload")
        records = section_records.get(section, [])
        for msg in check_against_baseline(records, baseline, args.check_tol):
            failures.append(f"{path}: {msg}")
    if args.check:
        if failures:
            # restore the committed baselines the run just overwrote, so a
            # re-run still compares against the ORIGINAL numbers instead of
            # silently ratcheting the floor down to the regressed run.
            for path, baseline in baselines:
                with open(path, "w") as f:
                    json.dump(baseline, f, indent=2)
                    f.write("\n")
            print("# --- check: REGRESSION (baseline files restored) ---")
            for msg in failures:
                print(f"# CHECK FAIL {msg}")
            sys.exit(1)
        print(f"# --- check: OK ({len(baselines)} baseline(s), tol={args.check_tol}) ---")

    if raised:
        print(f"# --- sections raised: {', '.join(raised)} ---")
        sys.exit(1)

    # roofline summary (from the dry-run artifact, if present)
    if args.only in (None, "roofline"):
        path = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun.json")
        if os.path.exists(path):
            print("# --- roofline ---")
            from repro.analysis.report import summary

            with open(path) as f:
                res = json.load(f)
            s = summary(res)
            for cid, t in sorted(s.items()):
                print(
                    f"roofline.{cid.replace('|','.')},0.0,"
                    f"dominant={t['dominant']};frac={t['roofline_fraction']:.3f};"
                    f"tc={t['t_compute_s']:.3e};tm={t['t_memory_s']:.3e};tl={t['t_collective_s']:.3e}"
                )


if __name__ == "__main__":
    main()
