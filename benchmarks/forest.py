"""Sharded-forest scaling section: ops/s and conflict retries per shard
count, for the perf trajectory (``results/BENCH_forest.json``).

Sweeps ``ABForest`` shard counts over three index workloads:

  forest.a.sK — uniform YCSB-A with validated optimistic point-reads
    under a concurrent writer replica (see ``benchmarks/ycsb.run_a_forest``):
    per-shard lane groups stay even, so this is the ragged-batching
    scaling leg.  The run fails unless 4 shards beat 1 shard on BOTH
    retries/op (strictly) and ops/s (sharding must pay in wall-clock),
    and unless s4 retries/op ≤ 0.54.  s4 runs with load-aware
    repartitioning enabled and must report zero repartitions: uniform
    traffic never trips the hot-shard window (the skew detector's
    false-positive gate).
  forest.a.zipf.sK — the skewed leg (Zipf-0.5 read keys) with load-aware
    repartitioning on: the hot-shard window must FIRE at 4 shards (the
    boundary moves toward the hot prefix) and stay silent at 1 shard.
  forest.e.sK — YCSB-E fused mixed rounds (cross-shard range lanes split
    at shard boundaries, one vmapped round per batch).

``python benchmarks/forest.py [--quick] [--trace PATH] [--audit PATH]``

``--trace PATH`` installs a phase ``Tracer`` on every forest the sweep
builds (via ``benchmarks.ycsb._instrument``) and writes Chrome
trace-event JSON to PATH.  ``--audit PATH`` appends the flight-recorder
leg: a fresh 4-shard YCSB-A run with the recorder installed, audit log
written to PATH and replayed through the linearizability witness.
"""
from __future__ import annotations

import argparse
import os
import sys

if __package__ in (None, ""):  # `python benchmarks/forest.py` (not -m)
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import benchmarks.ycsb as _ycsb
from benchmarks.common import emit
from benchmarks.ycsb import run_a_forest, run_e_forest


def main(quick=False, trace=None, audit=None):
    if trace:
        from repro.obs.tracer import Tracer

        _ycsb._TRACER = Tracer()
    try:
        _sections(quick=quick)
        if audit:
            _ycsb._run_audit(audit, workload="A", shards=4, quick=quick)
    finally:
        if trace:
            from repro.obs.trace_export import write_chrome_trace

            write_chrome_trace(trace, _ycsb._TRACER)
            print(f"# wrote trace: {trace} ({len(_ycsb._TRACER.events)} events)")
            _ycsb._TRACER = None


def _sections(quick=False):
    sweep = (1, 2, 4) if quick else (1, 2, 4, 8)

    # --- uniform scaling leg: sharding must pay in wall-clock ----------
    per_u = {}
    for k in sweep:
        m = run_a_forest(k, quick=quick, dist="uniform", repartition=(k == 4))
        per_u[k] = m
        emit(
            f"forest.a.s{k}",
            m["us_per_op"],
            f"tx/s={m['ops_per_s']:.0f};conflict_retries={m['conflict_retries']};"
            f"retries/op={m['retries_per_op']:.3f};repartitions={m['repartitions']}",
            **m,
        )
    if 4 in per_u:  # hard errors, not asserts: must survive python -O
        if per_u[4]["retries_per_op"] >= per_u[1]["retries_per_op"]:
            raise RuntimeError(
                f"forest(4) retries/op {per_u[4]['retries_per_op']:.3f} not "
                f"strictly below 1-shard baseline "
                f"{per_u[1]['retries_per_op']:.3f}"
            )
        if per_u[4]["retries_per_op"] > 0.54:
            raise RuntimeError(
                f"forest(4) retries/op {per_u[4]['retries_per_op']:.3f} "
                f"above the 0.54 ceiling"
            )
        if per_u[4]["ops_per_s"] < per_u[1]["ops_per_s"]:
            raise RuntimeError(
                f"forest(4) uniform ops/s {per_u[4]['ops_per_s']:.0f} below "
                f"1-shard baseline {per_u[1]['ops_per_s']:.0f} — sharding "
                f"lost wall-clock"
            )
        if per_u[4]["repartitions"] != 0:
            raise RuntimeError(
                f"forest(4) fired {per_u[4]['repartitions']} repartitions "
                f"under uniform traffic — the hot-shard window must not "
                f"trip without skew"
            )
    emit(
        "forest.a.scaling",
        0.0,
        ";".join(f"s{k}={per_u[k]['retries_per_op']:.3f}" for k in sweep),
        **{f"retries_per_op_s{k}": per_u[k]["retries_per_op"] for k in sweep},
        **{f"ops_per_s_s{k}": per_u[k]["ops_per_s"] for k in sweep},
    )

    # --- zipf skew leg: the load-aware repartition must fire -----------
    per_z = {}
    for k in (1, 2, 4):
        m = run_a_forest(k, quick=quick, dist="zipf", repartition=True)
        per_z[k] = m
        emit(
            f"forest.a.zipf.s{k}",
            m["us_per_op"],
            f"tx/s={m['ops_per_s']:.0f};conflict_retries={m['conflict_retries']};"
            f"retries/op={m['retries_per_op']:.3f};repartitions={m['repartitions']}",
            **m,
        )
    if per_z[1]["repartitions"] != 0:
        raise RuntimeError(
            f"forest(1) fired {per_z[1]['repartitions']} repartitions — "
            f"one shard has no partition to move"
        )
    if per_z[4]["repartitions"] < 1:
        raise RuntimeError(
            "forest(4) fired no repartition under Zipf reads — the "
            "hot-shard window never tripped"
        )
    emit(
        "forest.a.zipf.summary",
        0.0,
        ";".join(
            f"s{k}={per_z[k]['retries_per_op']:.3f}/r{per_z[k]['repartitions']}"
            for k in (1, 2, 4)
        ),
        **{f"retries_per_op_s{k}": per_z[k]["retries_per_op"] for k in (1, 2, 4)},
        **{f"repartitions_s{k}": per_z[k]["repartitions"] for k in (1, 2, 4)},
    )

    # --- YCSB-E fused mixed rounds -------------------------------------
    for k in sweep:
        m = run_e_forest(k, quick=quick)
        emit(
            f"forest.e.s{k}",
            m["us_per_op"],
            f"tx/s={m['ops_per_s']:.0f};items/s={m['items_per_s']:.0f};"
            f"conflict_retries={m['conflict_retries']}",
            **m,
        )


if __name__ == "__main__":
    from repro.compile_cache import use_persistent_cache

    use_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--trace",
        default=None,
        metavar="PATH",
        help="record a phase trace of the whole sweep (every forest it "
        "builds) and write Chrome trace-event JSON to PATH — render a "
        "table with `python -m repro.obs.report PATH`",
    )
    ap.add_argument(
        "--audit",
        default=None,
        metavar="PATH",
        help="append the flight-recorder leg: a 4-shard YCSB-A run with "
        "the recorder installed, audit log written to PATH and replayed "
        "through the linearizability witness (non-zero exit on violation)",
    )
    args = ap.parse_args()
    main(quick=args.quick, trace=args.trace, audit=args.audit)
