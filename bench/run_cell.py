"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json``; ``harness.py``
says what a run does.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones), ``device``,
with ``--trace 1`` also ``breakdown``, then ``info`` (where set-up went)
and, last, ``check``: each number the correctness check compared, with its
limit.  The same numbers close standard error.

A run that finds no TPU, fewer chips than the cell asks for, or a device
whose peaks ``peaks.py`` does not know prints no result and exits 3.
JAX's persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` or
``<checkout>/.jax_cache``, so only the first run in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    import peaks

    cell = harness.Cell(ROOT, args.workload)
    from repro.compile_cache import use_persistent_cache

    use_persistent_cache()
    import jax

    # cache every program, not only those that took a second to compile:
    # the many sub-second phase programs otherwise recompile every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < cell.chips:
        print(f"run_cell: the cell needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform!r} device(s); nothing was run",
              file=sys.stderr)
        return 3
    try:
        peaks.peaks(dev.device_kind)
    except KeyError as e:
        print(f"run_cell: {e.args[0]}; nothing was run", file=sys.stderr)
        return 3

    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": out["peak"]}
    result = {k: out[k] for k in ("correct", "attempted", "failed", "metrics")}
    result["device"] = device
    if args.trace:
        device["busy_s"] = out["busy_s"]
        device["window_s"] = out["window_s"]
        result["breakdown"] = out["breakdown"]
    result["info"] = out["info"]
    result["check"] = out["check"]
    for name, v in out["check"].items():
        print(f"check {name}: {v['value']} (limit {v['limit']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
