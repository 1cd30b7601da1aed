"""Idle device time by program phase, from a traced run's profile.

    python3 bench/idle_spans.py <trace file or directory>

prints one JSON object for the newest ``.xplane.pb`` at that path:

  window_s       the ``bench.window`` annotation's length
  idle_s         idle seconds of the first device with work in it
  idle_by_span   ``idle_s`` split by the innermost host annotation whose
                 name starts with ``repro.`` (the program's spans and its
                 pulls, ``repro.pull.<site>``), or ``(outside the program)``;
                 largest first, and complete: the pieces sum to ``idle_s``
  round_ms       p50 / p95 / max of the ``bench.apply_round`` annotations
                 in the window, and their count

The gaps are cut as ``trace_reduce.reduce_trace`` cuts its ``idle_gaps``
(at every start and end of a host activity); only the naming differs.
The program annotates its spans and pulls only while a profiler session
runs, so a program without them reads all idle time as outside it.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from collections import defaultdict

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import trace_reduce  # noqa: E402

PREFIX = "repro."
PULL = "repro.pull."
OUTSIDE = "(outside the program)"
ROUND = "bench.apply_round"


def idle_by_span(path: str) -> dict:
    """Reduce the trace at ``path`` (see the module docstring)."""
    from jax.profiler import ProfileData

    devices, host = trace_reduce._events(ProfileData.from_file(path))
    windows = [(a, b) for n, a, b in host if n == trace_reduce.WINDOW]
    if not windows:
        raise ValueError(f"trace {path!r} has no {trace_reduce.WINDOW!r} annotation")
    t0, t1 = windows[0]
    clipped = {d: trace_reduce._clip(ops, t0, t1) for d, ops in devices.items()}
    clipped = {d: ops for d, ops in clipped.items() if ops}
    host_iv = [(n, a, b) for n, a, b in host if a < t1 and b > t0 and n != trace_reduce.WINDOW]
    edges = np.unique(np.asarray([x for _, a, b in host_iv for x in (a, b)], np.float64))
    name_at = trace_reduce._namer([iv for iv in host_iv if iv[0].startswith(PREFIX)])
    idle = defaultdict(float)

    def charge(a, b):
        cuts = edges[np.searchsorted(edges, a, "right"):np.searchsorted(edges, b, "left")].tolist()
        for lo, hi in zip([a] + cuts, cuts + [b]):
            name = name_at((lo + hi) / 2)
            idle[OUTSIDE if name == trace_reduce.NO_HOST else name] += hi - lo

    busy = trace_reduce._union((a, b) for _, a, b, _ in clipped[sorted(clipped)[0]]) if clipped else []
    cursor = t0
    for a, b in busy + [[t1, t1]]:
        if a > cursor:
            charge(cursor, a)
        cursor = max(cursor, b)

    rounds = sorted(b - a for n, a, b in host_iv if n == ROUND and a >= t0 and b <= t1)
    round_ms = {"n": len(rounds)}
    if rounds:
        round_ms.update(zip(("p50", "p95", "max"),
                            (float(x) / 1e6 for x in np.percentile(rounds, [50, 95, 100]))))
    return {
        "window_s": (t1 - t0) / 1e9,
        "idle_s": sum(idle.values()) / 1e9,
        "idle_by_span": [[n, v / 1e9] for n, v in sorted(idle.items(), key=lambda kv: -kv[1])],
        "round_ms": round_ms,
    }


def for_run(root: str, reduced: dict) -> dict | None:
    """``idle_by_span`` of the trace a run under ``root`` just reduced:
    the newest trace under ``<root>/.bench_trace``, taken only if its
    window is the one ``reduced`` (``trace_reduce.reduce_trace``'s
    result) measured; None otherwise."""
    paths = glob.glob(os.path.join(root, ".bench_trace", "**", "*.xplane.pb"), recursive=True)
    if not paths:
        return None
    try:
        out = idle_by_span(max(paths, key=os.path.getmtime))
    except (OSError, ValueError):
        return None
    return out if out["window_s"] == reduced.get("window_s") else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path")
    args = ap.parse_args(argv)
    path = args.path if os.path.isfile(args.path) else trace_reduce.latest_trace(args.path)
    print(json.dumps(idle_by_span(path)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
