"""Shared benchmark utilities.

``emit`` both prints the human-readable CSV line and records a
machine-readable entry (with optional structured metrics such as ops/s,
round counts, or conflict retries).  ``benchmarks/run.py`` drains the
records after each section and writes them to ``results/BENCH_<name>.json``
so the perf trajectory accumulates across PRs.
"""
from __future__ import annotations

import json
import os
import time
from typing import List

_RECORDS: List[dict] = []


def timeit(fn, *, warmup=1, iters=3):
    """Mean wall seconds per call of ``fn`` after ``warmup`` calls.  JAX
    dispatch is asynchronous: ``fn`` must end in ``block_until_ready`` (or
    pull its result to the host), or this measures only the enqueue."""
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters


def device_stamp() -> dict:
    """The device a measurement ran on, as JAX reports it."""
    import jax

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }


def emit(name: str, us_per_call: float, derived: str = "", **metrics):
    """Print one CSV result line and record it (plus structured ``metrics``
    key/values and the device stamp) for the JSON dump."""
    print(f"{name},{us_per_call:.2f},{derived}")
    _RECORDS.append(
        {
            "name": name,
            "us_per_call": us_per_call,
            "derived": derived,
            **metrics,
            **device_stamp(),
        }
    )


def drain_records() -> List[dict]:
    """Return and clear the records emitted since the last drain."""
    out = list(_RECORDS)
    _RECORDS.clear()
    return out


def write_bench_json(workload: str, records: List[dict], directory: str = None) -> str:
    """Write one section's records to ``<directory>/BENCH_<workload>.json``.

    Defaults to the repo's ``results/`` directory.  Returns the path."""
    if directory is None:
        directory = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "results"
        )
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f"BENCH_{workload}.json")
    with open(path, "w") as f:
        json.dump({"workload": workload, "results": records}, f, indent=2)
        f.write("\n")
    return path
