"""Bring-up smoke run of the index on one TPU chip, through its user entry
points, at the size a deployment holds.

    python chip_smoke.py [--seed S] [--keys N] [--mixed-rounds R] [--out DIR]

Three phases run in this one process (the chip belongs to one process):

  store    an ``ABTree`` (b=8, a=2 — the ``TPU8`` shape; ``mode="elim"``,
           ``narrow=True``) is loaded with ``--keys`` int64 keys (10^7 by
           default) through ``apply_round`` insert rounds, then runs mixed
           rounds: a YCSB-A-like find/insert/delete mix at Zipf 0.99 plus
           YCSB-E-style OP_RANGE lanes (scans of up to ~100 records).
           Every lane's answer is checked against
           ``DictOracle.apply_mixed_round``, and the final contents once
           against the oracle.
  durable  a ``DurableABTree`` with group commit (4 rounds per commit)
           journals rounds into ``--out``; the last two rounds stay in an
           uncommitted group.  ``recover`` must return exactly the oracle
           state at the last group boundary.
  serve    a ``ServeEngine`` at the published widths of qwen2-0.5b (all 24
           layers, random weights from the seed, ``pipelined=True``, durable
           prefix and session indexes) answers a few requests, two of which
           share a prompt page.  Every generated token is checked against
           a plain greedy decode of the same token stream, the prefix index
           must hit the shared page, and the recovered index journals must
           equal the live indexes.

Each phase prints one JSON line: load / compile / run seconds, rounds,
oracle mismatches (must be 0), whether each kernel site ran a compiled
Pallas kernel or its jnp form, ``peak_bytes_in_use``, platform and device
kind.  The last line is ``{"ok": true, "device": {...}}`` only if every
phase passed on a TPU; on any other platform the script exits non-zero
before running anything, and on any phase failure it exits non-zero
without that line.  The phase functions take their sizes as arguments, so
tests call them at tiny size on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.compile_cache import use_persistent_cache  # noqa: E402

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_LOWER_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Sums JAX's compile-time events while installed (a ``with`` block):
    backend compile seconds (persistent-cache loads included), trace and
    lowering seconds, and persistent-cache hits."""

    def __init__(self):
        self.compile_s = 0.0
        self.lower_s = 0.0
        self.compiles = 0
        self.cache_hits = 0

    def _on_duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.compile_s += duration
            self.compiles += 1
        elif event in _LOWER_EVENTS:
            self.lower_s += duration

    def _on_event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def report(self) -> dict:
        return {
            "compile_s": self.compile_s,
            "lower_s": self.lower_s,
            "compiles": self.compiles,
            "cache_hits": self.cache_hits,
        }


def device_report() -> dict:
    import jax

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }


def kernel_paths(holder, capacities) -> dict:
    """Which form each kernel site of the round engine ran, from the
    dispatch gates themselves: the fused descent+probe (search / retry /
    overfull phases) at every pool capacity the phase visited, the scan
    descent's frontier compaction and the scan gather (pairwise or tiled
    rank-select at the frontier widths used).  ``compiled`` is False only
    where Pallas ran in interpret mode (the CPU backend)."""
    from repro.core.abtree import KEY_DTYPE
    from repro.kernels import interpret_mode
    from repro.kernels.range_scan.kernel import TILE_AUTO_THRESHOLD
    from repro.kernels.tree_descend.ops import (
        MAX_POOL_ROWS,
        descend_probe_uses_kernel,
    )

    descend = {
        str(c): "pallas" if descend_probe_uses_kernel(c + 1, KEY_DTYPE, holder.narrow)
        else "jnp"
        for c in sorted(capacities)
    }
    n = holder._scan_frontier * holder.cfg.b
    variant = "tiled" if n > TILE_AUTO_THRESHOLD else "pairwise"
    return {
        "compiled": not interpret_mode(),
        "descend_probe": descend,
        "descend_probe_max_pool_rows": MAX_POOL_ROWS,
        "frontier_compact": "pallas" if holder.narrow else "jnp",
        "range_scan": (
            f"pallas/{variant}(n={n})" if holder.narrow_scan else "jnp"
        ),
    }


def _ramp(width: int):
    """Round widths for loading an empty tree: 64, ×4 per round, up to
    ``width``.  A full-width first round into an empty tree would split
    its one leaf a single child per parent per wave — thousands of
    waves — while each ramp round at most quadruples the tree."""
    w = 64
    while True:
        yield min(w, width)
        w *= 4


def _unique_keys(rng, n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` distinct int64 keys in ``[lo, hi)``, in random order."""
    out = np.empty(0, np.int64)
    while out.size < n:
        extra = rng.integers(lo, hi, int((n - out.size) * 1.1) + 16, dtype=np.int64)
        out = np.unique(np.concatenate([out, extra]))
    return rng.permutation(out)[:n]


def _spread(xs) -> dict:
    """p50 / p90 / max of per-round wall times (seconds)."""
    if not xs:
        return {}
    a = np.asarray(xs)
    return {"p50": float(np.percentile(a, 50)), "p90": float(np.percentile(a, 90)),
            "max": float(a.max())}


def _span_totals(tracer) -> dict:
    """Seconds per engine span name (inclusive: a ``round`` span contains
    its phases), largest first."""
    tot = {}
    for ev in tracer.events:
        if ev["ph"] == "X":
            tot[ev["name"]] = tot.get(ev["name"], 0.0) + ev["dur"] / 1e6
    return dict(sorted(tot.items(), key=lambda kv: -kv[1]))


def _point_mismatches(out, results, found) -> int:
    r = np.asarray(out.results)
    f = np.asarray(out.found)
    return int(np.sum(r != np.asarray(results, np.int64)) + np.sum(f != np.asarray(found)))


def _scan_mismatches(out, scans) -> int:
    bad = 0
    keys = np.asarray(out.scan.keys)
    vals = np.asarray(out.scan.vals)
    count = np.asarray(out.scan.count)
    for i, want in enumerate(scans):
        if want is None:
            continue
        c = int(count[i])
        got = list(zip(keys[i, :c].tolist(), vals[i, :c].tolist()))
        bad += got != want
    return bad


# Narrow key contract: keys and values strictly inside int32.
_KEY_LO, _KEY_HI = 1, (1 << 31) - 2


def phase_store(
    *,
    n_keys: int,
    load_width: int,
    mixed_rounds: int,
    mixed_width: int,
    capacity: int,
    seed: int,
    scan_cap: int = 128,
) -> dict:
    """Load ``n_keys`` through insert rounds, run ``mixed_rounds`` mixed
    rounds, and check every lane and the final contents against the
    oracle.  Returns the phase report (``ok`` ⇔ zero mismatches)."""
    from repro.configs.abtree import TPU8
    from repro.core import (
        ABTree,
        DictOracle,
        OP_DELETE,
        OP_FIND,
        OP_INSERT,
        OP_RANGE,
    )
    from repro.data.workloads import zipf_keys

    from repro.obs import Tracer

    rng = np.random.default_rng(seed)
    cfg = TPU8._replace(capacity=capacity)
    tree = ABTree(cfg, mode="elim", narrow=True)
    tree.tracer = Tracer()  # fenced phase spans → the per-phase breakdown
    oracle = DictOracle()
    keys = _unique_keys(rng, n_keys, _KEY_LO, _KEY_HI)
    vals = rng.integers(0, 1 << 30, n_keys, dtype=np.int64)
    capacities = {tree.cfg.capacity}
    mismatches = 0
    rounds = 0
    load_rounds, mixed_rounds_s = [], []
    clock = CompileClock()
    with clock:
        t0 = time.perf_counter()
        i = 0
        for w in _ramp(load_width):
            if i >= n_keys:
                break
            k, v = keys[i : i + w], vals[i : i + w]
            ops = np.full(k.size, OP_INSERT, np.int32)
            tr = time.perf_counter()
            out = tree.apply_round(ops, k, v)
            load_rounds.append(time.perf_counter() - tr)
            mismatches += _point_mismatches(out, *oracle.apply_round(ops, k, v))
            capacities.add(tree.cfg.capacity)
            rounds += 1
            i += k.size
        load_s = time.perf_counter() - t0

        # YCSB-A-like point mix at Zipf 0.99 over the loaded keys (rank r is
        # the r-th key of a random permutation: hot keys spread over the
        # key space, as YCSB's scrambled Zipfian does), fresh-key inserts,
        # and YCSB-E-style scans of up to ~100 records.
        gap = (_KEY_HI - _KEY_LO) // max(n_keys, 1)
        t1 = time.perf_counter()
        for _ in range(mixed_rounds):
            u = rng.random(mixed_width)
            ops = np.select(
                [u < 0.45, u < 0.70, u < 0.90],
                [OP_FIND, OP_INSERT, OP_DELETE],
                OP_RANGE,
            ).astype(np.int32)
            k = keys[zipf_keys(rng, mixed_width, n_keys, 0.99)]
            fresh = (ops == OP_INSERT) & (rng.random(mixed_width) < 0.5)
            k = np.where(fresh, rng.integers(_KEY_LO, _KEY_HI, mixed_width), k)
            v = np.where(
                ops == OP_RANGE,
                rng.integers(1, 100 * gap + 2, mixed_width),
                rng.integers(0, 1 << 30, mixed_width),
            ).astype(np.int64)
            k = np.where(ops == OP_RANGE, np.minimum(k, _KEY_HI - v), k)
            tr = time.perf_counter()
            out = tree.apply_round(ops, k, v, scan_cap=scan_cap)
            mixed_rounds_s.append(time.perf_counter() - tr)
            res, fnd, scans = oracle.apply_mixed_round(ops, k, v, cap=scan_cap)
            mismatches += _point_mismatches(out, res, fnd)
            mismatches += _scan_mismatches(out, scans)
            capacities.add(tree.cfg.capacity)
            rounds += 1
        run_s = time.perf_counter() - t1
        contents_ok = tree.items() == oracle.d
    return {
        "phase": "store",
        "ok": mismatches == 0 and contents_ok,
        "keys_loaded": n_keys,
        "keys_final": len(oracle.d),
        "load_width": load_width,
        "mixed_width": mixed_width,
        "rounds": rounds,
        "load_s": load_s,
        "run_s": run_s,
        **clock.report(),
        "load_round_s": _spread(load_rounds),
        "mixed_round_s": _spread(mixed_rounds_s),
        "engine": {
            name: tree.metrics.value(name)
            for name in ("retry_passes", "split_waves", "split_nodes",
                         "underfull_waves", "scan_retries")
        },
        "phase_s": _span_totals(tree.tracer),
        "oracle_mismatches": mismatches,
        "contents_match": contents_ok,
        "capacity_final": tree.cfg.capacity,
        "kernels": kernel_paths(tree, capacities),
        **device_report(),
    }


def phase_durable(
    *,
    directory: str,
    n_keys: int,
    width: int,
    capacity: int,
    seed: int,
    group: int = 4,
    mixed_rounds: int = 6,
) -> dict:
    """Journal insert rounds and mixed point rounds through group commit,
    leave the last two rounds in an uncommitted group, recover, and
    compare the recovered dictionary with the oracle at the last group
    boundary."""
    from repro.configs.abtree import TPU8
    from repro.core import (
        DictOracle,
        DurableABTree,
        OP_DELETE,
        OP_FIND,
        OP_INSERT,
        recover,
    )

    rng = np.random.default_rng(seed + 1)
    shutil.rmtree(directory, ignore_errors=True)
    dt = DurableABTree(
        directory,
        TPU8._replace(capacity=capacity),
        mode="elim",
        group_commit_every=group,
        group_commit_max_wait_s=1e9,  # boundaries by round count only
    )
    oracle = DictOracle()
    keys = _unique_keys(rng, n_keys, _KEY_LO, _KEY_HI)
    rounds_plan = []
    i = 0
    for w in _ramp(width):
        if i >= n_keys:
            break
        k = keys[i : i + w]
        rounds_plan.append((np.full(k.size, OP_INSERT, np.int32), k, k * 3))
        i += k.size
    for _ in range(mixed_rounds):
        ops = rng.choice(
            np.array([OP_FIND, OP_INSERT, OP_DELETE], np.int32), width
        )
        k = keys[rng.integers(0, n_keys, width)]
        rounds_plan.append((ops, k, rng.integers(0, 1 << 30, width)))
    # end two rounds past a group boundary: they are absorbed, not committed
    n_rounds = len(rounds_plan) - (len(rounds_plan) % group) + 2
    while len(rounds_plan) < n_rounds:
        rounds_plan.append(rounds_plan[-1])
    rounds_plan = rounds_plan[:n_rounds]
    committed = None
    mismatches = 0
    clock = CompileClock()
    with clock:
        t0 = time.perf_counter()
        for r, (ops, k, v) in enumerate(rounds_plan):
            out = dt.apply_round(ops, k, v)
            mismatches += _point_mismatches(out, *oracle.apply_round(ops, k, v))
            if (r + 1) % group == 0:
                committed = dict(oracle.d)
        run_s = time.perf_counter() - t0
        stats = dt.stats()
        t1 = time.perf_counter()
        rec = recover(directory)
        recover_s = time.perf_counter() - t1
        recovered = rec.tree.items()
        # the recovered holder is live: one find round over the prefix
        probe = np.asarray(sorted(committed)[: min(width, len(committed))], np.int64)
        out = rec.apply_round(np.full(probe.size, OP_FIND, np.int32), probe)
        probe_ok = bool(np.all(np.asarray(out.found))) and np.array_equal(
            np.asarray(out.results), np.asarray([committed[int(x)] for x in probe])
        )
        dt.close()
        rec.close()
    recovered_ok = recovered == committed
    return {
        "phase": "durable",
        "ok": mismatches == 0 and recovered_ok and probe_ok,
        "keys": n_keys,
        "rounds": n_rounds,
        "rounds_committed": n_rounds - 2,
        "group_commit_every": group,
        "commits": stats["commits"],
        "fsyncs": stats["fsyncs"],
        "flush_bytes": stats["flush_bytes"],
        "run_s": run_s,
        "recover_s": recover_s,
        **clock.report(),
        "oracle_mismatches": mismatches,
        "recovered_matches_committed_prefix": recovered_ok,
        "recovered_find_round_ok": probe_ok,
        "kernels": kernel_paths(dt.tree, {dt.tree.cfg.capacity}),
        **device_report(),
    }


def phase_serve(
    *,
    cfg,
    directory: str,
    n_requests: int,
    max_new: int,
    seed: int,
    max_batch: int = 4,
) -> dict:
    """Serve ``n_requests`` requests (requests 0 and 1 share their first
    prompt page) through a pipelined ``ServeEngine`` with durable indexes,
    then check tokens, prefix hits and the journals."""
    import jax
    import jax.numpy as jnp

    from repro.core import recover
    from repro.models import backbone
    from repro.serve import Request, ServeEngine
    from repro.serve.pages import PAGE

    rng = np.random.default_rng(seed + 2)
    shutil.rmtree(directory, ignore_errors=True)
    prompt_len = PAGE + 8  # one full page (a prefix-index block) + a tail
    prompts = rng.integers(0, cfg.vocab, (n_requests, prompt_len)).astype(np.int32)
    prompts[1, :PAGE] = prompts[0, :PAGE]  # shared first page → one hit
    s_max = prompt_len + max_new + 8
    clock = CompileClock()
    with clock:
        t0 = time.perf_counter()
        eng = ServeEngine(
            cfg,
            max_batch=max_batch,
            s_max=s_max,
            n_pages=4 * n_requests,
            index_durable_dir=directory,
            pipelined=True,
            group_commit_every=4,
            seed=seed,
        )
        load_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        for rid in range(n_requests):
            eng.submit(Request(rid=rid, prompt=prompts[rid].tolist(), max_new=max_new))
        done = eng.run_until_done(max_ticks=100 * max_new * n_requests)
        run_s = time.perf_counter() - t1
        stats = eng.stats()

        # reference: a plain greedy decode, one batch row per request, on a
        # fresh cache — no scheduler, slots or pipeline — teacher-forced
        # with the engine's own tokens, so each of them must be a
        # (near-)argmax of the reference logits at its step.
        outs = np.zeros((n_requests, max_new), np.int32)
        for r in done:
            outs[r.rid, : len(r.out)] = r.out[:max_new]
        stream = np.concatenate([prompts, outs[:, :-1]], axis=1)
        decode = jax.jit(lambda p, c, t, q: backbone.forward_decode(p, c, t, q, cfg))
        cache = backbone.init_cache(cfg, n_requests, s_max)
        tokens_ok = np.zeros((n_requests, max_new), bool)
        finite = True
        for pos in range(stream.shape[1]):
            logits, cache = decode(
                eng.params, cache, jnp.asarray(stream[:, pos]), jnp.int32(pos)
            )
            j = pos - prompt_len + 1  # the generated token these logits pick
            if j < 0:
                continue
            ref = np.asarray(logits, np.float32)
            finite &= bool(np.all(np.isfinite(ref)))
            top, low = ref.max(axis=1), ref.min(axis=1)
            picked = ref[np.arange(n_requests), outs[:, j]]
            tokens_ok[:, j] = top - picked <= 0.01 * (top - low)
        token_ok = tokens_ok.all(axis=1).tolist()

        journals_ok = True
        for name, live in (("prefix", eng.index.tree), ("sessions", eng.sessions.tree)):
            rec = recover(os.path.join(directory, name))
            journals_ok &= rec.items() == live.items()
            rec.close()
        for h in (eng.index.tree, eng.sessions.tree):
            h.close()
    complete = len(done) == n_requests and all(
        len(r.out) == max_new and all(0 <= t < cfg.vocab for t in r.out) for r in done
    )
    hits = stats["cache_hit_blocks"]
    ok = (
        complete
        and all(token_ok)
        and finite
        and hits >= 1
        and journals_ok
        and stats["pages_used"] == 0
        and not stats["durability"]["degraded"]
    )
    return {
        "phase": "serve",
        "ok": ok,
        "model": cfg.name,
        "n_layers": cfg.n_layers,
        "requests": n_requests,
        "tokens_out": sum(len(r.out) for r in done),
        "ticks": stats["ticks"],
        "tick_latency": stats["tick_latency"],
        "load_s": load_s,
        "run_s": run_s,
        **clock.report(),
        "tokens_match_reference": token_ok,
        "logits_finite": finite,
        "prefix_hit_blocks": hits,
        "journals_match_live_indexes": journals_ok,
        "oracle_mismatches": (not complete) + (not journals_ok) + token_ok.count(False),
        "kernels": kernel_paths(
            eng.index.tree.forest, {eng.index.tree.forest.cfg.capacity}
        ),
        **device_report(),
    }


def _run_phase(name, fn, **kwargs) -> dict:
    t0 = time.perf_counter()
    try:
        rep = fn(**kwargs)
    except Exception:  # noqa: BLE001 — report the phase as failed, run the rest
        rep = {"phase": name, "ok": False, "error": traceback.format_exc()}
    rep["wall_s"] = time.perf_counter() - t0
    print(json.dumps(rep, default=float), flush=True)
    return rep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--keys", type=int, default=10_000_000,
                    help="keys the store phase loads")
    ap.add_argument("--mixed-rounds", type=int, default=24)
    ap.add_argument("--out", default=os.path.join(ROOT, "chip_smoke_data"),
                    help="directory for the durable and serve journals")
    args = ap.parse_args(argv)

    cache_dir = use_persistent_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: no TPU (JAX found {dev.platform!r}); nothing was run",
            file=sys.stderr,
        )
        return 2
    print(json.dumps({"compile_cache": cache_dir, "jax": jax.__version__}), flush=True)

    from repro.configs.qwen2_0_5b import CONFIG as QWEN2

    # Store rounds are 16,384 lanes: the TPU compile of the search phase's
    # key sort grows with the width (≈ 30 s per pool capacity at 16,384),
    # and each pool capacity recompiles it.  The pool starts at 2**19, the
    # largest the descent kernel keeps in VMEM, and doubles from there.
    reports = [
        _run_phase(
            "store", phase_store,
            n_keys=args.keys, load_width=16384,
            mixed_rounds=args.mixed_rounds, mixed_width=16384,
            capacity=1 << 19, seed=args.seed,
        ),
        _run_phase(
            "durable", phase_durable,
            directory=os.path.join(args.out, "durable"),
            n_keys=1 << 18, width=4096, capacity=1 << 17, seed=args.seed,
        ),
        _run_phase(
            "serve", phase_serve,
            cfg=QWEN2, directory=os.path.join(args.out, "serve"),
            n_requests=4, max_new=8, seed=args.seed,
        ),
    ]
    if not all(r["ok"] for r in reports):
        print("chip_smoke: FAILED phases: "
              + ", ".join(r["phase"] for r in reports if not r["ok"]), file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
