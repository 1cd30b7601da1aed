"""The cell harness: everything a run does between the command line and
its result line, for any cell that ``BENCHMARK.json`` names.

A cell is found by name: its entry in ``BENCHMARK.json`` names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``), and each per-layer metric is read by
``bench/metrics/<metric>.py``.  A run, in order:

  1. builds the system the configuration describes through the program's
     own entry points, and the traffic from the seed;
  2. prefills it through ``apply_round`` in rounds of ``PREFILL_WIDTH``
     inserts, after a short ramp into the empty tree;
  3. warms up: one round that empties a run of leaves (so the widest
     underfull waves compile), then ``WARMUP_ROUNDS`` rounds of the mix;
  4. drives the closed loop for ``seconds``: every round carries one
     operation of each client, and an operation's latency is its round's,
     from the call to the answers on the host (after the commit, in a
     durable configuration);
  5. a durable configuration then goes on to a fixed point of its snapshot
     cycle (the round whose commit writes a full snapshot) and one round
     more, copies its journal as that last answered round left it (before
     anything could drain a commit still pending), and times ``recover``
     on the copy;
  6. replays every round of the run through the plain reference and
     compares every lane's answer, every scan row, the final contents and,
     where durable, the recovered contents.

With ``trace`` the run records a profiler trace of the window's first
seconds, on the same unfenced path the end-to-end metrics time, then
installs the program's span tracer (which fences every phase) for the
rest of the window.  The device metrics and the breakdown come from the
first part, the span metrics from the second, the counters from the
whole window; the end-to-end metrics are not reported.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import time

import numpy as np

import reference
import trace_reduce
import traffic as traffic_mod
from traffic import OP_DELETE, OP_FIND, OP_INSERT, OP_RANGE

RAMP = 64  # the first prefill round into the empty tree
PREFILL_WIDTH = 16384  # lanes of a prefill round after the ramp
WARMUP_ROUNDS = 8  # rounds of the cell's own mix before the window
TRACE_SECONDS = 3.0  # profiled part of a traced window

# What a configuration file may hold.  ``args`` go to the holder as
# keyword arguments and ``tree`` to ``TreeConfig``, so an argument the
# program does not take raises instead of being ignored.
CONFIG_KEYS = {"name", "source", "deployment", "guarantee", "holder", "args", "tree",
               "key_range", "prefill_fraction", "recover_at", "reduced", "assumed"}


class Cell:
    """One ``workloads`` entry of ``BENCHMARK.json`` with its files."""

    def __init__(self, root: str, name: str):
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; BENCHMARK.json has {sorted(cells)}")
        w = cells[name]
        configs = {c["name"]: c for c in bench["configs"]}
        self.root = root
        self.bench_dir = os.path.join(root, "bench")
        self.name = name
        self.chips = int(w["chips"])
        self.config_name = w["config"]
        self.traffic_name = w["traffic"]
        with open(os.path.join(root, configs[w["config"]]["file"])) as f:
            self.config = json.load(f)
        unknown = set(self.config) - CONFIG_KEYS
        if unknown:
            raise ValueError(f"configuration {w['config']!r} sets {sorted(unknown)}, "
                             f"which the harness does not read")

        def applies(m):
            return "workloads" not in m or name in m["workloads"]

        self.end_to_end = [m for m in bench["end_to_end"] if applies(m)]
        self.per_layer = [m for m in bench["per_layer"] if applies(m)]
        self.readers = {m["name"]: _load_reader(self.bench_dir, m["name"]) for m in self.per_layer}

    def traffic(self, seed: int):
        return traffic_mod.load_traffic(self.bench_dir, self.traffic_name, self.config, seed)


def _load_reader(bench_dir: str, metric: str):
    path = os.path.join(bench_dir, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ----------------------------------------------------------------------------
# The system under test
# ----------------------------------------------------------------------------


class ProgramSystem:
    """The program's holder as the configuration describes it.  The
    flight recorder is off in every holder: its ring keeps up to 4096
    rounds of Python lists of every lane's answer and scan row, and a
    durable holder rewrites the whole ring at every commit, so its cost
    grows through a run (PERF.md)."""

    def __init__(self, config: dict, journal_dir: str):
        from repro import core
        from repro.obs.recorder import Recorder

        for name, code in (("OP_FIND", OP_FIND), ("OP_INSERT", OP_INSERT),
                           ("OP_DELETE", OP_DELETE), ("OP_RANGE", OP_RANGE)):
            if getattr(core, name) != code:
                raise RuntimeError(f"the program's {name} is not the traffic's {code}")
        cfg = core.TreeConfig(**config["tree"])
        holder, args = config["holder"], config["args"]
        if holder == "ABTree":
            self.h = core.ABTree(cfg, **args)
            self.tree = self.h
        elif holder == "DurableABTree":
            shutil.rmtree(journal_dir, ignore_errors=True)
            self.h = core.DurableABTree(journal_dir, cfg, **args)
            self.tree = self.h.tree
            self.journal_dir = journal_dir
        else:
            raise ValueError(f"unknown holder {holder!r}")
        self.durable = holder != "ABTree"
        self.h.recorder = Recorder(enabled=False)

    def apply(self, ops, keys, vals, scan_cap):
        kw = {} if scan_cap is None else {"scan_cap": scan_cap}
        out = self.h.apply_round(ops, keys, vals, **kw)
        scan = None
        if out.scan is not None:
            scan = (np.asarray(out.scan.count), np.asarray(out.scan.keys),
                    np.asarray(out.scan.vals))
        return np.asarray(out.results), np.asarray(out.found), scan

    def stats(self) -> dict:
        return self.h.stats()

    def items(self) -> dict:
        return self.tree.items()

    def set_tracer(self, tracer):
        from repro.obs.tracer import NULL_TRACER

        self.h.tracer = NULL_TRACER if tracer is None else tracer

    def full_snapshots(self) -> int:
        return self.tree.metrics.value("full_snapshots")

    def recover(self):
        """Copy the journal as it stands, with the holder still open, so
        that nothing drains a commit the last answer did not wait for;
        time ``recover`` of the copy until it returns an operational
        holder, and return ``(seconds, recovered contents)``."""
        from repro.core import recover

        image = self.journal_dir + ".image"
        shutil.rmtree(image, ignore_errors=True)
        shutil.copytree(self.journal_dir, image)
        os.sync()  # the copy's writeback would otherwise stall recovery's fsyncs
        t0 = time.perf_counter()
        rec = recover(image)
        seconds = time.perf_counter() - t0
        items = rec.tree.items()
        rec.close()
        shutil.rmtree(image, ignore_errors=True)
        return seconds, items

    def close(self):
        if self.durable:
            self.h.close()
            shutil.rmtree(self.journal_dir, ignore_errors=True)


# ----------------------------------------------------------------------------
# One run
# ----------------------------------------------------------------------------


def prefill_rounds(traffic, seed: int, width: int):
    """The prefill as insert rounds of ``width`` lanes, after a ramp (64
    inserts, ×4 per round) into the empty tree, since a full round into
    one leaf would split it one child per parent per wave.  Rounds are
    padded with finds of random keys to the cell's own width (the ramp)
    or to ``width``, so the prefill compiles no width but these two."""
    keys, vals = traffic.prefill()
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 99])
    i, step = 0, RAMP
    while i < keys.size:
        n = min(step, width, keys.size - i)
        w = traffic.clients if step <= traffic.clients else width
        ops = np.full(w, OP_FIND, np.int32)
        ops[:n] = OP_INSERT
        k = rng.integers(0, traffic.key_range, w, dtype=np.int64)
        v = np.zeros(w, np.int64)
        k[:n], v[:n] = keys[i : i + n], vals[i : i + n]
        yield ops, k, v
        i += n
        step *= 4


def structural_rounds(traffic, seed: int):
    """One warm-up round that compiles the wide underfull waves: it
    deletes ``clients`` consecutive keys of the prefill, emptying a run of
    leaves at once.  (An insert-only prefill leaves no leaf underfull,
    and a mix that deletes would otherwise first need those waves inside
    the window.)  The keys stay deleted: 0.4 % of a 10^6-key set."""
    keys = np.sort(traffic.prefill()[0])
    width = min(traffic.clients, keys.size)
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 98])
    at = int(rng.integers(0, keys.size - width + 1))
    yield (np.full(width, OP_DELETE, np.int32), keys[at : at + width],
           np.zeros(width, np.int64))


class CompileCounter:
    """Counts the backend compiles JAX reports while installed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self.seconds = 0.0
        self.names = []

    def _on(self, event, duration, fun_name=None, **_):
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration
            self.names.append(fun_name)

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def _percentile_ms(latencies, lanes, q):
    """The q-th percentile of all operations' latencies, each operation
    taking its round's latency (nearest rank)."""
    lat = np.repeat(np.asarray(latencies), np.asarray(lanes))
    return float(np.percentile(lat, q, method="inverted_cdf")) * 1e3


class RunView:
    """What a per-layer reader reads: the window's spans, counters, lane
    counts and the reduced device trace."""

    def __init__(self, *, rounds, ops, lanes, spans, span_rounds, before, after, trace,
                 trace_rounds):
        self.rounds = rounds
        self.ops = ops
        self.lanes = lanes
        self.spans = spans
        self.span_rounds = span_rounds
        self.before = before
        self.after = after
        self.trace = trace
        self.trace_rounds = trace_rounds

    def span_s(self, names) -> float | None:
        """Seconds inside spans of ``names``, counting a span nested in
        another of them once; None where none was recorded."""
        sel = sorted((a, b) for n, a, b in self.spans if n in names)
        if not sel:
            return None
        total, end = 0.0, -math.inf
        for a, b in sel:
            if a >= end:
                total += b - a
                end = b
            elif b > end:
                total += b - end
                end = b
        return total

    def per_round_ms(self, names) -> float | None:
        """Span milliseconds per round of the part of the window that the
        span tracer saw."""
        s = self.span_s(names)
        return None if s is None or not self.span_rounds else s * 1e3 / self.span_rounds

    def delta(self, counter: str):
        if counter not in self.after:
            return None
        return self.after[counter] - self.before.get(counter, 0)

    def kernel_ms_per_round(self, key: str) -> float | None:
        if self.trace is None or not self.trace_rounds:
            return None
        s = self.trace["kernels_s"].get(key, 0.0)
        return s * 1e3 / self.trace_rounds if s > 0 else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *, t_start: float,
             system=None, rounds: int | None = None) -> dict:
    """One run of ``cell``; returns the result line's object (without the
    device block, which the caller adds).  ``system`` replaces the
    program, and ``rounds`` fixes the window's round count in place of
    its seconds (the control uses both, the tests the first)."""
    journal_dir = os.path.join(cell.root, ".bench_journal", cell.name)
    trace_dir = os.path.join(cell.root, ".bench_trace", cell.name)
    cfg = cell.config
    t = time.perf_counter()
    info = {"start_s": t - t_start}  # interpreter, imports, JAX's backend
    traffic = cell.traffic(seed)
    traffic.prefill()
    info["traffic_s"] = time.perf_counter() - t
    cap = traffic.scan_cap
    sys_ = system if system is not None else ProgramSystem(cfg, journal_dir)
    log = []  # (ops, keys, vals, answer) of every round, for the check

    with CompileCounter() as compiles:
        t = time.perf_counter()
        for ops, keys, vals in prefill_rounds(traffic, seed, PREFILL_WIDTH):
            log.append((ops, keys, vals, sys_.apply(ops, keys, vals, cap)))
        info["prefill_s"] = time.perf_counter() - t
        info["prefill_rounds"] = len(log)
        t = time.perf_counter()
        for ops, keys, vals in structural_rounds(traffic, seed):
            log.append((ops, keys, vals, sys_.apply(ops, keys, vals, cap)))
        nxt = 0
        for _ in range(WARMUP_ROUNDS):
            log.append(_next_round(sys_, traffic, nxt, cap))
            nxt += 1
        info["warmup_s"] = time.perf_counter() - t
        info["setup_compiles"] = compiles.n
        info["setup_compile_s"] = compiles.seconds

    tracer = None
    if trace:
        import jax
        from repro.obs import Tracer

        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
    before = sys_.stats()

    # -- the window -------------------------------------------------------------
    lat, lanes = [], []
    n_window0 = len(log)
    trace_rounds = 0
    annotation = None
    with CompileCounter() as window_compiles:
        t_open = time.perf_counter()
        setup_s = t_open - t_start
        if trace:
            import jax

            annotation = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            annotation.__enter__()
        t_done = t_open
        while (t_done - t_open < seconds) if rounds is None else (len(lat) < rounds):
            with _annotate(trace, "bench.traffic"):
                ops, keys, vals = traffic.round(nxt)
            nxt += 1
            t_sub = time.perf_counter()
            with _annotate(trace, "bench.apply_round"):
                answer = sys_.apply(ops, keys, vals, cap)
            t_done = time.perf_counter()
            lat.append(t_done - t_sub)
            lanes.append(ops.size)
            log.append((ops, keys, vals, answer))
            if annotation is not None and (
                t_done - t_open >= min(TRACE_SECONDS, seconds / 2) if rounds is None
                else len(lat) >= rounds // 2
            ):
                trace_rounds = _stop_trace(annotation, len(lat))
                annotation = None
                tracer = Tracer()  # the rest of the window, fenced per phase
                sys_.set_tracer(tracer)
        if annotation is not None:
            trace_rounds = _stop_trace(annotation, len(lat))
        t_close = t_done
    window_rounds = len(log) - n_window0
    after = sys_.stats()
    info["window_rounds"] = window_rounds
    if lat:
        info["round_ms"] = dict(zip(("min", "p50", "p95", "max"),
                                    (float(x) * 1e3 for x in np.percentile(lat, [0, 50, 95, 100]))))
    info["compiles_in_window"] = window_compiles.names
    trace_path = None
    if trace:
        sys_.set_tracer(None)
        trace_path = trace_reduce.latest_trace(trace_dir)

    ops_done = int(sum(lanes))
    window_s = t_close - t_open
    metrics = {}
    e2e = {m["name"]: m for m in cell.end_to_end}

    # -- after the window: the durable fixed point and recovery -----------------
    recovered = None
    if getattr(sys_, "durable", False):
        fulls = sys_.full_snapshots()
        args = cfg["args"]
        cycle = args["snapshot_every"] * (args["full_snapshot_every"] + 1)
        for _ in range(cycle + 1):  # a store that never snapshots stops here
            if sys_.full_snapshots() != fulls:
                break
            log.append(_next_round(sys_, traffic, nxt, cap))
            nxt += 1
        # one round past the full snapshot: its answers are the last
        # acknowledged, and recovery must return them
        log.append(_next_round(sys_, traffic, nxt, cap))
        nxt += 1
        recover_s, recovered = sys_.recover()
        info["rounds_after_window"] = len(log) - n_window0 - window_rounds
        if "recover_s" in e2e:
            metrics["recover_s"] = recover_s
    live = sys_.items()
    peak = _memory_peak()
    sys_.close()
    del sys_
    gc.collect()

    # -- the check ----------------------------------------------------------------
    t = time.perf_counter()
    check = check_rounds(log, live, recovered, cap)
    info["check_s"] = time.perf_counter() - t

    if "ops_per_s" in e2e:
        metrics["ops_per_s"] = ops_done / window_s
    if "op_p95_ms" in e2e:
        metrics["op_p95_ms"] = _percentile_ms(lat, lanes, 95)
    metrics["setup_s"] = setup_s
    out = {
        "correct": all(v["value"] <= v["limit"] for v in check.values()),
        "attempted": ops_done,
        "failed": 0,
        "metrics": {},
        "peak": peak,
    }
    if trace:
        red = trace_reduce.reduce_trace(
            trace_path,
            kernels={k: getattr(r, "KERNEL") for k, r in cell.readers.items()
                     if hasattr(r, "KERNEL")},
        )
        window_lanes = {}
        for ops, _, _, _ in log[n_window0 : n_window0 + window_rounds]:
            for kind, code in (("find", OP_FIND), ("insert", OP_INSERT),
                               ("delete", OP_DELETE), ("scan", OP_RANGE)):
                window_lanes[kind] = window_lanes.get(kind, 0) + int(np.sum(ops == code))
        view = RunView(rounds=window_rounds, ops=ops_done, lanes=window_lanes,
                       spans=_span_list(tracer), span_rounds=window_rounds - trace_rounds,
                       before=before, after=after, trace=red, trace_rounds=trace_rounds)
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(view)
            if v is not None:
                out["metrics"][m["name"]] = {"value": float(v), "unit": m["unit"]}
        out["busy_s"] = red["busy_s"]
        out["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
        info["trace_rounds"] = trace_rounds
        info["span_rounds"] = window_rounds - trace_rounds
    else:
        for m in cell.end_to_end:
            if m["name"] in metrics:
                out["metrics"][m["name"]] = {"value": float(metrics[m["name"]]), "unit": m["unit"]}
    out["info"] = info
    out["check"] = check
    return out


def _next_round(sys_, traffic, i: int, cap):
    ops, keys, vals = traffic.round(i)
    return ops, keys, vals, sys_.apply(ops, keys, vals, cap)


def _stop_trace(annotation, rounds: int) -> int:
    """Close the traced part of the window; the profiler stops with it,
    so the trace holds just these rounds."""
    import jax

    annotation.__exit__(None, None, None)
    jax.profiler.stop_trace()
    return rounds


def _annotate(on: bool, name: str):
    """A profiler annotation in a traced run, nothing otherwise."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # no per-call Python tracing: it slows the host
    return opts


def _span_list(tracer):
    """The tracer's complete spans as ``(name, start_s, end_s)``."""
    if tracer is None:
        return []
    return [(e["name"], e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6)
            for e in tracer.events if e["ph"] == "X"]


def _memory_peak():
    import jax

    peaks = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# ----------------------------------------------------------------------------
# The check
# ----------------------------------------------------------------------------


def check_rounds(log: list, live: dict, recovered, cap) -> dict:
    """Replay every round through the reference and count what differs.
    Every number has the limit 0: the comparison is exact."""
    ref = reference.ReferenceSet()
    lane_bad = scan_bad = 0
    for ops, keys, vals, (res, fnd, scan) in log:
        r_res, r_fnd, r_scan = ref.apply_round(ops, keys, vals, cap or 128)
        lane_bad += int(np.sum((np.asarray(res) != r_res) | (np.asarray(fnd) != r_fnd)))
        if r_scan is not None:
            lanes, count, rk, rv = r_scan
            if scan is None:
                scan_bad += int(lanes.size)
                continue
            c, k, v = scan
            k = np.asarray(k)[lanes]
            v = np.asarray(v)[lanes]
            valid = np.arange(rk.shape[1])[None, :] < count[:, None]
            bad = (np.asarray(c)[lanes] != count) | np.any(k != rk, axis=1) | np.any(
                valid & (v != rv), axis=1
            )
            scan_bad += int(bad.sum())
    want = ref.items()
    out = {
        "lane_mismatches": {"value": lane_bad, "limit": 0},
        "scan_row_mismatches": {"value": scan_bad, "limit": 0},
        "content_mismatches": {"value": _dict_diff(live, want), "limit": 0},
    }
    if recovered is not None:
        out["recovered_mismatches"] = {"value": _dict_diff(recovered, want), "limit": 0}
    return out


def _dict_diff(a: dict, b: dict) -> int:
    """Keys present on one side only, or with different values."""
    return len(a.keys() ^ b.keys()) + sum(1 for k in a.keys() & b.keys() if a[k] != b[k])
