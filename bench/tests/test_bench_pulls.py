"""Idle time by program phase and the readers of the host-sync, capture
and pull-idle metrics, on the CPU."""
import os
import shutil
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import harness  # noqa: E402
import idle_spans  # noqa: E402
import trace_reduce  # noqa: E402
from test_bench_harness import BIG_SEED, _tiny_root  # noqa: E402

NEW = ("host.syncs_per_round", "host.d2h_mb_per_round", "durable.capture_ms",
       "device.pull_idle_share")


def _record(log_dir, program: bool):
    """Three rounds of device work and host waits inside ``bench.window``;
    with ``program`` the waits sit in nested ``repro.*`` annotations."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    ann = jax.profiler.TraceAnnotation
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with ann(trace_reduce.WINDOW):
        for _ in range(3):
            with ann("bench.apply_round"):
                if program:
                    with ann("repro.round"):
                        with ann("repro.search_combine"):
                            f(x).block_until_ready()
                        with ann("repro.pull.answers"):
                            time.sleep(0.06)
                        time.sleep(0.03)  # in the round, outside any inner span
                else:
                    f(x).block_until_ready()
                    time.sleep(0.09)
        with ann("bench.sleep"):
            time.sleep(0.1)
    jax.profiler.stop_trace()
    return trace_reduce.latest_trace(log_dir)


@pytest.fixture(scope="module")
def traces(tmp_path_factory):
    return {p: _record(str(tmp_path_factory.mktemp(f"trace{int(p)}")), p) for p in (True, False)}


def test_idle_by_span_splits_all_idle_time_by_the_innermost_program_span(traces):
    red = trace_reduce.reduce_trace(traces[True])
    out = idle_spans.idle_by_span(traces[True])
    pieces = dict(out["idle_by_span"])
    assert out["window_s"] == red["window_s"]
    idle = red["window_s"] - red["busy_s"]  # one device: its busy union
    assert sum(pieces.values()) == pytest.approx(out["idle_s"], abs=1e-12)
    assert out["idle_s"] == pytest.approx(idle, abs=1e-6 * len(pieces))
    assert pieces["repro.pull.answers"] == pytest.approx(0.18, abs=0.05)
    assert pieces["repro.round"] == pytest.approx(0.09, abs=0.05)
    assert pieces[idle_spans.OUTSIDE] == pytest.approx(0.1, abs=0.05)  # bench.sleep
    assert out["round_ms"]["n"] == 3 and out["round_ms"]["p50"] >= 90
    # reduce_trace's own gaps still name the innermost host activity of any kind
    assert dict(red["idle_gaps"])["repro.pull.answers"] == pytest.approx(0.18, abs=0.05)


def test_a_trace_without_program_spans_reads_as_outside_the_program(traces):
    red = trace_reduce.reduce_trace(traces[False])
    out = idle_spans.idle_by_span(traces[False])
    assert [n for n, _ in out["idle_by_span"]] == [idle_spans.OUTSIDE]
    assert out["idle_s"] == pytest.approx(red["window_s"] - red["busy_s"], abs=1e-6)
    gaps = dict(red["idle_gaps"])
    assert gaps["bench.sleep"] == pytest.approx(0.1, abs=0.05)
    assert gaps["bench.apply_round"] == pytest.approx(0.27, abs=0.08)


def test_the_cli_prints_the_newest_trace_under_a_directory(traces, capsys):
    import json

    assert idle_spans.main([os.path.dirname(traces[True])]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["idle_by_span"] == idle_spans.idle_by_span(traces[True])["idle_by_span"]
    assert out["idle_by_span"][0][0] == "repro.pull.answers"


def _view(**kw):
    args = dict(rounds=10, ops=640, lanes={}, spans=[], span_rounds=5, before={}, after={},
                trace=None, trace_rounds=5)
    args.update(kw)
    return harness.RunView(**args)


@pytest.fixture()
def readers(tmp_path):
    """The new readers, from a copy of the benchmark under ``tmp_path``
    (where ``device.pull_idle_share`` looks for the run's trace)."""
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return {m: harness._load_reader(str(tmp_path / "bench"), m) for m in NEW}


def test_the_counter_readers_take_the_window_delta_per_round(readers):
    v = _view(before={"host_syncs": 100, "d2h_bytes": 5_000_000},
              after={"host_syncs": 350, "d2h_bytes": 95_000_000})
    assert readers["host.syncs_per_round"].read(v) == pytest.approx(25.0)
    assert readers["host.d2h_mb_per_round"].read(v) == pytest.approx(9.0)
    assert readers["host.syncs_per_round"].read(_view()) is None  # a program without counters
    assert readers["host.d2h_mb_per_round"].read(_view()) is None
    assert readers["host.syncs_per_round"].read(_view(rounds=0, after={"host_syncs": 1})) is None


def test_the_capture_reader_reads_its_span_per_round(readers):
    spans = [("commit_capture", 0.0, 0.3), ("journal_flush", 0.3, 0.32),
             ("commit_capture", 1.0, 1.2)]
    assert readers["durable.capture_ms"].read(_view(spans=spans)) == pytest.approx(100.0)
    assert readers["durable.capture_ms"].read(
        _view(spans=[("journal_flush", 0.3, 0.32)])) is None


def test_the_pull_idle_reader_finds_the_runs_trace(readers, traces, tmp_path):
    reader = readers["device.pull_idle_share"]
    red = trace_reduce.reduce_trace(traces[True])
    assert reader.read(_view(trace=red)) is None  # no trace under the root yet
    run_dir = tmp_path / ".bench_trace" / "cell"
    shutil.copytree(os.path.dirname(traces[True]), run_dir)
    share = reader.read(_view(trace=red))
    assert share == pytest.approx(100 * 0.18 / red["window_s"], abs=100 * 0.05 / red["window_s"])
    assert share <= 100 * red["idle_share"]
    assert reader.read(_view(trace=dict(red, window_s=red["window_s"] + 1))) is None  # not this run's
    assert reader.read(_view(trace=None)) is None
    shutil.rmtree(run_dir)
    shutil.copytree(os.path.dirname(traces[False]), run_dir)
    assert reader.read(_view(trace=trace_reduce.reduce_trace(traces[False]))) is None


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "PREFILL_WIDTH", 256)
        mp.setattr(harness, "WARMUP_ROUNDS", 6)
        yield _tiny_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("base", ["set2m_volatile", "set2m_durable"])
def test_a_tiny_traced_run_reports_the_new_metrics(tiny, base):
    cell = harness.Cell(tiny, f"tiny_{base}.tiny_zipf")
    out = harness.run_cell(cell, BIG_SEED, 30.0, True, t_start=time.perf_counter(), rounds=16)
    assert out["correct"], out["check"]
    got = {m: out["metrics"][m]["value"] for m in NEW if m in out["metrics"]}
    want = set(NEW) if base == "set2m_durable" else set(NEW) - {"durable.capture_ms"}
    assert set(got) == want
    assert got["host.syncs_per_round"] > 0 and got["host.d2h_mb_per_round"] > 0
    assert 0 <= got["device.pull_idle_share"] <= out["metrics"]["device.idle_share"]["value"]
    if base == "set2m_durable":
        assert got["durable.capture_ms"] > 0
        # the capture's whole-pool pull alone: the persisted fields of the pool
        pool = cell.config["tree"]["capacity"] + 1
        b = cell.config["tree"]["b"]
        assert got["host.d2h_mb_per_round"] * 1e6 >= pool * (2 * b * 8 + b * 4 + 1 + 4)
    assert np.isfinite(list(got.values())).all()
