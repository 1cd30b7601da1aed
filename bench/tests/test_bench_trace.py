"""The trace reduction on a small trace recorded on the CPU in the test."""
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import trace_reduce  # noqa: E402


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    d = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.apply_round"):
                f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.2)
        time.sleep(0.1)  # covered by no annotation but the window's
    jax.profiler.stop_trace()
    return trace_reduce.latest_trace(d)


def test_busy_idle_and_kernels_from_a_cpu_trace(trace):
    red = trace_reduce.reduce_trace(trace, kernels={"matmul": "dot_general", "none": "no-such-op"})
    assert 0.3 < red["window_s"] < 5
    assert 0 < red["busy_s"] < red["window_s"]
    assert 0 < red["idle_share"] < 1
    assert red["idle_share"] == pytest.approx(1 - red["busy_s"] / red["window_s"])
    assert red["kernels_s"]["matmul"] > 0 and red["kernels_s"]["none"] == 0
    assert 0 < len(red["device_ops"]) <= 10
    assert sum(s for _, s in red["device_ops"]) <= red["busy_s"] * 1.0001


def test_idle_gaps_are_named_by_the_innermost_host_activity(trace):
    red = trace_reduce.reduce_trace(trace)
    gaps = dict(red["idle_gaps"])
    assert gaps["bench.sleep"] == pytest.approx(0.2, abs=0.05)
    assert gaps[trace_reduce.NO_HOST] == pytest.approx(0.1, abs=0.05)
    assert sum(gaps.values()) <= red["window_s"] - red["busy_s"] + 1e-6


def test_union_and_namer():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    name_at = trace_reduce._namer([("outer", 0, 10), ("inner", 2, 4), ("later", 6, 8)])
    assert [name_at(t) for t in (1, 3, 5, 7, 11)] == [
        "outer", "inner", "outer", "later", trace_reduce.NO_HOST]


def test_a_trace_without_the_window_annotation_is_refused(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    jax.numpy.ones(3).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace(trace_reduce.latest_trace(str(tmp_path)))
