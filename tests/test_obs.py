"""Observability subsystem: tracer overhead contract (no fences, no HLO
delta when disabled), flight-recorder overhead contract (host-side only,
no HLO delta on/off), metrics-registry/legacy-counter equivalence (incl.
the durable layer's ``DurableStats`` and merge re-keying), Chrome
trace-event schema + report CLI, and the forest's hot-shard hook."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import ABForest, ABTree, OP_FIND, OP_INSERT, TreeConfig
from repro.obs import MetricsRegistry, NULL_TRACER, Tracer

CFG = TreeConfig(capacity=2048, b=8, a=2, max_height=12)


def _insert_batch(rng, n=128, hi=10**6):
    keys = rng.choice(hi, size=n, replace=False).astype(np.int64)
    return np.full(n, OP_INSERT, np.int32), keys, keys * 2


# ---------------------------------------------------------------------------
# tracer overhead contract
# ---------------------------------------------------------------------------


def test_tracer_disabled_adds_no_fences_and_no_hlo(monkeypatch):
    """The whole disabled path is one attribute check: an untraced round
    must issue ZERO ``block_until_ready`` calls, and the jitted phases
    lower to byte-identical HLO before/after installing a live tracer
    (the tracer never enters jit)."""
    from repro.core import rounds as R

    t = ABTree(CFG)
    rng = np.random.default_rng(0)
    st0 = t.state
    batch = (
        jnp.full((64,), OP_INSERT, jnp.int32),
        jnp.asarray(rng.integers(0, 10**6, 64), jnp.int64),
        jnp.zeros((64,), jnp.int64),
    )
    hlo_before = R._phase_search_combine.lower(st0, batch, t.cfg, False).as_text()

    calls = []
    real = jax.block_until_ready

    def spy(x):
        calls.append(1)
        return real(x)

    monkeypatch.setattr("repro.obs.tracer.jax.block_until_ready", spy)

    assert t.tracer is NULL_TRACER  # no tracer installed → shared no-op
    t.apply_round(*_insert_batch(rng))
    t.scan_round([0], [10**6], cap=8)
    assert calls == [], "disabled tracer must never fence"

    t.tracer = Tracer()
    t.apply_round(*_insert_batch(rng))
    assert calls, "enabled tracer must fence the phases it times"
    assert t.tracer.events, "enabled tracer must record spans"

    hlo_after = R._phase_search_combine.lower(st0, batch, t.cfg, False).as_text()
    assert hlo_before == hlo_after, "tracing must not change lowered HLO"


def test_null_tracer_span_is_shared_noop():
    s1 = NULL_TRACER.span("a", shard=3, foo=1)
    s2 = NULL_TRACER.span("b")
    assert s1 is s2  # one shared object, no allocation per phase
    with s1 as sp:
        assert sp.fence(123) == 123
        sp.note(k=1)
    assert NULL_TRACER.events == []


# ---------------------------------------------------------------------------
# device→host pulls and the profiler's clock
# ---------------------------------------------------------------------------


class _ConversionSpy:
    """Counts the device arrays whose data the backend hands to the host,
    each once, however the conversion is asked for: ``int``/``bool``/
    ``tolist`` (and numpy off the CPU) reach the backend's fetch through
    ``_value``; numpy on the CPU takes the buffer protocol.  Once is what
    a copying backend (TPU) moves: it keeps the first fetch's host copy
    and serves every later conversion of that array from it."""

    def __init__(self, monkeypatch):
        from jax._src.array import ArrayImpl

        self.n = self.bytes = 0
        self._seen = {}  # id -> array, held so that no id is reused
        fetch, buffer = ArrayImpl._single_device_array_to_np_array_did_copy, ArrayImpl.__buffer__

        def on_fetch(arr):
            self._count(arr)
            return fetch(arr)

        def on_buffer(arr, flags):
            self._count(arr)
            return buffer(arr, flags)

        monkeypatch.setattr(ArrayImpl, "_single_device_array_to_np_array_did_copy", on_fetch)
        monkeypatch.setattr(ArrayImpl, "__buffer__", on_buffer)

    def _count(self, arr):
        if id(arr) not in self._seen:
            self._seen[id(arr)] = arr
            self.n += 1
            self.bytes += arr.nbytes


def _pull_round(seed=5):
    """A prefill and one mixed round (inserts that split leaves, deletes
    that leave nodes underfull, range and find lanes)."""
    rng = np.random.default_rng(seed)
    keys = rng.choice(10**5, 800, replace=False).astype(np.int64)
    prefill = [
        (np.full(200, OP_INSERT, np.int32), keys[i : i + 200], keys[i : i + 200])
        for i in range(0, 600, 200)
    ]
    from repro.core import OP_DELETE, OP_RANGE

    ops = np.concatenate([
        np.full(150, OP_INSERT), np.full(150, OP_DELETE),
        np.full(4, OP_RANGE), np.full(8, OP_FIND),
    ]).astype(np.int32)
    ks = np.concatenate([keys[600:750], keys[:150], keys[300:304], keys[400:408]])
    vs = np.concatenate([ks[:150], np.zeros(150, np.int64), np.full(4, 5000),
                         np.zeros(8, np.int64)])
    return prefill, (ops, ks, vs)


@pytest.mark.parametrize("holder", ["ABTree", "DurableABTree"])
def test_pull_counters_match_every_device_to_host_conversion(monkeypatch, tmp_path, holder):
    """One round moves ``host_syncs`` / ``d2h_bytes`` by exactly the
    conversions and bytes a spy on ``jax.Array`` sees.  The measured
    holder is the second of two identical ones, so every shape is
    compiled before the spy goes in (lowering embeds constants, which is
    a conversion of its own)."""
    from repro.core import DurableABTree

    def build(i):
        if holder == "ABTree":
            return ABTree(CFG)
        return DurableABTree(str(tmp_path / f"j{i}"), CFG, snapshot_every=4)

    prefill, mixed = _pull_round()
    for i in range(2):
        t = build(i)
        for batch in prefill:
            t.apply_round(*batch)
        m = t.metrics
        before = {k: m.value(k) for k in ("host_syncs", "d2h_bytes", "split_waves",
                                           "underfull_waves", "scans")}
        if i:
            spy = _ConversionSpy(monkeypatch)
        t.apply_round(*mixed)
    d = {k: m.value(k) - v for k, v in before.items()}
    assert d["split_waves"] and d["underfull_waves"] and d["scans"]  # every loop ran
    assert d["host_syncs"] == spy.n > 0
    assert d["d2h_bytes"] == spy.bytes
    monkeypatch.undo()
    st = t.stats()  # the counters as they stood before its own pull
    assert (st["host_syncs"], st["d2h_bytes"]) == (before["host_syncs"] + d["host_syncs"],
                                                   before["d2h_bytes"] + d["d2h_bytes"])
    if holder == "DurableABTree":
        t.close()


def test_pull_passes_host_values_through_uncounted():
    from repro.obs import pull

    reg = MetricsRegistry()
    holder = type("H", (), {"metrics": reg})()
    assert pull(holder, "x", [1, 2]).tolist() == [1, 2]
    assert pull(holder, "x", np.arange(3)).tolist() == [0, 1, 2]
    assert reg.value("host_syncs") == 0
    out = pull(holder, "x", jnp.arange(4, dtype=jnp.int32))
    assert isinstance(out, np.ndarray) and out.tolist() == [0, 1, 2, 3]
    assert (reg.value("host_syncs"), reg.value("d2h_bytes")) == (1, 16)
    assert pull(None, "x", jnp.ones(2)).tolist() == [1.0, 1.0]  # no registry: still a pull


def test_pull_counts_an_array_once():
    """A repeated pull of one array reads its host copy: one sync, its
    bytes once.  An equal array is another transfer, and an array's entry
    goes with it."""
    import gc

    from repro.obs import pull
    from repro.obs import tracer as T

    reg = MetricsRegistry()
    holder = type("H", (), {"metrics": reg})()
    x = jnp.arange(8, dtype=jnp.int32)
    assert pull(holder, "x", x).tolist() == pull(holder, "y", x).tolist() == list(range(8))
    assert (reg.value("host_syncs"), reg.value("d2h_bytes")) == (1, 32)
    pull(holder, "x", jnp.arange(8, dtype=jnp.int32))
    assert (reg.value("host_syncs"), reg.value("d2h_bytes")) == (2, 64)
    key = id(x)
    assert key in T._pulled
    del x
    gc.collect()
    assert key not in T._pulled


def _xplane_names(log_dir):
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            names.update(ev.name for ev in line.events)
    return names


@pytest.mark.parametrize("enabled", [False, True], ids=["null_tracer", "tracer"])
def test_spans_and_pulls_are_profiler_annotations_while_a_session_runs(tmp_path, monkeypatch,
                                                                       enabled):
    """Under ``jax.profiler`` a round's trace holds the program's span
    names and its pulls, whether the span tracer is enabled or not; a
    disabled tracer still adds no fence.  Outside a session the disabled
    span is the shared no-op again."""
    from repro.obs import tracer as T

    t = ABTree(CFG)
    rng = np.random.default_rng(8)
    t.apply_round(*_insert_batch(rng))
    t.tracer = Tracer() if enabled else NULL_TRACER
    fences = []
    real = jax.block_until_ready
    monkeypatch.setattr("repro.obs.tracer.jax.block_until_ready",
                        lambda x: (fences.append(1), real(x))[1])
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert NULL_TRACER.span("x") is not T._NULL_SPAN
        t.apply_round(*_insert_batch(rng))
    finally:
        jax.profiler.stop_trace()
    names = _xplane_names(str(tmp_path))
    assert {"repro.round", "repro.search_combine", "repro.apply", "repro.retry",
            "repro.rebalance"} <= names
    assert any(n.startswith("repro.pull.") for n in names), sorted(names)
    assert "repro.pull.answers" in names
    assert bool(fences) == enabled
    assert NULL_TRACER.span("a") is NULL_TRACER.span("b") is T._NULL_SPAN


# ---------------------------------------------------------------------------
# flight-recorder overhead contract
# ---------------------------------------------------------------------------


def test_recorder_host_side_only_and_no_hlo_delta(monkeypatch):
    """The recorder mirrors the tracer's overhead contract: it never
    fences (host-side capture of values the engine already materialised),
    disabling it turns every recording method into one attribute check,
    and the jitted phases lower to byte-identical HLO with recording on
    or off (the recorder never enters jit)."""
    from repro.core import rounds as R
    from repro.obs import NULL_RECORDER, Recorder

    t = ABTree(CFG)
    rng = np.random.default_rng(21)
    st0 = t.state
    batch = (
        jnp.full((64,), OP_INSERT, jnp.int32),
        jnp.asarray(rng.integers(0, 10**6, 64), jnp.int64),
        jnp.zeros((64,), jnp.int64),
    )
    hlo_on = R._phase_search_combine.lower(st0, batch, t.cfg, False).as_text()

    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(
        "repro.obs.tracer.jax.block_until_ready",
        lambda x: (calls.append(1), real(x))[1],
    )
    assert t.recorder.enabled, "holders construct an always-on recorder"
    t.apply_round(*_insert_batch(rng))
    t.scan_round([0], [10**6], cap=8)
    assert t.recorder.records(), "always-on recorder must capture rounds"
    assert calls == [], "the recorder must never fence"

    t.recorder = Recorder(enabled=False)
    t.apply_round(*_insert_batch(rng))
    t.scan_round([0], [10**6], cap=8)
    assert t.recorder.records() == []
    assert t.recorder.snapshot()["events"] == 0
    hlo_off = R._phase_search_combine.lower(st0, batch, t.cfg, False).as_text()
    assert hlo_on == hlo_off, "recording must not change lowered HLO"


def test_null_recorder_is_shared_noop():
    from repro.obs import NULL_RECORDER

    NULL_RECORDER.note_elim({"eliminated": [1]})
    NULL_RECORDER.note_occ(subrounds=3)
    NULL_RECORDER.note_scan_phase(retries=1, attempts=2)
    NULL_RECORDER.round(
        round_no=0, mode="elim", n_shards=1,
        ops=[1], keys=[2], vals=[3], results=[0], found=[False],
    )
    NULL_RECORDER.transition("split", shard=0)
    NULL_RECORDER.commit(0, 0)
    assert NULL_RECORDER.records() == []
    assert NULL_RECORDER.snapshot() == {
        "enabled": False,
        "capacity": NULL_RECORDER.capacity,
        "events": 0,
        "rounds": 0,
        "seq": 0,
    }


def test_recorder_ring_is_bounded():
    from repro.obs import Recorder

    r = Recorder(capacity=4)
    for i in range(10):
        r.transition("split", shard=i)
    recs = r.records()
    assert len(recs) == 4  # ring drops the oldest
    assert [x["shard"] for x in recs] == [6, 7, 8, 9]
    assert r.snapshot()["seq"] == 10


def test_recorder_in_serve_stats():
    """``ServeEngine.stats()`` exposes the recorder snapshot, and the
    setter installs one recorder across both index holders."""
    from repro.configs import get_config
    from repro.models import reduced
    from repro.obs import Recorder
    from repro.serve.engine import Request, ServeEngine

    eng = ServeEngine(
        reduced(get_config("qwen2-0.5b"), n_layers=1),
        max_batch=2,
        s_max=64,
        n_pages=64,
    )
    rec = Recorder()
    eng.recorder = rec
    assert eng.index.tree.recorder is rec
    assert eng.sessions.tree.recorder is rec
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new=2))
    eng.run_until_done(max_ticks=20)
    s = eng.stats()
    assert s["recorder"]["enabled"] is True
    assert s["recorder"]["rounds"] > 0  # lookup/publish rounds recorded


# ---------------------------------------------------------------------------
# metrics registry + legacy-counter equivalence
# ---------------------------------------------------------------------------


def test_metrics_registry_shard_attribution():
    m = MetricsRegistry()
    m.inc("x", 3, shard=0)
    m.inc("x", 2, shard=2)
    m.inc_shard("x", 5, 1)  # per-shard only: global stays 5
    assert m.value("x") == 5
    assert m.per_shard("x", 3) == [3, 5, 2]
    m.insert_shard(1)  # split at 1: cells ≥ 1 shift up
    assert m.per_shard("x", 4) == [3, 0, 5, 2]
    m.observe("h", 1.0)
    m.observe("h", 3.0)
    s = m.histogram_summary("h")
    assert s["count"] == 2 and s["min"] == 1.0 and s["max"] == 3.0
    snap = m.snapshot()
    assert snap["counters"]["x"] == 5
    assert snap["per_shard"]["x"] == {"0": 3, "2": 5, "3": 2}
    assert snap["histograms"]["h"]["count"] == 2


def test_legacy_counters_are_registry_backed():
    """``tree._rounds`` / ``_scans`` / ``_scan_retries`` and the registry
    are ONE store — reads agree after writes through either surface."""
    t = ABTree(CFG)
    rng = np.random.default_rng(1)
    t.apply_round(*_insert_batch(rng))
    t.apply_round(*_insert_batch(rng))
    t.scan_round([0], [10**6], cap=8)
    assert t._rounds == t.stats()["rounds"] == t.metrics.value("rounds") == 2
    assert t._scans == t.stats()["scans"] == t.metrics.value("scans") == 1
    assert t._scan_retries == t.metrics.value("scan_retries")
    t._rounds = 77  # legacy write lands in the registry
    assert t.metrics.value("rounds") == 77
    snap = t.metrics.snapshot()
    assert snap["engine"]["rounds"] == 77
    assert "retries_per_op" in snap["derived"]


def test_metrics_registry_remove_shard_rekeys_cells():
    """``remove_shard`` drops the retired shard's cells and shifts the
    cells above it down — attribution keeps following surviving shards."""
    m = MetricsRegistry()
    m.inc("x", 1, shard=0)
    m.inc("x", 2, shard=1)
    m.inc("x", 3, shard=2)
    m.remove_shard(1)
    assert m.per_shard("x", 2) == [1, 3]
    assert m.value("x") == 6  # the global total keeps the retired cell


def test_merge_cold_attributes_to_survivor_after_rekeying():
    """Regression: ``_merge_cold`` must re-key the registry BEFORE
    attributing the merge.  When the survivor is the retired shard's
    upper neighbor its post-restack index EQUALS the retired index, so
    incrementing first left the count on the cell ``remove_shard`` was
    about to pop — the survivor read 0 merges."""
    f = ABForest(n_shards=2, cfg=CFG, key_space=(0, 4096))
    keys = np.arange(0, 4096, 16, dtype=np.int64)
    f.apply_round(np.full(keys.size, OP_INSERT, np.int32), keys, keys)
    n_before = len(f.items())
    assert f._merge_cold(0)  # survivor t=1 restacks to index 0
    assert f.n_shards == 1
    assert len(f.items()) == n_before  # merge moved, never dropped, keys
    assert f.metrics.value("shard_merges", shard=0) == 1
    assert f.metrics.per_shard("shard_merges", 1) == [1]


def test_forest_per_shard_lanes_sum_to_global():
    f = ABForest(n_shards=4, cfg=CFG, key_space=(0, 4096))
    rng = np.random.default_rng(2)
    keys = rng.choice(4096, size=256, replace=False).astype(np.int64)
    f.apply_round(np.full(256, OP_INSERT, np.int32), keys, keys)
    total = f.metrics.value("point_lanes")
    assert total == 256
    assert sum(f.metrics.per_shard("point_lanes", 4)) == total


def test_durable_stats_match_registry(tmp_path):
    """The durable layer mirrors every ``DurableStats`` field into the
    backing holder's registry (ONE ``holder.metrics`` surface), and
    snapshot churn actually garbage-collects superseded journal files."""
    from repro.core.durable import DurableForest

    dur = DurableForest(str(tmp_path), 2, CFG, snapshot_every=2)
    rng = np.random.default_rng(3)
    for _ in range(5):
        dur.apply_round(*_insert_batch(rng, n=64))
    m = dur.metrics  # delegated to the backing forest's registry
    assert m is dur.forest.metrics
    for field in ("commits", "flush_bytes", "fsyncs", "nodes_flushed", "gc_removed"):
        assert m.value(field) == getattr(dur.dstats, field), field
    assert dur.dstats.gc_removed > 0
    h = m.histogram_summary("fsync_latency_s")
    assert h["count"] > 0 and h["p99"] >= h["p50"] > 0.0


# ---------------------------------------------------------------------------
# trace export schema + report CLI
# ---------------------------------------------------------------------------


def _traced_forest_trace(tmp_path):
    f = ABForest(n_shards=2, cfg=CFG, key_space=(0, 4096))
    f.tracer = Tracer()
    rng = np.random.default_rng(4)
    keys = rng.choice(4096, size=200, replace=False).astype(np.int64)
    f.apply_round(np.full(200, OP_INSERT, np.int32), keys, keys)
    f.scan_round([0, 2048], [2048, 4096], cap=16)
    path = str(tmp_path / "trace.json")
    f.tracer.export(path)
    return path


def test_trace_export_schema(tmp_path):
    from repro.obs.trace_export import load_trace, validate_trace

    path = _traced_forest_trace(tmp_path)
    doc = load_trace(path)
    assert validate_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    # every engine phase of the round pipeline shows up in one traced run
    for phase in ("round", "search_combine", "apply", "retry", "rebalance", "scan"):
        assert phase in names, phase
    # per-shard attribution rides instant events on tid >= 1
    assert any(
        e["ph"] == "i" and e["tid"] >= 1 for e in doc["traceEvents"]
    ), "expected per-shard instants"


def test_report_cli_roundtrip(tmp_path, capsys):
    from repro.obs import report

    path = _traced_forest_trace(tmp_path)
    assert report.main([path]) == 0
    out = capsys.readouterr().out
    assert "phase breakdown" in out and "search_combine" in out

    bad = tmp_path / "bad.json"
    bad.write_text('{"traceEvents": [{"ph": "Q"}]}')
    assert report.main([str(bad)]) == 1


def test_validate_trace_rejects_malformed():
    from repro.obs.trace_export import validate_trace

    assert validate_trace({"nope": 1})
    assert validate_trace({"traceEvents": [{"name": "x", "ph": "X", "pid": 0}]})
    ok = {
        "traceEvents": [
            {"name": "x", "ph": "X", "ts": 0.0, "dur": 1.0, "pid": 0, "tid": 0}
        ]
    }
    assert validate_trace(ok) == []


# ---------------------------------------------------------------------------
# hot-shard hook
# ---------------------------------------------------------------------------


def test_hot_shard_hook_fires_under_skew():
    """A Zipf-skewed stream concentrating on one shard's key range must
    trip the hook with that shard's id once the observation window fills;
    a uniform stream across shards must not."""
    events = []
    f = ABForest(
        n_shards=2, cfg=CFG, key_space=(0, 4096),
        hot_shard_frac=0.9, hot_shard_window=128,
    )
    f.hot_shard_hook = lambda s, info: events.append((s, info))
    rng = np.random.default_rng(5)
    for _ in range(3):
        keys = rng.choice(2048, size=128, replace=False).astype(np.int64)
        f.apply_round(np.full(128, OP_INSERT, np.int32), keys, keys)  # all shard 0
    assert events, "skewed load must fire the hot-shard hook"
    s, info = events[0]
    assert s == 0
    assert info["frac"] >= 0.9
    assert info["bounds"][0] <= 0 < info["bounds"][1]
    assert f.metrics.value("hot_shard_events", shard=0) == len(events)

    events.clear()
    f2 = ABForest(
        n_shards=2, cfg=CFG, key_space=(0, 4096),
        hot_shard_frac=0.9, hot_shard_window=128,
    )
    f2.hot_shard_hook = lambda s, info: events.append((s, info))
    keys = rng.choice(4096, size=256, replace=False).astype(np.int64)  # uniform
    f2.apply_round(np.full(256, OP_INSERT, np.int32), keys, keys)
    assert not events, "balanced load must not fire the hook"


# ---------------------------------------------------------------------------
# ragged-router pack telemetry + repartition span
# ---------------------------------------------------------------------------


def test_pad_waste_drops_under_ragged_packing():
    """The router's pow2-bucketed per-shard widths must ship materially
    less padding than the full-batch-width packing they replaced: on a
    uniform round the observed ``pack_pad_waste`` sits well below the
    waste of padding every shard to the whole batch's pow2 width.  The
    ``router_pack_width`` / ``pad_waste_frac`` gauges expose the last
    pack's numbers."""
    from repro.core.rounds import _pow2

    f = ABForest(n_shards=4, cfg=CFG, key_space=(0, 4096))
    rng = np.random.default_rng(9)
    bsz = 64
    for _ in range(3):
        keys = rng.integers(0, 4096, bsz).astype(np.int64)
        f.apply_round(np.full(bsz, OP_INSERT, np.int32), keys, keys)
    h = f.metrics.histogram_summary("pack_pad_waste")
    assert h["count"] >= 3
    # full-width packing pads every shard to pow2(batch): 4·pow2(64) slots
    # for 64 real lanes.
    full_waste = (4 * _pow2(bsz) - bsz) / (4 * _pow2(bsz))
    assert h["p50"] < full_waste - 0.15, (h, full_waste)
    snap = f.metrics.snapshot()["gauges"]
    assert snap["router_pack_width"] >= bsz  # S·w slots actually shipped
    assert 0.0 <= snap["pad_waste_frac"] < full_waste


def test_report_surfaces_pack_stats_and_repartition_span(tmp_path, capsys):
    """``python -m repro.obs.report`` renders the router pack table (count,
    mean width, mean pad waste) and lists the ``repartition`` span in the
    phase breakdown once a load-aware rebalance has fired in the trace."""
    from repro.obs import report

    f = ABForest(
        n_shards=2, cfg=CFG, key_space=(0, 400),
        auto_repartition=True, hot_shard_window=64,
    )
    f.tracer = Tracer()
    rng = np.random.default_rng(13)
    seed = np.arange(0, 400, 2, dtype=np.int64)
    f.apply_round(np.full(seed.size, OP_INSERT, np.int32), seed, seed)
    for _ in range(4):  # 80/20 skew: trips the window into a rebalance
        keys = np.concatenate(
            [rng.integers(0, 100, 38), rng.integers(200, 400, 10)]
        ).astype(np.int64)
        f.apply_round(np.full(48, OP_FIND, np.int32), keys, np.zeros(48, np.int64))
        if int(f.metrics.snapshot()["counters"].get("repartitions", 0)):
            break
    assert int(f.metrics.snapshot()["counters"].get("repartitions", 0)) >= 1
    path = str(tmp_path / "trace_rep.json")
    f.tracer.export(path)
    assert report.main([path]) == 0
    out = capsys.readouterr().out
    assert "repartition" in out  # the span rides the phase breakdown
    assert "router pack stats" in out
    assert "mean_pad_waste" in out
    assert "(no router_pack spans)" not in out
