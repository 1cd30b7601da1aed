"""Pipelined serving tests: the double-buffered tick (decode dispatched,
next round's admit overlapped under the in-flight device work), the
``tick_overlap_frac`` telemetry that PINS the overlap, span ordering in the
tracer, and the pipelined engine's durability surface — group-commit depth,
drain-at-exit, warm restart with the grouping knobs."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import reduced
from repro.obs import Tracer
from repro.serve.engine import Request, ServeEngine

CFG = reduced(get_config("qwen2-0.5b"), n_layers=1)


def _mk_engine(**kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("s_max", 64)
    kw.setdefault("n_pages", 128)
    return ServeEngine(CFG, **kw)


def _submit_all(eng, n, seed=0, max_new=4):
    rng = np.random.default_rng(seed)
    for i in range(n):
        eng.submit(Request(rid=i, prompt=list(rng.integers(0, 100, 8)),
                           max_new=max_new))


def test_pipelined_completes_same_requests_as_serial():
    """Pipelining reorders HOST work only: every submitted request still
    completes with exactly ``max_new`` generated tokens, admission and
    retirement counters agree with the serial engine."""
    results = {}
    for pipelined in (False, True):
        eng = _mk_engine(pipelined=pipelined)
        _submit_all(eng, 6, seed=1)
        done = eng.run_until_done(max_ticks=200)
        results[pipelined] = {
            "rids": sorted(r.rid for r in done),
            "lens": sorted(len(r.out) for r in done),
            "admitted": eng.metrics.value("admitted"),
            "retired": eng.metrics.value("retired"),
        }
    assert results[False] == results[True]
    assert results[True]["rids"] == list(range(6))
    assert results[True]["lens"] == [4] * 6


def test_prefill_keeps_other_slots_cache():
    """A slot's prefill writes only its own cache row: the cache a request
    built during its prefill is the same whether or not another request
    was admitted (and prefilled) after it in the same tick.  The decode
    step writes every row, so an unmasked prefill would overwrite the
    earlier request's prompt with the later one's padding tokens."""
    import jax

    rows = []
    for n in (1, 2):
        eng = _mk_engine(max_batch=2)
        _submit_all(eng, n, seed=4)
        eng._admit()
        rows.append([np.asarray(x)[:, 0, :, :7] for x in jax.tree.leaves(eng.cache)])
    for alone, shared in zip(*rows):
        np.testing.assert_array_equal(alone, shared)


def test_tick_overlap_frac_is_positive():
    """The whole point of the double-buffered tick: admit work runs WHILE a
    decode is in flight, so the overlap fraction must be strictly positive
    on decode ticks (and the gauge reflects the last tick)."""
    eng = _mk_engine(pipelined=True)
    _submit_all(eng, 8, seed=2)
    eng.run_until_done(max_ticks=200)
    h = eng.metrics.histogram_summary("tick_overlap_frac")
    assert h["count"] == eng.metrics.value("ticks")
    assert h["max"] > 0.0, "no tick overlapped host work with a decode"
    assert eng.metrics.snapshot()["gauges"]["tick_overlap_frac"] > 0.0


def test_serial_engine_does_not_emit_overlap_metric():
    eng = _mk_engine(pipelined=False)
    _submit_all(eng, 2, seed=3)
    eng.run_until_done(max_ticks=100)
    assert eng.metrics.histogram_summary("tick_overlap_frac")["count"] == 0


def test_pipelined_span_ordering_proves_overlap():
    """Tracer evidence of the pipeline shape: within a tick the spans
    close in dispatch → admit → decode(fence) order, the overlapped admit
    is flagged, and the dispatch span is CHEAP relative to the fenced
    decode span (dispatch returns before the device finishes)."""
    eng = _mk_engine(pipelined=True)
    eng.tracer = Tracer()
    _submit_all(eng, 6, seed=4)
    eng.run_until_done(max_ticks=200)
    names = [e["name"] for e in eng.tracer.events]
    assert "serve.decode.dispatch" in names
    # per-tick ordering: every dispatch is followed by an admit and then a
    # fenced decode before the next dispatch
    seq = [n for n in names
           if n in ("serve.decode.dispatch", "serve.admit", "serve.decode")]
    for i, n in enumerate(seq):
        if n == "serve.decode.dispatch":
            assert seq[i + 1] == "serve.admit" and seq[i + 2] == "serve.decode"
    overlapped = [e for e in eng.tracer.events
                  if e["name"] == "serve.admit" and e["args"].get("overlapped")]
    assert overlapped, "no admit ran under an in-flight decode"
    # start-time ordering inside one tick: admit starts after the dispatch
    # span opened, decode fences after the admit finished
    ev = {e["name"]: e for e in eng.tracer.events
          if e["name"].startswith("serve.")}  # last tick's spans win
    d, a, f = (ev["serve.decode.dispatch"], ev["serve.admit"], ev["serve.decode"])
    assert d["ts"] <= a["ts"] <= f["ts"]


def test_pipelined_durable_engine_groups_drains_and_restarts(tmp_path):
    """The full PR-10 stack: pipelined ticks + grouped async commits on
    both index journals.  ``stats()['durability']`` surfaces the group
    depth (``rounds_per_commit``) and the pending-group age;
    ``run_until_done`` drains so NOTHING stays volatile at exit; a second
    engine on the same directory warm-restarts with the same knobs."""
    d = str(tmp_path / "idx")
    eng = _mk_engine(pipelined=True, index_shards=2, index_durable_dir=d,
                     group_commit_every=4, group_commit_max_wait_s=1e9)
    _submit_all(eng, 12, seed=5, max_new=3)
    done = eng.run_until_done(max_ticks=300)
    assert sorted(r.rid for r in done) == list(range(12))
    dur = eng.stats()["durability"]
    assert not dur["degraded"]
    for name in ("prefix", "sessions"):
        assert dur[name]["group_commit_every"] == 4
        assert dur[name]["pending_rounds"] == 0, "exit drain left a group pending"
    # the session journal carries the churn: groups actually batched
    assert dur["sessions"]["rounds_per_commit"]["max"] > 1
    # warm restart with the same grouping knobs — the recovered journals
    # resume grouped commits and the engine serves on top of them
    eng2 = _mk_engine(pipelined=True, index_shards=2, index_durable_dir=d,
                      group_commit_every=4, group_commit_max_wait_s=1e9)
    assert eng2.sessions.tree.group_commit_every == 4
    _submit_all(eng2, 4, seed=6, max_new=2)
    done2 = eng2.run_until_done(max_ticks=100)
    assert len(done2) == 4
    assert eng2.stats()["durability"]["sessions"]["pending_rounds"] == 0
