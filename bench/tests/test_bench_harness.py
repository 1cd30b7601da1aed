"""The harness on the CPU: cells found by file name, a new cell from new
files alone, a tiny cell end to end through the harness's functions, and
the check refusing a broken timed path and the control."""
import json
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

import control  # noqa: E402
import harness  # noqa: E402
from traffic import OP_DELETE, OP_INSERT, OP_RANGE, Traffic  # noqa: E402

BIG_SEED = 2**31 + 977


def _tiny_root(tmp, extra_traffic=None):
    """A copy of the benchmark with tiny cells added by new files and new
    BENCHMARK.json entries only."""
    shutil.copytree(BENCH, os.path.join(tmp, "bench"), ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for base in ("set2m_volatile", "set2m_durable"):
        with open(os.path.join(BENCH, "configs", f"{base}.json")) as f:
            cfg = json.load(f)
        cfg.update(name=f"tiny_{base}", key_range=4000)
        cfg["tree"]["capacity"] = 4096
        with open(os.path.join(tmp, "bench", "configs", f"tiny_{base}.json"), "w") as f:
            json.dump(cfg, f)
        bench["configs"].append({"name": f"tiny_{base}", "source": "test",
                                 "file": f"bench/configs/tiny_{base}.json",
                                 "reduced": [], "why": "test"})
    with open(os.path.join(BENCH, "traffic", "zipf_upd100.json")) as f:
        mix = json.load(f)
    mix["clients"] = 64
    with open(os.path.join(tmp, "bench", "traffic", "tiny_zipf.json"), "w") as f:
        json.dump(mix, f)
    for base in ("set2m_volatile", "set2m_durable"):
        name = f"tiny_{base}.tiny_zipf"
        bench["workloads"].append({"name": name, "config": f"tiny_{base}",
                                   "traffic": "tiny_zipf", "chips": 1, "why": "test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if f"{base}.zipf_upd100" in m.get("workloads", ()):
                m["workloads"].append(name)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


@pytest.fixture(scope="module", autouse=True)
def small_setup():
    """Prefill and warm-up at the tiny cells' size."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "PREFILL_WIDTH", 256)
        mp.setattr(harness, "WARMUP_ROUNDS", 6)
        yield


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return _tiny_root(str(tmp_path_factory.mktemp("bench")))


def _run(root, name, system=None, rounds=24, trace=False):
    cell = harness.Cell(root, name)
    return harness.run_cell(cell, BIG_SEED, 30.0, trace, t_start=time.perf_counter(),
                            system=system, rounds=rounds)


def test_a_cell_finds_its_configuration_mix_and_metrics_by_name():
    cell = harness.Cell(ROOT, "set2m_volatile.ycsb_e")
    assert cell.config["holder"] == "ABTree" and cell.chips == 1
    assert cell.traffic_name == "ycsb_e"
    assert {m["name"] for m in cell.end_to_end} == {"ops_per_s", "op_p95_ms", "setup_s"}
    assert "scan.ms" in cell.readers and "elim.eliminated_share" not in cell.readers
    assert all(callable(r.read) for r in cell.readers.values())
    with pytest.raises(KeyError):
        harness.Cell(ROOT, "no_such.cell")


def test_new_files_and_entries_make_a_new_cell_without_an_edit(tmp_path):
    root = _tiny_root(str(tmp_path))
    before = {f: open(os.path.join(root, "bench", f)).read()
              for f in ("harness.py", "traffic.py", "reference.py")}
    with open(os.path.join(root, "bench", "traffic", "find_mostly.json"), "w") as f:
        json.dump({"clients": 32, "ops": {"find": 0.9, "insert": 0.05, "delete": 0.05},
                   "keys": {"dist": "uniform"}}, f)
    with open(os.path.join(root, "bench", "traffic", "coded.json"), "w") as f:
        json.dump({"clients": 16}, f)
    with open(os.path.join(root, "bench", "traffic", "coded.py"), "w") as f:
        f.write("import numpy as np\n"
                "class Traffic:\n"
                "    def __init__(self, params, config, seed):\n"
                "        self.clients = params['clients']\n"
                "    def round(self, i):\n"
                "        k = np.arange(self.clients, dtype=np.int64) + i\n"
                "        return np.full(self.clients, 1, np.int32), k, k\n")
    with open(os.path.join(root, "bench", "metrics", "lanes.find_share.py"), "w") as f:
        f.write("def read(run):\n    return 100.0 * run.lanes.get('find', 0) / run.ops\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"] += [
        {"name": "tiny_set2m_volatile.find_mostly", "config": "tiny_set2m_volatile",
         "traffic": "find_mostly", "chips": 1, "why": "test"},
        {"name": "tiny_set2m_volatile.coded", "config": "tiny_set2m_volatile",
         "traffic": "coded", "chips": 1, "why": "test"},
    ]
    bench["per_layer"].append({"name": "lanes.find_share", "unit": "%", "better": "higher",
                               "source": "program_counter", "layer": "round engine",
                               "moves": "ops_per_s",
                               "workloads": ["tiny_set2m_volatile.find_mostly"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = harness.Cell(root, "tiny_set2m_volatile.find_mostly")
    ops, keys, vals = cell.traffic(5).round(0)
    assert ops.size == 32 and int(np.sum(ops == 1)) == 29
    view = harness.RunView(rounds=1, ops=32, lanes={"find": 29}, spans=[], span_rounds=1,
                           before={}, after={}, trace=None, trace_rounds=0)
    assert cell.readers["lanes.find_share"].read(view) == pytest.approx(100 * 29 / 32)
    coded = harness.Cell(root, "tiny_set2m_volatile.coded").traffic(5)
    assert coded.round(3)[1].tolist()[:2] == [3, 4]
    for f, text in before.items():
        assert open(os.path.join(root, "bench", f)).read() == text


def test_traffic_rounds_come_from_the_seed_with_the_mix_exact_shares():
    cfg = {"key_range": 2000, "prefill_fraction": 0.5}
    params = {"clients": 200, "ops": {"scan": 0.95, "insert": 0.05},
              "keys": {"dist": "zipf", "s": 0.99}, "insert_keys": "fresh",
              "scan": {"min_records": 1, "max_records": 100, "cap": 128}}
    a, b = Traffic(params, cfg, BIG_SEED), Traffic(params, cfg, BIG_SEED)
    for x, y in zip(a.round(3), b.round(3)):
        assert np.array_equal(x, y)
    assert not np.array_equal(a.round(3)[1], Traffic(params, cfg, BIG_SEED + 1).round(3)[1])
    ops, keys, vals = a.round(0)
    assert int(np.sum(ops == OP_RANGE)) == 190 and int(np.sum(ops == OP_INSERT)) == 10
    assert vals[ops == OP_RANGE].min() >= 2 and vals[ops == OP_RANGE].max() <= 200
    present = set(a.prefill()[0].tolist())
    fresh = [k for i in range(5) for k in a.round(i)[1][a.round(i)[0] == OP_INSERT].tolist()]
    assert len(set(fresh)) == len(fresh) and not present & set(fresh)
    assert len(present) == 1000


def test_a_tiny_cell_runs_correct_through_the_harness(tiny):
    out = _run(tiny, "tiny_set2m_volatile.tiny_zipf")
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"ops_per_s", "op_p95_ms", "setup_s"}
    assert out["attempted"] == 24 * 64 and out["info"]["window_rounds"] == 24
    assert list(out["check"]) == ["lane_mismatches", "scan_row_mismatches", "content_mismatches"]


def test_a_tiny_traced_run_reports_its_layers(tiny):
    out = _run(tiny, "tiny_set2m_volatile.tiny_zipf", trace=True)
    assert out["correct"], out["check"]
    for name in ("rounds.structural_ms", "rounds.search_combine_ms", "elim.eliminated_share",
                 "device.idle_share"):
        assert out["metrics"][name]["value"] >= 0, name
    assert "kernel.descend_ms" not in out["metrics"]  # interpret mode: no kernel to find
    assert out["info"]["trace_rounds"] == out["info"]["span_rounds"] == 12
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]


def test_a_tiny_durable_cell_recovers_every_acknowledged_round(tiny):
    out = _run(tiny, "tiny_set2m_durable.tiny_zipf")
    assert out["correct"], out["check"]
    assert out["check"]["recovered_mismatches"]["value"] == 0
    assert out["metrics"]["recover_s"]["value"] > 0
    journal = os.path.join(tiny, ".bench_journal", "tiny_set2m_durable.tiny_zipf")
    assert not os.path.exists(journal) and not os.path.exists(journal + ".image")


def test_a_configuration_field_the_harness_does_not_read_is_refused(tiny, tmp_path):
    root = _tiny_root(str(tmp_path))
    path = os.path.join(root, "bench", "configs", "tiny_set2m_durable.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["narrow"] = True
    with open(path, "w") as f:
        json.dump(cfg, f)
    with pytest.raises(ValueError, match="narrow"):
        harness.Cell(root, "tiny_set2m_durable.tiny_zipf")
    del cfg["narrow"]
    cfg["args"]["narrow"] = True  # DurableABTree takes no such argument
    with open(path, "w") as f:
        json.dump(cfg, f)
    with pytest.raises(TypeError, match="narrow"):
        _run(root, "tiny_set2m_durable.tiny_zipf", rounds=2)


def _unchanged(orig):
    def apply_round(self, ops, keys, vals=None, **kw):
        st = self.stacked
        out = orig(self, ops, keys, vals, **kw)
        self.stacked = st
        return out
    return apply_round


def _half(orig):
    def apply_round(self, ops, keys, vals=None, **kw):
        n = len(ops) // 2
        out = orig(self, ops[:n], keys[:n], vals[:n], **kw)
        res = np.full(len(ops), np.iinfo(np.int64).min, np.int64)
        fnd = np.zeros(len(ops), bool)
        res[:n], fnd[:n] = np.asarray(out.results), np.asarray(out.found)
        return out._replace(results=res, found=fnd)
    return apply_round


def _altered(orig):
    def apply_round(self, ops, keys, vals=None, **kw):
        out = orig(self, ops, keys, vals, **kw)
        res = np.asarray(out.results).copy()
        res[len(res) // 3] += 1
        return out._replace(results=res)
    return apply_round


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered])
def test_a_broken_timed_path_is_not_correct(tiny, monkeypatch, fault):
    from repro.core import ABTree

    monkeypatch.setattr(ABTree, "apply_round", fault(ABTree.apply_round))
    out = _run(tiny, "tiny_set2m_volatile.tiny_zipf", rounds=8)
    assert not out["correct"], out["check"]


def test_a_durable_store_that_skips_its_commits_is_not_correct(tiny, monkeypatch):
    from repro.core import DurableABTree

    monkeypatch.setattr(DurableABTree, "apply_round",
                        lambda self, ops, keys, vals=None: self.tree.apply_round(ops, keys, vals))
    out = _run(tiny, "tiny_set2m_durable.tiny_zipf", rounds=8)
    assert not out["correct"]
    assert out["check"]["recovered_mismatches"]["value"] > 0


def _slow_commits(orig):
    def _commit_finish(self, cap):
        time.sleep(0.1)  # a disk slower than the copy of the journal
        return orig(self, cap)
    return _commit_finish


@pytest.mark.parametrize("knobs", [
    {"group_commit_every": 4, "group_commit_max_wait_s": 60.0},
    {"commit_async": True},
], ids=["group_commit", "commit_async"])
def test_a_durable_store_that_answers_before_its_commit_is_not_correct(tiny, monkeypatch, knobs):
    from repro.core import DurableABTree

    init = DurableABTree.__init__
    monkeypatch.setattr(DurableABTree, "__init__",
                        lambda self, *a, **kw: init(self, *a, **{**kw, **knobs}))
    monkeypatch.setattr(DurableABTree, "_commit_finish",
                        _slow_commits(DurableABTree._commit_finish))
    out = _run(tiny, "tiny_set2m_durable.tiny_zipf", rounds=8)
    assert not out["correct"]
    assert out["check"]["recovered_mismatches"]["value"] > 0
    assert out["check"]["lane_mismatches"]["value"] == 0


@pytest.mark.parametrize("base", ["set2m_volatile", "set2m_durable"])
def test_the_control_is_not_correct(tiny, base):
    cell = harness.Cell(tiny, f"tiny_{base}.tiny_zipf")
    out = harness.run_cell(cell, BIG_SEED, 0.0, False, t_start=time.perf_counter(),
                           system=control.ControlSystem(base == "set2m_durable"), rounds=24)
    assert not out["correct"]
    assert out["check"]["lane_mismatches"]["value"] > 0
    if base == "set2m_durable":
        assert out["check"]["recovered_mismatches"]["value"] > 0


def test_the_control_fails_a_scan_mix_through_its_scans():
    cfg = {"key_range": 4000, "prefill_fraction": 0.5, "holder": "ABTree"}
    params = {"clients": 256, "ops": {"scan": 0.95, "insert": 0.05},
              "keys": {"dist": "zipf", "s": 0.99}, "insert_keys": "fresh",
              "scan": {"min_records": 1, "max_records": 100, "cap": 128}}
    tr = Traffic(params, cfg, BIG_SEED)
    ctl, log = control.ControlSystem(False), []
    for ops, keys, vals in harness.prefill_rounds(tr, BIG_SEED, 256):
        log.append((ops, keys, vals, ctl.apply(ops, keys, vals, 128)))
    for i in range(4):
        ops, keys, vals = tr.round(i)
        log.append((ops, keys, vals, ctl.apply(ops, keys, vals, 128)))
    check = harness.check_rounds(log, ctl.items(), None, 128)
    assert check["scan_row_mismatches"]["value"] > 0
    assert int(np.sum(tr.round(0)[0] == OP_DELETE)) == 0
