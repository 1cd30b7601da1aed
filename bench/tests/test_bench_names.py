"""BENCHMARK.json keeps to the benchmark's contract: allowed characters in
every name and unit, and every file and metric a cell needs exists."""
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_and_units_use_only_the_allowed_characters(bench):
    names = [c["name"] for c in bench["configs"]]
    for w in bench["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
    for c in bench["configs"]:
        names += c["reduced"]
    metrics = bench["end_to_end"] + bench["per_layer"]
    names += [m["name"] for m in metrics]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({x["name"] for x in bench[group]}) == len(bench[group])
    texts = [c["source"] for c in bench["configs"]] + [x["why"] for x in bench["configs"] + bench["workloads"]]
    texts += [m["layer"] for m in bench["per_layer"]] + bench["command"]
    assert all(0 < len(t) <= 200 and "\n" not in t and "\t" not in t for t in texts)


def test_every_cell_finds_its_files_and_reports_what_its_metrics_move(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    assert bench["paths"] == ["bench"]
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    assert len({c["file"] for c in configs.values()}) == len(configs)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and 0 < e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())

    def reports(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    for w in bench["workloads"]:
        assert w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(ROOT, configs[w["config"]]["file"]))
        assert os.path.exists(os.path.join(BENCH, "traffic", f"{w['traffic']}.json"))
        own = [m for m in e2e.values() if reports(m, w["name"])]
        assert len(own) >= 2 and any(m["name"] == "setup_s" for m in own)
        assert any(reports(m, w["name"]) for m in bench["per_layer"])
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(BENCH, "metrics", f"{m['name']}.py"))
        for cell in m.get("workloads", [w["name"] for w in bench["workloads"]]):
            assert reports(e2e[m["moves"]], cell), (m["name"], cell)


def test_configuration_files_list_what_was_cut(bench):
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["name"] == c["name"] and cfg["assumed"]
