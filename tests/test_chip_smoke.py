"""``chip_smoke.py`` rehearsed on the CPU: every phase at tiny size with its
oracle checks, and the script's refusal to report success off a TPU."""
import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _assert_clean(rep):
    assert rep["ok"], rep
    assert rep["oracle_mismatches"] == 0
    assert rep["platform"] == "cpu"
    assert rep["kernels"]["compiled"] is False  # interpret mode, CPU only


def test_store_phase_tiny(smoke):
    rep = smoke.phase_store(
        n_keys=1500, load_width=256, mixed_rounds=2, mixed_width=256,
        capacity=256, seed=3,
    )
    _assert_clean(rep)
    assert rep["contents_match"]
    assert rep["keys_loaded"] == 1500
    # the narrow store runs every kernel site's Pallas form at this size
    assert set(rep["kernels"]["descend_probe"].values()) == {"pallas"}
    assert rep["kernels"]["frontier_compact"] == "pallas"
    assert rep["kernels"]["range_scan"].startswith("pallas/")
    assert rep["engine"]["split_waves"] > 0


def test_durable_phase_tiny(smoke, tmp_path):
    rep = smoke.phase_durable(
        directory=str(tmp_path / "durable"), n_keys=1000, width=256,
        capacity=256, seed=3, mixed_rounds=2,
    )
    _assert_clean(rep)
    assert rep["recovered_matches_committed_prefix"]
    assert rep["recovered_find_round_ok"]
    assert rep["rounds_committed"] % rep["group_commit_every"] == 0
    assert rep["rounds"] == rep["rounds_committed"] + 2
    # one commit per group, plus the journal's initial empty commit
    assert rep["commits"] == rep["rounds_committed"] // rep["group_commit_every"] + 1


def test_serve_phase_tiny(smoke, tmp_path):
    from repro.configs.qwen2_0_5b import CONFIG
    from repro.models import reduced

    rep = smoke.phase_serve(
        cfg=reduced(CONFIG, n_layers=1), directory=str(tmp_path / "serve"),
        n_requests=3, max_new=3, seed=3,
    )
    _assert_clean(rep)
    assert rep["tokens_out"] == 9
    assert rep["prefix_hit_blocks"] >= 1
    assert all(rep["tokens_match_reference"])
    assert rep["journals_match_live_indexes"]


def test_main_refuses_off_tpu(smoke, capsys):
    """On the CPU the script runs nothing and never reports success."""
    assert smoke.main(["--keys", "64"]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out


def test_script_alone_refuses(tmp_path):
    """Copied out of the repository, the script fails before printing."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
