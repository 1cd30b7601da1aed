"""Crash-under-load soak: YCSB-A traffic through a firing ``FaultPlan``.

The durability claims that matter under a *sick* disk, not just a clean
one: every recovery must land on a witnessed committed round prefix, the
serve layer must keep ticking (degraded volatile mode) instead of raising,
and recovery time must not regress.  Each soak leg drives a seeded YCSB-A
stream (50% updates, Zipf) over a 2-shard ``DurableForest`` while a
``FaultPlan`` injects one fault class — transient fsync EIO, ENOSPC on
segment writes, silent torn segments, manifest-rename failures, or a
fail-stop kill mid-protocol — then abandons the live object, recovers from
disk, and verifies the recovered contents two independent ways:

  1. **forensics witness** — the recovered sidecar's history must be
     linearizable (``check_history`` raises ``WitnessError`` otherwise)
     and the recovered contents must be one of its oracle round-prefix
     states (``collect_prefixes=True``);
  2. **driver oracle** — the recovered contents must equal a round prefix
     of the *driver's* own sequential replay of the stream it submitted
     (ground truth independent of the recorder).

Fault schedules are pure hash functions of (seed, site, commit, shard,
attempt) — no wall clock, no thread order — so the committed prefix each
leg recovers is deterministic and ``run.py --check`` gates it exactly
(``rounds`` = recovered prefix length, ``commits`` = successful commits).
Recovery latency is the throughput-gated metric (``ops_per_s`` =
recoveries/s, a cliff detector).

The final leg boots a ``ServeEngine`` on a journal whose manifest fsyncs
always fail: the engine must serve every session to completion with ZERO
exceptions from ``tick()`` (the section raises otherwise), flip its
``stats()["durability"]["degraded"]`` flag, and auto-reattach once the
plan is cleared (the disk "healed").
"""
from __future__ import annotations

import shutil
import tempfile
import time

import numpy as np

from benchmarks.common import emit

SHARDS = 2
SEEDS = (1, 2, 3)

# one spec per fault class; p < 1.0 so retry attempts re-draw (a commit
# eventually succeeds), torn writes "succeed" silently and surface only at
# recovery as CRC mismatches.  The kill class is a CrashPoint instead,
# cycling through the mid-protocol steps by seed.
_KILL_STEPS = ("after_segment", "mid_manifest", "before_dirsync")


def _plan_for(klass: str, seed: int, rounds: int):
    from repro.core.faults import CrashPoint, FaultPlan, FaultSpec

    plan = FaultPlan(seed=seed)
    if klass == "eio":
        plan.add(FaultSpec(site="segment_fsync", kind="eio", p=0.2))
        plan.add(FaultSpec(site="manifest_fsync", kind="eio", p=0.1))
    elif klass == "enospc":
        plan.add(FaultSpec(site="segment_write", kind="enospc", p=0.25))
    elif klass == "torn":
        # window past commit 0: the initial snapshot is the root of every
        # shard's chain, and a torn SNAPSHOT surviving into both manifest
        # generations is unrecoverable by design (RecoveryError — covered
        # by tests/test_faults.py); the soak exercises the recoverable
        # path, torn SEGMENTS, so its leg never snapshots mid-run.
        plan.add(
            FaultSpec(
                site="segment_write", kind="torn", p=0.5, torn_frac=0.4,
                commits=(1 + rounds // 2, 10**9),
            )
        )
    elif klass == "rename_fail":
        plan.add(FaultSpec(site="manifest_rename", kind="rename_fail", p=0.35))
    elif klass == "kill":
        step = _KILL_STEPS[seed % len(_KILL_STEPS)]
        plan.add_crash(CrashPoint(step=step, at_commit=1 + rounds // 2))
    else:  # pragma: no cover - registry drift guard
        raise ValueError(f"unknown fault class {klass!r}")
    return plan


def _soak_leg(klass: str, seed: int, rounds: int, batch: int, key_range: int):
    from repro.configs.abtree import TPU8
    from repro.core.durable import DurableForest, recover_forest
    from repro.core.faults import SimulatedCrash
    from repro.core.oracle import DictOracle
    from repro.data.workloads import WorkloadConfig, op_stream
    from repro.obs.witness import check_history

    cfg = WorkloadConfig(
        key_range=key_range, update_frac=0.5, dist="zipf", zipf_s=1.0,
        batch=batch, seed=seed,
    )
    stream = list(op_stream(cfg, rounds))
    # driver-side ground truth: sequential replay of the exact stream we
    # submit; prefixes[r] = contents after the first r rounds.
    oracle = DictOracle()
    prefixes = [oracle.items()]
    for ops, keys, vals in stream:
        oracle.apply_round(ops, keys, vals)
        prefixes.append(oracle.items())

    d = tempfile.mkdtemp(prefix=f"fault_soak_{klass}_s{seed}_")
    plan = _plan_for(klass, seed, rounds)
    dur = DurableForest(
        d, n_shards=SHARDS, cfg=TPU8._replace(capacity=4 * key_range),
        mode="elim", key_space=(0, key_range),
        snapshot_every=10**9 if klass == "torn" else 4, faults=plan,
    )
    killed = False
    t0 = time.perf_counter()
    for ops, keys, vals in stream:
        try:
            dur.apply_round(ops, keys, vals)
        except SimulatedCrash:
            killed = True
            break
    t_run = time.perf_counter() - t0
    status = dur.durability_status()
    n_commits = int(dur.dstats.commits)
    del dur  # the live object is "dead" — recovery must come from disk

    t1 = time.perf_counter()
    rec = recover_forest(d)
    t_recover = time.perf_counter() - t1
    got = rec.items()

    # (1) forensics witness: the recovered sidecar's history is legal AND
    # the recovered contents are one of its round-prefix oracle states.
    recs = rec.forensics_records()
    rep = check_history(recs, collect_prefixes=True)
    if recs and got not in rep.prefix_states:
        raise RuntimeError(
            f"fault_soak.{klass}.seed{seed}: recovered contents match no "
            f"witnessed sidecar prefix ({len(rep.prefix_states)} candidates)"
        )
    # (2) driver oracle: the recovered contents are a committed prefix of
    # the stream the driver actually submitted.
    matches = [r for r, st in enumerate(prefixes) if st == got]
    if not matches:
        raise RuntimeError(
            f"fault_soak.{klass}.seed{seed}: recovered contents are not a "
            f"prefix of the driver's oracle replay (killed={killed})"
        )
    recovered_rounds = matches[-1]
    if klass == "kill" and recovered_rounds >= rounds:
        raise RuntimeError(
            f"fault_soak.{klass}.seed{seed}: kill leg committed the whole "
            f"stream — the crash point never fired"
        )
    shutil.rmtree(d, ignore_errors=True)

    n_ops = batch * max(recovered_rounds, 1)
    emit(
        f"fault_soak.{klass}.seed{seed}",
        t_run / (batch * rounds) * 1e6,
        f"recovered_rounds={recovered_rounds}/{rounds};killed={killed};"
        f"faults={plan.injected};retries={status['commit_retries']};"
        f"quarantined={len(rec._quarantined)};recovery_ms={t_recover * 1e3:.1f}",
        ops_per_s=1.0 / max(t_recover, 1e-9),
        rounds=recovered_rounds,
        commits=n_commits,
        faults_injected=plan.injected,
        commit_retries=status["commit_retries"],
        quarantined=len(rec._quarantined),
        recovery_ms=t_recover * 1e3,
        replay_items=len(got),
        replay_ops=n_ops,
    )


def _serve_leg(quick: bool):
    from repro.configs import get_config
    from repro.core.faults import FaultPlan, FaultSpec
    from repro.models import reduced
    from repro.serve import Request, ServeEngine

    cfg = reduced(get_config("qwen2-0.5b"), n_layers=1)
    plan = FaultPlan(seed=7)
    plan.add(FaultSpec(site="manifest_fsync", kind="eio"))  # p=1: always sick
    ddir = tempfile.mkdtemp(prefix="fault_soak_serve_")
    eng = ServeEngine(
        cfg, max_batch=4, s_max=64, n_pages=128,
        index_shards=2, index_durable_dir=ddir, index_faults=plan,
    )
    rng = np.random.default_rng(0)
    n_sessions = 4 if quick else 8
    for rid in range(n_sessions):
        eng.submit(
            Request(rid=rid, prompt=list(rng.integers(0, cfg.vocab, 8)), max_new=2)
        )
    raised = 0
    t0 = time.perf_counter()
    ticks = 0
    while (eng.waiting or eng.running) and ticks < 500:
        try:
            eng.tick()
        except Exception:  # noqa: BLE001 - the gate IS "tick never raises"
            raised += 1
            break
        ticks += 1
    t_sick = time.perf_counter() - t0
    s = eng.stats()
    degraded = bool(s.get("durability", {}).get("degraded"))
    if raised or not degraded:
        raise RuntimeError(
            f"fault_soak.serve: sick-disk serving must degrade without "
            f"raising (raised={raised}, degraded={degraded})"
        )
    # disk "heals": the next reattach probe must close the breaker.
    plan.clear()
    for rid in range(100, 100 + n_sessions):
        eng.submit(
            Request(rid=rid, prompt=list(rng.integers(0, cfg.vocab, 8)), max_new=2)
        )
    while (eng.waiting or eng.running) and ticks < 1000:
        eng.tick()
        ticks += 1
    s2 = eng.stats()
    if s2["durability"]["degraded"]:
        raise RuntimeError("fault_soak.serve: breaker failed to reattach after heal")
    shutil.rmtree(ddir, ignore_errors=True)
    emit(
        "fault_soak.serve.degraded",
        t_sick / max(ticks, 1) * 1e6,
        f"ticks={ticks};raised={raised};degraded_then_reattached=True;"
        f"suspended={s['durability']['sessions']['commits_suspended']}",
        ops_per_s=ticks / max(t_sick, 1e-9),
        rounds=ticks,
        raised=raised,
        n_done=len(eng.done),
    )


def main(quick: bool = False):
    rounds = 10 if quick else 20
    batch, key_range = 64, 512
    for klass in ("eio", "enospc", "torn", "rename_fail", "kill"):
        for seed in SEEDS:
            _soak_leg(klass, seed, rounds, batch, key_range)
    _serve_leg(quick)


if __name__ == "__main__":
    from repro.compile_cache import use_persistent_cache

    use_persistent_cache()
    main()
