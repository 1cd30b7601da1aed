"""Round-engine tests: fused mixed-op rounds (OP_RANGE lanes alongside
finds/inserts/deletes in one ``apply_round`` call) against the oracle's
mixed-round reference semantics, lane classification (``RoundPlan``), the
fused scan+delete round, and the scan cursor API (``scan_stream``)."""
import numpy as np
import pytest

try:  # property tests need hypothesis; deterministic tests run without it
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.core import (
    ABTree,
    DictOracle,
    EMPTY,
    OP_DELETE,
    OP_FIND,
    OP_INSERT,
    OP_NOP,
    OP_RANGE,
    TreeConfig,
    build_plan,
    check_invariants,
)
from repro.core.oracle import tree_contents

SMALL = TreeConfig(capacity=512, b=8, a=2, max_height=12)

_NOTFOUND_SCANLESS = None  # marker only; point lanes have scans[i] is None


def _check_mixed_round(tree, oracle, ops, keys, vals, cap=64):
    """One fused apply_round vs the oracle's mixed-round semantics."""
    out = tree.apply_round(ops, keys, vals, scan_cap=cap)
    exp_res, exp_found, exp_scans = oracle.apply_mixed_round(ops, keys, vals, cap=cap)
    got_res = np.asarray(out.results).tolist()
    got_found = np.asarray(out.found).tolist()
    for i, op in enumerate(ops):
        assert got_found[i] == exp_found[i], (i, op, got_found[i], exp_found[i])
        if op == OP_RANGE or exp_found[i]:
            assert got_res[i] == exp_res[i], (i, op, got_res[i], exp_res[i])
        if exp_scans[i] is not None:
            n = int(np.asarray(out.scan.count)[i])
            row = [
                (int(k), int(v))
                for k, v in zip(
                    np.asarray(out.scan.keys)[i, :n], np.asarray(out.scan.vals)[i, :n]
                )
            ]
            assert row == exp_scans[i], (i, row[:4], exp_scans[i][:4])
            # rows beyond count stay EMPTY-padded
            assert all(
                int(k) == int(EMPTY) for k in np.asarray(out.scan.keys)[i, n:]
            )
    assert tree_contents(tree.state, tree.cfg) == oracle.items()
    return out


@pytest.mark.parametrize("mode", ["elim", "occ"])
def test_fused_mixed_round_acceptance(mode):
    """The headline capability: finds + inserts + deletes + ≥2 range lanes
    in ONE apply_round call, oracle-exact, scans linearized before the
    round's net writes."""
    t = ABTree(SMALL, mode=mode)
    o = DictOracle()
    pre = list(range(0, 100, 2))  # evens present pre-round
    t.apply_round([OP_INSERT] * len(pre), pre, [k * 10 for k in pre])
    o.apply_round([OP_INSERT] * len(pre), pre, [k * 10 for k in pre])
    ops = [OP_FIND, OP_INSERT, OP_RANGE, OP_DELETE, OP_RANGE, OP_INSERT, OP_FIND]
    keys = [4, 5, 0, 6, 3, 7, 99]
    vals = [0, 55, 10, 0, 5, 77, 0]  # lane 2 scans [0,10); lane 4 scans [3,8)
    rounds_before = t.stats()["rounds"]
    out = _check_mixed_round(t, o, ops, keys, vals, cap=16)
    assert t.stats()["rounds"] == rounds_before + 1  # ONE round
    # scans observe the pre-round state: 5 and 7 (inserted this round) are
    # invisible; 6 (deleted this round) is still visible.
    scan0 = np.asarray(out.scan.keys)[2, :5].tolist()
    assert scan0 == [0, 2, 4, 6, 8]
    assert np.asarray(out.scan.keys)[4, :2].tolist() == [4, 6]
    check_invariants(t.state, t.cfg)


@pytest.mark.parametrize("mode", ["elim", "occ"])
def test_fused_randomized_rounds_match_oracle(mode):
    """Randomized mixed rounds on overlapping keys stay oracle-exact."""
    rng = np.random.default_rng(7)
    t = ABTree(SMALL, mode=mode)
    o = DictOracle()
    for r in range(10):
        bsz = 48
        ops = rng.choice(
            [OP_NOP, OP_FIND, OP_INSERT, OP_DELETE, OP_RANGE],
            bsz,
            p=[0.05, 0.2, 0.3, 0.25, 0.2],
        ).astype(np.int32)
        keys = rng.integers(0, 300, bsz).astype(np.int64)
        vals = rng.integers(0, 1000, bsz).astype(np.int64)
        vals = np.where(ops == OP_RANGE, rng.integers(0, 80, bsz), vals)
        _check_mixed_round(t, o, ops.tolist(), keys.tolist(), vals.tolist(), cap=32)
        if r % 3 == 0:
            check_invariants(t.state, t.cfg)


@pytest.mark.parametrize("mode", ["elim", "occ"])
def test_pure_range_batch_via_apply_round(mode):
    """An all-OP_RANGE batch through apply_round matches scan_round."""
    t = ABTree(SMALL, mode=mode)
    keys = list(range(64))
    t.apply_round([OP_INSERT] * 64, keys, [k * 2 for k in keys])
    lo = np.array([0, 10, 60], np.int64)
    span = np.array([5, 30, 100], np.int64)
    want = t.scan_round(lo, lo + span, cap=32)
    out = t.apply_round([OP_RANGE] * 3, lo, span, scan_cap=32)
    np.testing.assert_array_equal(np.asarray(out.scan.keys), np.asarray(want.keys))
    np.testing.assert_array_equal(np.asarray(out.scan.count), np.asarray(want.count))
    assert np.asarray(out.results).tolist() == np.asarray(want.count).tolist()


def test_zero_span_range_lane_is_legal_empty_scan():
    t = ABTree(SMALL)
    t.apply_round([OP_INSERT], [5], [50])
    out = t.apply_round([OP_RANGE], [5], [0])  # [5, 5): empty, not malformed
    assert int(np.asarray(out.scan.count)[0]) == 0
    assert not bool(np.asarray(out.found)[0])


def test_range_lane_hi_saturates_at_top_of_key_space():
    """lo + span past the int64 top must scan 'everything ≥ lo' (like the
    unbounded oracle), not wrap to a negative hi that scans nothing."""
    t = ABTree(SMALL)
    o = DictOracle()
    big = int(EMPTY) - 5  # valid key just below the EMPTY sentinel
    t.apply_round([OP_INSERT] * 2, [big, 7], [1, 2])
    o.apply_round([OP_INSERT] * 2, [big, 7], [1, 2])
    _check_mixed_round(t, o, [OP_RANGE], [big - 10], [100], cap=8)


def test_malformed_lanes_raise():
    t = ABTree(SMALL)
    with pytest.raises(ValueError, match="malformed"):
        t.apply_round([OP_RANGE, OP_INSERT], [10, 1], [-2, 5])
    with pytest.raises(ValueError, match="unknown op"):
        t.apply_round([7], [0], [0])
    with pytest.raises(ValueError, match="equal-length"):
        t.apply_round([OP_INSERT], [1, 2], [0, 0])


def test_round_plan_classification():
    plan = build_plan(
        [OP_NOP, OP_FIND, OP_RANGE, OP_DELETE], [0, 1, 10, 3], [0, 0, 7, 0], holder=None
    )
    assert plan.has_point and plan.has_range and plan.n_range == 1
    assert np.asarray(plan.is_range).tolist() == [False, False, True, False]
    # OP_RANGE masked out of the combine's batch
    assert np.asarray(plan.point_ops).tolist() == [OP_NOP, OP_FIND, OP_NOP, OP_DELETE]
    assert int(np.asarray(plan.lo)[2]) == 10 and int(np.asarray(plan.hi)[2]) == 17
    # non-range lanes scan the empty interval [EMPTY, EMPTY)
    assert int(np.asarray(plan.lo)[0]) == int(EMPTY)
    point_only = build_plan([OP_INSERT], [1], [1], holder=None)
    assert point_only.has_point and not point_only.has_range


@pytest.mark.parametrize("mode", ["elim", "occ"])
def test_scan_delete_round_is_one_round(mode):
    t = ABTree(SMALL, mode=mode)
    keys = list(range(100))
    t.apply_round([OP_INSERT] * 100, keys, [k * 3 for k in keys])
    r0 = t.stats()["rounds"]
    out = t.scan_delete_round([20], [40], cap=64)
    assert t.stats()["rounds"] == r0 + 1
    assert int(np.asarray(out.count)[0]) == 20
    assert [int(k) for k in np.asarray(out.keys)[0, :20]] == list(range(20, 40))
    assert [int(v) for v in np.asarray(out.vals)[0, :20]] == [
        k * 3 for k in range(20, 40)
    ]
    check_invariants(t.state, t.cfg)
    assert sorted(t.items()) == [k for k in keys if not 20 <= k < 40]


def test_session_eviction_round_count_halved():
    """evict_range now costs ONE round per chunk (was scan + delete = 2)."""
    from repro.serve.pages import SessionIndex

    si = SessionIndex(mode="elim")
    si.publish_batch(list(range(100, 140)), list(range(40)))
    r0 = si.tree.stats()["rounds"]
    freed = si.evict_range(100, 120, cap=8)  # 20 matches, cap 8 → 3 chunks
    assert sorted(freed) == list(range(20))
    # 3 truncated-chunk sweeps: each is exactly one fused round
    assert si.tree.stats()["rounds"] - r0 == 3
    assert si.lookup_batch([105, 125]) == [None, 25]


def test_scan_stream_straddles_leaf_boundaries():
    """Cursor API: a cap-bounded stream resumes from the last emitted key
    and crosses leaf boundaries without loss or duplication."""
    t = ABTree(SMALL)  # b=8 → 150 keys span many leaves
    o = DictOracle()
    rng = np.random.default_rng(11)
    keys = rng.choice(2000, size=150, replace=False).tolist()
    vals = [k * 5 for k in keys]
    t.apply_round([OP_INSERT] * 150, keys, vals)
    o.apply_round([OP_INSERT] * 150, keys, vals)
    # cap=7 < leaf fanout 8 guarantees pages end mid-leaf AND at boundaries
    got = list(t.scan_stream(0, 2000, cap=7))
    assert got == o.range(0, 2000)
    # sub-range with both endpoints interior
    lo, hi = sorted(keys)[10] + 1, sorted(keys)[120]
    assert list(t.scan_stream(lo, hi, cap=7)) == o.range(lo, hi)
    # empty and reversed ranges stream nothing
    assert list(t.scan_stream(3000, 4000, cap=7)) == []
    assert list(t.scan_stream(50, 50, cap=7)) == []
    # non-positive cap is rejected eagerly (before the first next())
    with pytest.raises(ValueError, match="cap"):
        t.scan_stream(0, 100, cap=0)


def test_scan_stream_is_capacity_bounded():
    """The stream issues ceil(n/cap) scan rounds of ≤ cap entries each."""
    t = ABTree(SMALL)
    n = 60
    t.apply_round([OP_INSERT] * n, list(range(n)), list(range(n)))
    scans0 = t.stats()["scans"]
    got = list(t.scan_stream(0, n, cap=16))
    assert len(got) == n
    pages = t.stats()["scans"] - scans0
    assert pages == -(-n // 16)  # 4 pages of ≤ 16


# ---------------------------------------------------------------------------
# hypothesis property tests (skipped when hypothesis is not installed)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    lane_strategy = st.one_of(
        st.tuples(  # point lane
            st.sampled_from([OP_FIND, OP_INSERT, OP_DELETE]),
            st.integers(min_value=0, max_value=30),
            st.integers(min_value=0, max_value=10**6),
        ),
        st.tuples(  # range lane: lo in the same hot key range, short span
            st.just(OP_RANGE),
            st.integers(min_value=0, max_value=30),
            st.integers(min_value=0, max_value=12),
        ),
    )

    @settings(
        max_examples=25,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large],
    )
    @given(
        rounds=st.lists(
            st.lists(lane_strategy, min_size=1, max_size=40), min_size=1, max_size=5
        ),
        mode=st.sampled_from(["elim", "occ"]),
    )
    def test_property_mixed_rounds_oracle_equivalence(rounds, mode):
        """For any interleaving of OP_RANGE lanes with elim/occ point ops on
        overlapping keys, fused rounds are oracle-exact — in particular a
        scan never observes writes from its own round (the oracle evaluates
        scans on the pre-round snapshot)."""
        t = ABTree(SMALL, mode=mode)
        o = DictOracle()
        for r in rounds:
            ops = [x[0] for x in r]
            keys = [x[1] for x in r]
            vals = [x[2] for x in r]
            _check_mixed_round(t, o, ops, keys, vals, cap=16)
        check_invariants(t.state, t.cfg)

else:

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_mixed_rounds_oracle_equivalence():
        pass


# ---------------------------------------------------------------------------
# Unified engine: exactly one host-sequencing implementation
# ---------------------------------------------------------------------------


def test_forest_has_no_host_sequencing_copies():
    """Grep-pin for the unified engine: ``core/forest.py`` must contain NO
    copy of the round engine's host loops — they live once, in
    ``core/rounds.py``'s (S, wave_w) form, shared with ABTree (S = 1)."""
    import inspect

    import repro.core.forest as F
    import repro.core.rounds as R

    src = inspect.getsource(F)
    for token in (
        "_drain_deferred",
        "_split_cascade",
        "_occ_round",
        "_fix_underfull",
        "underfull",
        "_combine_apply",
        "_v_scan",
        "_v_split",
        "_v_underfull",
        "run_scan_phase",
        "run_point_phases",
        "subrounds",
    ):
        assert token not in src, f"forest.py re-implements/host-sequences {token!r}"
    rsrc = inspect.getsource(R)
    for token in (
        "_drain_deferred",
        "_split_cascade",
        "_occ_round",
        "_fix_underfull_all",
        "run_scan_phase",
        "execute_plan",
        "execute_scan_delete",
    ):
        assert token in rsrc, f"rounds.py lost the unified {token!r}"


def test_abtree_rounds_execute_through_s1_stacked_path(monkeypatch):
    """ABTree rounds must run through the unified engine's vmapped S = 1
    path: every phase sees a leading shard axis of size 1, and the
    RoundOutput semantics are unchanged (oracle-exact)."""
    from repro.core import rounds as R

    combine_shapes = []
    scan_shapes = []
    scan_sids = []
    orig_combine = R._v_search_combine
    orig_scan = R._phase_scan_flat

    def spy_combine(state, batch, cfg, narrow=False):
        combine_shapes.append(tuple(np.asarray(batch[0]).shape))
        return orig_combine(state, batch, cfg, narrow)

    def spy_scan(state, cfg, sid, lo, hi, fc, cap, narrow, narrow_descent=False):
        scan_shapes.append(tuple(np.asarray(lo).shape))
        scan_sids.append(np.asarray(sid))
        return orig_scan(state, cfg, sid, lo, hi, fc, cap, narrow, narrow_descent)

    monkeypatch.setattr(R, "_v_search_combine", spy_combine)
    monkeypatch.setattr(R, "_phase_scan_flat", spy_scan)

    t = ABTree(SMALL)
    o = DictOracle()
    ops = [OP_INSERT, OP_INSERT, OP_RANGE, OP_DELETE, OP_FIND]
    keys = [3, 9, 0, 3, 9]
    vals = [30, 90, 20, 0, 0]
    _check_mixed_round(t, o, ops, keys, vals, cap=16)
    assert combine_shapes and all(s[0] == 1 and len(s) == 2 for s in combine_shapes)
    # the scan phase is flat/ragged: 1-D packed sub-lane blocks whose every
    # live lane routes to the single shard (sid == 0 at S = 1)
    assert scan_shapes and all(len(s) == 1 for s in scan_shapes)
    assert all((sid == 0).all() for sid in scan_sids)
