"""The plain reference against answers worked out by hand (paper, §3)."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from reference import EMPTY, NOTFOUND, ControlSet, ReferenceSet  # noqa: E402
from traffic import OP_DELETE, OP_FIND, OP_INSERT, OP_NOP, OP_RANGE  # noqa: E402


def _round(s, lanes, cap=4):
    ops, keys, vals = (np.asarray(x) for x in zip(*lanes))
    return s.apply_round(ops.astype(np.int32), keys, vals, cap)


def test_insert_on_a_present_key_keeps_and_returns_the_old_value():
    s = ReferenceSet()
    res, fnd, _ = _round(s, [(OP_INSERT, 5, 50), (OP_INSERT, 5, 51), (OP_FIND, 5, 0)])
    assert res.tolist() == [NOTFOUND, 50, 50]
    assert fnd.tolist() == [False, True, True]
    assert s.items() == {5: 50}


def test_delete_then_insert_in_one_round_follows_arrival_order():
    s = ReferenceSet()
    _round(s, [(OP_INSERT, 7, 70)])
    res, fnd, _ = _round(s, [(OP_DELETE, 7, 0), (OP_INSERT, 7, 71), (OP_DELETE, 7, 0),
                             (OP_DELETE, 7, 0), (OP_NOP, 7, 0)])
    assert res.tolist() == [70, NOTFOUND, 71, NOTFOUND, NOTFOUND]
    assert fnd.tolist() == [True, False, True, False, False]
    assert s.items() == {}


def test_scans_at_the_edges_see_the_round_start_set():
    s = ReferenceSet()
    _round(s, [(OP_INSERT, k, 10 * k) for k in (1, 3, 5, 7, 9, 11)])
    lanes = [
        (OP_RANGE, 1, 1),     # [1, 2): the lowest key alone
        (OP_RANGE, 0, 1),     # [0, 1): empty, just below the set
        (OP_RANGE, 11, 100),  # [11, 111): the highest key alone
        (OP_RANGE, 12, 5),    # above the set
        (OP_RANGE, 3, 0),     # a span of 0 is empty
        (OP_RANGE, 0, 100),   # six matches, clipped to the cap of 4
        (OP_DELETE, 1, 0),    # writes of the same round come after the scans
        (OP_INSERT, 2, 20),
    ]
    res, fnd, scan = _round(s, lanes)
    idx, count, keys, vals = scan
    assert idx.tolist() == [0, 1, 2, 3, 4, 5]
    assert count.tolist() == [1, 0, 1, 0, 0, 4]
    assert keys[0].tolist() == [1, EMPTY, EMPTY, EMPTY]
    assert keys[2].tolist() == [11, EMPTY, EMPTY, EMPTY]
    assert keys[5].tolist() == [1, 3, 5, 7] and vals[5].tolist() == [10, 30, 50, 70]
    assert res.tolist()[:6] == [1, 0, 1, 0, 0, 4] and res[6] == 10 and res[7] == NOTFOUND
    assert fnd.tolist() == [True, False, True, False, False, True, True, False]
    # the next round's scan sees both writes
    _, _, scan = _round(s, [(OP_RANGE, 0, 4)])
    assert scan[2][0].tolist()[:2] == [2, 3] and scan[1].tolist() == [2]


def test_control_loses_the_order_within_a_round():
    lanes = [(OP_INSERT, 4, 40), (OP_FIND, 4, 0), (OP_RANGE, 0, 10)]
    good = _round(ReferenceSet(), lanes)
    bad = _round(ControlSet(), lanes)
    assert good[0].tolist()[:2] == [NOTFOUND, 40] and good[2][1].tolist() == [0]
    assert bad[0].tolist()[:2] == [NOTFOUND, NOTFOUND] and bad[2][1].tolist() == [1]
