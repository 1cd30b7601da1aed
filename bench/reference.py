"""The plain reference: a sorted-dict set with the paper's §3 semantics,
written apart from the program (it imports nothing of ``src/repro``).

One round is a batch of concurrent lanes, answered as in one sequential
order: every scan lane first, against the set as of round start; then the
point lanes in arrival order.

  find k        the value of k, or NOTFOUND; found ⇔ present
  insert k v    absent: store v, answer NOTFOUND, found False;
                present: keep the old value and answer it, found True
  delete k      present: remove it and answer its value, found True;
                absent: NOTFOUND, found False
  scan lo span  the ≤ cap smallest keys in [lo, lo + span), ascending,
                with their values; the answer is the row count and
                found ⇔ the row count is not 0
  nop           NOTFOUND, found False

``ControlSet`` is the control: the same set with one guarantee broken.
Each point lane is answered against the set as of round start, as if no
other lane of its round came before it, and each scan against the set as
of round end, after the round's writes.  The contents stay right; only
the order within a round is lost.
"""
from __future__ import annotations

import numpy as np

from traffic import OP_DELETE, OP_FIND, OP_INSERT, OP_NOP, OP_RANGE

NOTFOUND = -(2**63)  # the program's ⊥ answer
EMPTY = 2**63 - 1  # the program's padding of an unused scan row slot


class ReferenceSet:
    """A dict for point lanes, kept beside a sorted key/value array that
    scans read; the array catches up with the dict only when a scan needs
    it, by deleting and inserting just the keys that changed."""

    def __init__(self):
        self.d: dict = {}
        self._keys = np.empty(0, np.int64)
        self._vals = np.empty(0, np.int64)
        self._added: dict = {}  # in d, not in the arrays
        self._removed: set = set()  # in the arrays, no longer valid

    # -- point lanes -------------------------------------------------------------

    def point(self, op: int, k: int, v: int):
        d = self.d
        if op == OP_FIND:
            r = d.get(k)
            return (NOTFOUND, False) if r is None else (r, True)
        if op == OP_INSERT:
            r = d.get(k)
            if r is not None:
                return r, True
            d[k] = v
            self._added[k] = v
            return NOTFOUND, False
        if op == OP_DELETE:
            r = d.pop(k, None)
            if r is None:
                return NOTFOUND, False
            if self._added.pop(k, None) is None:
                self._removed.add(k)
            return r, True
        if op == OP_NOP:
            return NOTFOUND, False
        raise ValueError(f"unknown op {op}")

    # -- scans -------------------------------------------------------------------

    def _sync(self):
        if self._removed:
            gone = np.fromiter(self._removed, np.int64, len(self._removed))
            pos = np.searchsorted(self._keys, gone)
            self._keys = np.delete(self._keys, pos)
            self._vals = np.delete(self._vals, pos)
            self._removed = set()
        if self._added:
            k = np.fromiter(self._added.keys(), np.int64, len(self._added))
            v = np.fromiter(self._added.values(), np.int64, len(self._added))
            order = np.argsort(k)
            k, v = k[order], v[order]
            pos = np.searchsorted(self._keys, k)
            self._keys = np.insert(self._keys, pos, k)
            self._vals = np.insert(self._vals, pos, v)
            self._added = {}

    def scan_rows(self, lo: np.ndarray, hi: np.ndarray, cap: int):
        """Rows of scans ``[lo, hi)`` against the set as it stands:
        ``(count (n,), keys (n, cap), vals (n, cap))``, keys padded with
        EMPTY and values with 0 past each row's count."""
        self._sync()
        a = np.searchsorted(self._keys, lo, side="left")
        e = np.minimum(np.searchsorted(self._keys, hi, side="left"), a + cap)
        count = np.maximum(e - a, 0)
        idx = a[:, None] + np.arange(cap)[None, :]
        valid = idx < e[:, None]
        idx = np.minimum(idx, max(self._keys.size - 1, 0))
        if self._keys.size == 0:
            return count, np.full(idx.shape, EMPTY, np.int64), np.zeros(idx.shape, np.int64)
        keys = np.where(valid, self._keys[idx], EMPTY)
        vals = np.where(valid, self._vals[idx], 0)
        return count, keys, vals

    # -- rounds ------------------------------------------------------------------

    def apply_round(self, ops, keys, vals, cap: int = 128):
        """One round: ``(results, found, scan)`` where ``scan`` is None
        without scan lanes, else ``(lanes, count, keys, vals)`` for the
        scan lanes in lane order."""
        ops = np.asarray(ops)
        keys = np.asarray(keys, np.int64)
        vals = np.asarray(vals, np.int64)
        results = np.full(ops.size, NOTFOUND, np.int64)
        found = np.zeros(ops.size, bool)
        scan = None
        is_scan = ops == OP_RANGE
        if is_scan.any():
            scan = self._scans(ops, keys, vals, is_scan, cap)
        self._points(ops, keys, vals, ~is_scan, results, found)
        if scan is not None:
            results[scan[0]] = scan[1]
            found[scan[0]] = scan[1] > 0
        return results, found, scan

    def _scans(self, ops, keys, vals, is_scan, cap):
        lanes = np.nonzero(is_scan)[0]
        lo = keys[lanes]
        span = vals[lanes]
        if np.any(span < 0):
            raise ValueError("scan lane with a negative span")
        hi = np.where(lo + span < lo, EMPTY, lo + span)
        return (lanes, *self.scan_rows(lo, hi, cap))

    def _points(self, ops, keys, vals, mask, results, found):
        point = self.point
        for i, op, k, v in zip(
            np.nonzero(mask)[0].tolist(), ops[mask].tolist(),
            keys[mask].tolist(), vals[mask].tolist(),
        ):
            results[i], found[i] = point(op, k, v)

    def items(self) -> dict:
        return self.d


class ControlSet(ReferenceSet):
    """The control (see the module docstring): point lanes answered
    against the round-start set, scans against the round-end set."""

    def apply_round(self, ops, keys, vals, cap: int = 128):
        ops = np.asarray(ops)
        keys = np.asarray(keys, np.int64)
        vals = np.asarray(vals, np.int64)
        results = np.full(ops.size, NOTFOUND, np.int64)
        found = np.zeros(ops.size, bool)
        is_scan = ops == OP_RANGE
        lanes = np.nonzero(~is_scan)[0]
        before = [self.d.get(k) for k in keys[lanes].tolist()]
        for i, op, r in zip(lanes.tolist(), ops[lanes].tolist(), before):
            if op != OP_NOP and r is not None:
                results[i], found[i] = r, True
        self._points(ops, keys, vals, ~is_scan, np.empty_like(results), np.empty_like(found))
        scan = None
        if is_scan.any():
            scan = self._scans(ops, keys, vals, is_scan, cap)
            results[scan[0]] = scan[1]
            found[scan[0]] = scan[1] > 0
        return results, found, scan
