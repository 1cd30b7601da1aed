"""Sequential oracle + structural invariant checker for the batched trees.

The oracle applies a round's ops in arrival order against a plain dict —
this is a *valid linearization* of the round (all ops are concurrent), so
the batched tree's per-op results must match it exactly, in both elim and
occ modes.  (The paper's elimination argument, §4: reordering concurrent
same-key ops is legal; we always pick arrival order, so results are
deterministic and oracle-checkable.)

``check_invariants`` walks the array state on the host and asserts the
paper's Theorem 3.5 invariants in their batched form:
  1. reachable nodes form a relaxed (a,b)-tree (sizes within bounds except
     the root; uniform leaf depth — our waves maintain *strict* balance,
     which implies the relaxed invariant),
  4. a key appears at most once in a leaf,
  plus search-structure: router sortedness and key-range containment
  (invariants 2/7), parent/pidx link consistency, and size-field accuracy
  (invariant 6).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.abtree import (
    EMPTY,
    NOTFOUND,
    OP_DELETE,
    OP_FIND,
    OP_INSERT,
    OP_NOP,
    OP_RANGE,
    TreeState,
)

_EMPTY = int(EMPTY)
_NOTFOUND = int(NOTFOUND)


class DictOracle:
    """Reference dictionary with the paper's §3 semantics."""

    def __init__(self):
        self.d: Dict[int, int] = {}

    def _apply_point(self, op: int, k: int, v: int) -> Tuple[int, bool]:
        if op == OP_NOP:
            return _NOTFOUND, False
        if op == OP_FIND:
            r = self.d.get(k)
            return (_NOTFOUND if r is None else r), r is not None
        if op == OP_INSERT:
            r = self.d.get(k)
            if r is None:
                self.d[k] = v
                return _NOTFOUND, False
            return r, True  # paper: insert returns existing value
        if op == OP_DELETE:
            r = self.d.pop(k, None)
            return (_NOTFOUND if r is None else r), r is not None
        raise ValueError(f"bad op {op}")

    def apply_round(
        self, ops: Sequence[int], keys: Sequence[int], vals: Sequence[int]
    ) -> Tuple[List[int], List[bool]]:
        results, found = [], []
        for op, k, v in zip(ops, keys, vals):
            r, f = self._apply_point(int(op), int(k), int(v))
            results.append(r)
            found.append(f)
        return results, found

    def apply_mixed_round(
        self,
        ops: Sequence[int],
        keys: Sequence[int],
        vals: Sequence[int],
        cap: Optional[int] = None,
    ) -> Tuple[List[int], List[bool], List[Optional[List[Tuple[int, int]]]]]:
        """Reference semantics of one FUSED round (the round engine's
        linearization): every OP_RANGE lane (key = lo, val = span) scans the
        dictionary *as of round start* — scans linearize before the round's
        net writes — then point lanes apply in arrival order.

        Returns ``(results, found, scans)``: ``scans[i]`` is the ascending
        (k, v) list for lane i (clipped to ``cap``, matching a truncated
        device scan) or None on point lanes; a range lane's ``results``
        entry is its match count and ``found`` ⇔ non-empty.
        """
        # round-start snapshot: sorted keys + a frozen copy of the values,
        # so each range lane is two binary searches, not a dictionary walk
        snap_keys = snap_vals = None
        if any(int(op) == OP_RANGE for op in ops):
            snap_keys = np.sort(np.fromiter(self.d, np.int64, len(self.d)))
            snap_vals = dict(self.d)
        results: List[int] = []
        found: List[bool] = []
        scans: List[Optional[List[Tuple[int, int]]]] = []
        for op, k, v in zip(ops, keys, vals):
            op, k, v = int(op), int(k), int(v)
            if op == OP_RANGE:
                if v < 0:
                    raise ValueError(f"malformed OP_RANGE lane: negative span {v}")
                lo, hi = k, k + v
                a, e = np.searchsorted(
                    snap_keys, [lo, min(hi, _EMPTY)], side="left"
                )
                if cap is not None:
                    e = min(e, a + cap)
                items = [(kk, snap_vals[kk]) for kk in snap_keys[a:e].tolist()]
                scans.append(items)
                results.append(len(items))
                found.append(bool(items))
            else:
                r, f = self._apply_point(op, k, v)
                results.append(r)
                found.append(f)
                scans.append(None)
        return results, found, scans

    def items(self) -> dict:
        return dict(sorted(self.d.items()))

    def range(self, lo: int, hi: int) -> List[Tuple[int, int]]:
        """All (k, v) with lo ≤ k < hi, ascending — the linearized result a
        scan round must produce (clip to ``cap`` to compare truncated
        scans)."""
        return sorted((k, v) for k, v in self.d.items() if lo <= k < hi)


def check_invariants(state: TreeState, cfg) -> None:
    """Host walk asserting the paper's structural invariants (see module
    docstring).  Raises AssertionError with a precise message on violation."""
    keys = np.asarray(state.keys)
    children = np.asarray(state.children)
    parent = np.asarray(state.parent)
    pidx = np.asarray(state.pidx)
    is_leaf = np.asarray(state.is_leaf)
    size = np.asarray(state.size)
    level = np.asarray(state.level)
    alloc = np.asarray(state.alloc)
    root = int(state.root)
    height = int(state.height)
    a, b = cfg.a, cfg.b

    assert alloc[root], "root not allocated"
    assert parent[root] == -1, "root has a parent"

    seen = set()
    leaf_depths = set()
    all_keys: List[int] = []

    def walk(nid: int, lo: int, hi: int, depth: int):
        assert nid >= 0, "NULL child reached"
        assert alloc[nid], f"unallocated node {nid} reachable"
        assert nid not in seen, f"node {nid} reachable twice (cycle/shared)"
        seen.add(nid)
        sz = int(size[nid])
        if is_leaf[nid]:
            leaf_depths.add(depth)
            ks = [int(k) for k in keys[nid] if int(k) != _EMPTY]
            assert len(ks) == sz, f"leaf {nid}: size {sz} != #keys {len(ks)} (inv 6)"
            assert len(set(ks)) == len(ks), f"leaf {nid}: duplicate key (inv 4)"
            for k in ks:
                assert lo <= k < hi, f"leaf {nid}: key {k} outside range [{lo},{hi}) (inv 2/7)"
            assert level[nid] == 0, f"leaf {nid}: level {level[nid]} != 0"
            if nid != root:
                assert sz >= a, f"leaf {nid}: underfull size {sz} (inv 1)"
            assert sz <= b, f"leaf {nid}: overfull size {sz} (inv 1)"
            all_keys.extend(ks)
            return
        # internal
        assert 2 <= sz <= b or (nid == root and 1 <= sz <= b), (
            f"internal {nid}: bad size {sz}"
        )
        if nid != root:
            assert sz >= a, f"internal {nid}: underfull size {sz} (inv 1)"
        routers = [int(k) for k in keys[nid, : b - 1]]
        used = routers[: sz - 1]
        assert all(used[i] < used[i + 1] for i in range(len(used) - 1)), (
            f"internal {nid}: routers not strictly sorted: {used}"
        )
        assert all(int(r) == _EMPTY for r in routers[sz - 1 :]), (
            f"internal {nid}: stale router beyond size"
        )
        for j in range(sz):
            c = int(children[nid, j])
            assert c >= 0, f"internal {nid}: NULL child {j}"
            assert parent[c] == nid, f"child {c}: parent {parent[c]} != {nid}"
            assert pidx[c] == j, f"child {c}: pidx {pidx[c]} != {j}"
            clo = lo if j == 0 else used[j - 1]
            chi = hi if j == sz - 1 else used[j]
            assert level[c] == level[nid] - 1, (
                f"child {c} level {level[c]} != parent level {level[nid]} - 1"
            )
            walk(c, clo, chi, depth + 1)

    import sys

    old_limit = sys.getrecursionlimit()
    sys.setrecursionlimit(100000)
    try:
        walk(root, -(2**63), _EMPTY, 0)
    finally:
        sys.setrecursionlimit(old_limit)

    assert len(leaf_depths) == 1, f"leaves at multiple depths: {leaf_depths}"
    assert leaf_depths == {height - 1}, (
        f"height {height} inconsistent with leaf depth {leaf_depths}"
    )
    assert len(all_keys) == len(set(all_keys)), "key present in two leaves"
    # every allocated node reachable (no leaks)
    alloc_ids = set(np.nonzero(alloc)[0].tolist())
    assert alloc_ids == seen, (
        f"allocation leak: allocated-but-unreachable {sorted(alloc_ids - seen)[:10]}"
    )


def tree_contents(state: TreeState, cfg) -> dict:
    """Dictionary contents by host walk (for oracle comparison)."""
    keys = np.asarray(state.keys)
    vals = np.asarray(state.vals)
    is_leaf = np.asarray(state.is_leaf)
    alloc = np.asarray(state.alloc)
    out = {}
    for nid in np.nonzero(is_leaf & alloc)[0]:
        for j in range(cfg.b):
            k = int(keys[nid, j])
            if k != _EMPTY:
                assert k not in out, f"key {k} in two leaves"
                out[k] = int(vals[nid, j])
    return dict(sorted(out.items()))
