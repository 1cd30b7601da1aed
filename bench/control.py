"""The correctness check's control: a run of a cell whose timed path is
the reference with one guarantee broken, which the check must refuse.

    python3 bench/control.py --workload <cell> --rounds <n> --seeds <s> [<s> ...]

``ControlSystem`` stands in for the program.  It answers every round
with ``reference.ControlSet``: point lanes against the round-start set
and scans against the round-end set, so the order within a round is
lost (the linearizability the configurations state).  In a durable
configuration its recovery also returns the set as it was before the
last acknowledged round, as a store that acknowledged a round before
its commit would.  Each seed's run drives ``--rounds`` window rounds,
as many as a run of the program does, and prints the numbers the check
compared with their limits; ``PERF.md`` records the readings.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import numpy as np  # noqa: E402

from reference import EMPTY, ControlSet  # noqa: E402


class ControlSystem:
    def __init__(self, durable: bool):
        self.set = ControlSet()
        self.durable = durable
        self.rounds = 0
        self._undo = {}

    def apply(self, ops, keys, vals, scan_cap):
        d = self.set.d
        self._undo = {int(k): d.get(int(k)) for k in keys.tolist()}
        self.rounds += 1
        cap = scan_cap or 128
        res, fnd, scan = self.set.apply_round(ops, keys, vals, cap)
        if scan is not None:  # the program's form: every lane has a row
            lanes, count, k, v = scan
            c = np.zeros(ops.size, np.int32)
            rk = np.full((ops.size, cap), EMPTY, np.int64)
            rv = np.zeros((ops.size, cap), np.int64)
            c[lanes], rk[lanes], rv[lanes] = count, k, v
            scan = (c, rk, rv)
        return res, fnd, scan

    def stats(self) -> dict:
        return {}

    def items(self) -> dict:
        return dict(self.set.d)

    def set_tracer(self, tracer):
        pass

    def full_snapshots(self) -> int:
        return self.rounds

    def recover(self):
        items = dict(self.set.d)
        for k, v in self._undo.items():
            if v is None:
                items.pop(k, None)
            else:
                items[k] = v
        return 0.0, items

    def close(self):
        pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rounds", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.Cell(os.path.dirname(HERE), args.workload)
    durable = cell.config["holder"] != "ABTree"
    for seed in args.seeds:
        out = harness.run_cell(
            cell, seed, 0.0, False, t_start=time.perf_counter(),
            system=ControlSystem(durable), rounds=args.rounds,
        )
        print(json.dumps({"workload": cell.name, "seed": seed, "correct": out["correct"],
                          "window_rounds": out["info"]["window_rounds"],
                          "check": out["check"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
