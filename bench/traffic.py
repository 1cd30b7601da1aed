"""The one traffic generator.  Every mix is a data file
``bench/traffic/<mix>.json`` of parameters that this module reads; a mix
that needs code of its own may add ``bench/traffic/<mix>.py`` with a
``Traffic`` class of the same interface, which is then used instead.

A mix is a closed loop of ``clients`` lanes: each client has one
operation outstanding and a round carries all of them, so a round is
``clients`` operations.  Each round holds the mix's exact shares
(``round(share * clients)`` lanes of each kind, the rest going to the
last kind listed) in a random order, so every round does the same kind
and amount of work and only keys, values and order come from the seed.

Parameters (``bench/traffic/<mix>.json``):

  clients        lanes per round
  ops            ``{"find"|"insert"|"delete"|"scan": share}``
  keys           ``{"dist": "uniform"}`` or ``{"dist": "zipf", "s": 0.99}``:
                 the key (or scan start) each lane draws.  Zipf ranks are
                 mapped through a seed-fixed permutation of the key range,
                 so hot keys spread over it as YCSB's scrambled Zipfian's do.
  insert_keys    ``"drawn"`` (inserts draw from ``keys``) or ``"fresh"``
                 (each insert takes the next key of a seed-fixed shuffle of
                 the keys the prefill left out: always absent, as YCSB's
                 inserts of new records are).
  scan           ``{"min_records": 1, "max_records": 100, "cap": 128}``:
                 records per scan uniform in the range; the lane's span is
                 the record count divided by the prefill density, rounded
                 up, so it covers that many records on average.

The key range and the prefill come from the configuration
(``key_range``, ``prefill_fraction``): the prefill is that share of the
range, drawn without replacement and inserted in random order (the
paper's SetBench method).  Values are uniform in ``[0, 2**30)``, inside
the int32 contract of the narrow kernels.
"""
from __future__ import annotations

import importlib.util
import json
import os

import numpy as np

# The program's lane encoding (``repro.core.OP_*``); the harness checks
# these against the program before a run.
OP_NOP, OP_FIND, OP_INSERT, OP_DELETE, OP_RANGE = 0, 1, 2, 3, 4
OP_CODES = {"find": OP_FIND, "insert": OP_INSERT, "delete": OP_DELETE, "scan": OP_RANGE}
VAL_RANGE = 1 << 30

# independent random streams of one seed
_PREFILL, _PERM, _FRESH, _ROUNDS = 1, 2, 3, 4


def _rng(seed: int, stream: int, *more: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), stream, *more])


def zipf_cdf(n: int, s: float) -> np.ndarray:
    """Inverse-CDF table of the bounded Zipf(s) over ranks ``[0, n)``."""
    w = 1.0 / np.power(np.arange(1, n + 1, dtype=np.float64), s)
    return np.cumsum(w) / np.sum(w)


class Traffic:
    """Rounds of one mix over one configuration, from one seed.  Round
    ``i`` depends only on the seed and ``i``."""

    def __init__(self, params: dict, config: dict, seed: int):
        self.params = params
        self.seed = int(seed)
        self.clients = int(params["clients"])
        self.key_range = int(config["key_range"])
        self.density = float(config["prefill_fraction"])
        kinds = list(params["ops"].items())
        counts = [int(round(share * self.clients)) for _, share in kinds]
        counts[-1] = self.clients - sum(counts[:-1])
        if min(counts) < 0:
            raise ValueError(f"op shares {params['ops']} do not fit {self.clients} lanes")
        self.op_lanes = np.repeat(
            np.asarray([OP_CODES[k] for k, _ in kinds], np.int32), counts
        )
        self.n_insert = int(np.sum(self.op_lanes == OP_INSERT))
        keys = params["keys"]
        self.dist = keys["dist"]
        if self.dist == "zipf":
            self._cdf = zipf_cdf(self.key_range, float(keys["s"]))
            self._perm = _rng(seed, _PERM).permutation(self.key_range)
        elif self.dist != "uniform":
            raise ValueError(f"unknown key distribution {self.dist!r}")
        self.fresh = params.get("insert_keys", "drawn") == "fresh"
        scan = params.get("scan")
        self.scan_cap = int(scan["cap"]) if scan else None
        self._scan = scan
        self._prefill = None
        self._fresh_keys = None

    # -- prefill ---------------------------------------------------------------

    def prefill(self):
        """``(keys, vals)``: the prefill set in insertion order."""
        if self._prefill is None:
            rng = _rng(self.seed, _PREFILL)
            n = int(self.key_range * self.density)
            keys = rng.choice(self.key_range, size=n, replace=False).astype(np.int64)
            vals = rng.integers(0, VAL_RANGE, n, dtype=np.int64)
            self._prefill = (keys, vals)
        return self._prefill

    def _fresh(self) -> np.ndarray:
        if self._fresh_keys is None:
            present = np.zeros(self.key_range, bool)
            present[self.prefill()[0]] = True
            absent = np.nonzero(~present)[0].astype(np.int64)
            self._fresh_keys = _rng(self.seed, _FRESH).permutation(absent)
        return self._fresh_keys

    # -- rounds ----------------------------------------------------------------

    def _draw_keys(self, rng, n: int) -> np.ndarray:
        if self.dist == "zipf":
            return self._perm[np.searchsorted(self._cdf, rng.random(n))].astype(np.int64)
        return rng.integers(0, self.key_range, n, dtype=np.int64)

    def round(self, i: int):
        """Round ``i``: ``(ops int32, keys int64, vals int64)`` of
        ``clients`` lanes.  A scan lane carries ``key = lo`` and
        ``val = span`` (the program's OP_RANGE encoding)."""
        rng = _rng(self.seed, _ROUNDS, i)
        ops = rng.permutation(self.op_lanes)
        keys = self._draw_keys(rng, self.clients)
        vals = rng.integers(0, VAL_RANGE, self.clients, dtype=np.int64)
        if self.fresh and self.n_insert:
            fresh = self._fresh()
            lo = i * self.n_insert
            if lo + self.n_insert > fresh.size:
                raise ValueError(f"round {i}: the key range has no fresh keys left")
            keys[ops == OP_INSERT] = fresh[lo : lo + self.n_insert]
        is_scan = ops == OP_RANGE
        if is_scan.any():
            records = rng.integers(
                int(self._scan["min_records"]), int(self._scan["max_records"]) + 1,
                int(is_scan.sum()),
            )
            vals[is_scan] = np.ceil(records / self.density).astype(np.int64)
        return ops, keys, vals


def load_traffic(bench_dir: str, mix: str, config: dict, seed: int) -> Traffic:
    """The traffic of mix ``mix``: its parameter file, and its own
    generator where ``bench/traffic/<mix>.py`` exists."""
    with open(os.path.join(bench_dir, "traffic", f"{mix}.json")) as f:
        params = json.load(f)
    code = os.path.join(bench_dir, "traffic", f"{mix}.py")
    if os.path.exists(code):
        spec = importlib.util.spec_from_file_location(f"bench_traffic_{mix}", code)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.Traffic(params, config, seed)
    return Traffic(params, config, seed)
