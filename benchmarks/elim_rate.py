"""Elimination-rate study (paper §4 validation): fraction of update ops
eliminated and write reduction as a function of Zipf skew — the mechanism
behind the Figs 12–15 gap."""
from __future__ import annotations

from repro.configs.abtree import TPU8
from repro.core import ABTree
from repro.data.workloads import WorkloadConfig, op_stream, prefill_tree

from benchmarks.common import emit


def main(quick=False):
    svals = [0.5, 1.0, 1.5] if quick else [0.0, 0.5, 0.8, 1.0, 1.2, 1.5, 2.0]
    for s in svals:
        cfg = WorkloadConfig(
            key_range=4096, update_frac=1.0, dist="zipf" if s > 0 else "uniform",
            zipf_s=s, batch=512, seed=5,
        )
        tree = ABTree(TPU8._replace(capacity=1 << 15), mode="elim")
        prefill_tree(tree, cfg)
        n_updates = 0
        for ops, keys, vals in op_stream(cfg, 12):
            tree.apply_round(ops, keys, vals)
            n_updates += int((ops > 1).sum())
        st = tree.stats()
        rate = st["eliminated"] / max(n_updates, 1)
        emit(
            f"elim_rate.zipf{s}",
            0.0,
            f"eliminated_frac={rate:.3f};slot_writes={st['slot_writes']};updates={n_updates}",
        )


if __name__ == "__main__":
    from repro.compile_cache import use_persistent_cache

    use_persistent_cache()
    main()
