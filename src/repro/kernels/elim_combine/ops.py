"""Public wrapper for the elimination combine."""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels.elim_combine.kernel import elim_combine_pallas
from repro.kernels.elim_combine.ref import elim_combine_ref


def elim_combine(
    ops: jax.Array,
    vals: jax.Array,
    seg_head: jax.Array,
    present0: jax.Array,
    val0: jax.Array,
    *,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
    tile: int = 256,
):
    """Segmented publishing-elimination fold.  Returns
    (before_present, before_val, after_present, after_val)."""
    if use_pallas:
        return elim_combine_pallas(
            ops, vals, seg_head, present0, val0, tile=tile, interpret=interpret
        )
    return elim_combine_ref(ops, vals, seg_head, present0, val0)
