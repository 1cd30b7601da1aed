"""Pallas TPU kernels for the tree's hot loops (see README.md)."""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a ``pallas_call`` runs in interpret mode — the one place
    every kernel decides it.  ``None`` (every wrapper's default) means
    interpret exactly when the default backend is the CPU, so the TPU
    always runs compiled kernels.  An explicit ``False`` compiles (also
    for a described, unattached chip); ``True`` off the CPU is refused,
    because the interpreter on an accelerator hides the device."""
    on_cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return on_cpu
    if interpret and not on_cpu:
        raise ValueError(
            "Pallas interpret mode is for the CPU backend only; "
            f"the default backend is {jax.default_backend()!r}"
        )
    return bool(interpret)
