"""Parameter-spec machinery: one declaration drives initialization, the
dry-run ShapeDtypeStruct tree, and sharding (logical-axis rules, MaxText
style).

Every parameter is declared as a ``P(shape, logical_axes, …)``.  Logical
axis names are mapped to physical mesh axes by a *rules* dict, so sharding
strategies (TP-only, FSDP×TP, EP, …) are data — hillclimbing swaps rule
tables, not model code.
"""
from __future__ import annotations

import hashlib
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


class P(NamedTuple):
    """Parameter spec: shape + logical axes + init."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]  # logical axis name per dim (None = replicated)
    init: str = "normal"  # normal | zeros | ones | embed
    scale: float = 0.02
    dtype: Any = jnp.float32

    def __post_init__(self):  # pragma: no cover - NamedTuple has no post_init
        pass


# Default logical→physical rules.  `fsdp` variants additionally shard the
# non-contracting large dim over 'data' (ZeRO-3-equivalent under jit).
RULES_TP = {
    "layers": None,
    "embed": None,
    "vocab": "model",
    "ffn": "model",
    "heads": "model",
    "kv_heads": "model",
    "qkv": "model",
    "experts": None,
    "expert_ffn": "model",
    "conv": None,
    "state": None,
}
RULES_FSDP_TP = dict(RULES_TP, embed="data")
# Expert parallelism: experts over 'model', expert-internal dims replicated.
RULES_EP = dict(RULES_TP, experts="model", expert_ffn=None)
RULES_EP_FSDP = dict(RULES_EP, embed="data")

RULE_SETS = {
    "tp": RULES_TP,
    "fsdp_tp": RULES_FSDP_TP,
    "ep": RULES_EP,
    "ep_fsdp": RULES_EP_FSDP,
}


def logical_to_pspec(axes, rules) -> PartitionSpec:
    phys = []
    for ax in axes:
        m = rules.get(ax) if ax is not None else None
        phys.append(m)
    return PartitionSpec(*phys)


def _leaf_key(path: str, seed: int) -> jax.Array:
    h = int.from_bytes(hashlib.sha256(path.encode()).digest()[:4], "little")
    return jax.random.fold_in(jax.random.key(h), seed)


def init_leaf(spec: P, path: str, seed: int = 0) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    k = _leaf_key(path, seed)
    scale = spec.scale
    if spec.init == "embed":
        scale = 1.0 / np.sqrt(spec.shape[-1])
    return (jax.random.normal(k, spec.shape, jnp.float32) * scale).astype(spec.dtype)


def is_spec(x) -> bool:
    return isinstance(x, P)


def _walk(tree, path=""):
    if is_spec(tree):
        yield path, tree
        return
    for k in sorted(tree.keys()):
        yield from _walk(tree[k], f"{path}/{k}")


def map_specs(fn, tree):
    """Apply fn(path, P) to every spec leaf, preserving structure."""

    def rec(t, path):
        if is_spec(t):
            return fn(path, t)
        return {k: rec(v, f"{path}/{k}") for k, v in t.items()}

    return rec(tree, "")


def init_params(spec_tree, seed: int = 0) -> dict:
    """Random parameters: each leaf's key folds ``seed`` into a hash of its
    path, so one seed fixes the whole tree."""
    return map_specs(lambda p, s: init_leaf(s, p, seed), spec_tree)


def abstract_params(spec_tree) -> dict:
    return map_specs(lambda p, s: jax.ShapeDtypeStruct(s.shape, s.dtype), spec_tree)


def param_shardings(spec_tree, mesh: Mesh, rules) -> dict:
    def shard_one(path, s: P):
        pspec = logical_to_pspec(s.axes, rules)
        # drop shardings that do not divide evenly — replicate that dim
        fixed = []
        for dim, ax in zip(s.shape, pspec):
            if ax is None:
                fixed.append(None)
                continue
            axsize = int(np.prod([mesh.shape[a] for a in (ax if isinstance(ax, tuple) else (ax,))]))
            fixed.append(ax if dim % axsize == 0 else None)
        return NamedSharding(mesh, PartitionSpec(*fixed))

    return map_specs(shard_one, spec_tree)


def count_params(spec_tree) -> int:
    return sum(int(np.prod(s.shape)) for _, s in _walk(spec_tree))


def activation_sharding(mesh: Mesh, *axes):
    """with_sharding_constraint helper for activations."""
    return NamedSharding(mesh, PartitionSpec(*axes))
