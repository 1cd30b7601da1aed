"""Mixture-of-experts FFN: top-k token-choice routing with static-capacity
sort-based dispatch (GShard/Switch style), shardable two ways:

  * EP  — experts over the `model` axis (`rules='ep'`): dispatch becomes an
    all-to-all in XLA; right when E % model == 0 (deepseek-v3: 256/16).
  * TP  — expert d_ff over `model` (`rules='tp'`): experts replicated,
    within-expert tensor parallel; right when E doesn't divide (granite 40).

The router is a hot skewed dictionary workload: expert-choice frequencies
are Zipfian, which is exactly the contention profile the paper's elimination
targets — serve/pages.py keeps router-stat counters in the Elim-ABtree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import cdt
from repro.models.params import P


def moe_spec(cfg):
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    s = {
        "router": P((d, e), ("embed", None)),
        "wi": P((e, d, f), ("experts", "embed", "expert_ffn")),
        "wg": P((e, d, f), ("experts", "embed", "expert_ffn")),
        "wo": P((e, f, d), ("experts", "expert_ffn", "embed")),
    }
    if cfg.n_shared:
        s["shared"] = {
            "wi": P((d, cfg.n_shared * f), ("embed", "ffn")),
            "wg": P((d, cfg.n_shared * f), ("embed", "ffn")),
            "wo": P((cfg.n_shared * f, d), ("ffn", "embed")),
        }
    return s


def _dispatch_ffn(p, xf, cfg, cap: int):
    """Sort-based capacity dispatch + expert SwiGLU over one token group
    xf: (T, d) → (T, d)."""
    dt = cdt(cfg)
    t, d = xf.shape
    e, k = cfg.n_experts, cfg.top_k

    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)  # (T, k)
    topw = topw / jnp.sum(topw, -1, keepdims=True)  # renormalize over chosen

    # flatten (token, slot) pairs and rank within expert by sorted order
    eid = topi.reshape(-1)  # (T*k,)
    tok = jnp.arange(t * k) // k  # token of each (token, slot) pair
    w = topw.reshape(-1)
    order = jnp.argsort(eid, stable=True)
    eid_s, tok_s, w_s = eid[order], tok[order], w[order]
    # rank within expert: i - first index of this expert in the sorted list
    first = jnp.searchsorted(eid_s, jnp.arange(e), side="left")  # (E,)
    rank = jnp.arange(t * k) - first[eid_s]
    ok = rank < cap
    slot = jnp.where(ok, eid_s * cap + rank, e * cap)  # overflow → dropped row

    # gather tokens to (E, cap, d)
    xe = jnp.zeros((e * cap + 1, d), dt).at[slot].set(xf[tok_s].astype(dt))
    xe = xe[:-1].reshape(e, cap, d)

    # expert FFN (SwiGLU)
    h = jnp.einsum("ecd,edf->ecf", xe, p["wi"].astype(dt))
    g = jnp.einsum("ecd,edf->ecf", xe, p["wg"].astype(dt))
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, p["wo"].astype(dt))

    # scatter back with routing weights
    ye_flat = ye.reshape(e * cap, d)
    contrib = ye_flat[jnp.clip(slot, 0, e * cap - 1)] * jnp.where(ok, w_s, 0.0)[:, None].astype(dt)
    return jnp.zeros((t, d), dt).at[tok_s].add(contrib)


def _grouped_dispatch(p, xg, cfg, cap: int):
    """Grouped dispatch with the group dim pinned to the data axes.

    The dispatch scatter has data-dependent indices, which the SPMD
    partitioner cannot prove local — it replicates the (E, cap, d)
    dispatched tensor via giant all-reduces (observed: 64–128 GB/device on
    granite train_4k).  `shard_map` over the (pod, data) axes makes the
    scatter a *local* op on local shapes by construction; the `model` axis
    stays on auto so expert-weight sharding (TP d_ff or EP experts) is
    still handled by the partitioner inside the body."""
    import numpy as np

    from repro.parallel.ctx import _ambient_mesh

    mesh = _ambient_mesh()

    def run(p_, xx):
        return jax.vmap(lambda one: _dispatch_ffn(p_, one, cfg, cap))(xx)

    manual = tuple(a for a in ("pod", "data") if mesh is not None and a in mesh.axis_names)
    shards = int(np.prod([mesh.shape[a] for a in manual])) if manual else 1
    if mesh is None or not manual or xg.shape[0] % shards:
        return run(p, xg)
    from jax.sharding import PartitionSpec as PS

    # shard_map with the manual axes; the model axis stays auto so the
    # partitioner still applies TP/EP weight sharding inside.
    fn = jax.shard_map(
        run,
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: PS(), p), PS(manual, None, None)),
        out_specs=PS(manual, None, None),
        axis_names=set(manual),
        check_vma=False,
    )
    return fn(p, xg)


def moe_apply(p, x, cfg):
    """x: (B, S, d) → (B, S, d).  Static capacity = T·k/E·cf per expert.

    ``cfg.moe_groups > 0`` enables GROUPED dispatch (§Perf beyond-paper
    optimization): tokens are routed within fixed groups that align with the
    (pod, data) batch sharding, so the sort/gather/scatter of the dispatch
    never crosses a data shard — experts are either replicated (TP rules)
    or model-sharded (EP rules), and in both cases the only cross-shard
    traffic left is the expert matmul's own reduction.  Routing semantics
    are identical except that capacity overflow is evaluated per group
    (same total capacity)."""
    dt = cdt(cfg)
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    g = cfg.moe_groups if cfg.moe_groups and t % cfg.moe_groups == 0 else 1
    cap = int(max(1, round(t / g * k / e * cfg.capacity_factor)))
    xf = x.reshape(t, d)

    if g > 1:
        xg = xf.reshape(g, t // g, d)
        y = _grouped_dispatch(p, xg, cfg, cap)
        y = y.reshape(t, d)
    else:
        y = _dispatch_ffn(p, xf, cfg, cap)

    if cfg.n_shared:
        sp = p["shared"]
        hs = jnp.einsum("td,df->tf", xf, sp["wi"].astype(dt))
        gs = jnp.einsum("td,df->tf", xf, sp["wg"].astype(dt))
        y = y + jnp.einsum("tf,fd->td", jax.nn.silu(gs) * hs, sp["wo"].astype(dt))

    return y.reshape(b, s, d)


def aux_load_balance_loss(logits: jax.Array, topi: jax.Array, n_experts: int):
    """Switch-style auxiliary load-balancing loss (returned by train_step
    for MoE archs)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    me = jnp.mean(probs, axis=0)  # mean router prob per expert
    ce = jnp.mean(
        jax.nn.one_hot(topi[..., 0], n_experts, dtype=jnp.float32), axis=0
    )  # fraction routed (top-1 proxy)
    return n_experts * jnp.sum(me * ce)
