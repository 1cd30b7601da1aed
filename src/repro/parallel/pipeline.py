"""Pipeline parallelism over the `pod` axis (GPipe-style, collective-permute).

At the assigned scale (512 chips) TP×DP covers every config, so PP is not
enabled by default (DESIGN.md §6); this module provides the working stage
loop for ≥4-pod deployments where the pod axis becomes the PP axis:

  * the layer stack is split into `n_stages` equal groups, stage s resident
    on pod s (params sharded over 'pod' on the stacked-layer dim);
  * microbatches stream through stages; activations hop pods via
    `jax.lax.ppermute` (ICI/DCN point-to-point);
  * the steady state keeps all pods busy except the usual (S-1) bubble
    fill/drain — bubble fraction = (S-1)/(S-1+M) for M microbatches.

`pipeline_apply` is jit-compatible and differentiable (ppermute has a
transpose rule), and is exercised by tests/test_pipeline.py on a host mesh.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS


def pipeline_apply(
    stage_params,  # pytree stacked on leading dim = n_stages
    x,  # (M, micro_batch, ...) microbatches
    body: Callable,  # body(params_slice, activation) -> activation
    mesh,
    axis: str = "pod",
):
    """Run x through n_stages pipeline stages laid out on `axis`.

    Schedule: for t in range(M + S - 1): every stage processes the
    microbatch it currently holds, then activations shift one pod to the
    right (ppermute ring).  Stage s processes microbatch m at t = m + s.
    """
    n_stages = mesh.shape[axis]
    m = x.shape[0]

    def shard_fn(params, xs):
        # params: this pod's stage slice (leading dim 1); xs: all microbatches
        sp = jax.tree.map(lambda a: a[0], params)
        stage = jax.lax.axis_index(axis)
        total = m + n_stages - 1

        def step(carry, t):
            acts, outs = carry  # acts: activation currently held (mb, ...)
            # stage 0 ingests microbatch t (when valid)
            mb_idx = jnp.clip(t, 0, m - 1)
            fresh = xs[mb_idx]
            cur = jnp.where(stage == 0, fresh, acts)
            live = (t - stage >= 0) & (t - stage < m)
            out = body(sp, cur)
            out = jnp.where(live, out, cur)
            # last stage emits; everyone else hands off to the right
            emit_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            is_emit = (stage == n_stages - 1) & live
            outs = outs.at[emit_idx].set(jnp.where(is_emit, out, outs[emit_idx]))
            nxt = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return (nxt, outs), None

        outs0 = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(
            step, (jnp.zeros_like(xs[0]), outs0), jnp.arange(total)
        )
        # the final outputs live on the last stage; broadcast via psum of
        # one-hot contribution (everyone else holds zeros)
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs

    manual = {axis}
    in_specs = (
        jax.tree.map(lambda _: PS(axis), stage_params),
        PS(),  # microbatches replicated in (activations stream through)
    )
    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=PS(),
        axis_names=manual,
        check_vma=False,
    )
    return fn(stage_params, x)
