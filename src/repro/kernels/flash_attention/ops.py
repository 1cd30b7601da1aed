"""jit'd wrapper: Pallas forward + XLA backward (custom_vjp over the ref).

The Pallas kernel is forward-only; for training we register the oracle's
VJP so gradients are exact while the forward pays kernel cost.  On real TPU
hardware the flash backward kernel would replace it; the ref path is used
in train_step anyway (use_pallas=False default in model configs).  The
kernel runs in interpret mode only on the CPU backend (tests and
benchmarks there); see ``repro.kernels.interpret_mode``."""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6)
)
def flash_attention(q, k, v, causal=True, window=0, sm_scale=None, interpret=None):
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, sm_scale=sm_scale, interpret=interpret
    )


def _fwd(q, k, v, causal, window, sm_scale, interpret):
    out = flash_attention(q, k, v, causal, window, sm_scale, interpret)
    return out, (q, k, v)


def _bwd(causal, window, sm_scale, interpret, resid, g):
    q, k, v = resid
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_ref(
            q_, k_, v_, causal=causal, window=window, sm_scale=sm_scale
        ),
        q, k, v,
    )
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)
