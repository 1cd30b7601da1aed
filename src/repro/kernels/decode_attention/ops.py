"""Public wrapper for decode attention."""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref


def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_len=None,
    *,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
    block_k: int = 256,
):
    if use_pallas:
        return decode_attention_pallas(
            q, k, v, kv_len, block_k=block_k, interpret=interpret
        )
    return decode_attention_ref(q, k, v, kv_len)
