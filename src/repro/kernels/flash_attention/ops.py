"""jit'd wrapper: BSHD-layout flash attention (matches nn.layers layout)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention


def flash_attention_bshd(q, k, v, *, causal=True, window=None, chunk=None,
                         block_q=128, block_k=128, interpret=None):
    """q: (B,S,H,D), k/v: (B,S,KVH,D) → (B,S,H,D)."""
    out = flash_attention(
        jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
        causal=causal, window=window, chunk=chunk, block_q=block_q,
        block_k=block_k, interpret=interpret)
    return jnp.moveaxis(out, 1, 2)
