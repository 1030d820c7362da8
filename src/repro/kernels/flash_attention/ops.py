"""Jit'd wrapper + batched convenience for the flash-attention kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import kernel as fk


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bkv"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bkv: int = 128):
    """(H, T, d) or batched (B, H, T, d) flash attention."""
    if q.ndim == 4:
        return jax.vmap(lambda a, b, c: fk.flash_attention_pallas(
            a, b, c, causal=causal, bq=bq, bkv=bkv))(q, k, v)
    return fk.flash_attention_pallas(q, k, v, causal=causal, bq=bq,
                                     bkv=bkv)
