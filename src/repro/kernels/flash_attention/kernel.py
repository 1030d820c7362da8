"""Pallas TPU flash attention: online-softmax over KV blocks, q-tiled grid.

The perf-critical substrate kernel for the LM side of the framework
(DESIGN §3): scores never touch HBM — each (q-block, kv-block) tile lives
in VMEM, sized to the MXU (block dims multiples of 128 at production
shapes).  Grid = (heads, q_blocks); the kv loop runs inside the kernel as
a fori_loop over VMEM-resident K/V blocks so m/l/acc carries stay in
registers/VMEM (contrast with the jnp blockwise path in models/attention,
whose carries round-trip HBM — the §Perf P3 lesson).

Layout: q (H, T, d) blocked (1, BQ, d); k/v (H, T, d) blocked (1, T, d) —
whole-K/V per head resident (fits VMEM for T <= ~8k at d=128; longer
sequences compose with the model-level sequence sharding).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels

NEG_INF = -2.0e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bkv: int,
                  causal: bool, scale: float):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # (BQ, d)
    t_kv = k_ref.shape[1]
    nkv = t_kv // bkv

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * bkv, bkv), :]
        v = v_ref[0, pl.ds(j * bkv, bkv), :]
        s = jax.lax.dot_general(q, k.astype(jnp.float32),
                                (((1,), (1,)), ((), ())))  # (BQ, BKV)
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bkv), 0)
            k_pos = j * bkv + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bkv), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())))
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, q_ref.shape[2]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nkv, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
    causal: bool = True, bq: int = 128,
    bkv: int = 128) -> jnp.ndarray:
    """q/k/v (H, T, d) -> (H, T, d).  T % bq == 0 and T % bkv == 0."""
    h, t, d = q.shape
    assert t % bq == 0 and t % bkv == 0
    scale = d ** -0.5
    kernel = functools.partial(_flash_kernel, bq=bq, bkv=bkv, causal=causal,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(h, t // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda hi, qi: (hi, qi, 0)),
            pl.BlockSpec((1, t, d), lambda hi, qi: (hi, 0, 0)),
            pl.BlockSpec((1, t, d), lambda hi, qi: (hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda hi, qi: (hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((h, t, d), q.dtype),
        interpret=kernels.interpret_mode(),
    )(q, k, v)
