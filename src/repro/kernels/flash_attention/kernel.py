"""Pallas TPU kernel: FlashAttention (causal / sliding-window / chunked).

TPU-native design:
- grid = (batch·q_heads, n_q_blocks, n_kv_blocks) with the KV dimension
  innermost; the (m, l, acc) online-softmax state lives in VMEM scratch and
  persists across the KV sweep for a fixed (head, q-block).
- BlockSpecs tile Q/K/V/O as (block_q|block_k, head_dim) VMEM tiles with
  head_dim as the lane dimension (128-aligned for the MXU); GQA is handled
  in the K/V index_map (q-head → kv-head = h // group_size) without
  materializing repeated KV.
- fully-masked (q-block, kv-block) pairs (outside the causal triangle /
  sliding window / chunk diagonal) are skipped with ``pl.when`` — predicated
  out, no MXU work.

Validated in interpret mode against ``ref.mha_reference`` over
shape/dtype/mask sweeps (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_q, block_k, n_k, scale, causal, window, chunk):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    # block-level liveness (positions are the row/col indices)
    live = True
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + block_q - 1)
    if window is not None:
        live = jnp.logical_and(
            live, q_start - (k_start + block_k - 1) < window)
    if chunk is not None:
        live = jnp.logical_and(
            live, (q_start + block_q - 1) // chunk >= k_start // chunk)
        live = jnp.logical_and(
            live, q_start // chunk <= (k_start + block_k - 1) // chunk)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones(s.shape, jnp.bool_)
        if causal:
            mask &= kp <= qp
        if window is not None:
            mask &= (qp - kp) < window
        if chunk is not None:
            mask &= (qp // chunk) == (kp // chunk)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, 1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, 1)
        m_ref[...] = m_new
        v = v_ref[...].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "chunk", "block_q", "block_k",
                     "interpret"))
def flash_attention(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, KVH, Skv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window=None,
    chunk=None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    B, H, Sq, D = q.shape
    KVH, Skv = k.shape[1], k.shape[2]
    G = H // KVH
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0
    n_q, n_k = Sq // block_q, Skv // block_k
    scale = 1.0 / math.sqrt(D)

    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * KVH, Skv, D)
    vf = v.reshape(B * KVH, Skv, D)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, n_k=n_k,
        scale=scale, causal=causal, window=window, chunk=chunk)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((None, block_k, D),
                         lambda b, iq, ik, G=G: (b // G, ik, 0)),
            pl.BlockSpec((None, block_k, D),
                         lambda b, iq, ik, G=G: (b // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D),
                               lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),       # m (running max)
            pltpu.VMEM((block_q,), jnp.float32),       # l (running denom)
            pltpu.VMEM((block_q, D), jnp.float32),     # acc
        ],
        interpret=interpret_mode(interpret),
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, D)
