"""Pallas TPU kernel: fused AdaHessian moment + parameter update.

Per element (f32 accumulation):

    m ← β1·m + (1−β1)·g
    v ← β2·v + (1−β2)·h²          (h = spatially averaged Hessian diagonal)
    p ← p − lr · (m/bc1) / ((v/bc2)^{κ/2} + ε)

Five HBM reads + three writes fused into one pass over (BLOCK_ROWS × 128)
VMEM tiles; the jnp path (repro.optim.adahessian) performs the same update
as ~6 separate elementwise HLO ops. Scalars (lr, β, bias corrections, κ, ε)
arrive in a small prefetch vector.

Two variants live here:

- ``adahessian_update_flat`` — the original single-worker kernel (one
  (rows, 128) view, all scalars prefetched).
- ``adahessian_update_batched_flat`` — the multi-worker local-phase kernel
  (ISSUE-7): p/g/h/m/v carry a leading worker axis (k, rows, 128) and one
  grid pass over row tiles updates every worker's moments and parameters
  together — one HBM round-trip per τ-step for the whole pool, mirroring
  the elastic comm kernel's layout. Only the per-worker bias corrections
  are runtime scalars (straggler-frozen workers have diverging step
  counts); the config constants (lr, β, κ/2, ε, lr·wd) are baked into the
  kernel as Python floats so the traced ops are *identical* to the jnp
  oracle's (`repro.optim.adahessian.moment_update`) — with a traced
  exponent, e.g., ``jnp.power(x, 0.5)`` could no longer constant-fold the
  way the oracle's does, and interpret-mode bit-exactness would be lost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

BLOCK_ROWS = 256
LANES = 128


def _kernel(s_ref, p_ref, g_ref, h_ref, m_ref, v_ref,
            p_out, m_out, v_out):
    lr, b1, b2, bc1, bc2, half_k, eps = (s_ref[i] for i in range(7))
    g = g_ref[...].astype(jnp.float32)
    h = h_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * h * h
    denom = jnp.exp(half_k * jnp.log(v / bc2 + 1e-30)) + eps
    p = p_ref[...].astype(jnp.float32) - lr * (m / bc1) / denom
    p_out[...] = p.astype(p_out.dtype)
    m_out[...] = m
    v_out[...] = v


@functools.partial(
    jax.jit, static_argnames=("interpret", "block_rows"))
def adahessian_update_flat(
    p, g, h, m, v, scalars, *, interpret: bool | None = None,
    block_rows: int = BLOCK_ROWS,
):
    """All arrays (rows, 128); scalars (7,) f32 = lr,b1,b2,bc1,bc2,κ/2,ε."""
    rows, lanes = p.shape
    assert lanes == LANES and rows % block_rows == 0
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    sspec = pl.BlockSpec((7,), lambda i: (0,))
    out = pl.pallas_call(
        _kernel,
        grid=(rows // block_rows,),
        in_specs=[sspec, spec, spec, spec, spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct(p.shape, p.dtype),
            jax.ShapeDtypeStruct(m.shape, jnp.float32),
            jax.ShapeDtypeStruct(v.shape, jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(scalars, p, g, h, m, v)
    return out


# ---------------------------------------------------------------------------
# multi-worker fused local phase (ISSUE-7)
# ---------------------------------------------------------------------------

def _make_batched_kernel(k: int, lr: float, b1: float, b2: float,
                         denom_pow: float, eps: float, lrwd: float):
    def kernel(bc_ref, p_ref, g_ref, h_ref, m_ref, v_ref,
               p_out, m_out, v_out):
        # bc_ref: (2, k) scalar-prefetched into SMEM (per-worker bias
        # corrections — straggler-frozen workers carry diverging counts);
        # the data blocks are (k, bR, LANES). The ops below mirror
        # repro.optim.adahessian.moment_update one-for-one (constants are
        # the same Python floats), so interpret mode is bit-exact with it.
        for i in range(k):  # k is static → unrolled; scalar SMEM reads
            bc1 = bc_ref[0, i]
            bc2 = bc_ref[1, i]
            g = g_ref[i].astype(jnp.float32)
            h = h_ref[i].astype(jnp.float32)
            m = b1 * m_ref[i] + (1 - b1) * g
            v = b2 * v_ref[i] + (1 - b2) * jnp.square(h)
            denom = jnp.power(v / bc2 + 1e-30, denom_pow) + eps
            u = -lr * (m / bc1) / denom
            if lrwd:
                u = u - lrwd * p_ref[i].astype(jnp.float32)
            p_out[i] = (p_ref[i].astype(jnp.float32) + u).astype(p_out.dtype)
            m_out[i] = m
            v_out[i] = v

    return kernel


def batched_block_rows(k: int, block_rows: int = BLOCK_ROWS) -> int:
    """Shrink the row tile so the 8 resident (k, bR, 128) f32 blocks
    (5 inputs + 3 outputs) stay within ~8 MB of VMEM."""
    budget = 8 * 1024 * 1024
    fit = budget // (8 * max(1, k) * LANES * 4)
    return max(8, min(block_rows, fit // 8 * 8))


@functools.partial(
    jax.jit, static_argnames=("lr", "b1", "b2", "denom_pow", "eps", "lrwd",
                              "interpret", "block_rows"))
def adahessian_update_batched_flat(
    p, g, h, m, v, bc1, bc2, *, lr: float, b1: float, b2: float,
    denom_pow: float, eps: float, lrwd: float = 0.0,
    interpret: bool | None = None, block_rows: int | None = None,
):
    """All data arrays (k, rows, 128); bc1/bc2 (k,) f32 per-worker bias
    corrections (the only runtime scalars — everything else is a static
    Python float baked into the kernel). Returns (p', m', v')."""
    k, rows, lanes = p.shape
    if block_rows is None:
        block_rows = batched_block_rows(k)
    assert lanes == LANES and rows % block_rows == 0, (p.shape, block_rows)
    assert bc1.shape == bc2.shape == (k,)
    bc = jnp.stack([bc1.astype(jnp.float32), bc2.astype(jnp.float32)])
    spec = pl.BlockSpec((k, block_rows, LANES), lambda i, bv: (0, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # bc lands in SMEM before the body runs
        grid=(rows // block_rows,),
        in_specs=[spec] * 5,
        out_specs=[spec] * 3,
    )
    out = pl.pallas_call(
        _make_batched_kernel(k, lr, b1, b2, denom_pow, eps, lrwd),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(p.shape, p.dtype),
            jax.ShapeDtypeStruct(m.shape, jnp.float32),
            jax.ShapeDtypeStruct(v.shape, jnp.float32),
        ],
        interpret=interpret_mode(interpret),
        name="adahessian_update_batched",
    )(bc, p, g, h, m, v)
    return out
