"""Pallas TPU kernel: fused dynamic-weight elastic update (paper eqs. 12–13).

    θ^i ← θ^i − h1 · (θ^i − θ^m)
    θ^m ← θ^m + h2 · (θ^i − θ^m)

The update is memory-bound and elementwise over the *entire* parameter
pytree: the jnp path reads both trees twice (once per equation). The kernel
fuses both updates into a single HBM round-trip over VMEM tiles of
(BLOCK_ROWS × 128) — one read of (w, m), one write of (w', m'). h1/h2 are
prefetched scalars (SMEM) since they are per-*worker*, not per-element.

Weights flow in flattened to (rows, 128); the ops.py wrapper handles pytree
flattening/padding. Accumulation in f32 regardless of storage dtype.
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


def _kernel(h_ref, w_ref, m_ref, w_out_ref, m_out_ref):
    h1 = h_ref[0]
    h2 = h_ref[1]
    w = w_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    diff = w - m
    w_out_ref[...] = (w - h1 * diff).astype(w_out_ref.dtype)
    m_out_ref[...] = (m + h2 * diff).astype(m_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def elastic_update_flat(
    w: jax.Array,
    m: jax.Array,
    h1: jax.Array,
    h2: jax.Array,
    *,
    interpret: bool | None = None,
    block_rows: int = BLOCK_ROWS,
) -> tuple:
    """w, m: (rows, 128) — rows must be a multiple of ``block_rows``."""
    rows, lanes = w.shape
    assert lanes == LANES and rows % block_rows == 0, (w.shape, block_rows)
    grid = (rows // block_rows,)
    h = jnp.stack([h1.astype(jnp.float32), h2.astype(jnp.float32)])
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((2,), lambda i: (0,)),  # h1/h2 broadcast to all tiles
            spec, spec,
        ],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct(w.shape, w.dtype),
            jax.ShapeDtypeStruct(m.shape, m.dtype),
        ],
        interpret=interpret_mode(interpret),
    )(h, w, m)
    return out[0], out[1]


# ---------------------------------------------------------------------------
# multi-worker fused communication phase
# ---------------------------------------------------------------------------

def _make_batched_kernel(k: int, stale: bool = False):
    def kernel(h_ref, w_ref, m_ref, *rest):
        # h_ref: (2, k) scalar-prefetched into SMEM; w_ref: (k, bR, LANES).
        # With ``stale`` (delayed averaging) an extra ref block follows m:
        # diffs are measured against it, accumulation stays on m.
        if stale:
            r_ref, w_out_ref, m_out_ref = rest
            ref = r_ref[...].astype(jnp.float32)
        else:
            w_out_ref, m_out_ref = rest
        m = m_ref[...].astype(jnp.float32)
        if not stale:
            ref = m
        acc = jnp.zeros_like(m)
        for i in range(k):  # k is static → unrolled; scalar SMEM reads
            h1 = h_ref[0, i]
            h2 = h_ref[1, i]
            w = w_ref[i].astype(jnp.float32)
            diff = w - ref
            w_out_ref[i] = (w - h1 * diff).astype(w_out_ref.dtype)
            acc = acc + h2 * diff
        m_out_ref[...] = (m + acc).astype(m_out_ref.dtype)

    return kernel


def batched_block_rows(k: int, block_rows: int = BLOCK_ROWS) -> int:
    """Shrink the row tile so all k worker blocks fit in VMEM together."""
    return max(8, (block_rows // max(1, k)) // 8 * 8)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def elastic_update_batched_flat(
    w: jax.Array,
    m: jax.Array,
    h1: jax.Array,
    h2: jax.Array,
    ref: jax.Array | None = None,
    *,
    interpret: bool | None = None,
    block_rows: int | None = None,
) -> tuple:
    """w: (k, rows, 128) stacked workers; m: (rows, 128); h1/h2: (k,).

    One grid pass over row tiles performs every worker update *and* the
    h2-weighted master reduction θ^m ← θ^m + Σ_i h2_i (θ^i − θ^m) in a
    single HBM round-trip: each (w, m) element is read once and each
    (w', m') element written once, vs 2k reads of m in the sequential scan.

    ``ref`` (optional, (rows, 128)): delayed averaging — every diff is
    measured against this stale master snapshot instead of ``m``, while the
    master accumulation target stays ``m`` (one extra read per element).
    ``None`` compiles the exact pre-staleness kernel.
    """
    k, rows, lanes = w.shape
    if block_rows is None:
        block_rows = batched_block_rows(k)
    assert lanes == LANES and rows % block_rows == 0, (w.shape, block_rows)
    assert m.shape == (rows, lanes) and h1.shape == h2.shape == (k,)
    h = jnp.stack([h1.astype(jnp.float32), h2.astype(jnp.float32)])
    wspec = pl.BlockSpec((k, block_rows, LANES), lambda i, hv: (0, i, 0))
    mspec = pl.BlockSpec((block_rows, LANES), lambda i, hv: (i, 0))
    stale = ref is not None
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # h lands in SMEM before the body runs
        grid=(rows // block_rows,),
        in_specs=[wspec, mspec] + ([mspec] if stale else []),
        out_specs=[wspec, mspec],
    )
    operands = (h, w, m) + ((ref,) if stale else ())
    out = pl.pallas_call(
        _make_batched_kernel(k, stale=stale),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(w.shape, w.dtype),
            jax.ShapeDtypeStruct(m.shape, m.dtype),
        ],
        interpret=interpret_mode(interpret),
        name="elastic_update_batched",
    )(*operands)
    return out[0], out[1]
