"""jit'd wrappers: fused AdaHessian step over flat / stacked pytree views."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import OptimizerConfig
from repro.kernels.adahessian.kernel import (BLOCK_ROWS, LANES,
                                             adahessian_update_batched_flat,
                                             adahessian_update_flat,
                                             batched_block_rows)
from repro.kernels.flatten import flatten_stacked, unflatten_stacked
from repro.optim.base import apply_updates
from repro.optim.adahessian import moment_update


def pack_scalars(cfg: OptimizerConfig, t: jax.Array) -> jax.Array:
    b1, b2 = cfg.betas
    tf = t.astype(jnp.float32)
    return jnp.stack([
        jnp.float32(cfg.lr), jnp.float32(b1), jnp.float32(b2),
        1.0 - b1 ** tf, 1.0 - b2 ** tf,
        jnp.float32(cfg.hessian_power / 2.0), jnp.float32(cfg.eps),
    ])


def adahessian_step_pallas(p, g, h, m, v, cfg: OptimizerConfig, t,
                           *, interpret: bool | None = None):
    """p,g,h,m,v: 1-D same-length f32 arrays (pre-flattened). Returns
    (p', m', v') with padding handled internally."""
    n = p.shape[0]
    tile = BLOCK_ROWS * LANES
    pad = (-n) % tile
    r2 = lambda x: jnp.pad(x.astype(jnp.float32), (0, pad)).reshape(-1, LANES)
    # pad v with 1s so the fractional power sees a benign value
    vp = jnp.pad(v.astype(jnp.float32), (0, pad), constant_values=1.0)
    p2, m2, v2 = adahessian_update_flat(
        r2(p), r2(g), r2(h), r2(m), vp.reshape(-1, LANES),
        pack_scalars(cfg, jnp.asarray(t)), interpret=interpret)
    unr = lambda x: x.reshape(-1)[:n]
    return unr(p2), unr(m2), unr(v2)


def adahessian_update_batched(worker_params, grads, hs, opt_state,
                              cfg: OptimizerConfig, *,
                              use_kernel: bool = True,
                              interpret: bool | None = None):
    """Batched AdaHessian step for all k workers in one pass (ISSUE-7).

    ``worker_params`` / ``grads`` / ``hs`` are stacked pytrees with a
    leading (k,) worker axis; ``hs`` is the *already spatially averaged*
    Hutchinson diagonal (averaging is per-worker — it must happen before
    stacking, or scalar leaves would average across workers).
    ``opt_state`` is the vmapped AdaHessian state ({count: (k,), m, v});
    per-worker counts may differ (straggler freezing), so the bias
    corrections are per-worker prefetch scalars. Returns
    ``(new_params, new_opt_state)``.

    ``use_kernel=False`` runs the same update as a vmapped
    ``repro.optim.adahessian.moment_update`` — the path used per shard
    under sharded placement (mirroring the elastic comm kernel's
    single-device-only gating) and by the local-phase benchmark; both
    branches execute identical elementwise ops and agree bitwise in
    interpret mode.
    """
    b1, b2 = cfg.betas
    t = opt_state["count"] + 1  # (k,) int32

    if not use_kernel:
        def one(p, count, m, v, g, h):
            upd, o2 = moment_update(
                cfg, g, {"count": count, "m": m, "v": v}, p, h)
            return apply_updates(p, upd), o2

        return jax.vmap(one)(worker_params, opt_state["count"],
                             opt_state["m"], opt_state["v"], grads, hs)

    tf = t.astype(jnp.float32)
    bc1 = 1 - b1 ** tf
    bc2 = 1 - b2 ** tf
    k = t.shape[0]
    tile = batched_block_rows(k)
    pf, p_leaves, p_def, n = flatten_stacked(worker_params, tile)
    gf = flatten_stacked(grads, tile)[0]
    hf = flatten_stacked(hs, tile)[0]
    mf, m_leaves, m_def, _ = flatten_stacked(opt_state["m"], tile)
    # pad v with 1s so the fractional power sees a benign value
    vf = flatten_stacked(opt_state["v"], tile, pad_value=1.0)[0]
    p2, m2, v2 = adahessian_update_batched_flat(
        pf, gf, hf, mf, vf, bc1, bc2,
        lr=cfg.lr, b1=b1, b2=b2, denom_pow=cfg.hessian_power / 2.0,
        eps=cfg.eps, lrwd=cfg.lr * cfg.weight_decay,
        interpret=interpret, block_rows=tile)
    return (unflatten_stacked(p2, p_leaves, p_def, n),
            {"count": t,
             "m": unflatten_stacked(m2, m_leaves, m_def, n),
             "v": unflatten_stacked(v2, m_leaves, m_def, n)})
