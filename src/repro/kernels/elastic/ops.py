"""jit'd public wrappers: fused elastic update over parameter pytrees."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.elastic.kernel import (BLOCK_ROWS, LANES,
                                          batched_block_rows,
                                          elastic_update_batched_flat,
                                          elastic_update_flat)
from repro.kernels.flatten import (flatten_stacked, flatten_tree, unflatten,
                                   unflatten_stacked)

# Shared with repro.kernels.adahessian via repro.kernels.flatten; the old
# private names stay importable.
_flatten_tree = flatten_tree
_unflatten = unflatten
_flatten_stacked = flatten_stacked
_unflatten_stacked = unflatten_stacked


def elastic_update_pallas(worker_params, master_params, h1, h2, *,
                          interpret: bool | None = None):
    """Fused eqs. (12)–(13) over whole pytrees. Returns (worker', master')."""
    wf, wl, wd, n = flatten_tree(worker_params, BLOCK_ROWS)
    mf, ml, md, _ = flatten_tree(master_params, BLOCK_ROWS)
    w2d, m2d = elastic_update_flat(
        wf, mf, jnp.asarray(h1), jnp.asarray(h2), interpret=interpret)
    return (unflatten(w2d, wl, wd, n), unflatten(m2d, ml, md, n))


def elastic_update_batched_pallas(worker_stacked, master_params, h1, h2, *,
                                  master_ref=None,
                                  interpret: bool | None = None):
    """All k worker exchanges + the h2-weighted master reduction in one
    kernel pass. ``worker_stacked`` leaves carry a leading (k,) axis; h1/h2
    are (k,) vectors (pass ``master_schedule_weights(h2)`` for event-order
    parity with the sequential scan). Returns (workers', master').

    ``master_ref`` (optional pytree like the master): delayed averaging —
    the elastic diffs θ^i − θ^ref are measured against this stale snapshot
    while the accumulation target stays the live master (see
    ``repro.core.elastic.elastic_update_batched``). ``None`` is the exact
    pre-staleness kernel."""
    h1 = jnp.asarray(h1, jnp.float32)
    h2 = jnp.asarray(h2, jnp.float32)
    k = h1.shape[0]
    tile_rows = batched_block_rows(k)
    wf, wl, wd, n = flatten_stacked(worker_stacked, tile_rows)
    mf, ml, md, _ = flatten_tree(master_params, tile_rows)
    rf = None
    if master_ref is not None:
        rf = flatten_tree(master_ref, tile_rows)[0]
    w3d, m2d = elastic_update_batched_flat(
        wf, mf, h1, h2, ref=rf, interpret=interpret, block_rows=tile_rows)
    return (unflatten_stacked(w3d, wl, wd, n), unflatten(m2d, ml, md, n))
