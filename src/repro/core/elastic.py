"""Elastic-averaging parameter updates (EASGD eqs. 8–9; dynamic eqs. 12–13).

    θ^i ← θ^i − h1 · (θ^i − θ^m)          (worker pulled toward master)
    θ^m ← θ^m + h2 · (θ^i − θ^m)          (master pulled toward worker)

With h1 = h2 = α this is exactly EASGD's symmetric elastic force. The fused
form (one pass over both pytrees) also exists as a Pallas TPU kernel
(``repro.kernels.elastic``); this is the jnp path / oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _unzip_pairs(pairs):
    """Split a pytree of (worker, master) leaf tuples into two pytrees."""
    is_pair = lambda x: isinstance(x, tuple)
    return (jax.tree.map(lambda p: p[0], pairs, is_leaf=is_pair),
            jax.tree.map(lambda p: p[1], pairs, is_leaf=is_pair))


def elastic_update(worker_params, master_params, w1, w2):
    """Apply eqs. (12)–(13). w1/w2 are scalars (possibly traced)."""

    def upd(w, m):
        wf = w.astype(jnp.float32)
        mf = m.astype(jnp.float32)
        diff = wf - mf
        return ((wf - w1 * diff).astype(w.dtype),
                (mf + w2 * diff).astype(m.dtype))

    return _unzip_pairs(jax.tree.map(upd, worker_params, master_params))


def elastic_update_batched(worker_stacked, master_params, w1, w2,
                           axis_name=None, master_ref=None):
    """All k worker exchanges plus the master reduction in one batched pass.

    ``worker_stacked`` leaves have a leading worker axis (k, ...); w1/w2 are
    (k,) vectors. Every worker syncs against the *same* master snapshot and

        θ^i ← θ^i − w1_i · (θ^i − θ^m)
        θ^m ← θ^m + Σ_i w2_i · (θ^i − θ^m)

    Pass ``dynamic_weight.master_schedule_weights(h2)`` as ``w2`` to make the
    master reduction exactly match the sequential event-ordered scan.

    With ``axis_name`` (sharded placement, inside ``shard_map``): the leading
    axis holds only this shard's k/n_pods workers and the master reduction
    becomes a cross-pod collective. The worker pull stays shard-local; the
    diffs and the (k,) weights are all-gathered along the worker axis and
    the master reduction is the *same* (k, ...)-shaped weighted sum as the
    single-device path — an all-reduce decomposed as all-gather + local
    reduction — so the sharded master is bit-exact with the single-device
    fused master. (A ``psum`` of per-shard partial sums would re-associate
    the sum; gathering the products ``w2·diff`` instead of the diffs would
    let the compiler fuse the multiply into the reduction on one path and
    not the other, which differs in the last ulp once it contracts
    multiply-adds.)

    ``master_ref`` (optional pytree like the master): delayed averaging
    (DaSGD / ``ElasticConfig.staleness``) — every diff θ^i − θ^ref is
    measured against this stale snapshot while the accumulation target stays
    the live master:

        θ^i ← θ^i − w1_i · (θ^i − θ^ref)
        θ^m ← θ^m + Σ_i w2_i · (θ^i − θ^ref)

    so round r's exchange depends only on the snapshot, not on round r−1's
    master reduction. ``None`` (the default) is the exact pre-staleness
    code path — ``staleness=0`` trajectories are bit-identical.
    """
    w1 = jnp.asarray(w1, jnp.float32)
    w2 = jnp.asarray(w2, jnp.float32)
    if axis_name is not None:
        w2 = jax.lax.all_gather(w2, axis_name, axis=0, tiled=True)

    def upd(ws, m, ref=None):
        h1 = w1.reshape((-1,) + (1,) * (ws.ndim - 1))
        h2 = w2.reshape((-1,) + (1,) * (ws.ndim - 1))
        wf = ws.astype(jnp.float32)
        mf = m.astype(jnp.float32)
        diff = wf - (mf[None] if ref is None
                     else ref.astype(jnp.float32)[None])
        new_w = (wf - h1 * diff).astype(ws.dtype)
        if axis_name is not None:
            diff = jax.lax.all_gather(diff, axis_name, axis=0, tiled=True)
        return new_w, (mf + jnp.sum(h2 * diff, axis=0)).astype(m.dtype)

    if master_ref is None:
        pairs = jax.tree.map(upd, worker_stacked, master_params)
    else:
        pairs = jax.tree.map(upd, worker_stacked, master_params, master_ref)
    return _unzip_pairs(pairs)


def elastic_update_grouped(worker_stacked, submasters, w1, w2, grp,
                           axis_name=None):
    """Rack-level exchange: every worker syncs against its group's sub-master.

    ``submasters`` leaves carry a leading group axis (G, ...); ``grp`` is the
    static (capacity,) slot→group assignment. Each worker i is pulled toward
    its own sub-master and each sub-master accumulates its members' pushes:

        θ^i   ← θ^i   − w1_i · (θ^i − θ^s_{g(i)})
        θ^s_g ← θ^s_g + Σ_{i : g(i)=g} w2_i · (θ^i − θ^s_{g(i)})

    Pass ``dynamic_weight.master_schedule_weights_grouped(h2, grp)`` as
    ``w2`` so each group's reduction matches a sequential event-ordered scan
    of its own members (groups are independent: worker j in another group
    never discounts worker i's push).

    With ``axis_name`` (sharded placement, inside ``shard_map``): the worker
    leaves/weights hold only this shard's slots; sub-masters are replicated.
    The weighted pushes are all-gathered to the full (capacity, ...) shape
    and every shard performs the *identical* full segment reduction into
    (G, ...) — same shape, same summation tree as the single-device path —
    so sharded sub-masters are bit-exact with single-device ones (the same
    trick ``elastic_update_batched`` uses for the flat master).

    Two segment-reduction paths, picked statically from the topology:

    - **Balanced racks** (capacity divisible by G and ``grp`` is the
      contiguous balanced assignment ``group_assignment`` produces — the
      common case): reshape to (G, k/G, ...), broadcast-subtract the
      sub-master row, reduce over the rack axis. No gather, no scatter —
      this path costs within ~10% of the flat master reduction.
    - **General** (uneven racks): gather each worker's sub-master row and
      segment-sum via a one-hot (G, capacity) matmul. The matmul rather
      than ``.at[grp].add``: XLA's CPU scatter serializes per index and
      measures >2x slower than the equivalent matmul at rack sizes.

    The two paths differ in summation order (last-ulp on sub-masters), but
    the choice is a static function of the topology, so any given config
    is internally consistent — and bit-exact across placements, which is
    the invariant tests/test_hierarchy.py pins.
    """
    w1 = jnp.asarray(w1, jnp.float32)
    w2 = jnp.asarray(w2, jnp.float32)
    grp_np = np.asarray(grp)                 # static topology, never traced
    cap = grp_np.shape[0]
    n_groups = jax.tree.leaves(submasters)[0].shape[0]
    balanced = (cap % n_groups == 0 and np.array_equal(
        grp_np, (np.arange(cap) * n_groups) // cap))
    grp = jnp.asarray(grp_np)
    if axis_name is not None:
        k_local = jax.tree.leaves(worker_stacked)[0].shape[0]
        i0 = jax.lax.axis_index(axis_name) * k_local
        grp_local = jax.lax.dynamic_slice_in_dim(grp, i0, k_local)
    else:
        grp_local = grp

    if balanced and axis_name is None:
        s = cap // n_groups

        def upd(ws, sm):
            h1 = w1.reshape((n_groups, s) + (1,) * (ws.ndim - 1))
            h2 = w2.reshape((n_groups, s) + (1,) * (ws.ndim - 1))
            wf = ws.astype(jnp.float32).reshape(
                (n_groups, s) + ws.shape[1:])
            smf = sm.astype(jnp.float32)
            diff = wf - smf[:, None]
            acc = jnp.sum(h2 * diff, axis=1)
            return ((wf - h1 * diff).reshape(ws.shape).astype(ws.dtype),
                    (smf + acc).astype(sm.dtype))

        return _unzip_pairs(jax.tree.map(upd, worker_stacked, submasters))

    if balanced:
        s = cap // n_groups
        w2_all = jax.lax.all_gather(w2, axis_name, axis=0, tiled=True)

        def upd(ws, sm):
            h1 = w1.reshape((-1,) + (1,) * (ws.ndim - 1))
            h2 = w2_all.reshape((n_groups, s) + (1,) * (ws.ndim - 1))
            wf = ws.astype(jnp.float32)
            smf = sm.astype(jnp.float32)
            diff = wf - jnp.take(smf, grp_local, axis=0)
            new_w = (wf - h1 * diff).astype(ws.dtype)
            # identical values and expression as the single-device branch:
            # gather the diffs (not the pushes, for the reason given in
            # elastic_update_batched), reshape to (G, k/G, ...) and reduce
            diff = jax.lax.all_gather(diff, axis_name, axis=0, tiled=True)
            diff = diff.reshape((n_groups, s) + diff.shape[1:])
            return new_w, (smf + jnp.sum(h2 * diff, axis=1)).astype(sm.dtype)

        return _unzip_pairs(jax.tree.map(upd, worker_stacked, submasters))

    seg = (grp[:, None] == jnp.arange(n_groups)[None, :]).astype(jnp.float32)

    def upd(ws, sm):
        h1 = w1.reshape((-1,) + (1,) * (ws.ndim - 1))
        h2 = w2.reshape((-1,) + (1,) * (ws.ndim - 1))
        wf = ws.astype(jnp.float32)
        smf = sm.astype(jnp.float32)
        diff = wf - jnp.take(smf, grp_local, axis=0)
        push = h2 * diff
        if axis_name is not None:
            push = jax.lax.all_gather(push, axis_name, axis=0, tiled=True)
        acc = (seg.T @ push.reshape(push.shape[0], -1)).reshape(smf.shape)
        return ((wf - h1 * diff).astype(ws.dtype),
                (smf + acc).astype(sm.dtype))

    return _unzip_pairs(jax.tree.map(upd, worker_stacked, submasters))
