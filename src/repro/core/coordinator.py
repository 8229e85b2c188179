"""Asynchronous elastic-averaging coordinator (the paper's system, §V–§VI).

Round inputs travel as one :class:`RoundInputs` pytree (batches, rng, fail,
failed_recent, straggle, restart) instead of a growing positional signature;
``round_step`` runs one round per jit call and ``round_chunk`` runs R rounds
inside a single jit via ``lax.scan`` (inputs carry a leading (R,) axis), so
per-round Python/dispatch overhead is paid once per chunk. The driver that
builds the inputs — batcher, schedule, eval cadence — is
``repro.api.session.ElasticSession``.

One round =

  1. **local phase** — every worker runs τ local optimizer steps on its own
     (overlap-sharded) data: ``vmap`` over the worker axis, ``scan`` over τ.
     With AdaHessian the Hutchinson HVP rides along (EAHES); with
     SGD/Momentum this is EASGD/EAMSGD. Under ``use_pallas`` the AdaHessian
     τ-step is *fused* (ISSUE-7): the gradient and the HVP share one
     linearization and all k workers' moment + parameter updates run as a
     single batched Pallas kernel over flat (k, rows, 128) views
     (``repro.kernels.adahessian``) — one HBM round-trip per τ-step,
     bit-exact with the plain path.
  2. **communication phase** — workers sync with the master: update the
     u-history from the estimated master distance, compute the raw score,
     map through h1/h2 (or fixed α / oracle), and apply the elastic
     exchange — unless this worker's communication is suppressed by the
     failure schedule this round. ``ecfg.comm_mode`` picks the backend:
     ``"sequential"`` scans workers one by one (event-ordered asynchrony,
     matching the paper's single-device simulation); ``"fused"`` batches
     all k syncs into one vmapped scoring pass plus one multi-worker
     elastic update (Pallas kernel on TPU), with event-order-equivalent
     master weights so the two masters agree whenever per-worker h2 do.

Placement (``ecfg.placement``) picks where the k workers live:

- ``"single"`` — all k workers simulated on one device (``vmap`` over the
  worker axis); both comm modes available. This is the paper's setting.
- ``"sharded"`` — the worker axis is partitioned over the mesh's ``'pod'``
  axis via ``shard_map`` (``round_step_sharded`` / ``round_chunk_sharded``):
  each shard runs its k/n_pods workers' local phase fully in parallel and
  scores them locally; cross-shard traffic per round is the fused master
  reduction (an all-gather of k scalars for the event-order schedule
  weights plus one worker-axis all-gather of the weighted pulls, reduced
  with the same (k, ...)-shaped sum as the single-device path — so the
  sharded master is **bit-exact** with single-device fused mode) plus one
  scalar psum for the mean-loss metric. Requires
  ``comm_mode="fused"``: the sequential backend is an event-ordered scan
  where each worker reads the master the previous one wrote, a serial
  dependency that cannot be placed on disjoint shards. Any extra mesh axes
  ('data', 'model') are currently *replicated* inside the sharded round,
  whose ``jax.shard_map`` is fully manual (see ``_round_sharded``). The
  production multi-pod lowering in
  repro/launch/dryrun.py reuses exactly these entry points.

Both placements run the same ``_round`` body; the sharded path threads the
mesh axis name through the local/comm phases, which switch their few
cross-worker reductions (mean loss, master reduction) to collectives.

Elastic membership (ISSUE-5): the worker axis is sized at
``ecfg.cap >= num_workers`` *slots* and an optional per-round ``active``
mask in :class:`RoundInputs` selects the live ones. Inactive slots are
frozen end to end — no local steps, no history push, no elastic exchange,
no loss contribution — so membership (join / leave / resize) can change
between rounds with zero recompiles: every shape is fixed at capacity.
Slots joining this round arrive in the ``join`` mask and are re-seated
from the master (EASGD cold start) exactly like a crash-restart rejoin.
When ``active``/``join`` are ``None`` (a fixed-k run), the traced round is
literally the pre-capacity graph — masking costs nothing and the
all-active path is bit-exact with it by construction (``jnp.where`` /
logical masking with an all-True mask is an elementwise identity).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ElasticConfig, OptimizerConfig
from repro.core import dynamic_weight as dw
from repro.core.elastic import (elastic_update, elastic_update_batched,
                                elastic_update_grouped)
from repro.optim.adahessian import spatial_average
from repro.optim.base import apply_updates, make_optimizer
from repro.optim.hutchinson import hessian_diag, hessian_diag_with_grad


def tree_stack_copies(tree, k: int):
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (k,) + x.shape).copy(),
                        tree)


# Mesh axis hosting the worker shards under sharded placement (the
# production meshes in repro/launch/mesh.py name it the same).
POD_AXIS = "pod"


def padded_capacity(capacity: int, n_pod: int) -> int:
    """Smallest multiple of ``n_pod`` >= ``capacity`` — sharded placement
    partitions the slot axis evenly over the pod axis, so a capacity that
    does not divide is padded up and the extra slots stay permanently
    inactive (uneven-shard masking: shards may hold unequal numbers of
    *live* workers, but equal numbers of slots)."""
    return -(-capacity // n_pod) * n_pod


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RoundInputs:
    """Everything one simulated round consumes, as a single pytree.

    Leaves are per-round (``round_step``) or carry a leading (R,) rounds
    axis (``round_chunk``, which scans over that axis). ``straggle`` and
    ``restart`` stay ``None`` when a scenario never fires them — ``None``
    is an empty subtree, so the jitted round specializes those branches
    away entirely (single trace, no mask traffic). Keep the None-ness
    consistent across calls to avoid retraces.

    All per-worker leaves are sized at the *slot capacity*
    ``ElasticConfig.cap`` (written k below; k == num_workers unless the
    pool is capacity-padded):

    - ``batches``: pytree with (τ, k, ...) leaves (or (R, τ, k, ...))
    - ``rng``: per-round PRNG key (or a stacked (R,) key array)
    - ``fail``: (k,) bool — communication suppressed this round
    - ``failed_recent``: (k,) bool — oracle feed, see
      ``ScenarioSchedule.failed_recent``
    - ``straggle``: optional (k,) bool — reduced-τ slow workers
    - ``restart``: optional (k,) bool — crash-rejoin resets
    - ``active``: optional (k,) bool — live-membership mask; ``None``
      means every slot is live (the fixed-k fast path). Inactive slots
      freeze entirely: no local steps, no sync, no history, no loss.
    - ``join``: optional (k,) bool — slots (re)joining the pool this
      round; their params are re-seated from the master before the local
      phase (same cold-start op as a crash-restart rejoin).
    - ``corrupt``: optional (k,) bool — byzantine slots (ISSUE-9): their
      gradients are adversarially corrupted every local τ-step
      (``ElasticConfig.byzantine_mode``). They still sync — a poisoned
      node does not announce itself.
    - ``speed``: optional (k,) float32 in (0, 1] — persistent per-slot
      speeds (ISSUE-9): slot i completes ``max(1, round(speed·τ))`` local
      steps this round. Unlike ``straggle`` this does not stale the
      worker's score against ``master_prev``.
    """

    batches: Any
    rng: jax.Array
    fail: jax.Array
    failed_recent: jax.Array
    straggle: Optional[jax.Array] = None
    restart: Optional[jax.Array] = None
    active: Optional[jax.Array] = None
    join: Optional[jax.Array] = None
    corrupt: Optional[jax.Array] = None
    speed: Optional[jax.Array] = None


@dataclasses.dataclass(eq=False)  # hash by id → usable as a static jit arg
class ElasticTrainer:
    model: Any
    opt_cfg: OptimizerConfig
    ecfg: ElasticConfig
    use_pallas: bool = False
    # sharded placement only: mesh whose 'pod' axis hosts the worker shards
    mesh: Any = None
    # Fused local phase (ISSUE-7): one batched multi-worker AdaHessian
    # update per τ-step instead of vmapping the per-worker optimizer, with
    # the gradient and the Hutchinson HVP sharing one linearization. None
    # (default) follows ``use_pallas``; an explicit bool decouples the
    # fused *structure* from the Pallas kernel (the local-phase benchmark
    # measures the jnp-fused variant this way). AdaHessian-only — other
    # optimizers fall back to the plain path.
    fused_local: Any = None
    # Hierarchical averaging (ISSUE-10): None (default) follows
    # ``ecfg.hierarchical`` (groups > 1 or global_period > 1); an explicit
    # True forces the hierarchical state/comm structure even at the trivial
    # groups=1, global_period=1 topology, where it collapses to the flat
    # fused phase bit-for-bit (the degenerate-equivalence proof in
    # tests/test_hierarchy.py runs exactly this).
    hierarchical: Any = None

    def __post_init__(self):
        self.opt = make_optimizer(self.opt_cfg)
        # times a round program (any of the four round entry points) was
        # traced: their Python bodies run only when JAX traces them, so a
        # rise between two calls means the second one lowered again
        self.traced = 0
        self._fused_local = (
            (self.use_pallas if self.fused_local is None
             else bool(self.fused_local))
            and self.opt_cfg.name == "adahessian")
        self._hier = (self.ecfg.hierarchical if self.hierarchical is None
                      else bool(self.hierarchical))
        if self._hier:
            if self.ecfg.comm_mode != "fused":
                raise ValueError(
                    "hierarchical averaging needs comm_mode='fused' (the "
                    "sequential scan has no grouped equivalent)")
            if self.ecfg.staleness:
                raise ValueError(
                    "hierarchical averaging is incompatible with "
                    "staleness=1 (workers sync against sub-masters; there "
                    "is no stale sub-master snapshot)")
            # static slot→group map; group count after clamping to capacity
            self._grp = dw.group_assignment(self.ecfg.cap, self.ecfg.groups)
            self._n_groups = int(self._grp.max()) + 1
        if self.ecfg.placement == "sharded":
            if self.mesh is None:
                raise ValueError(
                    "placement='sharded' needs a mesh with a 'pod' axis "
                    "(see repro.launch.mesh.make_host_mesh)")
            if POD_AXIS not in self.mesh.shape:
                raise ValueError(
                    f"sharded placement needs a {POD_AXIS!r} mesh axis, "
                    f"mesh has {tuple(self.mesh.shape)}")
            n_pod = self.mesh.shape[POD_AXIS]
            if self.ecfg.cap % n_pod:
                raise ValueError(
                    f"worker capacity={self.ecfg.cap} must divide evenly "
                    f"over the {n_pod}-way {POD_AXIS!r} mesh axis (pad it "
                    f"with coordinator.padded_capacity and leave the extra "
                    f"slots inactive)")

    # -- state ----------------------------------------------------------------
    def init_state(self, rng: jax.Array, params=None):
        """All worker-axis entries are sized at ``ecfg.cap`` slots; slots
        beyond the initial membership hold master copies until a join
        re-seats them (they are frozen by the active mask regardless)."""
        from repro.nn.param import init_tree

        k = self.ecfg.cap
        if params is None:
            params = init_tree(rng, self.model.spec)
        master = jax.tree.map(lambda x: x.astype(jnp.float32), params)
        worker_params = tree_stack_copies(params, k)
        worker_opt = jax.vmap(self.opt.init)(worker_params)
        state = {
            "workers": worker_params,
            "opt": worker_opt,
            "master": master,
            # previous-round master snapshot: the stale estimate straggling
            # workers score against (scenario engine, repro/core/scenarios.py).
            # A distinct buffer, not an alias of "master": round_step donates
            # the state, and donation rejects the same buffer appearing twice.
            "master_prev": jax.tree.map(jnp.copy, master),
            "u_hist": jnp.full((k, self.ecfg.score_window), -30.0,
                               jnp.float32),
            "round": jnp.zeros((), jnp.int32),
        }
        if self._hier:
            # one sub-master per rack, seeded from the master like workers;
            # rack-level distance history mirrors the worker u_hist shape
            state["submasters"] = tree_stack_copies(master, self._n_groups)
            state["g_u_hist"] = jnp.full(
                (self._n_groups, self.ecfg.score_window), -30.0, jnp.float32)
        return state

    # -- failure-scenario state transitions --------------------------------------
    def apply_restarts(self, state, restart):
        """Crash-restart rejoin (scenario ``crash_restart``): workers with
        ``restart[i]`` True have their params reset to the master. The
        u-history is deliberately kept — the recorded pre-crash drift makes
        the next score see the distance collapse, driving the recovery path
        h1→1 / h2→0 (§V-B).

        Optimizer accumulators are restored rather than re-initialized
        (restore-from-checkpoint semantics): a cold AdaHessian state takes
        violently large first steps from the master position, and the h2 map
        gives runaway workers the full α for any positive score, so a fresh
        init lets a single rejoin corrupt the master.
        """

        def sel(new, old):
            r = restart.reshape((-1,) + (1,) * (new.ndim - 1))
            return jnp.where(r, new, old)

        workers = jax.tree.map(
            lambda w, m: sel(jnp.broadcast_to(m.astype(w.dtype), w.shape), w),
            state["workers"], state["master"])
        return dict(state, workers=workers)

    # -- byzantine gradient corruption (ISSUE-9) ---------------------------------
    def _poison(self, grads, rng):
        """The adversarial gradient a byzantine worker reports, per
        ``ecfg.byzantine_mode`` (static — the trace only ever contains one
        mode's ops): ``sign_flip`` ascends the loss, ``scale`` overshoots
        by ``byzantine_scale``×, ``noise`` adds N(0, byzantine_scale²) per
        element. Noise keys are folded from the worker's step key, so the
        honest PRNG stream is untouched."""
        mode, c = self.ecfg.byzantine_mode, self.ecfg.byzantine_scale
        if mode == "sign_flip":
            return jax.tree.map(jnp.negative, grads)
        if mode == "scale":
            return jax.tree.map(lambda g: (c * g).astype(g.dtype), grads)
        leaves, treedef = jax.tree.flatten(grads)
        keys = jax.random.split(jax.random.fold_in(rng, 0x6B7A), len(leaves))
        return jax.tree.unflatten(treedef, [
            g + c * jax.random.normal(kk, g.shape, g.dtype)
            for g, kk in zip(leaves, keys)])

    def _corrupt_grads(self, grads, corrupt_i, rng):
        """One worker's gradients with the byzantine corruption selected in
        where ``corrupt_i`` (scalar bool) is True. Only the gradient
        channel is attacked; the Hutchinson curvature estimate rides
        through untouched (AdaHessian preconditions by |diag|, which
        sign_flip would not change anyway — the gradient is the attack
        surface that reaches the master)."""
        bad = self._poison(grads, rng)
        return jax.tree.map(lambda b, g: jnp.where(corrupt_i, b, g),
                            bad, grads)

    # -- local phase ------------------------------------------------------------
    def _one_step(self, params, opt_state, batch, rng, corrupt_i=None):
        loss_fn = lambda p: self.model.loss(p, batch)[0]
        loss, grads = jax.value_and_grad(loss_fn)(params)
        if corrupt_i is not None:
            grads = self._corrupt_grads(grads, corrupt_i, rng)
        extras = None
        if self.opt.needs_hessian:
            extras = {
                "hess_diag": hessian_diag(
                    jax.grad(loss_fn), params, rng,
                    self.opt_cfg.hutchinson_samples)
            }
        updates, opt_state = self.opt.update(grads, opt_state, params, extras)
        params = apply_updates(params, updates)
        return params, opt_state, loss

    def _grads_one(self, params, batch, rng):
        """Front half of ``_one_step`` for the fused local phase: loss,
        gradient and *spatially averaged* Hutchinson diagonal for one
        worker. The gradient and the HVP probes share one linearization
        (``hessian_diag_with_grad``) instead of ``value_and_grad`` plus a
        fresh ``jvp`` — same bits, one less backward derivation. Spatial
        averaging happens here, per worker, because a stacked scalar leaf
        would otherwise average across the worker axis."""
        loss_fn = lambda p: self.model.loss(p, batch)[0]
        loss = loss_fn(params)
        grads, diag = hessian_diag_with_grad(
            jax.grad(loss_fn), params, rng, self.opt_cfg.hutchinson_samples)
        hs = jax.tree.map(
            lambda h: spatial_average(h, self.opt_cfg.spatial_block), diag)
        return loss, grads, hs

    def _fused_local_step(self, params, opt_state, batch, rngs, axis,
                          corrupt=None):
        """One τ-step for all k workers with the update batched (ISSUE-7):
        per-worker gradients + averaged Hessian diagonals, then a single
        multi-worker AdaHessian step over the stacked trees — the Pallas
        kernel on the single-device path (interpret mode on CPU), the
        bitwise-identical vmapped jnp expression per shard under sharded
        placement (mirroring the elastic comm kernel's gating)."""
        from repro.kernels import interpret_mode
        from repro.kernels.adahessian.ops import adahessian_update_batched

        loss, grads, hs = jax.vmap(self._grads_one)(params, batch, rngs)
        if corrupt is not None:
            # per-worker corruption on the stacked gradient trees, same
            # semantics as the plain path's in-step corruption
            grads = jax.vmap(self._corrupt_grads)(grads, corrupt, rngs)
        new_p, new_o = adahessian_update_batched(
            params, grads, hs, opt_state, self.opt_cfg,
            use_kernel=self.use_pallas and axis is None,
            interpret=interpret_mode())
        return new_p, new_o, loss

    def local_phase(self, state, batches, rng, straggle=None, active=None,
                    axis=None, corrupt=None, speed=None):
        """batches: pytree with leading (τ, k, ...) axes (k = slot capacity).

        ``straggle``: optional (k,) bool — straggling workers are slow, not
        dead: they complete only the first
        ``max(1, round(straggler_tau_scale·τ))`` local steps; params and
        optimizer state freeze for the rest of the phase.

        ``corrupt``: optional (k,) bool — byzantine slots: every local
        τ-step their gradients are replaced by the adversarial variant
        (``_corrupt_grads``). Applied on both the plain and fused local
        paths; ``None`` keeps the corruption-free trace bit-identical
        (the branch is specialized away, tests/test_adversarial.py).

        ``speed``: optional (k,) float32 in (0, 1] — persistent per-slot
        speeds: slot i runs ``max(1, round(speed·τ))`` steps and freezes
        for the rest of the phase, composing with (not replacing) the
        transient straggler mask. Distinct semantics: a straggler also
        scores against a stale master, a slow-but-healthy node does not.

        ``active``: optional (k,) bool — live-membership mask. Inactive
        slots freeze for the whole phase (params/optimizer unchanged) and
        contribute neither loss nor active-count to the mean-loss metric,
        so the metric averages over the live pool only.

        ``axis``: mesh axis name when running inside ``shard_map`` (sharded
        placement). The worker axis of every input then holds only this
        shard's k/n_pods workers; each worker's τ steps are computed exactly
        as in single placement (the per-worker PRNG keys are split from the
        global key and sliced by shard, so worker i sees identical keys
        under either placement) and the only collective is one scalar psum
        of the loss/active-count totals *after* the τ-step scan — the τ
        local steps themselves run with zero cross-shard traffic. (This
        re-associates the mean-loss reduction, which is why that metric —
        and only that metric — is last-ulp-tolerant across placements.)
        """
        k = self.ecfg.cap
        tau = jax.tree.leaves(batches)[0].shape[0]
        k_loc = jax.tree.leaves(batches)[0].shape[1]
        tau_eff = max(1, round(self.ecfg.straggler_tau_scale * tau))
        # persistent heterogeneity: per-slot step budget for this round
        # (computed once — speed is constant across the τ scan)
        speed_steps = (None if speed is None else
                       jnp.maximum(1, jnp.round(speed * tau))
                       .astype(jnp.int32))

        def tau_step(carry, inp):
            params, opt_state = carry
            batch_t, rng_t, t = inp
            rngs = jax.random.split(rng_t, k)
            if axis is not None:
                i0 = jax.lax.axis_index(axis) * k_loc
                rngs = jax.lax.dynamic_slice_in_dim(rngs, i0, k_loc)
            if self._fused_local:
                new_p, new_o, loss = self._fused_local_step(
                    params, opt_state, batch_t, rngs, axis,
                    corrupt=corrupt)
            elif corrupt is not None:
                new_p, new_o, loss = jax.vmap(self._one_step)(
                    params, opt_state, batch_t, rngs, corrupt)
            else:
                new_p, new_o, loss = jax.vmap(self._one_step)(
                    params, opt_state, batch_t, rngs)
            # frozen steps (slow stragglers past their reduced τ, slots past
            # their heterogeneous speed budget, inactive slots) contribute
            # neither updates nor loss metrics
            live = None
            if straggle is not None:
                live = jnp.logical_or(~straggle, t < tau_eff)
            if speed_steps is not None:
                live_sp = t < speed_steps
                live = live_sp if live is None else jnp.logical_and(live,
                                                                    live_sp)
            if active is not None:
                live = active if live is None else jnp.logical_and(live,
                                                                   active)
            if live is not None:
                sel = lambda n, o: jnp.where(
                    live.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)
                new_p = jax.tree.map(sel, new_p, params)
                new_o = jax.tree.map(sel, new_o, opt_state)
                loss = jnp.where(live, loss, 0.0)
                active_f = live
            else:
                active_f = jnp.ones_like(loss, bool)
            return ((new_p, new_o),
                    (jnp.sum(loss), jnp.sum(active_f), loss, active_f))

        rngs = jax.random.split(rng, tau)
        (workers, opt_state), (losses, counts, loss_steps, live_steps) = (
            jax.lax.scan(tau_step, (state["workers"], state["opt"]),
                         (batches, rngs, jnp.arange(tau))))
        sum_loss, n_active = jnp.sum(losses), jnp.sum(counts)
        if axis is not None:
            # one collective for the whole phase: metric totals only
            sum_loss, n_active = jax.lax.psum((sum_loss, n_active), axis)
        mean_loss = sum_loss / jnp.maximum(n_active, 1)
        # per-slot mean loss over each slot's *live* steps (frozen straggler
        # tails and vacancies excluded) — the controller's progress signal.
        # Slot-local, so it needs no collective under sharded placement.
        # The scalar mean-loss reduction above is kept verbatim: loss_w is
        # an additional scan output, not a re-association of that metric.
        loss_w = (jnp.sum(loss_steps, axis=0)
                  / jnp.maximum(jnp.sum(live_steps, axis=0), 1))
        return dict(state, workers=workers, opt=opt_state), mean_loss, loss_w

    # -- communication phase -----------------------------------------------------
    def comm_phase(self, state, fail_mask, failed_recent=None, straggle=None,
                   active=None, axis=None):
        """fail_mask: (k,) bool — True suppresses this worker's sync.

        ``straggle``: optional (k,) bool — straggling workers score against
        the *previous* round's master snapshot (their estimate of the master
        is stale; the elastic exchange itself still uses the live master,
        which the parameter server holds).

        ``active``: optional (k,) bool — live-membership mask. An inactive
        slot is a vacancy, not a failure: it performs no elastic exchange
        *and* its u-history stays frozen (a failed worker keeps training
        locally and keeps scoring; a vacant slot has no worker at all). In
        the sequential scan it is a no-op on the master, so the event order
        of the live workers is identical to a pool that never had the slot.

        Dispatches on ``ecfg.comm_mode``: "sequential" is the paper's
        event-ordered scan; "fused" batches all k syncs into one scoring
        pass plus one multi-worker elastic update. ``axis`` (sharded
        placement) is fused-only — the sequential scan's serial master
        dependency cannot shard.
        """
        ecfg = self.ecfg
        if failed_recent is None:
            failed_recent = jnp.zeros_like(fail_mask)
        if ecfg.comm_mode == "fused":
            if self._hier:
                return self._comm_phase_hier(state, fail_mask, failed_recent,
                                             straggle, active, axis)
            return self._comm_phase_fused(state, fail_mask, failed_recent,
                                          straggle, active, axis)
        if axis is not None:  # unreachable: ElasticConfig validates this
            raise ValueError("sequential comm cannot run sharded")
        stale_master = state.get("master_prev", state["master"])
        straggle_in = (jnp.zeros_like(fail_mask) if straggle is None
                       else straggle)
        active_in = (jnp.ones_like(fail_mask) if active is None
                     else active)

        def sync_one(master, xs):
            w_i, hist_i, fail_i, fr_i, st_i, act_i = xs
            # u from the estimated master (other-worker estimate ≈ current
            # master in the event-ordered simulation)
            u_t = dw.log_distance(w_i, master)
            if straggle is not None:
                u_t = jnp.where(st_i, dw.log_distance(w_i, stale_master),
                                u_t)
            if ecfg.score_clip > 0:
                # quarantine (ISSUE-9): a worker whose distance left
                # float32 range (diverged byzantine slot) is re-seated to
                # the master here, so the refused exchange below never
                # computes 0·inf and the u-history stays finite. The
                # pushed u is exactly log_distance(master, master); the
                # resulting huge positive score keeps the slot refused
                # while it stays suspicious.
                quar = ~jnp.isfinite(u_t)
                w_i = jax.tree.map(
                    lambda w, m: jnp.where(quar, m.astype(w.dtype), w),
                    w_i, master)
                u_t = jnp.where(quar, jnp.log(jnp.float32(1e-30)), u_t)
            hist_new = dw.push_history(hist_i, u_t)
            if active is not None:
                hist_new = jnp.where(act_i, hist_new, hist_i)
            a = dw.raw_score(hist_new, ecfg.score_weights)
            w1, w2 = dw.weights_for(ecfg, a, failed_recently=fr_i)
            # suppressed communication (failure or vacancy): no exchange
            dead_i = (fail_i if active is None
                      else jnp.logical_or(fail_i, ~act_i))
            w1 = jnp.where(dead_i, 0.0, w1)
            w2 = jnp.where(dead_i, 0.0, w2)
            if self.use_pallas:
                from repro.kernels import interpret_mode
                from repro.kernels.elastic.ops import elastic_update_pallas

                new_w, new_master = elastic_update_pallas(
                    w_i, master, w1, w2, interpret=interpret_mode())
            else:
                new_w, new_master = elastic_update(w_i, master, w1, w2)
            if active is not None:  # vacant slots report zeroed diagnostics
                u_t = jnp.where(act_i, u_t, 0.0)
                a = jnp.where(act_i, a, 0.0)
            return new_master, (new_w, hist_new, (u_t, a, w1, w2))

        master, (workers, hist, diag) = jax.lax.scan(
            sync_one, state["master"],
            (state["workers"], state["u_hist"], fail_mask, failed_recent,
             straggle_in, active_in))
        u, a, w1, w2 = diag
        metrics = {"u": u, "score": a, "h1": w1, "h2": w2}
        return dict(state, workers=workers, master=master,
                    master_prev=state["master"], u_hist=hist,
                    round=state["round"] + 1), metrics

    def _comm_phase_fused(self, state, fail_mask, failed_recent,
                          straggle=None, active=None, axis=None):
        """Batched communication: one vmapped scoring pass over all k
        workers, then a single multi-worker elastic update.

        Workers sync against the round-start master (delayed averaging);
        the master reduction uses the event-order-equivalent weights
        g_i = h2_i·Π_{j>i}(1−h2_j), so the resulting master matches the
        sequential scan exactly whenever the per-worker h2 agree (e.g. the
        fixed-α and oracle modes). Scores are computed against the same
        round-start master, which drops the scan's serial dependency.

        ``ecfg.staleness = 1`` deepens the delay by one round (DaSGD):
        scoring *and* the elastic diffs use the previous round's master
        snapshot (``master_prev``), with the weighted pulls still
        accumulated onto the live master. Straggler stale scoring
        coincides with the ordinary scoring in that mode (both read
        ``master_prev``).

        ``axis`` (sharded placement): scoring runs on this shard's local
        workers against the replicated master; the schedule weighting
        all-gathers the k h2 scalars and the elastic update all-gathers the
        weighted pulls for a reduction bit-exact with the single-device
        path. The Pallas kernel covers the single-device fused path only —
        per-shard the update is the plain jnp expression, which XLA fuses
        fine at k/n_pods workers per device.
        """
        ecfg = self.ecfg
        master = state["master"]
        # Delayed averaging (ElasticConfig.staleness, DaSGD): score and
        # pull toward the previous round's master snapshot instead of the
        # round-start master, so this round's exchange depends only on
        # state known before the previous reduction landed (comm of round
        # r can overlap local of round r+1). With staleness=0 ``ref`` is
        # the master itself and every expression below is unchanged.
        ref = (state.get("master_prev", master) if ecfg.staleness
               else master)
        workers_in = state["workers"]
        if ecfg.score_clip > 0:
            # quarantine (ISSUE-9), mirroring the sequential scan: a
            # worker whose log-distance left float32 range is re-seated to
            # the scoring reference before anything else reads it, so the
            # refused master reduction never multiplies 0·inf and the
            # history push (inside comm_scores_batched, which re-measures
            # the sanitized workers) records the exact re-seat distance.
            u0 = dw.log_distance_batched(workers_in, ref)
            quar = ~jnp.isfinite(u0)
            workers_in = jax.tree.map(
                lambda w, m: jnp.where(
                    quar.reshape((-1,) + (1,) * (w.ndim - 1)),
                    m.astype(w.dtype)[None], w),
                workers_in, ref)
        u, hist, a, w1, w2 = dw.comm_scores_batched(
            ecfg, workers_in, ref, state["u_hist"],
            failed_recently=failed_recent,
            stale_master=(None if straggle is None
                          else state.get("master_prev", master)),
            straggle=straggle, active=active, axis_name=axis)
        # suppressed communication: no elastic exchange at all. A vacant
        # (inactive) slot additionally freezes its u-history and zeroes its
        # diagnostics — it contributes g_i = 0 to the master reduction,
        # exactly like the sequential scan skipping it.
        dead = (fail_mask if active is None
                else jnp.logical_or(fail_mask, ~active))
        w1 = jnp.where(dead, 0.0, w1)
        w2 = jnp.where(dead, 0.0, w2)
        if active is not None:
            hist = jnp.where(active[:, None], hist, state["u_hist"])
            u = jnp.where(active, u, 0.0)
            a = jnp.where(active, a, 0.0)
        g2 = dw.master_schedule_weights(w2, axis_name=axis)
        master_ref = ref if ecfg.staleness else None
        # workers_in == state["workers"] unless the score_clip quarantine
        # re-seated a diverged slot above
        if self.use_pallas and axis is None:
            from repro.kernels import interpret_mode
            from repro.kernels.elastic.ops import elastic_update_batched_pallas

            workers, master = elastic_update_batched_pallas(
                workers_in, master, w1, g2, master_ref=master_ref,
                interpret=interpret_mode())
        else:
            workers, master = elastic_update_batched(
                workers_in, master, w1, g2, axis_name=axis,
                master_ref=master_ref)
        metrics = {"u": u, "score": a, "h1": w1, "h2": w2}
        return dict(state, workers=workers, master=master,
                    master_prev=state["master"], u_hist=hist,
                    round=state["round"] + 1), metrics

    def _comm_phase_hier(self, state, fail_mask, failed_recent,
                         straggle=None, active=None, axis=None):
        """Two-level hierarchical communication (ISSUE-10, tree-EASGD).

        **Rack level, every round**: each worker scores and elastic-averages
        against its group's *sub-master* — the same batched scoring +
        event-order-equivalent reduction as the flat fused phase, with the
        schedule weights grouped (``master_schedule_weights_grouped``) so
        every sub-master matches a per-rack sequential scan. The (G, ...)
        sub-master trees are replicated under sharded placement; the
        grouped reduction all-gathers the weighted pushes and performs the
        identical full scatter-add on every shard, so sub-masters stay
        bit-exact across placements (see ``elastic_update_grouped``).

        **Global level, every** ``global_period`` **rounds**: sub-masters
        play the worker role against the global master — their own
        u-history (``g_u_hist``), raw scores and dynamic h1/h2, the same
        event-order weights, one ``elastic_update_batched``. A rack with no
        syncing member this round (all failed/vacant — e.g. a correlated
        rack outage) is down-weighted exactly like a dead worker at rack
        level: gw1 = gw2 = 0, no exchange, while a merely *dark* history
        still records the drift. A fully vacant rack freezes its history
        and zeroes its diagnostics, like a vacant slot. Off-cycle rounds
        skip the global phase entirely under ``lax.cond`` — no comparison,
        no distance computation, no master traffic — which is the
        per-round comm saving the hierarchy buys (benchmarks/run.py
        ``--what hierarchy``). Everything the global phase reads is
        replicated or all-gathered, so it runs identically on every shard
        with zero collectives of parameter size.

        **Degenerate topology** (groups=1 and global_period=1): statically
        collapses to the flat fused phase — the master trajectory is
        bit-exact with ``_comm_phase_fused`` by construction — and the
        single sub-master mirrors the new master (a global sync through a
        lone all-member rack is the flat exchange twice over; mirroring
        keeps the checkpointable hierarchical state consistent without
        perturbing the proof trajectory).

        Stragglers score against their live sub-master (no stale-snapshot
        variant at rack granularity — there is no ``submaster_prev``);
        ``staleness=1`` is rejected at construction.
        """
        ecfg = self.ecfg
        G = self._n_groups
        if G == 1 and ecfg.global_period == 1:
            new_state, metrics = self._comm_phase_fused(
                state, fail_mask, failed_recent, straggle, active, axis)
            new_state["submasters"] = jax.tree.map(
                lambda m: m[None], new_state["master"])
            z = jnp.zeros((1,), jnp.float32)
            metrics.update(g_u=z, g_score=z, g_h1=z, g_h2=z)
            return new_state, metrics

        master = state["master"]
        submasters = state["submasters"]
        grp = jnp.asarray(self._grp)
        if axis is not None:
            k_loc = fail_mask.shape[0]
            i0 = jax.lax.axis_index(axis) * k_loc
            grp_local = jax.lax.dynamic_slice_in_dim(grp, i0, k_loc)
        else:
            grp_local = grp
        # each worker's reference: its rack's sub-master row
        sub_ref = jax.tree.map(lambda sm: jnp.take(sm, grp_local, axis=0),
                               submasters)

        workers_in = state["workers"]
        u = dw.log_distance_batched_ref(workers_in, sub_ref)
        if ecfg.score_clip > 0:
            # quarantine (ISSUE-9), as in the flat fused phase, but the
            # re-seat target is the worker's sub-master; the recorded u is
            # exactly log_distance(sub_ref, sub_ref)
            quar = ~jnp.isfinite(u)
            workers_in = jax.tree.map(
                lambda w, r: jnp.where(
                    quar.reshape((-1,) + (1,) * (w.ndim - 1)),
                    r.astype(w.dtype), w),
                workers_in, sub_ref)
            u = jnp.where(quar, jnp.log(jnp.float32(1e-30)), u)
        hist = dw.push_history(state["u_hist"], u)
        a = dw.raw_score(hist, ecfg.score_weights)
        w1, w2 = dw.weights_for(ecfg, a, failed_recently=failed_recent,
                                u=u, live=active, axis_name=axis)
        dead = (fail_mask if active is None
                else jnp.logical_or(fail_mask, ~active))
        w1 = jnp.where(dead, 0.0, w1)
        w2 = jnp.where(dead, 0.0, w2)
        if active is not None:
            hist = jnp.where(active[:, None], hist, state["u_hist"])
            u = jnp.where(active, u, 0.0)
            a = jnp.where(active, a, 0.0)

        # grouped event-order weights couple workers within a rack only,
        # but a shard may hold a rack fragment — compute on the full (k,)
        # h2 vector, identically on every shard, and slice back
        if axis is not None:
            w2_full = jax.lax.all_gather(w2, axis, axis=0, tiled=True)
            g2 = jax.lax.dynamic_slice_in_dim(
                dw.master_schedule_weights_grouped(w2_full, grp), i0, k_loc)
        else:
            g2 = dw.master_schedule_weights_grouped(w2, grp)
        workers, submasters = elastic_update_grouped(
            workers_in, submasters, w1, g2, self._grp, axis_name=axis)

        # rack liveness, from the full masks (replicated across shards)
        gather = (lambda x: x) if axis is None else (
            lambda x: jax.lax.all_gather(x, axis, axis=0, tiled=True))
        as_i32 = lambda b: b.astype(jnp.int32)
        seg_any = lambda b: (jnp.zeros((G,), jnp.int32)
                             .at[grp].max(as_i32(b))) > 0
        g_synced = seg_any(~gather(dead))   # some member exchanged
        g_live = (jnp.ones((G,), bool) if active is None
                  else seg_any(gather(active)))
        g_fr = seg_any(gather(failed_recent))

        round_new = state["round"] + 1

        @jax.named_scope("global_sync")
        def global_sync(args):
            subs, mast, g_hist = args
            g_u = dw.log_distance_batched(subs, mast)
            g_hist_new = dw.push_history(g_hist, g_u)
            g_hist_new = jnp.where(g_live[:, None], g_hist_new, g_hist)
            g_a = dw.raw_score(g_hist_new, ecfg.score_weights)
            gw1, gw2 = dw.weights_for(ecfg, g_a, failed_recently=g_fr,
                                      u=g_u, live=g_live)
            g_dead = ~g_synced
            gw1 = jnp.where(g_dead, 0.0, gw1)
            gw2 = jnp.where(g_dead, 0.0, gw2)
            gg2 = dw.master_schedule_weights(gw2)
            subs2, mast2 = elastic_update_batched(subs, mast, gw1, gg2)
            g_u = jnp.where(g_live, g_u, 0.0)
            g_a = jnp.where(g_live, g_a, 0.0)
            return subs2, mast2, g_hist_new, (g_u, g_a, gw1, gw2)

        def global_skip(args):
            subs, mast, g_hist = args
            z = jnp.zeros((G,), jnp.float32)
            return subs, mast, g_hist, (z, z, z, z)

        submasters, master, g_hist, (g_u, g_a, gw1, gw2) = jax.lax.cond(
            (round_new % ecfg.global_period) == 0, global_sync, global_skip,
            (submasters, master, state["g_u_hist"]))

        metrics = {"u": u, "score": a, "h1": w1, "h2": w2,
                   "g_u": g_u, "g_score": g_a, "g_h1": gw1, "g_h2": gw2}
        return dict(state, workers=workers, master=master,
                    master_prev=state["master"], u_hist=hist,
                    submasters=submasters, g_u_hist=g_hist,
                    round=round_new), metrics

    # -- full round ---------------------------------------------------------------
    def _round(self, state, inputs: RoundInputs, axis=None):
        """One simulated round under a failure scenario: optional crash
        rejoins and membership joins (both re-seat params from the master),
        the local phase (with per-worker straggler slowdown and the
        live-membership mask), then the communication phase under the fail
        mask. ``axis`` names the worker-hosting mesh axis inside
        ``shard_map`` (sharded placement); ``apply_restarts`` is per-worker
        against the replicated master, so it needs no axis awareness.

        The three steps run under the named scopes ``reseat``,
        ``local_phase`` and ``comm_phase`` (``global_sync`` inside the
        hierarchical exchange), which the compiled program carries in its
        operations' metadata, so a device trace attributes each operation
        to its phase."""
        reseat = inputs.restart
        if inputs.join is not None:
            # a joining slot cold-starts from the master, EASGD-style —
            # the same re-seat op as a crash-restart rejoin
            reseat = (inputs.join if reseat is None
                      else jnp.logical_or(reseat, inputs.join))
        if reseat is not None:
            with jax.named_scope("reseat"):
                state = self.apply_restarts(state, reseat)
        with jax.named_scope("local_phase"):
            state, loss, loss_w = self.local_phase(
                state, inputs.batches, inputs.rng, inputs.straggle,
                inputs.active, axis=axis, corrupt=inputs.corrupt,
                speed=inputs.speed)
        with jax.named_scope("comm_phase"):
            state, metrics = self.comm_phase(state, inputs.fail,
                                             inputs.failed_recent,
                                             inputs.straggle, inputs.active,
                                             axis=axis)
        metrics["loss"] = loss
        metrics["loss_w"] = loss_w
        return state, metrics

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def round_step(self, state, inputs: RoundInputs):
        """One round per jit call; ``inputs`` leaves are per-round.

        ``state`` is donated: the output state reuses the input buffers, so
        a run holds one copy of the (k × params)-sized worker state instead
        of double-buffering it across calls. Don't reuse a state object
        after passing it in — keep the returned one.
        """
        self.traced += 1
        return self._round(state, inputs)

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def round_chunk(self, state, inputs: RoundInputs):
        """R rounds in one jit call: every ``inputs`` leaf carries a leading
        (R,) axis and ``lax.scan`` threads the state through the rounds, so
        the Python/dispatch cost of a round is paid once per chunk. The
        scanned body is exactly ``round_step``'s, so a chunked run is
        bit-identical to R separate ``round_step`` calls; metrics come back
        stacked with a leading (R,) axis. ``state`` is donated, as in
        ``round_step``."""
        self.traced += 1
        return jax.lax.scan(self._round, state, inputs)

    # -- sharded placement entry points -------------------------------------------
    def state_shard_specs(self):
        """Per-entry partition specs of the trainer state under sharded
        placement: worker-axis entries split over 'pod', master and
        counters replicated. The single source of truth for both the
        shard_map in/out specs (``_shard_specs``) and the session's
        device-resident state layout (``ElasticSession._place_state``) —
        a new state entry added here is placed consistently everywhere.
        """
        from jax.sharding import PartitionSpec as P

        wrk, rep = P(POD_AXIS), P()
        specs = {"workers": wrk, "opt": wrk, "master": rep,
                 "master_prev": rep, "u_hist": wrk, "round": rep}
        if self._hier:
            # sub-masters and their history replicate like the master: the
            # grouped reduction rebuilds them identically on every shard
            specs["submasters"] = rep
            specs["g_u_hist"] = rep
        return specs

    def _shard_specs(self, inputs: RoundInputs, chunk: bool):
        """``shard_map`` partition specs for (state, inputs, metrics).

        Worker-axis leaves split over the 'pod' axis; the master, the PRNG
        keys and the round counter replicate. Specs are pytree prefixes, so
        ``None`` scenario fields (straggle/restart) mirror the input's
        Noneness and keep the specialized trace. ``chunk`` prepends the
        (R,) rounds axis, which is never sharded.
        """
        from jax.sharding import PartitionSpec as P

        lead = (None,) if chunk else ()
        wrk = P(*lead, POD_AXIS)
        rep = P()
        state_spec = self.state_shard_specs()
        mask = lambda x: None if x is None else wrk
        in_spec = RoundInputs(
            batches=P(*lead, None, POD_AXIS),  # (R?, τ, k, ...)
            rng=rep,
            fail=wrk, failed_recent=mask(inputs.failed_recent),
            straggle=mask(inputs.straggle), restart=mask(inputs.restart),
            active=mask(inputs.active), join=mask(inputs.join),
            corrupt=mask(inputs.corrupt), speed=mask(inputs.speed))
        met_spec = {"u": wrk, "score": wrk, "h1": wrk, "h2": wrk,
                    "loss": rep, "loss_w": wrk}
        if self._hier:
            # rack-level diagnostics are (G,)-replicated, like the master
            met_spec.update(g_u=rep, g_score=rep, g_h1=rep, g_h2=rep)
        return state_spec, in_spec, met_spec

    def _round_sharded(self, state, inputs: RoundInputs, chunk: bool):
        """Shared body of the sharded jits: ``shard_map`` the round (or the
        R-round scan) over the mesh, fully manual. Specs mention only the
        'pod' axis, so any 'data'/'model' axes replicate the per-worker
        computation — exactly equivalent on the size-1 host-mesh axes.
        The partial-manual form (``axis_names=``), which would let GSPMD
        shard each worker's model *within* its pod, has not been tried on
        the installed jax 0.9.0."""
        state_spec, in_spec, met_spec = self._shard_specs(inputs, chunk)
        step = functools.partial(self._round, axis=POD_AXIS)
        body = (lambda s, i: jax.lax.scan(step, s, i)) if chunk else step
        fn = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(state_spec, in_spec),
            out_specs=(state_spec, met_spec),
            check_vma=False)
        return fn(state, inputs)

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def round_step_sharded(self, state, inputs: RoundInputs):
        """``round_step`` with the worker axis placed over the mesh's 'pod'
        axis. Master params are bit-exact with single-device fused mode
        (tests/test_placement.py); ``state`` is donated and stays resident
        in its sharded layout across calls."""
        self.traced += 1
        return self._round_sharded(state, inputs, chunk=False)

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def round_chunk_sharded(self, state, inputs: RoundInputs):
        """``round_chunk`` under sharded placement: the R-round ``lax.scan``
        runs *inside* ``shard_map``, so one jit call executes R rounds with
        the worker axis on hardware and per-round collectives only."""
        self.traced += 1
        return self._round_sharded(state, inputs, chunk=True)

    # -- eval ----------------------------------------------------------------------
    @functools.partial(jax.jit, static_argnums=0)
    def master_accuracy(self, state, batch):
        params = state["master"]
        return self.model.accuracy(params, batch)

    @functools.partial(jax.jit, static_argnums=0)
    def master_loss(self, state, batch):
        params = state["master"]
        loss, _ = self.model.loss(params, batch)
        return loss
