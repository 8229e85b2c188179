"""`RunSpec` → `ElasticSession`: the one driver for the paper's system.

Before ISSUE-3 the repo carried five hand-rolled copies of the same loop
(train CLI, paper_repro, grid, both examples), each re-deriving batchers,
failure schedules, mask conversion and the 7-positional-argument round call,
with semantics drifting between copies. This module replaces all of them:

- :class:`RunSpec` is a frozen, validated description of a run —
  architecture (or explicit :class:`ModelConfig`), optimizer, elastic /
  failure configuration, synthetic-data sizes, seeds, eval cadence,
  checkpoint path, and ``rounds_per_call``.
- :class:`ElasticSession` owns the mutable half: trainer state, the
  precomputed :class:`ScenarioSchedule`, the worker batcher, and the eval
  batch. ``run()`` / ``run_iter()`` yield one :class:`RoundRecord` per
  simulated round.

Chunked execution (the speed headline): with ``rounds_per_call = R`` the
session stacks R rounds of batches, masks and PRNG keys into one
:class:`RoundInputs` whose leaves carry a leading (R,) axis and calls
``ElasticTrainer.round_chunk`` — a ``lax.scan`` over the identical round
body inside a single jit — so per-round Python/dispatch overhead (the
DaSGD-style driver tax) is paid once per chunk. Chunked and per-round
execution are bit-identical (``tests/test_session.py`` asserts master-param
equality); chunk boundaries are snapped to eval rounds so the eval cadence
never changes results. Scenarios that never straggle/restart keep those
inputs ``None``, preserving the specialized single-trace fast path.

Sharded placement (``ElasticConfig.placement = "sharded"``): the session
builds (or accepts) a mesh whose ``'pod'`` axis hosts the worker shards,
device_puts the trainer state into its sharded-resident layout once at
init, and drives ``round_step_sharded`` / ``round_chunk_sharded`` instead —
the k workers' local+comm phases run on disjoint mesh shards with one
master reduction per round, bit-exact with single-device fused mode
(``tests/test_placement.py``). Records, eval and checkpointing are
placement-agnostic: the master is replicated, so everything host-side reads
identically.

Elastic membership (ISSUE-5): with ``ElasticConfig.capacity > num_workers``
(or any non-static ``membership_scenario``) the worker axis is
capacity-padded and a per-round active mask rides through ``RoundInputs``.
The session owns the membership lifecycle: it snaps chunk boundaries to
membership-transition rounds (so the host can re-partition the data over
the new pool — the shared overlap O is k-independent and stays put), feeds
join masks so the coordinator re-seats joining slots from the master, and
echoes the live mask in every :class:`RoundRecord`. ``resize()`` /
``set_membership()`` change the pool live between ``run`` calls, and
``restore()`` warm-starts a session — possibly at a *different* capacity —
from a checkpoint's master, re-seating the saved live slots' u-histories
and cold-starting any extra joiners from the master, EASGD-style.

Closed-loop control (ISSUE-6): live control is now a typed, single-entry
surface — ``apply(ControlAction)`` executes one membership edit (the old
``resize()``/``set_membership()`` delegate to it and emit
``DeprecationWarning``). Observers (:class:`SessionObserver`) attach via
``add_observer`` or ``RunSpec.controller``; they see every
:class:`RoundRecord` (``on_round``) and get a mutation window between jit
chunks (``on_chunk_end``), which is where the rule controller
(``repro.control``) closes the detect→decide→act loop.
``RunSpec(detector_blind=True)`` echoes a mask-zeroed schedule view into
the records so a controller provably runs on observable telemetry only;
each record also carries host-measured ``round_ms``/``dispatch_ms``, the
step-time outlier signal.

Tracing: each chunk's host work is annotated for ``jax.profiler`` —
``repro.chunk`` (a step annotation numbered by the chunk's first round)
around ``repro.build_batches``, ``repro.round_keys`` (the rounds'
``fold_in``s and the keys' stack), ``repro.to_device`` (the input copies;
the stack runs between them, in a ``repro.round_keys`` of its own),
``repro.dispatch``, ``repro.fetch``, ``repro.records`` and
``repro.observers``, each with the first round as ``chunk`` and the
transfer counts or the retrace count as args. The host work keeps the
order it had before it was annotated. The spans land in the same
trace as the device's operations, on one clock, and cost about a
microsecond each when no profiler session is active.
"""
from __future__ import annotations

import dataclasses
import os
import time
import warnings
from typing import Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint
from repro.control.actions import ControlAction, SessionObserver
from repro.configs.base import (ElasticConfig, ModelConfig, OptimizerConfig,
                                get_config)
from repro.core.coordinator import ElasticTrainer, RoundInputs
from repro.core.scenarios import (ScenarioSchedule, make_membership,
                                  make_scenario)
from repro.data.pipeline import TokenWorkerBatcher, WorkerBatcher
from repro.data.synthetic import SyntheticImages, SyntheticTokens
from repro.models.registry import build_model
from repro.train.steps import init_train_state, make_train_step


def _count_transfers(span, kind: str, tree) -> None:
    """Give a span the transfers it makes, one per leaf of ``tree``, as
    ``<kind>_transfers`` and ``<kind>_bytes``; only while a profiler session
    records it, so the count costs nothing otherwise."""
    if span.is_enabled():
        leaves = jax.tree.leaves(tree)
        span.set_metadata(**{f"{kind}_transfers": len(leaves),
                             f"{kind}_bytes": sum(x.nbytes for x in leaves)})


@dataclasses.dataclass(frozen=True)
class RunSpec:
    """Everything a run needs, validated at construction.

    ``arch``/``smoke`` name a registered config; ``model_cfg`` (when given)
    overrides both. ``plain=True`` is the single-worker control (the k=1
    limit with no elastic sync, no failures): one "round" is one optimizer
    step. The synthetic data source follows the model family — images +
    :class:`WorkerBatcher` for ``cnn``, token stream +
    :class:`TokenWorkerBatcher` otherwise. ``data_seed`` seeds dataset
    *generation* (keep it fixed across methods to compare on identical
    data, as paper §VI does); ``seed`` seeds init, batching and the
    per-round PRNG; the failure schedule draws from ``scenario_seed``
    (default ``seed + 7``, the historical convention). ``schedule``
    injects a hand-crafted :class:`ScenarioSchedule` instead of the
    scenario engine (e.g. the failure demo's deterministic outage).
    """

    arch: str = "paper-cnn"
    smoke: bool = False
    model_cfg: Optional[ModelConfig] = None
    optimizer: OptimizerConfig = dataclasses.field(
        default_factory=OptimizerConfig)
    elastic: ElasticConfig = dataclasses.field(default_factory=ElasticConfig)
    rounds: int = 20
    rounds_per_call: int = 1
    seed: int = 0
    scenario_seed: Optional[int] = None
    schedule: Optional[ScenarioSchedule] = None
    plain: bool = False
    # synthetic data source (family-dependent)
    batch_size: int = 32
    seq_len: int = 128
    n_data: int = 8000
    n_test: int = 1000
    n_tokens: int = 100_000
    data_seed: int = 0
    # eval / io
    eval_every: int = 0  # 0 = never; >0 = every e rounds + the final round
    save_path: Optional[str] = None
    use_pallas: bool = False
    # closed-loop control (ISSUE-6)
    controller: Optional[str] = None  # None = open loop; "rules" = RuleController
    detector_blind: bool = False  # echo mask-zeroed schedule into records

    def __post_init__(self):
        for name in ("rounds", "rounds_per_call", "batch_size", "seq_len",
                     "n_data", "n_test", "n_tokens"):
            v = getattr(self, name)
            if v < 1:
                raise ValueError(f"RunSpec.{name} must be >= 1, got {v}")
        if self.eval_every < 0:
            raise ValueError(
                f"RunSpec.eval_every must be >= 0, got {self.eval_every}")
        if self.schedule is not None:
            if self.plain:
                raise ValueError(
                    "RunSpec: plain mode has no failure schedule")
            want = (self.rounds, self.elastic.cap)
            if self.schedule.fail.shape != want:
                raise ValueError(
                    f"RunSpec.schedule shape {self.schedule.fail.shape} != "
                    f"(rounds, capacity) = {want}")
        if self.controller is not None:
            if self.controller != "rules":
                raise ValueError(
                    f"RunSpec.controller must be None or 'rules', got "
                    f"{self.controller!r}")
            if self.plain:
                raise ValueError(
                    "RunSpec: plain mode has no worker pool to control")
        if self.detector_blind and self.elastic.oracle:
            raise ValueError(
                "RunSpec: detector_blind contradicts ElasticConfig.oracle — "
                "the oracle weighting itself reads the ground-truth masks")

    def replace(self, **kw) -> "RunSpec":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class RoundRecord:
    """One communication round, materialized on the host.

    ``u``/``score``/``h1``/``h2`` are the (cap,) dynamic-weighting
    diagnostics (zeros in plain mode and for vacant slots);
    ``fail``/``straggle``/``restart`` echo the schedule row that drove the
    round and ``active`` the live-membership mask (all-True for fixed-k
    runs); under ``RunSpec.detector_blind`` the echoed event masks are
    all-False (the truth still drives the run — see
    ``ScenarioSchedule.blind``). ``eval_loss``/``eval_acc`` are the
    master's held-out metrics, populated only on eval rounds (``eval_acc``
    only for model families that define ``accuracy``). ``loss_w`` is the
    (cap,) per-slot mean local-phase loss (``None`` in plain mode);
    ``round_ms`` is host wall time attributed to this round (its chunk's
    wall time / rounds in the chunk) and ``dispatch_ms`` the chunk's
    dispatch latency: the host's time from before the inputs' host-to-device
    copies to the jit call's return, before the metrics are materialized —
    both are chunk-grained, repeated on each record of the chunk.
    """

    round: int
    loss: float
    u: np.ndarray
    score: np.ndarray
    h1: np.ndarray
    h2: np.ndarray
    fail: np.ndarray
    straggle: np.ndarray
    restart: np.ndarray
    eval_loss: Optional[float] = None
    eval_acc: Optional[float] = None
    active: Optional[np.ndarray] = None
    loss_w: Optional[np.ndarray] = None
    round_ms: float = 0.0
    dispatch_ms: float = 0.0
    # (cap,) bool — byzantine slots this round (ISSUE-9), echoed from the
    # schedule like fail/straggle/restart (all-False under detector_blind
    # or when the scenario has no corruption channel). Trails the field
    # list with a default so older positional constructions keep working.
    corrupt: Optional[np.ndarray] = None
    # (groups,) rack-level diagnostics (ISSUE-10): sub-master distance /
    # score / h1 / h2 against the global master. ``None`` on flat runs;
    # all-zero on hierarchical rounds that skip the global sync (the
    # two-period cadence — a round's g_h2 is nonzero only every
    # ``global_period`` rounds).
    g_u: Optional[np.ndarray] = None
    g_score: Optional[np.ndarray] = None
    g_h1: Optional[np.ndarray] = None
    g_h2: Optional[np.ndarray] = None

    @property
    def num_active(self) -> int:
        return int(self.active.sum()) if self.active is not None else 0


class ElasticSession:
    """Stateful driver for one run: trainer state + schedule + batcher + eval.

    ``run_iter()`` yields :class:`RoundRecord` s as rounds complete;
    ``run()`` collects them. Execution advances in chunks of up to
    ``spec.rounds_per_call`` rounds per jit call (``round_chunk``); chunk
    boundaries are shortened to land exactly on eval rounds, so the eval
    cadence is independent of the chunking. When the full ``spec.rounds``
    have run and ``spec.save_path`` is set, the master checkpoint is saved
    automatically with ``{"rounds", "arch", "scenario"}`` metadata.

    Under ``spec.elastic.placement == "sharded"`` the session drives the
    shard_mapped round fns; ``mesh`` overrides the default
    ``make_host_mesh(pod=jax.device_count())`` (it needs a 'pod' axis whose
    size divides ``num_workers``). The trainer state lives device-resident
    in its sharded layout from init: worker-axis entries split over 'pod',
    master replicated, with the donated round fns updating it in place.
    """

    def __init__(self, spec: RunSpec, mesh=None):
        self.spec = spec
        cfg = spec.model_cfg or get_config(spec.arch, smoke=spec.smoke)
        if cfg.use_pallas != spec.use_pallas:
            # RunSpec.use_pallas is the single source of truth (ISSUE-7):
            # the flag also exists on ModelConfig (it gates model-internal
            # kernels like flash attention), and a preset/model_cfg that
            # disagrees with the spec would silently split the run into
            # half-kernel/half-jnp execution. Coerce the model config so
            # one flag drives every kernel path.
            cfg = dataclasses.replace(cfg, use_pallas=spec.use_pallas)
        self.model_cfg = cfg
        self.model = build_model(cfg)
        ecfg = spec.elastic
        if spec.plain:
            # the k=1 limit has no worker axis to place (and no pool)
            ecfg = dataclasses.replace(ecfg, num_workers=1, capacity=0,
                                       tau=1, overlap_ratio=0.0,
                                       failure_prob=0.0, placement="single",
                                       membership_scenario="static",
                                       groups=1, global_period=1)
        self.ecfg = ecfg
        self.capacity = ecfg.cap
        self._sharded = ecfg.placement == "sharded"
        if not self._sharded and mesh is not None:
            raise ValueError(
                "ElasticSession: a mesh was passed but "
                f"placement={ecfg.placement!r} would ignore it — set "
                "ElasticConfig(placement='sharded', comm_mode='fused') to "
                "place the worker axis on it")
        if self._sharded and mesh is None:
            # default mesh: every visible device becomes one worker shard
            from repro.launch.mesh import make_host_mesh

            mesh = make_host_mesh(pod=jax.device_count())
        self.mesh = mesh
        self.trainer = ElasticTrainer(self.model, spec.optimizer, ecfg,
                                      use_pallas=spec.use_pallas,
                                      mesh=self.mesh)
        # -- data -----------------------------------------------------------
        if cfg.family == "cnn":
            ds = SyntheticImages(n=spec.n_data, n_test=spec.n_test,
                                 seed=spec.data_seed)
            self.batcher = WorkerBatcher(ds.images, ds.labels, ecfg,
                                         batch_size=spec.batch_size,
                                         seed=spec.seed)
            self._test = {k: jnp.asarray(v) for k, v in
                          ds.test_batch().items()}
        else:
            toks = SyntheticTokens(vocab=cfg.vocab_size,
                                   n_tokens=spec.n_tokens,
                                   seed=spec.data_seed)
            self.batcher = TokenWorkerBatcher(toks.tokens, ecfg,
                                              batch_size=spec.batch_size,
                                              seq_len=spec.seq_len,
                                              seed=spec.seed)
            # held-out eval batch from the same stream, disjoint rng
            self._test = {k: jnp.asarray(v) for k, v in toks.batch(
                np.random.default_rng(spec.seed + 31), spec.batch_size,
                spec.seq_len).items()}
        # -- schedule -------------------------------------------------------
        self._active = np.arange(self.capacity) < ecfg.num_workers
        if spec.plain:
            self.schedule = None
            self._failed_recent = None
            self._membership = None
            self._join_rows = None
        else:
            if spec.schedule is not None:
                self.schedule = spec.schedule
            else:
                sseed = (spec.scenario_seed if spec.scenario_seed is not None
                         else spec.seed + 7)
                self.schedule = make_scenario(ecfg).schedule(
                    sseed, spec.rounds, self.capacity)
            if self.schedule.active is None and (
                    self.capacity > ecfg.num_workers
                    or ecfg.membership_scenario != "static"):
                # membership stream: planned resize events at capacity
                self.schedule = self.schedule.with_membership(
                    make_membership(ecfg).active_schedule(
                        spec.rounds, self.capacity, ecfg.num_workers))
            self._failed_recent = self.schedule.failed_recent_all()
            self._refresh_membership()
        # -- observers / controller (ISSUE-6) -------------------------------
        # detector-blind runs echo a mask-zeroed schedule view into records;
        # the real schedule still drives RoundInputs
        self._echo = (self.schedule.blind()
                      if (not spec.plain and spec.detector_blind)
                      else self.schedule)
        self._observers: List[SessionObserver] = []
        self.controller = None
        if spec.controller is not None:
            from repro.control.actuator import make_controller

            self.controller = make_controller(spec.controller, self.capacity)
            self.add_observer(self.controller)
        # -- state ----------------------------------------------------------
        if spec.plain:
            self.state = init_train_state(self.model, spec.optimizer,
                                          jax.random.key(spec.seed))
            step = make_train_step(self.model, spec.optimizer)
            self._plain_chunk = jax.jit(
                lambda st, xs: jax.lax.scan(
                    lambda s, x: step(s, x[0], x[1]), st, xs))
        else:
            self.state = self.trainer.init_state(jax.random.key(spec.seed))
            if self._sharded:
                self.state = self._place_state(self.state)
        if not spec.plain and self.schedule.has_membership:
            # seat round 0's membership (a custom schedule or a plan step
            # at round 0 may start with a different pool than num_workers)
            self._apply_membership(self.schedule.active[0])
        self._rng_base = jax.random.key(spec.seed)
        self._eval_loss = jax.jit(lambda p, b: self.model.loss(p, b)[0])
        self._eval_acc = (jax.jit(self.model.accuracy)
                          if hasattr(self.model, "accuracy") else None)
        self.round = 0  # rounds completed so far

    # -- sharded placement ---------------------------------------------------
    def _place_state(self, state):
        """Device_put the trainer state into its sharded-resident layout,
        per entry as declared by ``ElasticTrainer.state_shard_specs`` (the
        same specs shard_map runs under, so there is no per-call
        resharding). Done once at init; the donated sharded round fns then
        keep the state resident in this layout for the whole run."""
        from jax.sharding import NamedSharding

        specs = self.trainer.state_shard_specs()
        return {key: jax.tree.map(
                    lambda x, s=specs[key]: jax.device_put(
                        x, NamedSharding(self.mesh, s)), sub)
                for key, sub in state.items()}

    # -- membership ----------------------------------------------------------
    def _refresh_membership(self):
        """Re-derive the per-round membership/join input rows from the
        schedule. Join rows stay ``None`` when no slot ever flips
        inactive→active, preserving the specialized no-join trace."""
        self._membership = self.schedule.active
        joins = self.schedule.joins()
        self._join_rows = joins if joins.any() else None

    def _apply_membership(self, row: np.ndarray):
        """Host-side membership transition: remember the live mask and
        re-partition the data over the new pool (O stays put; only the
        unique shards are redealt)."""
        if np.array_equal(row, self._active):
            return
        self._active = row.copy()
        self.batcher.set_active_mask(row)

    @property
    def active_mask(self) -> np.ndarray:
        """(cap,) bool — the live-membership mask as of the next round."""
        return self._active.copy()

    @property
    def num_active(self) -> int:
        return int(self._active.sum())

    def _set_membership(self, mask: np.ndarray) -> None:
        """Live membership change between chunks: the given (cap,) bool
        mask becomes the pool for every remaining round (overriding the
        scheduled stream from here on). Newly activated slots join at the
        next round, cold-started from the master. With a fixed-k spec (no
        membership stream) the first call materializes one, which retraces
        the jitted round once — capacity-padded specs
        (``capacity > num_workers`` or a membership scenario) pay nothing.
        """
        if self.spec.plain:
            raise ValueError("plain mode has no worker pool to resize")
        mask = np.asarray(mask, bool)
        if mask.shape != (self.capacity,):
            raise ValueError(
                f"membership mask shape {mask.shape} != ({self.capacity},)")
        if not mask.any():
            raise ValueError("at least one worker must stay active")
        if self.round >= self.spec.rounds:
            raise ValueError("run already complete; nothing left to resize")
        rows = self.schedule.active
        if rows is None:
            rows = np.arange(self.capacity)[None] < self.ecfg.num_workers
            rows = np.repeat(rows, self.spec.rounds, axis=0)
            rows[:self.round] = self._active  # frozen history
        rows = rows.copy()
        rows[self.round:] = mask
        self.schedule = self.schedule.with_membership(rows)
        self._refresh_membership()
        self._apply_membership(mask)

    def _resize(self, k: int) -> None:
        """Pool resize to ``k``: growing activates the lowest-numbered
        vacant slots (joiners, cold-started from the master); shrinking
        retires the highest-numbered live slots."""
        if self.spec.plain:
            raise ValueError("plain mode has no worker pool to resize")
        if not 1 <= k <= self.capacity:
            raise ValueError(
                f"resize target {k} outside 1..capacity={self.capacity}")
        mask = self._active.copy()
        live = np.flatnonzero(mask)
        if k > len(live):
            vacant = np.flatnonzero(~mask)
            mask[vacant[:k - len(live)]] = True
        elif k < len(live):
            mask[live[k:]] = False
        self._set_membership(mask)

    def apply(self, action: ControlAction) -> None:
        """The single live-control entrypoint (ISSUE-6): execute one
        :class:`ControlAction` against the pool. Legal between ``run``
        calls and inside ``on_chunk_end`` observer hooks (membership is
        baked into each jit chunk, so mid-chunk edits are impossible by
        construction). ``evict`` requires its slots live, ``readmit``
        requires them vacant — slot state is part of the action's meaning,
        so a stale action errors instead of silently half-applying (the
        controller's :class:`~repro.control.actuator.Actuator` journals and
        re-scopes stale actions before calling this).
        """
        if not isinstance(action, ControlAction):
            raise TypeError(
                f"ElasticSession.apply expects a ControlAction, got "
                f"{type(action).__name__}")
        if action.kind == "noop":
            return
        if action.kind == "resize":
            self._resize(action.k)
            return
        if action.kind == "set_membership":
            self._set_membership(action.mask)
            return
        if self.spec.plain:
            raise ValueError("plain mode has no worker pool to resize")
        bad = [s for s in action.slots if not 0 <= s < self.capacity]
        if bad:
            raise ValueError(
                f"{action.kind} slots {bad} outside 0..{self.capacity - 1}")
        mask = self._active.copy()
        if action.kind == "evict":
            dead = [s for s in action.slots if not mask[s]]
            if dead:
                raise ValueError(f"cannot evict vacant slots {dead}")
            mask[list(action.slots)] = False
        else:  # readmit
            live = [s for s in action.slots if mask[s]]
            if live:
                raise ValueError(f"cannot readmit live slots {live}")
            mask[list(action.slots)] = True
        self._set_membership(mask)

    def set_membership(self, mask) -> None:
        """Deprecated: use ``apply(ControlAction.set_membership(mask))``."""
        warnings.warn(
            "ElasticSession.set_membership() is deprecated; use "
            "apply(ControlAction.set_membership(mask))",
            DeprecationWarning, stacklevel=2)
        self._set_membership(mask)

    def resize(self, k: int) -> None:
        """Deprecated: use ``apply(ControlAction.resize(k))``."""
        warnings.warn(
            "ElasticSession.resize() is deprecated; use "
            "apply(ControlAction.resize(k))",
            DeprecationWarning, stacklevel=2)
        self._resize(k)

    # -- observers -----------------------------------------------------------
    def add_observer(self, observer: SessionObserver) -> None:
        """Attach an observer: ``on_round(record)`` fires for every
        completed round, ``on_chunk_end(session)`` between jit chunks (the
        mutation window — the only place ``apply`` is called by a
        controller). Both hooks are optional; missing ones are skipped."""
        self._observers.append(observer)

    # -- eval ---------------------------------------------------------------
    @property
    def master_params(self):
        """The authoritative parameters: the elastic master, or the single
        worker's params in plain mode."""
        return (self.state["params"] if self.spec.plain
                else self.state["master"])

    def evaluate(self):
        """(held-out loss, accuracy-or-None) of the master params."""
        loss = float(self._eval_loss(self.master_params, self._test))
        acc = (float(self._eval_acc(self.master_params, self._test))
               if self._eval_acc is not None else None)
        return loss, acc

    def _is_eval_round(self, r: int) -> bool:
        e = self.spec.eval_every
        return e > 0 and (r % e == 0 or r == self.spec.rounds - 1)

    # -- checkpoint ---------------------------------------------------------
    def save(self, path: Optional[str] = None,
             extra_metadata: Optional[dict] = None) -> str:
        """Save the master params with unified metadata. Every session
        checkpoint — plain or elastic, any entrypoint — records at least
        ``{"rounds", "arch", "scenario"}``; elastic checkpoints add the
        per-slot membership manifest (capacity, active mask, u-history)
        that ``restore`` re-seats — possibly into a different capacity."""
        path = path or self.spec.save_path
        if not path:
            raise ValueError("no save path: pass one or set RunSpec.save_path")
        meta = {"rounds": self.round, "arch": self.model_cfg.name,
                "scenario": ("none" if self.spec.plain
                             else self.ecfg.failure_scenario)}
        hier = not self.spec.plain and getattr(self.trainer, "_hier", False)
        if not self.spec.plain:
            meta["elastic"] = checkpoint.elastic_manifest(
                self._active, np.asarray(self.state["u_hist"], np.float32),
                **({"groups": self.trainer._n_groups,
                    "global_period": self.ecfg.global_period,
                    "g_u_hist": np.asarray(self.state["g_u_hist"],
                                           np.float32)} if hier else {}))
        meta.update(extra_metadata or {})
        if hier:
            # sub-master params ride in a sibling sub-checkpoint, written
            # *before* the main manifest — the manifest-last completeness
            # ordering (read_fingerprint) then covers them too. The main
            # tree stays a bare master-params tree, so flat consumers
            # (serving hot-swap ``restore(like=master)``) read
            # hierarchical checkpoints unchanged.
            checkpoint.save(os.path.join(path, "submasters"),
                            self.state["submasters"])
        checkpoint.save(path, self.master_params, metadata=meta)
        return path

    def restore(self, path: str) -> dict:
        """Warm-start this session from a saved checkpoint; returns its
        metadata. The master is restored exactly; every worker slot is
        cold-started *from the master* (EASGD-style — per-worker params are
        not checkpointed, and a restore is a pool-wide rejoin) with fresh
        optimizer accumulators. The checkpoint's live slots are re-seated
        into this session's active slots in order, carrying their
        u-histories across even when the two capacities differ; any extra
        active slots here are joiners with blank histories. Raises on an
        architecture mismatch between the manifest and this session's spec.
        """
        from repro.nn.param import abstract_tree

        arch = checkpoint.read_metadata(path).get("arch")
        if arch is not None and arch != self.model_cfg.name:
            raise ValueError(
                f"checkpoint {path!r} was saved from arch {arch!r}, this "
                f"session runs {self.model_cfg.name!r}")
        if self.spec.plain:
            tree, meta = checkpoint.restore(path, like=self.state["params"])
            self.state = dict(self.state, params=tree)
            return meta
        # the master lives (and was saved) in float32 — restore it at f32 so
        # it comes back bit-exact even when the model's param dtype is
        # narrower (bf16 transformers); workers re-seat at param dtype, as
        # a fresh run's workers would be
        spec_tree = abstract_tree(self.model.spec)
        like32 = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), spec_tree)
        master, meta = checkpoint.restore(path, like=like32)
        params = jax.tree.map(lambda m, s: m.astype(s.dtype), master,
                              spec_tree)
        u_hist = checkpoint.reseat_u_hist(
            meta.get("elastic"), self.capacity, self._active,
            self.ecfg.score_window)
        state = self.trainer.init_state(jax.random.key(self.spec.seed),
                                        params=params)
        state["master"] = master
        state["master_prev"] = jax.tree.map(jnp.copy, master)
        state["u_hist"] = jnp.asarray(u_hist)
        if getattr(self.trainer, "_hier", False):
            # hierarchical warm start (ISSUE-10), possibly at a different
            # group count: saved racks carry their sub-masters/histories
            # across in order, extra racks cold-start from the master; a
            # flat checkpoint seats every rack from the master
            sub_path = os.path.join(path, "submasters")
            saved = None
            if os.path.exists(os.path.join(sub_path, "manifest.json")):
                saved, _ = checkpoint.restore(sub_path)
            n_groups = self.trainer._n_groups
            state["submasters"] = checkpoint.reseat_submasters(
                saved, master, n_groups)
            state["g_u_hist"] = jnp.asarray(checkpoint.reseat_group_hist(
                (meta.get("elastic") or {}).get("g_u_hist"), n_groups,
                self.ecfg.score_window))
        self.state = self._place_state(state) if self._sharded else state
        return meta

    # -- execution ----------------------------------------------------------
    def _round_rng(self, r: int) -> jax.Array:
        return jax.random.fold_in(self._rng_base, r)

    def _next_chunk(self, end: int) -> int:
        """Rounds to run in the next jit call: at most ``rounds_per_call``,
        never past ``end``, never past the next eval round (evals read the
        master between chunks, so eval rounds must close a chunk), and
        never across a membership transition (the host re-partitions the
        data over the new pool between chunks, so a transition round must
        open a fresh chunk — this re-snap composes with the eval snapping,
        and the eval cadence itself never moves)."""
        n = min(self.spec.rounds_per_call, end - self.round)
        if self.spec.eval_every > 0:
            for r in range(self.round, self.round + n):
                if self._is_eval_round(r):
                    n = r - self.round + 1
                    break
        if self._membership is not None:
            row = self._membership[self.round]
            for r in range(self.round + 1, self.round + n):
                if not np.array_equal(self._membership[r], row):
                    n = r - self.round
                    break
        return n

    def _stack_batches(self, n: int):
        with jax.profiler.TraceAnnotation("repro.build_batches",
                                          chunk=self.round):
            rounds = [self.batcher.round_batches() for _ in range(n)]
            return {key: np.stack([b[key] for b in rounds])
                    for key in rounds[0]}

    def _run_chunk_elastic(self, n: int) -> List[RoundRecord]:
        lo, hi = self.round, self.round + n
        sched = self.schedule
        if self._membership is not None:
            # membership is chunk-constant (_next_chunk snaps transitions);
            # re-partition the data before building this chunk's batches
            self._apply_membership(self._membership[lo])
        stacked = self._stack_batches(n)
        with jax.profiler.TraceAnnotation("repro.round_keys", chunk=lo):
            rngs = [self._round_rng(r) for r in range(lo, hi)]
        # specialization on whole-schedule has_* keeps one trace per run
        # even when an individual chunk happens to be event-free
        straggle = sched.straggle[lo:hi] if sched.has_stragglers else None
        restart = sched.restart[lo:hi] if sched.has_restarts else None
        # adversarial channels (ISSUE-9) gate on has_* like the masks
        # above, so an all-False corrupt array / all-ones speed array never
        # reaches RoundInputs and the corruption-free trace is untouched
        corrupt = sched.corrupt[lo:hi] if sched.has_corruption else None
        speed = sched.speed[lo:hi] if sched.has_hetero else None
        active = (self._membership[lo:hi] if self._membership is not None
                  else None)
        join = self._join_rows[lo:hi] if self._join_rows is not None else None
        host = dict(batches=stacked, fail=sched.fail[lo:hi],
                    failed_recent=self._failed_recent[lo:hi],
                    straggle=straggle, restart=restart, active=active,
                    join=join, corrupt=corrupt, speed=speed)
        if n == 1:  # round_step takes per-round leaves
            host = jax.tree.map(lambda a: a[0], host)
        batches = host.pop("batches")
        if self._sharded:
            step = (self.trainer.round_step_sharded if n == 1
                    else self.trainer.round_chunk_sharded)
        else:
            step = (self.trainer.round_step if n == 1
                    else self.trainer.round_chunk)
        traced = self.trainer.traced
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("repro.to_device",
                                          chunk=lo) as span:
            _count_transfers(span, "h2d", (batches, host))
            batches = {k: jnp.asarray(v) for k, v in batches.items()}
            # the keys' stack (made on the device) stays between the batch
            # copies and the schedule rows' copies, where it has always run
            rng = rngs[0]
            if n > 1:
                with jax.profiler.TraceAnnotation("repro.round_keys",
                                                  chunk=lo):
                    rng = jnp.stack(rngs)
            inputs = RoundInputs(
                batches=batches, rng=rng,
                **{k: None if v is None else jnp.asarray(v)
                   for k, v in host.items()})
        with jax.profiler.TraceAnnotation("repro.dispatch",
                                          chunk=lo) as span:
            self.state, m = step(self.state, inputs)
            t1 = time.perf_counter()
            span.set_metadata(traced=self.trainer.traced - traced)
        with jax.profiler.TraceAnnotation("repro.fetch", chunk=lo) as span:
            _count_transfers(span, "d2h", m)
            m = jax.tree.map(((lambda x: np.asarray(x)[None]) if n == 1
                              else np.asarray), m)
        # materializing m above synced the chunk, so t2 - t0 is its wall
        # time; t1 - t0 is the async-dispatch latency (jit-call return)
        t2 = time.perf_counter()
        round_ms = (t2 - t0) * 1e3 / n
        dispatch_ms = (t1 - t0) * 1e3
        self.round = hi
        echo = self._echo
        no_corrupt = np.zeros(self.capacity, bool)
        records = []
        with jax.profiler.TraceAnnotation("repro.records", chunk=lo):
            for i, r in enumerate(range(lo, hi)):
                ev_loss = ev_acc = None
                if r == hi - 1 and self._is_eval_round(r):
                    ev_loss, ev_acc = self.evaluate()
                records.append(RoundRecord(
                    round=r, loss=float(m["loss"][i]),
                    u=m["u"][i], score=m["score"][i],
                    h1=m["h1"][i], h2=m["h2"][i],
                    fail=echo.fail[r], straggle=echo.straggle[r],
                    restart=echo.restart[r],
                    corrupt=(echo.corrupt[r] if echo.corrupt is not None
                             else no_corrupt),
                    eval_loss=ev_loss, eval_acc=ev_acc,
                    active=(self._membership[r]
                            if self._membership is not None
                            else np.ones(self.capacity, bool)),
                    loss_w=m["loss_w"][i],
                    round_ms=round_ms, dispatch_ms=dispatch_ms,
                    **({"g_u": m["g_u"][i], "g_score": m["g_score"][i],
                        "g_h1": m["g_h1"][i], "g_h2": m["g_h2"][i]}
                       if "g_u" in m else {})))
        return records

    def _run_chunk_plain(self, n: int) -> List[RoundRecord]:
        lo, hi = self.round, self.round + n
        stacked = self._stack_batches(n)
        # WorkerBatcher emits (τ=1, k=1, B, ...); drop the unit axes
        host = {k: v[:, 0, 0] for k, v in stacked.items()}
        with jax.profiler.TraceAnnotation("repro.to_device",
                                          chunk=lo) as span:
            _count_transfers(span, "h2d", host)
            batches = {k: jnp.asarray(v) for k, v in host.items()}
        with jax.profiler.TraceAnnotation("repro.round_keys", chunk=lo):
            rng = jnp.stack([self._round_rng(r) for r in range(lo, hi)])
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("repro.dispatch", chunk=lo):
            self.state, m = self._plain_chunk(self.state, (batches, rng))
            t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("repro.fetch", chunk=lo) as span:
            _count_transfers(span, "d2h", m["loss"])
            loss = np.asarray(m["loss"])
        t2 = time.perf_counter()
        round_ms = (t2 - t0) * 1e3 / n
        dispatch_ms = (t1 - t0) * 1e3
        self.round = hi
        z = np.zeros(1, np.float32)
        zb = np.zeros(1, bool)
        records = []
        with jax.profiler.TraceAnnotation("repro.records", chunk=lo):
            for i, r in enumerate(range(lo, hi)):
                ev_loss = ev_acc = None
                if r == hi - 1 and self._is_eval_round(r):
                    ev_loss, ev_acc = self.evaluate()
                records.append(RoundRecord(
                    round=r, loss=float(loss[i]), u=z, score=z, h1=z, h2=z,
                    fail=zb, straggle=zb, restart=zb, corrupt=zb,
                    eval_loss=ev_loss, eval_acc=ev_acc, active=~zb,
                    round_ms=round_ms, dispatch_ms=dispatch_ms))
        return records

    def run_iter(self, rounds: Optional[int] = None
                 ) -> Iterator[RoundRecord]:
        """Advance up to ``rounds`` rounds (default: the rest of the run),
        yielding a :class:`RoundRecord` per round as each chunk lands."""
        remaining = (self.spec.rounds - self.round if rounds is None
                     else rounds)
        end = self.round + remaining
        if end > self.spec.rounds:
            raise ValueError(
                f"run would exceed RunSpec.rounds = {self.spec.rounds} "
                f"(at round {self.round}, asked for {rounds} more)")
        run_chunk = (self._run_chunk_plain if self.spec.plain
                     else self._run_chunk_elastic)
        while self.round < end:
            lo, n = self.round, self._next_chunk(end)
            with jax.profiler.StepTraceAnnotation("repro.chunk", step_num=lo,
                                                  rounds=n):
                records = run_chunk(n)
                # observers run before the next chunk is built: on_chunk_end
                # is the mutation window where a controller may apply()
                # membership edits that the following chunk executes under
                with jax.profiler.TraceAnnotation("repro.observers",
                                                  chunk=lo):
                    for obs in self._observers:
                        on_round = getattr(obs, "on_round", None)
                        if on_round is not None:
                            for rec in records:
                                on_round(rec)
                    for obs in self._observers:
                        on_chunk_end = getattr(obs, "on_chunk_end", None)
                        if on_chunk_end is not None:
                            on_chunk_end(self)
            yield from records
        if self.round >= self.spec.rounds and self.spec.save_path:
            self.save()

    def run(self, rounds: Optional[int] = None) -> List[RoundRecord]:
        return list(self.run_iter(rounds))
