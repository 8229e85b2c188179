"""One run of one cell: set-up, the measured window, the check.

Set-up builds one ``ElasticSession`` from the cell's configuration and mix,
with its weights made on the device from the seed, its data feed and
failure schedule from the benchmark's generator (``bench/traffic.py``). It
drives the session through ``check_chunks`` whole chunks with the window's
own call (the first compiles), reads the program's numbers for the
comparison from them, and hands the same session to the window.

The window runs whole chunks (``ElasticSession.run``, so host batch
building, dispatch, the device and record materialisation are all in it)
until ``seconds`` have passed. ``--trace 1`` runs a shorter window under
the profiler instead and reads the per-layer metrics from it.

After the window: the peak device memory is read, the program's state is
freed, and the plain reference (``bench/reference.py``) follows the
compared rounds from the same seed; ``bench/compare.py`` decides
``correct``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import sys
import tempfile
import time
from typing import List, Optional
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, flops, traffic as traffic_lib
from bench import trace as trace_lib
from bench.peaks import peaks
from bench.reference import ElasticReference, Readings, policy
from bench.spec import Cell

TRACE_SECONDS = 3.0   # traced window: at least this long ...
TRACE_CHUNKS = 3      # ... and at least this many chunks


class NoChip(RuntimeError):
    pass


def chips_for(cell: Cell) -> List:
    """The cell's devices; raises ``NoChip`` where JAX finds no accelerator
    or fewer chips than the cell asks for."""
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip(f"no chip: JAX sees only {devs[0].platform} devices")
    if len(devs) < cell.chips:
        raise NoChip(f"no chip: the cell needs {cell.chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs[:cell.chips]


# -- set-up -------------------------------------------------------------------
@contextlib.contextmanager
def _init_on_device():
    """Make the trainer's initial state in one jitted call, placed where the
    session keeps it (sharded over 'pod' under sharded placement), instead
    of op by op on the default device. The op-by-op build does not fit one
    chip at stablelm-3b-2l's size (20 GB for k=4), and with it 3 of 6 51 s
    windows of cnn-k4-tau4-iid on a TPU v5e read 3.8-6.2% fewer samples
    per second than the median (with the jitted build none of 17 fell more
    than 3.1%)."""
    from jax.sharding import NamedSharding

    from repro.core.coordinator import ElasticTrainer

    plain = ElasticTrainer.init_state

    def init_state(self, rng, params=None):
        if params is not None:
            return plain(self, rng, params)
        out = None
        if self.ecfg.placement == "sharded":
            out = {k: NamedSharding(self.mesh, s)
                   for k, s in self.state_shard_specs().items()}
        return jax.jit(lambda r: plain(self, r), out_shardings=out)(rng)

    with mock.patch.object(ElasticTrainer, "init_state", init_state):
        yield


def build_session(cell: Cell, seed: int, devices: List):
    from repro.api import ElasticSession, RunSpec
    from repro.configs.base import ElasticConfig, ModelConfig, OptimizerConfig
    from repro.core.scenarios import ScenarioSchedule

    cfg, tr = cell.config, cell.traffic
    e = dict(tr["elastic"])
    e["score_weights"] = tuple(e["score_weights"])
    ecfg = ElasticConfig(**e)
    o = dict(cfg["optimizer"])
    o["betas"] = tuple(o["betas"])
    fail = traffic_lib.failures(tr, seed)
    zeros = np.zeros_like(fail)
    spec = RunSpec(
        model_cfg=ModelConfig(**cfg["model"]),
        optimizer=OptimizerConfig(**o), elastic=ecfg,
        rounds=int(tr["max_rounds"]),
        rounds_per_call=int(tr["rounds_per_call"]),
        seed=traffic_lib.jax_seed(seed),
        schedule=ScenarioSchedule(fail, zeros, zeros.copy()),
        batch_size=int(tr["batch_per_worker"]),
        seq_len=int(tr.get("seq_len", 128)),
        # the program's own synthetic sets are replaced by the feed below;
        # keep the ones the session builds anyway small
        n_data=64, n_test=8, n_tokens=4 * (int(tr.get("seq_len", 128)) + 2),
        eval_every=0, use_pallas=bool(tr["use_pallas"]))
    # the matmul precision the configuration states (None: the backend's
    # default, which for bfloat16 operands is bfloat16). The program has no
    # setting of its own; this is JAX's global, which a user of the program
    # sets with JAX_DEFAULT_MATMUL_PRECISION.
    jax.config.update("jax_default_matmul_precision",
                      cfg.get("matmul_precision"))
    mesh = None
    if ecfg.placement == "sharded":
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(pod=len(devices))
    with _init_on_device():
        session = ElasticSession(spec, mesh=mesh)
    session.batcher = traffic_lib.Feed(cfg, tr, seed)
    return session, fail


def _per_worker_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(
        jnp.square(x.astype(jnp.float32)), axis=tuple(range(1, x.ndim)))),
        tree)


def _stack_leaves(tree, worker_axis=True) -> np.ndarray:
    leaves = [np.asarray(x) for x in jax.tree.leaves(tree)]
    return np.stack(leaves, axis=-1) if worker_axis else np.array(leaves)


def check_chunks(session, cell: Cell, seed: int):
    """Drive the session through the compared chunks (the first compiles)
    and return the program's readings of them."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.nn.param import init_tree

    R = int(cell.traffic["rounds_per_call"])
    records = []
    m_norm = v_norm = None
    norms = jax.jit(lambda s: (_per_worker_norms(s["opt"]["m"]),
                               _per_worker_norms(s["opt"]["v"])))
    for c in range(int(cell.traffic["check_chunks"])):
        records += session.run(R)
        if c == 0:
            m, v = norms(session.state)
            m_norm, v_norm = _stack_leaves(m), _stack_leaves(v)
    out = None
    if session.mesh is not None:
        out = NamedSharding(session.mesh, PartitionSpec())
    init = jax.jit(lambda k: init_tree(k, session.model.spec),
                   out_shardings=out)(
        jax.random.key(traffic_lib.jax_seed(seed)))

    def changes(state, p0):
        f32 = lambda x: x.astype(jnp.float32)
        master = jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(
            jnp.square(f32(a) - f32(b)))), state["master"], p0)
        workers = jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(
            jnp.square(f32(a) - f32(b)[None]),
            axis=tuple(range(1, a.ndim)))), state["workers"], p0)
        return master, workers

    master, workers = jax.jit(changes)(session.state, init)
    del init
    return Readings(
        loss=np.array([r.loss for r in records]),
        u=np.stack([r.u for r in records]),
        h1=np.stack([r.h1 for r in records]),
        h2=np.stack([r.h2 for r in records]),
        m_norm=m_norm, v_norm=v_norm,
        master_change=_stack_leaves(master, worker_axis=False),
        worker_change=_stack_leaves(workers))


# -- the window ---------------------------------------------------------------
@dataclasses.dataclass
class Window:
    seconds: float
    chunks: int
    rounds: int
    periods: List[float]       # per chunk, seconds
    dispatch_ms: List[float]   # per chunk
    losses: List[float]
    compiles: int


class _CompileCounter:
    """Counts backend compilations while active (there should be none in
    the window). One listener per process."""

    _instance = None

    def __init__(self):
        self.n = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    @classmethod
    def get(cls) -> "_CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _event(self, name, *_args, **_kw):
        if self.on and "backend_compile" in name:
            self.n += 1


def run_window(session, cell: Cell, seconds: float, min_chunks: int,
               counter: _CompileCounter, after_chunk=None) -> Window:
    R = int(cell.traffic["rounds_per_call"])
    jax.block_until_ready(session.state)
    counter.on, counter.n = True, 0
    periods, dispatch, losses = [], [], []
    t0 = prev = time.perf_counter()
    while True:
        recs = session.run(R)
        if after_chunk is not None:
            after_chunk()
        now = time.perf_counter()
        periods.append(now - prev)
        prev = now
        dispatch.append(recs[0].dispatch_ms)
        losses += [r.loss for r in recs]
        if now - t0 >= seconds and len(periods) >= min_chunks:
            break
    with jax.profiler.TraceAnnotation("bench.sync"):
        jax.block_until_ready(session.state)
    end = time.perf_counter()
    periods[-1] += end - prev
    counter.on = False
    return Window(seconds=end - t0, chunks=len(periods),
                  rounds=len(periods) * R, periods=periods,
                  dispatch_ms=dispatch, losses=losses, compiles=counter.n)


@contextlib.contextmanager
def host_spans(session):
    """Name the host's work of each chunk in the profiler's trace: building
    the batches, building the inputs and dispatching the jit call, then
    waiting for the device and materialising the records."""
    trainer = session.trainer
    # the session drives round_step(_sharded) for one-round chunks
    name = "round_" + ("step" if session.spec.rounds_per_call == 1
                       else "chunk")
    if session.mesh is not None:
        name += "_sharded"
    jitted = getattr(type(trainer), name)
    stack = session._stack_batches
    state = {"wait": None}

    def end_wait():
        if state["wait"] is not None:
            state["wait"].__exit__(None, None, None)
            state["wait"] = None

    def build(n):
        with jax.profiler.TraceAnnotation("bench.build"):
            return stack(n)

    def dispatch(st, inputs):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            out = jitted(trainer, st, inputs)
        state["wait"] = jax.profiler.TraceAnnotation("bench.wait_and_record")
        state["wait"].__enter__()
        return out

    session._stack_batches = build
    setattr(trainer, name, dispatch)
    try:
        yield end_wait
    finally:
        end_wait()
        del session._stack_batches
        delattr(trainer, name)


@dataclasses.dataclass
class TraceRun:
    """What the per-layer metric readers read."""

    trace: trace_lib.Trace
    window_ns: tuple
    rounds: int
    chips: int
    peaks: dict
    flops_per_round: float
    dispatch_ms: List[float]

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9


def traced_window(session, cell: Cell, counter, devices):
    """The profiled window: (what the readers read, the window)."""
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as d:
        with host_spans(session) as end_wait:
            jax.profiler.start_trace(d)
            try:
                w = run_window(session, cell, TRACE_SECONDS, TRACE_CHUNKS,
                               counter, after_chunk=end_wait)
            finally:
                end_wait()
                jax.profiler.stop_trace()
        tr = trace_lib.load(d)
    e = cell.traffic["elastic"]
    k, tau = int(e["num_workers"]), int(e["tau"])
    ref = cell.reference
    per_sample = flops.step_flops_per_sample(
        ref.products(cell.config, cell.traffic))
    return TraceRun(
        trace=tr, window_ns=tr.window, rounds=w.rounds, chips=len(devices),
        peaks=peaks(devices[0].device_kind),
        flops_per_round=per_sample * k * tau
        * int(cell.traffic["batch_per_worker"]),
        dispatch_ms=w.dispatch_ms), w


# -- the check ----------------------------------------------------------------
def reference_readings(cell: Cell, seed: int, devices, precision,
                       rounds: int, fault: Optional[str] = None) -> Readings:
    """The reference's readings over the first ``rounds`` rounds, in the
    given precision (``"reference"`` or a control's policy fields),
    optionally with a fault planted in it."""
    R = int(cell.traffic["rounds_per_call"])
    feed = traffic_lib.Feed(cell.config, cell.traffic, seed)
    fail = traffic_lib.failures(cell.traffic, seed)
    ref = ElasticReference(cell.reference, cell.config, cell.traffic,
                           policy(precision), devices, fault=fault)
    with jax.default_device(devices[0]):
        return ref.run(seed, rounds, R, feed.batches, fail)


def memory_peak(devices) -> Optional[int]:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             devices: List, started: float) -> dict:
    """One run; returns the result object of the contract (and ``checks``
    last). ``started`` is the process's start on ``time.perf_counter``."""
    counter = _CompileCounter.get()
    session, _ = build_session(cell, seed, devices)
    prog = check_chunks(session, cell, seed)
    jax.block_until_ready(session.state)
    setup_s = time.perf_counter() - started
    if trace:
        run, w = traced_window(session, cell, counter, devices)
        metrics = {}
        for m in cell.per_layer:
            val = cell.readers[m["name"]].read(run)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        busy = [trace_lib.busy_ns(ops, run.window_ns) / 1e9
                for ops in run.trace.devices]
        extra_dev = {"busy_s": float(np.mean(busy)),
                     "window_s": run.window_s}
        breakdown = {
            "device_ops": trace_lib.top_ops(run.trace.devices, run.window_ns),
            "idle_gaps": trace_lib.labelled_gaps(
                run.trace.devices, run.trace.spans, run.window_ns)}
    else:
        w = run_window(session, cell, seconds, 1, counter)
        samples = (w.rounds * int(cell.traffic["elastic"]["num_workers"])
                   * int(cell.traffic["elastic"]["tau"])
                   * int(cell.traffic["batch_per_worker"]))
        R = int(cell.traffic["rounds_per_call"])
        values = {
            "samples_per_s": samples / w.seconds,
            "round_ms_p95": float(np.percentile(w.periods, 95)) / R * 1e3,
            "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
        extra_dev, breakdown = {}, None
    if w.compiles:
        print(f"warning: {w.compiles} compilations inside the window",
              file=sys.stderr)
    mem = memory_peak(devices)
    # free the program's state before the reference runs on the chips
    session.state = None
    del session
    gc.collect()
    ref = reference_readings(cell, seed, devices, "reference",
                             len(prog.loss))
    alpha = float(cell.traffic["elastic"]["alpha"])
    nums = compare.numbers(prog, ref, alpha)
    correct, checks = compare.judge(nums, cell.limits)
    failed = int(np.sum(~np.isfinite(w.losses)))
    correct = correct and failed == 0
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    dev = devices[0]
    result = {
        "correct": bool(correct), "attempted": int(w.rounds),
        "failed": failed, "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": mem,
                   **extra_dev}}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result

