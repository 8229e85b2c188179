"""The program's own spans and scopes, read back from a profiler trace.

``ElasticSession`` annotates each chunk's host work (``repro.*`` spans,
with the chunk's first round as ``chunk``) and the round program names its
phases with ``jax.named_scope``; both land in the profiler's ``.xplane.pb``
on the device trace's clock. ``ElasticTrainer.traced`` counts the times a
round program was traced, and ``repro.dispatch`` carries its change.
"""
import dataclasses
import glob
import os
import warnings

import jax
import numpy as np
import pytest

from repro.api import ElasticSession, RunSpec
from repro.configs.base import ElasticConfig, OptimizerConfig
from repro.core.coordinator import ElasticTrainer

CHILDREN = ("repro.build_batches", "repro.round_keys", "repro.to_device",
            "repro.dispatch", "repro.fetch", "repro.records",
            "repro.observers")


def _spec(rounds=4, rounds_per_call=2, **elastic):
    e = dict(num_workers=2, tau=1, alpha=0.1, comm_mode="fused")
    e.update(elastic)
    return RunSpec(arch="paper-cnn", smoke=True,
                   optimizer=OptimizerConfig(name="sgd", lr=0.01),
                   elastic=ElasticConfig(**e), rounds=rounds,
                   rounds_per_call=rounds_per_call, seed=3, batch_size=4,
                   n_data=64, n_test=8)


def _events(trace_dir):
    """Every ``repro.*`` host event of the trace: (name, start, end, args),
    sorted by start."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    with warnings.catch_warnings():
                        warnings.simplefilter("ignore", DeprecationWarning)
                        args = dict(ev.stats)
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, args))
    return sorted(out, key=lambda e: e[1])


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """Two chunks of two rounds under the profiler, with what the session
    handed to ``round_chunk`` and got back from it."""
    session = ElasticSession(_spec())
    calls = []
    jitted = type(session.trainer).round_chunk

    def capture(state, inputs):
        out = jitted(session.trainer, state, inputs)
        calls.append((inputs, out[1]))
        return out

    session.trainer.round_chunk = capture
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    try:
        session.run(2)
        session.run(2)
    finally:
        jax.profiler.stop_trace()
    del session.trainer.round_chunk
    return session, calls, _events(d)


def test_one_chunk_span_holds_its_children_in_order(traced_run):
    _, _, events = traced_run
    chunks = [e for e in events if e[0] == "repro.chunk"]
    assert [c[3]["step_num"] for c in chunks] == [0, 2]
    assert all(c[3]["rounds"] == 2 for c in chunks)
    for name, lo, hi, args in chunks:
        inside = [e for e in events
                  if e[0] != "repro.chunk" and lo <= e[1] and e[2] <= hi]
        assert all(e[3]["chunk"] == args["step_num"] for e in inside)
        # the keys' stack runs between the input copies, in a round_keys
        # span of its own inside to_device
        nested = [e for e in inside if any(
            o is not e and o[1] <= e[1] and e[2] <= o[2] for o in inside)]
        to_dev, = [e for e in inside if e[0] == "repro.to_device"]
        assert [e[0] for e in nested] == ["repro.round_keys"]
        assert to_dev[1] <= nested[0][1] and nested[0][2] <= to_dev[2]
        children = [e for e in inside if e not in nested]
        assert tuple(e[0] for e in children) == CHILDREN
        # siblings do not overlap
        assert all(a[2] <= b[1] for a, b in zip(children, children[1:]))
    assert len(events) == len(chunks) * (2 + len(CHILDREN))


def test_transfer_counters(traced_run):
    _, calls, events = traced_run
    to_dev = [e[3] for e in events if e[0] == "repro.to_device"]
    fetch = [e[3] for e in events if e[0] == "repro.fetch"]
    assert len(calls) == len(to_dev) == len(fetch) == 2
    for (inputs, metrics), h2d, d2h in zip(calls, to_dev, fetch):
        # every leaf but the round keys, which are made on the device
        copied = jax.tree.leaves(dataclasses.replace(inputs, rng=None))
        assert h2d["h2d_transfers"] == len(copied)
        assert h2d["h2d_bytes"] == sum(x.nbytes for x in copied)
        leaves = jax.tree.leaves(metrics)
        assert d2h["d2h_transfers"] == len(leaves)
        assert d2h["d2h_bytes"] == sum(x.nbytes for x in leaves)


def test_retrace_counter(traced_run):
    session, _, events = traced_run
    assert [e[3]["traced"] for e in events
            if e[0] == "repro.dispatch"] == [1, 0]
    assert session.trainer.traced == 1
    # a second session's trainer is a new static argument: its chunk is
    # traced (and lowered) once more
    again = ElasticSession(_spec())
    again.run(2)
    assert again.trainer.traced == 1
    again.run(2)
    assert again.trainer.traced == 1


def test_retrace_counter_round_step():
    session = ElasticSession(_spec(rounds=2, rounds_per_call=1))
    session.run()
    assert session.trainer.traced == 1


def test_plain_session_spans(tmp_path):
    session = ElasticSession(RunSpec(
        arch="paper-cnn", smoke=True, plain=True, rounds=2,
        rounds_per_call=2, optimizer=OptimizerConfig(name="sgd", lr=0.01),
        batch_size=4, n_data=64, n_test=8))
    jax.profiler.start_trace(str(tmp_path))
    try:
        session.run()
    finally:
        jax.profiler.stop_trace()
    names = [e[0] for e in _events(str(tmp_path))]
    # the plain step copies its batches before it derives the round keys
    assert names == ["repro.chunk", "repro.build_batches", "repro.to_device",
                     "repro.round_keys", *CHILDREN[3:]]


@pytest.mark.parametrize("elastic,scopes", [
    ({}, ("local_phase", "comm_phase")),
    ({"num_workers": 4, "groups": 2, "global_period": 2},
     ("local_phase", "comm_phase", "global_sync")),
    ({"failure_scenario": "crash_restart", "failure_prob": 0.5},
     ("reseat", "local_phase", "comm_phase")),
], ids=["flat", "hierarchical", "restarts"])
def test_compiled_round_chunk_names_its_phases(traced_run, elastic, scopes):
    _, calls, _ = traced_run
    session = ElasticSession(_spec(**elastic))
    trainer = session.trainer
    k = session.capacity
    inputs, _ = calls[0]
    shape = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    R = inputs.fail.shape[0]
    batches = {key: jax.ShapeDtypeStruct(
        v.shape[:2] + (k,) + v.shape[3:], v.dtype)
        for key, v in inputs.batches.items()}
    mask = jax.ShapeDtypeStruct((R, k), np.bool_)
    restart = mask if session.schedule.has_restarts else None
    inputs = dataclasses.replace(inputs, batches=batches,
                                 rng=shape(inputs.rng), fail=mask,
                                 failed_recent=mask, restart=restart)
    state = jax.tree.map(shape, session.state)
    text = ElasticTrainer.round_chunk.lower(
        trainer, state, inputs).compile().as_text()
    names = [line.split('op_name="', 1)[1].split('"', 1)[0]
             for line in text.splitlines() if 'op_name="' in line]
    for scope in scopes:
        assert any(f"/{scope}/" in f"/{n}/" for n in names), scope
    if "global_sync" not in scopes:
        assert not any("/global_sync/" in f"/{n}/" for n in names)
    assert trainer.traced == 1
