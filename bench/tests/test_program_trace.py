"""The reduction of the program's own spans and scopes: on hand-made
operations and spans, and on a trace of a session recorded on the CPU."""
import jax
import pytest

from bench import program_trace as P
from bench import trace as T

LOCAL = "jit(round_chunk)/while/body/closed_call/local_phase/dot_general"
COMM = "jit(round_chunk)/while/body/closed_call/comm_phase/mul"


def ptrace(*devices, program=(), bench=()):
    """A ProgramTrace from devices of (name, start, end, scope) operations,
    scope None for an operation with no scope recorded."""
    return P.ProgramTrace(
        trace=T.Trace(devices=[[T.Op(n, s, e) for n, s, e, _ in d]
                               for d in devices],
                      spans=[T.Op(*b) for b in bench]),
        program=sorted(program, key=lambda s: s.start),
        scopes=[{n: sc for n, _, _, sc in d if sc is not None}
                for d in devices])


def spans(*spec):
    return [P.Span(n, s, e, dict(args)) for n, s, e, args in spec]


def test_scope_is_a_path_component():
    assert P.in_scope(LOCAL, "local_phase")
    assert not P.in_scope(LOCAL, "local")
    assert not P.in_scope(LOCAL, "comm_phase")
    assert not P.in_scope(None, "local_phase")


def test_scopes_from_event_metadata(tmp_path):
    """An operation's scope is the ``tf_op`` stat of its event metadata,
    held as a string or as a reference to an interned one, without the
    ``:<type>`` suffix, keyed by the name ``bench.trace`` gives it; a name
    two programs give to operations of different scopes has none."""
    space = P._xspace_class()()
    plane = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "flops"), (3, LOCAL + ":")):
        entry = plane.stat_metadata.add(key=key)
        entry.value.id, entry.value.name = key, name
    a = plane.event_metadata.add(key=10).value
    a.name = "%fusion.1 = f32[] fusion()"
    a.stats.add(metadata_id=2, str_value="7")
    a.stats.add(metadata_id=1, str_value=COMM + ":")
    b = plane.event_metadata.add(key=11).value
    b.name = "%copy.2 = f32[] copy()"
    b.stats.add(metadata_id=1, ref_value=3)
    plane.event_metadata.add(key=12).value.name = "%bare.3 = f32[] add()"
    for key, where in ((13, "jit(stack)/concatenate:"),
                       (14, "jit(fold_in)/concatenate:")):
        c = plane.event_metadata.add(key=key).value
        c.name = "%pad_add_fusion = f32[] fusion()"
        c.stats.add(metadata_id=1, str_value=where)
    space.planes.add(name="/host:CPU")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert P.scopes(str(path)) == {
        "/device:TPU:0": {"fusion.1": COMM, "copy.2": LOCAL,
                          "pad_add_fusion": None},
        "/host:CPU": {}}


def test_innermost_pieces():
    """Each instant once, under the innermost span: to_device less the
    round_keys nested in it, the chunk less all its children."""
    prog = spans(("repro.chunk", 0, 100, {"step_num": 4}),
                 ("repro.round_keys", 5, 10, {"chunk": 4}),
                 ("repro.to_device", 10, 40, {"chunk": 4}),
                 ("repro.round_keys", 20, 25, {"chunk": 4}),
                 ("repro.fetch", 40, 90, {"chunk": 4}))
    got = [(p.name, p.start, p.end) for p in P.innermost(prog)]
    assert got == [("repro.chunk", 0, 5), ("repro.round_keys", 5, 10),
                   ("repro.to_device", 10, 20), ("repro.round_keys", 20, 25),
                   ("repro.to_device", 25, 40), ("repro.fetch", 40, 90),
                   ("repro.chunk", 90, 100)]
    assert all(p.chunk == 4 for p in P.innermost(prog))
    window = (0, 100)
    # per chunk: the chunk's two round_keys spans add up; to_device leaves
    # the nested one out
    assert P.span_ms_per_chunk(prog, "repro.round_keys", window) == 10e-6
    assert P.span_ms_per_chunk(prog, "repro.to_device", window) == 25e-6
    assert P.span_ms_per_chunk(prog, "repro.chunk", window) == 15e-6


def test_phase_ms_per_round_scoped_and_unscoped():
    dev = [("fusion.1", 0, 30, LOCAL), ("fusion.2", 30, 40, COMM),
           ("copy.3", 40, 60, "jit(round_chunk)/while"),
           ("fusion.4", 100, 130, LOCAL), ("fusion.5", 130, 140, COMM)]
    window, rounds = (0, 200), 2
    pt = ptrace(dev)
    assert P.scope_ms_per_round(pt, "local_phase", window, rounds) == 30e-6
    assert P.scope_ms_per_round(pt, "comm_phase", window, rounds) == 10e-6
    # averaged over the devices; clipped to the window
    two = ptrace(dev, [("fusion.1", 0, 50, LOCAL)])
    assert P.scope_ms_per_round(two, "local_phase", (0, 120),
                                rounds) == (50 + 50) / 2 / 2 / 1e6
    # a program without the scope reads nothing
    bare = ptrace([("fusion.1", 0, 30, None)])
    assert P.scope_ms_per_round(bare, "local_phase", window, 2) is None
    got = P.scope_shares(pt, window)
    assert got["share"]["local_phase"] == pytest.approx(60 / 100)
    assert got["share"]["comm_phase"] == pytest.approx(20 / 100)
    assert got["unscoped_share"] == pytest.approx(20 / 100)
    assert got["unscoped_top"] == [["copy.3", 20e-9]]
    assert got["ambiguous"] == []


def test_to_device_median_per_chunk():
    prog = spans(("repro.to_device", 0, 4_000_000, {"chunk": 0}),
                 ("repro.to_device", 5_000_000, 7_000_000, {"chunk": 2}),
                 ("repro.to_device", 8_000_000, 11_000_000, {"chunk": 4}),
                 ("repro.fetch", 0, 10, {"chunk": 0}))
    window = (0, 11_000_000)
    assert P.span_ms_per_chunk(prog, "repro.to_device", window) == 3.0
    assert P.span_ms_per_chunk(prog, "repro.to_device",
                               (5, 11_000_000)) == 2.5
    assert P.span_ms_per_chunk(prog, "repro.records", window) is None


def test_transfers_per_chunk():
    prog = spans(
        ("repro.to_device", 0, 10, {"chunk": 0, "h2d_transfers": 4,
                                    "h2d_bytes": 3_000_000}),
        ("repro.fetch", 10, 20, {"chunk": 0, "d2h_transfers": 6,
                                 "d2h_bytes": 200}),
        ("repro.to_device", 30, 40, {"chunk": 2, "h2d_transfers": 4,
                                     "h2d_bytes": 3_000_000}),
        ("repro.fetch", 40, 50, {"chunk": 2, "d2h_transfers": 6,
                                 "d2h_bytes": 200}))
    assert P.transfers_per_chunk(prog, (0, 50)) == {
        "h2d_transfers": 4, "h2d_mb": 3.0,
        "d2h_transfers": 6, "d2h_mb": 200 / 1e6}
    # spans recorded without counters read nothing
    assert P.transfers_per_chunk(spans(("repro.fetch", 0, 5, {})),
                                 (0, 5))["d2h_transfers"] is None


def test_fetch_idle_busy_then_idle():
    """The device runs through the first 6 ms of each fetch and is idle in
    the rest of it."""
    ms = 1_000_000
    dev = [("fusion.1", 0, 16 * ms, LOCAL), ("fusion.2", 30 * ms, 46 * ms,
                                             LOCAL)]
    prog = spans(("repro.fetch", 10 * ms, 15 * ms, {"chunk": 0}),
                 ("repro.fetch", 40 * ms, 50 * ms, {"chunk": 2}))
    window = (0, 50 * ms)
    # chunk 0: busy through the whole fetch; chunk 2: idle 46..50
    assert P.idle_ms_in_span(ptrace(dev, program=prog), "repro.fetch",
                             window) == 2.0
    # averaged over devices: a second device idle through both fetches
    two = ptrace(dev, [("fusion.1", 0, ms, None)], program=prog)
    assert P.idle_ms_in_span(two, "repro.fetch", window) == (
        (0 + 5) / 2 + (4 + 10) / 2) / 2
    assert P.idle_ms_in_span(two, "repro.to_device", window) is None


def test_idle_by_span_and_labels_prefer_program_spans():
    dev = [("fusion.1", 10, 30, LOCAL), ("fusion.2", 60, 70, COMM)]
    window = (0, 100)
    prog = spans(("repro.chunk", 0, 90, {"step_num": 0, "rounds": 2}),
                 ("repro.to_device", 2, 8, {"chunk": 0}),
                 ("repro.dispatch", 8, 12, {"chunk": 0, "traced": 0}),
                 ("repro.fetch", 30, 58, {"chunk": 0}),
                 ("repro.records", 58, 80, {"chunk": 0}))
    bench = [("bench.wait_and_record", 12, 95)]
    pt = ptrace(dev, program=prog, bench=bench)
    # idle: 0..10, 30..60, 70..100
    got = P.idle_by_span(pt, window)
    assert got["repro.to_device"] == 6 and got["repro.dispatch"] == 2
    assert got["repro.fetch"] == 28 and got["repro.records"] == 12
    assert got["repro.chunk"] == 2 + 10       # 0..2, 80..90
    assert got["outside"] == 10               # 90..100
    assert sum(got.values()) == 100 - 30
    # 70..100: records 10, the chunk between its children 10, and the
    # bench span only where no program span is (90..95), not the 25 it
    # covers in all
    assert P.labelled_gaps(pt, window, n=3) == [
        ["repro.fetch@dev0", 30e-9], ["repro.records@dev0", 30e-9],
        ["repro.to_device@dev0", 10e-9]]
    # where no program span is, the bench span names the gap
    early = spans(("repro.chunk", 0, 60, {"step_num": 0, "rounds": 2}),
                  ("repro.fetch", 30, 58, {"chunk": 0}))
    assert P.labelled_gaps(ptrace(dev, program=early, bench=bench), window,
                           n=2) == [["repro.fetch@dev0", 30e-9],
                                    ["bench.wait_and_record@dev0", 30e-9]]
    rep = P.report(pt, window, rounds=2)
    assert rep["chunks"] == 1 and rep["traced_in_window"] == 0
    assert rep["idle_in_program_share"] == pytest.approx(60 / 70)
    assert rep["local_phase_ms"] == 10e-6 and rep["comm_phase_ms"] == 5e-6


def test_recorded_session_trace(tmp_path):
    """A paper-cnn session's two chunks, recorded on the CPU with a bench
    span around each: the benchmark's reduction reads its own spans and
    window as if the program had none, and the program's spans come back
    with their args."""
    from repro.api import ElasticSession, RunSpec
    from repro.configs.base import ElasticConfig, OptimizerConfig

    session = ElasticSession(RunSpec(
        arch="paper-cnn", optimizer=OptimizerConfig(name="sgd", lr=0.01),
        elastic=ElasticConfig(num_workers=2, tau=1, comm_mode="fused"),
        rounds=4, rounds_per_call=2, batch_size=4, n_data=64, n_test=8))
    session.run(2)   # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.chunk"):
        session.run(1)
    with jax.profiler.TraceAnnotation("bench.chunk"):
        session.run(1)
    jax.profiler.stop_trace()
    bt = T.load(str(tmp_path))
    assert [s.name for s in bt.spans] == ["bench.chunk"] * 2
    assert bt.window == (bt.spans[0].start, bt.spans[1].end)
    pt = P.load(str(tmp_path))
    assert pt.trace == bt
    assert pt.scopes == [{}]   # the CPU records no scope
    chunks = [s for s in pt.program if s.name == "repro.chunk"]
    assert [c.args for c in chunks] == [{"step_num": 2, "rounds": 1},
                                        {"step_num": 3, "rounds": 1}]
    to_dev = [s for s in pt.program if s.name == "repro.to_device"]
    assert all(s.args["h2d_transfers"] == 4 and s.args["h2d_bytes"] > 0
               for s in to_dev)
    assert [s.args["traced"] for s in pt.program
            if s.name == "repro.dispatch"] == [1, 0]  # round_step: new
    rep = P.report(pt, bt.window, rounds=2)
    assert rep["chunks"] == 2 and rep["to_device_ms"] > 0
    assert rep["fetch_idle_ms"] is not None
    assert rep["transfers_per_chunk"]["d2h_transfers"] == 6
    assert rep["local_phase_ms"] is None
