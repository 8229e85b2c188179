"""The scope reader that sees through JAX's transforms, on hand-made
operations and spans."""
import pytest

from bench import program_trace as P
from bench import scope_report as S
from bench import trace as T

FWD = "jit(round_chunk)/while/body/closed_call/local_phase/vmap(jvp(jvp(conv)))/conv_general_dilated"
BWD = "jit(round_chunk)/while/body/closed_call/local_phase/vmap(jvp(transpose(jvp(conv))))/transpose"
FC = "jit(round_chunk)/while/body/closed_call/local_phase/vmap(jvp(jvp()))/dot_general"


def test_unwrap_strips_the_transforms():
    assert S.unwrap("vmap(jvp(transpose(jvp(conv))))") == "conv"
    assert S.unwrap("conv") == "conv"
    assert S.unwrap("vmap(jvp(jvp()))") == ""


def test_scope_matches_through_the_transforms():
    assert S.in_scope(FWD, "conv") and S.in_scope(BWD, "conv")
    assert not P.in_scope(FWD, "conv")  # the strict reader misses it
    assert S.in_scope(FWD, "local_phase")
    assert not S.in_scope(FC, "conv")
    assert not S.in_scope(FWD, "con")
    assert not S.in_scope(None, "conv")


def _pt():
    ops = [("fusion.1", 0, 40, FWD), ("fusion.2", 30, 60, BWD),
           ("fusion.3", 70, 90, FC), ("fusion.1", 100, 140, FWD)]
    program = [P.Span("repro.chunk", 0, 200, {"step_num": 0, "rounds": 2}),
               P.Span("repro.dispatch", 5, 10, {"chunk": 0}),
               P.Span("repro.dispatch", 105, 110, {"chunk": 2}),
               P.Span("repro.dispatch", 205, 210, {"chunk": 4})]
    return P.ProgramTrace(
        trace=T.Trace(devices=[[T.Op(n, s, e) for n, s, e, _ in ops]],
                      spans=[T.Op("bench.window", 0, 200)]),
        program=program, scopes=[{n: sc for n, _, _, sc in ops}])


def test_rounds_and_scope_time_per_round():
    pt = _pt()
    window = (0, 200)
    assert S.rounds_in_window(pt, window) == 4  # two dispatches of 2 rounds
    # conv ops cover [0, 60) and [100, 140): 100 ns over 4 rounds
    assert S.scope_ms_per_round(pt, "conv", window, 4) == pytest.approx(
        100 / 4 / 1e6)
    assert S.scope_ms_per_round(pt, "local_phase", window, 4) == \
        pytest.approx(120 / 4 / 1e6)
    assert S.scope_ms_per_round(pt, "comm_phase", window, 4) is None


def test_report_ranks_operations_and_categories():
    pt = _pt()
    info = {"fusion.1": ("f32[4,32,24,24,64]", "convolution fusion"),
            "fusion.2": ("f32[4,3,3,32,64]", "convolution fusion"),
            "fusion.3": ("f32[4,128]", "loop fusion")}
    got = S.report(pt, info, ["conv"], top=2)
    assert got["rounds"] == 4
    assert [o["name"] for o in got["top_ops"]] == ["fusion.1", "fusion.2"]
    assert got["top_ops"][0]["shape"] == "f32[4,32,24,24,64]"
    assert got["category_share_of_op_time"] == pytest.approx(
        {"convolution fusion": 110 / 130, "loop fusion": 20 / 130})
