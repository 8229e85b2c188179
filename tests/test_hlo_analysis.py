"""HLO text parsers: collective accounting + loop-aware cost model."""
import numpy as np
import pytest

from repro.analysis.hlo import collective_bytes
from repro.analysis.hlo_cost import loop_aware_costs

SIMPLE = """
HloModule test

ENTRY %main (p0: f32[128,64]) -> f32[128,64] {
  %p0 = f32[128,64]{1,0} parameter(0)
  %ar = f32[128,64]{1,0} all-reduce(%p0), replica_groups={}
  %ag = bf16[256,64]{1,0} all-gather(%p0), dimensions={0}
  ROOT %out = f32[128,64]{1,0} add(%ar, %ar)
}
"""


def test_collective_bytes_simple():
    c = collective_bytes(SIMPLE)
    assert c["all-reduce"] == 128 * 64 * 4
    assert c["all-gather"] == 256 * 64 * 2
    assert c["total"] == 128 * 64 * 4 + 256 * 64 * 2


LOOPED = """
HloModule test

%cond (arg: (s32[], f32[8,8])) -> pred[] {
  %arg = (s32[], f32[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %limit = s32[] constant(12)
  ROOT %lt = pred[] compare(%i, %limit), direction=LT
}

%body (arg: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %arg = (s32[], f32[8,8]{1,0}) parameter(0)
  %x = f32[8,8]{1,0} get-tuple-element(%arg), index=1
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %arx = f32[8,8]{1,0} all-reduce(%d), replica_groups={}
  %i = s32[] get-tuple-element(%arg), index=0
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]{1,0}) tuple(%i2, %arx)
}

ENTRY %main (p: f32[8,8]) -> f32[8,8] {
  %p = f32[8,8]{1,0} parameter(0)
  %big = f32[16,8]{1,0} dot(%p, %p), lhs_contracting_dims={0}, rhs_contracting_dims={0}
  %init = (s32[], f32[8,8]{1,0}) tuple(%p, %p)
  %w = (s32[], f32[8,8]{1,0}) while(%init), condition=%cond, body=%body
  ROOT %o = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""


def test_loop_aware_flops_multiplied():
    c = loop_aware_costs(LOOPED)
    body_dot = 2 * 8 * 8 * 8          # one 8×8×8 dot per iteration
    entry_dot = 2 * 16 * 8 * 8        # dims are parsed from result+lhs
    assert c["dot_flops"] == pytest.approx(entry_dot + 12 * body_dot)
    assert c["dot_flops_trip1"] == pytest.approx(entry_dot + body_dot)
    # collective inside the loop is ×12
    assert c["coll_total"] == pytest.approx(12 * 8 * 8 * 4)
    assert c["coll_total_trip1"] == pytest.approx(8 * 8 * 4)
    # multipliers feed the calibration
    assert c["coll_total"] / c["coll_total_trip1"] == pytest.approx(12.0)


def test_loop_aware_bytes_positive_and_scaled():
    c = loop_aware_costs(LOOPED)
    assert c["bytes"] > c["bytes_trip1"] > 0


def test_collective_done_not_double_counted():
    txt = """
ENTRY %main (p0: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %s = f32[64]{0} all-gather-start(%p0), dimensions={0}
  ROOT %d = f32[64]{0} all-gather-done(%s)
}
"""
    c = collective_bytes(txt)
    assert c["all-gather"] == 64 * 4  # start counted once, done skipped


def test_real_compiled_module_roundtrip():
    """End-to-end: compile a tiny scanned model, check loop multiplication."""
    import jax
    import jax.numpy as jnp

    def step(x, _):
        return x @ w, None

    w = jnp.ones((32, 32))

    def f(x):
        y, _ = jax.lax.scan(step, x, None, length=7)
        return y

    compiled = jax.jit(f).lower(jnp.ones((4, 32))).compile()
    c = loop_aware_costs(compiled.as_text())
    one_dot = 2 * 4 * 32 * 32
    assert c["dot_flops"] == pytest.approx(7 * one_dot, rel=0.01)


FUSED_SLICE = """
HloModule test

%fused_computation.1 (param_0.1: f32[64,128], param_1.1: s32[]) -> f32[1,128] {
  %param_0.1 = f32[64,128]{1,0} parameter(0)
  %param_1.1 = s32[] parameter(1)
  %c0 = s32[] constant(0)
  ROOT %ds = f32[1,128]{1,0} dynamic-slice(%param_0.1, %param_1.1, %c0), dynamic_slice_sizes={1,128}
}

ENTRY %main (p: f32[64,128], i: s32[]) -> f32[1,128] {
  %p = f32[64,128]{1,0} parameter(0)
  %i = s32[] parameter(1)
  ROOT %f = f32[1,128]{1,0} fusion(%p, %i), kind=kLoop, calls=%fused_computation.1
}
"""


def test_fusion_sliced_param_charged_slice_bytes():
    """A fusion whose param is consumed by an internal dynamic-slice reads
    only the slice from HBM — the parser must not charge the full 64×128."""
    c = loop_aware_costs(FUSED_SLICE)
    full = 64 * 128 * 4
    slice_b = 1 * 128 * 4
    # result + sliced param (not full) + s32 index
    assert c["bytes"] < full, c["bytes"]
    assert c["bytes"] >= 2 * slice_b


def test_roofline_peaks_keyed_by_device_kind():
    from repro.analysis.roofline import DRYRUN_DEVICE_KIND, peaks

    v5e = peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source
    assert peaks(DRYRUN_DEVICE_KIND) is v5e
    with pytest.raises(ValueError, match="no published peaks"):
        peaks("cpu")
