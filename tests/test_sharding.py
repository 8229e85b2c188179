"""Sharding rules: divisibility dropping, axis-uniqueness, mesh handling.

Pure PartitionSpec logic runs on the default single device; an 8-device
integration lowering runs in a subprocess (device count is locked at jax
init)."""
import json
import os
import subprocess
import sys

import pytest
from _property_shim import given, strategies as st
from jax.sharding import PartitionSpec as P

import jax

from repro.nn.param import ParamSpec
from repro.nn.sharding import batch_spec, physical_spec

ROOT = os.path.join(os.path.dirname(__file__), "..")


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape

    @property
    def devices(self):
        import numpy as np

        return np.empty(tuple(self.shape.values()))


MESH = FakeMesh({"data": 16, "model": 16})
MESH3 = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_divisible_dims_shard():
    spec = physical_spec((4096, 2560), ("mlp", "embed"), MESH)
    assert spec == P("model", "data")


def test_non_divisible_axis_dropped():
    # 8 kv heads on a 16-way model axis → replicated
    spec = physical_spec((8, 128), ("kv_heads", None), MESH)
    assert spec == P()


def test_axis_used_once():
    # both dims want 'model'; first wins, second replicates
    spec = physical_spec((32, 32), ("heads", "mlp"), MESH)
    assert spec == P("model")


def test_tuple_axis_partial_divisibility():
    # seq wants ('data','model'); 16 divides, 256 doesn't fit twice? 512 does
    spec = physical_spec((512, 4), ("seq_shard", None), MESH)
    assert spec == P(("data", "model"))
    spec = physical_spec((16, 4), ("seq_shard", None), MESH)
    assert spec == P("data")


def test_pod_axis_ignored_on_single_pod_mesh():
    spec = physical_spec((2, 100), ("worker", None), MESH)
    assert spec == P()
    spec3 = physical_spec((2, 100), ("worker", None), MESH3)
    assert spec3 == P("pod")


@given(b=st.sampled_from([1, 2, 4, 16, 32, 256, 100, 3]))
def test_batch_spec_always_valid(b):
    spec = batch_spec(b, MESH3)
    prod = 1
    for ax in (spec[0] if isinstance(spec[0], tuple) else
               ([spec[0]] if spec[0] else [])):
        prod *= MESH3.shape[ax]
    assert b % prod == 0


@pytest.mark.slow
def test_eight_device_lowering_subprocess():
    """Real NamedSharding lowering on an 8-device host mesh (2×4)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.configs.base import get_config, OptimizerConfig, ShapeConfig
from repro.models.registry import build_model
from repro.nn.param import abstract_tree
from repro.nn.sharding import tree_pspecs
from repro.train.steps import (abstract_train_state, make_train_step,
                               train_state_pspecs)

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
model = build_model(get_config("qwen3_4b", smoke=True))
ocfg = OptimizerConfig(name="adahessian")
shape = ShapeConfig("t", 64, 4, "train")
state = abstract_train_state(model, ocfg)
named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                               is_leaf=lambda x: isinstance(x, P))
state_sh = named(train_state_pspecs(model, ocfg, mesh))
specs = model.input_specs(shape)
batch = {k: jax.ShapeDtypeStruct(s.shape, s.dtype) for k, s in specs.items()}
batch_sh = {k: NamedSharding(mesh, P("data")) for k in specs}
step = make_train_step(model, ocfg)
with mesh:
    lowered = jax.jit(step, in_shardings=(state_sh, batch_sh,
                                          NamedSharding(mesh, P()))).lower(
        state, batch, jax.ShapeDtypeStruct((2,), jnp.uint32))
    compiled = lowered.compile()
ca = compiled.cost_analysis()
print("COMPILED_OK", ca["flops"] > 0)
"""
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=540)
    assert "COMPILED_OK True" in out.stdout, out.stderr[-2000:]
