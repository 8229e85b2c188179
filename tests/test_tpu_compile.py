"""The training-path kernels and the whole paper-cnn round compile for a
TPU v5e, checked without a chip: the TPU compiler compiles against a
described ``v5e:2x2`` topology, and the compiled module must hold the Pallas
kernel as a ``tpu_custom_call`` (compiled, not interpreted).

This is the only test file that describes the chip. Only one process at a
time may load the TPU's library, so the topology is described inside a
module-scoped fixture, never at import time: every test worker collects the
same tests, and only the worker given this file loads the library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ElasticConfig, OptimizerConfig, get_config
from repro.core.coordinator import ElasticTrainer, RoundInputs
from repro.kernels.adahessian.kernel import adahessian_update_batched_flat
from repro.kernels.elastic.kernel import elastic_update_batched_flat
from repro.kernels.flash_attention.ops import flash_attention_bshd
from repro.models.registry import build_model

# paper-cnn's 1.2M parameters as (rows, 128) lanes, rounded to whole tiles
ROWS = 9216


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on one described chip, with the persistent compilation
    cache off: entries compiled for a described chip cannot be read back
    without one."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _struct(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_calls(compiled):
    return [line for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("k,dtype,stale", [
    (4, jnp.float32, False),
    (32, jnp.bfloat16, False),  # the row tile drops to 8 rows
    (4, jnp.float32, True),     # delayed averaging: the ref= variant
])
def test_elastic_kernel_compiles(one_chip, k, dtype, stale):
    s = lambda shape, d=dtype: _struct(one_chip, shape, d)
    ref = s((ROWS, 128)) if stale else None
    compiled = elastic_update_batched_flat.lower(
        s((k, ROWS, 128)), s((ROWS, 128)), s((k,), jnp.float32),
        s((k,), jnp.float32), ref, interpret=False).compile()
    calls = _kernel_calls(compiled)
    assert len(calls) == 1 and "elastic_update_batched" in calls[0]


@pytest.mark.parametrize("k,dtype", [(4, jnp.float32), (16, jnp.bfloat16)])
def test_adahessian_kernel_compiles(one_chip, k, dtype):
    s = lambda d: _struct(one_chip, (k, ROWS, 128), d)
    bc = _struct(one_chip, (k,))
    compiled = adahessian_update_batched_flat.lower(
        s(dtype), s(dtype), s(dtype), s(jnp.float32), s(jnp.float32), bc, bc,
        lr=0.01, b1=0.9, b2=0.999, denom_pow=0.5, eps=1e-8,
        interpret=False).compile()
    calls = _kernel_calls(compiled)
    assert len(calls) == 1 and "adahessian_update_batched" in calls[0]


@pytest.mark.parametrize("window", [None, 1024])
def test_flash_attention_compiles_at_qwen3_4b_heads(one_chip, window):
    cfg = get_config("qwen3-4b")
    seq = 2048
    q = _struct(one_chip, (1, seq, cfg.num_heads, cfg.hd), jnp.bfloat16)
    kv = _struct(one_chip, (1, seq, cfg.num_kv_heads, cfg.hd), jnp.bfloat16)
    fn = jax.jit(lambda q, k, v: flash_attention_bshd(
        q, k, v, causal=True, window=window, interpret=False))
    assert _kernel_calls(fn.lower(q, kv, kv).compile())


def test_paper_cnn_round_chunk_compiles(one_chip, monkeypatch):
    """The whole jitted 2-round chunk of the paper's k=4, τ=2 AdaHessian
    round with both Pallas kernels: flattening and padding, scalar
    prefetch inside ``lax.scan``, and the comm phase. The model's 3×3
    convolutions are XLA's convolution at the matmul precision in force,
    and no im2col patch tensor (24×24 outputs of conv2 by 9 taps of 32
    channels) is built."""
    import repro.kernels

    # the coordinator asks interpret_mode() at trace time; this process's
    # backend is the CPU, the compile target is the described TPU
    monkeypatch.setattr(repro.kernels, "interpret_mode",
                        lambda interpret=None: False)
    k, tau, rounds, batch = 4, 2, 2, 32
    ecfg = ElasticConfig(num_workers=k, tau=tau, comm_mode="fused",
                         failure_prob=1 / 3)
    trainer = ElasticTrainer(build_model(get_config("paper-cnn")),
                             OptimizerConfig(name="adahessian"), ecfg,
                             use_pallas=True)
    place = lambda t: jax.tree.map(
        lambda x: _struct(one_chip, x.shape, x.dtype), t)
    state = place(jax.eval_shape(trainer.init_state, jax.random.key(0)))
    lead = (rounds, tau, k, batch)
    inputs = RoundInputs(
        batches={"images": _struct(one_chip, lead + (28, 28, 1)),
                 "labels": _struct(one_chip, lead, jnp.int32)},
        rng=place(jax.eval_shape(
            lambda: jax.random.split(jax.random.key(0), rounds))),
        fail=_struct(one_chip, (rounds, k), jnp.bool_),
        failed_recent=_struct(one_chip, (rounds, k), jnp.bool_))
    with jax.default_matmul_precision("highest"):
        compiled = ElasticTrainer.round_chunk.lower(trainer, state,
                                                    inputs).compile()
    calls = "\n".join(_kernel_calls(compiled))
    assert "adahessian_update_batched" in calls
    assert "elastic_update_batched" in calls
    hlo = compiled.as_text()
    convs = [line for line in hlo.splitlines() if " convolution(" in line]
    # the workers' batched dots lower to convolutions too; only the conv
    # layers' own have a 3×3 spatial window, and carry the `conv` scope
    # (wrapped by the transforms, as in `vmap(jvp(conv))`)
    layers = [line for line in convs if "window={size=3x3" in line]
    assert layers
    for line in layers:
        path = re.search(r'op_name="([^"]*)"', line).group(1)
        assert "conv" in {re.sub(r"^(\w+\()+|\)+$", "", part)
                          for part in path.split("/")}, path
    assert all("operand_precision={highest,highest}" in line
               for line in convs)
    assert not re.search(r"24,24,(9,32|288)\]", hlo)
