"""Sharded placement (ISSUE-4): mesh axis shapes, config/trainer
validation, state donation, and the core acceptance property — the
shard_mapped worker axis produces master params bit-exact with the
single-device fused path.

The multi-device checks run in a subprocess (the device count is locked at
jax init; ``--xla_force_host_platform_device_count=4`` forces a 4-device
CPU host). The in-process checks run on the default single device, where a
pod=1 mesh exercises the full shard_map code path.
"""
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ElasticConfig, OptimizerConfig, get_config
from repro.core.coordinator import ElasticTrainer
from repro.models.registry import build_model

ROOT = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# mesh builders: axis shapes
# ---------------------------------------------------------------------------

def test_production_mesh_axis_shapes(monkeypatch):
    """Both production meshes request the documented (shape, axes) pairs —
    checked by capturing the jax.make_mesh call, since building them needs
    256/512 real devices."""
    import repro.launch.mesh as mesh_mod

    calls = []
    monkeypatch.setattr(mesh_mod.jax, "make_mesh",
                        lambda shape, axes, **kw: calls.append((shape, axes)))
    mesh_mod.make_production_mesh()
    mesh_mod.make_production_mesh(multi_pod=True)
    assert calls[0] == ((16, 16), ("data", "model"))
    assert calls[1] == ((2, 16, 16), ("pod", "data", "model"))


def test_host_mesh_axis_shapes(monkeypatch):
    import repro.launch.mesh as mesh_mod

    calls = []
    monkeypatch.setattr(mesh_mod.jax, "make_mesh",
                        lambda shape, axes, **kw: calls.append((shape, axes)))
    mesh_mod.make_host_mesh()
    mesh_mod.make_host_mesh(pod=4)
    mesh_mod.make_host_mesh(pod=2, data=3, model=5)
    assert calls == [((1, 1, 1), ("pod", "data", "model")),
                     ((4, 1, 1), ("pod", "data", "model")),
                     ((2, 3, 5), ("pod", "data", "model"))]


def test_host_mesh_real_single_device():
    """On the default 1-device host the trivial mesh actually builds, with
    all three axes present (uniform axis names across host/production)."""
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh()
    assert dict(mesh.shape) == {"pod": 1, "data": 1, "model": 1}


# ---------------------------------------------------------------------------
# config / trainer validation
# ---------------------------------------------------------------------------

def test_placement_validated():
    with pytest.raises(ValueError):
        ElasticConfig(placement="nope")


def test_sharded_requires_fused_comm():
    with pytest.raises(ValueError, match="fused"):
        ElasticConfig(placement="sharded", comm_mode="sequential")
    ElasticConfig(placement="sharded", comm_mode="fused")  # ok


def _sharded_trainer(k, mesh):
    model = build_model(get_config("paper_cnn"))
    return ElasticTrainer(
        model, OptimizerConfig(name="sgd", lr=0.01),
        ElasticConfig(num_workers=k, comm_mode="fused",
                      placement="sharded"), mesh=mesh)


def test_sharded_trainer_requires_mesh():
    with pytest.raises(ValueError, match="mesh"):
        _sharded_trainer(4, None)


def test_sharded_trainer_requires_pod_axis():
    mesh = jax.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="pod"):
        _sharded_trainer(4, mesh)


def test_sharded_trainer_requires_divisible_workers():
    class FakeMesh:
        shape = {"pod": 3}
        axis_names = ("pod",)

    with pytest.raises(ValueError, match="divide"):
        _sharded_trainer(4, FakeMesh())


def test_session_rejects_mesh_under_single_placement():
    """A mesh passed to a single-placement session would be silently
    ignored — that's a misconfiguration, surfaced at construction."""
    from repro.api import ElasticSession, RunSpec
    from repro.launch.mesh import make_host_mesh

    spec = RunSpec(arch="paper-cnn",
                   elastic=ElasticConfig(num_workers=2))
    with pytest.raises(ValueError, match="placement"):
        ElasticSession(spec, mesh=make_host_mesh())


# ---------------------------------------------------------------------------
# donation: round state buffers are single-buffered
# ---------------------------------------------------------------------------

def test_round_state_donated():
    """round_step donates its state: the input buffers are consumed (reuse
    raises), so chunked runs stop double-buffering the (k × params) worker
    state. Result-equality under donation is asserted by
    tests/test_scenarios.py::test_round_chunk_scans_stacked_inputs and the
    session equivalence suite."""
    from repro.core.coordinator import RoundInputs

    model = build_model(get_config("paper_cnn"))
    tr = ElasticTrainer(model, OptimizerConfig(name="sgd", lr=0.01),
                        ElasticConfig(num_workers=2, tau=1))
    state = tr.init_state(jax.random.key(0))
    probe = jax.tree.leaves(state["workers"])[0]
    batches = {
        "images": jnp.zeros((1, 2, 4, 28, 28, 1), jnp.float32),
        "labels": jnp.zeros((1, 2, 4), jnp.int32),
    }
    new_state, _ = tr.round_step(state, RoundInputs(
        batches=batches, rng=jax.random.key(1),
        fail=jnp.zeros(2, bool), failed_recent=jnp.zeros(2, bool)))
    assert probe.is_deleted()
    assert not jax.tree.leaves(new_state["workers"])[0].is_deleted()


# ---------------------------------------------------------------------------
# pod=1 shard_map path on the default single device
# ---------------------------------------------------------------------------

def test_sharded_pod1_matches_single_bit_exact():
    """placement='sharded' over a trivial pod=1 mesh runs the whole
    shard_map machinery on one device and must match single placement
    bit-for-bit (k_loc == k, so even the vmap widths agree)."""
    from repro.api import ElasticSession, RunSpec

    def run(placement):
        spec = RunSpec(
            arch="paper-cnn", optimizer=OptimizerConfig(name="sgd", lr=0.01),
            elastic=ElasticConfig(num_workers=2, tau=1, dynamic=True,
                                  comm_mode="fused", placement=placement),
            rounds=2, seed=1, batch_size=4, n_data=64, n_test=32)
        sess = ElasticSession(spec)
        return sess, sess.run()

    s1, r1 = run("single")
    s2, r2 = run("sharded")
    for a, b in zip(jax.tree.leaves(s1.master_params),
                    jax.tree.leaves(s2.master_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(r1, r2):
        assert a.loss == b.loss
        np.testing.assert_array_equal(a.h2, b.h2)


# ---------------------------------------------------------------------------
# the acceptance property, on a real 4-device host mesh (subprocess)
# ---------------------------------------------------------------------------

_SUBPROCESS_EQUIV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import jax
import numpy as np
from repro.api import ElasticSession, RunSpec
from repro.configs.base import ElasticConfig, OptimizerConfig

assert jax.device_count() == 4

def run(placement, k, scenario, rpc):
    spec = RunSpec(
        arch="paper-cnn", optimizer=OptimizerConfig(name="sgd", lr=0.01),
        elastic=ElasticConfig(num_workers=k, tau=2, dynamic=True,
                              comm_mode="fused", placement=placement,
                              failure_scenario=scenario),
        rounds=4, rounds_per_call=rpc, seed=1, batch_size=4,
        n_data=96, n_test=32)
    sess = ElasticSession(spec)
    return sess, sess.run()

cases = ([(4, s, rpc) for s in ("iid", "crash_restart") for rpc in (1, 2)]
         + [(8, "straggler", 2)])
for k, scenario, rpc in cases:
    s1, r1 = run("single", k, scenario, rpc)
    s2, r2 = run("sharded", k, scenario, rpc)
    assert s2.mesh.shape["pod"] == 4
    for a, b in zip(jax.tree.leaves(s1.master_params),
                    jax.tree.leaves(s2.master_params)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            (k, scenario, rpc, "master not bit-exact")
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(a.h2, b.h2)
        np.testing.assert_array_equal(a.u, b.u)
        # the scalar mean-loss metric may differ in the last ulp (its
        # totals are psum-reduced per shard, re-associating the sum); the
        # state itself is exact
        np.testing.assert_allclose(a.loss, b.loss, rtol=1e-6)
    print("OK", k, scenario, rpc)
print("EQUIV_OK")
"""

_SUBPROCESS_LOWERING = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import jax
import repro.launch.dryrun as dr
from repro.configs.base import ShapeConfig, get_config
from repro.launch.mesh import make_host_mesh

# the real dryrun elastic branch, shrunk: 2 pods x 2-way model axis, smoke
# config, tiny train shape
dr.make_production_mesh = lambda multi_pod=False: make_host_mesh(
    pod=2, data=1, model=2)
dr.get_config = lambda arch, smoke=False: get_config(arch, smoke=True)
dr.INPUT_SHAPES["tiny_train"] = ShapeConfig("tiny_train", 64, 4, "train")
out = dr.dryrun_one("qwen3_4b", "tiny_train", multi_pod=True)
assert out["status"] == "ok", out
assert out["lowered_kind"] == "elastic_round_step_sharded"
assert out["devices"] == 4
# capacity-padded pool (ISSUE-5): capacity 3 pads to 4 over the 2-way pod
# axis and lowers the membership-masked round (active/join inputs)
out = dr.dryrun_one("qwen3_4b", "tiny_train", multi_pod=True,
                    elastic_capacity=3)
assert out["status"] == "ok", out
print("LOWERING_OK")
"""


def _run_sub(code, timeout):
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)


def test_sharded_master_bit_exact_vs_single_4dev():
    """The ISSUE-4 acceptance bar: on a forced 4-device host mesh, sharded
    placement reproduces the single-device fused master bit-for-bit across
    {iid, crash_restart} (k=4, both per-round and chunked execution) and
    under straggler stale-master scoring at k=8 (two workers per shard)."""
    out = _run_sub(_SUBPROCESS_EQUIV, timeout=540)
    assert "EQUIV_OK" in out.stdout, out.stdout + out.stderr[-3000:]


def test_dryrun_elastic_branch_lowers_sharded_fn():
    """launch/dryrun's multi-pod train branch lowers the *real*
    ``ElasticTrainer._round_sharded`` (no dryrun-private round lowering),
    here against a shrunk 2-pod mesh with a nontrivial 'model' axis."""
    out = _run_sub(_SUBPROCESS_LOWERING, timeout=540)
    assert "LOWERING_OK" in out.stdout, out.stdout + out.stderr[-3000:]
