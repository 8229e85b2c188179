"""Per-kernel allclose vs pure-jnp oracles (interpret mode), with
shape/dtype sweeps as required for every Pallas kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import OptimizerConfig

pytestmark = pytest.mark.pallas  # interpret-mode kernel checks


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ops import flash_attention_bshd
from repro.kernels.flash_attention.ref import mha_reference


def _qkv(seed, B, H, KVH, S, D, dtype):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, H, S, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, KVH, S, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, KVH, S, D)).astype(dtype)
    return q, k, v


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("B,H,KVH,S,D", [
    (1, 2, 2, 128, 64),     # MHA
    (2, 4, 2, 256, 64),     # GQA
    (1, 8, 1, 128, 128),    # MQA, 128 lanes
])
def test_flash_shape_dtype_sweep(B, H, KVH, S, D, dtype, tol):
    q, k, v = _qkv(0, B, H, KVH, S, D, dtype)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("mask_kw", [
    dict(causal=True),
    dict(causal=False),
    dict(causal=True, window=96),
    dict(causal=True, window=17),
    dict(causal=True, chunk=64),
])
def test_flash_mask_variants(mask_kw):
    q, k, v = _qkv(1, 2, 2, 2, 256, 64, jnp.float32)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True,
                          **mask_kw)
    ref = mha_reference(q, k, v, **mask_kw)
    np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-5)


def test_flash_bshd_wrapper_matches_layers_layout():
    q, k, v = _qkv(2, 2, 4, 2, 128, 64, jnp.float32)
    o1 = flash_attention_bshd(jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
                              jnp.moveaxis(v, 1, 2), block_q=64, block_k=64)
    o2 = mha_reference(q, k, v)
    np.testing.assert_allclose(jnp.moveaxis(o1, 2, 1), o2, rtol=3e-5,
                               atol=3e-5)


# ---------------------------------------------------------------------------
# fused elastic update
# ---------------------------------------------------------------------------

from repro.core.elastic import elastic_update
from repro.kernels.elastic.ops import elastic_update_pallas


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shapes", [
    [(128,)], [(300, 17), (41,)], [(1000, 130), (5, 5, 5), ()],
])
def test_elastic_kernel_sweep(dtype, shapes):
    kw = jax.random.split(jax.random.key(0), 2 * len(shapes))
    w = {f"p{i}": jax.random.normal(kw[2 * i], s).astype(dtype)
         for i, s in enumerate(shapes)}
    m = {f"p{i}": jax.random.normal(kw[2 * i + 1], s).astype(dtype)
         for i, s in enumerate(shapes)}
    w1, m1 = elastic_update_pallas(w, m, 0.25, 0.07)
    w2, m2 = elastic_update(w, m, 0.25, 0.07)
    tol = 1e-6 if dtype == jnp.float32 else 1e-2
    for key in w:
        np.testing.assert_allclose(np.asarray(w1[key], np.float32),
                                   np.asarray(w2[key], np.float32),
                                   rtol=tol, atol=tol)
        np.testing.assert_allclose(np.asarray(m1[key], np.float32),
                                   np.asarray(m2[key], np.float32),
                                   rtol=tol, atol=tol)


def test_elastic_kernel_identity_cases():
    w = {"a": jnp.ones((256, 128))}
    m = {"a": jnp.zeros((256, 128))}
    # h1=1, h2=0: worker snaps to master, master untouched
    w1, m1 = elastic_update_pallas(w, m, 1.0, 0.0)
    np.testing.assert_allclose(w1["a"], 0.0)
    np.testing.assert_allclose(m1["a"], 0.0)
    # h1=0, h2=0: no-op
    w1, m1 = elastic_update_pallas(w, m, 0.0, 0.0)
    np.testing.assert_allclose(w1["a"], 1.0)


# ---------------------------------------------------------------------------
# fused adahessian
# ---------------------------------------------------------------------------

from repro.kernels.adahessian.ops import adahessian_step_pallas
from repro.kernels.adahessian.ref import adahessian_step_ref


@pytest.mark.parametrize("n", [100, 32768, 50000])
@pytest.mark.parametrize("t", [1, 100])
def test_adahessian_kernel_sweep(n, t):
    cfg = OptimizerConfig(lr=0.02, betas=(0.9, 0.999))
    r = lambda i: jax.random.normal(jax.random.key(i), (n,))
    p, g, h, m = r(1), r(2), r(3), r(4)
    v = jnp.abs(r(5))
    out_k = adahessian_step_pallas(p, g, h, m, v, cfg, t)
    out_r = adahessian_step_ref(p, g, h, m, v, cfg, t)
    for a, b in zip(out_k, out_r):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def test_adahessian_kernel_hessian_power():
    cfg = OptimizerConfig(lr=0.02, hessian_power=0.5)
    n = 1000
    r = lambda i: jax.random.normal(jax.random.key(i), (n,))
    p, g, h, m = r(1), r(2), r(3), r(4)
    v = jnp.abs(r(5))
    out_k = adahessian_step_pallas(p, g, h, m, v, cfg, 3)
    out_r = adahessian_step_ref(p, g, h, m, v, cfg, 3)
    np.testing.assert_allclose(out_k[0], out_r[0], rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# interpret-mode resolution
# ---------------------------------------------------------------------------

def test_interpret_mode_follows_backend_unless_explicit():
    from repro.kernels import interpret_mode

    assert interpret_mode() is (jax.default_backend() != "tpu")
    assert interpret_mode(False) is False
    assert interpret_mode(True) is True


@pytest.mark.parametrize("module", [
    "repro.kernels.elastic.kernel", "repro.kernels.elastic.ops",
    "repro.kernels.adahessian.kernel", "repro.kernels.adahessian.ops",
    "repro.kernels.flash_attention.kernel",
    "repro.kernels.flash_attention.ops"])
def test_kernel_entry_points_resolve_interpret_from_backend(module):
    """No entry point defaults to the interpreter: leaving ``interpret``
    out must give the compiled kernel on a TPU."""
    import importlib
    import inspect

    mod = importlib.import_module(module)
    entry = [f for f in vars(mod).values() if callable(f)
             and getattr(f, "__module__", None) == module]
    seen = 0
    for f in entry:
        try:
            params = inspect.signature(f).parameters
        except (TypeError, ValueError):
            continue
        if "interpret" in params:
            seen += 1
            assert params["interpret"].default is None, f.__name__
    assert seen
