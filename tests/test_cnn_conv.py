"""paper-cnn's two convolutions (``models/cnn.py`` ``_conv``, XLA's own
convolution) at the model's channel widths, under the transforms the local
step applies: vmap over the workers, the gradient, and the forward-over-
reverse Hutchinson HVP. Checked against a plain nine-tap shift-and-sum, and
in the compiled step. The v5e compile of the whole round is checked in
``test_tpu_compile.py``."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.cnn import _conv
from repro.optim.hutchinson import hessian_diag_with_grad

K, B = 4, 2  # workers, per-worker batch

# (input height and width, input channels, output channels) as each layer
# sees them from a 28×28×1 image
LAYERS = {"conv1": (28, 1, 32), "conv2": (26, 32, 64)}


def _shift_and_sum(x, w, b):
    """3×3 VALID conv as the sum over the nine taps of a shifted slice times
    the tap's (C, O) weight."""
    oh, ow = x.shape[1] - 2, x.shape[2] - 2
    out = sum(jnp.einsum("bhwc,co->bhwo", x[:, i:i + oh, j:j + ow], w[i, j],
                         precision="highest")
              for i in range(3) for j in range(3))
    return out + b


def _setup(layer):
    hw, c, o = LAYERS[layer]
    kx, kw, kb, kr = jax.random.split(jax.random.key(14), 4)
    x = jax.random.normal(kx, (K, B, hw, hw, c), jnp.float32)
    params = {"w": jax.random.normal(kw, (K, 3, 3, c, o)) / np.sqrt(9 * c),
              "b": 0.1 * jax.random.normal(kb, (K, o))}
    return x, params, jax.random.split(kr, K)


def _local_step(conv):
    """Per worker: output, gradient and Hutchinson diagonal of a smooth
    loss of the layer's output, as the local step takes them."""
    def one(x, params, rng):
        loss = lambda p: jnp.mean(jnp.tanh(conv(x, p["w"], p["b"])))
        grads, diag = hessian_diag_with_grad(jax.grad(loss), params, rng)
        return conv(x, params["w"], params["b"]), grads, diag
    return jax.jit(jax.vmap(one))


@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_conv_matches_shift_and_sum(layer):
    args = _setup(layer)
    with jax.default_matmul_precision("highest"):
        got = _local_step(_conv)(*args)
    want = _local_step(_shift_and_sum)(*args)
    for name, g, w in zip(("output", "gradient", "hvp"), got, want):
        for (path, b), a in zip(jax.tree_util.tree_leaves_with_path(w),
                                jax.tree.leaves(g)):
            scale = float(jnp.max(jnp.abs(b)))
            assert scale > 0, (name, path)
            # float32 sums of at most 9·32 products per output element
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * scale,
                                       err_msg=f"{layer} {name} {path}")


@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_conv_is_xla_convolution_under_conv_scope(layer):
    """Every derivative of the layer is an XLA convolution at the matmul
    precision in force, named by the ``conv`` scope (which the transforms
    wrap, as ``vmap(jvp(conv))``); no im2col patch tensor is built."""
    hw, c, _ = LAYERS[layer]
    with jax.default_matmul_precision("highest"):
        lowered = _local_step(_conv).lower(*_setup(layer))
    text = lowered.as_text(debug_info=True)
    locs = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))
    convs = [line for line in text.splitlines()
             if "stablehlo.convolution" in line]
    # forward, input and weight gradients, and their tangents
    assert len(convs) >= 3
    for line in convs:
        assert ("precision_config = [#stablehlo<precision HIGHEST>, "
                "#stablehlo<precision HIGHEST>]") in line
        loc = locs[re.search(r"loc\((#loc\d+)\)\s*$", line).group(1)]
        path = re.match(r'loc\("([^"]*)"', loc).group(1)
        assert "conv" in {re.sub(r"^(\w+\()+|\)+$", "", part)
                          for part in path.split("/")}, path
    oh = hw - 2
    assert not re.search(rf"{oh},{oh},(9,{c}|{9 * c})\]",
                         lowered.compile().as_text())
