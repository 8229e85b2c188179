"""Plain reference of paper-cnn (the PyTorch MNIST example CNN).

Forward: conv 3×3 VALID 1→32, ReLU, conv 3×3 VALID 32→64, ReLU, max-pool
2×2, flatten (H, W, C order), fc 9216→128, ReLU, fc 128→10; loss: mean
softmax cross entropy. The example's two dropout layers are left out, as
the program's model has none. Weights: LeCun-normal, std 1/√fan_in (fan_in
over the kernel's input axes), biases zero, one PRNG key per leaf in the
order of the sorted parameter names. A convolution is written as its definition: a
sum over the 3×3 window of matrix products.
"""
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _shapes(config):
    L = config["layers"]
    h, w, c = L["image"]
    k = L["conv_kernel"]
    c1, c2 = L["conv_channels"]
    flat = ((h - 2 * (k - 1)) // L["pool"]) * ((w - 2 * (k - 1))
                                              // L["pool"]) * c2
    # (shape, fan_in axis or None for zeros), in sorted-name order
    return {
        "conv1": {"b": ((c1,), None), "w": ((k, k, c, c1), 2)},
        "conv2": {"b": ((c2,), None), "w": ((k, k, c1, c2), 2)},
        "fc1": {"b": ((L["hidden"],), None), "w": ((flat, L["hidden"]), 0)},
        "fc2": {"b": ((L["classes"],), None),
                "w": ((L["hidden"], L["classes"]), 0)},
    }


def init_params(key, config):
    shapes = _shapes(config)
    names = [(a, b) for a in sorted(shapes) for b in sorted(shapes[a])]
    keys = jax.random.split(key, len(names))
    out = {a: {} for a in shapes}
    for kk, (a, b) in zip(keys, names):
        shape, axis = shapes[a][b]
        if axis is None:
            out[a][b] = jnp.zeros(shape, F32)
        else:
            std = 1.0 / math.sqrt(shape[axis])
            out[a][b] = (std * jax.random.normal(kk, shape)).astype(F32)
    return out


def _conv(x, w, b, pol):
    kh, kw = w.shape[:2]
    oh, ow = x.shape[1] - kh + 1, x.shape[2] - kw + 1
    y = sum(pol.einsum("bhwc,co->bhwo", x[:, i:i + oh, j:j + ow, :], w[i, j])
            for i in range(kh) for j in range(kw))
    return y + pol.act(b)


def loss(params, batch, config, pol):
    x = pol.act(batch["images"])
    x = jax.nn.relu(_conv(x, params["conv1"]["w"], params["conv1"]["b"], pol))
    x = jax.nn.relu(_conv(x, params["conv2"]["w"], params["conv2"]["b"], pol))
    B, H, W, C = x.shape
    p = config["layers"]["pool"]
    x = x.reshape(B, H // p, p, W // p, p, C).max(axis=(2, 4))
    x = x.reshape(B, -1)
    x = jax.nn.relu(pol.einsum("bi,io->bo", x, params["fc1"]["w"])
                    + pol.act(params["fc1"]["b"]))
    logits = (pol.einsum("bi,io->bo", x, params["fc2"]["w"])
              + pol.act(params["fc2"]["b"])).astype(F32)
    gold = jnp.take_along_axis(logits, batch["labels"][:, None], -1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


def products(config, traffic):
    """(multiply-adds per sample, is the data-input layer) of each matrix
    product of one forward pass."""
    s = _shapes(config)
    L = config["layers"]
    h, w, _ = L["image"]
    k = L["conv_kernel"]
    o1 = (h - k + 1) * (w - k + 1)
    o2 = (h - 2 * (k - 1)) * (w - 2 * (k - 1))
    c1, c2 = L["conv_channels"]
    cin = L["image"][2]
    return [(o1 * k * k * cin * c1, True), (o2 * k * k * c1 * c2, False),
            (math.prod(s["fc1"]["w"][0]), False),
            (math.prod(s["fc2"]["w"][0]), False)]


def n_params(config):
    s = _shapes(config)
    return sum(math.prod(v[0]) for a in s.values() for v in a.values())
