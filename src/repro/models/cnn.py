"""The paper's own model: a simple 2-conv-layer CNN for 28×28 10-class
classification ("a simple 2-layer convolutional neural network from PyTorch",
paper §VI — i.e. the canonical PyTorch MNIST example: conv(1→32,3×3),
conv(32→64,3×3), maxpool 2×2, fc(9216→128), fc(128→10))."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.nn.param import ParamSpec, fan_in_init, zeros_init


def _conv(x, w, b):
    """3×3 VALID conv, NHWC input, HWIO weights, as XLA's own convolution at
    the matmul precision in force.

    Not im2col: on the TPU the patch tensor (B, oh, ow, 9, C), whose minor
    dimension of 32 in conv2 the (8, 128) tiling pads up to 4×, was copied
    again in every transform of the step and made the local step bound by
    memory traffic; on the CPU the native form is faster as well.
    """
    with jax.named_scope("conv"):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + b


def _maxpool2(x):
    """2×2 max pool via reshape (fast differentiable path on CPU)."""
    B, Hh, Ww, C = x.shape
    return x.reshape(B, Hh // 2, 2, Ww // 2, 2, C).max(axis=(2, 4))


@dataclasses.dataclass
class PaperCNN:
    cfg: ModelConfig

    def __post_init__(self):
        f32 = jnp.float32
        self.spec = {
            "conv1": {"w": ParamSpec((3, 3, 1, 32), f32, fan_in_init(2)),
                      "b": ParamSpec((32,), f32, zeros_init)},
            "conv2": {"w": ParamSpec((3, 3, 32, 64), f32, fan_in_init(2)),
                      "b": ParamSpec((64,), f32, zeros_init)},
            "fc1": {"w": ParamSpec((9216, 128), f32, fan_in_init(0)),
                    "b": ParamSpec((128,), f32, zeros_init)},
            "fc2": {"w": ParamSpec((128, 10), f32, fan_in_init(0)),
                    "b": ParamSpec((10,), f32, zeros_init)},
        }

    def forward(self, params, batch, *, remat: bool = False):
        x = batch["images"]  # (B, 28, 28, 1)
        x = jax.nn.relu(_conv(x, params["conv1"]["w"], params["conv1"]["b"]))
        x = jax.nn.relu(_conv(x, params["conv2"]["w"], params["conv2"]["b"]))
        x = _maxpool2(x)
        x = x.reshape(x.shape[0], -1)
        x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
        return x @ params["fc2"]["w"] + params["fc2"]["b"], jnp.zeros((), jnp.float32)

    def loss(self, params, batch, *, remat: bool = False):
        logits, _ = self.forward(params, batch)
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, batch["labels"][:, None], -1)[:, 0]
        ce = jnp.mean(logz - gold)
        return ce, {"ce": ce,
                    "acc": jnp.mean(
                        (jnp.argmax(logits, -1) == batch["labels"]))}

    def accuracy(self, params, batch):
        logits, _ = self.forward(params, batch)
        return jnp.mean((jnp.argmax(logits, -1) == batch["labels"]).astype(
            jnp.float32))

    def input_specs(self, shape: ShapeConfig):
        B = shape.global_batch
        return {
            "images": ParamSpec((B, 28, 28, 1), jnp.float32, zeros_init,
                                ("batch", None, None, None)),
            "labels": ParamSpec((B,), jnp.int32, zeros_init, ("batch",)),
        }

    def dummy_batch(self, rng, shape: ShapeConfig):
        k1, k2 = jax.random.split(rng)
        B = shape.global_batch
        return {"images": jax.random.normal(k1, (B, 28, 28, 1)),
                "labels": jax.random.randint(k2, (B,), 0, 10, jnp.int32)}
