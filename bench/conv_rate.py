#!/usr/bin/env python3
"""The chip's float32 rate at the matmul precision HIGHEST, for paper-cnn's
conv2 as the local step runs it and for the plain matmuls it is measured
against.

    python3 bench/conv_rate.py [--iters 50]

Each case is one jitted call, compiled and run once first, then timed over
``--iters`` calls queued back to back (the host waits once, at the end), so
the time per call is the device's. FLOPs are counted from the shapes (2 per
multiply-add). Prints one JSON object: per case the shape, ms per call and
TFLOP/s. The cases:

- ``conv2_vmapped``: conv2's forward, 3×3 VALID, 32→64 channels, over 24×24
  outputs, vmapped over the k=4 workers with a batch of 32 each and a
  weight each (the TPU lowers it as a window over the worker axis);
- ``conv2_folded``: the same 128 images through one weight, no vmap;
- ``im2col_dot``: the same contraction as one (4·32·24·24, 288) @ (288, 64)
  matmul per worker;
- ``square_f32``: a 4096³ float32 matmul, the rate HIGHEST reaches on a
  large contraction;
- ``square_bf16``: the same in bfloat16 at the default precision, the
  peak the benchmark's ``step_mfu`` divides by.
"""
from __future__ import annotations

import json
import sys
import time


def _rate(fn, args, flops, iters):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    s = (time.perf_counter() - t0) / iters
    return {"ms": s * 1e3, "tflops": flops / s / 1e12}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--iters", type=int, default=50)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    k, b, hw, c, o = 4, 32, 26, 32, 64
    oh = hw - 2
    key = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(key[0], (k, b, hw, hw, c), jnp.float32)
    w = jax.random.normal(key[1], (k, 3, 3, c, o), jnp.float32)
    conv = lambda x, w: jax.lax.conv_general_dilated(
        x, w, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    conv_flops = 2 * k * b * oh * oh * 9 * c * o
    cols = jax.random.normal(key[2], (k, b * oh * oh, 9 * c), jnp.float32)
    n = 4096
    sq = jax.random.normal(key[3], (n, n), jnp.float32)
    cases = {
        "conv2_vmapped": (jax.vmap(conv), (x, w), conv_flops),
        "conv2_folded": (conv, (x.reshape(k * b, hw, hw, c), w[0]),
                         conv_flops),
        "im2col_dot": (lambda a, w: jnp.einsum("kmc,kco->kmo", a, w),
                       (cols, w.reshape(k, 9 * c, o)), conv_flops),
        "square_f32": (jnp.matmul, (sq, sq), 2 * n ** 3),
    }
    out = {"device": jax.devices()[0].device_kind, "iters": args.iters}
    with jax.default_matmul_precision("highest"):
        for name, (fn, a, flops) in cases.items():
            out[name] = dict(_rate(jax.jit(fn), a, flops, args.iters),
                             shapes=[list(v.shape) for v in a])
    half = sq.astype(jnp.bfloat16)
    out["square_bf16"] = dict(_rate(jax.jit(jnp.matmul), (half, half),
                                    2 * n ** 3, args.iters),
                              shapes=[[n, n], [n, n]])
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
