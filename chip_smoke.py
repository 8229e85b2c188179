#!/usr/bin/env python3
"""Smoke test of the paper's training path on a TPU.

Default phase (one chip, one process): an ``ElasticSession`` trains the
paper's own model (``paper-cnn`` at its published config) with k=4 workers,
τ=2 local AdaHessian steps per round, the fused dynamic-weighting comm phase
and iid communication failures at the paper's rate of 1/3, for 4 rounds in
chunks of 2. Both Pallas kernels of the round (the batched AdaHessian update
and the batched elastic update) must appear compiled (``tpu_custom_call``)
in the chunk program, and the master must agree with the same run on the
plain jnp path (``use_pallas=False``) within ``RTOL`` of the largest
master magnitude.

``--four-chips``: the same model with one worker per chip
(``placement="sharded"``), flat fused comm and hierarchical comm (2 racks,
global sync every 2 rounds), each compared against ``placement="single"`` in
the same process; every leaf of the sharded trainer state must live on all
four devices. This phase runs instead of the default one.

Exits non-zero, printing no result line, when JAX finds no TPU. The last line
of standard output is ``{"ok": true, "device": {...}}``; the lines before it
are informational (compile and round times, peak device memory, max |Δ|).

    python chip_smoke.py
    python chip_smoke.py --four-chips
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.api import ElasticSession, RunSpec  # noqa: E402
from repro.configs.base import ElasticConfig, OptimizerConfig  # noqa: E402
from repro.core.coordinator import ElasticTrainer  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# Bound on max |Δ master| after 4 rounds, relative to max |master|, for
# Pallas vs jnp and for sharded vs single placement. AdaHessian's step
# lr·m/√v is normalized per element, so an ulp of difference in m or v
# moves the step by a relative ulp regardless of the gradient's size, and
# 8 local steps compound it. The measured max |Δ| is printed, and whether
# the placements agree bit for bit, as the repo claims for the CPU.
RTOL = 1e-4
ROUNDS = 4
ROUNDS_PER_CALL = 2
KERNEL_NAMES = ("adahessian_update_batched", "elastic_update_batched")


def paper_spec(**elastic):
    """The paper's k=4 DEAHES round on paper-cnn, with ``elastic``
    overriding fields of the ElasticConfig."""
    ecfg = dict(num_workers=4, tau=2, comm_mode="fused", placement="single",
                dynamic=True, failure_prob=1 / 3)
    ecfg.update(elastic)
    return RunSpec(arch="paper-cnn", smoke=False,
                   optimizer=OptimizerConfig(name="adahessian"),
                   elastic=ElasticConfig(**ecfg), rounds=ROUNDS,
                   rounds_per_call=ROUNDS_PER_CALL, batch_size=32, seed=0)


class TimedChunk:
    """Stands in for ``ElasticTrainer.round_chunk``: compiles the chunk
    program ahead of time (timed, text kept) and times every call to
    ``block_until_ready``."""

    def __init__(self, trainer):
        self.trainer = trainer
        self.compiled = None
        self.compile_s = []
        self.call_s = []
        self.text = ""
        self.last_inputs = None

    def compile(self, state, inputs):
        t0 = time.perf_counter()
        compiled = ElasticTrainer.round_chunk.lower(
            self.trainer, state, inputs).compile()
        self.compile_s.append(time.perf_counter() - t0)
        return compiled

    def __call__(self, state, inputs):
        if self.compiled is None:
            self.compiled = self.compile(state, inputs)
            self.text = self.compiled.as_text()
        self.last_inputs = inputs
        t0 = time.perf_counter()
        out = jax.block_until_ready(self.compiled(state, inputs))
        self.call_s.append(time.perf_counter() - t0)
        return out


def custom_calls(hlo_text: str) -> list:
    """Names of the compiled Pallas kernels in an optimized HLO module."""
    return [line.split("=", 1)[0].split()[-1].lstrip("%")
            for line in hlo_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def max_abs(tree) -> float:
    return max(float(np.max(np.abs(np.asarray(x))))
               for x in jax.tree.leaves(tree))


def max_abs_diff(a, b) -> float:
    return max(float(np.max(np.abs(np.asarray(x, np.float64)
                                   - np.asarray(y, np.float64))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def check_finite(records, label: str) -> None:
    for rec in records:
        vals = {"loss": rec.loss, "u": rec.u, "h1": rec.h1, "h2": rec.h2}
        bad = [k for k, v in vals.items() if not np.all(np.isfinite(v))]
        if bad:
            raise AssertionError(
                f"{label}: round {rec.round} has non-finite {bad}")


def single_chip_phase() -> None:
    sess = ElasticSession(paper_spec().replace(use_pallas=True))
    chunk = TimedChunk(sess.trainer)
    sess.trainer.round_chunk = chunk  # instance attribute shadows the jit
    records = sess.run()
    check_finite(records, "pallas run")
    kernels = custom_calls(chunk.text)
    print(f"[info] compiled Pallas kernels in the chunk program: {kernels}")
    missing = [k for k in KERNEL_NAMES
               if not any(name.startswith(k) for name in kernels)]
    if missing:
        raise AssertionError(
            f"kernels not compiled for the TPU (interpreted or absent): "
            f"{missing}")
    # the same program again: served from this process's caches. A cold
    # first compile that beats an earlier process's shows the persistent
    # cache (JAX_COMPILATION_CACHE_DIR or <checkout>/.jax_cache).
    chunk.compile(sess.state, chunk.last_inputs)
    print(f"[info] chunk lower+compile s: first={chunk.compile_s[0]:.3f} "
          f"again_in_process={chunk.compile_s[1]:.3f}")
    warm = chunk.call_s[1:]
    print(f"[info] wall s per round after warm-up (block_until_ready): "
          f"{[s / ROUNDS_PER_CALL for s in warm]}")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[info] peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")

    ref = ElasticSession(paper_spec().replace(use_pallas=False))
    check_finite(ref.run(), "jnp run")
    diff = max_abs_diff(sess.master_params, ref.master_params)
    bound = RTOL * max_abs(ref.master_params)
    print(f"[info] pallas vs jnp master: max|d|={diff!r} bound={bound!r} "
          f"losses pallas={[r.loss for r in records]}")
    if not diff <= bound:
        raise AssertionError(
            f"pallas master differs from jnp master: {diff} > {bound}")


def four_chip_phase() -> None:
    if jax.device_count() != 4:
        raise SystemExit(f"--four-chips needs 4 devices, found "
                         f"{jax.device_count()}")
    layouts = {"flat": {}, "hier": {"groups": 2, "global_period": 2}}
    for name, extra in layouts.items():
        single = ElasticSession(paper_spec(**extra))
        check_finite(single.run(), f"{name} single")
        sharded = ElasticSession(paper_spec(placement="sharded", **extra))
        check_finite(sharded.run(), f"{name} sharded")
        spread = {len(x.sharding.device_set)
                  for x in jax.tree.leaves(sharded.state)}
        if spread != {4}:
            raise AssertionError(
                f"{name}: sharded state leaves span {spread} devices, "
                f"want all on 4")
        diff = max_abs_diff(sharded.master_params, single.master_params)
        bound = RTOL * max_abs(single.master_params)
        print(f"[info] {name} sharded vs single master: max|d|={diff!r} "
              f"bound={bound!r} bit_exact={diff == 0.0}")
        if not diff <= bound:
            raise AssertionError(
                f"{name}: sharded master differs from single: "
                f"{diff} > {bound}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the one-worker-per-chip phase on 4 chips")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {dev.platform!r} "
              f"devices); this check runs only on the chip",
              file=sys.stderr)
        return 2
    print(f"[info] compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase()
    else:
        single_chip_phase()
    print(f"[info] phase wall s: {time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
