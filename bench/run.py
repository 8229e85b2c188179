#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` measures the cell's end-to-end metrics over ``--seconds`` of
whole chunks; ``--trace 1`` reads its per-layer metrics from a short
profiled window instead. Both check the chunks they ran against the plain
reference. The last line of standard output is one JSON object (see
``bench/harness.py``); the numbers compared for ``correct`` are the last
lines of standard error. Exits non-zero, printing no result, where JAX finds
no accelerator or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    import jax

    from bench import harness
    from bench.spec import resolve

    cell = resolve(args.workload)
    try:
        devices = harness.chips_for(cell)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices=devices,
                              started=STARTED)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
