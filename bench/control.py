#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from. On the chip:

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ... \
        [--faults half_batch scaled_grad no_exchange] [--no-program]

For each seed, in one process: the program's numbers (the cell's set-up and
compared chunks, exactly as a run makes them) against the reference; the
control's (the reference in the precision below the configuration's,
``control_policy`` in the configuration file) against the reference; and
each fault planted in the reference. One JSON line per seed and reading.
The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(cell, seed, devices, *, program=True, faults=()):
    """Yield (what, numbers) for one seed."""
    from bench import compare, harness

    alpha = float(cell.traffic["elastic"]["alpha"])
    rounds = int(cell.traffic["check_chunks"]) * int(
        cell.traffic["rounds_per_call"])
    prog = None
    if program:
        session, _ = harness.build_session(cell, seed, devices)
        prog = harness.check_chunks(session, cell, seed)
        session.state = None
        del session
        gc.collect()
    ref = harness.reference_readings(cell, seed, devices, "reference", rounds)
    if prog is not None:
        yield "program", compare.numbers(prog, ref, alpha)
    ctrl = harness.reference_readings(cell, seed, devices,
                                      cell.config["control"], rounds)
    yield "control", compare.numbers(ctrl, ref, alpha)
    for f in faults:
        bad = harness.reference_readings(cell, seed, devices, "reference",
                                         rounds, fault=f)
        yield f, compare.numbers(bad, ref, alpha)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--no-program", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from bench.spec import resolve
    from repro.launch.compile_cache import enable_compile_cache

    cell = resolve(args.workload)
    try:
        devices = harness.chips_for(cell)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for seed in args.seeds:
        t0 = time.perf_counter()
        for what, nums in readings(cell, seed, devices,
                                   program=not args.no_program,
                                   faults=args.faults):
            print(json.dumps({"seed": seed, "reading": what, **nums}),
                  flush=True)
        print(f"seed {seed}: {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
