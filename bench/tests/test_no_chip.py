"""The CPU rehearsal of every cell: run.py finds no chip, says so, exits
non-zero and prints no result and no metric."""
import json
import os
import subprocess
import sys

import pytest

from bench.spec import ROOT, load_benchmark

CELLS = [w["name"] for w in load_benchmark()["workloads"]]
METRICS = [m["name"] for group in ("end_to_end", "per_layer")
           for m in load_benchmark()[group]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_no_chip(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         str(2**31 + 5), "--seconds", "1", "--trace", trace],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no chip" in p.stderr
    assert p.stdout.strip() == ""
    for name in METRICS + ["busy_s", "memory_peak_bytes"]:
        assert name not in p.stdout and name not in p.stderr
