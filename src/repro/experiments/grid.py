"""Paper figs 4–5 grid driver: 6 methods × k∈{4,8} × τ∈{1,2,4} (+ seeds),
run as a bounded pool of subprocesses (XLA-CPU underutilizes cores for this
model size, so process-level parallelism ≈ free wall-clock). On an
accelerator every child needs the chip, which one process holds at a time,
so the pool runs one child at a time unless ``JAX_PLATFORMS=cpu``; the
parent itself never imports JAX.

Also fig 3: overlap-ratio sweep {0, .125, .25, .375, .5} on EAHES-O, and a
beyond-paper scenario axis (``--what scenarios``): every failure regime from
``repro.core.scenarios`` × {EASGD, EAHES-O, DEAHES-O} at k=4/τ=1.

Results land in results/paper_repro/*.json; summarize() renders the tables
consumed by EXPERIMENTS.md §Repro.
"""
from __future__ import annotations

import glob
import itertools
import json
import os
import subprocess
import sys
import time

RESULTS = "results/paper_repro"


def job_cmd(method, k, tau, seed, rounds, out, overlap=None, scenario=None,
            rounds_per_call=1):
    cmd = [sys.executable, "-m", "repro.experiments.paper_repro",
           "--method", method, "--k", str(k), "--tau", str(tau),
           "--seed", str(seed), "--rounds", str(rounds), "--out", out,
           "--rounds-per-call", str(rounds_per_call)]
    if overlap is not None:
        cmd += ["--overlap-ratio", str(overlap)]
    if scenario is not None:
        cmd += ["--failure-scenario", scenario]
    return cmd


def run_pool(jobs, max_procs=5):
    """Run jobs as a bounded subprocess pool; returns the list of failed job
    names (empty when everything exited 0). ``max_procs`` applies only
    under ``JAX_PLATFORMS=cpu``; otherwise the children share one chip and
    run one at a time."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        max_procs = 1
    procs = []
    t0 = time.time()
    pending = list(jobs)
    done = 0
    total = len(pending)
    failed = []
    while pending or procs:
        while pending and len(procs) < max_procs:
            name, cmd = pending.pop(0)
            env = dict(os.environ)
            env["PYTHONPATH"] = "src"
            procs.append((name, subprocess.Popen(
                cmd, env=env, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL)))
        still = []
        for name, p in procs:
            if p.poll() is None:
                still.append((name, p))
            else:
                done += 1
                status = "ok" if p.returncode == 0 else f"FAIL({p.returncode})"
                if p.returncode != 0:
                    failed.append(name)
                print(f"[{time.time()-t0:7.1f}s] {done}/{total} {name}: "
                      f"{status}", flush=True)
        procs = still
        time.sleep(2.0)
    return failed


# Communication-round budget per τ (single-core container: τ=4 costs 4×
# the local compute per round, so the high-τ panels get fewer rounds).
ROUNDS_BY_TAU = {1: 16, 2: 12, 4: 8}


def grid_jobs(rounds=None, seeds=(0,), methods=None, ks=(4, 8),
              taus=(1, 2, 4), rounds_per_call=1):
    from repro.experiments.paper_repro import METHODS

    methods = methods or sorted(METHODS)
    jobs = []
    # τ-major order: complete (τ=1) panels land first so partial runs still
    # yield full method comparisons
    for tau, k, m, s in itertools.product(taus, ks, methods, seeds):
        r = rounds or ROUNDS_BY_TAU[tau]
        out = f"{RESULTS}/fig45_{m}_k{k}_tau{tau}_s{s}.json"
        if os.path.exists(out):
            continue
        jobs.append((f"{m} k={k} τ={tau} s={s}",
                     job_cmd(m, k, tau, s, r, out,
                             rounds_per_call=rounds_per_call)))
    return jobs


def scenario_jobs(rounds=12, seeds=(0,), scenarios=None,
                  methods=("EASGD", "EAHES-O", "DEAHES-O"), k=4, tau=1,
                  rounds_per_call=1):
    """Failure-regime axis: every scenario from the engine × the headline
    methods, at the paper's k=4/τ=1 operating point."""
    from repro.configs.base import FAILURE_SCENARIOS

    scenarios = scenarios or FAILURE_SCENARIOS
    jobs = []
    for sc, m, s in itertools.product(scenarios, methods, seeds):
        out = f"{RESULTS}/scen_{sc}_{m}_k{k}_tau{tau}_s{s}.json"
        if os.path.exists(out):
            continue
        jobs.append((f"{m} scen={sc} s={s}",
                     job_cmd(m, k, tau, s, rounds, out, scenario=sc,
                             rounds_per_call=rounds_per_call)))
    return jobs


def overlap_jobs(rounds=16, seeds=(0,), ratios=(0.0, 0.125, 0.25, 0.375, 0.5),
                 rounds_per_call=1):
    jobs = []
    for r, s in itertools.product(ratios, seeds):
        out = f"{RESULTS}/fig3_r{r}_s{s}.json"
        if os.path.exists(out):
            continue
        jobs.append((f"overlap r={r} s={s}",
                     job_cmd("EAHES-O", 4, 1, s, rounds, out, overlap=r,
                             rounds_per_call=rounds_per_call)))
    return jobs


def summarize(pattern=f"{RESULTS}/*.json"):
    rows = []
    for path in sorted(glob.glob(pattern)):
        with open(path) as f:
            rows.append(json.load(f))
    return rows


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=None,
                    help="override the per-τ round budget")
    ap.add_argument("--rounds-per-call", type=int, default=1,
                    help="jit-scan chunk size passed to every job (the "
                         "session API guarantees numbers are unchanged)")
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--max-procs", type=int, default=1)
    ap.add_argument("--what", default="all",
                    choices=["all", "fig45", "fig3", "scenarios"])
    args = ap.parse_args()
    seeds = tuple(range(args.seeds))
    rpc = args.rounds_per_call
    jobs = []
    if args.what in ("all", "fig45"):
        jobs += grid_jobs(args.rounds, seeds, rounds_per_call=rpc)
    if args.what in ("all", "fig3"):
        jobs += overlap_jobs(args.rounds or 16, seeds, rounds_per_call=rpc)
    if args.what in ("all", "scenarios"):
        jobs += scenario_jobs(args.rounds or 12, seeds, rounds_per_call=rpc)
    print(f"{len(jobs)} jobs")
    failed = run_pool(jobs, args.max_procs)
    if failed:
        print(f"{len(failed)} job(s) failed: " + ", ".join(failed),
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
