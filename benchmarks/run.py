"""Benchmark harness — one section per paper table/figure plus the roofline,
kernel microbenches and the session-API driver benchmarks. Prints
``name,us_per_call,derived`` CSV; ``--what session`` instead emits a single
JSON record comparing per-round vs jit-chunked session wall time, and
``--what placement`` a JSON record comparing single vs sharded placement
per-round time at k ∈ {4, 8} (force a multi-device host with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so the worker
shards actually spread), and ``--what membership`` a JSON record measuring
the capacity-padding overhead of the elastic worker pool (k ∈ {4, 8} live
workers at capacity ∈ {8, 16} vs an exact-fit pool), and ``--what
control`` a JSON record scoring the detector-blind closed-loop controller
against an oracle-scheduled controller and the open loop across the
failure scenarios (recovery delay, evictions/readmissions, master-loss
degradation), and ``--what serving`` a JSON record comparing continuous
(in-flight) vs static gang batching on the same bursty MMPP trace
(sustained req/s, p50/p99 request latency — ISSUE-8), and ``--what
local`` a JSON record comparing the plain
vmapped local phase against the fused local phase (ISSUE-7: shared
gradient/HVP linearization + batched multi-worker AdaHessian update) at
k ∈ {4, 8} — the jnp-fused row is the CPU win, the interpret-mode Pallas
row records that path's (expected, large) CPU overhead, and ``--what
scenarios`` a JSON record measuring what the ISSUE-9 adversarial schedule
channels cost per round (masked sign-flip corruption + score_clip
quarantine, per-slot speed masks) against the channel-free clean trace at
k ∈ {4, 8}, and ``--what hierarchy`` a JSON record comparing flat fused
vs two-level hierarchical communication (ISSUE-10) at k ∈ {16, 32, 64} —
per-round comm time drops as global sub-master↔master syncs amortize over
``global_period``, with a global-sync-count check and an end-to-end k=16
no-worse-than-flat session comparison."""
import argparse
import json
import sys


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--what", default="all",
                    choices=["all", "kernels", "comm_modes", "local",
                             "paper", "roofline", "session", "placement",
                             "membership", "control", "serving",
                             "scenarios", "hierarchy"])
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.what == "local":
        from benchmarks import kernels_bench

        print(json.dumps(kernels_bench.bench_local()))
        return

    if args.what == "session":
        from benchmarks import session_bench

        print(json.dumps(session_bench.bench_session()))
        return

    if args.what == "placement":
        from benchmarks import session_bench

        print(json.dumps(session_bench.bench_session_placement()))
        return

    if args.what == "membership":
        from benchmarks import session_bench

        print(json.dumps(session_bench.bench_session_membership()))
        return

    if args.what == "control":
        from benchmarks import control_bench

        print(json.dumps(control_bench.bench_control()))
        return

    if args.what == "serving":
        from benchmarks import serving_bench

        print(json.dumps(serving_bench.bench_serving()))
        return

    if args.what == "scenarios":
        from benchmarks import scenario_bench

        print(json.dumps(scenario_bench.bench_scenarios()))
        return

    if args.what == "hierarchy":
        from benchmarks import session_bench

        print(json.dumps(session_bench.bench_hierarchy()))
        return

    from benchmarks import (kernels_bench, paper_figs, roofline_bench,
                            session_bench)

    sections = []
    if args.what in ("all", "kernels"):
        sections.append(("kernels", kernels_bench.bench))
    if args.what in ("all", "comm_modes"):
        sections.append(("comm_modes", kernels_bench.bench_comm_modes))
    if args.what in ("all", "paper"):
        sections.append(("paper_fig3_overlap", paper_figs.bench_fig3))
        sections.append(("paper_fig45_convergence", paper_figs.bench_fig45))
    if args.what in ("all", "roofline"):
        sections.append(("roofline", roofline_bench.bench))
    if args.what == "all":
        sections.append(("session", session_bench.bench))

    print("name,us_per_call,derived")
    failed = []
    for name, fn in sections:
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001 — report, run the rest, fail
            print(f"{name},0,ERROR:{type(e).__name__}:{e}")
            failed.append(name)
            continue
        for row_name, us, derived in rows:
            print(f"{row_name},{us:.1f},{derived}")
    if failed:
        sys.exit(f"benchmark section(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
