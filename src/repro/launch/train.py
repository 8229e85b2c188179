"""Training launcher — a thin argv shim over ``repro.api.ElasticSession``.

Two modes:
- ``--elastic``: the paper's system — k workers, τ-periodic dynamic-weight
  elastic sync, failure injection (this is the default and the point of the
  framework).
- plain: single-worker training (the k=1 limit), useful as a control.

On real hardware this runs under the production mesh; on CPU it runs the
same code on the host mesh. ``--arch`` takes any assigned architecture id
(smoke variant with ``--smoke``) or ``paper-cnn``. ``--rounds-per-call R``
executes R rounds per jit call (``ElasticTrainer.round_chunk``) —
bit-identical to per-round execution, but the per-round driver overhead is
paid once per chunk. ``--placement sharded`` (with ``--comm-mode fused``)
places the worker axis over the mesh's 'pod' axis via shard_map instead of
simulating all k workers on one device — master params stay bit-exact with
single placement; force a multi-device CPU host with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` to exercise it
without TPUs (with one device, sharded runs on a 1-way pod axis).
``--capacity C`` pads the worker axis to C slots so the pool can resize
live (``--membership-scenario`` / ``--membership-plan "2:2,4:6"``) with
zero recompiles; under sharded placement capacity is padded to a multiple
of the pod axis and the extra slots stay inactive.

Closed-loop control (ISSUE-6): ``--controller rules`` attaches the
detector→policy→actuator loop (``repro.control``) — suspect slots are
evicted and probed back in at chunk boundaries, from observable telemetry
only. ``--detector-blind`` additionally zeroes the ground-truth event masks
echoed into the printed records, so what you see is exactly what the
controller saw.

Hierarchy & scale-out (ISSUE-10): ``--groups G`` partitions the slot axis
into G rack-sized groups, each owning a sub-master that its workers
elastic-average against every round; ``--global-period P`` syncs the
sub-masters with the global master only every P rounds (τ_g = P·τ), so the
global barrier amortizes P× (``repro.core.coordinator._comm_phase_hier``).
``--coordinator-address host:port --num-processes N --process-id i`` spans
the mesh across N processes via ``jax.distributed`` (sharded placement
only; on CPU each process falls back to a local mesh — see
``make_distributed_mesh``). Only process 0 prints rounds; every process
prints the final master l2 for cross-process agreement checks.

Trace replay (ISSUE-9): ``--dump-trace run.jsonl`` records the exact
fail/straggle/restart/corrupt/speed/membership stream the run executed
(including controller-applied resizes) as a JSON-lines scenario trace;
``--trace run.jsonl`` replays a recorded trace instead of drawing a fresh
schedule — rounds/capacity are coerced to the recorded shape, so the replay
is bit-identical given the same seed and model flags. Adversarial knobs:
``--failure-scenario byzantine`` plus ``--byzantine-*`` injects gradient
corruption into a persistent subset of slots, and ``--score-clip`` arms the
robustness clamp that lets the master refuse their pulls
(``repro.core.dynamic_weight``); ``--failure-scenario hetero`` plus
``--hetero-*`` gives each slot a persistent step-rate drawn once per run.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.api import ElasticSession, RunSpec
from repro.configs.base import (FAILURE_SCENARIOS, MEMBERSHIP_SCENARIOS,
                                ElasticConfig, OptimizerConfig)
from repro.core.scenarios import (parse_membership_plan, read_trace,
                                  write_trace)
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-cnn")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config of the arch family")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--rounds-per-call", type=int, default=1,
                    help="rounds executed inside one jit call (lax.scan "
                         "chunking; 1 = per-round dispatch)")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=0,
                    help="worker-slot capacity (>= --workers; 0 = exactly "
                         "--workers). Shapes are fixed at capacity, so "
                         "membership can resize up to it with zero "
                         "recompiles; under --placement sharded it is "
                         "padded up to a multiple of the pod axis")
    ap.add_argument("--membership-scenario", default="static",
                    choices=MEMBERSHIP_SCENARIOS,
                    help="planned worker-pool resize stream "
                         "(repro/core/scenarios.py); 'plan' runs "
                         "--membership-plan")
    ap.add_argument("--membership-k", type=int, default=0,
                    help="resize target (scale_up/scale_down) or preempted "
                         "count (preempt_rejoin); 0 = scenario default")
    ap.add_argument("--membership-round", type=int, default=0,
                    help="round the membership event fires (0 = mid-run)")
    ap.add_argument("--membership-plan", default="",
                    help="explicit resize steps 'round:k,round:k' (e.g. "
                         "'2:2,4:6'); implies --membership-scenario plan")
    ap.add_argument("--tau", type=int, default=1)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--optimizer", default="adahessian")
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--alpha", type=float, default=0.1)
    ap.add_argument("--overlap", type=float, default=0.25)
    ap.add_argument("--failure-prob", type=float, default=1 / 3)
    ap.add_argument("--failure-scenario", default="iid",
                    choices=FAILURE_SCENARIOS,
                    help="failure regime injected into the run "
                         "(see repro/core/scenarios.py)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="replay a recorded scenario trace (JSON-lines, "
                         "see repro.core.scenarios.read_trace) instead of "
                         "drawing a schedule; --rounds/--workers/--capacity "
                         "are coerced to the recorded shape")
    ap.add_argument("--dump-trace", default=None, metavar="PATH",
                    help="after the run, write the executed schedule "
                         "(including controller-applied membership) as a "
                         "replayable JSON-lines trace")
    ap.add_argument("--score-clip", type=float, default=0.0,
                    help="robustness clamp: raw scores above this give the "
                         "worker zero master weight and re-anchor it if it "
                         "diverged past float32 range; 0 = paper behaviour "
                         "(repro.core.dynamic_weight)")
    ap.add_argument("--u-zclip", type=float, default=0.0,
                    help="absolute-distance containment: refuse (w2=0) any "
                         "worker whose log-distance sits more than this "
                         "many robust z-scores (median/MAD over the live "
                         "pool) above the pool — catches attackers parked "
                         "at a static distance that score_clip's trend "
                         "clamp misses; 0 = off")
    ap.add_argument("--byzantine-frac", type=float, default=0.25,
                    help="fraction of slots drawn corrupt under "
                         "--failure-scenario byzantine")
    ap.add_argument("--byzantine-mode", default="sign_flip",
                    choices=("sign_flip", "scale", "noise"),
                    help="gradient corruption applied to corrupt slots")
    ap.add_argument("--byzantine-scale", type=float, default=5.0,
                    help="magnitude for the scale/noise corruption modes")
    ap.add_argument("--hetero-dist", default="lognormal",
                    choices=("lognormal", "bimodal"),
                    help="per-slot persistent speed distribution under "
                         "--failure-scenario hetero")
    ap.add_argument("--hetero-sigma", type=float, default=0.6,
                    help="lognormal sigma for --hetero-dist lognormal")
    ap.add_argument("--hetero-slow-frac", type=float, default=0.25,
                    help="fraction of slow slots for --hetero-dist bimodal")
    ap.add_argument("--hetero-slow-scale", type=float, default=0.25,
                    help="step-rate of slow slots for --hetero-dist bimodal")
    ap.add_argument("--no-dynamic", action="store_true")
    ap.add_argument("--comm-mode", default="sequential",
                    choices=("sequential", "fused"),
                    help="communication backend: event-ordered scan "
                         "(paper) or fused batched sync")
    ap.add_argument("--staleness", type=int, default=0, choices=(0, 1),
                    help="delayed averaging depth (DaSGD): 1 scores and "
                         "pulls against the previous round's master "
                         "snapshot so round r's exchange can overlap round "
                         "r+1's local compute (requires --comm-mode fused)")
    ap.add_argument("--use-pallas", action="store_true",
                    help="run the fused Pallas kernel paths (elastic comm, "
                         "batched AdaHessian local phase, model-internal "
                         "flash attention); interpret mode off-TPU. One "
                         "flag drives every kernel path (RunSpec is the "
                         "single source of truth)")
    ap.add_argument("--placement", default="single",
                    choices=("single", "sharded"),
                    help="worker placement: simulate all k workers on one "
                         "device, or shard_map the worker axis over the "
                         "mesh's 'pod' axis (requires --comm-mode fused; "
                         "k must divide over the device count)")
    ap.add_argument("--groups", type=int, default=1,
                    help="hierarchical averaging (ISSUE-10): partition the "
                         "slot axis into this many rack-sized groups, each "
                         "owning a sub-master that workers elastic-average "
                         "against every round; 1 = the flat topology "
                         "(requires --comm-mode fused when > 1)")
    ap.add_argument("--global-period", type=int, default=1,
                    help="rounds between sub-master↔global-master syncs "
                         "(τ_g = global_period·τ); the global master is "
                         "touched only every this many rounds")
    ap.add_argument("--coordinator-address", default=None, metavar="HOST:PORT",
                    help="multi-process mesh: jax.distributed coordinator "
                         "(process 0's address); launch one process per "
                         "host with matching --num-processes/--process-id")
    ap.add_argument("--num-processes", type=int, default=1,
                    help="total processes in the multi-process mesh")
    ap.add_argument("--process-id", type=int, default=0,
                    help="this process's index in 0..num_processes-1")
    ap.add_argument("--controller", default="none",
                    choices=("none", "rules"),
                    help="closed-loop membership control (repro.control): "
                         "'rules' runs the failure detector + rule policy "
                         "and applies evict/readmit at chunk boundaries")
    ap.add_argument("--detector-blind", action="store_true",
                    help="echo a mask-zeroed schedule view into records "
                         "(the controller never sees ground truth anyway; "
                         "this blinds the printed records too)")
    ap.add_argument("--elastic", action="store_true", default=True)
    ap.add_argument("--plain", dest="elastic", action="store_false")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-seed", type=int, default=0,
                    help="synthetic dataset generation seed; fixed by "
                         "default so --seed sweeps vary only init/batching/"
                         "schedule on identical data (the §VI convention)")
    ap.add_argument("--save", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    membership = args.membership_scenario
    plan = ()
    if args.membership_plan:
        membership = "plan"
        plan = parse_membership_plan(args.membership_plan)
    capacity = args.capacity
    schedule = None
    if args.trace:
        schedule = read_trace(args.trace)
        rounds, cap = schedule.fail.shape
        if (args.rounds, capacity or args.workers) != (rounds, cap):
            print(f"[train] trace {args.trace}: coercing rounds/capacity "
                  f"to the recorded ({rounds}, {cap})")
        args.rounds, capacity = rounds, cap
        args.workers = (int(schedule.active[0].sum())
                        if schedule.active is not None else cap)
        membership, plan = "static", ()  # the trace carries membership
    if membership != "static" and not capacity:
        # resize needs headroom: default the slot pool to the largest
        # worker count the scheduled stream ever reaches; a scale_up with
        # no explicit target grows into its headroom, so give it some
        capacity = max([args.workers, args.membership_k]
                       + [k for _, k in plan])
        if membership == "scale_up" and not args.membership_k:
            capacity = 2 * args.workers
    mesh = None
    if args.num_processes > 1 or args.coordinator_address:
        # multi-process mesh (ISSUE-10): initialize jax.distributed and
        # span the pod axis over every process's devices (process-local
        # fallback on CPU — see make_distributed_mesh)
        if args.placement != "sharded":
            raise SystemExit(
                "--coordinator-address/--num-processes need "
                "--placement sharded (the worker axis must live on the "
                "mesh for a multi-process run to mean anything)")
        from repro.launch.mesh import make_distributed_mesh

        mesh = make_distributed_mesh(
            coordinator_address=args.coordinator_address,
            num_processes=args.num_processes, process_id=args.process_id)
    if args.placement == "sharded":
        # the slot axis partitions evenly over the pod axis; pad capacity
        # up and leave the extra slots permanently inactive (uneven-shard
        # masking: shards hold equal slots, not equal live workers)
        import jax

        from repro.core.coordinator import padded_capacity

        n_pod = mesh.shape["pod"] if mesh is not None else jax.device_count()
        padded = padded_capacity(capacity or args.workers, n_pod)
        if padded != (capacity or args.workers):
            print(f"[train] padding capacity {capacity or args.workers} -> "
                  f"{padded} (multiple of the {n_pod}-way pod "
                  "axis; extra slots stay inactive)")
            capacity = padded
    ecfg = ElasticConfig(
        num_workers=args.workers, capacity=capacity, tau=args.tau,
        alpha=args.alpha, overlap_ratio=args.overlap,
        failure_prob=args.failure_prob,
        dynamic=not args.no_dynamic, comm_mode=args.comm_mode,
        staleness=args.staleness, placement=args.placement,
        failure_scenario=args.failure_scenario,
        score_clip=args.score_clip, u_zclip=args.u_zclip,
        byzantine_frac=args.byzantine_frac,
        byzantine_mode=args.byzantine_mode,
        byzantine_scale=args.byzantine_scale,
        hetero_dist=args.hetero_dist, hetero_sigma=args.hetero_sigma,
        hetero_slow_frac=args.hetero_slow_frac,
        hetero_slow_scale=args.hetero_slow_scale,
        groups=args.groups, global_period=args.global_period,
        membership_scenario=membership, membership_k=args.membership_k,
        membership_round=args.membership_round, membership_plan=plan)
    spec = RunSpec(
        schedule=schedule,
        arch=args.arch, smoke=args.smoke,
        optimizer=OptimizerConfig(name=args.optimizer, lr=args.lr),
        elastic=ecfg, rounds=args.rounds,
        rounds_per_call=args.rounds_per_call, seed=args.seed,
        plain=not args.elastic, batch_size=args.batch_size,
        seq_len=args.seq_len, n_data=8000, n_test=1000,
        data_seed=args.data_seed, save_path=args.save,
        use_pallas=args.use_pallas,
        controller=(None if args.controller == "none" else args.controller),
        detector_blind=args.detector_blind)
    sess = ElasticSession(spec, mesh=mesh)

    # multi-process runs: only process 0 narrates rounds (every process
    # still executes them; the final master-l2 line prints everywhere so a
    # launcher can assert cross-process agreement)
    is_main = args.process_id == 0
    t0 = time.time()
    if is_main and not spec.plain and sess.schedule.has_hetero:
        print(f"[train] persistent slot speeds: "
              f"{np.asarray(sess.schedule.speed[0]).round(3).tolist()}",
              flush=True)
    for rec in sess.run_iter():
        if not is_main:
            continue
        if spec.plain:
            print(f"step {rec.round}: loss={rec.loss:.4f}", flush=True)
            continue
        extra = ""
        if sess.schedule.has_membership or sess.controller is not None:
            extra += f" k={rec.num_active}/{sess.capacity}"
        if sess.schedule.has_stragglers:
            extra += f" straggle={rec.straggle.astype(int).tolist()}"
        if sess.schedule.has_restarts:
            extra += f" restart={rec.restart.astype(int).tolist()}"
        if sess.schedule.has_corruption:
            extra += f" corrupt={rec.corrupt.astype(int).tolist()}"
        if rec.g_h2 is not None and np.any(rec.g_h2):
            extra += f" g_h2={np.asarray(rec.g_h2).round(3).tolist()}"
        print(f"round {rec.round}: loss={rec.loss:.4f} "
              f"fails={rec.fail.astype(int).tolist()} "
              f"score={np.asarray(rec.score).round(3).tolist()} "
              f"h2={np.asarray(rec.h2).round(3).tolist()}{extra} "
              f"({time.time()-t0:.1f}s)", flush=True)
    # every process prints this (deterministic cross-process agreement
    # check for the distributed smoke: identical programs → identical l2)
    import jax

    l2 = float(np.sqrt(sum(
        float(np.sum(np.square(np.asarray(x, np.float64))))
        for x in jax.tree.leaves(sess.master_params))))
    print(f"[train] final master l2={l2:.10e}", flush=True)
    if sess.controller is not None:
        applied = [a for a in sess.controller.actuator.log if a.applied]
        print(f"[control] {len(applied)} membership action(s) applied:")
        for a in applied:
            print(f"[control]   round {a.round}: {a.action.describe()} "
                  f"-> {a.live_after} live")
    if args.dump_trace and sess.schedule is not None:
        write_trace(args.dump_trace, sess.schedule)
        print(f"[train] wrote scenario trace to {args.dump_trace}")
    if args.save:
        print(f"saved master params to {args.save}")


if __name__ == "__main__":
    main()
