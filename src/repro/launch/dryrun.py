"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) combo.

MUST be the very first two lines (jax locks the device count on first init):
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.analysis.hlo import collective_bytes  # noqa: E402
from repro.analysis.roofline import DRYRUN_DEVICE_KIND  # noqa: E402
from repro.configs.base import (INPUT_SHAPES, OptimizerConfig,  # noqa: E402
                                get_config, list_archs, normalize_arch,
                                shape_supported)
from repro.core.coordinator import (ElasticTrainer, RoundInputs,  # noqa: E402
                                    padded_capacity)
from repro.configs.base import ElasticConfig  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.nn.param import (ParamSpec, abstract_tree, stack_specs,  # noqa: E402
                            tree_map_spec)
from repro.nn.sharding import physical_spec, tree_pspecs  # noqa: E402
from repro.train.steps import (abstract_train_state,  # noqa: E402
                               make_serve_step, make_train_step,
                               train_state_pspecs)


# §Perf hillclimb rule-set overrides (see EXPERIMENTS.md §Perf)
RULE_SETS = {
    "baseline": None,
    # Megatron-style sequence parallelism: shard the residual stream's
    # sequence dim over 'model' (norm/elementwise run on S/16 tokens; GSPMD
    # gathers at attention/MLP entry, reduce-scatters at exit)
    "seqpar": {"seq": "model"},
    # tensor-parallel expert FFNs for MoE archs whose expert count does not
    # divide the model axis (mixtral 8e on a 16-way axis)
    "expert_tp": {"expert_mlp": "model"},
    "seqpar_expert_tp": {"seq": "model", "expert_mlp": "model"},
    # keep MoE dispatch buffers data-local (no expert-sharded activation
    # constraint): expert weights are all-gathered per layer instead of
    # resharding the (B,E,C,d) token buffers — wins when weight bytes ≪
    # token-buffer bytes (moonshot: 64 small experts)
    "moe_local": {"act_expert": None},
    "moe_local_seqpar": {"act_expert": None, "seq": "model"},
}


def _adapt_cfg(cfg, shape_name):
    """Shape-specific faithful adjustments (DESIGN.md §long_500k)."""
    if shape_name == "long_500k" and cfg.family == "hybrid":
        # zamba2's shared attention block runs SWA at 500k context
        cfg = cfg.replace(sliding_window=4096)
    return cfg


def _named(tree_pspec, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree_pspec,
        is_leaf=lambda x: isinstance(x, P))


def _abstract_pod(spec_tree, mesh, pod_dim=0):
    """ParamSpec pytree → ShapeDtypeStructs sharded over 'pod' on axis
    ``pod_dim`` (the worker axis) and replicated elsewhere — the layouts the
    fully-manual sharded round holds its state in (coordinator
    ``_round_sharded``: per-worker tensors are replicated over any
    'data'/'model' axes, since its ``shard_map`` is fully manual)."""
    def struct(st):
        sh = NamedSharding(mesh, P(*([None] * pod_dim), "pod"))
        return jax.ShapeDtypeStruct(st.shape, st.dtype, sharding=sh)

    return jax.tree.map(struct, abstract_tree(spec_tree))


def _abstract_inputs(model, shape, mesh, rules=None):
    specs = model.input_specs(shape)
    structs = {k: jax.ShapeDtypeStruct(s.shape, s.dtype)
               for k, s in specs.items()}
    shardings = {
        k: NamedSharding(mesh, physical_spec(s.shape, s.axes, mesh, rules))
        for k, s in specs.items()}
    return structs, shardings


def _analyse(lowered, compiled, mesh, elapsed):
    n_dev = mesh.devices.size
    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        }
    except Exception:
        mem_d = {}
    try:
        hlo_text = compiled.as_text()
        coll = collective_bytes(hlo_text)
    except Exception:
        hlo_text, coll = "", {"total": None}
    # loop-aware re-accounting: XLA's cost_analysis visits while bodies
    # once, undercounting scanned layer stacks ~L× (see analysis/hlo_cost)
    try:
        from repro.analysis.hlo_cost import loop_aware_costs

        la = loop_aware_costs(hlo_text)
    except Exception as e:  # noqa: BLE001
        la = {"dot_flops": None, "bytes": None, "coll": {},
              "coll_total": None, "error": str(e)}
    return {
        "devices": int(n_dev),
        "target_device_kind": DRYRUN_DEVICE_KIND,
        "flops_per_device": cost.get("flops"),
        "bytes_per_device": cost.get("bytes accessed"),
        "collective_bytes_per_device": coll,
        "loop_aware": {
            "dot_flops_per_device": la.get("dot_flops"),
            "bytes_per_device": la.get("bytes"),
            "collective_bytes_per_device": la.get("coll"),
            "collective_total_per_device": la.get("coll_total"),
            # loop multipliers (with-loops ÷ trip1) for calibrating
            # cost_analysis numbers — see analysis/hlo_cost.py
            "flops_multiplier": (la["dot_flops"] / la["dot_flops_trip1"]
                                 if la.get("dot_flops_trip1") else None),
            "bytes_multiplier": (la["bytes"] / la["bytes_trip1"]
                                 if la.get("bytes_trip1") else None),
            "coll_multiplier": (la["coll_total"] / la["coll_total_trip1"]
                                if la.get("coll_total_trip1") else None),
        },
        "memory": mem_d,
        "compile_s": round(elapsed, 1),
    }


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               opt_name: str = "adahessian", remat: str = "none",
               rules=None, elastic_workers: int = 2,
               elastic_capacity: int = 0, groups: int = 1,
               global_period: int = 1):
    arch = normalize_arch(arch)
    shape = INPUT_SHAPES[shape_name]
    if not shape_supported(arch, shape_name):
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped",
                "reason": "full-attention arch at 500k (DESIGN.md)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = _adapt_cfg(get_config(arch), shape_name)
    model = build_model(cfg)
    opt_cfg = OptimizerConfig(name=opt_name)
    t0 = time.time()

    if shape.kind == "train" and multi_pod:
        # The paper's technique in production form, through the *real*
        # sharded backend (ISSUE-4): ElasticTrainer's shard_mapped round —
        # worker axis manual over 'pod', per-worker model left to GSPMD on
        # the ('data', 'model') auto axes. Identical code to what
        # `--placement sharded` executes on a host mesh; no dryrun-private
        # lowering of the round anymore.
        k = elastic_workers
        # the slot axis is capacity-padded to a multiple of the pod axis
        # (uneven-shard masking, ISSUE-5); with no --elastic-capacity the
        # pool is exactly k slots, as before
        cap = padded_capacity(elastic_capacity or k, mesh.shape["pod"])
        ecfg = ElasticConfig(num_workers=k,
                             capacity=(0 if cap == k else cap),
                             tau=1, comm_mode="fused", placement="sharded",
                             groups=groups, global_period=global_period)
        trainer = ElasticTrainer(model, opt_cfg, ecfg, mesh=mesh)
        wspec = stack_specs(model.spec, cap, "worker")
        f32spec = tree_map_spec(
            lambda s: ParamSpec(s.shape, jnp.float32, s.init, s.axes), wspec)
        mspec = tree_map_spec(
            lambda s: ParamSpec(s.shape, jnp.float32, s.init, s.axes),
            model.spec)
        in_specs = model.input_specs(shape)
        per_worker = {
            name: ParamSpec((1, cap, s.shape[0] // cap) + s.shape[1:],
                            s.dtype, axes=(None, "worker") + s.axes)
            for name, s in in_specs.items()}
        rep = NamedSharding(mesh, P())
        state = {
            "workers": _abstract_pod(wspec, mesh),
            "opt": {"count": _abstract_pod(
                        ParamSpec((cap,), jnp.int32, axes=("worker",)),
                        mesh),
                    "m": _abstract_pod(f32spec, mesh),
                    "v": _abstract_pod(f32spec, mesh)},
            "master": jax.tree.map(
                lambda st: jax.ShapeDtypeStruct(st.shape, st.dtype,
                                                sharding=rep),
                abstract_tree(mspec)),
            "u_hist": _abstract_pod(
                ParamSpec((cap, ecfg.score_window), jnp.float32), mesh),
            "round": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
        }
        state["master_prev"] = state["master"]
        if trainer._hier:
            # hierarchical lowering (ISSUE-10): replicated (G, ...)
            # sub-master trees + rack-level history, like the master
            G = trainer._n_groups
            state["submasters"] = jax.tree.map(
                lambda st: jax.ShapeDtypeStruct((G,) + st.shape, st.dtype,
                                                sharding=rep),
                abstract_tree(mspec))
            state["g_u_hist"] = jax.ShapeDtypeStruct(
                (G, ecfg.score_window), jnp.float32, sharding=rep)
        slot_mask = lambda: _abstract_pod(ParamSpec((cap,), jnp.bool_), mesh)
        inputs = RoundInputs(
            batches=_abstract_pod(per_worker, mesh, pod_dim=1),
            rng=jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
            fail=slot_mask(),
            failed_recent=slot_mask(),
            # capacity-padded pools lower the masked round (live-membership
            # select + join re-seat in the graph); exact-fit pools keep the
            # fixed-k specialized trace
            active=slot_mask() if cap > k else None,
            join=slot_mask() if cap > k else None)
        jitted = jax.jit(
            lambda s, i: trainer._round_sharded(s, i, chunk=False),
            donate_argnums=(0,))
        # no `with mesh:` here — the sharded round carries its own mesh via
        # shard_map, and an *active* mesh context would turn the model's
        # internal logical_constraints into manual-axis violations (they
        # no-op at runtime too; the session never enters a mesh context)
        lowered = jitted.lower(state, inputs)
        compiled = lowered.compile()
        out = _analyse(lowered, compiled, mesh, time.time() - t0)
        out["lowered_kind"] = "elastic_round_step_sharded"

    elif shape.kind == "train":
        from repro.configs.base import TrainConfig

        if opt_name == "adahessian_stale":
            # beyond-paper lazy-Hessian off-refresh step (§Perf)
            from repro.train.steps import make_train_step_stale_hessian

            opt_cfg = OptimizerConfig(name="adahessian")
            train_step = make_train_step_stale_hessian(
                model, opt_cfg, TrainConfig(remat=remat))
        else:
            train_step = make_train_step(model, opt_cfg,
                                         TrainConfig(remat=remat))
        state = abstract_train_state(model, opt_cfg)
        state_sh = _named(train_state_pspecs(model, opt_cfg, mesh, rules),
                          mesh)
        batch, batch_sh = _abstract_inputs(model, shape, mesh, rules)
        rep = NamedSharding(mesh, P())
        jitted = jax.jit(train_step, in_shardings=(state_sh, batch_sh, rep),
                         donate_argnums=(0,))
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
        with mesh:
            lowered = jitted.lower(state, batch, rng)
            compiled = lowered.compile()
        out = _analyse(lowered, compiled, mesh, time.time() - t0)
        out["lowered_kind"] = "train_step"

    else:
        # serving: prefill or decode
        params = abstract_tree(model.spec)
        params_sh = _named(tree_pspecs(model.spec, mesh, rules), mesh)
        cache_len = shape.seq_len
        B = shape.global_batch
        cache_spec = model.cache_spec(B, cache_len)
        cache = abstract_tree(cache_spec)
        cache_sh = _named(tree_pspecs(cache_spec, mesh, rules), mesh)
        batch, batch_sh = _abstract_inputs(model, shape, mesh, rules)
        rep = NamedSharding(mesh, P())
        if shape.kind == "prefill":
            step = make_serve_step(model, "prefill")
            jitted = jax.jit(step,
                             in_shardings=(params_sh, batch_sh, cache_sh),
                             donate_argnums=(2,))
            args = (params, batch, cache)
        else:
            step = make_serve_step(model, "decode")
            jitted = jax.jit(
                step, in_shardings=(params_sh, batch_sh, cache_sh, rep),
                donate_argnums=(2,))
            args = (params, batch, cache,
                    jax.ShapeDtypeStruct((), jnp.int32))
        with mesh:
            lowered = jitted.lower(*args)
            compiled = lowered.compile()
        out = _analyse(lowered, compiled, mesh, time.time() - t0)
        out["lowered_kind"] = f"serve_step/{shape.kind}"

    out.update({"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "ok", "optimizer": opt_name, "remat": remat,
                "rules": rules or {}})
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--opt", default="adahessian")
    ap.add_argument("--remat", default="none", choices=["none", "full"])
    ap.add_argument("--rules", default="baseline",
                    choices=sorted(RULE_SETS))
    ap.add_argument("--elastic-workers", type=int, default=2,
                    help="initial live workers in the multi-pod elastic "
                         "train lowering")
    ap.add_argument("--capacity", type=int, default=0,
                    help="worker-slot capacity for the elastic lowering "
                         "(0 = exactly --elastic-workers); padded up to a "
                         "multiple of the pod axis, extra slots inactive — "
                         "capacities > workers lower the membership-masked "
                         "round")
    ap.add_argument("--groups", type=int, default=1,
                    help="hierarchical elastic lowering (ISSUE-10): rack "
                         "count for the sub-master level; 1 = flat")
    ap.add_argument("--global-period", type=int, default=1,
                    help="rounds between sub-master↔master global syncs "
                         "in the hierarchical lowering")
    ap.add_argument("--out", default=None)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    pods = [False, True] if args.both_meshes else [args.multi_pod]

    done = set()
    if args.skip_existing and args.out and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                r = json.loads(line)
                if r.get("status") in ("ok", "skipped"):
                    done.add((r["arch"], r["shape"], r["multi_pod"]))

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                if (normalize_arch(arch), shape, mp) in done:
                    continue
                tag = f"{arch} × {shape} × {'2x16x16' if mp else '16x16'}"
                try:
                    r = dryrun_one(arch, shape, multi_pod=mp,
                                   opt_name=args.opt, remat=args.remat,
                                   rules=RULE_SETS[args.rules],
                                   elastic_workers=args.elastic_workers,
                                   elastic_capacity=args.capacity,
                                   groups=args.groups,
                                   global_period=args.global_period)
                except Exception as e:  # noqa: BLE001
                    r = {"arch": normalize_arch(arch), "shape": shape,
                         "multi_pod": mp, "status": "error",
                         "error": f"{type(e).__name__}: {e}",
                         "trace": traceback.format_exc()[-2000:]}
                results.append(r)
                status = r["status"]
                extra = ""
                if status == "ok":
                    fl = r.get("flops_per_device")
                    extra = (f" flops/dev={fl:.3e}" if fl else "") + \
                        f" compile={r['compile_s']}s"
                print(f"[dryrun] {tag}: {status}{extra}", flush=True)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(r) + "\n")
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, "
          f"{len(results) - n_ok - n_skip} failed")
    return results


if __name__ == "__main__":
    main()
