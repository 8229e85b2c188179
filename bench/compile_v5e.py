#!/usr/bin/env python3
"""Compile one chunk of a cell for a described TPU v5e host, without a chip.

    JAX_PLATFORMS=cpu python3 bench/compile_v5e.py --workload <cell> \
        [--batch 1 2 4 ...]

Builds the cell's trainer at its published widths on a mesh of the described
``v5e:2x2`` topology's devices (the first ``chips`` of them), compiles the
chunk program the window drives (``round_chunk`` or ``round_chunk_sharded``)
for each per-worker batch given, and prints the compiler's
``memory_analysis`` per device. Nothing runs and nothing is allocated; this
is how the per-worker batch of a cell is fitted to a chip's 16 GB.
"""
from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, nargs="*")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bench.spec import resolve
    from repro.configs.base import ElasticConfig, ModelConfig, OptimizerConfig
    from repro.core.coordinator import ElasticTrainer, RoundInputs
    from repro.models.registry import build_model

    jax.config.update("jax_enable_compilation_cache", False)
    cell = resolve(args.workload)
    tr, cfg = cell.traffic, cell.config
    jax.config.update("jax_default_matmul_precision",
                      cfg.get("matmul_precision"))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devs = np.array(topo.devices[:cell.chips]).reshape(cell.chips, 1, 1)
    mesh = Mesh(devs, ("pod", "data", "model"))
    e = dict(tr["elastic"])
    e["score_weights"] = tuple(e["score_weights"])
    ecfg = ElasticConfig(**e)
    o = dict(cfg["optimizer"])
    o["betas"] = tuple(o["betas"])
    model = build_model(ModelConfig(**dict(cfg["model"], use_pallas=bool(
        tr["use_pallas"]))))
    sharded = ecfg.placement == "sharded"
    trainer = ElasticTrainer(model, OptimizerConfig(**o), ecfg,
                             use_pallas=bool(tr["use_pallas"]),
                             mesh=mesh if sharded else None)
    state = jax.eval_shape(trainer.init_state, jax.random.key(0))
    if sharded:
        specs = trainer.state_shard_specs()
        state = {k: jax.tree.map(lambda s, p=specs[k]: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, p)), v)
            for k, v in state.items()}
        put = lambda shape, dt, spec: jax.ShapeDtypeStruct(
            shape, dt, sharding=NamedSharding(mesh, spec))
    else:
        one = NamedSharding(mesh, P())
        state = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), state)
        put = lambda shape, dt, spec: jax.ShapeDtypeStruct(
            shape, dt, sharding=one)
    R, tau, k = int(tr["rounds_per_call"]), ecfg.tau, ecfg.cap
    S = int(tr.get("seq_len", 0))
    # the session drives round_step(_sharded) when a chunk is one round
    name = "round_" + ("step" if R == 1 else "chunk") + (
        "_sharded" if sharded else "")
    fn = getattr(ElasticTrainer, name)
    lead = () if R == 1 else (R,)
    wk = P(*((None,) * len(lead)), "pod")
    bk = P(*((None,) * len(lead)), None, "pod")
    for b in args.batch or [int(tr["batch_per_worker"])]:
        if cfg["data"]["kind"] == "tokens":
            tok = put(lead + (tau, k, b, S), jnp.int32, bk)
            batches = {"tokens": tok, "targets": tok}
        else:
            h, w, c = cfg["data"]["shape"]
            batches = {"images": put(lead + (tau, k, b, h, w, c),
                                     jnp.float32, bk),
                       "labels": put(lead + (tau, k, b), jnp.int32, bk)}
        key = jax.eval_shape(
            lambda: jax.random.split(jax.random.key(0), R)[
                0 if R == 1 else slice(None)])
        inputs = RoundInputs(
            batches=batches, rng=put(key.shape, key.dtype, P()),
            fail=put(lead + (k,), jnp.bool_, wk),
            failed_recent=put(lead + (k,), jnp.bool_, wk))
        compiled = fn.lower(trainer, state, inputs).compile()
        ma = compiled.memory_analysis()
        total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        print(f"batch {b}: per device argument {ma.argument_size_in_bytes} "
              f"output {ma.output_size_in_bytes} alias "
              f"{ma.alias_size_in_bytes} temp {ma.temp_size_in_bytes} "
              f"total {total} ({total / 1e9:.2f} GB)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
