"""Microbenchmarks for the paper's hot paths (CPU timings; the TPU story is
the roofline analysis in EXPERIMENTS.md §Roofline)."""
import time

import jax
import jax.numpy as jnp


def _time(fn, *args, iters=20):
    fn(*args)  # compile
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # µs


def bench_comm_modes(ks=(4, 8, 16, 32), n=1 << 14):
    """Sequential-scan vs fused-batched communication phase, sweeping the
    worker axis. Runs the real ``ElasticTrainer.comm_phase`` on a synthetic
    parameter tree (n floats/worker) — the sequential scan is k serially
    dependent score+update steps, so its time grows ~linearly in k, while
    fused is one batched scoring pass plus one batched update whose time
    should grow sublinearly in k."""
    from repro.configs.base import ElasticConfig, OptimizerConfig
    from repro.core.coordinator import ElasticTrainer

    rows, times = [], {}
    for k in ks:
        key = jax.random.key(k)
        state = {
            "workers": {"w": jax.random.normal(key, (k, n))},
            "master": {"w": jnp.zeros((n,))},
            "u_hist": jnp.full((k, 5), -1.0, jnp.float32),
            "round": jnp.zeros((), jnp.int32),
        }
        fail = jnp.zeros((k,), bool)
        for mode in ("sequential", "fused"):
            tr = ElasticTrainer(
                None, OptimizerConfig(name="sgd"),
                ElasticConfig(num_workers=k, comm_mode=mode))
            f = jax.jit(lambda s, t=tr, fl=fail: t.comm_phase(s, fl)[0])
            us = min(_time(f, state) for _ in range(3))  # CPU noise guard
            times[(mode, k)] = us
            rows.append((f"comm_phase_{mode}_k{k}", us, f"n={n}"))
    k0, k1 = ks[0], ks[-1]
    for mode in ("sequential", "fused"):
        growth = times[(mode, k1)] / times[(mode, k0)]
        rows.append((f"comm_phase_{mode}_growth_k{k0}to{k1}", growth,
                     f"{k1 // k0}x workers -> {growth:.2f}x time"))
    rows.append((f"comm_phase_fused_speedup_k{k1}",
                 times[("sequential", k1)] / times[("fused", k1)],
                 f"sequential/fused at k={k1}"))
    return rows


def bench_local(ks=(4, 8), tau=1, batch=8, iters=5, probes=3):
    """Local-phase wall time per round (ISSUE-7), paper CNN + AdaHessian.

    Three variants of ``ElasticTrainer.local_phase`` at each worker count:

    - ``plain`` — the per-worker ``value_and_grad`` + Hutchinson ``jvp`` +
      optimizer step, vmapped over workers (the pre-fusion path).
    - ``fused_jnp`` — the fused structure (``fused_local=True``): gradient
      and HVP share one ``jax.linearize`` and all k moment/parameter
      updates run as one batched jnp expression. This isolates the
      structural win; it is bit-exact with ``plain``.
    - ``fused_pallas_interp`` (``fused_pallas`` on a TPU, where the kernel
      is compiled) — the same structure through the batched
      Pallas kernel in interpret mode. On CPU the interpreter's per-op
      dispatch dominates at CNN scale, so this row records the honest
      interpret-mode *overhead* (the kernel targets TPU); the fused-path
      win on CPU is the ``fused_jnp`` row.

    ``probes`` is ``hutchinson_samples``. It defaults to 3 (multi-probe
    Hutchinson, §IV-B) because that is where the fusion is structural
    rather than CSE-able: the plain path's probe scan re-derives
    ``jvp(grad_fn)`` — a fresh linearization of the backward pass — in
    every scan iteration, while the fused path linearizes once and each
    probe only replays the tangent map. On CPU XLA hoists/merges the
    duplicated work well enough that the end-to-end rows time the same
    to within noise; they are recorded as the honest context for the
    update-step rows below, where the fusion win is unambiguous.

    The ``update_*`` rows isolate the optimizer-update step the batched
    kernel replaces, at 1M params/worker: ``update_perworker`` is k
    separate single-worker AdaHessian step dispatches — exactly what the
    orphaned per-worker Pallas entry point forced on a multi-worker
    trainer — and ``update_batched`` is the one-call batched path
    (``adahessian_update_batched``, one fused expression / one kernel
    launch per τ-step instead of k). Both jitted; measured win ~3.7x at
    k=4 and ~1.8x at k=8 on CPU.
    """
    from repro.configs.base import ElasticConfig, OptimizerConfig, get_config
    from repro.core.coordinator import ElasticTrainer
    from repro.kernels import interpret_mode
    from repro.models.registry import build_model

    interpret = interpret_mode()
    model = build_model(get_config("paper_cnn"))
    record = {"what": "local", "arch": "paper-cnn", "tau": tau,
              "batch_size": batch, "iters": iters, "ks": list(ks),
              "hutchinson_samples": probes}
    ocfg = OptimizerConfig(name="adahessian", lr=1e-3,
                           hutchinson_samples=probes)
    for k in ks:
        ecfg = ElasticConfig(num_workers=k, tau=tau, comm_mode="fused")
        key = jax.random.key(k)
        batches = {
            "images": jax.random.normal(key, (tau, k, batch, 28, 28, 1),
                                        jnp.float32),
            "labels": jnp.zeros((tau, k, batch), jnp.int32),
        }
        rng = jax.random.key(1)
        pallas = "fused_pallas_interp" if interpret else "fused_pallas"
        variants = (("plain", {}), ("fused_jnp", {"fused_local": True}),
                    (pallas, {"use_pallas": True}))
        for label, kw in variants:
            tr = ElasticTrainer(model, ocfg, ecfg, **kw)
            state = tr.init_state(jax.random.key(0))
            f = jax.jit(
                lambda s, b, r, t=tr: t.local_phase(s, b, r)[0]["workers"])
            if label == "fused_pallas_interp":  # seconds/call, 1 probe
                us = _time(f, state, batches, rng, iters=2)
            else:  # CPU noise guard, as in bench_comm_modes
                us = min(_time(f, state, batches, rng, iters=iters)
                         for _ in range(3))
            record[f"k{k}_{label}_ms_per_round"] = round(us / 1e3, 3)
        record[f"k{k}_fused_speedup"] = round(
            record[f"k{k}_plain_ms_per_round"]
            / record[f"k{k}_fused_jnp_ms_per_round"], 3)

    from repro.kernels.adahessian.ops import adahessian_update_batched
    from repro.kernels.adahessian.ref import adahessian_step_ref

    n = 1 << 20
    record["update_params_per_worker"] = n
    for k in ks:
        keys = jax.random.split(jax.random.key(100 + k), 5)
        p, g, h, m = (jax.random.normal(ki, (k, n)) for ki in keys[:4])
        v = jnp.abs(jax.random.normal(keys[4], (k, n)))
        t = jnp.full((k,), 3, jnp.int32)
        step1 = jax.jit(
            lambda p, g, h, m, v, t: adahessian_step_ref(p, g, h, m, v,
                                                         ocfg, t))
        def perworker():  # k dispatches: the orphaned-kernel structure
            outs = [step1(p[i], g[i], h[i], m[i], v[i], t[i])
                    for i in range(k)]
            return outs[-1]
        tree = lambda x: {"w": x}
        opt = {"count": t - 1, "m": tree(m), "v": tree(v)}
        fb = jax.jit(lambda p, g, h, o: adahessian_update_batched(
            p, g, h, o, ocfg, use_kernel=False))
        def batched():
            return fb(tree(p), tree(g), tree(h), opt)
        ms_s = min(_time(perworker, iters=10) for _ in range(3)) / 1e3
        ms_b = min(_time(batched, iters=10) for _ in range(3)) / 1e3
        record[f"k{k}_update_perworker_ms"] = round(ms_s, 3)
        record[f"k{k}_update_batched_ms"] = round(ms_b, 3)
        record[f"k{k}_update_batched_speedup"] = round(ms_s / ms_b, 3)
    return record


def bench():
    rows = []
    from repro.core.elastic import elastic_update
    from repro.kernels import interpret_mode
    from repro.kernels.elastic.ops import elastic_update_pallas

    tree = {"w": jax.random.normal(jax.random.key(0), (1024, 1024))}
    mtree = {"w": jax.random.normal(jax.random.key(1), (1024, 1024))}
    f_jnp = jax.jit(lambda w, m: elastic_update(w, m, 0.1, 0.1))
    us = _time(f_jnp, tree, mtree)
    rows.append(("elastic_update_jnp_1M", us, f"{8 * 2 ** 20 / us:.0f}B/us"))
    interpret = interpret_mode()
    f_pal = lambda w, m: elastic_update_pallas(w, m, 0.1, 0.1,
                                               interpret=interpret)
    us = _time(f_pal, tree, mtree)
    if interpret:
        rows.append(("elastic_update_pallas_interp_1M", us, "interpret-mode"))
    else:
        rows.append(("elastic_update_pallas_1M", us,
                     f"{8 * 2 ** 20 / us:.0f}B/us"))

    from repro.configs.base import OptimizerConfig
    from repro.kernels.adahessian.ref import adahessian_step_ref

    cfg = OptimizerConfig()
    n = 1 << 20
    args = [jax.random.normal(jax.random.key(i), (n,)) for i in range(4)]
    args.append(jnp.abs(jax.random.normal(jax.random.key(9), (n,))))
    f = jax.jit(lambda p, g, h, m, v: adahessian_step_ref(
        p, g, h, m, v, cfg, 3))
    us = _time(f, *args)
    rows.append(("adahessian_step_jnp_1M", us, ""))

    from repro.nn.flash import blockwise_attention, naive_attention

    B, S, H, D = 1, 1024, 4, 64
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    fb = jax.jit(lambda q, k, v: blockwise_attention(
        q, k, v, q_pos=pos, kv_pos=pos))
    us_b = _time(fb, q, k, v, iters=5)
    fn = jax.jit(lambda q, k, v: naive_attention(
        q, k, v, q_pos=pos, kv_pos=pos))
    us_n = _time(fn, q, k, v, iters=5)
    rows.append(("attn_blockwise_1k", us_b, f"naive={us_n:.0f}us"))

    from repro.nn.gla import gla_chunked, gla_ref

    B, T, Hh, N, P = 1, 512, 4, 32, 32
    ks = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(ks[0], (B, T, Hh, N))
    k = jax.random.normal(ks[1], (B, T, Hh, N))
    v = jax.random.normal(ks[2], (B, T, Hh, P))
    lw = -jnp.abs(jax.random.normal(ks[3], (B, T, Hh))) * 0.1
    fc = jax.jit(lambda q, k, v, lw: gla_chunked(
        q, k, v, lw, chunk=64, scalar_decay=True)[0])
    us_c = _time(fc, q, k, v, lw, iters=5)
    fr = jax.jit(lambda q, k, v, lw: gla_ref(q, k, v, lw)[0])
    us_r = _time(fr, q, k, v, lw, iters=5)
    rows.append(("ssd_chunked_512", us_c, f"sequential={us_r:.0f}us "
                 f"speedup={us_r / us_c:.1f}x"))
    return rows
