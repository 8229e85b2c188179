"""Plain reference of one elastic round, and the readings compared with it.

Independent of the program: it imports nothing of ``repro`` and takes
nothing the program made. It makes the weights from the seed by the
configuration's own init rule (the model's reference module beside its
configuration file), takes the batches and failures from the benchmark's
generator (``bench/traffic.py``), and follows the paper's equations:

- local phase (§IV-B, AdaHessian): for each of the k workers, τ steps of
  loss, gradient and one Hutchinson probe z ~ Rademacher (diag H ≈ z ⊙ Hz,
  Hz by forward-over-reverse), spatial averaging of |diag| in blocks of
  ``spatial_block`` along the last axis, Adam-style moments with the
  Hessian diagonal in the second moment, bias correction, the step;
- communication phase (§V-B, dynamic weighting, fused event-order form):
  u = log‖θ_i − θ_m‖ against the round-start master, the rolling history,
  the raw score, the piecewise-linear h1/h2, failed workers exchange
  nothing, worker pull θ_i − h1·(θ_i − θ_m), master
  θ_m + Σ_i g_i·(θ_i − θ_m) with g_i = h2_i·Π_{j>i}(1 − h2_j).

The PRNG keys follow the same rule from the seed as the program's session:
round r uses fold_in(key(seed), r), split into τ step keys, each split into
k worker keys, each split into one key per parameter leaf for z.

``Policy`` says in which precision the reference computes: ``REFERENCE``
(float32, matmuls at HIGHEST) decides ``correct``; a configuration's
``control`` policy, one step below the precision it states, must come out
as not correct.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.traffic import jax_seed

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Policy:
    """``activations``: dtype activations are kept in; ``operands``:
    precision of matmul operands, accumulated in float32: "float32" (at
    HIGHEST), "float32_high" (HIGH: three bfloat16 passes), "bfloat16", or
    "fp8" (float8_e4m3fn with a per-tensor scale in the forward value, the
    gradient passed straight through); ``store``: dtype the workers'
    parameters are kept in between steps."""

    activations: str = "float32"
    operands: str = "float32"
    store: str = "float32"

    def einsum(self, eq: str, a, b):
        if self.operands == "float32":
            return jnp.einsum(eq, a.astype(F32), b.astype(F32),
                              precision=jax.lax.Precision.HIGHEST)
        if self.operands == "float32_high":
            # HIGH written out, so that it means the same on every backend:
            # a·b ≈ hi(a)·hi(b) + hi(a)·lo(b) + lo(a)·hi(b), bf16 operands
            (ah, al), (bh, bl) = _split(a), _split(b)
            dot = lambda x, y: jnp.einsum(eq, x, y,
                                          preferred_element_type=F32)
            return (dot(ah, bh) + dot(ah, bl) + dot(al, bh)).astype(
                self.activations)
        if self.operands == "fp8":
            a, b = _fp8(a), _fp8(b)
        out = jnp.einsum(eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                         preferred_element_type=F32)
        return out.astype(self.activations)

    def act(self, x):
        return x.astype(self.activations)


def _split(x):
    x = x.astype(F32)
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(F32)).astype(jnp.bfloat16)


def _fp8(x):
    x = x.astype(F32)
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
                              / 448.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(q - x)


REFERENCE = Policy()


def policy(spec) -> Policy:
    """``"reference"`` or a dict of ``Policy`` fields (a configuration's
    ``control``)."""
    return REFERENCE if spec == "reference" else Policy(**spec)


def spatial_average(h, block: int):
    """Mean of |h| over blocks of ``block`` along the last axis (the
    largest divisor of the last dim not above ``block``)."""
    h = jnp.abs(h.astype(F32))
    if h.ndim == 0:
        return h
    d = h.shape[-1]
    b = next(c for c in range(min(block, d), 0, -1) if d % c == 0)
    hb = h.reshape(h.shape[:-1] + (d // b, b))
    return jnp.broadcast_to(hb.mean(-1, keepdims=True),
                            hb.shape).reshape(h.shape)


def rademacher(key, tree):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        2.0 * jax.random.bernoulli(k, 0.5, x.shape).astype(F32) - 1.0
        for k, x in zip(keys, leaves)])


def leaf_norms(tree) -> np.ndarray:
    return np.array([float(jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))))
                     for x in jax.tree.leaves(tree)])


@dataclasses.dataclass
class Readings:
    """What the comparison reads, from the program or from the reference.
    Leaf axes follow ``jax.tree.leaves`` of the parameter tree."""

    loss: np.ndarray          # (rounds,) mean local-phase loss
    u: np.ndarray             # (rounds, k)
    h1: np.ndarray            # (rounds, k)
    h2: np.ndarray            # (rounds, k)
    m_norm: np.ndarray        # (k, leaves) after the first chunk
    v_norm: np.ndarray        # (k, leaves) after the first chunk
    master_change: np.ndarray  # (leaves,) after all compared rounds
    worker_change: np.ndarray  # (k, leaves) after all compared rounds


class ElasticReference:
    """The k-worker elastic AdaHessian round in plain JAX.

    ``model`` is the configuration's reference module: ``init_params(key,
    config)`` gives the starting weights (values of the stored dtype, held
    in float32) and ``loss(params, batch, config, policy)`` the scalar loss.
    Worker i lives on ``devices[i % len(devices)]``.
    """

    FAULTS = (None, "half_batch", "scaled_grad", "no_exchange")

    def __init__(self, model, config: dict, traffic: dict, policy: Policy,
                 devices: List, fault: str | None = None):
        e = traffic["elastic"]
        if not e.get("dynamic", True) or e.get("comm_mode") != "fused":
            raise ValueError("the reference follows dynamic weighting in "
                             "the fused event-order form only")
        self.model, self.config, self.policy = model, config, policy
        self.k, self.tau = int(e["num_workers"]), int(e["tau"])
        self.alpha, self.score_k = float(e["alpha"]), float(e["score_k"])
        self.window = int(e["score_window"])
        c = np.asarray(e["score_weights"], np.float32)
        n = min(len(c), self.window - 1)
        self.c = c[:n] / c[:n].sum()
        opt = config["optimizer"]
        self.lr, (self.b1, self.b2) = float(opt["lr"]), opt["betas"]
        self.eps, self.block = float(opt["eps"]), int(opt["spatial_block"])
        self.power = float(opt["hessian_power"])
        if opt["name"] != "adahessian" or opt["hutchinson_samples"] != 1:
            raise ValueError("the reference follows AdaHessian with one "
                             "Hutchinson probe per step")
        if fault not in self.FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        # a fault planted in the reference reads what the comparison makes
        # of that fault in the program: half of each batch left out, one
        # leaf's gradient scaled by 1.1 where it is produced, or the
        # exchange between chips left out (the master takes only the pulls
        # of the workers on its own chip)
        self.fault = fault
        self.devices = devices
        self._step = jax.jit(self._local_step, donate_argnums=(0, 1, 2))

    # -- local phase ----------------------------------------------------------
    def _grads(self, w, batch, z):
        """(loss, gradient, Hz) of the batch's mean loss."""
        if self.fault == "half_batch":
            rows = jax.tree.leaves(batch)[0].shape[0] // 2
            batch = jax.tree.map(lambda x: x[:rows], batch)
        f = lambda p: self.model.loss(p, batch, self.config, self.policy)
        (loss, g), (_, hz) = jax.jvp(jax.value_and_grad(f), (w,), (z,))
        return loss, g, hz

    def _local_step(self, w, m, v, t, batch, key):
        z = rademacher(key, w)
        loss, g, hz = self._grads(w, batch, z)
        if self.fault == "scaled_grad":
            leaves, treedef = jax.tree.flatten(g)
            g = jax.tree.unflatten(treedef, [leaves[0] * 1.1] + leaves[1:])
        hs = jax.tree.map(lambda a, b: spatial_average(a * b, self.block),
                          z, hz)
        m = jax.tree.map(lambda m_, g_: self.b1 * m_ + (1 - self.b1) * g_,
                         m, g)
        v = jax.tree.map(lambda v_, h: self.b2 * v_ + (1 - self.b2) * h * h,
                         v, hs)
        tf = t.astype(F32)
        bc1, bc2 = 1 - self.b1 ** tf, 1 - self.b2 ** tf
        store = self.policy.store
        w = jax.tree.map(
            lambda p, m_, v_: (p - self.lr * (m_ / bc1) / (
                jnp.power(v_ / bc2 + 1e-30, self.power / 2) + self.eps))
            .astype(store).astype(F32), w, m, v)
        return w, m, v, loss

    # -- communication phase --------------------------------------------------
    def _h(self, a):
        k, al = self.score_k, self.alpha
        h1 = np.where(a < k, 1.0, np.where(a <= 0, 1 + (1 - al) / k
                                           * (a - k), al))
        h2 = np.where(a < k, 0.0, np.where(a <= 0, -al / k * a + al, al))
        return h1.astype(np.float32), h2.astype(np.float32)

    def run(self, seed: int, rounds: int, first_chunk: int,
            batches: Callable[[int], dict], fail: np.ndarray) -> Readings:
        """Follow ``rounds`` rounds from the seed. Worker i's weights and
        moments stay on its device; the master is kept on the host, where
        the weighted pulls are summed in worker order."""
        key = jax.random.key(jax_seed(seed))
        dev = lambda i: self.devices[i % len(self.devices)]
        p0 = jax.tree.map(np.asarray, self.model.init_params(key,
                                                             self.config))
        ws = [jax.device_put(p0, dev(i)) for i in range(self.k)]
        zeros = jax.tree.map(np.zeros_like, p0)
        ms = [jax.device_put(zeros, dev(i)) for i in range(self.k)]
        vs = [jax.device_put(zeros, dev(i)) for i in range(self.k)]
        master = jax.tree.map(np.copy, p0)
        hist = np.full((self.k, self.window), -30.0, np.float32)
        out = {"loss": [], "u": [], "h1": [], "h2": []}
        diff_of = jax.jit(lambda w, m: jax.tree.map(jnp.subtract, w, m))
        sq = jax.jit(lambda d: sum(jnp.sum(jnp.square(x))
                                   for x in jax.tree.leaves(d)))
        t = 0
        for r in range(rounds):
            keys_t = jax.random.split(jax.random.fold_in(key, r), self.tau)
            b = batches(r)
            losses = []
            for s in range(self.tau):
                keys_k = jax.random.split(keys_t[s], self.k)
                t += 1
                for i in range(self.k):
                    bi = jax.device_put(
                        jax.tree.map(lambda x: x[s, i], b), dev(i))
                    ws[i], ms[i], vs[i], loss = self._step(
                        ws[i], ms[i], vs[i], jnp.int32(t), bi,
                        jax.device_put(keys_k[i], dev(i)))
                    losses.append(loss)
            out["loss"].append(float(np.mean([float(x) for x in losses])))
            # comm: every worker against the round-start master
            diffs = [diff_of(ws[i], jax.device_put(master, dev(i)))
                     for i in range(self.k)]
            u = np.array([np.log(np.sqrt(float(sq(d))) + 1e-30)
                          for d in diffs], np.float32)
            hist = np.concatenate([hist[:, 1:], u[:, None]], axis=1)
            dh = (hist[:, 1:] - hist[:, :-1])[:, ::-1][:, :len(self.c)]
            a = dh @ self.c
            h1, h2 = self._h(a)
            h1 = np.where(fail[r], 0.0, h1).astype(np.float32)
            h2 = np.where(fail[r], 0.0, h2).astype(np.float32)
            om = 1.0 - h2
            g = np.array([h2[i] * np.prod(om[i + 1:]) for i in range(self.k)],
                         np.float32)
            store = self.policy.store
            pull = jax.tree.map(np.zeros_like, master)
            for i in range(self.k):
                ws[i] = jax.tree.map(
                    lambda w_, d: (w_ - h1[i] * d).astype(store).astype(F32),
                    ws[i], diffs[i])
                if self.fault != "no_exchange" or dev(i) == dev(0):
                    pull = jax.tree.map(
                        lambda acc, d: acc + np.asarray(g[i] * d), pull,
                        diffs[i])
                diffs[i] = None
            master = jax.tree.map(np.add, master, pull)
            for name, val in (("u", u), ("h1", h1), ("h2", h2)):
                out[name].append(val)
            if r + 1 == first_chunk:
                m_norm = np.stack([leaf_norms(x) for x in ms])
                v_norm = np.stack([leaf_norms(x) for x in vs])
        change = lambda a_, b_: leaf_norms(jax.tree.map(jnp.subtract, a_, b_))
        worker_change = np.stack([
            change(ws[i], jax.device_put(p0, dev(i))) for i in range(self.k)])
        return Readings(
            loss=np.array(out["loss"]), u=np.stack(out["u"]),
            h1=np.stack(out["h1"]), h2=np.stack(out["h2"]),
            m_norm=m_norm, v_norm=v_norm,
            master_change=change(master, p0), worker_change=worker_change)
