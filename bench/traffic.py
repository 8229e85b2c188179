"""The one generator of training traffic: batches and failures from a seed.

A traffic mix (``bench/traffic/<mix>.json``) is data: the worker pool (k, τ,
placement, comm mode, ...), the per-worker batch, the sequence length, the
failure model and the rounds per jit call. This module turns a mix, a
configuration and ``--seed`` into

- the iid Bernoulli failure schedule of the paper (§VI), a copy of the
  program's generator so that no later change to the program moves it;
- the per-round data feed: a pool of rows made once from the seed, dealt
  out in order so that the rows of the first rounds all differ.

The same seed gives the same schedule and the same rows, in the program's
run and in the reference's.
"""
from __future__ import annotations

import numpy as np

# sub-streams of one seed
_FAIL, _DATA = 1, 2


def seed_stream(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def jax_seed(seed: int) -> int:
    """The seed as JAX's PRNG takes it without 64-bit mode (< 2**32)."""
    return int(seed) % (1 << 32)


def iid_failures(seed: int, rounds: int, k: int, rate: float) -> np.ndarray:
    """(rounds, k) bool: True suppresses that worker's exchange that round,
    independently with probability ``rate``."""
    if not 0.0 <= rate <= 1.0:
        raise ValueError(f"failure rate must be in [0, 1], got {rate}")
    return seed_stream(seed, _FAIL).random((rounds, k)) < rate


def failures(traffic: dict, seed: int) -> np.ndarray:
    f = traffic["failures"]
    if f["kind"] != "iid":
        raise ValueError(f"unknown failure model {f['kind']!r}")
    return iid_failures(seed, traffic["max_rounds"],
                        traffic["elastic"]["num_workers"], f["rate"])


class Feed:
    """Per-round batches shaped (τ, k, B, ...), as the coordinator takes
    them. Round r's rows are the pool's rows [r·n, (r+1)·n) modulo the pool
    (n = τ·k·B); the pool holds ``pool_rounds`` rounds of distinct rows.

    ``round_batches()`` is the interface the program's session calls once
    per round; ``batches(r)`` gives any round's batches to the reference.
    """

    def __init__(self, config: dict, traffic: dict, seed: int):
        e = traffic["elastic"]
        self.tau, self.k = int(e["tau"]), int(e["num_workers"])
        self.b = int(traffic["batch_per_worker"])
        self.n = self.tau * self.k * self.b
        rows = self.n * int(traffic["pool_rounds"])
        rng = seed_stream(seed, _DATA)
        data = config["data"]
        if data["kind"] == "images":
            h, w, c = data["shape"]
            self.pool = {
                "images": rng.standard_normal((rows, h, w, c),
                                              dtype=np.float32),
                "labels": rng.integers(0, data["classes"], rows,
                                       dtype=np.int32)}
            self._split = lambda x: x
        elif data["kind"] == "tokens":
            s = int(traffic["seq_len"])
            self.pool = {"seq": rng.integers(0, data["vocab"], (rows, s + 1),
                                             dtype=np.int32)}
            self._split = lambda x: {"tokens": x["seq"][..., :-1],
                                     "targets": x["seq"][..., 1:]}
        else:
            raise ValueError(f"unknown data kind {data['kind']!r}")
        self.rows = rows
        self.round = 0

    def batches(self, r: int) -> dict:
        lo = (r * self.n) % self.rows
        idx = (lo + np.arange(self.n)) % self.rows
        shape = (self.tau, self.k, self.b)
        out = {key: v[lo:lo + self.n] if lo + self.n <= self.rows
               else v[idx] for key, v in self.pool.items()}
        out = {key: v.reshape(shape + v.shape[1:]) for key, v in out.items()}
        return self._split(out)

    def round_batches(self) -> dict:
        out = self.batches(self.round)
        self.round += 1
        return out
