"""The generator is deterministic in the seed, and the compared rounds see
rows that all differ."""
import json

import numpy as np
import pytest

from bench import traffic
from bench.spec import BENCH

SEED = 2**31 + 12345


def load(kind, name):
    return json.loads((BENCH / kind / name).read_text())


def test_iid_schedule_is_deterministic_in_seed():
    a = traffic.iid_failures(SEED, 3000, 8, 1 / 3)
    b = traffic.iid_failures(SEED, 3000, 8, 1 / 3)
    c = traffic.iid_failures(SEED + 1, 3000, 8, 1 / 3)
    assert a.dtype == bool and a.shape == (3000, 8)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert abs(a.mean() - 1 / 3) < 0.02


@pytest.mark.parametrize("cfg,mix", [
    ("paper-cnn.json", "k4-tau4-iid.json"),
    ("paper-cnn.json", "k4-tau1-iid.json"),
])
def test_feed_is_deterministic_and_rows_differ(cfg, mix):
    config, tr = load("configs", cfg), load("traffic", mix)
    f1, f2 = traffic.Feed(config, tr, SEED), traffic.Feed(config, tr, SEED)
    e = tr["elastic"]
    rounds = tr["check_chunks"] * tr["rounds_per_call"]
    seen = []
    for r in range(rounds):
        a, b = f1.round_batches(), f2.batches(r)
        for key in a:
            assert np.array_equal(a[key], b[key])
        x = a["images"]
        assert x.shape[:3] == (e["tau"], e["num_workers"],
                               tr["batch_per_worker"])
        seen.append(x.reshape(-1, x[0, 0, 0].size))
    rows = np.concatenate(seen)
    assert len(np.unique(rows, axis=0)) == len(rows)
    other = traffic.Feed(config, tr, SEED + 1).batches(0)
    assert not np.array_equal(other["images"], f1.batches(0)["images"])


def test_token_feed_shapes():
    config = load("configs", "stablelm-3b-2l.json")
    tr = load("traffic", "k4-tau2-sharded-s2048.json")
    tr = dict(tr, seq_len=16)
    b = traffic.Feed(config, tr, SEED).batches(1)
    assert b["tokens"].shape == (2, 4, tr["batch_per_worker"], 16)
    assert np.array_equal(b["tokens"][..., 1:], b["targets"][..., :-1])
    assert b["tokens"].max() < config["data"]["vocab"]
