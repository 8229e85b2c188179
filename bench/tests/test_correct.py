"""``correct`` at a size a test run can hold, on the CPU: the harness's look
for a chip is skipped and the rest of a run is driven. The unbroken program
comes out correct; the control (the reference in the precision below the
configuration's, put in the program's place) and each fault planted in the
timed path come out not correct, under the cells' own limits."""
import copy
import dataclasses
import json
import time

import jax
import pytest

from bench import compare, harness
from bench.spec import BENCH, load_module, resolve

SEED = 2**31 + 99


def small_cnn(name):
    cell = resolve(name)
    tr = copy.deepcopy(cell.traffic)
    tr["elastic"].update(num_workers=2, tau=1)
    tr.update(batch_per_worker=4, max_rounds=200)
    cell.traffic = tr
    return cell


def small_lm():
    """stablelm-3b-2l under its sharded mix, at a tiny width, on four CPU
    devices, in float32 so that the one-worker-per-chip path is held to the
    CNN cell's limits. (Its cell is not in BENCHMARK.json yet; PERF.md
    says why.)"""
    base = resolve("cnn-k4-tau4-iid")
    cfg_file = BENCH / "configs" / "stablelm-3b-2l.json"
    cell = dataclasses.replace(
        base, name="stablelm-3b-2l-k4-sharded", chips=4,
        config=json.loads(cfg_file.read_text()),
        traffic=json.loads((BENCH / "traffic"
                            / "k4-tau2-sharded-s2048.json").read_text()),
        reference=load_module(cfg_file.with_suffix(".py")))
    cfg = copy.deepcopy(cell.config)
    cfg["model"].update(d_model=64, num_heads=4, num_kv_heads=4, d_ff=96,
                        vocab_size=128, dtype="float32",
                        param_dtype="float32")
    cfg["data"]["vocab"] = 128
    cell.config = cfg
    tr = copy.deepcopy(cell.traffic)
    tr.update(seq_len=32, max_rounds=200)
    cell.traffic = tr
    return cell


def run(cell):
    return harness.run_cell(cell, SEED, 0.2, False,
                            devices=jax.devices()[:cell.chips],
                            started=time.perf_counter())


def control_correct(cell):
    rounds = cell.traffic["check_chunks"] * cell.traffic["rounds_per_call"]
    devs = jax.devices()[:cell.chips]
    ref = harness.reference_readings(cell, SEED, devs, "reference", rounds)
    ctl = harness.reference_readings(cell, SEED, devs,
                                     cell.config["control"], rounds)
    nums = compare.numbers(ctl, ref, cell.traffic["elastic"]["alpha"])
    return compare.judge(nums, cell.limits)[0]


# -- faults planted in the program -----------------------------------------
def unchanged_state(mp):
    from repro.core.coordinator import ElasticTrainer

    orig = ElasticTrainer._round

    def frozen(self, state, inputs, axis=None):
        return state, orig(self, state, inputs, axis=axis)[1]

    mp.setattr(ElasticTrainer, "_round", frozen)


def half_batch(mp):
    from repro.models.cnn import PaperCNN
    from repro.models.dense import DecoderLM

    for cls in (PaperCNN, DecoderLM):
        orig = cls.loss
        mp.setattr(cls, "loss", lambda self, p, b, _o=orig, **kw: _o(
            self, p, jax.tree.map(lambda x: x[:x.shape[0] // 2], b), **kw))


def altered_gradient(mp):
    from repro.core import coordinator

    orig = coordinator.hessian_diag_with_grad

    def scaled(*a, **kw):
        g, d = orig(*a, **kw)
        leaves, treedef = jax.tree.flatten(g)
        return jax.tree.unflatten(treedef, [leaves[0] * 1.1] + leaves[1:]), d

    mp.setattr(coordinator, "hessian_diag_with_grad", scaled)


def no_exchange(mp):
    from repro.core import coordinator

    orig = coordinator.elastic_update_batched
    mp.setattr(coordinator, "elastic_update_batched",
               lambda *a, axis_name=None, **kw: orig(*a, axis_name=None,
                                                     **kw))


@pytest.mark.parametrize("name", ["cnn-k4-tau4-iid", "cnn-k4-tau1-iid"])
def test_cnn_unbroken_is_correct(name):
    res = run(small_cnn(name))
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


def test_cnn_control_is_not_correct():
    assert not control_correct(small_cnn("cnn-k4-tau4-iid"))


@pytest.mark.parametrize("fault", [unchanged_state, half_batch,
                                   altered_gradient])
def test_cnn_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = run(small_cnn("cnn-k4-tau4-iid"))
    assert not res["correct"], res["checks"]


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
def test_lm_unbroken_is_correct():
    res = run(small_lm())
    assert res["correct"], res["checks"]


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
def test_lm_control_is_not_correct():
    assert not control_correct(small_lm())


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
@pytest.mark.parametrize("fault", [unchanged_state, half_batch,
                                   altered_gradient, no_exchange])
def test_lm_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = run(small_lm())
    assert not res["correct"], res["checks"]
