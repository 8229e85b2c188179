"""FLOP and byte counts against hand counts for paper-cnn's shapes."""
import json
from pathlib import Path

from bench import flops
from bench.spec import BENCH, load_module

CFG = json.loads((BENCH / "configs" / "paper-cnn.json").read_text())
REF = load_module(BENCH / "configs" / "paper-cnn.py")


def test_paper_cnn_products_by_hand():
    # conv1: 26·26 outputs × 3·3·1 inputs × 32 channels; conv2: 24·24 ×
    # 3·3·32 × 64; fc1: 12·12·64 × 128; fc2: 128 × 10
    assert REF.products(CFG, {}) == [
        (26 * 26 * 9 * 32, True), (24 * 24 * 288 * 64, False),
        (9216 * 128, False), (128 * 10, False)]
    assert REF.n_params(CFG) == (9 * 32 + 32) + (9 * 32 * 64 + 64) + (
        9216 * 128 + 128) + (128 * 10 + 10) == 1199882


def test_step_flops_factors():
    # first layer: fwd + weight grad + x·dW + its tangent = 4 products;
    # others: fwd + 2 backward + 2 forward tangents + 4 backward tangents
    assert flops.step_flops_per_sample([(100, True)]) == 2 * 100 * 4
    assert flops.step_flops_per_sample([(100, False)]) == 2 * 100 * 9
    per_sample = flops.step_flops_per_sample(REF.products(CFG, {}))
    assert per_sample == 2 * (194688 * 4 + (10616832 + 1179648 + 1280) * 9)
    assert per_sample == 213917184


def test_decoder_products_count_the_causal_half():
    cfg = json.loads((BENCH / "configs" / "stablelm-3b-2l.json").read_text())
    ref = load_module(BENCH / "configs" / "stablelm-3b-2l.py")
    S, d, f, v = 4, 2560, 6912, 50304
    got = ref.products(cfg, {"seq_len": S})
    pairs = 4 + 3 + 2 + 1
    layer = [(S * d * d, False)] * 4 + [(S * d * f, False)] * 3 + [
        (pairs * d, False)] * 2
    assert got == layer * 2 + [(S * d * v, False)]
    assert ref.n_params(cfg) == 416179200
