"""Whole round program (``repro.core.coordinator``): model FLOPs of the
traced window's rounds (``bench/flops.py``: forward, backward and the
Hutchinson HVP of every local step) over the window's seconds times the
chips times the chip's bf16 peak."""


def read(run):
    if run.rounds == 0 or run.window_s <= 0:
        return None
    peak = run.peaks["bf16_flops_per_s"] * run.chips
    return 100.0 * run.flops_per_round * run.rounds / run.window_s / peak
