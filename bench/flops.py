"""Operations and bytes from shapes: the yardstick of MFU and rooflines.

Model FLOPs of one training sample of the elastic AdaHessian local step.
Each matrix product of the forward pass, of size M·K·N multiply-adds per
sample (2·M·K·N FLOPs), costs in the step:

- forward: 1 product;
- backward: 2 (the weight gradient and the input gradient);
- Hutchinson HVP, forward-over-reverse (``jvp`` of the gradient, which
  AdaHessian needs once per step): the tangent of y = x·W is
  ẋ·W + x·Ẇ (2 products), and the tangents of the two backward products
  are 2 products each (4). So 6 more.

That is 9 products per forward product. The first layer of a network whose
input is data (not a parameter) has no input gradient, and its input has no
tangent: 1 (forward) + 1 (weight gradient) + 1 (x·Ẇ) + 1 (tangent of the
weight gradient, ẋ = 0 leaves xᵀ·ḋy) = 4. Products of two activations
(attention scores and values) have no weights but two input gradients: 9 as
well. Elementwise work (norms, softmax, the optimizer) is not counted, as
MFU conventionally does not; nothing recomputed is counted twice.
"""
from __future__ import annotations

from typing import Iterable, Tuple

PER_PRODUCT = 9        # forward + backward + HVP tangents, see above
PER_PRODUCT_FIRST = 4  # the data-input layer


def step_flops_per_sample(products: Iterable[Tuple[int, bool]]) -> float:
    """``products``: (multiply-adds per sample, is_first_layer) for each
    matrix product of one forward pass. Returns FLOPs of one local step per
    sample (forward, backward and one Hutchinson HVP)."""
    total = 0.0
    for macs, first in products:
        total += 2.0 * macs * (PER_PRODUCT_FIRST if first else PER_PRODUCT)
    return total
