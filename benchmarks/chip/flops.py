"""Operations and bytes of the work a round has to do, from its shapes.

``model_macs`` counts the multiply-accumulates of one image's forward
pass through the convolutions and the classifier (GroupNorm, ReLU and
pooling are not counted), and the parameters.  A training step costs
three forward passes' worth: the forward, and the backward's gradients
with respect to the activations and to the weights.  Recomputed work is
not counted.

``prox_bytes`` counts what the fused proximal-SGD update has to move per
local step: five float32 reads (theta, g, z, u, momentum) and two writes
(theta, momentum) of every parameter element of every worker.
"""
from __future__ import annotations

from .reference.resnet import block_shapes, widths

PROX_READS, PROX_WRITES = 5, 2


def model_macs(arch: dict) -> tuple[int, int]:
    """(forward multiply-accumulates per image, parameter count)."""
    size = arch["img_size"]
    stem, outs, _ = widths(arch)
    macs = size * size * 9 * 3 * stem
    params = 9 * 3 * stem + 2 * stem
    for _, _, cin, cmid, cout, s in block_shapes(arch):
        hw_in, hw_out = size * size, (size // s) ** 2
        if arch["bottleneck"]:
            convs = [(1, cin, cmid, hw_in), (9, cmid, cmid, hw_out),
                     (1, cmid, cout, hw_out)]
            norm_channels = 2 * cmid + cout
        else:
            convs = [(9, cin, cmid, hw_out), (9, cmid, cout, hw_out)]
            norm_channels = cmid + cout
        if s != 1 or cin != cout:
            convs.append((1, cin, cout, hw_out))
            norm_channels += cout
        macs += sum(k * ci * co * hw for k, ci, co, hw in convs)
        params += sum(k * ci * co for k, ci, co, _ in convs) \
            + 2 * norm_channels
        size //= s
    n = arch["n_classes"]
    macs += outs[-1] * n
    params += outs[-1] * n + n
    return macs, params


def train_flops_per_image(arch: dict) -> float:
    """Forward plus backward FLOPs of one image: 3 x 2 x the MACs."""
    return 6.0 * model_macs(arch)[0]


def prox_bytes_per_step(arch: dict, workers: int) -> int:
    """Bytes the prox update moves per local step over all workers."""
    return (PROX_READS + PROX_WRITES) * 4 * workers * model_macs(arch)[1]
