"""Plain ResNet for CIFAR-sized inputs, written from the published
architectures (He et al. 2016, Zagoruyko & Komodakis 2016) with GroupNorm
in place of BatchNorm, as the PruneX paper's functional setting uses.

``arch`` is the ``"arch"`` object of a configuration file under
``benchmarks/chip/configs``.  Nothing here imports the system under test.
Parameters are a nested dict keyed like the published layer names
(``stem``, ``gn0``, ``layer<s>/b<i>/conv1`` ..., ``fc_w``, ``fc_b``), so
that the comparison can pair them leaf by leaf.

Initialisation follows one stated recipe from the seed's key: the key is
split in 8; the stem takes part 0, the classifier part 7, and block ``i``
of stage ``s`` folds ``100*s + i`` into part 1 and splits the result once
per conv.  Every weight is ``N(0, 1/fan_in)`` with ``fan_in`` its
contracted size; GroupNorm scales start at 1 and biases at 0.

``dtype`` is the type every parameter, activation and sum is held in:
float32 for the reference (run it under
``jax.default_matmul_precision("highest")``), bfloat16 for its control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def widths(arch: dict) -> tuple[int, list[int], list[int]]:
    """(stem width, per-stage stream widths, per-stage inner widths).

    Explicit ``stem``/``outs``/``cmid`` entries (a reconfigured model) win;
    otherwise bottleneck stages stream ``4*w`` and run ``w*width_mult``
    inside, basic stages run ``w`` in both."""
    bb = arch["bottleneck"]
    mult = arch.get("width_mult", 1)
    outs = arch.get("outs") or [w * 4 if bb else w for w in arch["widths"]]
    cmid = arch.get("cmid") or [w * mult if bb else w
                                for w in arch["widths"]]
    stem = arch.get("stem") or arch["widths"][0]
    return stem, list(outs), list(cmid)


def stride(si: int, bi: int) -> int:
    return 2 if (bi == 0 and si > 0) else 1


def block_shapes(arch: dict):
    """Yield ``(stage, block, cin, cmid, cout, stride)`` for every block."""
    stem, outs, cmid = widths(arch)
    cin = stem
    for si, n in enumerate(arch["blocks"]):
        for bi in range(n):
            yield si, bi, cin, cmid[si], outs[si], stride(si, bi)
            cin = outs[si]


def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * (1.0 / math.sqrt(fan_in))).astype(dtype)


def _conv_w(key, kh, kw, cin, cout, dtype):
    return _normal(key, (kh, kw, cin, cout), kh * kw * cin, dtype)


def _gn(c, dtype):
    return {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)}


def init(arch: dict, key, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 8)
    stem, outs, _ = widths(arch)
    p = {"stem": _conv_w(ks[0], 3, 3, 3, stem, dtype), "gn0": _gn(stem, dtype)}
    for si, bi, cin, cmid, cout, s in block_shapes(arch):
        kb = jax.random.split(jax.random.fold_in(ks[1], si * 100 + bi),
                              4 if arch["bottleneck"] else 3)
        if arch["bottleneck"]:
            b = {"conv1": _conv_w(kb[0], 1, 1, cin, cmid, dtype),
                 "gn1": _gn(cmid, dtype),
                 "conv2": _conv_w(kb[1], 3, 3, cmid, cmid, dtype),
                 "gn2": _gn(cmid, dtype),
                 "conv3": _conv_w(kb[2], 1, 1, cmid, cout, dtype),
                 "gn3": _gn(cout, dtype)}
            kd = kb[3]
        else:
            b = {"conv1": _conv_w(kb[0], 3, 3, cin, cmid, dtype),
                 "gn1": _gn(cmid, dtype),
                 "conv2": _conv_w(kb[1], 3, 3, cmid, cout, dtype),
                 "gn2": _gn(cout, dtype)}
            kd = kb[2]
        if s != 1 or cin != cout:
            b["down"] = _conv_w(kd, 1, 1, cin, cout, dtype)
            b["gnd"] = _gn(cout, dtype)
        p.setdefault(f"layer{si}", {})[f"b{bi}"] = b
    p["fc_w"] = _normal(ks[7], (outs[-1], arch["n_classes"]), outs[-1], dtype)
    p["fc_b"] = jnp.zeros((arch["n_classes"],), dtype)
    return p


def conv(x, w, s=1):
    """'SAME'-padded NHWC convolution, HWIO weights."""
    return jax.lax.conv_general_dilated(
        x, w, (s, s), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


def group_norm(x, gn, size, eps=1e-5):
    """GroupNorm over groups of ``size`` consecutive channels."""
    n, h, w, c = x.shape
    xg = x.reshape(n, h, w, c // size, size)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mu), axis=(1, 2, 4), keepdims=True)
    y = ((xg - mu) / jnp.sqrt(var + eps)).reshape(n, h, w, c)
    return y * gn["scale"] + gn["bias"]


def _block(arch, b, x, s):
    gs = arch["gn_size"]
    relu = jax.nn.relu
    if arch["bottleneck"]:
        y = relu(group_norm(conv(x, b["conv1"]), b["gn1"], gs))
        y = relu(group_norm(conv(y, b["conv2"], s), b["gn2"], gs))
        y = group_norm(conv(y, b["conv3"]), b["gn3"], gs)
    else:
        y = relu(group_norm(conv(x, b["conv1"], s), b["gn1"], gs))
        y = group_norm(conv(y, b["conv2"]), b["gn2"], gs)
    skip = x
    if "down" in b:
        skip = group_norm(conv(x, b["down"], s), b["gnd"], gs)
    return relu(y + skip)


def logits(arch: dict, p: dict, images):
    x = images.astype(p["stem"].dtype)
    x = jax.nn.relu(group_norm(conv(x, p["stem"]), p["gn0"], arch["gn_size"]))
    for si, bi, *_, s in block_shapes(arch):
        x = _block(arch, p[f"layer{si}"][f"b{bi}"], x, s)
    x = jnp.mean(x, axis=(1, 2))
    return x @ p["fc_w"] + p["fc_b"]


def loss(arch: dict, p: dict, images, labels):
    """Mean softmax cross-entropy over the batch."""
    z = logits(arch, p, images)
    z = z - jnp.max(z, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(z), axis=-1))
    picked = jnp.take_along_axis(z, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)

