"""Squared group-norm reduction kernel (mask scores, paper §2.1).

x: (G, C, K) -> (G, C) sum of squares over the fan-in axis K.  Grid is
(G, C/bc, K/bk) with the K dimension sequential ("arbitrary"): partial
sums accumulate into the output tile, which Pallas keeps revisiting for
the same (g, c) block — the standard reduction pattern.  Blocks are the
whole dim or (8, 128)-aligned (Mosaic's tiling rule) on padded grids; the
pad columns of a non-dividing final K block are masked out of the sum.
The output is laid out (G, 1, C) so its block's last two dims are
(1, bc): the whole dim and a lane-aligned one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, out_ref, *, K, bk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.float32)
    if K % bk:
        col = k * bk + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(col < K, x, 0.0)
    out_ref[...] += jnp.sum(x * x, axis=-1)[None]


def group_norms_sq(x, *, block_c=128, block_k=512, interpret=False):
    G, C, K = x.shape
    bc = C if C <= block_c else block_c
    bk = K if K <= block_k else block_k
    grid = (G, pl.cdiv(C, bc), pl.cdiv(K, bk))
    out = pl.pallas_call(
        functools.partial(_kernel, K=K, bk=bk),
        out_shape=jax.ShapeDtypeStruct((G, 1, C), jnp.float32),
        grid=grid,
        in_specs=[pl.BlockSpec((None, bc, bk), lambda g, c, k: (g, c, k))],
        out_specs=pl.BlockSpec((None, 1, bc), lambda g, c, k: (g, 0, c)),
        interpret=interpret,
        name="group_norms_sq",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x)
    return out.reshape(G, C)
