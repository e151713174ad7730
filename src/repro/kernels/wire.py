"""Fused wire-path kernels: one streaming pass per payload leaf.

Encoding a consensus payload as separate XLA ops costs a pass to reduce
the per-row abs-max and another to scale/round/cast (and, for q4, to
pack).  These kernels collapse the encode into ONE pass: each
(block_r, C) row block is loaded into VMEM once, reduced to its per-row
abs-max, and written back quantized (q4: nibble-packed on the way out).
Decode is a per-row multiply that XLA fuses into whatever consumes it
(``kernels.ops``), so it has no kernel.

Scale granularity is one f32 scale per ROW of the (R, C) 2-D view —
deterministic in the leaf shape, NOT in the tunable kernel block size,
so the wire format and the analytic ``wire_bytes`` accounting stay
stable however the kernel is tiled (DESIGN.md "Per-row wire scales").

The q4 format packs two channels per byte along the minor axis (odd
minor dims carry one zero pad nibble); nibbles are two's-complement
4-bit in [-7, 7], sign-extended on decode as ``(n ^ 8) - 8``.  Mosaic
cannot interleave lanes (no lane-strided loads, no minor-dim reshape),
so the pack runs on the MXU: byte j = n[2j] + 16 * n[2j+1] is a product
with a constant 0/1/16 matrix — exact at any matmul precision, since
nibbles in [0, 15], the weights and every sum (at most 255) are exact in
bf16.

Grids pad with ``pl.cdiv``: a non-dividing final row block reads
garbage pad rows whose outputs fall outside the logical shape and are
discarded — no masking pass, no block-size degradation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# VMEM bytes one f32 row block may take: the block is double-buffered
# next to its output, inside v5e's 16 MiB default scoped VMEM
_BLOCK_BYTES = 2 << 20


def _row_grid(R: int, C: int, block_r: int) -> tuple[int, tuple[int]]:
    """Row block (a multiple of 8, or all of R) + its padded grid."""
    fit = max(8, (_BLOCK_BYTES // (4 * max(C, 1))) // 8 * 8)
    br = min(block_r, fit)
    br = R if R <= br else br
    return br, (pl.cdiv(R, br),)


def _row_scale(x, levels):
    return jnp.max(jnp.abs(x), axis=1, keepdims=True) / levels + 1e-30


# ---------------------------------------------------------------------------
# int8
# ---------------------------------------------------------------------------


def _quant_kernel(x_ref, q_ref, s_ref, *, levels):
    x = x_ref[...].astype(jnp.float32)
    s = _row_scale(x, levels)
    q_ref[...] = jnp.clip(jnp.round(x / s), -levels, levels).astype(jnp.int8)
    s_ref[...] = s


def quantize_rows(x, *, levels=127, block_r=256, interpret=False):
    """x: (R, C) -> (q int8 (R, C), scale f32 (R, 1)): per-row abs-max +
    quantize in one pass over the block in VMEM."""
    R, C = x.shape
    br, grid = _row_grid(R, C, block_r)
    return pl.pallas_call(
        functools.partial(_quant_kernel, levels=levels),
        out_shape=(jax.ShapeDtypeStruct((R, C), jnp.int8),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)),
        grid=grid,
        in_specs=[pl.BlockSpec((br, C), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((br, C), lambda i: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i: (i, 0))),
        interpret=interpret,
        name="quantize_rows",
    )(x)


# ---------------------------------------------------------------------------
# q4: quantize + pack two channels per byte
# ---------------------------------------------------------------------------

_PACK_IN = 256    # unpacked lanes per MXU pack step (-> 128 packed lanes)


def _pack_matrix(n_in: int, n_out: int):
    """(n_in, n_out): column j takes nibble 2j at weight 1 and nibble
    2j+1 at weight 16."""
    r = jax.lax.broadcasted_iota(jnp.int32, (n_in, n_out), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n_in, n_out), 1)
    w = jnp.where(r % 2 == 0, 1.0, 16.0)
    return jnp.where(r // 2 == c, w, 0.0)


def _q4_quant_kernel(x_ref, p_ref, s_ref):
    s = _row_scale(x_ref[...].astype(jnp.float32), 7.0)
    s_ref[...] = s

    def pack(lo, lo_out, n_in, n_out, mat):
        x = x_ref[:, pl.ds(lo, n_in)].astype(jnp.float32)
        q = jnp.clip(jnp.round(x / s), -7, 7).astype(jnp.int32) & 0xF
        b = jnp.dot(q.astype(jnp.float32), mat,
                    preferred_element_type=jnp.float32)
        p_ref[:, pl.ds(lo_out, n_out)] = b.astype(jnp.int32) \
            .astype(jnp.uint8)

    C = x_ref.shape[1]
    full = C // _PACK_IN
    if full:
        mat = _pack_matrix(_PACK_IN, _PACK_IN // 2)

        def body(j, carry):
            half = _PACK_IN // 2
            pack(pl.multiple_of(j * _PACK_IN, _PACK_IN),
                 pl.multiple_of(j * half, half), _PACK_IN, half, mat)
            return carry
        jax.lax.fori_loop(0, full, body, 0)
    tail = C - full * _PACK_IN
    if tail:
        n_out = (tail + 1) // 2
        pack(full * _PACK_IN, full * _PACK_IN // 2, tail, n_out,
             _pack_matrix(tail, n_out))


def quantize_pack_q4(x, *, block_r=256, interpret=False):
    """x: (R, C) -> (packed uint8 (R, ceil(C/2)), scale f32 (R, 1)):
    per-row abs-max, quantize to [-7, 7], and nibble-pack in one pass."""
    R, C = x.shape
    Cp = (C + 1) // 2
    br, grid = _row_grid(R, C, block_r)
    return pl.pallas_call(
        _q4_quant_kernel,
        out_shape=(jax.ShapeDtypeStruct((R, Cp), jnp.uint8),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)),
        grid=grid,
        in_specs=[pl.BlockSpec((br, C), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((br, Cp), lambda i: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i: (i, 0))),
        interpret=interpret,
        name="quantize_pack_q4",
    )(x)
