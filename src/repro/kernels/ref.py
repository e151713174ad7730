"""Pure-jnp oracles for every Pallas kernel (the correctness contract the
interpret-mode tests assert against)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fused_prox_sgd_ref(theta, g, z, u, mom, *, eta, rho, momentum):
    """Paper Eq. 8 + momentum, one fused memory pass:
    g_tot = g + rho*(theta - z + u);  m' = mu*m + g_tot;  th' = th - eta*m'.
    """
    gtot = g + rho * (theta - z + u)
    mom_new = momentum * mom + gtot
    return theta - eta * mom_new, mom_new


def quantize_rows_ref(x, levels=127):
    """x: (R, C) -> (q int8, scale f32 (R, 1)) per-row symmetric
    quantization (the wire.py scale-granularity contract)."""
    x = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(x), axis=1, keepdims=True) / levels + 1e-30
    q = jnp.clip(jnp.round(x / s), -levels, levels).astype(jnp.int8)
    return q, s


def gather_quantize_ref(x, idx, levels=127):
    """Two-pass reference of the fused kept-gather + quantize encode."""
    return quantize_rows_ref(jnp.take(x, idx, axis=1), levels)


def gather_dequantize_ref(q, s, idx):
    """(R, B) int8 + (R, 1) scale gathered by idx -> f32 (R, len(idx))."""
    return jnp.take(q, idx, axis=1).astype(jnp.float32) * s


def pack_q4_ref(q):
    """(R, n) int nibble values in [-8, 7] -> (R, ceil(n/2)) uint8, two
    two's-complement nibbles per byte (even column = low nibble)."""
    q = q.astype(jnp.int32) & 0xF
    if q.shape[1] % 2:
        q = jnp.pad(q, ((0, 0), (0, 1)))
    q = q.reshape(q.shape[0], -1, 2)
    return (q[..., 0] | (q[..., 1] << 4)).astype(jnp.uint8)


def unpack_q4_ref(p, n):
    """(R, Cp) uint8 -> (R, n) int32, sign-extended from 4 bits."""
    p = p.astype(jnp.int32)
    q = jnp.stack([p & 0xF, (p >> 4) & 0xF], axis=-1).reshape(p.shape[0], -1)
    return ((q ^ 8) - 8)[:, :n]


def quantize_pack_q4_ref(x):
    """x: (R, C) -> (packed uint8 (R, ceil(C/2)), scale f32 (R, 1))."""
    x = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 7.0 + 1e-30
    q = jnp.clip(jnp.round(x / s), -7, 7).astype(jnp.int32)
    return pack_q4_ref(q), s


def group_norms_ref(x):
    """x: (G, C, K) -> squared Frobenius norms (G, C) over the trailing
    fan-in axis (mask scores, paper §2.1)."""
    return jnp.sum(jnp.square(x.astype(jnp.float32)), axis=-1)

