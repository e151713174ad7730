"""Jitted public wrappers for the Pallas kernels.

Every shim has one path.  A shim backed by a kernel runs it natively on
the TPU and in interpret mode (the kernel body executed as traced JAX)
everywhere else — ``_interpret`` decides nothing but that, and a kernel
that fails to lower on the TPU raises.  The tests assert agreement with
the ``ref.py`` oracles.  Lane gathers (the compact pack/expand and the
compact wire encodes/decodes) and dequantization have no kernel: they
call the XLA op on every backend.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import ref as _ref
from .fused_prox_sgd import fused_prox_sgd as _fused
from .fused_prox_sgd import fused_prox_sgd_dyn as _fused_dyn
from .group_norms import group_norms_sq as _gnorms
from .wire import quantize_pack_q4 as _w_q4
from .wire import quantize_rows as _w_quant


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _rc(shape: tuple) -> tuple[int, int]:
    """(R, C) 2D view of any-rank operand: minor axis stays contiguous;
    0-D/1-D leaves (biases, scalars) pad to one row."""
    if len(shape) >= 2:
        return math.prod(shape[:-1]), shape[-1]
    return 1, max(math.prod(shape), 1)


@functools.partial(jax.jit, static_argnames=("eta", "rho", "momentum"))
def fused_prox_sgd(theta, g, z, u, mom, *, eta, rho, momentum=0.9):
    shape = theta.shape
    R, C = _rc(shape)
    flat = lambda x: x.reshape(R, C)
    t, m = _fused(flat(theta), flat(g), flat(z), flat(u), flat(mom),
                  eta=eta, rho=rho, momentum=momentum,
                  interpret=_interpret())
    return t.reshape(shape), m.reshape(shape)


def prox_sgd_update(theta, g, z, u, mom, rho, eta, *, momentum=0.9):
    """Dispatch shim for the Phase-1 update (paper Eq. 8).

    Computes, in one streaming pass when the fused kernel applies:

        g_tot = g + rho * (theta - z + u)     (analytic prox gradient)
        mom'  = momentum * mom + g_tot
        theta'= theta - eta * mom'

    ``rho`` is the bcast_rho-shaped layer-wise penalty (or None with z/u
    None in solo mode), ``eta`` a traced scalar.  Falls back to the jnp
    reference when an operand is missing (no momentum / no consensus) or
    when rho varies along the minor axis — the Pallas kernel streams rho
    as a per-row column.  Returns (theta', mom' or None).
    """
    e = jnp.asarray(eta).astype(theta.dtype)
    has_prox = z is not None
    rho_t = None
    if has_prox:
        rho_t = jnp.asarray(rho).astype(theta.dtype)
    # kernel streams rho as one value per (R, C)-view row: rho must be
    # constant along the minor axis (1-D leaves collapse to one row, so
    # they need a single rho value overall)
    minor_const = has_prox and theta.ndim >= 1 and (
        rho_t.ndim == 0 or rho_t.size == 1
        or (theta.ndim >= 2 and rho_t.shape[-1] == 1))
    if has_prox and mom is not None and minor_const and theta.size:
        shape = theta.shape
        R, C = _rc(shape)
        flat = lambda x: x.astype(theta.dtype).reshape(R, C)
        if theta.ndim >= 2:
            rho_col = jnp.broadcast_to(rho_t, shape[:-1] + (1,))
        else:  # 1-D leaf viewed as one row: rho is necessarily uniform
            rho_col = jnp.broadcast_to(rho_t.reshape(-1)[:1], (1, 1))
        t, m = _fused_dyn(flat(theta), flat(g), flat(z), flat(u), flat(mom),
                          rho_col.reshape(R, 1), e.reshape(1, 1),
                          momentum=momentum, interpret=_interpret())
        return t.reshape(shape), m.reshape(shape)
    gtot = g
    if has_prox:
        gtot = g + rho_t * (theta - z.astype(theta.dtype) + u)
    if mom is not None:
        m = momentum * mom + gtot
        return theta - e * m, m
    return theta - e * gtot, None


def _inverse_index(idx, full: int, fill: int):
    """(full,) int32: position of each channel in the kept list ``idx``,
    ``fill`` for dropped channels (zero-fill expansion as a gather into a
    zero-padded buffer, paper §4.4.3 — scatter hardware is never needed)."""
    return jnp.full((full,), fill, jnp.int32).at[idx].set(
        jnp.arange(idx.shape[0], dtype=jnp.int32))


@jax.jit
def compact_groups(x, idx):
    """Pack kept groups: x (..., C, K) gathered along axis -2 by idx (B,)."""
    return jnp.take(x, idx, axis=-2)


@functools.partial(jax.jit, static_argnames=("full",))
def expand_groups(c, idx, full: int):
    """Zero-fill recovery via inverse-permutation gather (paper §4.4.3)."""
    inv = _inverse_index(idx, full, idx.shape[0])
    pad = [(0, 0)] * c.ndim
    pad[-2] = (0, 1)
    return jnp.take(jnp.pad(c, pad), inv, axis=-2)


# ------------------------------------------------------------------ #
# wire path (kernels/wire.py): the repro.comm codecs' element formats.
# Scale granularity is one f32 per row of the (R, C) 2-D view — a
# function of the leaf SHAPE, never of the kernel block size, so
# wire_bytes stays analytic.
# ------------------------------------------------------------------ #


def _scale_shape(shape: tuple) -> tuple:
    """Broadcast shape of the per-row scales for an any-rank leaf."""
    return shape[:-1] + (1,) if len(shape) >= 2 else ((1,) if shape else ())


@functools.partial(jax.jit, static_argnames=("levels",))
def quantize_rows(x, levels=127):
    """Symmetric per-row quantize of any-rank ``x`` in one pass ->
    (q int8 like x, scale f32 broadcastable against x)."""
    shape = x.shape
    R, C = _rc(shape)
    q, s = _w_quant(x.reshape(R, C), levels=levels, interpret=_interpret())
    return q.reshape(shape), s.reshape(_scale_shape(shape))


@jax.jit
def dequantize_rows(q, scale):
    """Inverse of :func:`quantize_rows` (f32 out, caller casts)."""
    return q.astype(jnp.float32) * scale.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("levels",))
def gather_quantize(x, idx, levels=127):
    """x (R, C), idx (B,): kept-group gather + per-row quantize — the
    compact+q8 encode -> (q int8 (R, B), scale (R, 1))."""
    return _ref.gather_quantize_ref(x, idx.astype(jnp.int32), levels)


@functools.partial(jax.jit, static_argnames=("full",))
def scatter_dequantize(q, scale, idx, full: int):
    """Dequantize + zero-fill expansion: q (R, B) int8 of the kept
    channels ``idx`` -> f32 (R, full), zeros on the dropped channels."""
    inv = _inverse_index(idx, full, idx.shape[0])
    return _ref.gather_dequantize_ref(jnp.pad(q, ((0, 0), (0, 1))),
                                      scale.reshape(-1, 1), inv)


@jax.jit
def quantize_pack_q4(x):
    """q4 encode of any-rank ``x``: per-row quantize to [-7, 7] + pack
    two channels per byte -> (packed uint8 shape[:-1]+(ceil(C/2),),
    scale f32).  Odd minor dims carry one zero pad nibble."""
    shape = x.shape
    R, C = _rc(shape)
    p, s = _w_q4(x.reshape(R, C), interpret=_interpret())
    p_shape = (shape[:-1] if len(shape) >= 1 else ()) + ((C + 1) // 2,)
    return p.reshape(p_shape), s.reshape(_scale_shape(shape))


@functools.partial(jax.jit, static_argnames=("n",))
def unpack_dequantize_q4(p, scale, n: int):
    """Inverse of :func:`quantize_pack_q4`: packed (..., Cp) -> f32
    (..., n), trimming the pad nibble (``n`` = true minor dim)."""
    shape = p.shape
    Cp = shape[-1] if shape else 1
    R = max(math.prod(shape[:-1]), 1) if len(shape) >= 2 else 1
    q = _ref.unpack_q4_ref(p.reshape(R, Cp), n)
    out = q.astype(jnp.float32) * scale.reshape(R, 1)
    return out.reshape((shape[:-1] if len(shape) >= 2 else ()) + (n,))


@jax.jit
def gather_quantize_q4(x, idx):
    """x (R, C), idx (B,): gather + q4 quantize + nibble pack ->
    (packed uint8 (R, ceil(B/2)), scale (R, 1))."""
    return _ref.quantize_pack_q4_ref(jnp.take(x, idx.astype(jnp.int32),
                                              axis=1))


@functools.partial(jax.jit, static_argnames=("full",))
def scatter_dequantize_q4(p, scale, idx, full: int):
    """q4 unpack + dequantize + zero-fill expansion -> (R, full)."""
    R, _ = p.shape
    B = idx.shape[0]
    dec = _ref.unpack_q4_ref(p, B).astype(jnp.float32) * scale.reshape(R, 1)
    inv = _inverse_index(idx, full, B)
    return jnp.take(jnp.pad(dec, ((0, 0), (0, 1))), inv, axis=1)


@jax.jit
def gather_rows(x, idx):
    """Plain 2-D kept-gather: x (R, C), idx (B,) -> (R, B)."""
    return jnp.take(x, idx.astype(jnp.int32), axis=1)


@jax.jit
def group_norms_sq(x):
    """(G, C, K) -> (G, C) squared group norms."""
    return _gnorms(x, interpret=_interpret())
