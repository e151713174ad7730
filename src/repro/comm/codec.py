"""Pluggable wire codecs — ONE interface for every synchronization path.

PruneX's core claim is that the *wire format* of synchronization decides
scaling; CGX/PacTrain (PAPERS.md) show that making the compression layer
a first-class, swappable system interface is what unlocks adaptive
comm-efficiency.  This module is that seam.  A :class:`WireCodec` owns
three things for one fabric boundary:

  * ``encode``/``decode``  — the wire representation of one payload leaf
    (what actually crosses the fabric; used by tests/analysis and by the
    traced exchange),
  * ``group_reduce``       — the traced weighted group-sum over the
    leading consensus dim, exchanging leaves *in the codec's wire
    format* (this is what runs inside the fused round executable),
  * ``wire_bytes``         — the single source of truth for analytic
    byte accounting (``plan_bytes``, ``round_comm_bytes``, and the
    dryrun/hlo reports all derive from it).

Registered codecs (``get_codec`` specs):

  ``dense``        param-dtype payloads, plain weighted group-sum (paper)
  ``q8``           symmetric int8 quantization with one f32 scale per
                   row of the (R, C) 2-D leaf view, exchanged via a ring
                   of shifts, dequant-accumulated in f32 (beyond-paper
                   §Perf; was ``comm_quant="int8"``)
  ``q4``           packed 4-bit symmetric quantization: two channels per
                   byte + per-row f32 scales, quantized and packed
                   in-kernel (kernels/wire.py)
  ``topk:<rate>``  per-member magnitude top-``rate`` sparsification with
                   error feedback; values+int32-index payloads with
                   AllGather semantics (the DGC baseline, paper §5.1.4)
  ``compact``      structural-compaction *marker*: composes with an
                   element codec (``compact+q8``) to request the
                   H-SADMM physically-shrunk buffer at that boundary

Quantizing codecs encode through the fused Pallas wire kernels
(``kernels.ops`` shims over ``kernels/wire.py``): one streaming pass
computes the per-row abs-max in VMEM and quantizes (q4: and packs) on
the way out.  Decode, and the kept-group gather/zero-fill of the
compact encode/decode, are XLA ops that fuse into their consumers
(Mosaic has no lane gather).  Scale granularity is
per ROW of the (R, C) view — a function of the leaf shape only, so
``wire_bytes`` stays analytic (DESIGN.md "Per-row wire scales").

``compose`` stacks a marker with exactly one element codec, so the
paper's structural shrinkage and a quantized wire format select together
(``compact+q8``): compaction decides the payload *shape*, the element
codec decides the payload *bytes per element*.

Stateful codecs (top-k error feedback) thread their state through the
scanned round: ``group_reduce`` takes and returns a state pytree shaped
like the boundary payload; ``init_state`` builds the zero state.
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax
import jax.numpy as jnp

INDEX_BYTES = 4   # int32 index metadata per top-k entry (paper Table 1)


def _dtype_size(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def _leaf_elems(shape) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


def _leaf_rows(shape) -> int:
    """Rows of the (R, C) 2-D wire view of one leaf — the number of
    quantization scales it ships (0-D/1-D leaves are one row)."""
    return _leaf_elems(shape[:-1]) if len(shape) >= 2 else 1


def leaf_bytes(shape, dtype) -> int:
    """Dense bytes of one ``shape`` leaf at ``dtype`` (shared helper)."""
    return _leaf_elems(shape) * _dtype_size(dtype)


def collective_wire_bytes(kind: str, g: int, operand_b: int) -> float:
    """Per-device fabric traffic of one collective under the standard
    ring model — the shared byte model ``dist.hlo`` applies to measured
    collectives and the analytic accounting applies to planned ones."""
    if g <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2.0 * (g - 1) / g * operand_b
    if kind == "all-gather":
        return float((g - 1) * operand_b)
    if kind in ("reduce-scatter", "all-to-all", "ragged-all-to-all"):
        return (g - 1) / g * operand_b
    return float(operand_b)   # permute / broadcast: one shard on the wire


def _wbcast(w, x):
    return w.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)


def group_sum(x: jnp.ndarray, g: int,
              w: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(G*g, *p) -> (G, *p) sum over contiguous groups of g (optionally
    weighted by w: (G*g,) broadcast over param dims).  THE reference
    reduction every codec's group exchange must agree with; re-exported
    by ``core.hsadmm``."""
    if w is not None:
        x = x * _wbcast(w, x)
    return x.reshape((-1, g) + x.shape[1:]).sum(axis=1)


# ---------------------------------------------------------------------------
# the protocol
# ---------------------------------------------------------------------------


class WireCodec:
    """Base class/protocol of one wire format.  Subclasses override the
    encode/decode pair, ``group_reduce``, and ``wire_bytes``; the base
    implementations are the identity/dense behaviour."""

    name = "dense"
    #: True when ``group_reduce`` threads an error-feedback state pytree
    stateful = False
    #: True when the codec spec requests structural compaction at this
    #: boundary (set by the ``compact`` marker via ``compose``)
    compact = False
    #: True when per-member supports differ so the exchange is AllGather
    #: (every member's payload crosses the fabric) instead of a reduce
    gather = False

    # ---- wire representation ------------------------------------------- #
    def encode(self, leaf: jnp.ndarray):
        """Leaf -> wire payload (anything ``decode`` can invert)."""
        return leaf

    def decode(self, payload, like: Optional[jnp.ndarray] = None):
        return payload

    # ---- fused compact wire path (canonical (R, C) 2-D view) ------------ #
    def encode_compact(self, leaf2d: jnp.ndarray, idx: jnp.ndarray):
        """Kept-group gather along the minor axis + encode of a (R, C)
        leaf — the §4.4 packing composed with this codec's element
        format: gather the kept channels, then encode."""
        from ..kernels import ops
        return self.encode(ops.gather_rows(leaf2d, idx))

    def decode_expand(self, payload, idx: jnp.ndarray, full: int,
                      like: Optional[jnp.ndarray] = None):
        """Inverse of :meth:`encode_compact`: decode + zero-fill the
        dropped channels -> (R, full) (inverse-permutation gather into a
        zero-padded buffer; scatter hardware is never needed)."""
        from ..kernels import ops
        dec = self.decode(payload, like=like)
        B = idx.shape[0]
        inv = jnp.full((full,), B, jnp.int32).at[idx].set(
            jnp.arange(B, dtype=jnp.int32))
        out = ops.gather_rows(jnp.pad(dec, ((0, 0), (0, 1))), inv)
        return out.astype(like.dtype) if like is not None else out

    # ---- traced exchange ------------------------------------------------ #
    def init_state(self, tree):
        """Zero error-feedback state for one boundary payload tree
        (None for stateless codecs)."""
        return None

    def group_reduce(self, tree, g: int, w: Optional[jnp.ndarray] = None,
                     state=None):
        """Weighted group-sum of every leaf over contiguous groups of
        ``g`` along the leading consensus dim, exchanging in this wire
        format.  ``w`` is the (lead,) contribution-weight vector (None =
        unweighted).  Returns ``(reduced_tree, new_state)``."""
        return jax.tree.map(lambda x: group_sum(x, g, w), tree), state

    # ---- analytic accounting -------------------------------------------- #
    def wire_bytes(self, leaf_shape, dtype) -> int:
        """Bytes ONE group member puts on the wire for one payload leaf
        of ``leaf_shape`` whose accumulation dtype is ``dtype`` — the
        single source of truth for plan_bytes / round_comm_bytes /
        dryrun reports."""
        return leaf_bytes(leaf_shape, dtype)


class DenseCodec(WireCodec):
    """Param-dtype payloads, plain weighted group-sum (the paper)."""


def _member_rows(x: jnp.ndarray) -> jnp.ndarray:
    """(lead, *p) -> (lead, rows_p, C) view: the finest 2-D row view of
    each member's payload, members never sharing a row (so per-row wire
    scales never mix group members)."""
    if x.ndim >= 2:
        return x.reshape((x.shape[0], -1, x.shape[-1]))
    return x.reshape((x.shape[0], 1, 1))


class Q8Codec(WireCodec):
    """Per-row symmetric int8 quantization (beyond-paper §Perf).

    Each leaf is scaled per row of its (R, C) 2-D view to int8 (+ one
    f32 scale per row), exchanged across the group via a ring of shifts
    over the leading dim, and dequant-accumulated in f32 locally.
    Encode runs through the fused Pallas wire kernel (abs-max in VMEM +
    quantize in one pass; ``kernels.ops`` shim).  Slow-fabric
    bytes drop 2x vs bf16 / 4x vs f32 payloads; quantization error is
    bounded by max|row|/127 per row — at most the old per-leaf
    max|x|/127 bound — and is absorbed by the ADMM duals
    (tests/test_perf_levers.py)."""

    name = "q8"
    levels = 127

    def encode(self, leaf):
        from ..kernels import ops
        return ops.quantize_rows(leaf, levels=self.levels)

    def decode(self, payload, like=None):
        from ..kernels import ops
        q, scale = payload
        out = ops.dequantize_rows(q, scale)
        return out.astype(like.dtype) if like is not None else out

    def encode_compact(self, leaf2d, idx):
        from ..kernels import ops
        return ops.gather_quantize(leaf2d, idx, levels=self.levels)

    def decode_expand(self, payload, idx, full, like=None):
        from ..kernels import ops
        q, scale = payload
        out = ops.scatter_dequantize(q, scale, idx, full)
        return out.astype(like.dtype) if like is not None else out

    def group_reduce(self, tree, g, w=None, state=None):
        from ..kernels import ops

        def one(x):
            xw = x * _wbcast(w, x) if w is not None else x
            v = _member_rows(xw)
            q, scale = ops.quantize_rows(v, levels=self.levels)
            G = x.shape[0] // g
            acc = (q.astype(jnp.float32) * scale)
            qr, sr = q, scale
            for _ in range(g - 1):
                # ring shift WITHIN each contiguous group of g
                qr = qr.reshape((G, g) + q.shape[1:])
                sr = sr.reshape((G, g) + scale.shape[1:])
                qr = jnp.roll(qr, 1, axis=1).reshape(q.shape)
                sr = jnp.roll(sr, 1, axis=1).reshape(scale.shape)
                acc = acc + qr.astype(jnp.float32) * sr
            # every member of a group now holds the group sum
            out = acc.reshape((G, g) + acc.shape[1:])[:, 0]
            return out.reshape((G,) + x.shape[1:]).astype(x.dtype)
        return jax.tree.map(one, tree), state

    def wire_bytes(self, leaf_shape, dtype) -> int:
        # s8 payload + one f32 scale per (R, C)-view row
        return _leaf_elems(leaf_shape) * 1 + 4 * _leaf_rows(leaf_shape)


class Q4Codec(WireCodec):
    """Packed 4-bit symmetric quantization: two channels per byte.

    Rows of the (R, C) leaf view quantize to [-7, 7] (two's-complement
    nibbles, one f32 scale per row) and pack pairwise into uint8 —
    quantize + pack fused in one Pallas pass; decode unpacks and
    dequantizes in XLA.  The ring
    exchange rolls the PACKED buffer, so the bytes that cross the fabric
    are exactly ``wire_bytes`` = rows * (ceil(C/2) + 4).  Odd minor dims
    carry one zero pad nibble (trimmed on decode via the dense
    template)."""

    name = "q4"
    levels = 7

    def encode(self, leaf):
        from ..kernels import ops
        return ops.quantize_pack_q4(leaf)

    def decode(self, payload, like=None):
        from ..kernels import ops
        assert like is not None, \
            "q4 decode needs the dense template (the packed minor dim " \
            "is ambiguous by one pad nibble)"
        p, scale = payload
        n = like.shape[-1] if like.ndim else 1
        out = ops.unpack_dequantize_q4(p, scale, n)
        return out.reshape(like.shape).astype(like.dtype)

    def encode_compact(self, leaf2d, idx):
        from ..kernels import ops
        return ops.gather_quantize_q4(leaf2d, idx)

    def decode_expand(self, payload, idx, full, like=None):
        from ..kernels import ops
        p, scale = payload
        out = ops.scatter_dequantize_q4(p, scale, idx, full)
        return out.astype(like.dtype) if like is not None else out

    def group_reduce(self, tree, g, w=None, state=None):
        from ..kernels import ops

        def one(x):
            xw = x * _wbcast(w, x) if w is not None else x
            v = _member_rows(xw)
            C = v.shape[-1]
            p, scale = ops.quantize_pack_q4(v)
            G = x.shape[0] // g

            # Accumulate in nibble PLANES: low/high nibbles sign-extend
            # with two int8 arithmetic shifts (pure elementwise — fuses
            # into the hop loop), and the even/odd column interleave (a
            # materialized shuffle) runs ONCE on the accumulated planes
            # instead of once per received buffer.  Element values and
            # float accumulation order are identical to unpacking every
            # hop, so the group sum is bit-exact either way.
            def planes(pp, ss):
                s8 = pp.astype(jnp.int8)
                lo = ((s8 << 4) >> 4).astype(jnp.float32) * ss
                hi = (s8 >> 4).astype(jnp.float32) * ss
                return lo, hi

            acc_lo, acc_hi = planes(p, scale)
            pr, sr = p, scale
            for _ in range(g - 1):
                # the ring rolls the PACKED uint8 buffer + its scales
                pr = pr.reshape((G, g) + p.shape[1:])
                sr = sr.reshape((G, g) + scale.shape[1:])
                pr = jnp.roll(pr, 1, axis=1).reshape(p.shape)
                sr = jnp.roll(sr, 1, axis=1).reshape(scale.shape)
                lo, hi = planes(pr, sr)
                acc_lo = acc_lo + lo
                acc_hi = acc_hi + hi
            acc = jnp.stack([acc_lo, acc_hi], axis=-1)
            acc = acc.reshape(acc_lo.shape[:-1] + (-1,))[..., :C]
            out = acc.reshape((G, g) + acc.shape[1:])[:, 0]
            return out.reshape((G,) + x.shape[1:]).astype(x.dtype)
        return jax.tree.map(one, tree), state

    def wire_bytes(self, leaf_shape, dtype) -> int:
        C = leaf_shape[-1] if len(leaf_shape) else 1
        rows = _leaf_rows(leaf_shape)
        return rows * ((C + 1) // 2) + 4 * rows   # packed u8 + f32 scales


class TopKCodec(WireCodec):
    """Magnitude top-``rate`` sparsification with error feedback (DGC,
    paper §5.1.4 baseline).  Per-member supports differ, so the exchange
    is values + int32 indices with AllGather semantics — the metadata
    overhead the paper criticizes (Table 1).  The value width on the
    wire is the payload dtype's (bf16 values count 2 bytes, not 4)."""

    name = "topk"
    stateful = True
    gather = True

    def __init__(self, rate: float = 0.01):
        assert 0.0 < rate <= 1.0, rate
        self.rate = rate
        self.name = f"topk:{rate:g}"

    def k_of(self, n: int) -> int:
        return max(1, int(n * self.rate))

    def encode(self, leaf):
        flat = leaf.reshape(-1)
        _, idx = jax.lax.top_k(jnp.abs(flat), self.k_of(flat.size))
        return flat[idx], idx.astype(jnp.int32)

    def decode(self, payload, like=None):
        vals, idx = payload
        assert like is not None, "topk decode needs the dense template"
        return jnp.zeros(like.size, like.dtype).at[idx].set(vals) \
                  .reshape(like.shape)

    def init_state(self, tree):
        return jax.tree.map(jnp.zeros_like, tree)

    def _sparsify(self, x, e):
        """Per-member top-k + error feedback on one (lead, *p) leaf."""
        lead = x.shape[0]
        flat = (x + e).reshape(lead, -1)
        k = self.k_of(flat.shape[-1])
        _, idx = jax.lax.top_k(jnp.abs(flat), k)
        vals = jnp.take_along_axis(flat, idx, axis=-1)
        sparse = jnp.zeros_like(flat).at[
            jnp.arange(lead)[:, None], idx].set(vals)
        return sparse.reshape(x.shape), (flat - sparse).reshape(x.shape)

    def group_reduce(self, tree, g, w=None, state=None):
        if state is None:
            state = self.init_state(tree)

        def one(x, e):
            xw = x * _wbcast(w, x) if w is not None else x
            sparse, new_e = self._sparsify(xw, e)
            return group_sum(sparse, g), new_e
        flat_x, treedef = jax.tree.flatten(tree)
        flat_e = jax.tree.leaves(state)
        outs = [one(x, e) for x, e in zip(flat_x, flat_e)]
        red = jax.tree.unflatten(treedef, [o[0] for o in outs])
        new_state = jax.tree.unflatten(treedef, [o[1] for o in outs])
        return red, new_state

    def wire_bytes(self, leaf_shape, dtype) -> int:
        # value at the wire dtype's width + int32 index per kept entry
        return self.k_of(_leaf_elems(leaf_shape)) \
            * (INDEX_BYTES + _dtype_size(dtype))


class CompactMarker(WireCodec):
    """Structural-compaction marker.  Carries no element format of its
    own — ``compose`` attaches it to an element codec; standalone it is
    ``compact+dense``."""

    name = "compact"
    compact = True


class CompositeCodec(WireCodec):
    """``compose(compact, q8)``: markers set the ``compact`` flag, the
    single element codec provides encode/reduce/bytes."""

    def __init__(self, *parts: WireCodec):
        elems = [p for p in parts if not isinstance(p, CompactMarker)]
        if len(elems) > 1:
            raise ValueError(
                "compose() takes at most one element codec (got "
                f"{[p.name for p in elems]}); only the 'compact' marker "
                "stacks — two wire formats cannot both perform the "
                "group exchange")
        self._elem = elems[0] if elems else DenseCodec()
        self.compact = any(p.compact for p in parts)
        self.stateful = self._elem.stateful
        self.gather = self._elem.gather
        self.name = "+".join(
            (["compact"] if self.compact else []) + [self._elem.name])

    @property
    def element(self) -> WireCodec:
        return self._elem

    def encode(self, leaf):
        return self._elem.encode(leaf)

    def decode(self, payload, like=None):
        return self._elem.decode(payload, like)

    def encode_compact(self, leaf2d, idx):
        return self._elem.encode_compact(leaf2d, idx)

    def decode_expand(self, payload, idx, full, like=None):
        return self._elem.decode_expand(payload, idx, full, like)

    def init_state(self, tree):
        return self._elem.init_state(tree)

    def group_reduce(self, tree, g, w=None, state=None):
        return self._elem.group_reduce(tree, g, w, state)

    def wire_bytes(self, leaf_shape, dtype) -> int:
        return self._elem.wire_bytes(leaf_shape, dtype)


def compose(*codecs: "WireCodec | str") -> CompositeCodec:
    """Stack wire-format stages: structural ``compact`` + one element
    codec, so H-SADMM shrinkage selects together with quantization."""
    return CompositeCodec(*[get_codec(c) if isinstance(c, str) else c
                            for c in codecs])


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict = {}


def register_codec(name: str, factory) -> None:
    """``factory(arg: str | None) -> WireCodec``; ``name:arg`` specs pass
    the text after the colon."""
    _REGISTRY[name] = factory


register_codec("dense", lambda arg=None: DenseCodec())
register_codec("q8", lambda arg=None: Q8Codec())
register_codec("q4", lambda arg=None: Q4Codec())
register_codec("topk", lambda arg=None: TopKCodec(float(arg or 0.01)))
register_codec("compact", lambda arg=None: CompactMarker())


def list_codecs() -> list[str]:
    return sorted(_REGISTRY)


def get_codec(spec: "str | WireCodec") -> WireCodec:
    """Resolve a codec spec string: ``dense`` | ``q8`` | ``topk:0.01`` |
    ``compact+q8`` (markers and one element codec joined by ``+``)."""
    if isinstance(spec, WireCodec):
        return spec
    parts = [p.strip() for p in spec.split("+") if p.strip()]
    if not parts:
        raise ValueError(f"empty codec spec {spec!r}")
    built = []
    for part in parts:
        name, _, arg = part.partition(":")
        if name not in _REGISTRY:
            raise KeyError(
                f"unknown wire codec {name!r}; known: {list_codecs()}")
        built.append(_REGISTRY[name](arg or None))
    return built[0] if len(built) == 1 else CompositeCodec(*built)


# ---------------------------------------------------------------------------
# per-fabric-level selection (the paper's leader-follower split)
# ---------------------------------------------------------------------------

_LEGACY_QUANT = {"int8": "q8", "q8": "q8"}


def resolve_specs(hp) -> tuple[str, str]:
    """(intra, inter) codec spec strings from an ``HsadmmConfig``,
    honoring the deprecated ``comm_quant`` field (one-release shim)."""
    intra = getattr(hp, "wire_intra", None)
    inter = getattr(hp, "wire_inter", None)
    quant = getattr(hp, "comm_quant", None)
    if quant is not None:
        if quant not in _LEGACY_QUANT:
            raise ValueError(f"unknown comm_quant {quant!r}")
        warnings.warn(
            "HsadmmConfig.comm_quant is deprecated; use "
            f"wire_inter={_LEGACY_QUANT[quant]!r} (repro.comm codec "
            "specs) — comm_quant will be removed next release",
            DeprecationWarning, stacklevel=2)
        if inter is None:
            inter = _LEGACY_QUANT[quant]
    return intra or "dense", inter or "dense"


def level_codecs(hp, levels: tuple, compact_from_level: int
                 ) -> list[WireCodec]:
    """One codec per level boundary k=1..K.

    ``hp.wire_map`` (one spec string per boundary, e.g. from
    :class:`repro.comm.select.AdaptiveWireSelector`) overrides
    everything verbatim — including the flat-ablation exception below;
    an explicit per-boundary map is an explicit choice.

    Otherwise the top boundary (slow fabric) takes the *inter* codec;
    lower boundaries take the *intra* codec.  Exception
    (legacy-faithful): the flat K==1 ablation with
    ``compact_from_level >= 1`` is an honest dense AllReduce — its
    single boundary is the intra one, so ``comm_quant``/``wire_inter``
    never quantize it."""
    K = len(levels)
    wm = getattr(hp, "wire_map", None)
    if wm:
        if len(wm) != K:
            raise ValueError(
                f"wire_map has {len(wm)} entries but the hierarchy has "
                f"{K} level boundaries: {wm!r} vs levels={levels!r}")
        return [get_codec(s) for s in wm]
    intra_s, inter_s = resolve_specs(hp)
    kc = compact_from_level
    return [get_codec(inter_s) if (k == K and (K > 1 or kc == 0))
            else get_codec(intra_s) for k in range(1, K + 1)]
