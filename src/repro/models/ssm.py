"""Mamba2 (SSD — state-space duality) LM, arXiv:2405.21060.

Block: in-projections (z, x, B, C, dt) -> causal depthwise conv on (x,B,C)
-> chunked SSD scan -> gated RMSNorm -> out-projection.  The SSD scan is
the compute hot-spot; this module holds its pure-jnp implementation (no
Pallas kernel: Mosaic has no cumsum lowering).

Serving keeps O(1) per-token state: (B,H,hd,N) SSM state + (B,K-1,conv)
conv tail — this is why mamba2/jamba run the ``long_500k`` cell that pure
attention archs skip.

Sparsity target ``ssm_heads``: whole SSD heads (x/dt/A/D/conv/out-proj
slices) — the SSM analogue of conv-filter pruning (DESIGN.md §5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from ..core.sparsity import GroupRule, LeafAxis, SparsityPlan, keep_count
from .api import ModelBundle, pad_to
from . import layers as L

MODEL_AXIS_SIZE = 16


def _dt_(cfg):
    return jnp.dtype(cfg.param_dtype)


def dims(cfg: ArchConfig):
    d_in = cfg.ssm_expand * cfg.d_model
    H = d_in // cfg.ssm_head_dim
    return d_in, H, cfg.ssm_head_dim, cfg.ssm_state


def init_mixer(cfg: ArchConfig, key):
    d = cfg.d_model
    d_in, H, hd, N = dims(cfg)
    K = cfg.ssm_conv
    ks = jax.random.split(key, 9)
    dt = _dt_(cfg)
    return {
        "wz": L.dense_init(ks[0], (d, H, hd), d, dt),
        "wx": L.dense_init(ks[1], (d, H, hd), d, dt),
        "wB": L.dense_init(ks[2], (d, N), d, dt),
        "wC": L.dense_init(ks[3], (d, N), d, dt),
        "wdt": L.dense_init(ks[4], (d, H), d, dt),
        "bdt": jnp.full((H,), -3.0, dt),  # softplus(-3) ~ small init dt
        "A_log": jnp.zeros((H,), dt),     # A = -exp(A_log) = -1
        "D": jnp.ones((H,), dt),
        "conv_x": L.dense_init(ks[5], (K, H, hd), K, dt),
        "conv_B": L.dense_init(ks[6], (K, N), K, dt),
        "conv_C": L.dense_init(ks[7], (K, N), K, dt),
        "norm": jnp.ones((H, hd), dt),
        "wo": L.dense_init(ks[8], (H, hd, d), H * hd, dt),
    }


def _causal_conv(x, w, tail=None):
    """Depthwise causal conv over time.  x: (B,T,C...), w: (K,C...).
    ``tail``: (B,K-1,C...) previous timesteps for decode continuity.
    Returns (y, new_tail)."""
    K = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], K - 1) + x.shape[2:], x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(K))
    new_tail = xp[:, -(K - 1):] if K > 1 else tail
    return jax.nn.silu(y), new_tail


def ssd_scan(x, dtv, A, Bm, Cm, chunk, h0=None):
    """Chunked SSD (Mamba2 "state-space duality" alg).  x:(B,T,H,P)
    dtv:(B,T,H) A:(H,) Bm/Cm:(B,T,N).  Returns (y:(B,T,H,P), h:(B,H,N,P)).

    Recurrence: h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T ;  y_t = C_t h_t.
    One scan over chunks carries the SSM state; per chunk the intra-chunk
    part is a masked (Q,Q) attention-like product.
    """
    Bsz, T, H, Pd = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, T)
    while T % Q:
        Q -= 1
    nc = T // Q
    Af = A.astype(jnp.float32)
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    # xs stay in model dtype (bf16 on big archs): the scan's saved inputs
    # are O(T) tensors — f32 here doubles live HBM; f32 is used only inside
    # the (remat'd) body, whose per-chunk intermediates (the (Q,Q,H) decay
    # block) are recomputed on backward instead of stored.
    xc = jnp.moveaxis(x.reshape(Bsz, nc, Q, H, Pd), 1, 0)
    dtc = jnp.moveaxis(dtv.reshape(Bsz, nc, Q, H), 1, 0).astype(jnp.float32)
    Bc = jnp.moveaxis(Bm.reshape(Bsz, nc, Q, N), 1, 0)
    Cc = jnp.moveaxis(Cm.reshape(Bsz, nc, Q, N), 1, 0)

    def body(h, xs):
        xq, dtq, Bq, Cq = xs                    # (B,Q,H,P) (B,Q,H) (B,Q,N)
        cum = jnp.cumsum(dtq * Af, axis=1)      # (B,Q,H) f32, inclusive
        decay = jnp.exp(cum[:, :, None, :] - cum[:, None, :, :])
        decay = jnp.where(causal[None, :, :, None], decay, 0.0)
        cb = jnp.einsum("bqn,bsn->bqs", Cq, Bq,
                        preferred_element_type=jnp.float32)
        w = (cb[..., None] * decay * dtq[:, None]).astype(x.dtype)
        y1 = jnp.einsum("bqsh,bshp->bqhp", w, xq)  # keep model dtype:
        # f32 outputs force f32 cotangents on the O(T) scan xs (2x HBM)
        y2 = jnp.einsum("bqn,bqh,bhnp->bqhp", Cq.astype(jnp.float32),
                        jnp.exp(cum), h).astype(x.dtype)
        dec_end = jnp.exp(cum[:, -1:, :] - cum)           # (B,Q,H)
        sb = (Bq.astype(jnp.float32)[:, :, None, :]
              * (dec_end * dtq)[..., None]).astype(x.dtype)  # (B,Q,H,N)
        S = jnp.einsum("bshn,bshp->bhnp", sb, xq)
        h_new = h * jnp.exp(cum[:, -1])[..., None, None] + S.astype(jnp.float32)
        return h_new, (y1 + y2).astype(x.dtype)

    if h0 is None:
        h0 = jnp.zeros((Bsz, H, N, Pd), jnp.float32)
    h, yc = jax.lax.scan(jax.checkpoint(body), h0, (xc, dtc, Bc, Cc))
    y = jnp.moveaxis(yc, 0, 1).reshape(Bsz, T, H, Pd)
    return y, h


def mixer_apply(cfg: ArchConfig, p, h, state=None):
    """One Mamba2 mixer.  state: {"ssm": (B,H,N,P), "conv_*": tails} or None.
    Returns (out, new_state)."""
    B, T, d = h.shape
    z = jnp.einsum("btd,dhp->bthp", h, p["wz"])
    x = jnp.einsum("btd,dhp->bthp", h, p["wx"])
    Bm = jnp.einsum("btd,dn->btn", h, p["wB"])
    Cm = jnp.einsum("btd,dn->btn", h, p["wC"])
    dtv = jnp.einsum("btd,dh->bth", h, p["wdt"])

    st = state or {}
    x, tx = _causal_conv(x, p["conv_x"], st.get("conv_x"))
    Bm, tB = _causal_conv(Bm, p["conv_B"], st.get("conv_B"))
    Cm, tC = _causal_conv(Cm, p["conv_C"], st.get("conv_C"))
    dtv = jax.nn.softplus(dtv.astype(jnp.float32) + p["bdt"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    if state is None:
        y, _ = ssd_scan(x, dtv, A, Bm, Cm, cfg.ssm_chunk)
        new_state = None
    else:
        # O(1) recurrent decode (T small, usually 1): step the SSM directly
        def stepper(hs, xs):
            x_t, dt_t, B_t, C_t = xs                        # (B,H,P) (B,H) (B,N)
            decay = jnp.exp(dt_t * A)                       # (B,H)
            upd = dt_t[..., None, None] * B_t[:, None, :, None] \
                * x_t[:, :, None, :]                        # (B,H,N,P)
            hs = hs * decay[..., None, None] + upd
            y_t = jnp.einsum("bn,bhnp->bhp", C_t, hs)
            return hs, y_t

        hs = st.get("ssm")
        if hs is None:
            hs = jnp.zeros((B,) + (x.shape[2], Cm.shape[-1], x.shape[3]),
                           jnp.float32)
        hs, ys = jax.lax.scan(
            stepper, hs,
            (jnp.moveaxis(x.astype(jnp.float32), 1, 0),
             jnp.moveaxis(dtv, 1, 0),
             jnp.moveaxis(Bm.astype(jnp.float32), 1, 0),
             jnp.moveaxis(Cm.astype(jnp.float32), 1, 0)))
        y = jnp.moveaxis(ys, 0, 1).astype(h.dtype)
        new_state = {"ssm": hs, "conv_x": tx, "conv_B": tB, "conv_C": tC}

    y = y + x * p["D"].astype(x.dtype)[:, None]
    y = L.rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    return jnp.einsum("bthp,hpd->btd", y, p["wo"]), new_state


def init_block(cfg: ArchConfig, key):
    return {"ln": jnp.ones((cfg.d_model,), _dt_(cfg)),
            "mixer": init_mixer(cfg, key)}


def init(cfg: ArchConfig, key):
    ks = jax.random.split(key, 3)
    vp = pad_to(cfg.vocab, MODEL_AXIS_SIZE)
    blocks = jax.vmap(lambda k: init_block(cfg, k))(
        jax.random.split(ks[0], cfg.n_layers))
    return {
        "emb": L.dense_init(ks[1], (vp, cfg.d_model), cfg.d_model, _dt_(cfg)),
        "blocks": blocks,
        "ln_f": jnp.ones((cfg.d_model,), _dt_(cfg)),
        "head": L.dense_init(ks[2], (vp, cfg.d_model), cfg.d_model, _dt_(cfg)),
    }


def train_loss(cfg: ArchConfig, params, batch):
    tokens = batch["tokens"]
    h = L.embed_lookup(params["emb"], tokens)

    def body(h, bp):
        h = L.constrain_seq(h)
        out, _ = mixer_apply(cfg, bp["mixer"],
                             L.rms_norm(h, bp["ln"], cfg.norm_eps))
        return h + out, None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    h, _ = jax.lax.scan(body_fn, h, params["blocks"])
    h = L.rms_norm(h, params["ln_f"], cfg.norm_eps)
    tgt, valid = L.causal_targets(tokens)
    return L.chunked_xent(h, params["head"], tgt, valid)


# ---------------------------------------------------------------------------
# serving: recurrent state cache (O(1) in context length)
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, B: int, S: int):
    d_in, H, hd, N = dims(cfg)
    K = cfg.ssm_conv
    Lr = cfg.n_layers
    return {
        "ssm": jnp.zeros((Lr, B, H, N, hd), jnp.float32),
        "conv_x": jnp.zeros((Lr, B, K - 1, H, hd), _dt_(cfg)),
        "conv_B": jnp.zeros((Lr, B, K - 1, N), _dt_(cfg)),
        "conv_C": jnp.zeros((Lr, B, K - 1, N), _dt_(cfg)),
        "len": jnp.zeros((), jnp.int32),
    }


def step(cfg: ArchConfig, params, tokens, cache, **_):
    """Recurrent step for T tokens (prefill uses the same path: SSM state
    summarizes arbitrary context, so cache size is position-independent)."""
    B, T = tokens.shape
    h = L.embed_lookup(params["emb"], tokens)

    def body(h, xs):
        bp, ssm, cx, cB, cC = xs
        st = {"ssm": ssm, "conv_x": cx, "conv_B": cB, "conv_C": cC}
        out, ns = mixer_apply(cfg, bp["mixer"],
                              L.rms_norm(h, bp["ln"], cfg.norm_eps),
                              state=st)
        return h + out, (ns["ssm"], ns["conv_x"], ns["conv_B"], ns["conv_C"])

    h, (ssm, cx, cB, cC) = jax.lax.scan(
        body, h, (params["blocks"], cache["ssm"], cache["conv_x"],
                  cache["conv_B"], cache["conv_C"]))
    h = L.rms_norm(h, params["ln_f"], cfg.norm_eps)
    logits = jnp.einsum("bd,vd->bv", h[:, -1], params["head"],
                        preferred_element_type=jnp.float32)
    return logits, {"ssm": ssm, "conv_x": cx, "conv_B": cB, "conv_C": cC,
                    "len": cache["len"] + T}


def param_specs(cfg: ArchConfig):
    return {
        "emb": P("model", None),
        "ln_f": P(None),
        "head": P("model", None),
        "blocks": {
            "ln": P(None, None),
            "mixer": {
                "wz": P(None, None, None, "model"),
                "wx": P(None, None, None, "model"),
                "wB": P(None, None, None),
                "wC": P(None, None, None),
                "wdt": P(None, None, None),
                "bdt": P(None, None),
                "A_log": P(None, None),
                "D": P(None, None),
                "conv_x": P(None, None, None, "model"),
                "conv_B": P(None, None, None),
                "conv_C": P(None, None, None),
                "norm": P(None, None, "model"),
                "wo": P(None, None, "model", None),
            },
        },
    }


def sparsity_plan(cfg: ArchConfig) -> SparsityPlan:
    d_in, H, hd, N = dims(cfg)
    hp = cfg.hsadmm
    rules = []
    if "ssm_heads" in cfg.prune_targets:
        keep = keep_count(H, hp.keep_rate, 4)
        rules.append(GroupRule(
            "ssm_heads",
            (LeafAxis("blocks/mixer/wz", 2), LeafAxis("blocks/mixer/wx", 2),
             LeafAxis("blocks/mixer/wdt", 2), LeafAxis("blocks/mixer/bdt", 1),
             LeafAxis("blocks/mixer/A_log", 1), LeafAxis("blocks/mixer/D", 1),
             LeafAxis("blocks/mixer/conv_x", 2),
             LeafAxis("blocks/mixer/norm", 1),
             LeafAxis("blocks/mixer/wo", 1)),
            groups=H, keep=keep, stack_ndims=1))
    return SparsityPlan(tuple(rules))


def cache_specs(cfg: ArchConfig, B: int, S: int, data_axes) -> dict:
    import math
    dsz = math.prod(s for _, s in data_axes)
    names = tuple(n for n, _ in data_axes)
    bn = names if (B % dsz == 0 and B >= dsz) else None
    return {
        "ssm": P(None, bn, None, None, "model"),
        "conv_x": P(None, bn, None, None, "model"),
        "conv_B": P(None, bn, None, None),
        "conv_C": P(None, bn, None, None),
        "len": P(),
    }


def build(cfg: ArchConfig) -> ModelBundle:
    return ModelBundle(
        cfg=cfg,
        init=functools.partial(init, cfg),
        train_loss=functools.partial(train_loss, cfg),
        param_specs=param_specs(cfg),
        plan=sparsity_plan(cfg),
        stack_map=(("blocks", 1),),
        prefill=functools.partial(step, cfg),
        decode=functools.partial(step, cfg),
        init_cache=functools.partial(init_cache, cfg),
        cache_specs=functools.partial(cache_specs, cfg),
    )
