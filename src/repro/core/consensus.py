"""Hierarchical consensus step — Phases 2-5 of Algorithm 1, K-level general.

One call implements, for every parameter leaf:

  Phase 2  intra-node AllReduce of (theta + u)           [dense, fast fabric]
  Phase 3  node-level candidate z~_1 (Eq. 9), projection (Eq. 10),
           mask generation + global mask sync (Eq. 14 / score-consensus)
  Phase 4  per-level consensus reductions; boundaries at/above
           ``compact_from_level`` move *physically shrunk* payloads
           (paper §4.4) — the slow-fabric collective operand is the static-B
           compact buffer; zero-fill recovery afterwards
  Phase 5  dual updates (Eq. 12-13), residuals, layer-wise adaptive penalties
           (with scaled-dual rescaling), mask drift

The paper's two-level (node, global) hierarchy is levels=(P, M); the §4.1.5
extension to deeper hierarchies is levels=(P, M, pods) on the multi-pod mesh.
The flat ablation "PruneX (AR)" (paper §5.1.4) is levels=(W,) with
compact_from_level=1: one dense global AllReduce, sparsity enforced after
synchronization — exactly the standard distributed-ADMM failure mode the
paper argues against.  compact_from_level=0 compacts even the first
reduction (used when workers == pods, DESIGN.md §3.2 pod granularity).

Straggler mitigation / worker failure: ``state["weights"]`` scales each
worker's contribution (0 = dropped worker); all means are weight-normalized
so a dead worker never stalls or skews consensus (DESIGN.md §6).

Every group exchange routes through the per-boundary wire codec
(``repro.comm``, resolved by ``spec.codecs``): the paper's dense
param-dtype reduce, the beyond-paper int8 ring (``q8``), top-k with
error feedback (``topk:<rate>``, state threaded through ``state["wire"]``
across rounds), or structural compaction stacked with any of them
(``compact+q8``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .hsadmm import (EngineSpec, bcast_rho, group_sum, leaf_keys,
                     unflatten, ungroup)
from .masks import sync_masks, mask_drift
from .shrinkage import compact_params, expand_params
from .sparsity import apply_mask_rule, get_leaf, group_scores


def _norm_sq_per_stack(x: jnp.ndarray, stack_ndims: int,
                       offset: int) -> jnp.ndarray:
    """Sum of squares over all axes except the stack axes -> (stack,)."""
    axes = tuple(i for i in range(x.ndim)
                 if not (offset <= i < offset + stack_ndims))
    return jnp.sum(jnp.square(x.astype(jnp.float32)), axis=axes)


@jax.named_scope("consensus/masks")
def _make_masks(state, spec, mask_src, frozen):
    """Phase-3 mask generation + global synchronization."""
    new_masks, idxs, info = {}, {}, {}
    for rule in spec.plan.rules:
        if frozen:
            mstate = state["masks"][rule.name]
            new_masks[rule.name] = dict(mstate,
                                        drift=jnp.zeros((), jnp.float32))
        else:
            scores = group_scores(mask_src, rule, offset=1)  # (Msrc,*stack,C)
            idx, valid, mask = sync_masks(scores, rule, spec.sync_cfg)
            drift = mask_drift(state["masks"][rule.name]["mask"], mask)
            new_masks[rule.name] = {"idx": idx, "valid": valid, "mask": mask,
                                    "drift": drift}
            info[f"drift/{rule.name}"] = drift
        idxs[rule.name] = new_masks[rule.name]["idx"]
    return new_masks, idxs, info


def _solo_prune_step(state: dict, spec: EngineSpec, frozen: bool
                     ) -> tuple[dict, dict]:
    """Single-worker degenerate case: project theta directly (the paper's
    technique has no consensus to run on one worker; see DESIGN.md §5)."""
    theta = state["theta"]
    new_masks, idxs, info = _make_masks(state, spec, theta, frozen)
    for rule in spec.plan.rules:
        theta = apply_mask_rule(theta, rule,
                                new_masks[rule.name]["mask"][None], offset=1)
    new_state = dict(state)
    new_state.update(theta=theta, masks=new_masks, k=state["k"] + 1)
    info["r_primal"] = jnp.zeros((), jnp.float32)
    info["s_dual"] = jnp.zeros((), jnp.float32)
    return new_state, info


def consensus_step(state: dict, spec: EngineSpec, frozen: bool = False,
                   detail: bool = True) -> tuple[dict, dict]:
    """Run Phases 2-5.  ``frozen`` selects the cached-mask fast path
    (paper §4.5: projection degenerates to an elementwise multiply and
    compact buffer shapes are invariant — one-shot buffers).

    ``detail=False`` drops the per-leaf ``r_intra``/``r_inter*`` residual
    maps from the info dict — the fused round executable returns info as
    device outputs, and the per-leaf maps would be dead weight on every
    round (only the scalar residuals feed the stopping rule)."""
    if spec.solo:
        return _solo_prune_step(state, spec, frozen)
    levels = spec.consensus.levels
    K = len(levels)
    hp = spec.hp
    plan = spec.plan
    fulls = {r.name: r.groups for r in plan.rules}

    # per-boundary wire codecs (repro.comm) + their error-feedback state
    codecs = spec.codecs
    need_wire = any(c.stateful for c in codecs)
    wire_old = state.get("wire") if need_wire else None
    wire_new = list(wire_old) if wire_old is not None \
        else [{} for _ in codecs]

    theta, u = state["theta"], state["u"]
    w = state["weights"]
    rho = state["rho"]
    zs_old = state["z"]
    vs_old = state["v"]

    def wk_chain(wvec: jnp.ndarray) -> list:
        """Cumulative weights per level: chain[k] has shape (M_k,)."""
        out = [wvec]
        for g in levels:
            out.append(group_sum(out[-1], g))
        return out

    # cumulative weights per level: wk[k] has shape (M_k,)
    wk = wk_chain(w)
    M1 = spec.consensus.num_workers // levels[0]

    # per-coupling-class straggler weights (spec.class_weights): every
    # leaf's exchange is led by ONE class — the first plan rule touching
    # it (leaves coupled to several classes ride their lead class);
    # unruled leaves keep the global weights.  Each class multiplies its
    # (W,) weight vector into the global one, so all-ones class weights
    # are bit-identical to the unscoped path.
    cw = state.get("class_weights") if spec.class_weights else None
    key_class: dict = {}
    wk_by_class: dict = {}
    if cw is not None:
        for rule in plan.rules:
            for la in rule.all_leaves:
                key_class.setdefault(la.key, rule.name)
        wk_by_class = {name: wk_chain(w * cwv) for name, cwv in cw.items()}

    def wk_for(key: str) -> list:
        return wk_by_class.get(key_class.get(key), wk) if cw is not None \
            else wk

    def wire_reduce(tree: dict, k: int, g: int, lvl: int) -> dict:
        """Boundary-k weighted group exchange in that codec's format,
        weighted by the level-``lvl`` cumulative weights."""
        codec = codecs[k - 1]
        cst = wire_old[k - 1] if codec.stateful and wire_old is not None \
            else None
        if cw is None:
            red, cst = codec.group_reduce(tree, g, wk[lvl], cst)
            if codec.stateful:
                wire_new[k - 1] = cst
            return red
        # Partition the payload by lead coupling class: each class's
        # group_reduce is a SEPARATE collective carrying that class's
        # own weights, so XLA can ship early classes while later ones
        # still compute, and a straggler policy scoping a worker to one
        # class discounts only that class's payload.  The codec EF state
        # is partitioned by the same keys and merged back, so top-k
        # error feedback threads per leaf exactly as in the joint call.
        flat = {key: get_leaf(tree, key) for key in leaf_keys(tree)}
        cst_flat = {key: get_leaf(cst, key) for key in leaf_keys(cst)} \
            if cst is not None else None
        parts: dict = {}
        for key in flat:
            parts.setdefault(key_class.get(key), []).append(key)
        out_flat, new_cst_flat = {}, {}
        for cls in sorted(parts, key=lambda c: (c is None, c or "")):
            keys = parts[cls]
            sub = unflatten({kk: flat[kk] for kk in keys})
            sub_cst = unflatten({kk: cst_flat[kk] for kk in keys}) \
                if cst_flat is not None else None
            red, sc = codec.group_reduce(
                sub, g, wk_by_class.get(cls, wk)[lvl], sub_cst)
            for kk in keys:
                out_flat[kk] = get_leaf(red, kk)
                if codec.stateful:
                    new_cst_flat[kk] = get_leaf(sc, kk)
        if codec.stateful:
            wire_new[k - 1] = unflatten(new_cst_flat)
        return unflatten(out_flat)

    def cand1(buf_tree, z2v_tree):
        """z~_1 = (rho1*sum_j w_j(theta+u) + rho2*(z2 - v1)) / gamma (Eq. 9)."""
        out = {}
        for key in leaf_keys(buf_tree):
            b = get_leaf(buf_tree, key)
            sn = spec.stack_ndims(key)
            r1 = bcast_rho(get_leaf(rho[0], key), b, sn, 1)
            wsum = wk_for(key)[1].reshape(
                (-1,) + (1,) * (b.ndim - 1)).astype(b.dtype)
            num = r1 * b
            den = r1 * wsum + hp.weight_decay / max(M1, 1)
            if K > 1:
                r2 = bcast_rho(get_leaf(rho[1], key), b, sn, 1)
                num = num + r2 * get_leaf(z2v_tree, key)
                den = den + r2
            out[key] = (num / den).astype(b.dtype)
        return unflatten(out)

    # ---- Phases 2-3: the level-1 exchange and the masks --------------------
    info: dict = {}
    with jax.named_scope("consensus/level1"):
        payload0 = jax.tree.map(lambda t, uu: t + uu, theta, u)
        z2v = None
        if K > 1:
            z2v = jax.tree.map(lambda z2, v1: ungroup(z2, levels[1]) - v1,
                               zs_old[1], vs_old[0])

        if spec.boundary_compact(1, codecs):
            # masks from per-worker payloads; level-1 reduce is already
            # compact
            new_masks, idxs, minfo = _make_masks(state, spec, payload0,
                                                 frozen)
            info.update(minfo)
            pc = compact_params(payload0, plan, idxs, offset=1)
            buf = wire_reduce(pc, 1, levels[0], 0)   # compact collective
            z2v_c = compact_params(z2v, plan, idxs, offset=1) \
                if K > 1 else None
            z1c = cand1(buf, z2v_c)
            z1 = expand_params(z1c, plan, idxs, fulls, offset=1)  # recovery
        else:
            # dense intra AllReduce
            buf = wire_reduce(payload0, 1, levels[0], 0)
            z1t = cand1(buf, z2v)
            new_masks, idxs, minfo = _make_masks(state, spec, z1t, frozen)
            info.update(minfo)
            z1 = z1t
            for rule in plan.rules:              # projection Pi_S (Eq. 10)
                z1 = apply_mask_rule(z1, rule,
                                     new_masks[rule.name]["mask"][None],
                                     offset=1)

    # ---- Phase 4: levels 2..K ----------------------------------------------
    zs_new = [z1]
    for k in range(2, K + 1):
        with jax.named_scope(f"consensus/level{k}"):
            g = levels[k - 1]
            payload = jax.tree.map(lambda zk, vk: zk + vk, zs_new[-1],
                                   vs_old[k - 2])
            zkv = None
            if k < K:
                zkv = jax.tree.map(lambda zn, vn: ungroup(zn, levels[k]) - vn,
                                   zs_old[k], vs_old[k - 1])
            do_compact = spec.boundary_compact(k, codecs)
            if do_compact:
                payload = compact_params(payload, plan, idxs, offset=1)
                if zkv is not None:
                    zkv = compact_params(zkv, plan, idxs, offset=1)
            red = wire_reduce(payload, k, g, k - 1)  # level-k collective

            out = {}
            for key in leaf_keys(red):
                b = get_leaf(red, key)
                sn = spec.stack_ndims(key)
                wsum = wk_for(key)[k].reshape(
                    (-1,) + (1,) * (b.ndim - 1)).astype(b.dtype)
                if k == K:                           # Eq. 11: weighted mean
                    out[key] = (b / jnp.maximum(wsum, 1e-12)).astype(b.dtype)
                else:
                    rk = bcast_rho(get_leaf(rho[k - 1], key), b, sn, 1)
                    rk1 = bcast_rho(get_leaf(rho[k], key), b, sn, 1)
                    out[key] = ((rk * b + rk1 * get_leaf(zkv, key))
                                / (rk * wsum + rk1)).astype(b.dtype)
            zk = unflatten(out)
            if do_compact:
                # zero-fill recovery
                zk = expand_params(zk, plan, idxs, fulls, offset=1)
            zs_new.append(zk)

    with jax.named_scope("consensus/update"):
        # ---- Phase 5: duals (Eq. 12-13) -------------------------------------
        z1b = jax.tree.map(lambda z: ungroup(z, levels[0]), zs_new[0])
        u_new = jax.tree.map(
            lambda uu, th, zz: uu + (th - zz.astype(th.dtype)), u, theta, z1b)
        vs_new = []
        for k in range(1, K):
            zkp = jax.tree.map(lambda z: ungroup(z, levels[k]), zs_new[k])
            vs_new.append(jax.tree.map(lambda vv, zk, zp: vv + (zk - zp),
                                       vs_old[k - 1], zs_new[k - 1], zkp))

        # ---- residuals + layer-wise adaptive penalties (paper §3.4) ---------
        rho_new = []
        u_scaled, vs_scaled = u_new, list(vs_new)
        r_tot = jnp.zeros((), jnp.float32)
        s_tot = jnp.zeros((), jnp.float32)
        for b in range(K):  # boundary b: level-b <-> level-(b+1)
            if b == 0:
                lhs, rhs_new, rhs_old = theta, zs_new[0], zs_old[0]
            else:
                lhs, rhs_new, rhs_old = zs_new[b - 1], zs_new[b], zs_old[b]
            gb = levels[b]
            rho_b_new, factors = {}, {}
            for key in leaf_keys(rho[b]):
                sn = spec.stack_ndims(key)
                x = get_leaf(lhs, key)
                zn = ungroup(get_leaf(rhs_new, key), gb)
                r2 = _norm_sq_per_stack(x - zn.astype(x.dtype), sn, 1)
                dz = get_leaf(rhs_new, key) - get_leaf(rhs_old, key)
                s2 = _norm_sq_per_stack(dz, sn, 1)
                rho_b = get_leaf(rho[b], key)
                r_n = jnp.sqrt(r2)
                s_n = rho_b * jnp.sqrt(s2)
                f = jnp.where(r_n > hp.adapt_mu * s_n, hp.adapt_tau,
                              jnp.where(s_n > hp.adapt_mu * r_n,
                                        1.0 / hp.adapt_tau, 1.0))
                new_rho = jnp.clip(rho_b * f, 1e-8, hp.rho_max)
                rho_b_new[key] = new_rho
                # scaled-dual rescale (Boyd §3.4.1)
                factors[key] = rho_b / new_rho
                r_tot = r_tot + jnp.sum(r2)
                s_tot = s_tot + jnp.sum(s2)
                if detail:
                    tag = "r_intra" if b == 0 else f"r_inter{b}"
                    info.setdefault(tag, {})[key] = r_n
            rho_new.append(unflatten(rho_b_new))

            def _rescale(tree):
                out = {}
                for key in leaf_keys(tree):
                    x = get_leaf(tree, key)
                    f = bcast_rho(factors[key].astype(jnp.float32), x,
                                  spec.stack_ndims(key), 1).astype(x.dtype)
                    out[key] = x * f
                return unflatten(out)
            if b == 0:
                u_scaled = _rescale(u_new)
            else:
                vs_scaled[b - 1] = _rescale(vs_new[b - 1])

        info["r_primal"] = jnp.sqrt(r_tot)
        info["s_dual"] = jnp.sqrt(s_tot)

    new_state = dict(state)
    new_state.update(theta=theta, u=u_scaled, z=zs_new, v=vs_scaled,
                     rho=rho_new, masks=new_masks,
                     k=state["k"] + 1)
    if need_wire:
        new_state["wire"] = wire_new
    return new_state, info
