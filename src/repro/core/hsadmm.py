"""H-SADMM state and the Phase-1 local update (paper §3.1, Alg. 1 line 4).

State layout (DESIGN.md §3.3) — pure pytrees with leading consensus dims:

    theta, mom, u  : (W, *param)        per ADMM worker
    z[k], v[k]     : (M_k, *param)      per level-k consensus group, k=1..K
                     (M_k = W / prod(levels[:k]); M_K == 1 == global z)
    rho[k]         : per-leaf arrays of shape leaf.shape[:stack_ndims]
                     (layer-wise adaptive penalties, paper §3.4)
    weights        : (W,) f32           straggler/failure contribution weights
    masks          : per-rule {idx, valid, mask, drift}
    k              : outer iteration counter

The worker dim W is flat, outer-major over (pod, node, worker) so that
group-reshapes align with the mesh device order (prototype-validated).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..comm import group_sum   # reference reduction (shared with codecs)
from ..configs.base import ArchConfig, ConsensusSpec, HsadmmConfig
from .masks import MaskSyncConfig, budget as rule_budget
from .sparsity import SparsityPlan, get_leaf

Params = dict


# ---------------------------------------------------------------------------
# Static engine spec
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class EngineSpec:
    """Everything static the H-SADMM engine needs (closed over by jit)."""

    plan: SparsityPlan
    consensus: ConsensusSpec
    hp: HsadmmConfig
    # (prefix, ndims) pairs; longest matching prefix wins, default 0.  A
    # leaf's first `ndims` axes are scan-stack axes (layer index etc.) that
    # get independent layer-wise penalties/residuals (paper §3.4).
    stack_map: tuple[tuple[str, int], ...] = (("blocks", 1),)
    use_momentum: bool = True
    momentum: float = 0.9
    # Per-coupling-class straggler weights (dist.ft class-scoped
    # policies): adds a ``{rule: (W,)}`` weight tree to the state and
    # partitions the wire reduce per coupling class, so a slow worker
    # is discounted only on the classes it is late for — and the
    # per-class collectives become independently schedulable, letting
    # early classes' payloads ship while later classes still compute.
    class_weights: bool = False
    # Mesh whose ``data`` axis shards the worker dim W (set by the Engine
    # when W's sharding is exactly ``data``).  Mosaic kernels cannot be
    # partitioned by GSPMD, so local_step runs the Pallas prox update per
    # data shard under shard_map (other mesh axes see whole leaves).
    worker_mesh: Optional[Any] = None

    @property
    def sync_cfg(self) -> MaskSyncConfig:
        return MaskSyncConfig(self.hp.mask_mode, self.hp.bitwise_or_slack)

    @property
    def budgets(self) -> dict:
        return {r.name: rule_budget(r, self.sync_cfg) for r in self.plan.rules}

    @property
    def num_levels(self) -> int:
        return len(self.consensus.levels)

    @property
    def solo(self) -> bool:
        return (self.consensus.num_workers == 1
                and self.consensus.granularity == "pod")

    @property
    def codecs(self) -> list:
        """One :class:`repro.comm.WireCodec` per level boundary k=1..K
        (resolved from hp.wire_intra / hp.wire_inter, legacy comm_quant
        shimmed) — every consensus exchange routes through these."""
        from ..comm import level_codecs
        return level_codecs(self.hp, self.consensus.levels,
                            self.consensus.compact_from_level)

    def boundary_compact(self, k: int, codecs: list = None) -> bool:
        """Does boundary k (1..K) ship the physically-shrunk buffer?
        True when ``compact_from_level`` covers it OR its codec spec
        carries the ``compact`` marker.  THE predicate — consensus_step,
        the wire-state init, and the loop accounting all call this."""
        codecs = codecs if codecs is not None else self.codecs
        return (k - 1) >= self.consensus.compact_from_level \
            or codecs[k - 1].compact

    def group_sizes(self) -> tuple[int, ...]:
        return self.consensus.levels

    def stack_ndims(self, key: str) -> int:
        best, best_len = 0, -1
        for prefix, nd in self.stack_map:
            if (key.startswith(prefix + "/") or key == prefix) \
                    and len(prefix) > best_len:
                best, best_len = nd, len(prefix)
        return best


def leaf_keys(params: Params, prefix: str = "") -> list[str]:
    out = []
    for k, v in params.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.extend(leaf_keys(v, path))
        else:
            out.append(path)
    return out


def tree_map_leaves(fn: Callable, params: Params) -> Params:
    """Map over leaves with their '/'-joined key: fn(key, leaf)."""
    def rec(node, prefix):
        out = {}
        for k, v in node.items():
            path = f"{prefix}/{k}" if prefix else k
            out[k] = rec(v, path) if isinstance(v, dict) else fn(path, v)
        return out
    return rec(params, "")


# ---------------------------------------------------------------------------
# grouping helpers over the leading consensus dim
# ---------------------------------------------------------------------------


def ungroup(x: jnp.ndarray, g: int) -> jnp.ndarray:
    """(G, *p) -> (G*g, *p) broadcast children from their group value."""
    return jnp.broadcast_to(x[:, None], (x.shape[0], g) + x.shape[1:]) \
              .reshape((x.shape[0] * g,) + x.shape[1:])


def bcast_rho(rho: jnp.ndarray, leaf: jnp.ndarray, stack_ndims: int,
              offset: int) -> jnp.ndarray:
    """Broadcast a (stack,) penalty to a (lead..., stack, ...) leaf."""
    shape = [1] * leaf.ndim
    for i in range(stack_ndims):
        shape[offset + i] = rho.shape[i]
    return rho.reshape(shape).astype(leaf.dtype)


# ---------------------------------------------------------------------------
# state init
# ---------------------------------------------------------------------------


def init_state(params0: Params, spec: EngineSpec) -> dict:
    """Replicate initial params to every worker/node and zero the duals.

    params0 has *no* leading dims (a single model init); all workers start
    from the same point (paper Alg. 1 line 1), masks start all-ones.
    """
    W = spec.consensus.num_workers
    levels = spec.consensus.levels

    def rep(n):
        return lambda _, x: jnp.broadcast_to(x, (n,) + x.shape).copy() \
            if n > 1 else x[None]

    theta = tree_map_leaves(rep(W), params0)
    state = {"theta": theta, "k": jnp.zeros((), jnp.int32),
             "weights": jnp.ones((W,), jnp.float32)}
    if spec.use_momentum:
        state["mom"] = jax.tree.map(jnp.zeros_like, theta)
    if spec.solo:
        # Single-worker degenerate case (pod granularity on one pod): no
        # consensus variables exist; training is plain (FSDP) SGD and the
        # paper's technique reduces to direct structured projection of
        # theta (DESIGN.md §5 arch-applicability).
        state["masks"] = _init_masks(params0, spec)
        return state
    u = jax.tree.map(jnp.zeros_like, theta)
    state["u"] = u
    if spec.class_weights:
        # per-coupling-class contribution weights, multiplied into the
        # global (W,) weights inside consensus_step; all-ones init means
        # bit-identity with the unscoped path until a policy writes them
        state["class_weights"] = {r.name: jnp.ones((W,), jnp.float32)
                                  for r in spec.plan.rules}

    m = W
    zs = []
    for g in levels:
        m //= g
        zs.append(tree_map_leaves(rep(m), params0))
    state["z"] = zs
    # duals exist between consecutive levels only: v[k] couples z[k]<->z[k+1]
    state["v"] = [jax.tree.map(jnp.zeros_like, zk) for zk in zs[:-1]]

    # layer-wise penalties rho[k]: list over level boundaries (K entries:
    # rho[0] = worker<->z1 (paper rho1), rho[k>=1] = z_k<->z_{k+1})
    def rho_tree(val):
        return tree_map_leaves(
            lambda key, x: jnp.full(x.shape[:spec.stack_ndims(key)], val,
                                    jnp.float32), params0)
    rhos = [rho_tree(spec.hp.rho1)]
    for _ in range(len(levels) - 1):
        rhos.append(rho_tree(spec.hp.rho2))
    state["rho"] = rhos

    state["masks"] = _init_masks(params0, spec)
    codecs = spec.codecs
    if any(c.stateful for c in codecs):
        state["wire"] = _init_wire_states(params0, spec, codecs)
    return state


def _init_wire_states(params0: Params, spec: EngineSpec, codecs: list
                      ) -> list:
    """Per-boundary error-feedback state for stateful wire codecs
    (repro.comm, e.g. ``topk:<rate>``): one zero tree shaped like the
    boundary-k payload — leading dim M_{k-1}, leaf shapes compacted when
    that boundary ships the physically-shrunk buffer.  Stateless
    boundaries hold an empty subtree so the state pytree structure stays
    invariant across rounds."""
    from .shrinkage import plan_payload_shapes
    levels = spec.consensus.levels
    keys = leaf_keys(params0)
    full_shapes = {k: tuple(get_leaf(params0, k).shape) for k in keys}
    compact_shapes = plan_payload_shapes(full_shapes, spec.plan,
                                         spec.budgets)
    out: list = []
    m = spec.consensus.num_workers
    for k in range(1, len(levels) + 1):
        lead, m = m, m // levels[k - 1]
        codec = codecs[k - 1]
        if not codec.stateful:
            out.append({})
            continue
        shapes = compact_shapes if spec.boundary_compact(k, codecs) \
            else full_shapes
        flat = {key: jnp.zeros((lead,) + shapes[key],
                               get_leaf(params0, key).dtype)
                for key in keys}
        out.append(codec.init_state(_unflatten(flat)))
    return out


def identity_mask_state(rule, stack_shape: tuple, B: int) -> dict:
    """All-kept mask state for one rule: idx = arange(B) (block-local for
    balanced rules), valid/mask all-ones, drift zero.  The init state of
    every rule, and the migrated mask state of a reconfigured engine's
    compactable rules (whose group axis IS the budget).  All quantities
    are in the rule's GROUP units (``rule.group_size`` channels per
    group for the CNN family's GN-block-granular rules)."""
    if rule.shards == 1:
        idx = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32),
                               stack_shape + (B,))
    else:  # balanced rules use block-local indices
        idx = jnp.broadcast_to(
            jnp.arange(B // rule.shards, dtype=jnp.int32),
            stack_shape + (rule.shards, B // rule.shards))
    return {
        "idx": idx,
        "valid": jnp.ones(idx.shape, jnp.float32),
        "mask": jnp.ones(stack_shape + (rule.groups,), jnp.float32),
        "drift": jnp.zeros((), jnp.float32),
    }


def _init_masks(params0: Params, spec: EngineSpec) -> dict:
    # masks: all-ones init (paper line 1: m_global <- 1)
    return {rule.name: identity_mask_state(
                rule, _rule_stack_shape(params0, rule),
                spec.budgets[rule.name])
            for rule in spec.plan.rules}


def _rule_stack_shape(params0: Params, rule) -> tuple[int, ...]:
    leaf = get_leaf(params0, rule.leaves[0].key)
    return leaf.shape[:rule.stack_ndims]


# ---------------------------------------------------------------------------
# Phase 1: local prox-SGD step (Eq. 8)
# ---------------------------------------------------------------------------


def local_step(state: dict, batch, loss_fn: Callable, spec: EngineSpec,
               eta: float, grad_accum: int = 1) -> tuple[dict, jnp.ndarray]:
    """One minibatch prox-SGD step on every worker in parallel.

    loss_fn(params_one_worker, batch_one_worker) -> scalar.
    batch leaves have leading dim W.  The prox gradient
    rho1 * (theta - z1 + u) is added analytically (cheaper than autodiff
    through the penalty).  grad_accum > 1 splits the per-worker batch into
    microbatches and accumulates grads in a scan (activation memory drops
    grad_accum-fold).  Returns (new_state, mean loss).
    """
    levels = spec.consensus.levels
    theta = state["theta"]
    if spec.solo:
        u = z1_w = None
    else:
        u = state["u"]
        z1_w = jax.tree.map(lambda z: ungroup(z, levels[0]), state["z"][0])

    if grad_accum > 1:
        def worker_vg(th, bw):
            mb = jax.tree.map(
                lambda x: x.reshape((grad_accum, x.shape[0] // grad_accum)
                                    + x.shape[1:]), bw)

            def body(carry, b1):
                l, g = jax.value_and_grad(loss_fn)(th, b1)
                return (carry[0] + l, jax.tree.map(jnp.add, carry[1], g)), None

            init = (jnp.zeros((), jnp.float32),
                    jax.tree.map(jnp.zeros_like, th))
            (l, g), _ = jax.lax.scan(body, init, mb)
            ga = jnp.float32(grad_accum)
            return l / ga, jax.tree.map(lambda x: x / ga.astype(x.dtype), g)

        grad_fn = jax.vmap(worker_vg)
    else:
        grad_fn = jax.vmap(jax.value_and_grad(loss_fn))
    losses, g = grad_fn(theta, batch)

    rho1 = state.get("rho", [None])[0]

    def upd(key, th):
        # the update itself (prox gradient + momentum + SGD step) runs as
        # one streaming pass through the fused Pallas kernel when the
        # layout allows (kernels/ops.prox_sgd_update dispatch shim); eta
        # is cast to th.dtype there — a strong f32 eta would promote the
        # whole update (and its backward) to f32, 2x HBM
        gg = get_leaf(g, key)
        if spec.solo:
            zz = uu = r = None
        else:
            zz = get_leaf(z1_w, key)
            uu = get_leaf(u, key)
            r = bcast_rho(get_leaf(rho1, key), th,
                          spec.stack_ndims(key), offset=1)
        mm = get_leaf(state["mom"], key) if spec.use_momentum else None
        return _prox_update(spec, th, gg, zz, uu, mm, r, eta)

    new_theta, new_mom = {}, {}
    for key in leaf_keys(theta):
        t, m = upd(key, get_leaf(theta, key))
        new_theta[key] = t
        new_mom[key] = m
    theta = _unflatten(new_theta)
    out = dict(state)
    out["theta"] = theta
    if spec.use_momentum:
        out["mom"] = _unflatten(new_mom)
    return out, jnp.mean(losses)


def _prox_update(spec: EngineSpec, theta, g, z, u, mom, rho, eta):
    """``kernels.ops.prox_sgd_update`` on one (W, ...) leaf — per data
    shard of the worker dim when ``spec.worker_mesh`` is set."""
    from ..kernels.ops import prox_sgd_update
    upd = functools.partial(prox_sgd_update, momentum=spec.momentum)
    if spec.worker_mesh is None:
        return upd(theta, g, z, u, mom, rho, eta)
    ops_ = (theta, g, z, u, mom, rho)
    # operands with the worker dim split over data; rho (1, stack, 1..)
    # and eta are the same on every shard
    specs = tuple(None if a is None else
                  P("data") if a.ndim and a.shape[0] == theta.shape[0]
                  else P() for a in ops_)
    return jax.shard_map(
        lambda a, e: upd(*a, e), mesh=spec.worker_mesh,
        in_specs=(specs, P()),
        out_specs=(P("data"), None if mom is None else P("data")),
        check_vma=False)(ops_, eta)


def _unflatten(flat: dict) -> dict:
    out: dict = {}
    for key, v in flat.items():
        parts = key.split("/")
        node = out
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def flatten(params: Params) -> dict:
    return {k: get_leaf(params, k) for k in leaf_keys(params)}


def unflatten(flat: dict) -> dict:
    return _unflatten(flat)


# ---------------------------------------------------------------------------
# Fused round: E local steps + consensus in ONE trace (paper §4.1.4)
# ---------------------------------------------------------------------------


class RoundMetrics(NamedTuple):
    """Per-round telemetry as *device* arrays — the training loop drains
    these asynchronously (no host sync on the hot path)."""

    losses: jnp.ndarray        # (E,) mean-over-workers loss per local step
    r_primal: jnp.ndarray      # scalar primal residual (Alg. 1 l.29)
    s_dual: jnp.ndarray        # scalar dual residual
    drift: jnp.ndarray         # total mask drift (0 once frozen)
    converged: jnp.ndarray     # bool, paper stopping rule (False in solo)
    drift_by_rule: dict        # {rule name: scalar drift}


def round_metrics(state: dict, info: dict, losses: jnp.ndarray,
                  spec: EngineSpec) -> RoundMetrics:
    """Assemble RoundMetrics from a post-consensus state + info dict."""
    from .residuals import converged as _converged
    drifts = {r.name: state["masks"][r.name]["drift"]
              for r in spec.plan.rules}
    total = sum(drifts.values()) if drifts else jnp.zeros((), jnp.float32)
    conv = jnp.zeros((), bool) if spec.solo \
        else _converged(state, info, spec.hp)
    return RoundMetrics(losses=jnp.atleast_1d(losses),
                        r_primal=info["r_primal"], s_dual=info["s_dual"],
                        drift=jnp.asarray(total, jnp.float32),
                        converged=conv, drift_by_rule=drifts)


def round_step(state: dict, superbatch, loss_fn: Callable, spec: EngineSpec,
               eta, grad_accum: int = 1, frozen: bool = False
               ) -> tuple[dict, RoundMetrics]:
    """One full H-SADMM outer round as a single traceable program.

    ``lax.scan``s E local prox-SGD steps over a stacked ``(E, W, ...)``
    superbatch, then runs the hierarchical consensus (Phases 2-5) inside
    the same trace — jitted by the engine this is exactly one dispatch
    per round, with no device->host readback: all telemetry comes back
    as :class:`RoundMetrics` device arrays.
    """
    from .consensus import consensus_step

    def body(st, batch):
        st, loss = local_step(st, batch, loss_fn, spec, eta,
                              grad_accum=grad_accum)
        return st, loss

    state, losses = jax.lax.scan(body, state, superbatch)
    state, info = consensus_step(state, spec, frozen=frozen, detail=False)
    return state, round_metrics(state, info, losses, spec)


def round_step_overlapped(state: dict, superbatch, loss_fn: Callable,
                          spec: EngineSpec, eta, grad_accum: int = 1,
                          frozen: bool = False
                          ) -> tuple[dict, RoundMetrics]:
    """One overlapped round: staleness-1 pipelining of :func:`round_step`.

    The consensus (Phases 2-5, carrying the inter-node collectives) runs
    over the state AS-IS — i.e. over the theta the *previous* round's
    local scan produced — while this round's E prox-SGD steps scan over
    the SAME input state, anchoring to the one-round-stale z/u (the
    standard bounded-staleness async-ADMM relaxation).  The two programs
    share only reads, so XLA is free to overlap the slow-fabric reduce
    with the local compute; the outputs merge disjointly (theta/mom from
    the scan, every consensus variable — z, v, u, rho, masks, wire EF
    state, k — from the reduce).

    The wire error-feedback state threads consensus->consensus exactly
    as in the sequential round: each reduce encodes the theta snapshot
    its EF state was accumulated against, so top-k feedback always sees
    the buffer it actually encoded.

    The returned state still carries ONE pending (un-reduced) theta;
    :func:`flush_pipeline` drains it — required before a physical
    reconfiguration migrates the state, since masks/budgets derived from
    a stale consensus would migrate a buffer the shrunk plan never saw.
    """
    from .consensus import consensus_step
    if spec.solo:
        # no consensus variables exist; nothing to overlap
        return round_step(state, superbatch, loss_fn, spec, eta,
                          grad_accum=grad_accum, frozen=frozen)

    def body(st, batch):
        st, loss = local_step(st, batch, loss_fn, spec, eta,
                              grad_accum=grad_accum)
        return st, loss

    new_cstate, info = consensus_step(state, spec, frozen=frozen,
                                      detail=False)
    scan_state, losses = jax.lax.scan(body, state, superbatch)
    out = dict(new_cstate)
    out["theta"] = scan_state["theta"]
    if spec.use_momentum:
        out["mom"] = scan_state["mom"]
    return out, round_metrics(out, info, losses, spec)


def flush_pipeline(state: dict, spec: EngineSpec, frozen: bool = False
                   ) -> tuple[dict, RoundMetrics]:
    """Drain the pending consensus of an overlapped pipeline: one
    consensus-only step over the state as-is (no local scan).  After
    this the state is exactly what a sequential round would have left —
    safe to checkpoint as sequential, migrate through
    ``Engine.reconfigure``, or hand to a staleness-0 engine."""
    from .consensus import consensus_step
    state, info = consensus_step(state, spec, frozen=frozen, detail=False)
    return state, round_metrics(state, info,
                                jnp.zeros((0,), jnp.float32), spec)
