"""Engine: binds a ModelBundle + mesh + H-SADMM core into sharded,
donated, jitted step functions (DESIGN.md §3).

Responsibilities:
  * derive the consensus hierarchy from the mesh + arch granularity
    (chip: device->virtual-node->pod->global; pod: pod->global),
  * build NamedShardings for every H-SADMM state leaf (leading consensus
    dims over pod/data axes, TP over model, ZeRO-style FSDP spill of
    logically-replicated consensus state),
  * jit local_step / consensus_step (dynamic + frozen variants) and the
    serving steps with explicit in/out shardings and donation.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig, ConsensusSpec, ShapeConfig
from ..core.consensus import consensus_step
from ..core.hsadmm import (EngineSpec, flush_pipeline, init_state,
                           local_step, round_step, round_step_overlapped)
from ..models.api import ModelBundle


def make_consensus_spec(cfg: ArchConfig, mesh: Mesh,
                        node_size: int = None) -> ConsensusSpec:
    """Map arch granularity onto the mesh (DESIGN.md §3.2).

    chip: every data-rank is an ADMM worker; the data axis splits into
          virtual nodes of ``node_size`` (paper's two-level hierarchy inside
          a pod); the pod axis adds a third level (paper §4.1.5).
    pod:  each pod is one worker (sync FSDP inside); consensus across pods
          only, compact from the first boundary.
    """
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    data = axes.get("data", 1)
    pods = axes.get("pod", 1)
    g = cfg.consensus.granularity
    node_size = node_size or cfg.consensus.node_size
    if g == "chip":
        ns = min(node_size, data)
        levels = (ns,) + ((data // ns,) if data // ns > 1 else ()) \
            + ((pods,) if pods > 1 else ())
        if len(levels) == 1:
            levels = levels + (1,)  # keep a node->global boundary
        return ConsensusSpec(levels=levels, compact_from_level=1,
                             granularity="chip", node_size=ns)
    if g == "pod":
        levels = (pods,) if pods > 1 else (1,)
        return ConsensusSpec(levels=levels, compact_from_level=0,
                             granularity="pod")
    if g == "flat":   # paper §5.1.4 "PruneX (AR)" ablation: flat consensus
        levels = (data * pods,)
        return ConsensusSpec(levels=levels, compact_from_level=1,
                             granularity="flat")
    raise ValueError(g)


def _walk(tree, fn, path=()):
    """Map over a nested dict/list/tuple pytree with '/'-joined key paths."""
    if isinstance(tree, dict):
        return {k: _walk(v, fn, path + (str(k),)) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        t = [_walk(v, fn, path + (str(i),)) for i, v in enumerate(tree)]
        return type(tree)(t)
    return fn("/".join(path), tree)


def _flat_specs(spec_tree, prefix=""):
    out = {}
    for k, v in spec_tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(_flat_specs(v, path))
        else:
            out[path] = v
    return out


class Engine:
    def __init__(self, bundle: ModelBundle, mesh: Mesh,
                 shape: Optional[ShapeConfig] = None,
                 consensus: Optional[ConsensusSpec] = None,
                 extra_fsdp: bool = None, class_weights: bool = False):
        self.bundle = bundle
        self.cfg = bundle.cfg
        self.mesh = mesh
        self.axes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.consensus = consensus or make_consensus_spec(self.cfg, mesh)
        self.class_weights = class_weights
        worker_mesh = mesh if self.axes.get("data", 1) > 1 \
            and self._lead_spec(self.workers) == "data" else None
        self.spec = EngineSpec(
            plan=bundle.plan, consensus=self.consensus, hp=self.cfg.hsadmm,
            stack_map=tuple(bundle.stack_map), class_weights=class_weights,
            worker_mesh=worker_mesh)
        self.shape = shape
        if self.cfg.hsadmm.staleness not in (0, 1):
            raise ValueError(
                f"staleness={self.cfg.hsadmm.staleness} is not supported: "
                "0 (sequential round) and 1 (one-round-stale overlapped "
                "pipeline) are the implemented depths")
        self._check_cnn_batch_partition()
        # pod-granularity workers are internally synchronous-FSDP: spill
        # param dims over the data axis too
        if extra_fsdp is None:
            extra_fsdp = self.consensus.granularity == "pod"
        self.extra_fsdp = extra_fsdp
        self.param_specs_flat = _flat_specs(bundle.param_specs)
        self._shardings = None
        # set by reconfigure(): the full-shape parent engine + the frozen
        # full-shape mask state the shrunk shapes were derived from
        self.parent: Optional["Engine"] = None
        self.frozen_masks: Optional[dict] = None

    def _check_cnn_batch_partition(self):
        """W==devices CNN corner (DESIGN.md multi-device caveats): a CNN
        worker dim sharded so the per-worker batch is 1 makes the
        batch-group-conv trick degenerate, and GSPMD's partitioner on
        CPU dies much later with an opaque internal reshape RET_CHECK
        (``hlo_verifier.cc`` "Failed after spmd-partitioning") at the
        first round dispatch.  Detect it at engine construction and name
        the constraint instead."""
        if self.cfg.family != "cnn" or self.shape is None \
                or not self.shape.is_train:
            return
        W = self.workers
        per_worker = self.shape.global_batch // max(W, 1)
        if per_worker > 1:
            return
        lead = self._lead_spec(W)
        axes = lead if isinstance(lead, tuple) else (lead,)
        sharded = 1
        for ax in axes:
            if ax:
                sharded *= self.axes.get(ax, 1)
        if sharded <= 1:
            return
        if self.mesh.devices.flat[0].platform != "cpu":
            return  # only the CPU partitioner is known to trip
        raise ValueError(
            f"CNN worker dim sharded {sharded}-way with a per-worker "
            f"batch of {per_worker} (global_batch="
            f"{self.shape.global_batch} over W={W} workers): this trips "
            "a GSPMD batch-group-conv reshape corner on CPU (internal "
            "hlo_verifier RET_CHECK after spmd-partitioning). Use a "
            "global batch of at least 2 images per worker, or fewer "
            "workers over the data axis (the measured-HLO benchmarks "
            "pin W=4 over data=4).")

    def _derive(self, bundle: ModelBundle, *,
                class_weights: Optional[bool] = None) -> "Engine":
        """A sibling Engine over ``bundle`` — same mesh/shape/hierarchy,
        fresh jit/sharding caches — PRESERVING the reconfiguration
        lineage (parent + frozen masks), so deriving from a
        reconfigured engine doesn't silently forget it is one."""
        eng = Engine(bundle, self.mesh, self.shape,
                     consensus=self.consensus, extra_fsdp=self.extra_fsdp,
                     class_weights=self.class_weights
                     if class_weights is None else class_weights)
        eng.parent = self.parent
        eng.frozen_masks = self.frozen_masks
        return eng

    def with_wire(self, intra: Optional[str] = None,
                  inter: Optional[str] = None,
                  wire_map=None) -> "Engine":
        """A new Engine whose consensus exchanges run through the given
        ``repro.comm`` codec specs (None keeps the config's choice) —
        same bundle, mesh, hierarchy; fresh jit/sharding caches.
        ``wire_map`` (one spec per level boundary, e.g. a
        ``WireSelection.spec_map``) overrides intra/inter verbatim."""
        import dataclasses
        hp = self.cfg.hsadmm
        hp = dataclasses.replace(
            hp, wire_intra=intra if intra is not None else hp.wire_intra,
            wire_inter=inter if inter is not None else hp.wire_inter,
            wire_map=tuple(wire_map) if wire_map is not None
            else hp.wire_map)
        bundle = dataclasses.replace(self.bundle,
                                     cfg=self.cfg.replace(hsadmm=hp))
        return self._derive(bundle)

    def with_staleness(self, staleness: int) -> "Engine":
        """A new Engine running its rounds at the given overlap depth
        (``HsadmmConfig.staleness``: 0 sequential, 1 overlapped)."""
        import dataclasses
        hp = dataclasses.replace(self.cfg.hsadmm, staleness=staleness)
        bundle = dataclasses.replace(self.bundle,
                                     cfg=self.cfg.replace(hsadmm=hp))
        return self._derive(bundle)

    def with_class_weights(self, enabled: bool = True) -> "Engine":
        """A new Engine whose consensus carries per-coupling-class
        straggler weights (``dist.ft.class_scoped`` policies).  NOTE:
        this changes the STATE STRUCTURE (adds a ``class_weights``
        subtree) — init state through the new engine; a state from the
        unscoped engine does not round-trip."""
        return self._derive(self.bundle, class_weights=enabled)

    # ------------------------------------------------------------------ #
    # physical reconfiguration (paper §4.4 applied to the WHOLE run)
    # ------------------------------------------------------------------ #

    @property
    def reconfigured(self) -> bool:
        return self.parent is not None

    def _boundary_compact_flags(self) -> tuple:
        if self.spec.solo:
            return ()
        return tuple(self.spec.boundary_compact(k)
                     for k in range(1, self.spec.num_levels + 1))

    def reconfigure(self, state: Optional[dict] = None,
                    masks: Optional[dict] = None):
        """Retrace onto the physically-shrunk architecture once masks are
        frozen (PruneTrain-style reconfiguration).

        Builds a new Engine over the budget-B model (``models.
        shrink_config`` width mapping + the all-kept ``shrunk_plan``, same
        mesh/hierarchy/codecs) and migrates the ENTIRE H-SADMM state —
        theta/z/u, momenta, wire error-feedback, rho — through
        ``compact_state`` with one jitted executable pinned to the new
        engine's shardings.  Returns ``(new_engine, migrated_state)``;
        ``migrated_state`` is None when only ``masks`` (a frozen
        full-shape mask state, e.g. from a checkpoint's aux arrays) is
        given — the resume path, which restores directly into the new
        engine's shapes.
        """
        import dataclasses as _dc

        from ..core.hsadmm import flatten, identity_mask_state
        from ..core.shrinkage import (compact_state, compacting_rule,
                                      shrunk_plan,
                                      shrunk_projection_mask_state)
        from ..models import build as _build, shrink_config
        if self.reconfigured:
            raise ValueError("engine is already reconfigured")
        if masks is None:
            if state is None:
                raise ValueError("reconfigure() needs state= or masks=")
            masks = state["masks"]
        spec = self.spec
        budgets = spec.budgets
        p0 = jax.eval_shape(self.bundle.init, jax.random.PRNGKey(0))
        param_shapes = {k: tuple(v.shape) for k, v in flatten(p0).items()}
        new_cfg = shrink_config(self.cfg, spec.plan, budgets)
        new_plan = shrunk_plan(spec.plan, budgets, param_shapes)
        bundle2 = _dc.replace(_build(new_cfg), cfg=new_cfg, plan=new_plan)
        eng2 = Engine(bundle2, self.mesh, self.shape,
                      consensus=self.consensus, extra_fsdp=self.extra_fsdp,
                      class_weights=self.class_weights)
        eng2.parent = self
        eng2.frozen_masks = jax.tree.map(jnp.asarray, masks)
        if state is None:
            return eng2, None

        wire_compact = self._boundary_compact_flags()
        plan = spec.plan
        # identity-mask stack shapes come from the NEW architecture's leaf
        # shapes, not the old mask state: a rule that compacts another
        # rule's STACK axis (MoE "experts" slicing the (layer, expert)
        # stack "moe_ffn" masks live on) shrinks that stack extent too.
        p2 = jax.eval_shape(bundle2.init, jax.random.PRNGKey(0))
        shapes2 = {k: tuple(v.shape) for k, v in flatten(p2).items()}
        new_stacks = {r2.name: shapes2[r2.leaves[0].key][:r2.stack_ndims]
                      for r2 in new_plan.rules}

        def migrate(st):
            idxs = {r.name: st["masks"][r.name]["idx"] for r in plan.rules}
            new_masks = {}
            for r2 in new_plan.rules:
                old = st["masks"][r2.name]
                r1 = plan.rule(r2.name)
                if r1.compactable:
                    new_masks[r2.name] = identity_mask_state(
                        r2, new_stacks[r2.name], budgets[r2.name])
                elif any(compacting_rule(plan, la.key, a) is not None
                         for la in r1.all_leaves for a in la.axes):
                    # projection-only composite rule riding a compacted
                    # sub-axis (S_s over a shrunk C_in): gather the
                    # frozen mask onto the kept channels
                    new_masks[r2.name] = shrunk_projection_mask_state(
                        r1, r2, old, plan, idxs, param_shapes)
                else:
                    new_masks[r2.name] = dict(
                        old, drift=jnp.zeros((), jnp.float32))
            return compact_state(st, plan, idxs, new_masks, wire_compact)

        mig = jax.jit(migrate, out_shardings=eng2.state_shardings())
        return eng2, mig(state)

    def expand_reconfigured(self, state: dict) -> dict:
        """Inverse migration (on a RECONFIGURED engine): zero-fill the
        compact state back onto the parent's full-architecture shapes —
        cross-shape checkpoint restore, and the full-shape reference
        state of the differential conformance suite."""
        from ..core.shrinkage import expand_state
        if not self.reconfigured:
            raise ValueError("expand_reconfigured() needs a reconfigured "
                             "engine (see Engine.reconfigure)")
        parent = self.parent
        plan = parent.spec.plan
        masks_full = self.frozen_masks
        idxs = {r.name: masks_full[r.name]["idx"] for r in plan.rules}
        fulls = {r.name: r.groups for r in plan.rules}
        wire_compact = parent._boundary_compact_flags()
        exp = jax.jit(
            lambda st: expand_state(st, plan, idxs, fulls, masks_full,
                                    wire_compact),
            out_shardings=parent.state_shardings())
        return exp(state)

    # ------------------------------------------------------------------ #
    # sharding construction
    # ------------------------------------------------------------------ #

    @property
    def workers(self) -> int:
        return self.consensus.num_workers

    def _lead_spec(self, m: int):
        """Sharding entry for a leading consensus dim of size m."""
        pods = self.axes.get("pod", 1)
        data = self.axes.get("data", 1)
        if pods > 1 and m == pods * data:
            return ("pod", "data")
        if m == data:
            return "data"
        if pods > 1 and m % pods == 0 and m > 1:
            return "pod"
        return None

    def _param_spec(self, key: str, pshape, used_axes) -> tuple:
        base = self.param_specs_flat.get(key, P())
        entries = list(base) + [None] * (len(pshape) - len(base))
        # optional FSDP spill over unused lead axes (largest divisible dim)
        for ax in ("data", "pod"):
            if ax in used_axes or ax not in self.axes:
                continue
            if not (self.extra_fsdp or ax == "data"):
                continue
            size = self.axes[ax]
            best, best_dim = -1, 0
            for i, (e, dim) in enumerate(zip(entries, pshape)):
                if e is None and dim % size == 0 and dim > best_dim:
                    best, best_dim = i, dim
            if best >= 0 and (self.extra_fsdp or best_dim >= size * 64):
                entries[best] = ax
                used_axes = used_axes | {ax}
        return tuple(entries)

    def state_shardings(self):
        if self._shardings is not None:
            return self._shardings
        key = jax.random.PRNGKey(0)
        p0_shape = jax.eval_shape(self.bundle.init, key)
        st_shape = jax.eval_shape(
            functools.partial(init_state, spec=self.spec), p0_shape)

        W = self.workers

        def leaf_sharding(path, leaf):
            parts = path.split("/")
            group = parts[0]
            if group in ("theta", "u", "mom"):
                key2 = "/".join(parts[1:])
                lead = self._lead_spec(W)
                used = set(lead) if isinstance(lead, tuple) else \
                    ({lead} if lead else set())
                pspec = self._param_spec(key2, leaf.shape[1:], used)
                return NamedSharding(self.mesh, P(lead, *pspec))
            if group in ("z", "v"):
                key2 = "/".join(parts[2:])
                m = leaf.shape[0]
                lead = self._lead_spec(m)
                used = set(lead) if isinstance(lead, tuple) else \
                    ({lead} if lead else set())
                base = self.param_specs_flat.get(key2, P())
                entries = list(base) + [None] * (len(leaf.shape) - 1 -
                                                 len(base))
                # ZeRO-style data-axis spill ONLY when it aligns with the
                # natural reduce output (m==1 fully reduced, or pod-gran
                # workers already FSDP over data).  A partially-grouped lead
                # (e.g. M1=4 virtual nodes on a 16-wide data axis) cannot be
                # expressed in a PartitionSpec; forcing an FSDP respill there
                # makes GSPMD fall back to involuntary full remat (measured:
                # 98GiB/device) — keep those model-sharded + lead-replicated.
                if m == 1 or self.consensus.granularity == "pod":
                    for ax in ("data", "pod"):
                        if ax in used or ax not in self.axes:
                            continue
                        size = self.axes[ax]
                        best, best_dim = -1, 0
                        for i, (e, dim) in enumerate(
                                zip(entries, leaf.shape[1:])):
                            if e is None and dim % size == 0 \
                                    and dim > best_dim:
                                best, best_dim = i, dim
                        if best >= 0:
                            entries[best] = ax
                            used = used | {ax}
                return NamedSharding(self.mesh, P(lead, *entries))
            if group == "wire":
                # wire-codec error-feedback state (repro.comm): shaped
                # like the boundary payload — shard the lead consensus
                # dim when it maps onto a mesh axis, replicate the
                # (possibly compacted) param dims
                lead = self._lead_spec(leaf.shape[0])
                return NamedSharding(
                    self.mesh, P(lead, *([None] * (leaf.ndim - 1))))
            if group == "masks" and parts[-1] in ("idx", "valid") \
                    and leaf.ndim >= 2 \
                    and leaf.shape[-2] == self.axes.get("model", 0):
                # balanced-rule indices: keep the shard-block axis on the
                # model axis so FROZEN-path gathers stay shard-local (a
                # replicated idx forced GSPMD to all-gather every z leaf:
                # +1.5GiB/round measured on tinyllama)
                spec = [None] * leaf.ndim
                spec[-2] = "model"
                return NamedSharding(self.mesh, P(*spec))
            # rho / masks / weights / counters: tiny, replicated
            return NamedSharding(self.mesh, P())

        self._shardings = _walk(st_shape, leaf_sharding)
        self._state_shapes = st_shape
        return self._shardings

    def batch_sharding(self, batch_shapes: dict):
        lead = self._lead_spec(self.workers)
        # pod-granularity workers are internally synchronous-DP: the
        # per-worker batch dim shards over the data axis (and pod when the
        # lead dim doesn't consume it).
        inner = None
        if self.consensus.granularity == "pod":
            used = lead if isinstance(lead, tuple) else (lead,)
            free = [a for a in ("pod", "data") if a in self.axes
                    and a not in used]
            inner = tuple(free) if free else None
        return {k: NamedSharding(
            self.mesh, P(lead, inner, *([None] * (len(v.shape) - 2))))
            for k, v in batch_shapes.items()}

    def state_struct(self):
        """ShapeDtypeStructs with shardings attached (for AOT lowering).
        Structural zip (CNN rule names contain '/' — no path lookups)."""
        sh = self.state_shardings()
        return jax.tree.map(
            lambda leaf, s: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                                 sharding=s),
            self._state_shapes, sh,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    # ------------------------------------------------------------------ #
    # jitted steps
    # ------------------------------------------------------------------ #

    def local_step_fn(self):
        ga = max(self.cfg.grad_accum, 1)
        baxis = "data" if self.consensus.granularity == "pod" else None

        def fn(state, batch, eta):
            from ..models import layers as _L
            _L.set_batch_axis(baxis)   # trace-time activation-layout policy
            out = local_step(state, batch, self.bundle.train_loss,
                             self.spec, eta, grad_accum=ga)
            _L.set_batch_axis(None)
            return out
        return jax.jit(fn, donate_argnums=(0,))

    def consensus_step_fn(self, frozen: bool):
        def fn(state):
            return consensus_step(state, self.spec, frozen=frozen)
        return jax.jit(fn, donate_argnums=(0,))

    def round_step_fn(self, frozen: bool):
        """The fused round executable (paper §4.1.4): E scanned local
        prox-SGD steps + one hierarchical consensus, one dispatch, state
        donated, state outputs pinned to the canonical shardings.  The
        loop holds exactly two of these (dynamic + frozen).

        ``HsadmmConfig.staleness`` selects the round body: 0 jits the
        sequential ``round_step`` (bit-identical to the pre-overlap
        path), 1 the overlapped ``round_step_overlapped`` — same
        signature, donation and out-sharding discipline, still exactly
        one dispatch per round."""
        ga = max(self.cfg.grad_accum, 1)
        baxis = "data" if self.consensus.granularity == "pod" else None
        step = round_step if self.cfg.hsadmm.staleness == 0 \
            else round_step_overlapped

        def fn(state, superbatch, eta):
            from ..models import layers as _L
            _L.set_batch_axis(baxis)   # trace-time activation-layout policy
            out = step(state, superbatch, self.bundle.train_loss,
                       self.spec, eta, grad_accum=ga, frozen=frozen)
            _L.set_batch_axis(None)
            return out
        return jax.jit(fn, donate_argnums=(0,),
                       out_shardings=(self.state_shardings(), None))

    def flush_pipeline_fn(self, frozen: bool):
        """Jitted pipeline drain (``core.hsadmm.flush_pipeline``): one
        consensus-only dispatch over the pending buffer of an overlapped
        (staleness >= 1) round sequence, with the round executable's
        donation/out-sharding discipline.  After it the state is exactly
        what the sequential round would have left — required before
        ``reconfigure`` migrates the state, and before checkpointing a
        run that may resume at a different staleness."""
        def fn(state):
            return flush_pipeline(state, self.spec, frozen=frozen)
        return jax.jit(fn, donate_argnums=(0,),
                       out_shardings=(self.state_shardings(), None))

    def init_state_fn(self):
        sh = self.state_shardings()

        def fn(key):
            return init_state(self.bundle.init(key), self.spec)
        return jax.jit(fn, out_shardings=sh)

    # ------------------------------------------------------------------ #
    # compiled-HLO introspection (dist.hlo)
    # ------------------------------------------------------------------ #

    def consensus_hlo(self, state, frozen: bool = False) -> str:
        """Compiled-HLO text of the consensus executable for ``state``
        (an AOT lower+compile, independent of the loop's cached jit)."""
        return self.consensus_step_fn(frozen).lower(state) \
            .compile().as_text()

    def consensus_collectives(self, state, frozen: bool = False):
        """Trip-weighted :class:`repro.dist.hlo.Collective` records of the
        consensus executable — the *measured* communication schedule, to
        hold against the analytic ``plan_bytes`` accounting."""
        from ..dist.hlo_cost import weighted_cost
        txt = self.consensus_hlo(state, frozen=frozen)
        wc = weighted_cost(txt, model=self.axes.get("model", 1),
                           data=self.axes.get("data", 1),
                           node=self.consensus.node_size)
        return wc.collectives

    def superbatch_struct(self, shape: Optional[ShapeConfig] = None) -> dict:
        """ShapeDtypeStructs of one fused-round input bundle: per-step
        batches stacked to a leading E dim (scan axis, unsharded)."""
        shape = shape or self.shape
        if shape is None:
            raise ValueError("engine has no ShapeConfig; pass one")
        bs = self.bundle.train_inputs(shape, self.workers)
        e = max(self.cfg.hsadmm.local_steps, 1)
        bsh = self.batch_sharding(bs)
        return {k: jax.ShapeDtypeStruct(
                    (e,) + tuple(v.shape), v.dtype,
                    sharding=NamedSharding(self.mesh, P(None, *bsh[k].spec)))
                for k, v in bs.items()}

    def round_hlo(self, frozen: bool = False,
                  shape: Optional[ShapeConfig] = None) -> str:
        """Compiled-HLO text of the FUSED round executable (AOT lower +
        compile from shape structs — no concrete state needed)."""
        eta = jax.ShapeDtypeStruct((), jnp.float32)
        return self.round_step_fn(frozen).lower(
            self.state_struct(), self.superbatch_struct(shape), eta
        ).compile().as_text()

    def round_collectives(self, frozen: bool = False,
                          shape: Optional[ShapeConfig] = None):
        """Trip-weighted collective schedule of one whole fused round —
        E local steps AND the consensus, as XLA actually scheduled them."""
        from ..dist.hlo_cost import weighted_cost
        txt = self.round_hlo(frozen=frozen, shape=shape)
        wc = weighted_cost(txt, model=self.axes.get("model", 1),
                           data=self.axes.get("data", 1),
                           node=self.consensus.node_size)
        return wc.collectives

    # ------------------------------------------------------------------ #
    # serving shardings
    # ------------------------------------------------------------------ #

    def serve_param_shardings(self):
        key = jax.random.PRNGKey(0)
        p0 = jax.eval_shape(self.bundle.init, key)

        def one(path, leaf):
            pspec = self._param_spec(path, leaf.shape, set())
            return NamedSharding(self.mesh, P(*pspec))
        return _walk(p0, one)

    def serve_cache_shardings(self, B: int, S: int):
        data_axes = [(n, self.axes[n]) for n in ("pod", "data")
                     if n in self.axes]
        specs = self.bundle.cache_specs(B, S, data_axes)
        return jax.tree.map(
            lambda sp: NamedSharding(self.mesh, sp), specs,
            is_leaf=lambda x: isinstance(x, P))


def _get(tree, path):
    node = tree
    for part in path.split("/"):
        node = node[int(part)] if isinstance(node, (list, tuple)) \
            else node[part]
    return node
