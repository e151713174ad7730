"""Orchestration control loop — paper Algorithm 1 / §4.1.4.

The hot path is the FUSED ROUND: one jitted, state-donated executable per
outer iteration that scans the E local prox-SGD steps over a prefetched
``(E, W, ...)`` superbatch and runs the hierarchical consensus (intra-node
AllReduce, projection + mask sync, compact inter-node AllReduce, duals,
adaptive penalties) inside the same trace.  Exactly two executables exist
per run — dynamic and frozen (§4.5 one-shot buffers) — and the loop never
reads the device on the hot path: per-round telemetry comes back as
:class:`repro.core.hsadmm.RoundMetrics` device arrays and is drained in
blocks every ``RunConfig.metrics_every`` rounds (plus once at the end).

Consequences of the async cadence (all bounded by ``metrics_every``):

  * drift-stability mask freezing (§4.5) and the residual stopping rule
    (Alg. 1 l.29) take effect at the next drain boundary — ``t_freeze``
    freezing is host-known and still exact;
  * ``report`` lists are always fully per-round, whatever the cadence.

``RunConfig(fused_rounds=False)`` keeps the legacy per-step dispatch path
(E separate local-step jits + a consensus jit, synced every round) for
equivalence testing and dispatch-overhead benchmarks.

``RunConfig(reconfig=True)`` arms PHYSICAL RECONFIGURATION: once masks
have been frozen for ``reconfig_patience`` rounds, the loop migrates the
entire H-SADMM state onto the budget-B shapes (``Engine.reconfigure``)
and retraces the frozen round executable ONCE over the physically
smaller model — smaller per-step FLOPs and memory, compact payloads at
every fabric level.  Exactly one extra compile happens at the
reconfiguration point; the steady state stays one dispatch per round
with zero recompiles.  Checkpoints after the retrace are saved at the
shrunk shapes with ``meta["reconfigured"]`` and the frozen full-shape
masks in the aux arrays, so resume restores straight into a reconfigured
engine (and ``Engine.expand_reconfigured`` recovers full shapes).

Communication accounting is derived from which executable actually ran
each round: the per-level compaction boundary (``compact_from_level`` or
the codec's ``compact`` marker), the top boundary's wire codec
(``repro.comm`` — ``WireCodec.wire_bytes`` is the one formula shared
with ``plan_bytes`` and the dryrun reports), and — for dynamic rounds
only — the Phase-3 mask-agreement bytes.  The measured counterpart
(compiled-HLO collective schedule, ``dist.hlo``) is reported when
``RunConfig.hlo_stats`` is set.

Run parameters live in one :class:`RunConfig`; the legacy keyword surface
(``train(eng, outer_iters=..., shape=..., ...)``) is a thin wrapper over
it and keeps working.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ShapeConfig
from ..core.hsadmm import flatten, round_metrics
from ..core.shrinkage import mask_sync_bytes, plan_bytes
from ..data.pipeline import batches, prefetch, superbatches
from ..data.synthetic import make_stream
from ..dist import checkpoint as ckpt
from ..dist import hlo
from ..dist.monitor import span
from .engine import Engine


@dataclass(frozen=True)
class RunConfig:
    """Everything one training run needs beyond the engine itself.

    The training loop consumes this single object; launchers build it
    from CLI flags, tests from literals.  ``train`` also accepts the
    historical keyword form and assembles a RunConfig internally.
    """

    outer_iters: int
    shape: ShapeConfig
    eta: float = 1e-3
    seed: int = 0
    # fused round executable (one dispatch per round, state donated);
    # False = legacy per-step dispatch, kept for equivalence tests
    fused_rounds: bool = True
    # drain cadence of the async RoundMetrics stream: residuals/drift/loss
    # are host-read every this many rounds (and at the end), never on the
    # hot path.  1 = legacy synchronous behaviour.
    metrics_every: int = 5
    # checkpointing (dist.checkpoint): atomic + background; resume picks
    # up the newest checkpoint elastically (worker count may differ)
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 10
    ckpt_keep: Optional[int] = None
    resume: bool = True
    # fault tolerance (dist.ft): policy(k, W) -> (W,) consensus weights
    ft_policy: Optional[Callable] = None
    # optional per-iteration evaluation hook: eval_fn(k, state) -> value
    eval_fn: Optional[Callable] = None
    # parse the compiled collective schedule of the executables this run
    # dispatches (fused rounds, or consensus-only under fused_rounds=
    # False) into report.hlo_comm (two extra AOT compiles; off for tests)
    hlo_stats: bool = False
    # per-fabric-level wire-codec specs (repro.comm registry).  When set
    # they override the engine config's hsadmm.wire_intra/wire_inter for
    # this run (the loop rebuilds the engine spec around them).
    wire_intra: Optional[str] = None
    wire_inter: Optional[str] = None
    # explicit per-boundary codec map (one spec per level boundary;
    # e.g. an AdaptiveWireSelector spec_map) — overrides intra/inter
    wire_map: Optional[tuple] = None
    # measurement-driven codec selection (comm.AdaptiveWireSelector) run
    # INSIDE the loop: selects the map on the full-shape engine at
    # start, and RE-selects on the shrunk byte model at the physical
    # reconfiguration point (a map chosen for full shapes is stale once
    # the payloads shrink).  Mutually exclusive with an explicit
    # wire_map.  Both chosen maps land in the report.
    wire_auto: bool = False
    # overlapped-round depth override (HsadmmConfig.staleness): None
    # keeps the engine config's value; 0/1 rebuild the engine at that
    # depth for this run.  staleness >= 1 requires fused_rounds.
    staleness: Optional[int] = None
    # physical reconfiguration: once masks have been frozen for
    # `reconfig_patience` rounds (None = HsadmmConfig.reconfig_patience),
    # migrate the whole state onto budget-B shapes and retrace the frozen
    # round executable once (fused_rounds only)
    reconfig: bool = False
    reconfig_patience: Optional[int] = None
    log: Optional[Callable] = print

    # ------------------------------------------------------------------ #
    # JSON serialization — the repro.tune unlock: a tuner (or any tool)
    # can emit a winning RunConfig as JSON and `launch/train.py
    # --from-json` launches it directly.  Process-local callables
    # (eval_fn, log) are NOT serialized; ft_policy serializes by its
    # canonical dist.ft spec string (factories attach `.spec`).
    # ------------------------------------------------------------------ #

    _JSON_SKIP = ("eval_fn", "log")

    def to_json(self) -> dict:
        """Plain-JSON dict of this run, bit-stable through
        :meth:`from_json` (incl. wire_map and the reconfig fields)."""
        out = {}
        for f in dataclasses.fields(self):
            if f.name in self._JSON_SKIP:
                continue
            v = getattr(self, f.name)
            if f.name == "shape":
                v = dataclasses.asdict(v)
            elif f.name == "ft_policy" and v is not None:
                spec = getattr(v, "spec", None)
                if spec is None:
                    raise ValueError(
                        "RunConfig.ft_policy is not serializable: build "
                        "it through the repro.dist.ft factories (they "
                        "attach a canonical .spec) or ft.from_spec")
                v = spec
            elif f.name == "wire_map" and v is not None:
                v = list(v)
            out[f.name] = v
        return out

    @staticmethod
    def from_json(d: dict) -> "RunConfig":
        """Inverse of :meth:`to_json` (eval_fn/log take their
        defaults).  Unknown keys raise — a config emitted by a newer
        schema should fail loudly, not train a subtly different run."""
        from ..dist import ft as _ft
        d = dict(d)
        shape = ShapeConfig(**d.pop("shape"))
        ft_spec = d.pop("ft_policy", None)
        wm = d.pop("wire_map", None)
        known = {f.name for f in dataclasses.fields(RunConfig)
                 if f.name not in RunConfig._JSON_SKIP + ("shape",
                                                          "ft_policy",
                                                          "wire_map")}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown RunConfig JSON keys: "
                             f"{sorted(unknown)}")
        return RunConfig(
            shape=shape,
            ft_policy=_ft.from_spec(ft_spec) if ft_spec else None,
            wire_map=tuple(wm) if wm is not None else None, **d)


@dataclass
class TrainReport:
    losses: list = field(default_factory=list)
    drifts: list = field(default_factory=list)
    r_primal: list = field(default_factory=list)
    s_dual: list = field(default_factory=list)
    comm_bytes_internode: list = field(default_factory=list)
    comm_bytes_dense_equiv: list = field(default_factory=list)
    wall_times: list = field(default_factory=list)
    evals: list = field(default_factory=list)
    # which executable ran each round: "dynamic" | "frozen" |
    # "reconfigured" (the retraced frozen round on shrunk shapes)
    executables: list = field(default_factory=list)
    frozen_at: Optional[int] = None
    # first round dispatched on the reconfigured executable (None if the
    # run never physically reconfigured)
    reconfigured_at: Optional[int] = None
    outer_iters: int = 0
    # codec spec per level boundary the run's consensus actually routed
    # through (innermost first; None for solo engines) — reflects
    # wire_map / --wire-auto selection as well as intra/inter knobs
    wire_map: Optional[list] = None
    # codec map of the RECONFIGURED engine's consensus (None until a
    # physical reconfiguration): re-derived on the shrunk byte model
    # when RunConfig.wire_auto, otherwise the carried-over map — so a
    # report always shows which map each phase actually routed through
    wire_map_reconfigured: Optional[list] = None
    # measured collective schedule per executable (dist.hlo), keyed
    # "dynamic"/"frozen" (+"reconfigured" after a retrace); None unless
    # RunConfig.hlo_stats
    hlo_comm: Optional[dict] = None
    # the engine that dispatched the LAST round — the reconfigured engine
    # after a retrace (its bundle is the shrunk model; feed it to
    # launch.serve.serving_bundle_from_state / expand_reconfigured).
    # Not JSON-serializable; launchers drop it from report dumps.
    final_engine: Optional[object] = field(default=None, repr=False)


def _param_shapes(engine: Engine) -> dict:
    p0 = jax.eval_shape(engine.bundle.init, jax.random.PRNGKey(0))
    return {k: tuple(v.shape) for k, v in flatten(p0).items()}


def _plan_volume(shapes: dict, engine: Engine, codec) -> tuple[int, int]:
    return plan_bytes(shapes, engine.bundle.plan, engine.spec.budgets,
                      engine.bundle.cfg.param_dtype, codec=codec)


def comm_volume(engine: Engine, wire: bool = True) -> tuple[int, int]:
    """(dense, compact) inter-node payload bytes per consensus round, per
    node — analytic accounting from the sparsity plan through the
    engine's top-boundary :class:`repro.comm.WireCodec`.  ``wire=True``
    counts the *effective* wire format (q8 ships 1-byte elements +
    per-group scales, topk ships value+index entries); ``wire=False``
    counts param-dtype (dense-codec) equivalents.  The measured
    counterpart (actual XLA schedule) is ``engine.consensus_hlo`` +
    ``dist.hlo.collective_stats``."""
    codec = engine.spec.codecs[-1] if wire and not engine.spec.solo \
        else "dense"
    return _plan_volume(_param_shapes(engine), engine, codec)


def round_comm_bytes(engine: Engine) -> tuple[int, int, int]:
    """(dense_equiv, dynamic_bytes, frozen_bytes) per round, derived from
    the executables the loop actually runs — NOT a round-index heuristic:

      * the top-level (slow fabric) boundary ships the statically-compact
        buffer iff ``compact_from_level`` covers it or the codec spec
        carries the ``compact`` marker (neither does in the flat
        PruneX(AR) ablation, whose payload is honestly dense);
      * bytes come from the top boundary's ``WireCodec.wire_bytes`` —
        the same codec the consensus executable actually routes that
        exchange through (``spec.codecs[-1]``; the flat K=1,
        compact_from_level>=1 ablation resolves to the intra codec, so
        legacy ``comm_quant``/``wire_inter`` never touch it);
      * dynamic rounds add the Phase-3 mask-agreement bytes; frozen
        rounds (§4.5) skip mask sync entirely;
      * solo engines have no consensus exchange at all.
    """
    shapes = _param_shapes(engine)
    dense_eq, _ = _plan_volume(shapes, engine, "dense")
    if engine.spec.solo:
        return dense_eq, 0, 0
    codecs = engine.spec.codecs
    top = codecs[-1]
    dense_w, compact_w = _plan_volume(shapes, engine, top)
    base = compact_w if engine.spec.boundary_compact(len(codecs), codecs) \
        else dense_w
    mask_b = mask_sync_bytes(shapes, engine.bundle.plan,
                             engine.cfg.hsadmm.mask_mode)
    return dense_eq, base + mask_b, base


def _hlo_comm_report(engine: Engine, state, run: "RunConfig") -> dict:
    """Measured collective schedule (trip-weighted) of the executables
    this run actually dispatches: the FUSED round executables (E local
    steps + consensus in one program) by default, the consensus-only
    executables under ``fused_rounds=False``."""
    out = {}
    for name, frozen in (("dynamic", False), ("frozen", True)):
        if run.fused_rounds:
            colls = engine.round_collectives(frozen=frozen, shape=run.shape)
        else:
            colls = engine.consensus_collectives(state, frozen=frozen)
        out[name] = _hlo_entry(colls)
    return out


def _masks_aux(masks: dict, plan) -> dict:
    """Frozen full-shape mask state as flat checkpoint aux arrays."""
    flat = {}
    for r in plan.rules:
        for f, v in masks[r.name].items():
            flat[f"masks/{r.name}/{f}"] = jax.device_get(v)
    return flat


def _masks_from_aux(aux: dict, plan) -> dict:
    return {r.name: {f: jnp.asarray(aux[f"masks/{r.name}/{f}"])
                     for f in ("idx", "valid", "mask", "drift")}
            for r in plan.rules}


def _hlo_entry(colls) -> dict:
    return {"summary": hlo.summarize(colls),
            "axis_bytes": hlo.axis_bytes(colls),
            "internode_bytes": hlo.internode_bytes(colls)}


def train(engine: Engine, run: Optional[RunConfig] = None, *,
          shape: Optional[ShapeConfig] = None,
          **legacy_kw) -> tuple[dict, TrainReport]:
    """Run the full H-SADMM training loop on the engine's mesh.

    New surface: ``train(engine, RunConfig(...))``.  Legacy surface:
    ``train(engine, outer_iters=..., shape=..., eta=..., ...)`` — the
    keywords are exactly RunConfig's fields.
    """
    if run is None:
        run = RunConfig(shape=shape, **legacy_kw)
    else:
        if shape is not None:
            legacy_kw["shape"] = shape
        if legacy_kw:
            run = dataclasses.replace(run, **legacy_kw)
    return _train(engine, run)


def _train(engine: Engine, run: RunConfig) -> tuple[dict, TrainReport]:
    log = run.log
    if run.wire_auto and run.wire_map:
        raise ValueError("RunConfig.wire_auto and an explicit wire_map "
                         "are mutually exclusive")
    if run.wire_intra or run.wire_inter or run.wire_map:
        engine = engine.with_wire(run.wire_intra, run.wire_inter,
                                  run.wire_map)
    if run.wire_auto and not engine.spec.solo:
        from ..comm.select import AdaptiveWireSelector
        sel = AdaptiveWireSelector().select(engine)
        engine = sel.apply(engine)
        if log:
            log("[loop] wire-auto selected " + sel.to_json())
    if run.staleness is not None \
            and run.staleness != engine.cfg.hsadmm.staleness:
        engine = engine.with_staleness(run.staleness)
    staleness = engine.cfg.hsadmm.staleness
    if staleness and not run.fused_rounds:
        raise ValueError(
            "staleness >= 1 requires fused_rounds=True: the overlap "
            "lives inside the fused round executable (the legacy "
            "per-step path has no pipeline to overlap)")
    per_class = run.ft_policy is not None \
        and getattr(run.ft_policy, "per_class", False)
    if per_class and not engine.class_weights:
        engine = engine.with_class_weights(True)
        if log:
            log("[loop] class-scoped ft policy: enabled per-class "
                "consensus weights")
    if per_class:
        rule_names = {r.name for r in engine.bundle.plan.rules}
        unknown = set(run.ft_policy.class_weights(0, engine.workers)) \
            - rule_names
        if unknown:
            raise ValueError(
                f"class-scoped ft policy names unknown coupling classes "
                f"{sorted(unknown)}; plan has {sorted(rule_names)}")
    cfg = engine.cfg
    hp = cfg.hsadmm
    E = max(hp.local_steps, 1)
    stream = make_stream(cfg, run.shape, engine.workers)
    base_it = batches(stream, engine.bundle.extra_inputs, run.shape)
    if run.fused_rounds:
        it = prefetch(superbatches(base_it, E))
        round_dyn = engine.round_step_fn(frozen=False)
        round_frz = engine.round_step_fn(frozen=True)
    else:
        it = prefetch(base_it)
        local_fn = engine.local_step_fn()
        cons_dyn = engine.consensus_step_fn(frozen=False)
        cons_frz = engine.consensus_step_fn(frozen=True)

    if run.reconfig and not run.fused_rounds:
        raise ValueError("RunConfig.reconfig requires fused_rounds=True "
                         "(the retrace targets the fused round executable)")
    patience = run.reconfig_patience if run.reconfig_patience is not None \
        else hp.reconfig_patience
    rc_engine = None   # the reconfigured engine once the retrace happened

    state = None
    start_k = 0
    if run.ckpt_dir and run.resume:
        last = ckpt.latest(run.ckpt_dir)
        if last is not None:
            restore_eng = engine
            if ckpt.read_meta(last).get("reconfigured"):
                # the save is at shrunk shapes: rebuild the reconfigured
                # engine from the aux masks and restore straight into it
                masks_full = _masks_from_aux(ckpt.load_aux(last),
                                             engine.bundle.plan)
                rc_engine, _ = engine.reconfigure(masks=masks_full)
                if run.wire_auto and not rc_engine.spec.solo:
                    # the start-of-run selection above saw full shapes;
                    # re-select on the shrunk byte model this session
                    # actually dispatches
                    from ..comm.select import AdaptiveWireSelector
                    sel2 = AdaptiveWireSelector().select(rc_engine)
                    rc_engine = sel2.apply(rc_engine)
                restore_eng = rc_engine
            tmpl = jax.eval_shape(
                lambda: restore_eng.init_state_fn()(
                    jax.random.PRNGKey(run.seed)))
            tmpl = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tmpl)
            state, meta = ckpt.restore_elastic(last, tmpl, engine.workers)
            # restored leaves are host arrays: lay them out on the
            # engine's canonical shardings, or the donated round
            # executable's input/output aliasing disagrees on >1 device
            state = jax.device_put(state, restore_eng.state_shardings())
            start_k = int(meta["step"])
            if rc_engine is not None:
                if not run.fused_rounds:
                    raise ValueError(
                        f"checkpoint {last} was saved by a reconfigured "
                        "run; resuming it needs fused_rounds=True")
                round_frz = rc_engine.round_step_fn(frozen=True)
            if log:
                log(f"[loop] resumed from {last} at outer iter {start_k}"
                    + (" (reconfigured)" if rc_engine is not None else ""))
    if state is None:
        state = engine.init_state_fn()(jax.random.PRNGKey(run.seed))

    dense_eq_b, dyn_b, frz_b = round_comm_bytes(engine)
    if rc_engine is not None:
        _, _, frz_b = round_comm_bytes(rc_engine)
    report = TrainReport()
    report.wire_map = None if engine.spec.solo \
        else [c.name for c in engine.spec.codecs]
    if rc_engine is not None and not rc_engine.spec.solo:
        report.wire_map_reconfigured = \
            [c.name for c in rc_engine.spec.codecs]
    if run.hlo_stats:
        if rc_engine is not None:
            # reconfigured resume: the full-shape executables never
            # dispatch this session — don't pay their AOT compiles
            report.hlo_comm = {"reconfigured": _hlo_entry(
                rc_engine.round_collectives(frozen=True, shape=run.shape))}
        else:
            report.hlo_comm = _hlo_comm_report(engine, state, run)

    frozen = rc_engine is not None   # a reconfigured resume is frozen
    if frozen:
        report.frozen_at = start_k
        report.reconfigured_at = start_k
    stop = False
    eta = jnp.float32(run.eta)
    metrics_every = max(run.metrics_every, 1) if run.fused_rounds else 1
    pending: list = []   # [(k, was_frozen, RoundMetrics-on-device)]
    t_block = time.time()
    host_overhead = 0.0  # ckpt/eval host time, excluded from round walls

    def drain():
        """Read all pending RoundMetrics in one host sync; update the
        report and the drift-freeze / convergence decisions.  The sync
        forces every pending round's device compute, so wall time is
        attributed here: elapsed-since-last-drain (minus measured
        ckpt/eval host overhead) spread evenly over the drained rounds
        (async dispatch alone would time ~nothing)."""
        nonlocal frozen, stop, t_block, host_overhead
        if not pending:
            return
        with span("repro.round.drain"):
            vals = jax.device_get([m for (_, _, m) in pending])
        per_round = max(time.time() - t_block - host_overhead, 0.0) \
            / len(pending)
        report.wall_times.extend([per_round] * len(pending))
        for (k, was_frozen, _), m in zip(pending, vals):
            loss = float(np.reshape(m.losses, -1)[-1])  # last local step
            drift = 0.0 if was_frozen else float(m.drift)
            report.losses.append(loss)
            report.drifts.append(drift)
            report.r_primal.append(float(m.r_primal))
            report.s_dual.append(float(m.s_dual))
            if not frozen and k > 2 and drift == 0.0:
                frozen = True                       # §4.5 drift stability
                if report.frozen_at is None:
                    # first round the FROZEN executable actually runs —
                    # rounds dispatched between stability and this drain
                    # ran dynamic, and the report must say so
                    report.frozen_at = report.outer_iters
                if log:
                    log("[loop] masks frozen at outer iter "
                        f"{report.frozen_at}")
            if bool(m.converged):
                stop = True
                if log:
                    log(f"[loop] converged at outer iter {k + 1}")
            if log and (k % 5 == 0 or k == run.outer_iters - 1):
                log(f"[loop] k={k:3d} loss={loss:.4f} "
                    f"r={report.r_primal[-1]:.3e} drift={drift:.0f}")
        pending.clear()
        host_overhead = 0.0
        t_block = time.time()

    for k in range(start_k, run.outer_iters):
        if run.reconfig and frozen and rc_engine is None \
                and report.frozen_at is not None \
                and k - report.frozen_at >= patience:
            # masks stable for `patience` frozen rounds: migrate the whole
            # state onto budget-B shapes and retrace the frozen round ONCE
            drain()
            if stop:
                break   # converged in the drained block: skip the retrace
            with span("repro.round.reconfigure"):
                t_r = time.time()
                if staleness:
                    # drain the in-flight consensus before migrating: the
                    # overlapped state still carries one un-reduced theta,
                    # and the shrunk plan must migrate a buffer the frozen
                    # masks actually describe — not a pending one
                    state, _ = engine.flush_pipeline_fn(frozen=True)(state)
                rc_engine, state = engine.reconfigure(state)
                if run.wire_auto and not rc_engine.spec.solo:
                    # the start-of-run selection saw full-shape payloads;
                    # re-select on the shrunk byte model (satellite: a map
                    # chosen for full shapes is stale after the retrace)
                    from ..comm.select import AdaptiveWireSelector
                    sel2 = AdaptiveWireSelector().select(rc_engine)
                    rc_engine = sel2.apply(rc_engine)
                    if not any(c.stateful for c in rc_engine.spec.codecs) \
                            and "wire" in state:
                        # the reselected candidates are all stateless: the
                        # old codec's error-feedback buffers are meaningless
                        # under the new map — drop them so the state matches
                        # the reselected engine's structure
                        state = {k2: v for k2, v in state.items()
                                 if k2 != "wire"}
                    if log:
                        log("[loop] wire-auto reselected on shrunk shapes: "
                            + sel2.to_json())
                if not rc_engine.spec.solo:
                    report.wire_map_reconfigured = \
                        [c.name for c in rc_engine.spec.codecs]
                round_frz = rc_engine.round_step_fn(frozen=True)
                _, _, frz_b = round_comm_bytes(rc_engine)
                report.reconfigured_at = k
                if report.hlo_comm is not None:
                    report.hlo_comm["reconfigured"] = _hlo_entry(
                        rc_engine.round_collectives(frozen=True,
                                                    shape=run.shape))
                if log:
                    log(f"[loop] physically reconfigured at outer iter {k}: "
                        f"frozen-round payload {frz_b/1e6:.2f}MB/round")
                host_overhead += time.time() - t_r   # migration is host-timed;
                # the one retrace compile lands in the next round's wall time
        if run.ft_policy is not None:
            w = run.ft_policy(k, engine.workers)
            state = dict(state, weights=jnp.asarray(w, jnp.float32))
            if per_class:
                cw = dict(state["class_weights"])
                for name, v in run.ft_policy.class_weights(
                        k, engine.workers).items():
                    cw[name] = jnp.asarray(v, jnp.float32)
                state["class_weights"] = cw
        was_frozen = frozen
        if run.fused_rounds:
            sb = next(it)
            with span("repro.round.dispatch"):
                state, m = (round_frz if frozen else round_dyn)(
                    state, sb, eta)
        else:
            loss = None
            for _ in range(E):                      # Phase 1 (legacy path)
                batch = next(it)
                with span("repro.round.dispatch"):
                    state, loss = local_fn(state, batch, eta)
            with span("repro.round.dispatch"):
                state, info = (cons_frz if frozen else cons_dyn)(state)
            m = round_metrics(state, info, loss, engine.spec)
        pending.append((k, was_frozen, m))
        report.executables.append(
            "reconfigured" if (was_frozen and rc_engine is not None)
            else ("frozen" if was_frozen else "dynamic"))
        report.comm_bytes_internode.append(frz_b if was_frozen else dyn_b)
        report.comm_bytes_dense_equiv.append(dense_eq_b)
        report.outer_iters = k + 1
        if run.eval_fn is not None:
            t_e = time.time()
            report.evals.append(run.eval_fn(k, state))
            host_overhead += time.time() - t_e

        if not frozen and k + 1 >= hp.t_freeze:
            frozen = True                           # §4.5 schedule freezing
            report.frozen_at = k + 1
            if log:
                log(f"[loop] masks frozen at outer iter {k + 1}")

        if (k + 1) % metrics_every == 0 or k == run.outer_iters - 1:
            drain()
        if run.ckpt_dir and run.ckpt_every > 0 \
                and (k + 1) % run.ckpt_every == 0:
            drain()   # attribute pending compute before the host transfer
            with span("repro.round.checkpoint"):
                t_c = time.time()
                ckpt.save(run.ckpt_dir, jax.device_get(state),
                          {"step": k + 1, "arch": cfg.name,
                           "workers": engine.workers,
                           "levels": list(engine.consensus.levels),
                           "reconfigured": rc_engine is not None},
                          keep=run.ckpt_keep, background=True,
                          aux=_masks_aux(rc_engine.frozen_masks,
                                         engine.bundle.plan)
                          if rc_engine is not None else None)
            host_overhead += time.time() - t_c
        if stop:
            break
    drain()
    report.final_engine = rc_engine if rc_engine is not None else engine
    if run.ckpt_dir:
        ckpt.flush()   # background saves are durable once train() returns
    return state, report
