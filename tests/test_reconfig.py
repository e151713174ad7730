"""Physical reconfiguration (Engine.reconfigure / RunConfig.reconfig):
the differential conformance suite.

Once masks freeze, the run migrates its ENTIRE H-SADMM state onto the
budget-B shapes and retraces the fused round over the physically smaller
model.  The claim proved here: with masks frozen, the reconfigured
engine's round is the SAME algorithm as the full-shape masked round —
per-round losses, residuals and (expanded) parameters agree to tolerance
across every consensus hierarchy and wire codec — while the executable
keeps the fused-round guarantees (1 dispatch/round, exactly one extra
compile at the reconfiguration point, zero steady-state compiles) and the
measured collective bytes shrink at every fabric level.  The same claims
for the CNN and MoE families are in test_reconfig_cnn.py and
test_reconfig_moe.py, which import this file's helpers.

The ``WIRE_CODEC`` env var (CI codec-matrix job) swaps the default
top-boundary codec for the loop-level guards; the conformance matrix
pins its codecs explicitly.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ConsensusSpec, HsadmmConfig, ShapeConfig
from repro.core import (EngineSpec, compact_state, expand_state, get_leaf,
                        identity_mask_state, init_state, leaf_keys,
                        shrunk_plan)
from repro.core.sparsity import GroupRule, LeafAxis, SparsityPlan
from repro.data.pipeline import batches, superbatches
from repro.data.synthetic import make_stream
from repro.dist import checkpoint as ckpt
from repro.dist import monitor
from repro.launch.mesh import make_host_mesh
from repro.models import build
from repro.train.engine import Engine
from repro.train.loop import (RunConfig, _masks_aux, _masks_from_aux, train)

SHAPE = ShapeConfig("tiny", "train", 32, 8)
E = 2
ETA = jnp.float32(3e-3)

HIERARCHIES = {
    "chip": ((2, 2), 1, "chip"),   # compact from the node boundary
    "pod":  ((2, 2), 0, "pod"),    # compact from the very first boundary
    "flat": ((4,), 1, "flat"),     # PruneX(AR) ablation: dense AllReduce
}


def _engine(hier="chip", wire_inter=None, t_freeze=2, patience=1,
            use_env_codec=False):
    levels, kc, gran = HIERARCHIES[hier]
    wire = wire_inter if wire_inter is not None \
        else (os.environ.get("WIRE_CODEC") if use_env_codec else None)
    cfg = get_config("tinyllama-1.1b", smoke=True).replace(
        hsadmm=HsadmmConfig(rho1=1e-2, rho2=1e-3, local_steps=E,
                            t_freeze=t_freeze, reconfig_patience=patience,
                            wire_inter=wire))
    return Engine(build(cfg), make_host_mesh(), SHAPE,
                  consensus=ConsensusSpec(levels=levels,
                                          compact_from_level=kc,
                                          granularity=gran))



def _superbatch_iter(eng):
    stream = make_stream(eng.cfg, SHAPE, eng.workers)
    return superbatches(batches(stream, eng.bundle.extra_inputs, SHAPE), E)


def _frozen_state(eng, it, dyn_rounds=2):
    """Init + a few dynamic rounds + one frozen round -> settled masks."""
    state = eng.init_state_fn()(jax.random.PRNGKey(0))
    rdyn = eng.round_step_fn(frozen=False)
    rfrz = eng.round_step_fn(frozen=True)
    for _ in range(dyn_rounds):
        state, _ = rdyn(state, next(it), ETA)
    state, _ = rfrz(state, next(it), ETA)
    return state, rfrz


def _assert_trees_close(a, b, rtol=5e-4, atol=1e-5):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# the differential conformance matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("hier", sorted(HIERARCHIES))
@pytest.mark.parametrize("codec", ["dense", "q8", "compact+q8", "topk:0.01"])
def test_reconfigured_round_matches_full_shape(hier, codec):
    """Under frozen masks, N rounds on the reconfigured engine equal N
    rounds of the full-shape masked round from the identical (projected)
    state: per-round losses, residuals, and the zero-fill-expanded
    parameters all agree.  The full-shape reference is
    ``expand_reconfigured(migrated_state)`` — the run's own projection
    onto the frozen kept-set, which the full-shape frozen round preserves
    exactly (dropped groups have zero value AND zero gradient)."""
    eng = _engine(hier, wire_inter=codec)
    it = _superbatch_iter(eng)
    state, rfrz = _frozen_state(eng, it)

    eng2, st_c = eng.reconfigure(state)
    st_ref = eng2.expand_reconfigured(st_c)
    rfrz2 = eng2.round_step_fn(frozen=True)

    for _ in range(3):
        sb = next(it)
        st_ref, m_ref = rfrz(st_ref, sb, ETA)
        st_c, m_c = rfrz2(st_c, sb, ETA)
        np.testing.assert_allclose(np.asarray(m_c.losses),
                                   np.asarray(m_ref.losses),
                                   rtol=5e-4, atol=1e-5)
        np.testing.assert_allclose(float(m_c.r_primal),
                                   float(m_ref.r_primal),
                                   rtol=2e-3, atol=1e-5)
        np.testing.assert_allclose(float(m_c.s_dual), float(m_ref.s_dual),
                                   rtol=2e-3, atol=1e-5)
        assert float(m_c.drift) == 0.0

    full2 = eng2.expand_reconfigured(st_c)
    for grp in ("theta", "u", "mom"):
        _assert_trees_close(full2[grp], st_ref[grp])
    for zf, zr in zip(full2["z"], st_ref["z"]):
        _assert_trees_close(zf, zr)
    for rf, rr in zip(full2["rho"], st_ref["rho"]):
        _assert_trees_close(rf, rr, rtol=2e-3)


def test_reconfigured_shapes_are_budget_B():
    eng = _engine("chip")
    it = _superbatch_iter(eng)
    state, _ = _frozen_state(eng, it)
    eng2, st_c = eng.reconfigure(state)
    ffn = eng.bundle.plan.rule("ffn")
    B = eng.spec.budgets["ffn"]
    assert eng2.cfg.d_ff == B < eng.cfg.d_ff
    assert eng2.bundle.plan.rule("ffn").groups == B
    assert st_c["theta"]["blocks"]["mlp"]["wg"].shape[-1] == B
    for z in st_c["z"]:
        assert z["blocks"]["mlp"]["wd"].shape[-2] == B
    assert ffn.groups == eng.cfg.d_ff  # parent untouched


def test_moe_expert_keep_below_top_k_refused():
    """An expert keep budget smaller than moe_top_k can never route —
    the plan refuses at construction, naming both numbers."""
    cfg = get_config("qwen2-moe-a2.7b", smoke=True).replace(
        hsadmm=HsadmmConfig(keep_rate=0.2))         # keep_count(8,.2,2)=2
    cfg = cfg.replace(moe_top_k=4)
    with pytest.raises(ValueError, match="moe_top_k"):
        build(cfg)


def test_legacy_dff_shortcut_refuses_stacked_rules():
    """Satellite regression: a family WITHOUT its own shrink_config
    (ssm) falling back to the legacy strict=False d_ff shortcut must
    refuse a first ffn* rule stacked over (layer, expert) axes instead
    of silently collapsing the per-instance budgets onto one global
    d_ff."""
    from repro.models import shrink_config
    cfg = get_config("mamba2-780m", smoke=True)
    plan = SparsityPlan((
        GroupRule("ffn_experts", (LeafAxis("blocks/moe/we_g", 3),),
                  groups=16, keep=8, stack_ndims=2),))
    with pytest.raises(ValueError, match="ffn_experts"):
        shrink_config(cfg, plan, {"ffn_experts": 8}, strict=False)
    # a flat (unstacked) ffn* rule still takes the legacy shortcut
    flat = SparsityPlan((
        GroupRule("ffn", (LeafAxis("blocks/mlp/wg", 1),),
                  groups=16, keep=8, stack_ndims=1),))
    assert shrink_config(cfg, flat, {"ffn": 8},
                         strict=False).d_ff == 8


# ---------------------------------------------------------------------------
# S_f ∩ S_c: rules composing across axes of the SAME leaf (state-level)
# ---------------------------------------------------------------------------


def test_migrate_expand_composes_rules_across_axes():
    """compact_state/expand_state compose a filter rule (S_f, axis 1) and
    a channel rule (S_c, axis 0) on the same leaf: the migrated leaf is
    (B_c, B_f) and the round-trip equals projection onto the kept set."""
    W, Cin, Cout = 4, 8, 12
    key = jax.random.PRNGKey(0)
    params0 = {"w": jax.random.normal(key, (Cin, Cout))}
    plan = SparsityPlan((
        GroupRule("f", (LeafAxis("w", 1),), groups=Cout, keep=6,
                  stack_ndims=0),
        GroupRule("c", (LeafAxis("w", 0),), groups=Cin, keep=4,
                  stack_ndims=0),
    ))
    spec = EngineSpec(plan=plan,
                      consensus=ConsensusSpec(levels=(2, 2),
                                              compact_from_level=1),
                      hp=HsadmmConfig(rho1=1.0, rho2=1.0))
    state = init_state(params0, spec)
    state["theta"] = {"w": jax.random.normal(jax.random.fold_in(key, 1),
                                             (W, Cin, Cout))}
    idx_f = jnp.asarray([0, 2, 3, 7, 8, 11], jnp.int32)
    idx_c = jnp.asarray([1, 2, 5, 6], jnp.int32)
    masks = {
        "f": {"idx": idx_f, "valid": jnp.ones((6,), jnp.float32),
              "mask": jnp.zeros((Cout,)).at[idx_f].set(1.0),
              "drift": jnp.zeros((), jnp.float32)},
        "c": {"idx": idx_c, "valid": jnp.ones((4,), jnp.float32),
              "mask": jnp.zeros((Cin,)).at[idx_c].set(1.0),
              "drift": jnp.zeros((), jnp.float32)},
    }
    state["masks"] = masks
    budgets = spec.budgets
    new_plan = shrunk_plan(plan, budgets)
    assert new_plan.rule("f").groups == 6 and new_plan.rule("c").groups == 4
    idxs = {r.name: masks[r.name]["idx"] for r in plan.rules}
    new_masks = {r.name: identity_mask_state(r, (), budgets[r.name])
                 for r in new_plan.rules}
    st_c = compact_state(state, plan, idxs, new_masks,
                         (spec.boundary_compact(1),
                          spec.boundary_compact(2)))
    assert st_c["theta"]["w"].shape == (W, 4, 6)
    assert st_c["z"][0]["w"].shape == (2, 4, 6)
    fulls = {r.name: r.groups for r in plan.rules}
    st_f = expand_state(st_c, plan, idxs, fulls, masks)
    proj = np.asarray(state["theta"]["w"]) \
        * np.asarray(masks["c"]["mask"])[None, :, None] \
        * np.asarray(masks["f"]["mask"])[None, None, :]
    np.testing.assert_allclose(np.asarray(st_f["theta"]["w"]), proj,
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# fused-round guards, extended to the reconfigured executable
# ---------------------------------------------------------------------------


def test_reconfig_loop_one_dispatch_per_round(monkeypatch):
    """Through the REAL training loop with reconfig armed: still one
    dispatch per round, from exactly THREE executables (dynamic, frozen
    full-shape, frozen reconfigured), switching at frozen_at and at
    frozen_at + patience."""
    counts = monitor.CallCounter()
    real_round = Engine.round_step_fn

    def patched(self, frozen):
        label = "reconfigured" if self.reconfigured \
            else ("frozen" if frozen else "dynamic")
        return counts.wrap(real_round(self, frozen), label)

    monkeypatch.setattr(Engine, "round_step_fn", patched)
    eng = _engine("chip", t_freeze=2, patience=1, use_env_codec=True)
    _, rep = train(eng, RunConfig(outer_iters=6, shape=SHAPE, eta=3e-3,
                                  reconfig=True, metrics_every=10, log=None))
    assert counts.calls == 6                      # 1 dispatch per round
    assert counts.by_label == {"dynamic": 2, "frozen": 1,
                               "reconfigured": 3}
    assert rep.executables == ["dynamic"] * 2 + ["frozen"] \
        + ["reconfigured"] * 3
    assert rep.frozen_at == 2 and rep.reconfigured_at == 3
    assert len(rep.losses) == 6                   # metrics continuity
    assert rep.final_engine.reconfigured


def test_exactly_one_retrace_then_zero_steady_state_compiles():
    """The reconfiguration point costs exactly TWO executable builds (the
    one-time state migration + the ONE retraced round); afterwards the
    steady state compiles nothing."""
    eng = _engine("chip", use_env_codec=True)
    it = _superbatch_iter(eng)
    state, _ = _frozen_state(eng, it)
    jax.block_until_ready(state)
    with monitor.compile_count() as at_reconfig:
        eng2, st = eng.reconfigure(state)
        rfn2 = eng2.round_step_fn(frozen=True)
        st, _ = rfn2(st, next(it), ETA)
        jax.block_until_ready(st)
    assert at_reconfig.compiles == 2
    with monitor.compile_count() as steady:
        for _ in range(3):
            st, _ = rfn2(st, next(it), ETA)
        jax.block_until_ready(st)
    assert steady.compiles == 0


# ---------------------------------------------------------------------------
# cross-shape checkpointing
# ---------------------------------------------------------------------------


def test_ckpt_full_to_reconfigured_and_back(tmp_path):
    """save full -> restore -> reconfigure; save reconfigured (meta flag
    + aux masks) -> rebuild engine from aux -> restore -> expand to full:
    both directions land on the same state."""
    eng = _engine("chip")
    it = _superbatch_iter(eng)
    state, _ = _frozen_state(eng, it)

    d1 = str(tmp_path / "full")
    ckpt.save(d1, jax.device_get(state), {"step": 3})
    tmpl = jax.eval_shape(
        lambda: eng.init_state_fn()(jax.random.PRNGKey(0)))
    tmpl = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tmpl)
    st_full, meta = ckpt.restore(ckpt.latest(d1), tmpl)
    assert not meta.get("reconfigured", False)
    eng2, st_c = eng.reconfigure(st_full)

    d2 = str(tmp_path / "rec")
    ckpt.save(d2, jax.device_get(st_c),
              {"step": 4, "reconfigured": True},
              aux=_masks_aux(eng2.frozen_masks, eng.bundle.plan))
    last = ckpt.latest(d2)
    assert ckpt.read_meta(last)["reconfigured"]

    eng_b = _engine("chip")
    masks = _masks_from_aux(ckpt.load_aux(last), eng_b.bundle.plan)
    eng2b, none = eng_b.reconfigure(masks=masks)
    assert none is None
    tmpl_c = jax.eval_shape(
        lambda: eng2b.init_state_fn()(jax.random.PRNGKey(0)))
    tmpl_c = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tmpl_c)
    st_c2, _ = ckpt.restore(last, tmpl_c)
    _assert_trees_close(st_c2, st_c, rtol=1e-6)
    st_round_trip = eng2b.expand_reconfigured(st_c2)
    _assert_trees_close(st_round_trip, eng2.expand_reconfigured(st_c),
                        rtol=1e-6)


def test_restore_elastic_into_reconfigured(tmp_path):
    """restore_elastic seeds a NEW worker joining a reconfigured run from
    the global consensus z at the SHRUNK shapes, with zeroed duals."""
    eng = _engine("chip")                          # W = 4, levels (2, 2)
    it = _superbatch_iter(eng)
    state, _ = _frozen_state(eng, it)
    eng2, st_c = eng.reconfigure(state)
    d = str(tmp_path)
    ckpt.save(d, jax.device_get(st_c), {"step": 3, "reconfigured": True},
              aux=_masks_aux(eng2.frozen_masks, eng.bundle.plan))

    cfg8 = eng.cfg
    eng8 = Engine(build(cfg8), eng.mesh, SHAPE,
                  consensus=ConsensusSpec(levels=(2, 4),
                                          compact_from_level=1,
                                          granularity="chip"))   # W = 8
    eng8r, _ = eng8.reconfigure(masks=eng2.frozen_masks)
    tmpl = jax.eval_shape(
        lambda: eng8r.init_state_fn()(jax.random.PRNGKey(0)))
    tmpl = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tmpl)
    st8, _ = ckpt.restore_elastic(ckpt.latest(d), tmpl, 8)

    wg_old = np.asarray(st_c["theta"]["blocks"]["mlp"]["wg"])
    wg_new = np.asarray(st8["theta"]["blocks"]["mlp"]["wg"])
    B = eng.spec.budgets["ffn"]
    assert wg_new.shape[-1] == B                  # shrunk shapes
    np.testing.assert_array_equal(wg_new[:4], wg_old)   # survivors keep theta
    gz = np.asarray(st_c["z"][-1]["blocks"]["mlp"]["wg"])[0]
    for j in range(4, 8):                         # new workers: global z
        np.testing.assert_allclose(wg_new[j], gz, rtol=1e-6)
    assert np.all(np.asarray(st8["u"]["blocks"]["mlp"]["wg"])[4:] == 0.0)
    assert np.all(np.asarray(st8["weights"]) == 1.0)


def test_loop_resume_into_reconfigured_run(tmp_path):
    """A fresh engine resuming a reconfigured run's checkpoint restores
    straight into the shrunk shapes and keeps running the reconfigured
    executable."""
    d = str(tmp_path)
    eng = _engine("chip", t_freeze=2, patience=1, use_env_codec=True)
    run = RunConfig(outer_iters=6, shape=SHAPE, eta=3e-3, reconfig=True,
                    ckpt_dir=d, ckpt_every=3, metrics_every=2, log=None)
    st, rep = train(eng, run)
    assert rep.reconfigured_at == 3
    eng_b = _engine("chip", t_freeze=2, patience=1, use_env_codec=True)
    st2, rep2 = train(eng_b, RunConfig(outer_iters=8, shape=SHAPE,
                                       eta=3e-3, reconfig=True, ckpt_dir=d,
                                       ckpt_every=3, metrics_every=2,
                                       log=None))
    assert rep2.executables == ["reconfigured"] * 2
    assert rep2.reconfigured_at == 6
    B = eng_b.spec.budgets["ffn"]
    assert st2["theta"]["blocks"]["mlp"]["wg"].shape[-1] == B
    assert rep2.final_engine.reconfigured


# ---------------------------------------------------------------------------
# serve export: no round-trip expansion
# ---------------------------------------------------------------------------


def test_serve_export_from_reconfigured_state():
    """Exporting a serving bundle from a reconfigured run is a lead-dim
    squeeze of the compact consensus z — and equals the export of the
    expanded full-shape state through the masked path."""
    from repro.launch.serve import serving_bundle_from_state
    eng = _engine("chip", t_freeze=2, patience=1)
    st, rep = train(eng, RunConfig(outer_iters=5, shape=SHAPE, eta=3e-3,
                                   reconfig=True, metrics_every=2,
                                   log=None))
    eng2 = rep.final_engine
    assert eng2.reconfigured
    b_rec, p_rec = serving_bundle_from_state(eng2, st)
    assert b_rec.cfg.d_ff == eng.spec.budgets["ffn"]

    st_full = eng2.expand_reconfigured(st)
    b_full, p_full = serving_bundle_from_state(eng2.parent, st_full)
    assert b_full.cfg.d_ff == b_rec.cfg.d_ff
    _assert_trees_close(p_rec, p_full, rtol=1e-6)

    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              b_rec.cfg.vocab, jnp.int32)
    logits, _ = b_rec.prefill(p_rec, toks, b_rec.init_cache(2, 8))
    assert logits.shape[0] == 2 and np.isfinite(np.asarray(logits)).all()


# ---------------------------------------------------------------------------
# measured collective schedule shrinks at EVERY fabric level (8 devices)
# ---------------------------------------------------------------------------

_MEASURE_SRC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, jax
from repro.configs import get_config
from repro.configs.base import ConsensusSpec, HsadmmConfig, ShapeConfig
from repro.launch.mesh import make_host_mesh
from repro.models import build
from repro.train.engine import Engine
from repro.dist import hlo

SHAPE = ShapeConfig("tiny", "train", 32, 8)
cfg = get_config("tinyllama-1.1b", smoke=True).replace(
    hsadmm=HsadmmConfig(rho1=1e-2, rho2=1e-3, local_steps=2, t_freeze=2))
eng = Engine(build(cfg), make_host_mesh(model=2), SHAPE,
             consensus=ConsensusSpec(levels=(2, 2), compact_from_level=1,
                                     granularity="chip", node_size=2))
state = eng.init_state_fn()(jax.random.PRNGKey(0))
eng2, _ = eng.reconfigure(state=state)
print("RESULT " + json.dumps(
    {"full": hlo.axis_bytes(eng.round_collectives(frozen=True)),
     "rec": hlo.axis_bytes(eng2.round_collectives(frozen=True))}))
"""


def test_measured_bytes_shrink_at_every_fabric_level():
    """AOT-compile the frozen round on an 8-device forced-host mesh
    (data=4 x model=2, node_size=2 => intra-node, inter-node AND tp
    fabrics all carry traffic) and parse the compiled collective
    schedule: the reconfigured executable moves strictly fewer bytes on
    EVERY fabric tier — compaction is physical at every level, not only
    at the compact_from_level boundary."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _MEASURE_SRC],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         timeout=500)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    res = json.loads(line[len("RESULT "):])
    full, rec = res["full"], res["rec"]
    assert full and any(v > 0 for v in full.values())
    for fabric, b_full in full.items():
        if b_full <= 0:
            continue
        assert rec.get(fabric, 0.0) < b_full, \
            (fabric, b_full, rec.get(fabric))


# ---------------------------------------------------------------------------
# launch.dryrun must APPEND to user-provided XLA_FLAGS, not clobber them
# ---------------------------------------------------------------------------


def test_dryrun_preserves_user_xla_flags():
    code = ("import repro.launch.dryrun, os; "
            "print('FLAGS ' + os.environ['XLA_FLAGS'])")
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_dump_to=/tmp/xla_dump_regression_test")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("FLAGS ")][-1]
    assert "--xla_dump_to=/tmp/xla_dump_regression_test" in line
    assert "--xla_force_host_platform_device_count=512" in line
