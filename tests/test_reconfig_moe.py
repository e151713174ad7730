"""Physical reconfiguration of family="moe" (whole-expert pruning with
the router as follower, stacked (L, E) moe_ffn): the test_reconfig.py
conformance claims — the reconfigured round equals the full-shape masked
round, states land on the budget shapes, measured bytes shrink at every
fabric level — on the MoE configurations.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ConsensusSpec, HsadmmConfig
from repro.launch.mesh import make_host_mesh
from repro.models import build
from repro.train.engine import Engine

from test_reconfig import (E, ETA, HIERARCHIES, SHAPE, _assert_trees_close,
                            _frozen_state, _superbatch_iter)


# ---------------------------------------------------------------------------
# family="moe": expert-level pruning (router follower, stacked (L, E)
# moe_ffn composing with the expert-stack compaction)
# ---------------------------------------------------------------------------


def _moe_engine(hier="chip", wire_inter=None, t_freeze=2, patience=1,
                use_env_codec=False, arch="qwen2-moe-a2.7b"):
    levels, kc, gran = HIERARCHIES[hier]
    wire = wire_inter if wire_inter is not None \
        else (os.environ.get("WIRE_CODEC") if use_env_codec else None)
    cfg = get_config(arch, smoke=True).replace(
        hsadmm=HsadmmConfig(rho1=1e-2, rho2=1e-3, local_steps=E,
                            t_freeze=t_freeze, reconfig_patience=patience,
                            wire_inter=wire))
    return Engine(build(cfg), make_host_mesh(), SHAPE,
                  consensus=ConsensusSpec(levels=levels,
                                          compact_from_level=kc,
                                          granularity=gran))


@pytest.mark.parametrize("hier", sorted(HIERARCHIES))
@pytest.mark.parametrize("codec", ["dense", "compact+q8"])
def test_moe_reconfigured_round_matches_full_shape(hier, codec):
    """family="moe" differential conformance: whole-expert pruning (the
    router logit columns follow the expert class, so routing renormalizes
    over the survivors) composes with the per-(layer, expert) moe_ffn
    budgets, the shared-expert "ffn" class, and GQA heads — and the
    reconfigured frozen round equals the full-shape masked round on
    every hierarchy.  The -inf masking of dead router columns makes the
    full-shape model's discrete top-k routing identical to the compacted
    model's, so the conformance tolerance is the usual numeric one."""
    eng = _moe_engine(hier, wire_inter=codec)
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


@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "granite-moe-3b-a800m"])
def test_moe_reconfigured_shapes_are_budget_B(arch):
    """shrink_config(strict=True) succeeds for family="moe" and the
    migrated state lands on the budget shapes everywhere the expert
    class touches: the expert stack of we_g/we_u/we_d shrinks from E to
    B_experts, the router loses the SAME logit columns, the per-expert
    hidden width shrinks to the moe_ffn budget, and capacity stays
    pinned to the parent's expert count (moe_capacity_experts) so the
    per-token math is unchanged."""
    from repro.models import shrink_config
    eng = _moe_engine("chip", arch=arch)
    it = _superbatch_iter(eng)
    state, _ = _frozen_state(eng, it)
    eng2, st_c = eng.reconfigure(state)

    cfg, cfg2 = eng.cfg, eng2.cfg
    B_e = eng.spec.budgets["experts"]
    B_f = eng.spec.budgets["moe_ffn"]
    assert shrink_config(cfg, eng.bundle.plan, eng.spec.budgets,
                         strict=True) == cfg2
    assert cfg2.n_experts == B_e < cfg.n_experts
    assert cfg2.d_expert_eff == B_f < cfg.d_expert_eff
    assert cfg2.moe_top_k == cfg.moe_top_k <= B_e
    # capacity invariance: the shrunk model buckets against the PARENT's
    # expert count, not its own
    assert cfg2.moe_capacity_base == cfg.moe_capacity_base == cfg.n_experts

    W = eng.workers
    L = cfg.n_layers
    th = st_c["theta"]["blocks"]["moe"]
    assert th["we_g"].shape == (W, L, B_e, cfg.d_model, B_f)
    assert th["we_d"].shape == (W, L, B_e, B_f, cfg.d_model)
    assert th["router"].shape == (W, L, cfg.d_model, B_e)   # follower
    if cfg.n_shared_experts:
        B_s = eng.spec.budgets["ffn"]
        assert cfg2.d_shared_eff == B_s < cfg.d_shared_eff
        assert th["shared"]["wg"].shape == (W, L, cfg.d_model, B_s)
    for z in st_c["z"]:
        assert z["blocks"]["moe"]["we_u"].shape[-3:-1] \
            == (B_e, cfg.d_model)
    # parent untouched
    assert eng.bundle.plan.rule("experts").groups == cfg.n_experts


_MEASURE_MOE_SRC = r"""
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
# default keep_rate 0.5: expert keep_count(8, 0.5, 2) = 4 of 8 experts
cfg = get_config("qwen2-moe-a2.7b", smoke=True).replace(
    hsadmm=HsadmmConfig(rho1=1e-2, rho2=1e-3, local_steps=2, t_freeze=2))
eng = Engine(build(cfg), make_host_mesh(model=2), SHAPE,
             consensus=ConsensusSpec(levels=(2, 2), compact_from_level=1,
                                     granularity="chip", node_size=2))
state = eng.init_state_fn()(jax.random.PRNGKey(0))
eng2, _ = eng.reconfigure(state=state)
print("RESULT " + json.dumps(
    {"full": hlo.axis_bytes(eng.round_collectives(frozen=True)),
     "rec": hlo.axis_bytes(eng2.round_collectives(frozen=True)),
     "full_inter": hlo.internode_bytes(eng.round_collectives(frozen=True)),
     "rec_inter": hlo.internode_bytes(eng2.round_collectives(frozen=True))}))
"""


def test_moe_measured_bytes_shrink_at_every_fabric_level():
    """AOT-compile the MoE frozen round on the 8-device forced-host mesh
    (data=4 x model=2, node_size=2) and parse the compiled collective
    schedule: at expert keep 0.5 the reconfigured engine moves strictly
    fewer bytes on EVERY fabric tier — dropping whole experts shrinks
    the consensus payload (expert stacks AND router columns) physically
    on the wire, the paper's claim applied to the all-to-all/router
    class."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _MEASURE_MOE_SRC],
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
    assert res["full_inter"] > 0
    assert res["rec_inter"] < res["full_inter"], res
