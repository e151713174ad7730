"""Physical reconfiguration of the paper's own model family, the CNN
(coupled cross-layer classes, GN followers, the conv->fc boundary): the
test_reconfig.py conformance claims — the reconfigured round equals the
full-shape masked round, the loop reconfigures once, measured inter-node
bytes shrink — on resnet18.
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
from repro.train.loop import RunConfig, train

from test_reconfig import (E, ETA, HIERARCHIES, SHAPE, _assert_trees_close,
                            _frozen_state, _superbatch_iter)


def _cnn_engine(hier="chip", t_freeze=2, patience=1, use_env_codec=False,
                arch="resnet18"):
    """The paper's own model family (ResNet, coupled cross-layer plan)."""
    levels, kc, gran = HIERARCHIES[hier]
    wire = os.environ.get("WIRE_CODEC") if use_env_codec else None
    cfg = get_config(arch, smoke=True).replace(
        hsadmm=HsadmmConfig(rho1=1e-2, rho2=1e-3, local_steps=E,
                            t_freeze=t_freeze, reconfig_patience=patience,
                            wire_inter=wire))
    return Engine(build(cfg), make_host_mesh(), SHAPE,
                  consensus=ConsensusSpec(levels=levels,
                                          compact_from_level=kc,
                                          granularity=gran))

# ---------------------------------------------------------------------------
# the paper's own model family: CNN (coupled cross-layer classes, GN
# followers, conv->fc boundary, shape rules riding the sliced channels)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("hier", sorted(HIERARCHIES))
def test_cnn_reconfigured_round_matches_full_shape(hier):
    """family="cnn" differential conformance: the coupling-graph plan
    (stream/internal classes with GN scale/bias followers, identity-skip
    unions, fc rows) migrates the WHOLE state onto the shrunk ResNet and
    the reconfigured frozen round equals the full-shape masked round —
    losses, residuals and expanded params — on every hierarchy.  The
    projection-only S_s masks ride along, gathered onto the kept
    channels.  Wire codec comes from WIRE_CODEC (CI codec-matrix job)."""
    eng = _cnn_engine(hier, use_env_codec=True)
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


def test_cnn_reconfigured_shapes_follow_coupling_classes():
    """shrink_config(strict=True) succeeds for family="cnn" and every
    coupled leaf lands on its class budget: producer C_out AND consumer
    C_in of the same conv, GN followers, the fc rows — at channel keep
    0.5 the smoke model's widths halve (16,32 -> 8,16)."""
    from repro.models import shrink_config
    eng = _cnn_engine("chip")
    it = _superbatch_iter(eng)
    state, _ = _frozen_state(eng, it)
    eng2, st_c = eng.reconfigure(state)
    cfg2 = shrink_config(eng.cfg, eng.bundle.plan, eng.spec.budgets,
                         strict=True)
    assert cfg2.cnn_outs == eng2.cfg.cnn_outs == (8, 16)
    assert eng2.cfg.cnn_stem == 8 and eng2.cfg.cnn_cmid == (8, 16)
    th = st_c["theta"]
    assert th["stem"].shape == (4, 3, 3, 3, 8)
    assert th["gn0"]["scale"].shape == (4, 8)          # follower migrated
    assert th["layer1"]["b0"]["conv1"].shape == (4, 3, 3, 8, 16)
    assert th["layer1"]["b0"]["down"].shape == (4, 1, 1, 8, 16)
    assert th["fc_w"].shape == (4, 16, 10)             # conv->fc boundary
    for z in st_c["z"]:
        assert z["layer1"]["b0"]["gn2"]["bias"].shape[-1] == 16
    # shape-rule masks gathered onto the kept channels
    s = st_c["masks"]["s:layer1/b0/conv2"]
    assert s["mask"].shape == (3 * 3 * 16,)
    assert eng.cfg.cnn_outs == ()                      # parent untouched


def test_cnn_reconfig_through_training_loop(tmp_path):
    """The real loop drives the CNN family end to end: dynamic -> frozen
    -> reconfigured, finite losses, reconfigured engine reported — and a
    fresh engine RESUMES the reconfigured checkpoint (aux mask names
    carry CNN rule keys with '/' and ':') straight into shrunk shapes."""
    d = str(tmp_path)
    eng = _cnn_engine("chip", t_freeze=2, patience=1, use_env_codec=True)
    _, rep = train(eng, RunConfig(outer_iters=6, shape=SHAPE, eta=3e-3,
                                  reconfig=True, metrics_every=10,
                                  ckpt_dir=d, ckpt_every=6, log=None))
    assert rep.executables == ["dynamic"] * 2 + ["frozen"] \
        + ["reconfigured"] * 3
    assert rep.frozen_at == 2 and rep.reconfigured_at == 3
    assert rep.final_engine.reconfigured
    assert np.all(np.isfinite(rep.losses))
    assert rep.comm_bytes_internode[-1] < rep.comm_bytes_dense_equiv[-1]

    eng_b = _cnn_engine("chip", t_freeze=2, patience=1, use_env_codec=True)
    st2, rep2 = train(eng_b, RunConfig(outer_iters=8, shape=SHAPE,
                                       eta=3e-3, reconfig=True, ckpt_dir=d,
                                       ckpt_every=100, metrics_every=2,
                                       log=None))
    assert rep2.executables == ["reconfigured"] * 2
    assert st2["theta"]["fc_w"].shape[-2] == 16       # shrunk last stream
    assert rep2.final_engine.reconfigured


_MEASURE_CNN_SRC = r"""
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
cfg = get_config("resnet18", smoke=True).replace(
    hsadmm=HsadmmConfig(rho1=1e-2, rho2=1e-3, local_steps=2, t_freeze=2))
# W=4 ADMM workers sharded over a 4-wide data axis, 2-wide virtual nodes:
# the intra-node AND inter-node boundaries both schedule real collectives
# (a W==device-count CNN lead trips a GSPMD batch-group-conv corner at
# per-worker batch 1, so the measurement pins W=4 — same layout as the
# transformer measurement above)
eng = Engine(build(cfg), make_host_mesh(data=4), SHAPE,
             consensus=ConsensusSpec(levels=(2, 2), compact_from_level=1,
                                     granularity="chip", node_size=2))
state = eng.init_state_fn()(jax.random.PRNGKey(0))
eng2, _ = eng.reconfigure(state=state)
full = eng.round_collectives(frozen=True)
rec = eng2.round_collectives(frozen=True)
print("RESULT " + json.dumps(
    {"full": hlo.axis_bytes(full), "rec": hlo.axis_bytes(rec),
     "full_inter": hlo.internode_bytes(full),
     "rec_inter": hlo.internode_bytes(rec)}))
"""


def test_cnn_measured_internode_bytes_shrink():
    """AOT-compile the CNN frozen round on a forced-host mesh (W=4 ADMM
    workers sharded over data=4, 2-wide virtual nodes => real intra- AND
    inter-node collectives) and parse the compiled schedule: at channel
    keep 0.5 the reconfigured ResNet's inter-node collective bytes are
    strictly smaller — the coupled compaction is physical on the wire,
    not just masked."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _MEASURE_CNN_SRC],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         timeout=500)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    res = json.loads(line[len("RESULT "):])
    assert res["full_inter"] > 0
    assert res["rec_inter"] < res["full_inter"], res
    for fabric, b_full in res["full"].items():
        if b_full <= 0:
            continue
        assert res["rec"].get(fabric, 0.0) < b_full, \
            (fabric, b_full, res["rec"].get(fabric))
