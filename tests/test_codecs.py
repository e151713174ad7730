"""repro.comm wire codecs: registry/spec parsing, byte accounting,
group-reduce semantics, per-level selection, the fused-round guarantees
under EVERY registered codec, and measured-vs-analytic agreement.

The CI codec-matrix job selects one matrix cell via the ``WIRE_CODEC``
env var; unset (local tier-1) runs every cell."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (CompositeCodec, TopKCodec, compose, get_codec,
                        level_codecs, list_codecs)
from repro.configs.base import ConsensusSpec, HsadmmConfig, ShapeConfig
from repro.core import (EngineSpec, init_state, local_step, consensus_step,
                        round_step, get_leaf, leaf_keys)
from repro.core.sparsity import GroupRule, LeafAxis, SparsityPlan

MATRIX = ["dense", "q8", "compact+q8", "topk:0.01", "q4", "compact+q4"]
_env = os.environ.get("WIRE_CODEC")
CODECS = [_env] if _env else MATRIX


# ---------------------------------------------------------------------------
# registry / spec parsing
# ---------------------------------------------------------------------------


def test_registry_and_spec_parsing():
    assert {"dense", "q8", "q4", "topk", "compact"} <= set(list_codecs())
    assert get_codec("dense").name == "dense"
    # q8: 1 byte/elem + one f32 scale per ROW of the (R, C) leaf view
    assert get_codec("q8").wire_bytes((4, 4), "float32") == 16 + 4 * 4
    tk = get_codec("topk:0.25")
    assert isinstance(tk, TopKCodec) and tk.rate == 0.25
    cq = get_codec("compact+q8")
    assert isinstance(cq, CompositeCodec)
    assert cq.compact and cq.name == "compact+q8"
    assert cq.wire_bytes((4, 4), "float32") == 16 + 16  # delegates to q8
    c4 = get_codec("compact+q4")
    assert c4.compact and c4.name == "compact+q4"
    assert compose("compact", "dense").compact
    with pytest.raises(KeyError):
        get_codec("zstd")
    with pytest.raises(ValueError):
        compose("q8", "topk:0.1")   # two element codecs can't both reduce


def test_wire_bytes_formulas():
    d = get_codec("dense")
    assert d.wire_bytes((8, 4), "float32") == 128
    assert d.wire_bytes((8, 4), "bfloat16") == 64
    q = get_codec("q8")
    assert q.wire_bytes((8, 4), "float32") == 32 + 32   # s8 + f32 row scales
    assert q.wire_bytes((8, 4), "bfloat16") == 32 + 32  # dtype-independent
    q4 = get_codec("q4")
    # two channels per byte (odd minor dims round up) + f32 row scales
    assert q4.wire_bytes((4, 4), "float32") == 4 * 2 + 4 * 4
    assert q4.wire_bytes((8, 4), "float32") == 8 * 2 + 8 * 4
    assert q4.wire_bytes((8, 5), "float32") == 8 * 3 + 8 * 4  # pad nibble
    assert q4.wire_bytes((100,), "float32") == 50 + 4         # one row
    t = get_codec("topk:0.1")
    # k = max(1, int(n * rate)); index is int32, value width = wire dtype
    assert t.wire_bytes((100,), "float32") == 10 * (4 + 4)
    assert t.wire_bytes((100,), "bfloat16") == 10 * (4 + 2)  # 2+4, not 4+4
    assert t.wire_bytes((5,), "float32") == 1 * 8            # k floors to 1


# ---------------------------------------------------------------------------
# group_reduce semantics
# ---------------------------------------------------------------------------


def _tree(key, lead=8):
    return {"a": jax.random.normal(key, (lead, 6, 4)),
            "b": {"c": jax.random.normal(jax.random.fold_in(key, 1),
                                         (lead, 5))}}


def test_dense_group_reduce_is_weighted_group_sum():
    t = _tree(jax.random.PRNGKey(0))
    w = jnp.arange(1.0, 9.0)
    red, st = get_codec("dense").group_reduce(t, 4, w)
    assert st is None
    ref = (t["a"] * w[:, None, None]).reshape(2, 4, 6, 4).sum(1)
    np.testing.assert_allclose(np.asarray(red["a"]), np.asarray(ref),
                               rtol=1e-6)
    assert red["b"]["c"].shape == (2, 5)


def test_q8_group_reduce_within_quant_error():
    t = _tree(jax.random.PRNGKey(1))
    w = jnp.ones((8,))
    dense, _ = get_codec("dense").group_reduce(t, 4, w)
    q8, _ = get_codec("q8").group_reduce(t, 4, w)
    for k in ("a",):
        x = np.asarray(t[k]).reshape(2, 4, -1)
        # per-member error bound: max|x|/127 each, summed over the group
        bound = np.abs(x).max(-1).sum(1) * (1 / 127.0) + 1e-6
        err = np.abs(np.asarray(q8[k] - dense[k])).reshape(2, -1).max(-1)
        assert np.all(err <= bound)


def test_topk_group_reduce_error_feedback_is_lossless():
    """Over rounds, sum(reduced) + final residuals == sum(dense reduced):
    error feedback loses nothing (DGC invariant), now at the codec level."""
    codec = get_codec("topk:0.2")
    key = jax.random.PRNGKey(2)
    t0 = _tree(key, lead=4)
    w = jnp.ones((4,))
    st = None
    acc = None
    dense_acc = None
    for r in range(5):
        t = jax.tree.map(lambda x: x * (1.0 + 0.3 * r), t0)
        red, st = codec.group_reduce(t, 4, w, st)
        d, _ = get_codec("dense").group_reduce(t, 4, w)
        acc = red if acc is None else jax.tree.map(jnp.add, acc, red)
        dense_acc = d if dense_acc is None else \
            jax.tree.map(jnp.add, dense_acc, d)
    # residual still pending per member; fold it in (summed over members)
    resid = jax.tree.map(lambda e: e.reshape((1, 4) + e.shape[1:]).sum(1),
                         st)
    total = jax.tree.map(jnp.add, acc, resid)
    for k in leaf_keys(t0):
        np.testing.assert_allclose(np.asarray(get_leaf(total, k)),
                                   np.asarray(get_leaf(dense_acc, k)),
                                   rtol=1e-4, atol=1e-4)


def test_topk_encode_decode_roundtrip_keeps_topk_entries():
    codec = get_codec("topk:0.5")
    x = jnp.asarray([3.0, -1.0, 0.5, -4.0, 0.1, 2.0])
    vals, idx = codec.encode(x)
    dec = codec.decode((vals, idx), like=x)
    assert set(np.asarray(idx).tolist()) == {0, 3, 5}
    np.testing.assert_allclose(np.asarray(dec),
                               np.asarray([3.0, 0, 0, -4.0, 0, 2.0]))


# ---------------------------------------------------------------------------
# per-fabric-level selection (+ legacy comm_quant shim)
# ---------------------------------------------------------------------------


def test_level_codec_selection_and_legacy_shim():
    hier = ((2, 2), 1)
    names = lambda hp, lv, kc: [c.name for c in level_codecs(hp, lv, kc)]
    hp = HsadmmConfig(wire_inter="q8")
    assert names(hp, *hier) == ["dense", "q8"]       # intra dense, top q8
    assert names(hp, (4,), 1) == ["dense"]           # flat AR: honest dense
    assert names(hp, (4,), 0) == ["q8"]              # K=1 compact boundary
    hp2 = HsadmmConfig(wire_intra="q8", wire_inter="compact+q8")
    assert names(hp2, (2, 2, 2), 1) == ["q8", "q8", "compact+q8"]
    with pytest.warns(DeprecationWarning):
        assert names(HsadmmConfig(comm_quant="int8"), *hier) \
            == ["dense", "q8"]
    with pytest.warns(DeprecationWarning):           # explicit spec wins
        assert names(HsadmmConfig(comm_quant="int8", wire_inter="dense"),
                     *hier) == ["dense", "dense"]
    with pytest.raises(ValueError):
        names(HsadmmConfig(comm_quant="fp4"), *hier)


def test_wire_map_overrides_intra_inter():
    """An explicit per-boundary map (the AdaptiveWireSelector output /
    --wire-auto) wins over wire_intra/wire_inter verbatim — including on
    the flat-AR boundary the intra/inter knobs honestly leave dense."""
    names = lambda hp, lv, kc: [c.name for c in level_codecs(hp, lv, kc)]
    hp = HsadmmConfig(wire_intra="q8", wire_inter="compact+q8",
                      wire_map=("q4", "compact+q4"))
    assert names(hp, (2, 2), 1) == ["q4", "compact+q4"]
    # flat AR: the map is an explicit per-boundary choice, so it applies
    assert names(HsadmmConfig(wire_map=("q8",)), (4,), 1) == ["q8"]
    with pytest.raises(ValueError):   # one spec per boundary, exactly
        names(HsadmmConfig(wire_map=("q8",)), (2, 2), 1)


# ---------------------------------------------------------------------------
# fused-round equivalence under every codec (CI codec matrix)
# ---------------------------------------------------------------------------

E = 3


def _problem(key, W=4, L=3, D=8, F=16):
    params0 = {"blocks": {"w_in": jax.random.normal(key, (L, D, F)),
                          "w_out": jax.random.normal(
                              jax.random.fold_in(key, 1), (L, F, D))},
               "emb": jax.random.normal(jax.random.fold_in(key, 2), (32, D))}
    targets = jax.tree.map(
        lambda x: jax.random.normal(jax.random.fold_in(key, 3),
                                    (W,) + x.shape), params0)

    def loss_fn(th, t):
        return 0.5 * sum(jnp.sum((get_leaf(th, k) - get_leaf(t, k))**2)
                         for k in leaf_keys(th))
    superbatch = jax.tree.map(
        lambda x: jnp.stack([x * (1 + 0.1 * e) for e in range(E)]), targets)
    return params0, superbatch, loss_fn


def _spec(levels, kc, granularity, **hp_kw):
    plan = SparsityPlan((GroupRule(
        "ffn", (LeafAxis("blocks/w_in", 2), LeafAxis("blocks/w_out", 1)),
        groups=16, keep=8, stack_ndims=1),))
    return EngineSpec(plan=plan,
                      consensus=ConsensusSpec(levels=levels,
                                              compact_from_level=kc,
                                              granularity=granularity),
                      hp=HsadmmConfig(rho1=1.0, rho2=1.0, weight_decay=0.0,
                                      **hp_kw),
                      use_momentum=True)


@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("frozen", [False, True])
def test_round_step_matches_legacy_under_codec(codec, frozen):
    """round_step == E local_step calls + consensus_step under every wire
    codec — including stateful top-k error feedback threaded through
    ``state["wire"]`` across rounds."""
    key = jax.random.PRNGKey(0)
    params0, superbatch, loss_fn = _problem(key)
    spec = _spec((2, 2), 1, "chip", wire_inter=codec)
    state0 = init_state(params0, spec)
    if get_codec(codec).stateful:
        assert "wire" in state0 and state0["wire"][0] == {}
    if frozen:   # freeze from a post-dynamic-round state (meaningful masks)
        state0, _ = jax.jit(
            lambda s: round_step(s, superbatch, loss_fn, spec,
                                 jnp.float32(0.05)))(state0)

    st = state0
    jl = jax.jit(lambda s, b: local_step(s, b, loss_fn, spec, 0.05))
    jc = jax.jit(lambda s: consensus_step(s, spec, frozen=frozen))
    for e in range(E):
        st, _ = jl(st, jax.tree.map(lambda x: x[e], superbatch))
    st_leg, info = jc(st)

    jr = jax.jit(lambda s, sb: round_step(s, sb, loss_fn, spec,
                                          jnp.float32(0.05), frozen=frozen))
    st_fus, m = jr(state0, superbatch)

    def close(a, b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)
    for grp in ("theta", "u"):
        for k in leaf_keys(st_leg[grp]):
            close(get_leaf(st_fus[grp], k), get_leaf(st_leg[grp], k))
    for zl, zf in zip(st_leg["z"], st_fus["z"]):
        for k in leaf_keys(zl):
            close(get_leaf(zf, k), get_leaf(zl, k))
    if "wire" in st_leg:
        for wl, wf in zip(st_leg["wire"], st_fus["wire"]):
            for k in leaf_keys(wl) if wl else []:
                close(get_leaf(wf, k), get_leaf(wl, k))
    close(m.r_primal, info["r_primal"])
    close(m.s_dual, info["s_dual"])


def test_codec_forced_compaction_without_structural_kc():
    """The ``compact`` marker compacts a boundary the ConsensusSpec would
    ship dense: same algorithm (masks/projection unchanged), compact
    payload on the wire."""
    key = jax.random.PRNGKey(0)
    params0, superbatch, loss_fn = _problem(key)
    # kc=2 > K-1: no structural compaction anywhere; codec adds it at top
    ref_spec = _spec((2, 2), 2, "chip")
    cq_spec = _spec((2, 2), 2, "chip", wire_inter="compact+dense")
    out = {}
    for name, spec in (("ref", ref_spec), ("cq", cq_spec)):
        st = init_state(params0, spec)
        st, _ = jax.jit(lambda s, sb, sp=spec: round_step(
            s, sb, loss_fn, sp, jnp.float32(0.05)))(st, superbatch)
        out[name] = st
    # compacting the top boundary only drops already-masked groups from
    # the exchange, so the consensus is unchanged on the kept support
    for k in leaf_keys(out["ref"]["z"][-1]):
        np.testing.assert_allclose(
            np.asarray(get_leaf(out["cq"]["z"][-1], k)),
            np.asarray(get_leaf(out["ref"]["z"][-1], k)),
            rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the real loop: 1 dispatch/round + executable-derived accounting per codec
# ---------------------------------------------------------------------------

SHAPE = ShapeConfig("tiny", "train", 32, 8)


def _engine(codec, t_freeze=2):
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import build
    from repro.train.engine import Engine
    cfg = get_config("tinyllama-1.1b", smoke=True).replace(
        hsadmm=HsadmmConfig(rho1=1e-2, rho2=1e-3, local_steps=4,
                            t_freeze=t_freeze, wire_inter=codec))
    return Engine(build(cfg), make_host_mesh(), SHAPE,
                  consensus=ConsensusSpec(levels=(2, 2),
                                          compact_from_level=1,
                                          granularity="chip"))


@pytest.mark.parametrize("codec", CODECS)
def test_loop_one_dispatch_per_round_under_codec(codec, monkeypatch):
    """The fused-round dispatch guard (tests/test_fused_round.py) stays
    green under every codec: 1 dispatch per round from exactly 2
    executables, and the loop's byte accounting derives from the codec."""
    from repro.dist import monitor
    from repro.train.engine import Engine
    from repro.train.loop import RunConfig, round_comm_bytes, train
    counts = monitor.CallCounter()
    real_round = Engine.round_step_fn
    monkeypatch.setattr(
        Engine, "round_step_fn",
        lambda self, frozen: counts.wrap(
            real_round(self, frozen), "frozen" if frozen else "dynamic"))

    eng = _engine(codec, t_freeze=2)
    _, rep = train(eng, RunConfig(outer_iters=3, shape=SHAPE, eta=3e-3,
                                  metrics_every=10, log=None))
    assert counts.calls == 3
    assert counts.by_label == {"dynamic": 2, "frozen": 1}
    assert len(rep.losses) == 3

    dense_eq, dyn_b, frz_b = round_comm_bytes(eng)
    assert rep.comm_bytes_internode == [dyn_b, dyn_b, frz_b]
    assert frz_b < dyn_b
    if codec != "dense":       # q8 / topk shrink the wire payload further
        assert frz_b < dense_eq


@pytest.mark.parametrize("codec", CODECS)
def test_round_comm_bytes_agrees_with_plan_bytes(codec):
    """Acceptance: round_comm_bytes and plan_bytes agree when both derive
    from the SAME WireCodec.wire_bytes (the top boundary's codec)."""
    from repro.core.shrinkage import mask_sync_bytes, plan_bytes
    from repro.train.loop import _param_shapes, round_comm_bytes
    eng = _engine(codec)
    shapes = _param_shapes(eng)
    top = eng.spec.codecs[-1]
    assert top.name == get_codec(codec).name
    dense_w, compact_w = plan_bytes(shapes, eng.bundle.plan,
                                    eng.spec.budgets,
                                    eng.cfg.param_dtype, codec=top)
    dense_eq, dyn_b, frz_b = round_comm_bytes(eng)
    assert frz_b == compact_w          # top boundary ships compact @codec
    assert dyn_b == compact_w + mask_sync_bytes(
        shapes, eng.bundle.plan, eng.cfg.hsadmm.mask_mode)
    assert dense_eq == plan_bytes(shapes, eng.bundle.plan,
                                  eng.spec.budgets, eng.cfg.param_dtype,
                                  codec="dense")[0]


# ---------------------------------------------------------------------------
# measured (compiled-HLO) vs analytic agreement
# ---------------------------------------------------------------------------

_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import ConsensusSpec, HsadmmConfig
from repro.core import init_state, consensus_step, EngineSpec
from repro.core.sparsity import GroupRule, LeafAxis, SparsityPlan
from repro.dist import hlo
from repro.launch.mesh import make_host_mesh
from repro.train.engine import _walk

codec = sys.argv[1]
plan = SparsityPlan((GroupRule("g", (LeafAxis("w", 0),), groups=32,
                               keep=16, stack_ndims=0),))
spec = EngineSpec(plan=plan,
                  consensus=ConsensusSpec(levels=(4,), compact_from_level=0,
                                          granularity="chip"),
                  hp=HsadmmConfig(rho1=1.0, weight_decay=0.0,
                                  wire_inter=codec),
                  use_momentum=False, stack_map=())
params0 = {"w": jax.random.normal(jax.random.PRNGKey(0), (32, 8))}
state = init_state(params0, spec)
mesh = make_host_mesh()
state = _walk(state, lambda p, x: jax.device_put(
    x, NamedSharding(mesh, P("data") if getattr(x, "ndim", 0) > 0
                     and x.shape[0] == 4 else P())))
txt = jax.jit(lambda s: consensus_step(s, spec, frozen=True)) \
    .lower(state).compile().as_text()
colls = hlo.collective_stats(txt, model=1, data=4, node=2)
print(json.dumps([[c.kind, c.payload_bytes, c.group_size, c.tensor_bytes]
                  for c in colls]))
"""


@pytest.mark.parametrize("codec", [c for c in CODECS
                                   if c in ("dense", "q8", "q4")])
def test_measured_hlo_payloads_match_wire_bytes(codec):
    """The codec-format payloads XLA actually schedules equal
    ``WireCodec.wire_bytes`` of the compact buffer exactly; GSPMD may add
    resharding collectives around them (the collective-padding
    tolerance).  topk is excluded: its simulated exchange is
    dense-restored (like the DGC baseline), so the values+indices wire
    representation never appears in HLO."""
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", _SUBPROC, codec], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    colls = json.loads(r.stdout.strip().splitlines()[-1])
    payloads = [p for _, p, _, _ in colls]
    # compact payload: one rule, keep=16 of 32 groups -> (16, 8) f32
    if codec == "dense":
        expected = get_codec("dense").wire_bytes((16, 8), "float32")
        # the compact all-reduce; XLA's all-reduce combiner may carry a
        # scalar reduction in the same instruction, so match the tensor
        tensors = [b for c in colls if c[0] == "all-reduce" for b in c[3]]
        assert expected in tensors
    elif codec == "q8":
        # q8 ring: g-1 shifts, each moving the s8 buffer + its f32
        # per-row scales; s8 elems + scale bytes == wire_bytes exactly
        s8, sc = 16 * 8, 16 * 4
        assert get_codec("q8").wire_bytes((16, 8), "float32") == s8 + sc
        assert payloads.count(s8) >= 3       # g-1 = 3 ring shifts
        assert sc in payloads                # the f32 scales ride along
    else:
        # q4 ring rolls the PACKED uint8 buffer (16, 4) — 64 bytes —
        # plus the f32 row scales (16, 1) — also 64 bytes: 2 tensors
        # x (g-1) shifts, every one exactly 64B on the wire
        pk, sc = 16 * 4, 16 * 4
        assert get_codec("q4").wire_bytes((16, 8), "float32") == pk + sc
        assert payloads.count(64) >= 6
