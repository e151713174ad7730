#!/usr/bin/env python3
"""Chip smoke run: the H-SADMM training round of full-width ResNet-18 on TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: W=4 over data=4 vs one

One chip.  The paper's job through the objects ``repro.launch.train``
builds (``get_config`` -> ``build`` -> ``Engine(make_host_mesh(), ...)``
-> ``train(engine, RunConfig(...))``), on CIFAR-10 ResNet-18 at its
published widths (64/128/256/512, 32x32 inputs, 10 classes): W=4 ADMM
workers on the chip, consensus levels (2, 2) with the top boundary
compacted, 32 images per worker, E=8 local prox-SGD steps per round.
``t_freeze=2`` and ``reconfig_patience=1`` take five rounds through the
dynamic, frozen and reconfigured executables; the last round saves a
checkpoint of the shrunk state.  Then one frozen round of the
reconfigured model exchanges its top boundary as ``compact+q8``, which
runs the Pallas q8 encode kernel inside the round.  A first phase checks
the wire and prox kernels against their XLA references on the chip.

Four chips (``--chips 4``).  W=4 workers over ``data=4``: one dynamic,
two frozen and one reconfigured round, and the same rounds on the same
seed over one device.  Losses and the global consensus ``z`` must agree
within ``LOSS_RTOL`` / ``Z_RTOL``; the 4-device round must run the prox
kernel on worker shards (a quarter of the one-device kernel elements),
not on an all-gathered worker stack.

Weights are random from ``--seed``; data is the repo's deterministic
synthetic CIFAR stream.  One line per phase, then as the last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Exits non-zero, printing no result, when JAX finds no TPU or a phase
fails.  Compile seconds and persistent-cache hits are set-up, not speed.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
CKPT_DIR = os.path.join(REPO, ".smoke_ckpt")
WORKERS, PER_WORKER, LOCAL_STEPS = 4, 32, 8
LEVELS = (2, 2)
# 4 devices vs 1: the same rounds, partitioned differently.  On the CPU
# (4 virtual devices) the two agree to 1e-7.  On the chip the convs run
# at the TPU's default bf16-pass precision with a different algorithm per
# partitioning (per-worker vs worker-batched): the purely local first
# round already ends 1.1% apart in loss, and the ADMM rounds after it
# move that to 2.3% (TPU v5e, jax 0.9.0).  The consensus z, which
# averages the workers, stays within 0.51%.
LOSS_RTOL = 5e-2
Z_RTOL = 1e-2


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def _devices_or_exit(n: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX's default backend is "
                 f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < n:
        sys.exit(f"chip_smoke: --chips {n} needs {n} TPU devices, JAX "
                 f"sees {len(devs)}")
    return devs


def _peak_bytes() -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())


def _engine(mesh, *, t_freeze: int, patience: int):
    import dataclasses
    from repro.configs import get_config
    from repro.configs.base import ConsensusSpec, ShapeConfig
    from repro.models import build
    from repro.train.engine import Engine
    cfg = get_config("resnet18")
    cfg = cfg.replace(hsadmm=dataclasses.replace(
        cfg.hsadmm, local_steps=LOCAL_STEPS, t_freeze=t_freeze,
        reconfig_patience=patience))
    shape = ShapeConfig("chip-smoke", "train", 0, WORKERS * PER_WORKER)
    cons = ConsensusSpec(levels=LEVELS, compact_from_level=1,
                         granularity="chip")
    return Engine(build(cfg), mesh, shape, consensus=cons)


def _run(engine, outer_iters: int, seed: int, ckpt: bool):
    """train() with its round dispatches and compiles counted."""
    from repro.dist import monitor
    from repro.train.engine import Engine
    from repro.train.loop import RunConfig, train
    counts = monitor.CallCounter()
    real = Engine.round_step_fn

    def counted(self, frozen):
        label = "reconfigured" if self.reconfigured \
            else ("frozen" if frozen else "dynamic")
        return counts.wrap(real(self, frozen), label)
    run = RunConfig(outer_iters=outer_iters, shape=engine.shape, eta=1e-2,
                    seed=seed, reconfig=True, metrics_every=1,
                    ckpt_dir=CKPT_DIR if ckpt else None,
                    ckpt_every=outer_iters, ckpt_keep=1, resume=False,
                    log=lambda s: print(s, file=sys.stderr))
    Engine.round_step_fn = counted
    try:
        with monitor.compile_count() as comp:
            state, rep = train(engine, run)
            jax.block_until_ready(state)
    finally:
        Engine.round_step_fn = real
    return state, rep, counts, comp


def _kernels_in(hlo: str) -> int:
    """Pallas TPU kernel calls in compiled HLO text."""
    return hlo.count('custom_call_target="tpu_custom_call"')


def _custom_call_elems(hlo: str) -> int:
    """Elements of every tpu_custom_call result in compiled HLO text."""
    n = 0
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        head = line.split("custom-call(")[0]
        for dims in re.findall(r"[a-z]\w*\[([0-9,]*)\]", head):
            n += math.prod(int(d) for d in dims.split(",") if d)
    return n


def _finite(xs) -> bool:
    return bool(np.all(np.isfinite(np.asarray(xs, np.float64))))


# --------------------------------------------------------------------------
# one chip
# --------------------------------------------------------------------------


def phase_kernels() -> None:
    """The wire and prox kernels, natively compiled, against their XLA
    references on the chip (non-aligned shapes: padded grids, q4 pack
    tail step)."""
    from repro.kernels import ops, ref
    k = jax.random.PRNGKey(7)
    x = jax.random.normal(k, (300, 578)) * 3.0
    q, s = ops.quantize_rows(x)
    qr, sr = jax.jit(ref.quantize_rows_ref)(x)
    assert np.array_equal(np.asarray(q), np.asarray(qr)), "q8 encode"
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    p, s4 = ops.quantize_pack_q4(x)
    pr, _ = jax.jit(ref.quantize_pack_q4_ref)(x)
    assert np.array_equal(np.asarray(p), np.asarray(pr)), "q4 pack"
    xs = [jax.random.normal(jax.random.fold_in(k, i), (4, 75, 576))
          for i in range(5)]
    rho = jnp.full((1, 75, 1), 0.3)
    t, m = jax.jit(lambda *a: ops.prox_sgd_update(*a, momentum=0.9))(
        *xs, rho, jnp.float32(1e-2))
    mr = 0.9 * xs[4] + xs[1] + rho * (xs[0] - xs[2] + xs[3])
    np.testing.assert_allclose(np.asarray(m), np.asarray(mr), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(t),
                               np.asarray(xs[0] - 1e-2 * mr), rtol=1e-5,
                               atol=1e-5)
    log("kernels", q8_encode="exact", q4_pack="exact",
        prox_300x576_view="match")


def one_chip(seed: int) -> None:
    from repro.data.pipeline import batches, superbatches
    from repro.data.synthetic import make_stream
    from repro.dist import checkpoint, monitor
    from repro.launch.mesh import make_host_mesh
    phase_kernels()

    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    eng = _engine(make_host_mesh(), t_freeze=2, patience=1)
    t0 = time.perf_counter()
    state, rep, counts, comp = _run(eng, 5, seed, ckpt=True)
    wall = time.perf_counter() - t0
    assert _finite(rep.losses), rep.losses
    assert rep.executables == ["dynamic"] * 2 + ["frozen"] \
        + ["reconfigured"] * 2, rep.executables
    assert counts.calls == 5, counts.by_label
    rc = rep.final_engine
    widths = {"stem": rc.cfg.cnn_stem, "outs": list(rc.cfg.cnn_outs),
              "cmid": list(rc.cfg.cnn_cmid)}
    assert all(w < f for w, f in zip(widths["outs"], eng.cfg.cnn_widths))
    for kind in ("dynamic", "frozen", "reconfigured"):
        ls = [l for l, e in zip(rep.losses, rep.executables) if e == kind]
        log(kind, losses=[round(l, 6) for l in ls])
    log("train", executables=rep.executables,
        dispatches=dict(counts.by_label), compiles=comp.compiles,
        compile_s=round(comp.seconds, 1), cache_hits=comp.cache_hits,
        setup_wall_s=round(wall, 1), peak_bytes=_peak_bytes())
    log("reconfig", at=rep.reconfigured_at, widths=json.dumps(widths),
        full=list(eng.cfg.cnn_widths))

    # the checkpoint train() saved after the last (reconfigured) round
    last = checkpoint.latest(CKPT_DIR)
    assert last is not None, "no checkpoint published"
    meta = checkpoint.read_meta(last)
    assert meta["reconfigured"] and meta["step"] == 5, meta
    tmpl = jax.tree.map(lambda a: np.zeros(a.shape, a.dtype), state)
    restored, _ = checkpoint.restore(last, tmpl)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), "ckpt leaf"
    log("checkpoint", path=os.path.relpath(last, REPO), step=meta["step"],
        reconfigured=meta["reconfigured"], roundtrip="exact")

    with monitor.compile_count() as c:
        hlo = rc.round_hlo(frozen=True)
    n_cc = _kernels_in(hlo)
    assert n_cc > 0, "no Pallas kernel in the reconfigured round"
    log("round_hlo", executable="reconfigured",
        tpu_custom_call=n_cc, compiles=c.compiles,
        compile_s=round(c.seconds, 1), cache_hits=c.cache_hits)

    # one frozen round of the reconfigured model over compact+q8
    q8 = rc.with_wire(inter="compact+q8")
    stream = make_stream(q8.cfg, q8.shape, q8.workers)
    sb = next(superbatches(batches(stream, q8.bundle.extra_inputs,
                                   q8.shape, start_step=40), LOCAL_STEPS))
    eta = jnp.float32(1e-2)
    with monitor.compile_count() as c:
        compiled = q8.round_step_fn(frozen=True).lower(state, sb, eta) \
            .compile()
    n_q8 = _kernels_in(compiled.as_text())
    state, m = compiled(state, sb, eta)
    losses = np.asarray(m.losses)
    assert _finite(losses) and _finite(_global_z(state)), \
        "non-finite compact+q8 round"
    assert n_q8 > n_cc, "q8 encode kernel missing from the round"
    log("compact+q8", wire=[c_.name for c_ in q8.spec.codecs],
        losses=[round(float(l), 6) for l in losses],
        tpu_custom_call=n_q8, compiles=c.compiles,
        compile_s=round(c.seconds, 1), cache_hits=c.cache_hits,
        peak_bytes=_peak_bytes())


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------


def _global_z(state) -> np.ndarray:
    return np.concatenate([np.ravel(np.asarray(v, np.float64))
                           for v in jax.tree.leaves(state["z"][-1])])


def _worker_stack_gathers(hlo: str, engine) -> int:
    """All-gathers whose result is a whole (W, ...) theta leaf: GSPMD
    replicating the worker stack around an unpartitionable kernel."""
    stacks = {"f32[" + ",".join(map(str, x.shape)) + "]"
              for x in jax.tree.leaves(engine.state_struct()["theta"])}
    res = re.compile(r"=\s*(\w+\[[0-9,]*\])\S*\s+all-gather(?:-start)?\(")
    return sum(1 for m in map(res.search, hlo.splitlines())
               if m and m.group(1) in stacks)


def four_chips(seed: int, devices) -> None:
    from collections import Counter
    from repro.launch.mesh import make_host_mesh
    runs = {}
    for name, devs in (("4dev", devices[:4]), ("1dev", devices[:1])):
        eng = _engine(make_host_mesh(devices=devs), t_freeze=1, patience=2)
        state, rep, counts, comp = _run(eng, 4, seed, ckpt=False)
        assert rep.executables == ["dynamic"] + ["frozen"] * 2 \
            + ["reconfigured"], rep.executables
        assert _finite(rep.losses), rep.losses
        runs[name] = dict(losses=np.asarray(rep.losses), z=_global_z(state))
        log(f"chips:{name}", mesh=dict(eng.axes), workers=eng.workers,
            executables=rep.executables,
            losses=[round(l, 6) for l in rep.losses],
            dispatches=dict(counts.by_label), compiles=comp.compiles,
            compile_s=round(comp.seconds, 1), cache_hits=comp.cache_hits,
            leaf_device_sets=dict(Counter(
                len(x.sharding.device_set) for x in jax.tree.leaves(state))),
            theta_device_sets=dict(Counter(
                len(x.sharding.device_set)
                for x in jax.tree.leaves(state["theta"]))),
            peak_bytes=_peak_bytes())
        if name == "4dev":
            # the frozen full-shape round as compiled for the 4 devices:
            # every prox call (two outputs per theta leaf) on one worker
            from repro.dist import monitor
            with monitor.compile_count() as c:
                hlo = eng.round_hlo(frozen=True)
            whole = 2 * sum(x.size for x in
                            jax.tree.leaves(eng.state_struct()["theta"]))
            share = _custom_call_elems(hlo) / whole
            stack_ags = _worker_stack_gathers(hlo, eng)
            log("chips:hlo", executable="frozen", kernels=_kernels_in(hlo),
                kernel_elems_per_device_over_stack=share,
                all_gathers=hlo.count(" all-gather("),
                worker_stack_all_gathers=stack_ags, compiles=c.compiles,
                compile_s=round(c.seconds, 1), cache_hits=c.cache_hits)
            assert stack_ags == 0 and share <= 0.25, (stack_ags, share)
    a, b = runs["4dev"], runs["1dev"]
    loss_err = float(np.max(np.abs(a["losses"] - b["losses"])
                            / np.abs(b["losses"])))
    z_err = float(np.linalg.norm(a["z"] - b["z"]) / np.linalg.norm(b["z"]))
    log("chips:compare", loss_rel_err=loss_err, loss_rtol=LOSS_RTOL,
        z_rel_err=z_err, z_rtol=Z_RTOL)
    assert loss_err <= LOSS_RTOL, loss_err
    assert z_err <= Z_RTOL, z_err


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devs = _devices_or_exit(args.chips)
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch.cache import setup_compile_cache
    log("setup", platform=devs[0].platform, kind=repr(devs[0].device_kind),
        devices=len(devs), compile_cache=setup_compile_cache(),
        jax=jax.__version__)
    if args.chips == 4:
        four_chips(args.seed, devs)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
