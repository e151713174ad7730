"""Serving launcher: thin CLI over the ``repro.serve`` continuous-batching
tier, including the physically-shrunk ("pruned dense") serving mode — the
paper's inference acceleration claim: structured pruning yields a genuinely
SMALLER dense model (Table 1, last column).

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \\
        --smoke --batch 2 --prompt-len 16 --gen 8 --pruned

    # serve a training checkpoint (possibly saved by a reconfigured run)
    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \\
        --smoke --ckpt /tmp/run1 --replicas 2

The heavy lifting lives in :mod:`repro.serve`: :class:`BucketEngine`
compiles the per-bucket executable grid ahead of time,
:class:`ContinuousScheduler` runs the admit/decode/retire loop, and
:class:`ReplicaPool` serves N data-parallel replicas off one checkpoint.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from .cache import setup_compile_cache
from ..configs import get_config
from ..configs.base import ConsensusSpec
from ..core.shrinkage import compact_params
from ..core.sparsity import project
from ..models import build
from ..serve import (BucketEngine, ReplicaPool, Request, spec_for_workload)


def prune_params_compact(bundle, params):
    """Project params onto the sparsity plan, then PHYSICALLY SLICE the kept
    groups out — smaller dense weights, the paper's §4.4 applied at serve
    time.  Returns (compact params, keep masks)."""
    proj, masks = project(params, bundle.plan)
    idxs = {r.name: masks[r.name][1] for r in bundle.plan.rules}
    compact = compact_params(proj, bundle.plan, idxs)
    return compact, masks


def pruned_serving_bundle(bundle, params):
    """The ``--pruned`` serving mode as a function: project + compact the
    params and rebuild the model at the reduced width so GEMMs run at the
    compact size (paper Table 1, last column).  The width mapping is
    ``models.shrink_config`` — every compactable rule's group dimension
    becomes its keep budget (the FFN width-shrink branch shrinks the
    shared ``d_ff``; GQA-group rules shrink ``n_kv_heads``/``n_heads``,
    so the rebuilt model's shapes always match the compacted params).
    Returns (pruned bundle, compact params, masks)."""
    import dataclasses

    from ..models import build, shrink_config
    compact, masks = prune_params_compact(bundle, params)
    budgets = {r.name: r.keep for r in bundle.plan.rules}
    # strict=False: families without a full width mapping keep the
    # legacy serve-time behaviour (first ffn* rule shrinks d_ff)
    new_cfg = shrink_config(bundle.cfg, bundle.plan, budgets, strict=False)
    bundle2 = dataclasses.replace(build(new_cfg), cfg=new_cfg)
    return bundle2, compact, masks


def serving_bundle_from_state(engine, state):
    """Export a serving bundle straight from H-SADMM training state.

    The exported params are the top-level consensus ``z`` (the one
    vector every worker agrees on; ``theta`` in the solo degenerate
    case).  On a RECONFIGURED engine (``Engine.reconfigure``) the state
    is already at budget-B shapes and ``engine.bundle`` is already the
    shrunk model, so the export is a lead-dim squeeze — no round-trip
    expansion.  On a full-shape engine the frozen masks' kept-index set
    slices the compact params directly (no re-projection — serving uses
    exactly the mask the run converged to).  Returns (bundle, params)."""
    spec = engine.spec
    if spec.solo:
        params = jax.tree.map(lambda x: x[0], state["theta"])
    else:
        params = jax.tree.map(lambda z: z[0], state["z"][-1])
    if engine.reconfigured:
        return engine.bundle, params
    eng2, _ = engine.reconfigure(masks=state["masks"])
    idxs = {r.name: state["masks"][r.name]["idx"]
            for r in engine.bundle.plan.rules}
    compact = compact_params(params, engine.bundle.plan, idxs)
    return eng2.bundle, compact


def bundle_from_checkpoint(ckpt_dir: str, *, arch: str = None,
                           smoke: bool = False, cfg=None, log=None):
    """Restore a serving ``(bundle, params)`` from a training checkpoint.

    Mirrors the training loop's resume path: pick the newest complete
    save, read its meta FIRST to learn whether the run had physically
    reconfigured (shrunk shapes + frozen masks in the aux channel), build
    the matching engine, ``restore_elastic`` into its state template, and
    route the result through :func:`serving_bundle_from_state` — so a
    reconfigured save serves at the shrunk widths with no round-trip
    expansion, and a full-shape save is compacted with exactly the mask
    state the run converged to.
    """
    from ..dist import checkpoint as ckpt
    from ..train.engine import Engine
    from ..train.loop import _masks_from_aux
    from .mesh import make_host_mesh

    last = ckpt.latest(ckpt_dir)
    if last is None:
        raise FileNotFoundError(f"no complete checkpoint under {ckpt_dir!r}")
    meta = ckpt.read_meta(last)
    if cfg is None:
        # cfg override: a save whose run customized the arch/hsadmm
        # config needs the SAME config to rebuild matching plan shapes
        cfg = get_config(arch or meta.get("arch"), smoke=smoke)
    if meta.get("arch") not in (None, cfg.name) and log:
        log(f"[serve] WARNING: checkpoint arch {meta['arch']!r} != "
            f"requested {cfg.name!r}")
    bundle = build(cfg)
    levels = tuple(meta.get("levels") or (1,))
    engine = Engine(bundle, make_host_mesh(),
                    consensus=ConsensusSpec(levels=levels,
                                            compact_from_level=1))
    restore_eng = engine
    if meta.get("reconfigured"):
        masks_full = _masks_from_aux(ckpt.load_aux(last), bundle.plan)
        restore_eng, _ = engine.reconfigure(masks=masks_full)
    tmpl = jax.eval_shape(
        lambda: restore_eng.init_state_fn()(jax.random.PRNGKey(0)))
    tmpl = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tmpl)
    state, meta2 = ckpt.restore_elastic(last, tmpl, engine.workers)
    state = jax.device_put(state, restore_eng.state_shardings())
    if log:
        log(f"[serve] restored {last} (step {meta2.get('step')}"
            + (", reconfigured" if meta.get("reconfigured") else "") + ")")
    serve_bundle, params = serving_bundle_from_state(restore_eng, state)
    return serve_bundle, params, meta


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=2,
                    help="number of requests to serve")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--pruned", action="store_true",
                    help="serve the physically-shrunk model")
    ap.add_argument("--ckpt", default=None, metavar="DIR",
                    help="restore weights (and pruning state) from a "
                         "training checkpoint directory")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel serving replicas off one "
                         "checkpoint")
    ap.add_argument("--lanes", type=int, default=4,
                    help="decode lanes per sequence bucket")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature compiled into the decode "
                         "executable (0 = greedy argmax, the default)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (only used when "
                         "--temperature > 0)")
    args = ap.parse_args(argv)
    setup_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    key = jax.random.PRNGKey(0)
    if args.ckpt:
        bundle, params, _ = bundle_from_checkpoint(
            args.ckpt, arch=args.arch, smoke=args.smoke, log=print)
    else:
        bundle = build(cfg)
        params = bundle.init(key)
        if args.pruned:
            bundle, params, _ = pruned_serving_bundle(bundle, params)
    if args.pruned or args.ckpt:
        if cfg.family == "cnn":
            print(f"[serve] serving widths: stem {bundle.cfg.cnn_stem}, "
                  f"streams {bundle.cfg.cnn_outs}, mid {bundle.cfg.cnn_cmid}")
        elif cfg.family == "moe":
            print(f"[serve] serving widths: experts {bundle.cfg.n_experts} "
                  f"(top-{bundle.cfg.moe_top_k}), d_expert "
                  f"{bundle.cfg.d_expert_eff}, shared d "
                  f"{bundle.cfg.d_shared_eff}, kv heads "
                  f"{bundle.cfg.n_kv_heads}")
        else:
            print(f"[serve] serving widths: d_ff {bundle.cfg.d_ff}, "
                  f"kv heads {bundle.cfg.n_kv_heads}")

    B, P, G = args.batch, args.prompt_len, args.gen
    if bundle.decode is None:      # CNN family: batched classify requests
        spec = spec_for_workload(P, G, lanes=args.lanes,
                                 batch_buckets=(1, max(B, 1)))
    else:
        spec = spec_for_workload(P, G, lanes=args.lanes,
                                 batch_buckets=(1, 2))
    t0 = time.time()
    engine = BucketEngine(bundle, spec, params_like=params,
                          temperature=args.temperature, top_p=args.top_p)
    print(f"[serve] compiled {engine.num_executables} executables in "
          f"{time.time() - t0:.1f}s; cache {engine.cache_bytes()} B "
          f"across seq buckets {spec.seq_buckets}")
    pool = ReplicaPool(engine, params, replicas=args.replicas)

    rng = np.random.default_rng(0)
    if bundle.decode is None:
        s = bundle.cfg.img_size
        for i in range(B):
            pool.submit(Request(
                rid=i, image=rng.normal(size=(s, s, 3)).astype(np.float32)))
    else:
        for i in range(B):
            p = int(rng.integers(max(P // 2, 1), P + 1))
            pool.submit(Request(
                rid=i, prompt=rng.integers(0, cfg.vocab, size=(p,)),
                max_new=G))
    t0 = time.time()
    comps = pool.run_until_idle()
    dt = time.time() - t0
    comps.sort(key=lambda c: c.rid)
    if bundle.decode is None:
        print(f"[serve] classified {len(comps)} images in {dt*1e3:.1f} ms "
              f"({len(comps)/max(dt, 1e-9):.1f} img/s); dispatches "
              f"{pool.dispatches}")
        print("[serve] labels:", [c.label for c in comps])
    else:
        toks = pool.tokens_out
        print(f"[serve] {len(comps)} requests, {toks} tokens in "
              f"{dt*1e3:.1f} ms ({toks/max(dt, 1e-9):.1f} tok/s); "
              f"dispatches {pool.dispatches}")
        print("[serve] generated:", [c.tokens for c in comps])


if __name__ == "__main__":
    main()
