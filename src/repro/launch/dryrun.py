import os
# APPEND to any user-provided XLA_FLAGS rather than clobbering them (a
# user's dump/profiling flags must survive the dry-run); ours comes last
# so the forced device count wins if both set one.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512"
                           ).strip()

# --------------------------------------------------------------------------
# Multi-pod dry-run: lower + compile every (architecture x input shape) on
# the production mesh, prove it fits (memory_analysis), extract FLOPs/bytes
# (cost_analysis) and the collective schedule (HLO parse) for §Roofline.
#
# The XLA_FLAGS line above MUST precede every other import (jax locks the
# device count on first init); do not set it globally — smoke tests and
# benchmarks must see the single real CPU device.
# --------------------------------------------------------------------------

import argparse      # noqa: E402
import json          # noqa: E402
import subprocess    # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp                                  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P   # noqa: E402

from ..configs import ASSIGNED, SHAPES, get_config       # noqa: E402
from ..dist.hlo import axis_bytes, collective_stats, summarize  # noqa: E402
from ..dist.hlo_cost import weighted_cost                 # noqa: E402
from ..models import build                               # noqa: E402
from ..train.engine import Engine                        # noqa: E402
from .mesh import make_production_mesh                   # noqa: E402


def analyze(compiled, model: int, data: int, node: int = 4) -> dict:
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):     # jax<=0.4.x: one dict per program
        ca = ca[0] if ca else {}
    ma = compiled.memory_analysis()
    txt = compiled.as_text()
    # trip-count-weighted cost model (XLA's own counts scan bodies once)
    wc = weighted_cost(txt, model=model, data=data, node=node)
    colls = wc.collectives
    return {
        "flops_per_device": wc.flops,
        "bytes_per_device": wc.bytes,
        "xla_flops_unscaled": ca.get("flops", 0.0),
        "xla_bytes_unscaled": ca.get("bytes accessed", 0.0),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_hint_bytes": ma.argument_size_in_bytes
            + ma.output_size_in_bytes + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes,
        },
        "collectives": summarize(colls),
        "axis_fabric_bytes": axis_bytes(colls),
        "n_collectives": len(colls),
    }


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             frozen: bool = False, mask_mode: str = None,
             keep_rate: float = None, compact: bool = True,
             smoke: bool = False, comm_quant: str = None,
             wire_intra: str = None, wire_inter: str = None,
             wire_auto: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_sz, data_sz = axes["model"], axes["data"]
    cfg = get_config(arch, smoke=smoke)
    hp = cfg.hsadmm
    if mask_mode:
        hp = __import__("dataclasses").replace(hp, mask_mode=mask_mode)
    if keep_rate is not None:
        hp = __import__("dataclasses").replace(hp, keep_rate=keep_rate)
    if comm_quant:   # deprecated alias of --wire-inter q8
        hp = __import__("dataclasses").replace(hp, comm_quant=comm_quant)
    if wire_intra:
        hp = __import__("dataclasses").replace(hp, wire_intra=wire_intra)
    if wire_inter:
        hp = __import__("dataclasses").replace(hp, wire_inter=wire_inter)
    cfg = cfg.replace(hsadmm=hp)
    bundle = build(cfg)
    shape = SHAPES[shape_name]
    mesh_name = "multi_pod_2x16x16" if multi_pod else "single_pod_16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "frozen": frozen,
           "mask_mode": hp.mask_mode, "n_params": None}
    jax.set_mesh(mesh).__enter__()

    eng = Engine(bundle, mesh, shape)
    if not compact:
        cons = __import__("dataclasses").replace(
            eng.consensus, compact_from_level=len(eng.consensus.levels) + 1)
        eng = Engine(bundle, mesh, shape, consensus=cons)
    if wire_auto:
        from ..comm import AdaptiveWireSelector
        sel = AdaptiveWireSelector().select(eng)
        eng = sel.apply(eng)
        rec["wire_map"] = list(sel.spec_map)
        print("[wire-auto] " + sel.to_json())
    p0_shape = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    import math
    rec["n_params"] = sum(math.prod(x.shape)
                          for x in jax.tree.leaves(p0_shape))

    if shape.kind == "train":
        state = eng.state_struct()
        bshapes = bundle.train_inputs(shape, eng.workers)
        bsh = eng.batch_sharding(bshapes)
        batch = {k: _sds(v.shape, v.dtype, bsh[k]) for k, v in bshapes.items()}
        eta = jax.ShapeDtypeStruct((), jnp.float32)
        rec["consensus_levels"] = list(eng.consensus.levels)
        rec["workers"] = eng.workers

        node = eng.consensus.node_size
        t0 = time.time()
        low_l = eng.local_step_fn().lower(state, batch, eta)
        comp_l = low_l.compile()
        rec["local"] = analyze(comp_l, model_sz, data_sz, node)
        rec["local"]["compile_s"] = round(time.time() - t0, 1)

        t0 = time.time()
        low_c = eng.consensus_step_fn(frozen).lower(state)
        comp_c = low_c.compile()
        rec["consensus"] = analyze(comp_c, model_sz, data_sz, node)
        rec["consensus"]["compile_s"] = round(time.time() - t0, 1)
    else:
        psh = eng.serve_param_shardings()
        params = jax.tree.map(
            lambda l, s: _sds(l.shape, l.dtype, s), p0_shape, psh,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        B, S = shape.global_batch, shape.seq_len
        csh = eng.serve_cache_shardings(B, S)
        cache_shape = jax.eval_shape(lambda: bundle.init_cache(B, S))
        cache = jax.tree.map(lambda l, s: _sds(l.shape, l.dtype, s),
                             cache_shape, csh)
        dsz = data_sz * axes.get("pod", 1)
        tok_spec = P(tuple(n for n in ("pod", "data") if n in axes)) \
            if B % dsz == 0 and B >= dsz else P()
        tok_sh = NamedSharding(mesh, tok_spec)
        extras = {}
        for name, shp, dt in bundle.extra_inputs:
            e_spec = P(tok_spec[0] if len(tok_spec) else None,
                       *([None] * len(shp(shape))))
            extras[name] = _sds((B,) + shp(shape), dt,
                                NamedSharding(mesh, e_spec))
        t0 = time.time()
        if shape.kind == "prefill":
            toks = _sds((B, S), jnp.int32, tok_sh)
            fn = jax.jit(lambda p, t, c, **kw: bundle.prefill(p, t, c, **kw))
            low = fn.lower(params, toks, cache, **extras)
        else:
            # decode consumes cached cross-KV; modality extras are
            # prefill-only inputs
            toks = _sds((B, 1), jnp.int32, tok_sh)
            fn = jax.jit(lambda p, t, c: bundle.decode(p, t, c))
            low = fn.lower(params, toks, cache)
        comp = low.compile()
        rec["serve"] = analyze(comp, model_sz, data_sz)
        rec["serve"]["compile_s"] = round(time.time() - t0, 1)
    return rec


def cells_for(arch: str) -> list[str]:
    cfg = get_config(arch)
    if cfg.family == "cnn":
        return ["train_4k"]
    return [s for s in SHAPES if s not in cfg.skip_shapes]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--frozen", action="store_true")
    ap.add_argument("--mask-mode", default=None)
    ap.add_argument("--keep-rate", type=float, default=None)
    ap.add_argument("--dense", action="store_true",
                    help="disable compaction (dense-baseline ablation)")
    ap.add_argument("--quant", default=None,
                    help="DEPRECATED alias of --wire-inter q8 "
                         "(inter-pod wire format, int8)")
    ap.add_argument("--wire-intra", default=None,
                    help="intra-node wire codec spec (repro.comm)")
    ap.add_argument("--wire-inter", default=None,
                    help="top-boundary wire codec spec (repro.comm)")
    ap.add_argument("--wire-auto", action="store_true",
                    help="per-boundary codec map from "
                         "repro.comm.AdaptiveWireSelector (overrides "
                         "--wire-intra/--wire-inter)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--subprocess", action="store_true",
                    help="run each cell in a fresh process (bounded RSS)")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    archs = ASSIGNED if args.arch == "all" else [args.arch]
    os.makedirs(args.out, exist_ok=True)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for arch in archs:
        shapes = cells_for(arch) if args.shape == "all" else [args.shape]
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'mp' if mp else 'sp'}" + \
                    (f"_{args.tag}" if args.tag else "")
                path = os.path.join(args.out, tag + ".json")
                if args.subprocess:
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape, "--out",
                           args.out]
                    if mp:
                        cmd.append("--multi-pod")
                    for flag, val in [("--mask-mode", args.mask_mode),
                                      ("--keep-rate", args.keep_rate),
                                      ("--quant", args.quant),
                                      ("--wire-intra", args.wire_intra),
                                      ("--wire-inter", args.wire_inter)]:
                        if val is not None:
                            cmd += [flag, str(val)]
                    for flag, on in [("--frozen", args.frozen),
                                     ("--dense", args.dense),
                                     ("--smoke", args.smoke),
                                     ("--wire-auto", args.wire_auto)]:
                        if on:
                            cmd.append(flag)
                    if args.tag:
                        cmd += ["--tag", args.tag]
                    r = subprocess.run(cmd, capture_output=True, text=True)
                    ok = r.returncode == 0
                    print(("OK  " if ok else "FAIL") + f" {tag}")
                    if not ok:
                        failures.append(tag)
                        print(r.stdout[-2000:], r.stderr[-2000:])
                    continue
                try:
                    t0 = time.time()
                    rec = run_cell(arch, shape, mp, frozen=args.frozen,
                                   mask_mode=args.mask_mode,
                                   keep_rate=args.keep_rate,
                                   compact=not args.dense,
                                   smoke=args.smoke,
                                   comm_quant=args.quant,
                                   wire_intra=args.wire_intra,
                                   wire_inter=args.wire_inter,
                                   wire_auto=args.wire_auto)
                    rec["wall_s"] = round(time.time() - t0, 1)
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                    part = rec.get("local") or rec.get("serve")
                    print(f"OK   {tag}: peak/device="
                          f"{part['memory']['peak_hint_bytes']/2**30:.2f}GiB "
                          f"flops/dev={part['flops_per_device']:.3g} "
                          f"({rec['wall_s']}s)")
                except Exception:
                    failures.append(tag)
                    print(f"FAIL {tag}")
                    traceback.print_exc()
    if failures:
        print("FAILURES:", failures)
        sys.exit(1)
    print("all cells OK")


if __name__ == "__main__":
    main()
