"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \\
        --smoke --outer-iters 20 --batch 8 --seq 64 --workers 4

The mesh is every locally visible device (``launch.mesh.make_host_mesh``):
one TPU chip, a four-chip host, or the CPU in tests; the engine/loop are
mesh-agnostic.  ``--baseline ddp|topk`` runs the paper's comparison
trainers instead of H-SADMM.
"""
from __future__ import annotations

import argparse
import json

import jax

from .cache import setup_compile_cache
from ..configs import SHAPES, get_config
from ..configs.base import ConsensusSpec, ShapeConfig
from ..models import build
from ..train.engine import Engine
from ..train.loop import RunConfig, train
from ..train import baselines
from ..dist import ft
from .mesh import make_host_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="architecture name (required unless --from-json)")
    ap.add_argument("--from-json", default=None, metavar="WINNER",
                    help="launch a repro.tune winner spec "
                         "(experiments/tune/winner_<topology>.json): the "
                         "engine and RunConfig are rebuilt from the spec "
                         "verbatim; every other config flag is ignored")
    ap.add_argument("--outer-iters-override", type=int, default=None,
                    help="with --from-json: cap/override the spec's "
                         "outer_iters (smoke-launching a winner)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--shape", default=None, help="named shape (train_4k)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--node-size", type=int, default=2)
    ap.add_argument("--outer-iters", type=int, default=20)
    ap.add_argument("--eta", type=float, default=1e-3)
    ap.add_argument("--keep-rate", type=float, default=None)
    ap.add_argument("--mask-mode", default=None)
    ap.add_argument("--wire-intra", default=None, metavar="CODEC",
                    help="wire codec of the intra-node boundaries "
                         "(repro.comm spec: dense | q8 | topk:<rate> | "
                         "compact+q8)")
    ap.add_argument("--wire-inter", default=None, metavar="CODEC",
                    help="wire codec of the top inter-node (slow fabric) "
                         "boundary; also applied to --baseline trainers")
    ap.add_argument("--wire-auto", action="store_true",
                    help="measurement-driven per-boundary codec selection "
                         "(repro.comm.AdaptiveWireSelector): score every "
                         "candidate per fabric level from predicted ring "
                         "bytes + a measured encode probe, then train on "
                         "the chosen boundary->codec map (overrides "
                         "--wire-intra/--wire-inter); re-selects on the "
                         "shrunk byte model at the --reconfig point")
    ap.add_argument("--staleness", type=int, default=None, choices=[0, 1],
                    help="overlapped-round depth: 0 = sequential round "
                         "(default), 1 = round r's inter-node reduce "
                         "overlaps round r+1's local prox-SGD scan "
                         "(one-round-stale z, bounded-staleness "
                         "async-ADMM)")
    ap.add_argument("--baseline", default=None, choices=["ddp", "topk"])
    ap.add_argument("--flat", action="store_true",
                    help="PruneX (AR) flat-consensus ablation")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--ckpt-keep", type=int, default=None)
    ap.add_argument("--drop-worker", default=None,
                    help="j:k0:k1 — fail worker j during [k0,k1)")
    ap.add_argument("--straggler", default=None,
                    help="j:factor[:halflife] — down-weight worker j")
    ap.add_argument("--reconfig", action="store_true",
                    help="physically reconfigure once masks freeze: "
                         "migrate the whole H-SADMM state onto budget-B "
                         "shapes and retrace the frozen round executable "
                         "over the smaller dense model")
    ap.add_argument("--reconfig-patience", type=int, default=None,
                    help="frozen rounds to wait before the retrace "
                         "(default: HsadmmConfig.reconfig_patience)")
    ap.add_argument("--legacy-rounds", action="store_true",
                    help="per-step dispatch instead of the fused round "
                         "executable (equivalence / dispatch-overhead "
                         "comparisons)")
    ap.add_argument("--metrics-every", type=int, default=5,
                    help="drain the async round-metrics stream every N "
                         "rounds (fused mode; 1 = sync every round)")
    ap.add_argument("--hlo-stats", action="store_true",
                    help="report the measured collective schedule "
                         "(parsed from the compiled HLO) next to the "
                         "analytic plan_bytes volumes")
    ap.add_argument("--report", default=None, help="write JSON report here")
    args = ap.parse_args(argv)
    setup_compile_cache()

    if args.from_json:
        import dataclasses
        from ..tune.artifacts import load_winner
        eng, run, cand = load_winner(args.from_json)
        if args.outer_iters_override is not None:
            run = dataclasses.replace(
                run, outer_iters=args.outer_iters_override)
        print(f"[from-json] launching {cand.name} "
              f"({run.outer_iters} outer iters, wire_map="
              f"{list(run.wire_map) if run.wire_map else None})")
        _, rep = train(eng, run)
        _finish(args, rep)
        return
    if not args.arch:
        ap.error("--arch is required unless --from-json is given")

    cfg = get_config(args.arch, smoke=args.smoke)
    hp = cfg.hsadmm
    import dataclasses
    if args.keep_rate is not None:
        hp = dataclasses.replace(hp, keep_rate=args.keep_rate)
    if args.mask_mode:
        hp = dataclasses.replace(hp, mask_mode=args.mask_mode)
    if args.wire_intra:
        hp = dataclasses.replace(hp, wire_intra=args.wire_intra)
    if args.wire_inter:
        hp = dataclasses.replace(hp, wire_inter=args.wire_inter)
    cfg = cfg.replace(hsadmm=hp)
    bundle = build(cfg)
    shape = SHAPES[args.shape] if args.shape else ShapeConfig(
        "cli", "train", args.seq, args.batch)

    if args.baseline == "ddp":
        _, rep = baselines.ddp_train(bundle, args.workers, shape,
                                     steps=args.outer_iters * hp.local_steps,
                                     eta=args.eta, log=print,
                                     codec=args.wire_inter or "dense")
    elif args.baseline == "topk":
        _, rep = baselines.topk_train(bundle, args.workers, shape,
                                      steps=args.outer_iters * hp.local_steps,
                                      eta=args.eta, log=print,
                                      codec=args.wire_inter)
    else:
        mesh = make_host_mesh()
        W = args.workers
        ns = min(args.node_size, W)
        cons = ConsensusSpec(levels=(ns, W // ns) if W // ns > 1 else (ns, 1),
                             compact_from_level=1,
                             granularity="flat" if args.flat else "chip")
        if args.flat:
            cons = ConsensusSpec(levels=(W,), compact_from_level=1,
                                 granularity="flat")
        eng = Engine(bundle, mesh, shape, consensus=cons)
        policies = []
        if args.drop_worker:
            try:
                j, k0, k1 = map(int, args.drop_worker.split(":"))
            except ValueError:
                ap.error(f"--drop-worker expects j:k0:k1, "
                         f"got {args.drop_worker!r}")
            policies.append(ft.fail_window({j: (k0, k1)}))
        if args.straggler:
            try:
                parts = args.straggler.split(":")
                j, factor = int(parts[0]), float(parts[1])
                halflife = int(parts[2]) if len(parts) > 2 else 0
            except (ValueError, IndexError):
                ap.error(f"--straggler expects j:factor[:halflife], "
                         f"got {args.straggler!r}")
            policies.append(ft.straggler_decay({j: factor},
                                               halflife=halflife))
        run = RunConfig(outer_iters=args.outer_iters, shape=shape,
                        eta=args.eta, ckpt_dir=args.ckpt_dir,
                        ckpt_every=args.ckpt_every, ckpt_keep=args.ckpt_keep,
                        ft_policy=ft.compose(*policies) if policies else None,
                        fused_rounds=not args.legacy_rounds,
                        metrics_every=args.metrics_every,
                        reconfig=args.reconfig,
                        reconfig_patience=args.reconfig_patience,
                        hlo_stats=args.hlo_stats,
                        wire_auto=args.wire_auto,
                        staleness=args.staleness)
        _, rep = train(eng, run)
        if rep.reconfigured_at is not None and rep.comm_bytes_internode:
            print(f"[train] physically reconfigured at outer iter "
                  f"{rep.reconfigured_at}; frozen-round payload "
                  f"{rep.comm_bytes_internode[-1]/1e6:.3f}MB vs dense "
                  f"{rep.comm_bytes_dense_equiv[-1]/1e6:.3f}MB")
        if rep.hlo_comm:
            for name, h in rep.hlo_comm.items():
                print(f"[hlo:{name}] collectives="
                      f"{h['summary']['total_count']} "
                      f"wire={h['summary']['total_wire_bytes']/1e6:.3f}MB "
                      f"internode={h['internode_bytes']/1e6:.3f}MB "
                      f"by_fabric={h['axis_bytes']}")
    _finish(args, rep)


def _finish(args, rep):
    if args.report:
        with open(args.report, "w") as f:
            json.dump({k: v for k, v in rep.__dict__.items()
                       if k != "final_engine"}, f, indent=1)
    if rep.losses:
        print("final loss:", rep.losses[-1])
    else:
        print("no iterations run (checkpoint already at/after "
              "the configured outer iteration count)")


if __name__ == "__main__":
    main()
