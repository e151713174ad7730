#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip.

    python benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 1,2,... [--control-seeds a,b,c] [--kinds k1,k2] \\
        [--out file.json]

In one process: for every ``--seeds`` seed, the system's first rounds
(``Session.warm_up``, the timed path's executable and feed) against the
reference, which gives the lower readings.  For every ``--control-seeds``
seed, the reference put in the system's place in two lower precisions
(``control``: parameters held in bfloat16; ``bf16_compute``: the local
step computed in bfloat16 over float32 parameters), and the reference
with each planted fault (``half_batch``; with more than one node,
``drop_top_exchange``; in a dynamic round, ``mask_reversed`` and, with
more than one node, ``mask_unsynced``), each against the float32
reference: the upper readings.  A state left unchanged reads 1 on every leaf number
and needs no run.  Prints one JSON line per reading and a summary; the
benchmark's own runs never run this.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def _plain(x):
    """Readings as JSON: arrays to lists, numbers to floats."""
    import numpy as np
    if isinstance(x, dict):
        return {str(k): _plain(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_plain(v) for v in x]
    if isinstance(x, np.ndarray):
        return x.tolist()
    return float(x)


def calibrate(cell, devices, seeds, control_seeds, emit, kinds=None):
    import jax.numpy as jnp
    from benchmarks.chip import check, generator, session
    out = {"sound": {}, "raw": {}}
    batch = session.per_worker_batch(cell)
    sess = None
    for seed in seeds:
        pool = generator.ImagePool(seed, cell["traffic"],
                                   cell["config"]["arch"], batch)
        if sess is None:
            sess = session.Session(cell, devices, seed)
        else:
            sess.start(seed)
        it = sess.feed(pool)
        rec = sess.warm_up(it)
        pool.stop = True
        for _ in it:
            pass
        sess.state = None
        gc.collect()
        key = session.seed_key(seed)
        ref = check.reference_readings(cell, pool, key, device=devices[0])
        out["raw"][seed] = {"system": _plain(rec), "reference": _plain(ref)}
        out["sound"][seed] = check.compare(rec, ref)
        emit("sound", seed, out["sound"][seed])
        if seed not in control_seeds:
            continue
        t = cell["traffic"]
        nodes = len(t["levels"]) > 1 and t["workers"] > t["levels"][0]
        runs = {"control": dict(dtype=jnp.bfloat16),
                "bf16_compute": dict(compute_dtype=jnp.bfloat16),
                "half_batch": dict(half_batch=True)}
        if nodes:
            runs["drop_top_exchange"] = dict(drop_top_exchange=True)
        if t["phase"] == "dynamic":
            runs["mask_reversed"] = dict(mask_fault="reversed")
            if nodes:
                runs["mask_unsynced"] = dict(mask_fault="unsynced")
        for kind, kw in runs.items():
            if kinds is not None and kind not in kinds:
                continue
            got = check.reference_readings(cell, pool, key,
                                           device=devices[0], **kw)
            out["raw"][seed][kind] = _plain(got)
            out.setdefault(kind, {})[seed] = check.compare(got, ref)
            emit(kind, seed, out[kind][seed])
    summary = {}
    for kind, by_seed in out.items():
        if not by_seed or kind == "raw":
            continue
        agg = max if kind == "sound" else min
        summary[kind] = {n: agg(r[n] for r in by_seed.values())
                         for n in check.NUMBERS
                         if all(n in r for r in by_seed.values())}
    out["summary"] = summary
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--kinds", type=lambda s: set(s.split(",")),
                    help="only these control and fault runs (default: all)")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from benchmarks.chip import cells, run
    cell = cells.load(args.workload)
    run.setup_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"calibrate.py: {args.workload} needs {cell['chips']} TPU "
              f"chip(s)", file=sys.stderr)
        return 3
    sys.path.insert(0, os.path.join(ROOT, "src"))
    t0 = time.perf_counter()

    def emit(kind, seed, numbers):
        print(json.dumps({"kind": kind, "seed": seed, "numbers": numbers,
                          "t": round(time.perf_counter() - t0, 1)}),
              flush=True)
    out = calibrate(cell, devs, args.seeds, set(args.control_seeds), emit,
                    args.kinds)
    print(json.dumps({"summary": out["summary"]}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
