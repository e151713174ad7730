#!/usr/bin/env python3
"""Chip benchmark of PruneX's H-SADMM training round: one cell, one run.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell's files are found by name (``cells.py``).  A run builds the
system's engine for the cell, compiles its fused round executable ahead of
time (JAX's persistent compilation cache lives in ``.jax_cache`` at the
checkout's root), makes the state and the input pool from ``--seed``,
drives the first rounds as warm-up, and then dispatches rounds for
``--seconds``.  With ``--trace 0`` the end-to-end metrics are reported.
With ``--trace 1`` the JAX profiler traces the window's first drains, up
to ``TRACE_SECONDS``; once it has stopped, the window runs on for
``--seconds``.  The per-layer metrics are reported: those of the trace
over the traced rounds, those of the host clock over the rest of the
window, which the profiler does not slow.  After the window the
system's state is freed and the plain reference (``reference/``) repeats
the first rounds: ``correct`` is the comparison of the two
(``check.py``) within the cell's limits.

The last line of standard output is one JSON object: ``correct``,
``attempted`` and ``failed`` (rounds in the window, and those whose
losses were not finite), ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its
limit.  The same numbers end standard error.  Without TPUs, or with fewer
than the cell needs, the run exits non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
# a traced run traces the window's first drains up to this many seconds;
# its per-layer metrics are over those rounds
TRACE_SECONDS = 2.0
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_events = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0}
_listening = False


def _listen():
    """Count backend compiles, their seconds and persistent-cache hits
    (one listener per process: JAX cannot remove one)."""
    global _listening
    if _listening:
        return
    import jax

    def on_duration(name, secs, **_):
        if name == _COMPILE:
            _events["compiles"] += 1
            _events["compile_s"] += secs

    def on_event(name, **_):
        if name == _CACHE_HIT:
            _events["cache_hits"] += 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    _listening = True


def setup_cache():
    """JAX's persistent compilation cache at one fixed directory inside
    the checkout, every program cached."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def profile_options():
    """The profiler's options in a traced run: the host tracer keeps the
    loop's own annotations and leaves out the runtime's and Python's
    calls, which the reduction does not read."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    return opts


def untraced_part(win: dict, traced: dict) -> dict:
    """The window's rounds, seconds and input wait after the profiler
    stopped: what the host clock reads in a traced run."""
    return {"rounds": win["rounds"] - traced["rounds"],
            "seconds": win["t_start"] + win["seconds"] - traced["resumed"],
            "input_wait_s": win["input_wait_s"] - traced["input_wait_s"]}


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devices,
             t0: float) -> dict:
    """Everything after the look for chips: set-up, warm-up, window,
    reference, metrics.  Returns the result object."""
    import jax
    import numpy as np
    from benchmarks.chip import cells, check, flops, generator, peaks, \
        session, tracing

    _listen()
    before = dict(_events)
    conf, traffic = cell["config"], cell["traffic"]
    t_imp = time.perf_counter()
    pool = generator.ImagePool(seed, traffic, conf["arch"],
                               session.per_worker_batch(cell))
    t_pool = time.perf_counter()
    sess = session.Session(cell, devices, seed)
    hlo_text = sess.hlo_text
    t_build = time.perf_counter()
    it = sess.feed(pool)
    rec = sess.warm_up(it)
    t_warm = time.perf_counter()
    log(f"[setup] until_imports_s={t_imp - t0:.3f} pool_s={t_pool - t_imp:.3f} "
        f"engine_compile_state_s={t_build - t_pool:.3f} "
        f"warm_up_s={t_warm - t_build:.3f}")
    setup = {"seconds": time.perf_counter() - t0,
             "compiles": _events["compiles"] - before["compiles"],
             "compile_s": _events["compile_s"] - before["compile_s"],
             "cache_hits": _events["cache_hits"] - before["cache_hits"]}
    log(f"[setup] seconds={setup['seconds']:.3f} "
        f"compiles={setup['compiles']} compile_s={setup['compile_s']:.3f} "
        f"cache_hits={setup['cache_hits']} cache_dir={CACHE_DIR}")

    annotate = on_drain = None
    traced = {}
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR,
                                 profiler_options=profile_options())
        annotate = jax.profiler.TraceAnnotation

        def on_drain(so_far):
            # the rest of the window, which the host-clock metrics read,
            # runs for the whole ``seconds`` once the profiler has stopped
            if not traced and so_far["seconds"] >= TRACE_SECONDS:
                jax.profiler.stop_trace()
                traced.update(so_far, resumed=time.perf_counter())
                return traced["resumed"] + seconds
    before = dict(_events)
    gen_before = pool.seconds
    win = sess.window(it, seconds, annotate=annotate, on_drain=on_drain)
    if trace and not traced:
        jax.profiler.stop_trace()
        traced.update(win, resumed=win["t_start"] + win["seconds"])
    host = untraced_part(win, traced) if trace else win
    in_window = _events["compiles"] - before["compiles"]
    log(f"[window] rounds={win['rounds']} seconds={win['seconds']:.6f} "
        f"compiles={in_window} input_wait_s={win['input_wait_s']:.6f} "
        f"generator_s_per_round="
        f"{(pool.seconds - gen_before) / win['rounds']:.6f}")
    if trace:
        log(f"[window] traced rounds={traced['rounds']} "
            f"seconds={traced['seconds']:.6f}; untraced rounds="
            f"{host['rounds']} seconds={host['seconds']:.6f}")
    peak = session.peak_bytes(sess.devices)
    used = sess.devices
    pool.stop = True
    for _ in it:          # let the prefetch thread finish
        pass
    sess.free()
    del sess, it
    gc.collect()

    t_ref = time.perf_counter()
    ref = check.reference_readings(cell, pool, session.seed_key(seed),
                                   device=used[0])
    numbers = check.compare(rec, ref)
    log(f"[reference] seconds={time.perf_counter() - t_ref:.3f}")
    log("[compare] losses system=" + json.dumps(np.round(np.asarray(
        rec["losses"], np.float64), 5).tolist()) + " reference=" + json.dumps(
        np.round(np.asarray(ref["losses"], np.float64), 5).tolist()))
    limits = cell["limits"]
    correct = check.verdict(numbers, limits) \
        and win["non_finite_rounds"] == 0 and in_window == 0

    arch_run = check.reference_arch(cell)
    pk = peaks.peaks(used[0].device_kind)
    ctx = {"cell": cell, "window": host, "traced": traced, "setup": setup,
           "chips": cell["chips"], "peaks": pk,
           "images_per_round": session.images_per_round(cell),
           "flops_per_image": flops.train_flops_per_image(arch_run),
           "trace": None, "notes": []}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        t_tr = time.perf_counter()
        ex = tracing.extract(TRACE_DIR, hlo_text)
        ctx["trace"] = tr = tracing.clip(ex, *tracing.window_of(ex))
        with gzip.open(os.path.join(TRACE_DIR, "extract.json.gz"), "wt") as f:
            json.dump(tr, f)
        span = tr["window"][1] - tr["window"][0]
        busy = [tracing.busy_ns(ops) for ops in tr["devices"].values()]
        device["busy_s"] = sum(busy) / max(len(busy), 1) / 1e9
        device["window_s"] = span / 1e9
        breakdown = {"device_ops": tracing.top_ops(tr),
                     "idle_gaps": tracing.idle_gaps(tr)}
        log(f"[trace] events={sum(map(len, tr['devices'].values()))} "
            f"read_s={time.perf_counter() - t_tr:.3f}")
        if host["rounds"]:
            log(f"[trace] busy_s_per_traced_round="
                f"{device['busy_s'] / traced['rounds']:.6f} "
                f"untraced_s_per_round={host['seconds'] / host['rounds']:.6f}")
    metrics = {}
    for name in cells.names("metrics"):
        read, unit, kind = cells.metric_reader(name)
        if (kind == "per_layer") != bool(trace):
            continue
        value = read(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    for note in ctx["notes"]:
        log(f"[metrics] {note}")

    log("[compare] " + " ".join(f"{n}={v}" for n, v in numbers.items()
                                if n not in limits))
    checks = {n: {"value": numbers.get(n), "limit": lim}
              for n, lim in limits.items()}
    for n, c in checks.items():
        log(f"[check] {n}={c['value']} limit={c['limit']}")
    result = {"correct": bool(correct), "attempted": win["rounds"],
              "failed": win["non_finite_rounds"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmarks.chip import cells
    cell = cells.load(args.workload)
    setup_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        log(f"run.py: {args.workload} needs {cell['chips']} TPU chip(s); "
            f"JAX sees {len(devs)} {devs[0].platform} device(s)")
        return 3
    sys.path.insert(0, os.path.join(ROOT, "src"))
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs,
                      T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
