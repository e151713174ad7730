"""The system under test, driven as a training job drives it.

``Session`` builds the engine through the normal path
(``repro.configs.get_config`` -> ``repro.models.build`` -> ``Engine`` over
``make_host_mesh``), compiles the cell's fused round executable once,
ahead of time, and makes the state on the device from the seed.
``warm_up`` drives that executable through the first rounds, recording
what the correctness comparison needs, and ``window`` dispatches it for a
fixed time with ``train()``'s discipline: one dispatch per round, the
state donated, the round metrics drained with one ``device_get`` every
``DRAIN_EVERY`` rounds, and the input through the program's
``superbatches`` and ``prefetch``.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

WARM_ROUNDS = 3
DRAIN_EVERY = 5       # train()'s default RunConfig.metrics_every


def seed_key(seed: int):
    """The PRNG key of ``--seed``: every bit of a seed up to 64 bits
    counts."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def per_worker_batch(cell: dict) -> int:
    return cell["config"]["per_worker_batch"]


def images_per_round(cell: dict) -> int:
    t = cell["traffic"]
    return (cell["config"]["hsadmm"]["local_steps"] * t["workers"]
            * per_worker_batch(cell))


def _leaf_norms(tree) -> dict:
    """{leaf path: Frobenius norm over the whole leaf}."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p, simple=True, separator="/"):
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in flat}


class Session:
    """One cell's engine, compiled round executable and state."""

    def __init__(self, cell: dict, devices, seed: int):
        self._build(cell, devices, seed_key(seed))
        self.start(seed)

    def start(self, seed: int):
        """A fresh state on the device from ``seed``."""
        self.key = seed_key(seed)
        self.state = None           # free the old state before the new one
        self.state = self._init(self.key)

    def _build(self, cell: dict, devices, key):
        from repro.configs import get_config
        from repro.configs.base import ConsensusSpec, ShapeConfig
        from repro.launch.mesh import make_host_mesh
        from repro.models import build
        from repro.train.engine import Engine

        conf, traffic = cell["config"], cell["traffic"]
        cfg = get_config(conf["registry"])
        stated = dict(conf["hsadmm"])
        momentum = stated.pop("momentum")
        cfg = cfg.replace(hsadmm=dataclasses.replace(cfg.hsadmm, **stated))
        check_arch(cfg, conf["arch"])
        levels = tuple(traffic["levels"])
        shape = ShapeConfig(cell["name"], "train", 0,
                            traffic["workers"] * per_worker_batch(cell))
        engine = Engine(build(cfg), make_host_mesh(
            devices=devices[:cell["chips"]]), shape,
            consensus=ConsensusSpec(levels=levels, compact_from_level=1,
                                    granularity="chip", node_size=levels[0]))
        if traffic["phase"] == "reconfigured":
            masks = jax.jit(lambda k: engine.init_state_fn()(k)["masks"])(
                key)
            engine, _ = engine.reconfigure(masks=masks)
        elif traffic["phase"] != "dynamic":
            raise ValueError(f"unknown phase {traffic['phase']!r}")
        if engine.spec.momentum != momentum:
            raise ValueError(f"the engine's momentum is "
                             f"{engine.spec.momentum}, the file states "
                             f"{momentum}")
        self.engine = engine
        self.frozen = traffic["phase"] == "reconfigured"
        self.eta = jnp.float32(conf["eta"])
        sb = engine.superbatch_struct()
        self.superbatch_shardings = {k: v.sharding for k, v in sb.items()}
        # the configuration states the convolutions' precision
        with jax.default_matmul_precision(conf["precision"]["matmul"]):
            self.step = engine.round_step_fn(self.frozen).lower(
                engine.state_struct(), sb,
                jax.ShapeDtypeStruct((), jnp.float32)).compile()
        self.hlo_text = self.step.as_text()
        self._mom_norms = jax.jit(lambda st: _leaf_norms(st["mom"]))
        self._change_norms = jax.jit(self._changes)
        self._init = engine.init_state_fn()
        self.devices = list(devices[:cell["chips"]])

    def _changes(self, state, key):
        """Norms of what the rounds changed: theta and every z level from
        the initial parameters, every dual v from zero."""
        p0 = self.engine.bundle.init(key)
        out = {"theta": _leaf_norms(jax.tree.map(
            lambda t, p: t - p[None], state["theta"], p0))}
        for lvl, z in enumerate(state["z"]):
            out[f"z{lvl + 1}"] = _leaf_norms(jax.tree.map(
                lambda t, p: t - p[None], z, p0))
        for lvl, v in enumerate(state["v"]):
            out[f"v{lvl + 1}"] = _leaf_norms(v)
        return out

    def feed(self, pool):
        """The program's input path over the benchmark's loader: E steps
        stacked per round by ``superbatches``, laid out on the round's
        input shardings and prefetched by a background thread."""
        from repro.data.pipeline import prefetch, superbatches
        E = self.engine.cfg.hsadmm.local_steps

        def put():
            for sb in superbatches(pool.stream(), E):
                if pool.stop:
                    return
                yield jax.device_put(sb, self.superbatch_shardings)
        return prefetch(put())

    def warm_up(self, it) -> dict:
        """The first ``WARM_ROUNDS`` rounds through the window's own call
        and feed, with what the correctness comparison reads: each round's
        losses and masks (by pruning class), the momenta after round 1,
        and the changes after the last round."""
        rec = {"losses": [], "masks": []}
        for r in range(WARM_ROUNDS):
            self.state, m = self.step(self.state, next(it), self.eta)
            rec["losses"].append(np.asarray(jax.device_get(m.losses)))
            rec["masks"].append({k.split(":")[-1]: np.asarray(v["mask"])
                                 for k, v in jax.device_get(
                                     self.state["masks"]).items()})
            if r == 0:
                rec["mom"] = jax.device_get(self._mom_norms(self.state))
        rec["change"] = jax.device_get(
            self._change_norms(self.state, self.key))
        rec["losses"] = np.stack(rec["losses"])
        return rec

    def window(self, it, seconds: float, annotate=None,
               on_drain=None) -> dict:
        """Dispatch rounds for ``seconds``; every dispatched round is
        waited for and counted.  ``annotate(name)`` gives a context
        manager around each host phase (the profiler's annotation in a
        traced run); ``on_drain(so_far)`` is called after each drain,
        when every round dispatched so far has finished, with the
        window's counts up to then, and may return a new deadline (a
        ``time.perf_counter()`` reading)."""
        import contextlib
        ann = annotate or (lambda name: contextlib.nullcontext())
        pending, rounds, bad = [], 0, 0
        wait = 0.0

        def drain():
            nonlocal bad
            with ann("bench.drain"):
                vals = jax.device_get(pending)
            bad += sum(1 for m in vals if not np.all(np.isfinite(m.losses)))
            pending.clear()

        t_start = time.perf_counter()
        deadline = t_start + seconds
        while True:
            t = time.perf_counter()
            with ann("bench.input_next"):
                sb = next(it)
            wait += time.perf_counter() - t
            with ann("bench.dispatch"):
                self.state, m = self.step(self.state, sb, self.eta)
            pending.append(m)
            rounds += 1
            if rounds % DRAIN_EVERY == 0:
                drain()
                if on_drain is not None:
                    moved = on_drain({
                        "rounds": rounds, "input_wait_s": wait,
                        "seconds": time.perf_counter() - t_start})
                    if moved is not None:
                        deadline = moved
            if time.perf_counter() >= deadline:
                break
        drain()
        with ann("bench.drain"):
            jax.block_until_ready(self.state)
        seconds_run = time.perf_counter() - t_start
        return {"rounds": rounds, "seconds": seconds_run,
                "non_finite_rounds": bad, "input_wait_s": wait,
                "t_start": t_start}

    def free(self):
        """Drop the state and executables so the reference has the chip."""
        self.state = None
        self.step = None
        self._mom_norms = self._change_norms = self._init = None


def check_arch(cfg, arch: dict):
    """The registry's configuration must be the one the file states."""
    got = {"blocks": list(cfg.cnn_blocks), "widths": list(cfg.cnn_widths),
           "bottleneck": bool(cfg.cnn_bottleneck),
           "width_mult": int(cfg.cnn_width_mult), "img_size": cfg.img_size,
           "n_classes": cfg.n_classes, "gn_size": cfg.cnn_gn_size,
           "param_dtype": cfg.param_dtype}
    want = {k: arch[k] for k in got}
    if got != want:
        raise ValueError(f"registry config {cfg.name} is {got}, the "
                         f"configuration file states {want}")
    if cfg.prune_targets != ("channel",):
        raise ValueError(f"{cfg.name}: prune targets {cfg.prune_targets}; "
                         "the reference prunes coupled channels only")


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

