"""Compilation/dispatch accounting hooks.

The fused-round contract ("one outer round == one jitted dispatch, two
executables per run") is a perf invariant that silently regresses: an
accidental host read or a shape change re-introduces per-step dispatch
without failing any correctness test.  This module gives the test suite
(and ad-hoc profiling) two cheap counters:

  * :func:`compile_count` — a context manager counting XLA *backend
    compilations* via ``jax.monitoring`` duration events (one
    ``/jax/core/compile/backend_compile_duration`` event per executable
    built, including AOT ``.compile()`` calls), their seconds, and the
    executables loaded from the persistent compilation cache instead;
  * :func:`counting` — wraps any callable (e.g. an engine's jitted round
    fn) with an invocation counter, for asserting dispatches-per-round.

jax.monitoring has no listener *removal* API, so one module-level
listener is installed lazily and kept; nesting/overlap of
``compile_count`` blocks is safe (each block reads deltas).
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass, field

import jax

_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_totals = {"compiles": 0, "seconds": 0.0, "cache_hits": 0}
_installed = False


def _on_duration(name: str, duration: float, **kw) -> None:
    if name == _EVENT:
        _totals["compiles"] += 1
        _totals["seconds"] += duration


def _on_event(name: str, **kw) -> None:
    if name == _CACHE_HIT:
        _totals["cache_hits"] += 1


def _ensure_listener() -> None:
    global _installed
    if not _installed:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _installed = True


@dataclass
class CompileStats:
    compiles: int = 0
    seconds: float = 0.0      # backend compile time of those builds
    cache_hits: int = 0       # executables read from the persistent cache


@contextlib.contextmanager
def compile_count():
    """``with compile_count() as stats: ...`` — afterwards,
    ``stats.compiles`` is the number of XLA executables built inside the
    block (jit cache hits and op-by-op dispatches count zero),
    ``stats.seconds`` their compile time and ``stats.cache_hits`` the
    executables the persistent compilation cache supplied."""
    _ensure_listener()
    start = dict(_totals)
    stats = CompileStats()
    try:
        yield stats
    finally:
        stats.compiles = _totals["compiles"] - start["compiles"]
        stats.seconds = _totals["seconds"] - start["seconds"]
        stats.cache_hits = _totals["cache_hits"] - start["cache_hits"]


def probe_seconds(fn, *args, reps: int = 3, warmup: int = 1
                  ) -> tuple[float, int]:
    """Median wall-seconds per call of ``fn(*args)`` after ``warmup``
    compile calls, plus the number of XLA compiles observed during the
    TIMED calls (a short measured probe — repro.comm.select uses this
    for codec selection; nonzero steady-state compiles mean the probe
    timed XLA, not the computation, and should be discarded)."""
    import time
    out = None
    for _ in range(max(warmup, 1)):
        out = fn(*args)
    jax.block_until_ready(out)
    _ensure_listener()
    with compile_count() as stats:
        ts = []
        for _ in range(max(reps, 1)):
            t0 = time.perf_counter()
            out = fn(*args)
            jax.block_until_ready(out)
            ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2], stats.compiles


@dataclass
class CallCounter:
    calls: int = 0
    by_label: dict = field(default_factory=dict)

    def wrap(self, fn, label: str = ""):
        """Count invocations of ``fn`` (shared counter + per-label)."""
        def wrapped(*a, **kw):
            self.calls += 1
            if label:
                self.by_label[label] = self.by_label.get(label, 0) + 1
            return fn(*a, **kw)
        return wrapped


def counting(fn, label: str = "") -> tuple:
    """(wrapped_fn, CallCounter) for a single callable."""
    c = CallCounter()
    return c.wrap(fn, label), c
