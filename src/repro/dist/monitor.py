"""Compilation/dispatch accounting hooks and the program's host spans.

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
    fn) with an invocation counter, for asserting dispatches-per-round;
  * :func:`span` — names a phase of the host's work (``repro.feed.*`` in
    the input pipeline, ``repro.round.*`` in ``train()``): under the JAX
    profiler it is a ``TraceAnnotation`` on the host plane of the trace,
    on the clock of the device events, and with or without a profiler
    it adds its count and seconds to a process-wide table that
    :func:`span_totals` reads.

jax.monitoring has no listener *removal* API, so one module-level
listener is installed lazily and kept; nesting/overlap of
``compile_count`` blocks is safe (each block reads deltas).
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
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


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

SPAN_LOG = 1 << 16        # the most recent spans kept with their times
_span_lock = threading.Lock()
_span_totals: dict = {}   # name -> [count, seconds]
_span_log: collections.deque = collections.deque(maxlen=SPAN_LOG)


@dataclass(frozen=True)
class SpanTotal:
    count: int = 0
    seconds: float = 0.0


@contextlib.contextmanager
def span(name: str):
    """``with span("repro.feed.load"): ...`` — a profiler annotation of
    the block, and one more count and the block's seconds for ``name``
    (safe from any thread; spans nest)."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        t1 = time.perf_counter()
        with _span_lock:
            tot = _span_totals.setdefault(name, [0, 0.0])
            tot[0] += 1
            tot[1] += t1 - t0
            _span_log.append((name, t0, t1))


def span_totals(since: float = None, until: float = None) -> dict:
    """``{name: SpanTotal}``.  Without arguments, every span since the
    process started: a snapshot that callers subtract, as
    :func:`compile_count` does.  With ``since``/``until`` (readings of
    ``time.perf_counter()``), the part of the last ``SPAN_LOG`` spans
    inside that interval: the seconds each overlaps it, and a count for
    each that ends in it."""
    with _span_lock:
        if since is None and until is None:
            return {n: SpanTotal(c, s) for n, (c, s) in _span_totals.items()}
        log = list(_span_log)
    lo = float("-inf") if since is None else since
    hi = float("inf") if until is None else until
    out: dict = {}
    for name, t0, t1 in log:
        overlap = min(t1, hi) - max(t0, lo)
        if overlap < 0 or (overlap == 0 and t1 != t0):
            continue
        c, s = out.get(name, (0, 0.0))
        out[name] = (c + (lo <= t1 < hi), s + overlap)
    return {n: SpanTotal(c, s) for n, (c, s) in out.items()}
