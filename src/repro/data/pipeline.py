"""Host input pipeline: deterministic stream -> device, with background
prefetch so host batch synthesis overlaps device compute (the paper's Phase
1 is compute-bound; input stall would pollute its timing benchmarks).

Each stage is a ``dist.monitor.span``, so a profiler trace and the span
counters show where the feed spends its time:

  ``repro.feed.load``      superbatches: the E ``next()`` calls upstream
  ``repro.feed.stack``     superbatches: stacking the E batches
  ``repro.feed.produce``   prefetch worker: ``next()`` on its upstream
  ``repro.feed.put_wait``  prefetch worker: blocked on a full queue
  ``repro.feed.get_wait``  prefetch consumer: blocked on an empty queue
"""
from __future__ import annotations

import queue
import threading
from typing import Iterator

import jax

from ..dist.monitor import span


def batches(stream, extra_inputs=(), shape=None, start_step: int = 0
            ) -> Iterator[dict]:
    """Infinite iterator of global batches (leading worker dim), including
    modality stubs (precomputed frame/patch embeddings per the assignment)."""
    import numpy as np
    step = start_step
    while True:
        b = stream.batch_at(step)
        if extra_inputs:
            rng = np.random.default_rng((step << 16) + 31)
            W, bs = b[next(iter(b))].shape[:2]
            for name, shp, dt in extra_inputs:
                arr = rng.standard_normal((W, bs) + shp(shape),
                                          dtype=np.float32)
                b[name] = jax.numpy.asarray(arr).astype(dt)
        yield b
        step += 1


def superbatches(it: Iterator, e: int) -> Iterator:
    """Stack ``e`` consecutive global batches into one ``(E, W, ...)``
    superbatch — the unit the fused round executable scans over (one
    bundle per outer round; wrap with :func:`prefetch` so bundle
    assembly overlaps the previous round's device compute)."""
    while True:
        with span("repro.feed.load"):
            bs = [next(it) for _ in range(e)]
        with span("repro.feed.stack"):
            sb = jax.tree.map(lambda *xs: jax.numpy.stack(xs), *bs)
        yield sb


def superbatch_chunks(it: Iterator, e: int, steps: int) -> Iterator:
    """Steps-bounded :func:`superbatches`: yields ``(n, superbatch)``
    covering exactly ``steps`` total steps in chunks of ``e`` plus one
    possibly-shorter tail (at most two distinct leading dims, so a
    scanned consumer compiles at most twice)."""
    done = 0
    while done < steps:
        n = min(e, steps - done)
        bs = [next(it) for _ in range(n)]
        yield n, jax.tree.map(lambda *xs: jax.numpy.stack(xs), *bs)
        done += n


def prefetch(it: Iterator, size: int = 2) -> Iterator:
    """Background-thread prefetch (double buffering by default)."""
    q: queue.Queue = queue.Queue(maxsize=size)
    stop = object()

    def worker():
        try:
            while True:
                with span("repro.feed.produce"):
                    item = next(it, stop)
                if item is stop:
                    break
                with span("repro.feed.put_wait"):
                    q.put(item)
        finally:
            q.put(stop)

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    while True:
        with span("repro.feed.get_wait"):
            item = q.get()
        if item is stop:
            return
        yield item
