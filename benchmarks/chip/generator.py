"""The benchmark's input: an in-memory CIFAR-10-shaped loader made from the
seed.

At set-up, ``ImagePool`` draws ``POOL`` uint8 images of ``size x size x 3``
and their labels (as many as CIFAR-10's training set: 154 MB at 32x32),
and a permutation that fixes the order in which rows are served.  Step ``s``
serves the next ``workers x batch`` rows of that order, wrapping round,
converted to the float32 ``(workers, batch, size, size, 3)`` images and
int32 labels a training step takes.  Within one pass over the pool no row
repeats, and every seed serves the same sizes.

The work per step, a gather from the pool and a conversion to float32,
stands for a real in-memory loader's.  ``seconds`` accumulates the time
spent making batches, so a starved loader can be told from a slow step.
"""
from __future__ import annotations

import time

import numpy as np

POOL = 50_000


class ImagePool:
    def __init__(self, seed: int, traffic: dict, arch: dict, batch: int):
        rng = np.random.default_rng([seed, 0x1CF0])
        n = POOL
        size = arch["img_size"]
        self.images = rng.integers(0, 256, (n, size, size, 3), np.uint8)
        self.labels = rng.integers(0, arch["n_classes"], n).astype(np.int32)
        self.order = rng.permutation(n)
        self.workers = traffic["workers"]
        self.batch = batch
        self.seconds = 0.0
        self.stop = False    # set to end a feed over this pool

    def batch_at(self, step: int) -> dict:
        """``{"images", "labels"}`` of step ``step`` as numpy arrays."""
        t0 = time.perf_counter()
        w, b = self.workers, self.batch
        n = len(self.order)
        rows = self.order[(step * w * b + np.arange(w * b)) % n]
        img = self.images[rows].astype(np.float32)
        img = (img * np.float32(1 / 127.5) - np.float32(1.0)).reshape(
            (w, b) + self.images.shape[1:])
        out = {"images": img, "labels": self.labels[rows].reshape(w, b)}
        self.seconds += time.perf_counter() - t0
        return out

    def stream(self, start: int = 0):
        """Steps ``start, start + 1, ...``."""
        step = start
        while True:
            yield self.batch_at(step)
            step += 1

    def round_inputs(self, r: int, local_steps: int):
        """Round ``r``'s ``(E, W, B, ...)`` images and labels, as the
        stream serves them from step 0."""
        bs = [self.batch_at(r * local_steps + e) for e in range(local_steps)]
        return (np.stack([b["images"] for b in bs]),
                np.stack([b["labels"] for b in bs]))
