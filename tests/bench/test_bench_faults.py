"""A run of the chip benchmark with the timed path broken underneath reads
``correct`` false: the harness's whole run (set-up, warm-up, window,
reference, metrics) on the CPU at smoke size, past its look for a chip,
once sound and once per fault a training cell can have, and once with
the masks selected in the wrong order."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench_smoke import smoke_cell

from benchmarks.chip import peaks, run, session

SEED = 2**31 + 4242


def _same_state(step):
    def broken(state, sb, eta):
        copy = jax.tree.map(jnp.copy, state)
        _, m = step(copy, sb, eta)
        return state, m
    return broken


def _half_batch(step):
    def broken(state, sb, eta):
        def halve(x):
            b = x.shape[2]
            first = jax.lax.slice_in_dim(x, 0, b // 2, axis=2)
            return jnp.concatenate([first, first], axis=2)
        return step(state, jax.tree.map(halve, sb), eta)
    return broken


def _no_top_exchange(monkeypatch):
    """The top level's reduce keeps the first group member's payload
    alone, as if nothing crossed the node boundary."""
    from repro.comm import codec

    def group_reduce(self, tree, g, w=None, state=None):
        def one(x):
            if x.shape[0] == g:                      # the top boundary
                x = jnp.broadcast_to(x[:1], x.shape)
                w1 = None if w is None else jnp.broadcast_to(w[:1], w.shape)
                return codec.group_sum(x, g, w1)
            return codec.group_sum(x, g, w)
        return jax.tree.map(one, tree), state
    monkeypatch.setattr(codec.DenseCodec, "group_reduce", group_reduce)


def _reversed_masks(monkeypatch):
    """The mask selection keeps the lowest-scoring groups."""
    from repro.core import masks, sparsity
    topk = sparsity.topk_mask

    def lowest(scores, keep, shards=1):
        return topk(-scores, keep, shards)
    monkeypatch.setattr(masks, "topk_mask", lowest)


@pytest.mark.parametrize("fault", ["none", "state_unchanged", "half_batch",
                                   "no_top_exchange", "reversed_masks"])
def test_broken_round_reads_incorrect(fault, monkeypatch):
    cell, _ = smoke_cell("resnet18.dynamic", monkeypatch)
    monkeypatch.setattr(peaks, "peaks", lambda kind: {
        "bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    wrap = {"state_unchanged": _same_state, "half_batch": _half_batch}
    if fault in wrap:
        build = session.Session._build

        def broken_build(self, *a, **kw):
            build(self, *a, **kw)
            self.step = wrap[fault](self.step)
        monkeypatch.setattr(session.Session, "_build", broken_build)
    if fault == "no_top_exchange":
        _no_top_exchange(monkeypatch)
    if fault == "reversed_masks":
        _reversed_masks(monkeypatch)
    res = run.run_cell(cell, SEED, 0.5, False, jax.devices(),
                       time.perf_counter())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["correct"] is (fault == "none"), res["checks"]
