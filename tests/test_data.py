"""Synthetic data pipeline: determinism + shard disjointness; the feed's
superbatch stack."""
import numpy as np
import pytest

from repro.data.synthetic import SyntheticLM, SyntheticImages
from repro.data.pipeline import (batches, prefetch, superbatch_chunks,
                                 superbatches)
from repro.dist import monitor


def test_lm_stream_deterministic():
    s = SyntheticLM(vocab=101, seq_len=16, batch=2, workers=4)
    a = s.batch_at(3)["tokens"]
    b = s.batch_at(3)["tokens"]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    c = s.batch_at(4)["tokens"]
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert a.shape == (4, 2, 16)
    assert int(a.max()) < 101


def test_images_learnable_structure():
    s = SyntheticImages(img_size=8, n_classes=4, batch=64, workers=1)
    b = s.batch_at(0)
    x, y = np.asarray(b["images"]), np.asarray(b["labels"])
    means = [x[0][y[0] == c].mean() for c in range(4) if (y[0] == c).any()]
    assert np.std(means) > 0.1  # class-dependent means are separable


def test_prefetch_order():
    it = prefetch(iter(range(10)), size=2)
    assert list(it) == list(range(10))


class _HostImages:
    """A loader that serves numpy batches, as the chip benchmark's does."""

    def batch_at(self, step: int) -> dict:
        rng = np.random.default_rng(step)
        return {"images": rng.standard_normal((2, 3, 4, 4, 3),
                                              dtype=np.float32),
                "labels": rng.integers(0, 10, (2, 3)).astype(np.int32)}


def _stream(kind):
    """Batches of ``kind``: host (numpy leaves), device (``jax.Array``
    leaves) or mixed (numpy images plus a device stub, as
    ``batches(extra_inputs=...)`` makes)."""
    import jax.numpy as jnp
    if kind == "mixed":
        return batches(_HostImages(), (("frames", lambda s: (5,),
                                        jnp.bfloat16),))
    it = batches(_HostImages())
    if kind == "device":
        return ({k: jnp.asarray(v) for k, v in b.items()} for b in it)
    return it


@pytest.mark.parametrize("consumer", ["superbatches", "superbatch_chunks"])
@pytest.mark.parametrize("kind", ["host", "device", "mixed"])
def test_superbatch_stacks_each_leaf_where_it_lives(kind, consumer):
    """A leaf that is numpy in every batch is stacked on the host, any
    other on the device; the values are ``jnp.stack``'s bit for bit, in
    the chunks' shorter tail too; ``repro.feed.host_stack`` counts once
    per superbatch that has a host leaf."""
    import jax
    import jax.numpy as jnp
    e = 3
    before = monitor.span_totals().get("repro.feed.host_stack",
                                       monitor.SpanTotal()).count
    if consumer == "superbatches":
        it = superbatches(_stream(kind), e)
        got = [(e, next(it)) for _ in range(2)]
    else:
        got = list(superbatch_chunks(_stream(kind), e, 2 * e + 1))
        assert [n for n, _ in got] == [e, e, 1]
    n_host = monitor.span_totals().get("repro.feed.host_stack",
                                       monitor.SpanTotal()).count - before
    assert n_host == (0 if kind == "device" else len(got))
    ref = _stream(kind)
    for n, sb in got:
        bs = [next(ref) for _ in range(n)]
        assert set(sb) == set(bs[0])
        for k, v in sb.items():
            host = kind == "host" or (kind == "mixed" and k != "frames")
            assert isinstance(v, np.ndarray) == host
            assert isinstance(v, jax.Array) != host
            want = jnp.stack([b[k] for b in bs])
            assert v.shape == (n,) + bs[0][k].shape and v.dtype == want.dtype
            assert np.asarray(v).tobytes() == np.asarray(want).tobytes()


def test_host_stack_reuses_an_array_only_once_released():
    """A superbatch the consumer still holds, or a view of it, is never
    written again; once dropped, and its ``device_put`` done, its array
    takes a later round, and what was put keeps its values."""
    import jax
    it = superbatches(_stream("host"), 2)
    held = next(it)["images"][1]              # a view keeps its array
    held_bytes = held.tobytes()
    ptrs, puts = set(), []
    for _ in range(6):
        sb = next(it)
        ptrs.add(sb["images"].ctypes.data)
        puts.append(jax.block_until_ready(jax.device_put(sb)))
        del sb
    assert held.tobytes() == held_bytes
    assert held.base.ctypes.data not in ptrs
    assert len(ptrs) < 6                      # arrays were written again
    ref = _stream("host")
    bs = [next(ref) for _ in range(14)]
    for r, p in enumerate(puts):
        want = np.stack([b["images"] for b in bs[2 + 2 * r:4 + 2 * r]])
        assert np.asarray(p["images"]).tobytes() == want.tobytes()
