"""``dist.monitor.span``: the process-wide counters behind the program's
host spans, and the input pipeline's ``repro.feed.*`` spans."""
import threading
import time

import pytest

from repro.data.pipeline import prefetch, superbatches
from repro.dist import monitor


def _delta(before, after, name):
    b = before.get(name, monitor.SpanTotal())
    a = after[name]
    return a.count - b.count, a.seconds - b.seconds


def test_span_counts_seconds_and_snapshot_deltas():
    before = monitor.span_totals()
    with monitor.span("test.outer"):
        time.sleep(0.02)
        with monitor.span("test.inner"):
            time.sleep(0.03)
    with monitor.span("test.inner"):
        pass
    after = monitor.span_totals()
    n_out, s_out = _delta(before, after, "test.outer")
    n_in, s_in = _delta(before, after, "test.inner")
    assert (n_out, n_in) == (1, 2)
    assert s_out >= 0.05 and s_out >= s_in >= 0.03
    # a snapshot is a copy: later spans leave it as it was
    with monitor.span("test.outer"):
        pass
    assert after["test.outer"].count == n_out + before.get(
        "test.outer", monitor.SpanTotal()).count


def test_span_counts_from_two_threads_and_through_an_exception():
    before = monitor.span_totals()

    def work():
        for _ in range(200):
            with monitor.span("test.threads"):
                pass
    ts = [threading.Thread(target=work) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    with pytest.raises(ValueError):
        with monitor.span("test.threads"):
            raise ValueError("the span still counts")
    n, _ = _delta(before, monitor.span_totals(), "test.threads")
    assert n == 401


def test_span_totals_over_an_interval():
    """Seconds are the part of each span inside the interval; a span is
    counted where it ends."""
    with monitor.span("test.interval"):
        time.sleep(0.02)
        since = time.perf_counter()
        time.sleep(0.03)              # this part inside, ends inside
    end1 = time.perf_counter()
    start2 = time.perf_counter()
    with monitor.span("test.interval"):
        time.sleep(0.02)              # wholly inside
    until = time.perf_counter()
    with monitor.span("test.interval"):
        time.sleep(0.01)              # wholly after
    got = monitor.span_totals(since=since, until=until)["test.interval"]
    assert got.count == 2
    assert got.seconds == pytest.approx((end1 - since) + (until - start2),
                                        abs=5e-3)
    assert "test.interval" not in monitor.span_totals(
        since=time.perf_counter() + 1)


@pytest.mark.parametrize("slow", ["producer", "consumer"])
def test_prefetch_counters(slow):
    """A slow producer leaves the consumer waiting in ``get`` and never
    fills the queue; a slow consumer leaves the producer waiting in
    ``put``."""
    def items():
        for i in range(8):
            if slow == "producer":
                time.sleep(0.03)
            yield i
    before = monitor.span_totals()
    got = []
    for x in prefetch(items(), size=2):
        if slow == "consumer":
            time.sleep(0.03)
        got.append(x)
    assert got == list(range(8))
    after = monitor.span_totals()
    n_get, get_s = _delta(before, after, "repro.feed.get_wait")
    n_put, put_s = _delta(before, after, "repro.feed.put_wait")
    n_prod, prod_s = _delta(before, after, "repro.feed.produce")
    assert n_get == 9 and n_put == 8 and n_prod == 9   # + the end marker
    if slow == "producer":
        assert get_s >= 0.15 and put_s < 0.05 and prod_s >= 0.2
    else:
        assert put_s >= 0.1 and get_s < 0.05 and prod_s < 0.05


def test_superbatches_count_load_and_stack_once_per_round():
    import numpy as np

    def stream():
        while True:
            yield {"x": np.ones((2, 3), np.float32)}
    before = monitor.span_totals()
    it = superbatches(stream(), 4)
    sbs = [next(it) for _ in range(3)]
    after = monitor.span_totals()
    assert sbs[0]["x"].shape == (4, 2, 3)
    assert _delta(before, after, "repro.feed.load")[0] == 3
    assert _delta(before, after, "repro.feed.stack")[0] == 3
