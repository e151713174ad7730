"""The program's ``repro.feed.produce`` span counter per round: the
prefetch thread's time in its upstream ``next()`` (loading, stacking and
placing one round's input), over the part of a traced window after the
profiler stopped."""
from benchmarks.chip import scopes

KIND, UNIT = "per_layer", "ms"


def read(ctx):
    return scopes.feed_ms_per_round(ctx, "repro.feed.produce")
