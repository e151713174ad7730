"""The program's ``repro.feed.stack`` span counter per round: stacking a
round's E batches in ``superbatches``, over the part of a traced window
after the profiler stopped."""
from benchmarks.chip import scopes

KIND, UNIT = "per_layer", "ms"


def read(ctx):
    return scopes.feed_ms_per_round(ctx, "repro.feed.stack")
