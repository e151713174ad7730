"""Device time under the program's ``local/model`` scope (the vmapped
forward and backward pass of the E local steps) per traced round,
averaged over the chips."""
from benchmarks.chip import scopes

KIND, UNIT = "per_layer", "ms"


def read(ctx):
    return scopes.ms_per_round(ctx, "local/model")
