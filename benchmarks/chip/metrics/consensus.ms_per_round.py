"""Device time under every ``consensus/*`` scope of the program (masks,
each level's exchange, the dual and penalty update) per traced round,
averaged over the chips."""
from benchmarks.chip import scopes

KIND, UNIT = "per_layer", "ms"


def read(ctx):
    return scopes.ms_per_round(ctx, "consensus/")
