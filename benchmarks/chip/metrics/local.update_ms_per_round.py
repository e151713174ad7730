"""Device time under the program's ``local/update`` scope (the per-leaf
prox-SGD update of the E local steps: the Pallas kernel and the views
around it) per traced round, averaged over the chips."""
from benchmarks.chip import scopes

KIND, UNIT = "per_layer", "ms"


def read(ctx):
    return scopes.ms_per_round(ctx, "local/update")
