"""Device time under the program's ``consensus/masks`` scope (group
scores, mask sync, drift) per traced round, averaged over the chips;
absent where the round makes no masks (a frozen round)."""
from benchmarks.chip import scopes

KIND, UNIT = "per_layer", "ms"


def read(ctx):
    return scopes.ms_per_round(ctx, "consensus/masks")
