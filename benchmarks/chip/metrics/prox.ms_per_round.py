"""Device time of the fused prox-SGD kernel (the round's Pallas calls) per
round, averaged over the chips, over the rounds traced."""
from benchmarks.chip import tracing

KIND, UNIT = "per_layer", "ms"


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["devices"]:
        return None
    per_dev = [tracing.kind_ns(ops, "prox") for ops in tr["devices"].values()]
    if not any(per_dev):
        return None
    return sum(per_dev) / len(per_dev) / 1e6 / ctx["traced"]["rounds"]
