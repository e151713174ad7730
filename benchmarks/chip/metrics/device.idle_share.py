"""Share of the traced window in which the chip ran no operation, averaged
over the chips (each chip's share goes to standard error)."""
from benchmarks.chip import tracing

KIND, UNIT = "per_layer", "%"


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["devices"]:
        return None
    start, end = tr["window"]
    shares = {name: 100.0 * (1 - tracing.busy_ns(ops) / (end - start))
              for name, ops in sorted(tr["devices"].items())}
    ctx["notes"].append("idle share per chip: " + " ".join(
        f"{n}={v:.3f}%" for n, v in shares.items()))
    return sum(shares.values()) / len(shares)
