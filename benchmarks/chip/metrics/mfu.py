"""Model FLOP utilisation of the round: forward and backward FLOPs of the
images completed (3 x 2 x the conv and classifier MACs of the widths the
executable runs) over window seconds x chips x the chip's bf16 peak.  In
a traced run, over the part of the window after the profiler stopped."""
KIND, UNIT = "per_layer", "%"


def read(ctx):
    w = ctx["window"]
    if not w["rounds"]:
        return None
    done = w["rounds"] * ctx["images_per_round"] * ctx["flops_per_image"]
    return 100.0 * done / (w["seconds"] * ctx["chips"]
                           * ctx["peaks"]["bf16_flops"])
