"""Training throughput: every image of every round completed in the
window, over the window's seconds (drains and input waits included)."""
KIND, UNIT = "end_to_end", "images/s"


def read(ctx):
    w = ctx["window"]
    return w["rounds"] * ctx["images_per_round"] / w["seconds"]
