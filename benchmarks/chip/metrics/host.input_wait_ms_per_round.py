"""Host clock around the window loop's ``next()`` on the prefetch
iterator: how long a round waited for its input.  In a traced run, over
the part of the window after the profiler stopped."""
KIND, UNIT = "per_layer", "ms"


def read(ctx):
    w = ctx["window"]
    if not w["rounds"]:
        return None
    return 1e3 * w["input_wait_s"] / w["rounds"]
