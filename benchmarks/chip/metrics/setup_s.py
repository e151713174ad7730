"""Set-up: from process start to the first timed round (imports, the
loader's pool, engine build, compile or cache load, state, warm-up)."""
KIND, UNIT = "end_to_end", "s"


def read(ctx):
    return ctx["setup"]["seconds"]
