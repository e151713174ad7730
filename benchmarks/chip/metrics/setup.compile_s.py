"""Backend compile seconds during set-up, from JAX's compile events (a
persistent-cache hit compiles nothing)."""
KIND, UNIT = "per_layer", "s"


def read(ctx):
    return ctx["setup"]["compile_s"]
