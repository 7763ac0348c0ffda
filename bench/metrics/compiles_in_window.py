"""XLA compiles (or compile-cache loads) inside the closed-loop window."""


def read(ctx):
    return ctx["compiles_in_window"] if ctx["loop"] == "closed" else None
