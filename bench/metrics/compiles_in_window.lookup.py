"""XLA compiles (or compile-cache loads) inside the open-loop window."""


def read(ctx):
    return ctx["compiles_in_window"] if ctx["loop"] == "open" else None
