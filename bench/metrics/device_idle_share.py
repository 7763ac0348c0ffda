"""Share (%) of the traced closed-loop window in which no operation ran on
the device."""
from bench import readers


def read(ctx):
    return readers.idle_share(ctx) if ctx["loop"] == "closed" else None
