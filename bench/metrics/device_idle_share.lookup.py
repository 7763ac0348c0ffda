"""Share (%) of the traced open-loop window in which no operation ran on
the device."""
from bench import readers


def read(ctx):
    return readers.idle_share(ctx) if ctx["loop"] == "open" else None
