"""Service batching under open-loop lookups: model calls over dispatches,
window totals of the streams' session counters."""
from bench import readers


def read(ctx):
    return readers.calls_per_dispatch(ctx) if ctx["loop"] == "open" else None
