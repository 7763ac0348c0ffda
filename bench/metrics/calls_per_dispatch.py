"""Service batching in the closed loop: model calls (``llm_calls``) over
``complete_many`` dispatches (``dispatch_batches``), window totals."""
from bench import readers


def read(ctx):
    return readers.calls_per_dispatch(ctx) if ctx["loop"] == "closed" else None
