"""Rows the model answered (``rows_predicted``: a select counts the rows it
judged) in the queries that started inside the window, over the time from
the window's start to the end of the last of them."""
from bench import readers


def read(ctx):
    if ctx["loop"] != "closed":
        return None
    return readers.total(ctx, "rows_predicted") / readers.window_span_s(ctx)
