"""90th percentile (nearest rank) of query latency over every query due in
the window, from its due time to its last result chunk.  A query that
failed or never finished counts as missing: it takes the time waited for
it, which is more than any finished query of the window."""
from bench import readers


def read(ctx):
    if ctx["loop"] != "open":
        return None
    w = ctx["window"]
    lat = []
    for q in ctx["queries"]:
        if q["end"] is not None and q.get("ok", True):
            lat.append(q["end"] - q["due"])
        else:
            lat.append(max(w["end"], w["close"] + 60.0) - q["due"])
    return readers.nearest_rank(lat, 90) if lat else None
