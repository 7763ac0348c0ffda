"""Decode-batch occupancy (%) in the closed loop: live rows summed over the
engine's decode ticks (``decode_rows``) over the batch's slots summed over
the same ticks (``decode_slots``: ticks times ``num_slots`` in the
continuous batcher).  None where the program keeps no such counters."""


def read(ctx):
    if ctx["loop"] != "closed":
        return None
    stats = [q["stats"] for q in ctx["queries"] if q["stats"]]
    if not stats or "decode_slots" not in stats[0]:
        return None
    slots = sum(s["decode_slots"] for s in stats)
    return 100.0 * sum(s["decode_rows"] for s in stats) / slots \
        if slots else None
