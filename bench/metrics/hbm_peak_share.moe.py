"""Peak device memory in use after the window (``peak_bytes_in_use``) as a
share (%) of the chip's HBM."""


def read(ctx):
    if ctx["loop"] != "closed" or not ctx["peaks"]:
        return None
    return 100.0 * ctx["memory_peak_bytes"] / ctx["peaks"]["hbm_bytes"]
