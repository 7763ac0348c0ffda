"""Decode steps' share (%) of their roofline: the least time the chip needs
for the window's decode steps (the larger of their operations over the bf16
peak and their bytes over HBM bandwidth: weights at the compute dtype once
a step, each live row's KV read and its new KV written) over the device
time of the decode-step programs in the trace."""
from bench import readers


def read(ctx):
    t = ctx["trace"]
    if ctx["loop"] != "closed" or not t or not ctx["peaks"]:
        return None
    names = [p for p in t["program_s"] if "decode_step" in p]
    busy = sum(t["program_s"][p] for p in names)
    steps = sum(t["program_n"][p] for p in names)
    if busy <= 0 or not steps:
        return None
    flops, ctxs = readers.decode_work(ctx)
    pk = ctx["peaks"]
    least = max(flops / pk["bf16_flops"],
                ctx["dims"].decode_bytes(int(round(steps)), ctxs)
                / pk["hbm_bytes_per_s"])
    return 100.0 * least / busy
