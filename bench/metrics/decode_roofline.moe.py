"""Decode steps' share (%) of their roofline, for a model with experts: the
least time the chip needs for the window's decode steps (the larger of
their operations over the bf16 peak and their bytes over HBM bandwidth)
over the device time of the decode-step programs in the trace.  Bytes:
every weight outside the routed experts once a step, each expert a step
touched once (``expert_loads_decode``, from the program's counters), and
each live row's cached latents read and its new one written."""
from bench import readers


def read(ctx):
    t = ctx["trace"]
    if ctx["loop"] != "closed" or not t or not ctx["peaks"]:
        return None
    stats = [q["stats"] for q in ctx["queries"] if q["stats"]]
    if not stats or "expert_loads_decode" not in stats[0]:
        return None
    names = [p for p in t["program_s"] if "decode_step" in p]
    busy = sum(t["program_s"][p] for p in names)
    steps = sum(t["program_n"][p] for p in names)
    if busy <= 0 or not steps:
        return None
    dims, pk = ctx["dims"], ctx["peaks"]
    flops, ctxs = readers.decode_work(ctx)
    loads = sum(s["expert_loads_decode"] for s in stats)
    nbytes = int(round(steps)) * dims.weight_bytes() + \
        loads * dims.expert_bytes() + \
        (sum(ctxs) + len(ctxs)) * dims.kv_bytes_per_token()
    least = max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / busy
