"""Paged prefill steps' share (%) of their roofline, for a model with
experts: the least time the chip needs for the window's prefills (the
larger of their operations over the bf16 peak and their bytes over HBM
bandwidth) over the device time of the ``paged_prefill_step`` programs in
the trace.  Operations: each request's prompt through every layer and the
head, less the tokens served from the radix tree.  Bytes: every weight
outside the routed experts once a step, each expert a step touched once
(``expert_loads_prefill``, from the program's counters), and the
prefilled tokens' latents written."""


def read(ctx):
    t = ctx["trace"]
    if ctx["loop"] != "closed" or not t or not ctx["peaks"] or \
            not ctx["requests"]:
        return None
    stats = [q["stats"] for q in ctx["queries"] if q["stats"]]
    if not stats or "expert_loads_prefill" not in stats[0]:
        return None
    names = [p for p in t["program_s"] if "paged_prefill_step" in p]
    busy = sum(t["program_s"][p] for p in names)
    steps = sum(t["program_n"][p] for p in names)
    if busy <= 0 or not steps:
        return None
    dims, pk = ctx["dims"], ctx["peaks"]
    hits = ctx["radix_hit_tokens"]
    flops = sum(dims.request_flops(len(r["prompt"]), 1)[0]
                for r in ctx["requests"]) - hits * dims.linear_flops()
    tokens = sum(len(r["prompt"]) for r in ctx["requests"]) - hits
    loads = sum(s["expert_loads_prefill"] for s in stats)
    nbytes = int(round(steps)) * dims.weight_bytes() + \
        loads * dims.expert_bytes() + tokens * dims.kv_bytes_per_token()
    least = max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / busy
