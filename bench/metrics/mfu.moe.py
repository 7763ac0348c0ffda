"""Model FLOP/s utilization (%) of a model with experts, as ``mfu``: the
forward-pass operations of the tokens the window computed (prompt tokens
less those served from the radix tree, then one decode step per served
token but the last; no padding; the family's ``Dims`` count the routed
experts a token uses and the shared ones), over the window's span times
the chip's bf16 peak."""
from bench import readers


def read(ctx):
    if ctx["loop"] != "closed" or not ctx["peaks"] or not ctx["requests"]:
        return None
    dims, flops = ctx["dims"], 0
    for r in ctx["requests"]:
        pre, dec = dims.request_flops(len(r["prompt"]), len(r["text"]) + 1)
        flops += pre + dec
    flops -= ctx["radix_hit_tokens"] * dims.linear_flops()
    return 100.0 * flops / (readers.window_span_s(ctx)
                            * ctx["peaks"]["bf16_flops"])
