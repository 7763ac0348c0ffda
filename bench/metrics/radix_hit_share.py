"""Share (%) of the window's prompt tokens served from the paged engine's
radix prefix tree: ``radix_hit_tokens`` over the prompts' own token count
(the engine's ``prefill_tokens`` include bucket padding)."""


def read(ctx):
    if ctx["kv_layout"] != "paged":
        return None
    prompt = sum(len(r["prompt"]) for r in ctx["requests"])
    return 100.0 * ctx["radix_hit_tokens"] / prompt if prompt else None
