"""Share of prompt tokens served from the prefix cache: window delta of
the engine's cached-token and prompt-token counters."""


def read(ctx, params):
    a, b = ctx.before["engine"], ctx.after["engine"]
    prompts = b["prompt_tokens_total"] - a["prompt_tokens_total"]
    if prompts <= 0:
        return None
    return 100.0 * (b["cached_tokens_total"]
                    - a["cached_tokens_total"]) / prompts
