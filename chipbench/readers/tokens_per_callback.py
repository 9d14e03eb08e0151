"""Tokens that one delivery to a request's callback carried: the window's
records' ``emit_tokens`` summed, over their ``emit_callbacks`` (the
deliveries that bursts' flushes made: one a sequence and burst, where a
token a call reads 1.0; a prefill's first token is in neither count).
Nothing where the records carry no such count (a program that delivers
per token writes none) or no burst was flushed in the window."""


def read(ctx, params):
    steps = [s for s in ctx.steps if "emit_callbacks" in s]
    callbacks = sum(s["emit_callbacks"] for s in steps)
    if not callbacks:
        return None
    return sum(s.get("emit_tokens", 0) for s in steps) / callbacks
