"""Host time of ``emit`` per token it delivered, in microseconds: the
window's records' ``phases.emit`` summed, over their ``emit_tokens`` (what
``generation_tokens_total`` gained inside the phase: a burst's tokens; the
first token of a request, which a prefill's flush emits, is not in it).
Nothing where the records carry no such count."""


def read(ctx, params):
    steps = [s for s in ctx.steps if "emit_tokens" in s]
    tokens = sum(s["emit_tokens"] for s in steps)
    if not tokens:
        return None
    return 1e6 * sum(s["phases"].get("emit", 0.0) for s in steps) / tokens
