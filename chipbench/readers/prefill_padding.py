"""Share of the token positions the prefill programs computed on that were
padding: ``100 x (padded - real) / padded`` over the window's prefill step
records, ``padded_tokens`` being rows x bucket of the step's dispatches
and ``tokens`` the prompt tokens among them. Nothing where the records
carry no ``padded_tokens`` (a program that does not count it)."""


def read(ctx, params):
    steps = [s for s in ctx.steps
             if s["kind"] in params["kinds"] and s.get("padded_tokens")]
    padded = sum(s["padded_tokens"] for s in steps)
    if not padded:
        return None
    return 100.0 * (padded - sum(s["tokens"] for s in steps)) / padded
