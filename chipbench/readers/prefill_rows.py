"""Mean prompts in a prefill step: ``rows`` over the window's step records
of the kinds named (a plain-prefill group's record counts its members; a
single prefill's reads 1). Nothing where the window holds no such step or
the records carry no ``rows``."""


def read(ctx, params):
    rows = [s["rows"] for s in ctx.steps
            if s["kind"] in params["kinds"] and s.get("rows")]
    if not rows:
        return None
    return sum(rows) / len(rows)
