"""Mean rows in a decode step, weighted by the steps' forwards."""


def read(ctx, params):
    steps = [s for s in ctx.steps if s["kind"] == "decode_burst"]
    forwards = sum(s["forwards"] for s in steps)
    if not forwards:
        return None
    return sum(s["rows"] * s["forwards"] for s in steps) / forwards
