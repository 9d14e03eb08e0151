"""Mean, in milliseconds, of one field (seconds) of the step records over
the window's records that carry it. Nothing where none does."""


def read(ctx, params):
    values = [s[params["field"]] for s in ctx.steps if params["field"] in s]
    return 1000.0 * sum(values) / len(values) if values else None
