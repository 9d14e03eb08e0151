"""1 - union of the device's operation intervals over the traced span."""


def read(ctx, params):
    if ctx.device is None:
        return None
    return 100.0 * (1.0 - ctx.device["busy_s"] / ctx.device["window_s"])
