"""Programs compiled or fetched from the compile cache in the window."""


def read(ctx, params):
    return float(ctx.compiles_in_window)
