"""Share, in percent, of the device's idle seconds (the gaps that
``chipbench.xplane.reduce`` lists under ``idle_gaps``, short ones
included) whose cause begins with the given prefix: ``host:engine.`` is a
gap during which the engine loop's own annotation (a phase, or the step
around it) was the shortest host event covering it, so that the gap has a
name. Nothing where the program does not annotate its loop (its step
records carry no phases)."""


def read(ctx, params):
    if ctx.device is None or not any("phases" in s for s in ctx.steps):
        return None
    gaps = ctx.device["idle_gaps"]
    total = sum(seconds for _, seconds in gaps)
    if total <= 0:
        return None
    named = sum(seconds for cause, seconds in gaps
                if cause.startswith(params["prefix"]))
    return 100.0 * named / total
