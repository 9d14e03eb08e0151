"""Share, in percent, of the named phases' wall time during which the
engine thread held no processor: ``100 x (1 - CPU / wall)`` with both
summed over the window's records (``phases_cpu`` beside ``phases``, and
``gap_phases_cpu`` beside ``gap_phases`` for a phase that lies before the
step: the thread's CPU clock read at the two edges at which the wall clock
is read). Off the processor the thread waits: for the interpreter lock,
in a blocking call, on the kernel. Nothing where the records carry no CPU
seconds."""


def read(ctx, params):
    wall = cpu = 0.0
    for s in ctx.steps:
        if "phases_cpu" not in s:
            continue
        for p in params["phases"]:
            wall += s["phases"].get(p, 0.0) + s["gap_phases"].get(p, 0.0)
            cpu += (s["phases_cpu"].get(p, 0.0)
                    + s["gap_phases_cpu"].get(p, 0.0))
    return 100.0 * (1.0 - cpu / wall) if wall > 0 else None
