"""Passes over the layer stack a forward ran: ``loop_passes`` (what a
looped family's forward counts on its scan's carry, ``Family.stats``)
over ``stats_forwards``, summed over the window's step records that carry
both. ``total_ut_steps`` in a sound run. Nothing where no record carries
the count: a program whose layers run once."""


def read(ctx, params):
    steps = [s for s in ctx.steps
             if s.get("stats_forwards") and "loop_passes" in s]
    forwards = sum(s["stats_forwards"] for s in steps)
    return sum(s["loop_passes"] for s in steps) / forwards if steps else None
