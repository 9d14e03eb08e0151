"""Host time of the engine loop per step, in milliseconds: mean over the
window's step records of the named phases (``schedule`` lies in the gap
before a step, ``build``, ``enqueue`` and ``emit`` inside it). It leaves
out ``readback``, the host waiting for the device, and ``idle_wait``: what
remains is the time a step cannot go below however fast the device is.
Nothing where the records carry no phases."""


def read(ctx, params):
    steps = [s for s in ctx.steps if "phases" in s]
    if not steps:
        return None
    total = sum(s["phases"].get(p, 0.0) + s.get("gap_phases", {}).get(p, 0.0)
                for s in steps for p in params["phases"])
    return 1000.0 * total / len(steps)
