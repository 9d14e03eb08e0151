"""Host-clock time of the step recorder's steps of the given kinds in
the window: mean per step, or per forward (a decode burst is several)."""


def read(ctx, params):
    steps = [s for s in ctx.steps if s["kind"] in params["kinds"]]
    div = (sum(s["forwards"] for s in steps) if params["per"] == "forward"
           else len(steps))
    return 1000.0 * sum(s["wall_s"] for s in steps) / div if div else None
