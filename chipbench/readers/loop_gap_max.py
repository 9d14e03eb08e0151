"""The longest, in milliseconds, that the engine loop was between two
steps with work waiting: the largest ``gap_before_s`` less the
``idle_wait`` inside it (the loop asleep for want of work) over the
window's step records. What remains of a gap is scheduling, a flush with
no step to follow, and whatever stalled the thread. Nothing where the
records carry no gaps."""


def read(ctx, params):
    gaps = [s["gap_before_s"] - s["gap_phases"].get("idle_wait", 0.0)
            for s in ctx.steps if "gap_phases" in s]
    return 1000.0 * max(gaps) if gaps else None
