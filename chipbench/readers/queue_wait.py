"""Percentile of the ``engine.queue`` span (arrival at the engine to the
start of prefill) over the traces of the requests due in the window."""
from chipbench import timeline


def read(ctx, params):
    waits = [1000.0 * s["duration_s"] for t in ctx.traces
             for s in t["spans"] if s["name"] == "engine.queue"]
    return timeline.percentile(waits, params["q"]) if waits else None
