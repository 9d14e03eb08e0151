"""Percentile, in milliseconds, of the duration of the named span over the
traces of the requests due in the window. Nothing where the program
writes no such span."""
from chipbench import timeline


def read(ctx, params):
    values = [1000.0 * s["duration_s"] for t in ctx.traces
              for s in t["spans"] if s["name"] == params["span"]]
    return timeline.percentile(values, params["q"]) if values else None
