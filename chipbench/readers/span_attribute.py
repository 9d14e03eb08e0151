"""Percentile, in milliseconds, of one attribute (seconds) of the named
span over the traces of the requests due in the window. Nothing where the
program writes no such span or attribute."""
from chipbench import timeline


def read(ctx, params):
    values = [1000.0 * s["attributes"][params["attribute"]]
              for t in ctx.traces for s in t["spans"]
              if s["name"] == params["span"]
              and params["attribute"] in s["attributes"]]
    return timeline.percentile(values, params["q"]) if values else None
