"""Percentile, in milliseconds, of one cause of queue wait: an attribute of
the ``engine.queue`` span (``behind_decode_s``, ``behind_prefill_s``,
``behind_other_s``: the seconds of the wait during which a decode-kind
step, a prefill-kind step of another request, or neither held the engine
loop) over the traces of the requests due in the window. Nothing where
the program does not write the attribute."""
from chipbench import timeline


def read(ctx, params):
    values = [1000.0 * s["attributes"][params["attribute"]]
              for t in ctx.traces for s in t["spans"]
              if s["name"] == "engine.queue"
              and params["attribute"] in s["attributes"]]
    return timeline.percentile(values, params["q"]) if values else None
