"""A percentile of time to first token over the requests due in the
window, in milliseconds (recorded beside the deciding percentile)."""
from chipbench import timeline


def read(ctx, params):
    if not ctx.due:
        return None
    return 1000.0 * timeline.percentile(
        [timeline.ttft(r) for r in ctx.due], params["q"])
