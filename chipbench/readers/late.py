"""How late requests left the generator (sent - due), a percentile in
milliseconds, over the requests due in the window."""
from chipbench import timeline


def read(ctx, params):
    late = timeline.late_ms(ctx.due)
    return timeline.percentile(late, params["q"]) if late else None
