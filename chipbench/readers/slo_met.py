"""Share of the requests due in the window that met both latency limits
of the traffic file; a failed request is a miss."""
from chipbench import timeline


def read(ctx, params):
    limits = ctx.traffic.get("limits") or {}
    if not ctx.due or "ttft_limit_s" not in limits:
        return None
    return timeline.slo_met_pct(ctx.due, limits["ttft_limit_s"],
                                limits["tpot_limit_s"])
