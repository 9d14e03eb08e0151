"""Mean time the router added to a request, from the window's delta of
``vllm_router:router_overhead_seconds`` sum and count."""


def read(ctx, params):
    (s0, c0), (s1, c1) = (ctx.before["router_overhead"],
                          ctx.after["router_overhead"])
    return 1000.0 * (s1 - s0) / (c1 - c0) if c1 > c0 else None
