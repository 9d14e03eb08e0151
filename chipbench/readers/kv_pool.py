"""Share of the KV pool's blocks held by running or prefilling requests
(``kv_blocks_live`` over live + cached + free, the counts a step record
takes when the step is scheduled), mean over the window's records, in
percent. ``cached`` blocks are held only by the prefix cache and can be
evicted. Nothing where the records carry no counts."""


def read(ctx, params):
    shares = []
    for s in ctx.steps:
        total = (s.get("kv_blocks_live", 0) + s.get("kv_blocks_cached", 0)
                 + s.get("kv_blocks_free", 0))
        if total > 0:
            shares.append(100.0 * s[params["count"]] / total)
    return sum(shares) / len(shares) if shares else None
