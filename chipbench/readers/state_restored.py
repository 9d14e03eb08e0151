"""Share, in percent, of the rows of the window's prefill dispatches that
began from a cache block's state: ``state_restores`` over ``state_rows``
of the step records (a program whose blocks hold a state beside their
pages, ``Family.block_state``: a row behind a prefix hit or an earlier
chunk reads its halo from the block before its first position, with no
copy). Nothing where no record carries the counts: a program without
such a state."""


def read(ctx, params):
    steps = [s for s in ctx.steps if s.get("state_rows")]
    rows = sum(s["state_rows"] for s in steps)
    if not rows:
        return None
    return 100.0 * sum(s.get("state_restores", 0) for s in steps) / rows
