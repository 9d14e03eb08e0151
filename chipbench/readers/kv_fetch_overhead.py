"""What the decode attention kernel copies out of HBM beyond what its rows
hold: ``100 x (fetched - live) / live`` over the window's decode step
records, ``kv_fetch_tokens`` being the token slots the kernel copies (per
scan step the live pages, whole, of the rows that write a token) and
``kv_live_tokens`` the tokens those same rows hold at those same steps.
Nothing where the records carry neither (the XLA path, or a program that
does not count them)."""


def read(ctx, params):
    steps = [s for s in ctx.steps
             if s["kind"] in params["kinds"] and s.get("kv_live_tokens")]
    live = sum(s["kv_live_tokens"] for s in steps)
    if not live:
        return None
    fetched = sum(s["kv_fetch_tokens"] for s in steps)
    return 100.0 * (fetched - live) / live
