"""Share, in percent, of ``emit``'s time that the requests' token
callbacks took: an estimate. The program times the callback of one token
of each sequence in a burst (``emit_callback_s`` over
``emit_callback_samples``); a record's callbacks are taken to cost that
mean times its ``emit_tokens``, and the records' estimates are summed over
the ``phases.emit`` of the same records. Nothing where no record carries
a sample."""


def read(ctx, params):
    steps = [s for s in ctx.steps if s.get("emit_callback_samples")]
    emit = sum(s["phases"].get("emit", 0.0) for s in steps)
    if emit <= 0:
        return None
    callbacks = sum(s["emit_callback_s"] * s["emit_tokens"]
                    / s["emit_callback_samples"] for s in steps)
    return 100.0 * callbacks / emit
