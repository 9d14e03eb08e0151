"""Share, in percent, of device busy time (self time of the ``XLA Ops``
events) by the model part an operation belongs to: the innermost
``jax.named_scope`` of the program on the operation's name stack
(``chipbench.tracefile``: the ``tf_op`` stat of the event's metadata).
``scopes`` lists the parts to add up. An operation whose name contains one
of ``kernels`` is a kernel and belongs to no part; with ``"unscoped":
true`` the share is that of the operations that are neither under a part
nor a kernel: what the scopes leave unnamed. Nothing where the run has no
trace, or no operation of it lies under any part: a program without the
scopes."""
from chipbench import tracefile


def read(ctx, params):
    kernels = params.get("kernels", [])

    def part(op):
        if any(k in op[0] for k in kernels):
            return "kernel"
        return tracefile.scope_of(op[3]) or "none"

    wanted = ["none"] if params.get("unscoped") else params["scopes"]
    named = scoped = total = 0.0
    for plane in tracefile.for_run(ctx):
        by_part = tracefile.self_seconds(plane, part)
        total += sum(by_part.values())
        scoped += sum(v for k, v in by_part.items()
                      if k not in ("kernel", "none"))
        named += sum(by_part.get(k, 0.0) for k in wanted)
    return 100.0 * named / total if scoped > 0 else None
