"""Share, in percent, of device busy time (self time of the ``XLA Ops``
events) spent in operations whose name stack holds one of ``names``
(``jax.named_scope`` names of the program, anywhere on the stack: a scope
nested in a part ``chipbench.tracefile.SCOPES`` knows is found here and
leaves that part's share as it is; or the whole stack of an operation the
compiler made and named itself, as it does the grouped matmuls of
``jax.lax.ragged_dot``: ``ragged-dot-none:``; the colon that ends every
stack is dropped). Nothing where the run has no trace,
or no operation of it lies under any of the names: a program without the
scopes."""
from chipbench import tracefile


def holds(op, names: set) -> bool:
    """Whether the operation's name stack holds one of ``names``."""
    return bool(names.intersection(op[3].rstrip(":").split("/")))


def read(ctx, params):
    names = set(params["names"])

    def under(op):
        return "in" if holds(op, names) else "out"

    inside = total = 0.0
    for plane in tracefile.for_run(ctx):
        by = tracefile.self_seconds(plane, under)
        inside += by.get("in", 0.0)
        total += sum(by.values())
    return 100.0 * inside / total if inside > 0 else None
