"""Share, in percent, of device busy time spent in the programs whose name
starts with the given prefix: each operation of the ``XLA Ops`` line counts
its self time for the program whose ``XLA Modules`` event covers its start
(``jit_prefill_cached(...)`` is the program ``prefill_cached``). Nothing
where the run has no trace, or no program of the trace bears the prefix:
the step programs of a tree before PR 25 were all called ``fwd``."""
from chipbench import tracefile


def read(ctx, params):
    named = total = 0.0
    for plane in tracefile.for_run(ctx):
        at = tracefile.module_at(plane)
        by_program = tracefile.self_seconds(plane, lambda op: at(op[1]))
        total += sum(by_program.values())
        named += sum(seconds for name, seconds in by_program.items()
                     if name.startswith(params["prefix"]))
    return 100.0 * named / total if named > 0 else None
