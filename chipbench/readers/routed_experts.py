"""The expert layer's two numbers for a configuration that names its
sizes ``num_layers`` (every layer has an expert layer), ``n_routed_experts``
(held here), ``hidden_size`` and ``expert_ffn_hidden_size``. ``what``:

- ``hit_pct``: held experts that received a token, per layer and decode
  forward, over the experts held (``readers/experts_after_dense.py``'s
  ``hit_pct``, handed this file's sizes under the names it reads);
- ``matmul_roofline_pct``: the share of its roofline the expert layer's
  operations reach in the ``decode_k<K>`` programs of the traced span:
  per layer and decode forward, the larger of the bytes' time (each
  expert hit read whole, each assignment's rows:
  ``expert_matmul_roofline.py``'s ``layer_bytes`` / ``layer_flops`` over
  the step records' exact counts) and the operations' time, over the
  device time of the operations under ``names`` per layer and forward of
  **the median decode program of the trace**. The median, and not the
  sum over the programs' count: where prefill takes most of a window the
  two traced seconds hold five or six decode programs, the first or the
  last of them cut by the trace's edge with its operations half in it,
  and the sum then read 106% where a trace of decode programs alone read
  81 (my chip runs, PR 41). Zero-compute experts have no matmul and are
  not in it.

Nothing where the configuration lacks the keys, the records carry no
counts, or (the roofline) the run has no trace with an operation under
the names."""
import bisect
import copy
import statistics

from chipbench import peaks, tracefile
from chipbench.readers import experts_after_dense
from chipbench.readers.expert_counts import counted
from chipbench.readers.expert_matmul_roofline import layer_bytes, layer_flops
from chipbench.readers.stack_share import holds

KEYS = ("num_layers", "n_routed_experts", "hidden_size",
        "expert_ffn_hidden_size")


def seconds_a_pair(plane: dict, names: set, prefix: str, layers: int):
    """For each program of the plane whose name starts with ``prefix``
    (``decode_k<K>``: K forwards of every layer), the device seconds of
    its operations under ``names`` per layer and forward."""
    modules = [(start, start + duration, tracefile.program(name))
               for name, start, duration in plane["modules"]
               if tracefile.program(name).startswith(prefix)]
    starts = [start for start, _, _ in modules]

    def label(op):
        if not holds(op, names):
            return "out"
        i = bisect.bisect_right(starts, op[1] + 1e-12) - 1
        return i if i >= 0 and op[1] <= modules[i][1] + 1e-9 else "out"

    by_module = tracefile.self_seconds(plane, label)
    return [seconds / (int(modules[i][2][len(prefix):]) * layers)
            for i, seconds in by_module.items() if i != "out"]


def matmul_roofline_pct(ctx, sizes: dict, layers: int, names: set,
                        prefix: str):
    forwards, sums = counted(ctx.traced_steps)
    if not forwards:
        forwards, sums = counted(ctx.steps)
    if not forwards:
        return None
    pairs = forwards * layers
    peak = peaks.peaks_for(ctx.device_kind)
    floor_s = max(
        layer_bytes(sizes, sums["moe_experts_hit"] / pairs,
                    sums["moe_assignments"] / pairs)
        / peak["hbm_bytes_per_s"],
        layer_flops(sizes, sums["moe_assignments"] / pairs)
        / peak["bf16_flops_per_s"])
    seconds = [s for plane in tracefile.for_run(ctx)
               for s in seconds_a_pair(plane, names, prefix, layers)]
    if not seconds:
        return None
    return 100.0 * floor_s / statistics.median(seconds)


def read(ctx, params):
    if any(key not in ctx.config for key in KEYS):
        return None
    sizes = {"hidden_size": ctx.config["hidden_size"],
             "moe_intermediate_size": ctx.config["expert_ffn_hidden_size"],
             "num_experts": ctx.config["n_routed_experts"]}
    layers = ctx.config["num_layers"]
    if params["what"] == "hit_pct":
        sized = copy.copy(ctx)
        sized.config = sizes
        return experts_after_dense.hit_pct(sized, layers)
    if params["what"] == "matmul_roofline_pct":
        return matmul_roofline_pct(ctx, sizes, layers, set(params["names"]),
                                   params["program_prefix"])
    raise ValueError(f"unknown number {params['what']!r}")
