"""The expert layer's two numbers for a configuration that says where its
sparse layers begin with ``num_dense_layers`` (no ``mlp_layer_types``
list, which ``readers/expert_counts.py::sparse_layers`` counts): the
sparse layers are the held layers less the leading dense ones. ``what``:

- ``hit_pct``: held experts that received a token, per sparse layer and
  decode forward, over the experts held (``readers/expert_counts.py``'s
  number over this file's layer count);
- ``matmul_roofline_pct``: the share of its roofline the expert layer's
  operations reach in the ``decode_k<K>`` programs of the traced span, as
  ``readers/expert_matmul_roofline.py`` computes it and with its
  ``layer_bytes`` / ``layer_flops``: per sparse layer and decode forward,
  the larger of the bytes' time (each expert hit read whole, each
  assignment's rows) and the operations' time, over the mean device time
  of the operations under ``names``.

Nothing where the records carry no counts, the configuration no sparse
layer, or (the roofline) the run no trace with an operation under the
names."""
from chipbench import peaks, tracefile
from chipbench.readers.expert_counts import counted
from chipbench.readers.expert_matmul_roofline import layer_bytes, layer_flops
from chipbench.readers.stack_share import holds


def sparse_layers(config: dict) -> int:
    held = config["num_hidden_layers"]
    return held - min(config.get("num_dense_layers", 0), held)


def hit_pct(ctx, layers: int):
    forwards, sums = counted(ctx.steps)
    if not forwards or not sums["moe_assignments"]:
        return None
    return 100.0 * sums["moe_experts_hit"] / (
        forwards * layers * ctx.config["num_experts"])


def matmul_roofline_pct(ctx, layers: int, names: set, prefix: str):
    forwards, sums = counted(ctx.traced_steps)
    if not forwards:
        forwards, sums = counted(ctx.steps)
    if not forwards:
        return None
    pairs = forwards * layers
    peak = peaks.peaks_for(ctx.device_kind)
    floor_s = max(
        layer_bytes(ctx.config, sums["moe_experts_hit"] / pairs,
                    sums["moe_assignments"] / pairs)
        / peak["hbm_bytes_per_s"],
        layer_flops(ctx.config, sums["moe_assignments"] / pairs)
        / peak["bf16_flops_per_s"])
    seconds = traced_pairs = 0.0
    for plane in tracefile.for_run(ctx):
        at = tracefile.module_at(plane)

        def label(op):
            return ("in" if holds(op, names)
                    and at(op[1]).startswith(prefix) else "out")

        seconds += tracefile.self_seconds(plane, label).get("in", 0.0)
        for name, _, _ in plane["modules"]:
            program = tracefile.program(name)
            if program.startswith(prefix):
                traced_pairs += int(program[len(prefix):]) * layers
    if seconds <= 0 or not traced_pairs:
        return None
    return 100.0 * floor_s / (seconds / traced_pairs)


def read(ctx, params):
    layers = sparse_layers(ctx.config)
    if not layers:
        return None
    if params["what"] == "hit_pct":
        return hit_pct(ctx, layers)
    if params["what"] == "matmul_roofline_pct":
        return matmul_roofline_pct(ctx, layers, set(params["names"]),
                                   params["program_prefix"])
    raise ValueError(f"unknown number {params['what']!r}")
