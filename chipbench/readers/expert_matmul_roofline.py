"""Share of its roofline the expert layer's grouped matmuls reach in the
decode programs: for one sparse layer of one decode forward, the larger
of the time its bytes need at the peak bandwidth and the time its
operations need at the peak bf16 rate, over the mean device time of the
expert layer's operations in the ``decode_k<K>`` programs of the traced
span, per layer and forward: those whose name stack holds one of
``names`` (the program's scope ``moe_experts``: sort, gathers, combine;
and ``ragged-dot-none`` / ``ragged-dot-metadata``, which is all the name
stack the TPU compiler leaves on the grouped matmuls it makes of
``jax.lax.ragged_dot``: PERF.md section 6, PR 33).

The bytes and operations are counted here, from the configuration's keys
and the step records' exact counts (means per sparse layer and decode
forward over the traced span's records, the window's where the span has
none):

- bytes: each held expert that received a token is read whole, three
  matrices of ``hidden_size x moe_intermediate_size`` in bf16 (18.9 MB at
  3072 x 1024); each assignment's row is read twice and written once at
  ``hidden_size`` and written twice and read twice at the expert's width;
- operations: ``2 x 3 x hidden_size x moe_intermediate_size`` an
  assignment.

A decode program of the span counts where its ``XLA Modules`` event lies
inside the trace; it is ``K`` forwards of every sparse layer. Nothing
where the run has no trace, the records no counts or the trace no
operation under the names."""
from chipbench import peaks, tracefile
from chipbench.readers.expert_counts import counted, sparse_layers
from chipbench.readers.stack_share import holds

WEIGHT_BYTES = 2  # bf16


def layer_bytes(config: dict, experts_hit: float, assignments: float):
    hidden, width = config["hidden_size"], config["moe_intermediate_size"]
    weights = experts_hit * 3 * hidden * width * WEIGHT_BYTES
    rows = assignments * (3 * hidden + 4 * width) * WEIGHT_BYTES
    return weights + rows


def layer_flops(config: dict, assignments: float):
    return 2 * 3 * config["hidden_size"] * config[
        "moe_intermediate_size"] * assignments


def read(ctx, params):
    forwards, sums = counted(ctx.traced_steps)
    if not forwards:
        forwards, sums = counted(ctx.steps)
    layers = sparse_layers(ctx.config)
    if not forwards or not layers:
        return None
    pairs = forwards * layers
    peak = peaks.peaks_for(ctx.device_kind)
    floor_s = max(
        layer_bytes(ctx.config, sums["moe_experts_hit"] / pairs,
                    sums["moe_assignments"] / pairs)
        / peak["hbm_bytes_per_s"],
        layer_flops(ctx.config, sums["moe_assignments"] / pairs)
        / peak["bf16_flops_per_s"])
    names, prefix = set(params["names"]), params["program_prefix"]
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
