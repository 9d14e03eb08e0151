"""Share of the bf16 peak the prefill attention of a latent-attention
(MLA) model reaches: the model's own operations, unpadded (for every
causal pair of a query and a key it sees, ``num_attention_heads`` heads
times a score over ``qk_nope_head_dim + qk_rope_head_dim`` lanes and an
output over ``v_head_dim``, two operations a lane, in each of the ``2 x
num_layers`` attention sublayers), over the peak rate, over the device
time of the operations under the scope ``attention`` in the ``prefill``
and ``prefill_cached`` programs of the traced span. The pairs are the
program's own count (``attn_pairs`` of the traced span's prefill step
records: a span ``[start, end)`` of a prompt has ``sum(p + 1)`` of
them). What the program spends beyond them (a padded chunk, a gathered
context as wide as the table's bucket, 192-lane keys in 256-lane tiles)
lowers the share; the up-projection of the latents is under ``mla_proj``
and not in it. Nothing where the run has no trace, the records no count
(another model, the parent), or the trace no operation under the scope."""
from chipbench import peaks, tracefile
from chipbench.readers.stack_share import holds


def read(ctx, params):
    if "kv_lora_rank" not in ctx.config:
        return None
    pairs = sum(s.get("attn_pairs", 0) for s in ctx.traced_steps)
    if not pairs:
        return None
    config = ctx.config
    flops = (pairs * 2 * config["num_attention_heads"]
             * (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
                + config["v_head_dim"]) * 2 * config["num_layers"])
    names, prefix = set(params["names"]), params["program_prefix"]
    seconds = 0.0
    for plane in tracefile.for_run(ctx):
        at = tracefile.module_at(plane)

        def label(op):
            return ("in" if holds(op, names)
                    and at(op[1]).startswith(prefix) else "out")

        seconds += tracefile.self_seconds(plane, label).get("in", 0.0)
    if seconds <= 0:
        return None
    return 100.0 * flops / peaks.peaks_for(
        ctx.device_kind)["bf16_flops_per_s"] / seconds
