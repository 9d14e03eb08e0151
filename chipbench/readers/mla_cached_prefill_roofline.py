"""Share of the bf16 peak that prefill attention over a latent (MLA)
cache reaches, whichever form the program gives it, for a configuration
with one attention sublayer in each of its ``num_hidden_layers`` layers.

The work is the model's own, unpadded, and the same whether the program
up-projects the cached context or absorbs the up-projection into the
queries: for every causal pair of a query and a key it sees,
``num_attention_heads`` heads times a score over ``qk_nope_head_dim +
qk_rope_head_dim`` lanes and an output over ``v_head_dim``, two
operations a lane, in every layer. The pairs are the program's own count
(``attn_pairs`` of the traced span's prefill step records: a span
``[start, end)`` of a prompt has ``sum(p + 1)`` of them).

The time is everything the ``prefill`` and ``prefill_cached`` programs of
the traced span spend on attention once the chunk's own projections are
done: the device time of the operations under the scopes ``names``
(``attention``: the gather of the pages and the scores, softmax and
weighted sum; ``mla_up_context``: the cached context's up-projection to
per-head keys and values, where the program takes that form;
``mla_absorb``: the matmuls that fold the up-projection into the queries
and the outputs, where it takes the other). One boundary for both forms,
so a program that changes form, or that reads the pages without
gathering them, is held to the same work. What it spends beyond the
model's pairs (a padded chunk, a gathered context as wide as the table's
bucket, the up-projection of tokens no query of the chunk needed anew)
lowers the share.

Nothing where the run has no trace, the configuration lacks the keys,
the records no count (another model, the parent), or the trace no
operation under the scopes in such a program."""
from chipbench import peaks, tracefile
from chipbench.readers.stack_share import holds

KEYS = ("kv_lora_rank", "num_hidden_layers", "num_attention_heads",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")


def pair_flops(config: dict) -> int:
    """Operations one causal pair costs the model over all its layers."""
    return (2 * config["num_attention_heads"]
            * (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
               + config["v_head_dim"]) * config["num_hidden_layers"])


def read(ctx, params):
    if any(key not in ctx.config for key in KEYS):
        return None
    pairs = sum(s.get("attn_pairs", 0) for s in ctx.traced_steps)
    if not pairs:
        return None
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
    return 100.0 * pairs * pair_flops(ctx.config) / peaks.peaks_for(
        ctx.device_kind)["bf16_flops_per_s"] / seconds
