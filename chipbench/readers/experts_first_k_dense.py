"""The expert layer's two numbers for a configuration that names its
sizes ``num_hidden_layers``, ``first_k_dense_replace`` (the leading
layers whose MLP is dense: the sparse layers are the rest),
``n_routed_experts`` (held here), ``hidden_size`` and
``moe_intermediate_size``. ``what``:

- ``hit_pct``: held experts that received a token, per sparse layer and
  decode forward, over the experts held
  (``readers/experts_after_dense.py``'s ``hit_pct``, handed this file's
  sizes under the names it reads);
- ``matmul_roofline_pct``: the share of its roofline the routed experts'
  operations reach in the ``decode_k<K>`` programs of the traced span, as
  ``readers/routed_experts.py`` computes it (the median decode program of
  the trace; ``expert_matmul_roofline.py``'s ``layer_bytes`` /
  ``layer_flops`` over the step records' exact counts), over this file's
  sizes and its count of sparse layers. The shared expert is under
  ``moe_shared`` and not in it.

Nothing where the configuration lacks the keys, the records carry no
counts, or (the roofline) the run has no trace with an operation under
the names."""
import copy

from chipbench.readers import experts_after_dense, routed_experts

KEYS = ("num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "hidden_size", "moe_intermediate_size")


def read(ctx, params):
    if any(key not in ctx.config for key in KEYS):
        return None
    sizes = {"hidden_size": ctx.config["hidden_size"],
             "moe_intermediate_size": ctx.config["moe_intermediate_size"],
             "num_experts": ctx.config["n_routed_experts"]}
    layers = ctx.config["num_hidden_layers"] - min(
        ctx.config["first_k_dense_replace"], ctx.config["num_hidden_layers"])
    if layers <= 0:
        return None
    if params["what"] == "hit_pct":
        sized = copy.copy(ctx)
        sized.config = sizes
        return experts_after_dense.hit_pct(sized, layers)
    if params["what"] == "matmul_roofline_pct":
        return routed_experts.matmul_roofline_pct(
            ctx, sizes, layers, set(params["names"]),
            params["program_prefix"])
    raise ValueError(f"unknown number {params['what']!r}")
