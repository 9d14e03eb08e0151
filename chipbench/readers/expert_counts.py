"""What the expert layer's counters say of the window's decode forwards
(``Family.stats`` of the program: a step record carries
``moe_assignments``, ``moe_experts_hit`` and ``moe_max_expert_load``,
each summed over the ``stats_forwards`` decode forwards it covers and over
the sparse layers). ``what``:

- ``hit_pct``: held experts that received a token, per sparse layer and
  decode forward, over the experts held;
- ``max_over_mean``: the largest load of a held expert over the mean
  load, weighted by the forwards (sum of the largest loads over sum of
  assignments / experts held).

Nothing where no record carries the counts: a program without them."""


def sparse_layers(config: dict) -> int:
    held = config["num_hidden_layers"]
    return config.get("mlp_layer_types", [])[:held].count("sparse")


def counted(steps):
    """(decode forwards, {count: sum}) over the records that carry the
    expert layer's counts."""
    steps = [s for s in steps if s.get("stats_forwards")]
    names = ("moe_assignments", "moe_experts_hit", "moe_max_expert_load")
    return (sum(s["stats_forwards"] for s in steps),
            {n: sum(s[n] for s in steps) for n in names})


def read(ctx, params):
    forwards, sums = counted(ctx.steps)
    if not forwards or not sums["moe_assignments"]:
        return None
    held = ctx.config["num_experts"]
    if params["what"] == "hit_pct":
        return 100.0 * sums["moe_experts_hit"] / (
            forwards * sparse_layers(ctx.config) * held)
    if params["what"] == "max_over_mean":
        return held * sums["moe_max_expert_load"] / sums["moe_assignments"]
    raise ValueError(f"unknown count {params['what']!r}")
