"""Share, in percent, of the window's decode assignments that landed on a
zero-compute expert: ``moe_zero_assignments`` of the step records (the
expert layer's count, summed over the decode forwards a record covers
and over the layers) over all assignments of the window's decode
forwards, ``tokens x moe_topk x num_layers`` of its decode bursts (a
record carries the counts of the bursts read back in its step, so the
two sums are of the same bursts but for the window's first and last).
With random weights every router output is as likely as another:
``zero_expert_num`` of ``n_routed_experts x chips_per_layer +
zero_expert_num``, a third at 256 of 768; a checkpoint's skew shows
here. Nothing where no record carries the count: a program without
zero-compute experts."""


def read(ctx, params):
    counted = [s for s in ctx.steps if s.get("stats_forwards")
               and "moe_zero_assignments" in s]
    tokens = sum(s["tokens"] for s in ctx.steps
                 if s["kind"] == "decode_burst")
    if not counted or not tokens or "moe_topk" not in ctx.config:
        return None
    return 100.0 * sum(s["moe_zero_assignments"] for s in counted) / (
        tokens * ctx.config["moe_topk"] * ctx.config["num_layers"])
