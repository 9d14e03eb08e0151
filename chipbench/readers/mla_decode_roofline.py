"""Share of its roofline the absorbed latent (MLA) decode kernel reaches.
One call is one attention sublayer of one decode forward: it has to read
every live token's latent and rotated key once (``kv_lora_rank +
qk_rope_head_dim`` values in bf16: the page is key and value) and to do,
for each of ``num_attention_heads`` heads and each live token, the score
over ``kv_lora_rank + qk_rope_head_dim`` lanes and the output over
``kv_lora_rank``: two operations a lane. The floor is the larger of the
bytes' time at the peak bandwidth and the operations' time at the peak
bf16 rate (at 64 heads the two are within a factor of two of each other,
which is why the kernel is bound by neither alone), for the mean live
tokens per decode forward of the traced span's decode bursts (the step
records' exact ``kv_live_tokens``), over the kernel's mean device time
per call in the trace. The count is this file's own, from the
configuration's keys in ``ctx.config``. Nothing where the run has no
trace, the trace no call of the kernel (the XLA path, another model), or
the records lack the count."""
from chipbench import peaks, xplane

VALUE_BYTES = 2  # bf16


def token_bytes(config: dict) -> int:
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * VALUE_BYTES


def token_flops(config: dict) -> int:
    return 2 * config["num_attention_heads"] * (
        2 * config["kv_lora_rank"] + config["qk_rope_head_dim"])


def read(ctx, params):
    if ctx.device is None or "kv_lora_rank" not in ctx.config:
        return None
    steps = [s for s in ctx.traced_steps if s["kind"] == "decode_burst"
             and s.get("kv_live_tokens") is not None]
    forwards = sum(s["forwards"] for s in steps)
    calls = sum(n for k, n in ctx.device["op_counts"].items()
                if params["kernel"] in k)
    seconds = xplane.kernel_seconds(ctx.device, [params["kernel"]])
    if not forwards or not calls or seconds <= 0:
        return None
    tokens = sum(s["kv_live_tokens"] for s in steps) / forwards
    peak = peaks.peaks_for(ctx.device_kind)
    floor_s = max(tokens * token_bytes(ctx.config) / peak["hbm_bytes_per_s"],
                  tokens * token_flops(ctx.config)
                  / peak["bf16_flops_per_s"])
    return 100.0 * floor_s / (seconds / calls)
