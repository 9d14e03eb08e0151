"""Share of the HBM roofline the decode attention kernel reaches: the
bytes of live keys and values one call must read (mean live KV tokens
per decode forward of the step recorder's bursts inside the traced span,
times the bytes a token holds in one layer) over the peak bandwidth, divided by the
kernel's mean device time per call in the trace. One call is one layer
of one decode forward. Bandwidth-bound: a decode query does 2 FLOPs per
KV byte. The recorder counts a burst's context at its start, so the
bytes, and the share, are a lower bound."""
from chipbench import peaks, xplane


def read(ctx, params):
    if ctx.device is None:
        return None
    steps = [s for s in ctx.traced_steps if s["kind"] == "decode_burst"]
    forwards = sum(s["forwards"] for s in steps)
    calls = sum(n for k, n in ctx.device["op_counts"].items()
                if params["kernel"] in k)
    seconds = xplane.kernel_seconds(ctx.device, [params["kernel"]])
    if not forwards or not calls or seconds <= 0:
        return None
    tokens_per_forward = sum(s["kv_read_tokens"] for s in steps) / forwards
    page_bytes = 1 if ctx.kv_cache_dtype == "int8" else 2
    bytes_per_call = tokens_per_forward * peaks.kv_bytes_per_token_per_layer(
        ctx.config, page_bytes)
    floor_s = bytes_per_call / peaks.peaks_for(
        ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * floor_s / (seconds / calls)
