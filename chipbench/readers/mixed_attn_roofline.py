"""Share of the HBM roofline the decode attention kernel reaches in a
model whose layers are of two kinds: a full layer's call must read the
keys and values of every live token, a sliding layer's only those of the
last ``sliding_window``. Bytes of one decode forward over all held layers
(full layers x ``kv_live_tokens`` + sliding layers x
``kv_live_tokens_window``, the step records' exact counts over the traced
span's decode bursts, times the bytes a token holds in one layer's
pages), divided by the layers: the mean call's bytes, over the peak
bandwidth, over the kernel's mean device time per call in the trace. One
call is one layer of one decode forward. The count per layer kind is
this file's own, from the configuration's keys in ``ctx.config``. Nothing
where the run has no trace or the records lack the counts (the XLA path,
a program without them)."""
from chipbench import peaks, xplane


def read(ctx, params):
    if ctx.device is None:
        return None
    steps = [s for s in ctx.traced_steps if s["kind"] == "decode_burst"
             and s.get("kv_live_tokens_window") is not None]
    forwards = sum(s["forwards"] for s in steps)
    calls = sum(n for k, n in ctx.device["op_counts"].items()
                if params["kernel"] in k)
    seconds = xplane.kernel_seconds(ctx.device, [params["kernel"]])
    if not forwards or not calls or seconds <= 0:
        return None
    held = ctx.config["num_hidden_layers"]
    kinds = ctx.config["layer_types"][:held]
    sliding = kinds.count("sliding_attention")
    tokens = ((held - sliding) * sum(s["kv_live_tokens"] for s in steps)
              + sliding * sum(s["kv_live_tokens_window"] for s in steps))
    page_bytes = 1 if ctx.kv_cache_dtype == "int8" else 2
    bytes_per_call = (tokens / forwards / held
                      * peaks.kv_bytes_per_token_per_layer(
                          ctx.config, page_bytes))
    floor_s = bytes_per_call / peaks.peaks_for(
        ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * floor_s / (seconds / calls)
