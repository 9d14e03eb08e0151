"""Shares of a roofline in the decode programs of a model that names its
sizes as SmallThinker's config does: sparsely routed ReGLU experts in
every layer, full and window attention layers mixed
(``sliding_window_layout``), four kv heads. The bytes and operations are
counted here, from the configuration's published keys in ``ctx.config``
and the step records' exact counts over the traced span's decode bursts.
``what``:

- ``reglu_expert_roofline_pct``: the expert layer's grouped matmuls. Per
  layer and decode forward, the larger of the bytes' time at the peak
  bandwidth and the operations' time at the peak bf16 rate: each expert
  hit read whole, three matrices of ``hidden_size x moe_ffn_hidden_size``
  in bf16 (11.8 MB at 2560 x 768); each assignment's row read twice and
  written once at ``hidden_size`` and written twice and read twice at the
  expert's width; ``2 x 3 x hidden_size x moe_ffn_hidden_size``
  operations an assignment (``readers/expert_matmul_roofline.py``'s
  reckoning, under this model's key names). Over the device time of the
  operations under ``names`` (the scope ``moe_experts``; the names the
  compiler leaves on ``ragged_dot``) per layer and forward in the median
  ``decode_k<K>`` program of the trace (``readers/routed_experts.py``:
  the first or last program of a trace is cut by its edge).
- ``window_full_attn_roofline_pct``: the decode attention kernel. Bytes
  of one decode forward over all held layers: ``kv_live_tokens`` in the
  layers whose ``sliding_window_layout`` is 0, ``kv_live_tokens_window``
  in the others, times the bytes a token holds in one layer's pages (``2
  x num_key_value_heads x head_dim`` x 2 B: 2 KiB), divided by the
  layers: the mean call's bytes, over the peak bandwidth, over the
  kernel's mean device time per call in the trace.
- ``sparse_decode_step_roofline_pct``: the whole decode step. Every byte
  a decode forward must read: each layer's attention and router
  matrices, the experts the counters say were hit, the head, the live
  pages by layer kind: at the peak bandwidth, over the device time a
  forward takes in the median ``decode_k<K>`` program of the trace (the
  self time of all its operations over K). Embedding rows, norm weights
  and activations are left out, so the share is a lower bound and reads
  under 100.

Nothing where the configuration lacks the keys (another model), the run
has no trace, the records lack the counts (the parent; a burst off the
Pallas kernel) or the trace holds no decode program."""
import statistics

from chipbench import peaks, tracefile, xplane
from chipbench.readers.looped_decode_roofline import seconds_a_forward
from chipbench.readers.routed_experts import matmul_roofline_pct

KEYS = ("moe_num_primary_experts", "moe_ffn_hidden_size",
        "sliding_window_layout", "num_hidden_layers", "hidden_size",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "vocab_size")
WEIGHT_BYTES = 2  # bf16


def page_bytes_a_token(config: dict, kv_cache_dtype: str) -> int:
    """Keys and values of one token in one layer's pages."""
    size = 1 if kv_cache_dtype == "int8" else 2
    return 2 * config["num_key_value_heads"] * config["head_dim"] * size


def window_layers(config: dict) -> int:
    held = config["num_hidden_layers"]
    return sum(1 for flag in config["sliding_window_layout"][:held] if flag)


def decode_bursts(ctx):
    """(the traced span's decode records that carry both live-token
    counts, their forwards)."""
    steps = [s for s in ctx.traced_steps if s["kind"] == "decode_burst"
             and s.get("kv_live_tokens_window") is not None]
    return steps, sum(s["forwards"] for s in steps)


def page_bytes_a_forward(ctx, steps, forwards) -> float:
    """Live pages one decode forward reads over all held layers."""
    held, window = ctx.config["num_hidden_layers"], window_layers(ctx.config)
    tokens = ((held - window) * sum(s["kv_live_tokens"] for s in steps)
              + window * sum(s["kv_live_tokens_window"] for s in steps))
    return tokens / forwards * page_bytes_a_token(ctx.config,
                                                  ctx.kv_cache_dtype)


def dense_bytes_a_layer(config: dict) -> int:
    """Attention (q, k, v, o) and router matrices of one layer."""
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    kv_heads, head_dim = config["num_key_value_heads"], config["head_dim"]
    return WEIGHT_BYTES * (
        hidden * (heads + 2 * kv_heads) * head_dim + heads * head_dim * hidden
        + hidden * config["moe_num_primary_experts"])


def expert_bytes(config: dict) -> int:
    return (3 * config["hidden_size"] * config["moe_ffn_hidden_size"]
            * WEIGHT_BYTES)


def reglu_expert_roofline_pct(ctx, params):
    sizes = {"hidden_size": ctx.config["hidden_size"],
             "moe_intermediate_size": ctx.config["moe_ffn_hidden_size"]}
    return matmul_roofline_pct(
        ctx, sizes, ctx.config["num_hidden_layers"], set(params["names"]),
        params["program_prefix"])


def window_full_attn_roofline_pct(ctx, params):
    steps, forwards = decode_bursts(ctx)
    calls = sum(n for k, n in ctx.device["op_counts"].items()
                if params["kernel"] in k)
    seconds = xplane.kernel_seconds(ctx.device, [params["kernel"]])
    if not forwards or not calls or seconds <= 0:
        return None
    bytes_per_call = (page_bytes_a_forward(ctx, steps, forwards)
                      / ctx.config["num_hidden_layers"])
    floor_s = bytes_per_call / peaks.peaks_for(
        ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * floor_s / (seconds / calls)


def sparse_decode_step_roofline_pct(ctx, params):
    steps, forwards = decode_bursts(ctx)
    counted = [s for s in ctx.traced_steps if s.get("stats_forwards")
               and "moe_experts_hit" in s]
    stats_forwards = sum(s["stats_forwards"] for s in counted)
    seconds = [s for plane in tracefile.for_run(ctx)
               for s in seconds_a_forward(plane)]
    if not forwards or not stats_forwards or not seconds:
        return None
    config = ctx.config
    hit = sum(s["moe_experts_hit"] for s in counted) / stats_forwards
    total = (config["num_hidden_layers"] * dense_bytes_a_layer(config)
             + hit * expert_bytes(config)
             + WEIGHT_BYTES * config["hidden_size"] * config["vocab_size"]
             + page_bytes_a_forward(ctx, steps, forwards))
    floor_s = total / peaks.peaks_for(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * floor_s / statistics.median(seconds)


READ = {"reglu_expert_roofline_pct": reglu_expert_roofline_pct,
        "window_full_attn_roofline_pct": window_full_attn_roofline_pct,
        "sparse_decode_step_roofline_pct": sparse_decode_step_roofline_pct}


def read(ctx, params):
    if ctx.device is None or any(key not in ctx.config for key in KEYS):
        return None
    return READ[params["what"]](ctx, params)
