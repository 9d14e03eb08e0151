"""Share of the HBM roofline a decode forward reaches, for a model that
runs its ``num_hidden_layers`` layers ``total_ut_steps`` times over the
same weights (``model_type: ouro``).

The bytes are the model's own work: no pass can begin before the last
ended and a layer's matrices (103 MB at Ouro-2.6B's widths) outlast no
on-chip memory, so a forward reads every layer's matrices once in every
pass, the head once, and every live token's keys and values in every
page layer (one for each pass of each layer). Per forward, over the
decode bursts of the traced span's step records::

    total_ut_steps x num_hidden_layers x layer_bytes + head_bytes
    + kv_live_tokens / forwards x kv bytes a token and page layer
      x total_ut_steps x num_hidden_layers

(``kv_live_tokens``: the live tokens of the rows that write a token,
summed over the burst's steps, as the decode kernel's own reader counts
them.) The time is the device time a forward takes in **the median
``decode_k<K>`` program of the trace** (the self time of its operations
over K; the median, because the first or last program of a trace is cut
by its edge: ``readers/routed_experts.py``). Embedding rows, norm
weights and activations are left out of the bytes, so the share is a
lower bound, and it reads under 100.

Nothing where the configuration has no ``total_ut_steps`` (another
model), the run no trace, the records no ``kv_live_tokens`` (the parent,
or a burst off the Pallas kernel), or the trace no decode program."""
import bisect
import statistics

from chipbench import peaks, tracefile

KEYS = ("total_ut_steps", "num_hidden_layers", "hidden_size",
        "intermediate_size", "num_attention_heads", "vocab_size")
PREFIX = "decode_k"


def layer_bytes(config: dict, weight_bytes: int = 2) -> int:
    """Bytes of one layer's matrices: q, k, v, o and the SwiGLU's three."""
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    kv_heads = config.get("num_key_value_heads", heads)
    head_dim = config.get("head_dim") or hidden // heads
    return weight_bytes * (
        hidden * (heads + 2 * kv_heads) * head_dim + heads * head_dim * hidden
        + 3 * hidden * config["intermediate_size"])


def forward_bytes(config: dict, live_tokens: float, page_bytes: int = 2,
                  weight_bytes: int = 2) -> float:
    """Bytes one decode forward over ``live_tokens`` cached tokens must
    read: the weights pass by pass, the head, every page layer."""
    applications = config["total_ut_steps"] * config["num_hidden_layers"]
    return (applications * layer_bytes(config, weight_bytes)
            + weight_bytes * config["hidden_size"] * config["vocab_size"]
            + live_tokens * applications
            * peaks.kv_bytes_per_token_per_layer(config, page_bytes))


def seconds_a_forward(plane: dict) -> list:
    """For each ``decode_k<K>`` program of the plane, the self seconds of
    its operations over K."""
    modules = [(start, start + duration, tracefile.program(name))
               for name, start, duration in plane["modules"]
               if tracefile.program(name).startswith(PREFIX)]
    starts = [start for start, _, _ in modules]

    def label(op):
        i = bisect.bisect_right(starts, op[1] + 1e-12) - 1
        return i if i >= 0 and op[1] <= modules[i][1] + 1e-9 else "out"

    return [seconds / int(modules[i][2][len(PREFIX):])
            for i, seconds in tracefile.self_seconds(plane, label).items()
            if i != "out"]


def read(ctx, params):
    if any(key not in ctx.config for key in KEYS):
        return None
    steps = [s for s in ctx.traced_steps
             if s["kind"] == "decode_burst" and "kv_live_tokens" in s]
    forwards = sum(s["forwards"] for s in steps)
    seconds = [s for plane in tracefile.for_run(ctx)
               for s in seconds_a_forward(plane)]
    if not forwards or not seconds:
        return None
    live = sum(s["kv_live_tokens"] for s in steps) / forwards
    page_bytes = 1 if ctx.kv_cache_dtype == "int8" else 2
    floor_s = forward_bytes(ctx.config, live, page_bytes) / peaks.peaks_for(
        ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * floor_s / statistics.median(seconds)
