"""What the program's counters say of a model that names its sizes as
SmallThinker's config does (``moe_num_primary_experts``,
``sliding_window_layout``, ``sliding_window_size``): each number from the
window's step records alone, no trace. ``what``:

- ``primary_experts_hit_pct``: held experts that received a row, per
  layer and decode forward, over the ``moe_num_primary_experts`` held:
  ``moe_experts_hit`` (summed over the ``stats_forwards`` decode forwards
  a record covers and over the layers, every one of which has an expert
  layer) over ``stats_forwards x num_hidden_layers x experts``;
- ``window_spared_kv_pct``: how far the window binds under the traffic,
  ``100 x (1 - kv_live_tokens_window / kv_live_tokens)`` over the
  window's decode records: of the live tokens a full layer's decode
  kernel reads, the share a window layer's does not (0 where every
  context lies inside ``sliding_window_size``: the cell is mis-sized);
- ``kv_window_dead_pct``: what a per-kind allocator (or a ring in the
  window layers) would free of the live pool: the engine's
  ``kv_window_dead_tokens`` (over the decoding sequences, ``max(0,
  context - window)`` summed, times the window layers' share of the page
  layers) over the pool's live tokens (``kv_blocks_live x block size``),
  mean over the window's decode records, in percent.

Nothing where the configuration lacks the keys (another model) or no
record carries the counts (the parent, or a burst off the Pallas path)."""

KEYS = ("moe_num_primary_experts", "sliding_window_layout",
        "sliding_window_size", "num_hidden_layers")


def block_size(config: dict) -> int:
    flags = config.get("server_flags", [])
    return (int(flags[flags.index("--block-size") + 1])
            if "--block-size" in flags else 64)


def primary_experts_hit_pct(ctx):
    steps = [s for s in ctx.steps if s.get("stats_forwards")
             and "moe_experts_hit" in s]
    forwards = sum(s["stats_forwards"] for s in steps)
    if not forwards:
        return None
    slots = (forwards * ctx.config["num_hidden_layers"]
             * ctx.config["moe_num_primary_experts"])
    return 100.0 * sum(s["moe_experts_hit"] for s in steps) / slots


def window_spared_kv_pct(ctx):
    steps = [s for s in ctx.steps if s["kind"] == "decode_burst"
             and s.get("kv_live_tokens_window") is not None]
    live = sum(s["kv_live_tokens"] for s in steps)
    if not live:
        return None
    return 100.0 * (1.0 - sum(s["kv_live_tokens_window"] for s in steps)
                    / live)


def kv_window_dead_pct(ctx):
    tokens = block_size(ctx.config)
    shares = [100.0 * s["kv_window_dead_tokens"]
              / (s["kv_blocks_live"] * tokens)
              for s in ctx.steps if s["kind"] == "decode_burst"
              and "kv_window_dead_tokens" in s and s.get("kv_blocks_live")]
    return sum(shares) / len(shares) if shares else None


READ = {"primary_experts_hit_pct": primary_experts_hit_pct,
        "window_spared_kv_pct": window_spared_kv_pct,
        "kv_window_dead_pct": kv_window_dead_pct}


def read(ctx, params):
    if any(key not in ctx.config for key in KEYS):
        return None
    return READ[params["what"]](ctx)
