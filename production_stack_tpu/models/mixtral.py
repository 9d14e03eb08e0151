"""Mixtral-style sparse-MoE decoder (BASELINE config 5: Mixtral-8x7B).

Llama attention + a top-k routed expert MLP. Expert compute is expressed as
a dense einsum over all experts weighted by the routing mask — on TPU this
keeps the MXU busy with one big batched matmul and avoids dynamic shapes;
with an ``ep`` mesh axis the expert dimension shards across chips and XLA
inserts the all-to-all. (Capacity-based token dropping is not needed because
every token computes its top-k experts exactly.)
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.models.llama import rms_norm, rope
from production_stack_tpu.ops.attention import (
    context_prefill_attention,
    paged_decode_attention,
    prefill_attention,
    write_kv_pages,
)


def init_params(cfg: ModelConfig, rng: jax.Array, **_unused) -> Dict:
    dtype = cfg.jnp_dtype
    H, KVH, D, Hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.hidden_size
    I, L, V, E = cfg.intermediate_size, cfg.num_layers, cfg.vocab_size, cfg.num_experts
    keys = jax.random.split(rng, 12)

    def stack(key, shape, fan_in):
        return (
            jax.random.normal(key, (L,) + shape, jnp.float32) / jnp.sqrt(fan_in)
        ).astype(dtype)

    return {
        "embed": (0.02 * jax.random.normal(keys[0], (V, Hd), jnp.float32)).astype(dtype),
        "layers": {
            "attn_norm": jnp.ones((L, Hd), dtype),
            "wq": stack(keys[1], (Hd, H * D), Hd),
            "wk": stack(keys[2], (Hd, KVH * D), Hd),
            "wv": stack(keys[3], (Hd, KVH * D), Hd),
            "wo": stack(keys[4], (H * D, Hd), H * D),
            "mlp_norm": jnp.ones((L, Hd), dtype),
            "router": stack(keys[5], (Hd, E), Hd),
            "w_gate": stack(keys[6], (E, Hd, I), Hd),
            "w_up": stack(keys[7], (E, Hd, I), Hd),
            "w_down": stack(keys[8], (E, I, Hd), I),
        },
        "final_norm": jnp.ones((Hd,), dtype),
        "lm_head": (
            jax.random.normal(keys[9], (Hd, V), jnp.float32) / jnp.sqrt(Hd)
        ).astype(dtype),
    }


def moe_mlp(cfg: ModelConfig, p: Dict, h: jax.Array) -> jax.Array:
    """Top-k routed expert MLP. h: [B, T, Hd] -> [B, T, Hd]."""
    B, T, Hd = h.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    router_logits = (h @ p["router"]).astype(jnp.float32)  # [B,T,E]
    topk_vals, topk_idx = jax.lax.top_k(router_logits, K)
    topk_w = jax.nn.softmax(topk_vals, axis=-1)  # [B,T,K]
    one_hot = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32)  # [B,T,K,E]
    dense_w = jnp.einsum("btk,btke->bte", topk_w, one_hot)  # [B,T,E]
    # All-expert compute, weighted combine (MXU-dense, EP-shardable).
    gate = jnp.einsum("bth,ehi->btei", h, p["w_gate"])
    up = jnp.einsum("bth,ehi->btei", h, p["w_up"])
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(h.dtype) * up
    out = jnp.einsum("btei,eih->bteh", act, p["w_down"])
    return jnp.einsum(
        "bteh,bte->bth", out.astype(jnp.float32), dense_w
    ).astype(h.dtype)


def _layer(
    cfg: ModelConfig, mode: str, x, p, kv, layer,
    positions, slot_mapping, block_tables, context_lens, seq_lens,
):
    B, T, Hd = x.shape
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    scale = 1.0 / (D ** 0.5)
    k_pages, v_pages = kv  # stacked [L, NB, bs, KVH, D]

    # Scope names as in llama._layer (docs/profiling.md): metadata only.
    with jax.named_scope("attn_proj"):
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        q = rope((h @ p["wq"]).reshape(B, T, H, D), positions,
                 cfg.rope_theta)
        k = rope((h @ p["wk"]).reshape(B, T, KVH, D), positions,
                 cfg.rope_theta)
        v = (h @ p["wv"]).reshape(B, T, KVH, D)
    k_pages, v_pages = write_kv_pages(
        k_pages, v_pages, k, v, slot_mapping, layer)
    with jax.named_scope("attention"):
        if mode == "prefill":
            attn = prefill_attention(
                q, k, v, scale=scale, seq_lens=seq_lens)
        elif mode == "prefill_cached":
            # Suffix prefill after a prefix-cache hit: attend over HBM
            # pages (cached prefix + just-written suffix).
            attn = context_prefill_attention(
                q, k_pages, v_pages, block_tables, positions, context_lens,
                layer, scale=scale, k_new=k, v_new=v, suffix_lens=seq_lens,
            )
        else:
            attn = paged_decode_attention(
                q[:, 0], k_pages, v_pages, block_tables, context_lens,
                layer, scale=scale,
            )[:, None]
    with jax.named_scope("attn_proj"):
        x = x + attn.reshape(B, T, H * D) @ p["wo"]

    with jax.named_scope("mlp"):
        h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
        x = x + moe_mlp(cfg, p, h)
    return x, (k_pages, v_pages)


def apply(
    params: Dict,
    cfg: ModelConfig,
    token_ids, positions, kv_pages, slot_mapping, block_tables,
    context_lens, seq_lens, *, mode: str, adapter_ids=None, output_hidden: bool = False,
    last_token=None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    del adapter_ids  # LoRA slots are a Llama-family feature for now
    with jax.named_scope("embed"):
        x = params["embed"][token_ids].astype(cfg.jnp_dtype)
    k_all, v_all = kv_pages
    layer_fn = functools.partial(
        _layer, cfg, mode,
        positions=positions, slot_mapping=slot_mapping,
        block_tables=block_tables, context_lens=context_lens, seq_lens=seq_lens,
    )

    # Stacked KV pages ride the scan carry whole (in-place under XLA);
    # see llama.apply.
    L = (k_all[0] if isinstance(k_all, tuple) else k_all).shape[0]

    def scan_body(carry, layer_params):
        x, k_all, v_all, l = carry
        x, (k_all, v_all) = layer_fn(x, layer_params, (k_all, v_all), l)
        return (x, k_all, v_all, l + 1), None

    (x, k_all, v_all, _), _ = jax.lax.scan(
        scan_body, (x, k_all, v_all, jnp.int32(0)), params["layers"],
        length=L,
    )
    with jax.named_scope("head"):
        if last_token is not None:
            # Prefill sampling reads ONE position: slice before norm +
            # head (positionwise ops commute with the slice; see
            # llama.apply).
            x = jnp.take_along_axis(x, last_token[:, None, None], axis=1)
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        if output_hidden:
            return x.astype(jnp.float32), (k_all, v_all)
        logits = (x @ params["lm_head"]).astype(jnp.float32)
        return logits, (k_all, v_all)
