"""OPT decoder (facebook/opt-*) for the smoke-test config.

BASELINE config 1 is ``facebook/opt-125m`` single-pod; the reference deploys
it via CPU vLLM (``values-01-minimal-example.yaml``). Differences from the
Llama family: learned positional embeddings (offset by 2), LayerNorm instead
of RMSNorm, ReLU MLP, no RoPE, MHA only. Same paged-KV serving interface.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.ops.attention import (
    context_prefill_attention,
    paged_decode_attention,
    prefill_attention,
    write_kv_pages,
)

POS_OFFSET = 2  # OPT's learned-position quirk


def layer_norm(x, weight, bias, eps=1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def init_params(cfg: ModelConfig, rng: jax.Array, **_unused) -> Dict:
    dtype = cfg.jnp_dtype
    H, D, Hd = cfg.num_heads, cfg.head_dim, cfg.hidden_size
    I, L, V = cfg.intermediate_size, cfg.num_layers, cfg.vocab_size
    keys = jax.random.split(rng, 8)

    def stack(key, shape, fan_in):
        return (
            jax.random.normal(key, (L,) + shape, jnp.float32) / jnp.sqrt(fan_in)
        ).astype(dtype)

    return {
        "embed": (0.02 * jax.random.normal(keys[0], (V, Hd), jnp.float32)).astype(dtype),
        "pos_embed": (
            0.02 * jax.random.normal(keys[1], (cfg.max_position + POS_OFFSET, Hd), jnp.float32)
        ).astype(dtype),
        "layers": {
            "ln1_w": jnp.ones((L, Hd), dtype),
            "ln1_b": jnp.zeros((L, Hd), dtype),
            "wq": stack(keys[2], (Hd, H * D), Hd),
            "wq_b": jnp.zeros((L, H * D), dtype),
            "wk": stack(keys[3], (Hd, H * D), Hd),
            "wk_b": jnp.zeros((L, H * D), dtype),
            "wv": stack(keys[4], (Hd, H * D), Hd),
            "wv_b": jnp.zeros((L, H * D), dtype),
            "wo": stack(keys[5], (H * D, Hd), H * D),
            "wo_b": jnp.zeros((L, Hd), dtype),
            "ln2_w": jnp.ones((L, Hd), dtype),
            "ln2_b": jnp.zeros((L, Hd), dtype),
            "fc1": stack(keys[6], (Hd, I), Hd),
            "fc1_b": jnp.zeros((L, I), dtype),
            "fc2": stack(keys[7], (I, Hd), I),
            "fc2_b": jnp.zeros((L, Hd), dtype),
        },
        "final_ln_w": jnp.ones((Hd,), dtype),
        "final_ln_b": jnp.zeros((Hd,), dtype),
    }


def _layer(
    cfg: ModelConfig, mode: str, x, p, kv, layer,
    positions, slot_mapping, block_tables, context_lens, seq_lens,
):
    B, T, Hd = x.shape
    H, D = cfg.num_heads, cfg.head_dim
    scale = 1.0 / (D ** 0.5)
    k_pages, v_pages = kv  # stacked [L, NB, bs, KVH, D]

    # Scope names as in llama._layer (docs/profiling.md): metadata only.
    with jax.named_scope("attn_proj"):
        h = layer_norm(x, p["ln1_w"], p["ln1_b"])
        q = (h @ p["wq"] + p["wq_b"]).reshape(B, T, H, D)
        k = (h @ p["wk"] + p["wk_b"]).reshape(B, T, H, D)
        v = (h @ p["wv"] + p["wv_b"]).reshape(B, T, H, D)
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
        x = x + attn.reshape(B, T, H * D) @ p["wo"] + p["wo_b"]

    with jax.named_scope("mlp"):
        h = layer_norm(x, p["ln2_w"], p["ln2_b"])
        h = jax.nn.relu(h @ p["fc1"] + p["fc1_b"])
        x = x + h @ p["fc2"] + p["fc2_b"]
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
        x = x + params["pos_embed"][positions + POS_OFFSET].astype(
            cfg.jnp_dtype)
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
        x = layer_norm(x, params["final_ln_w"], params["final_ln_b"])
        if output_hidden:
            return x.astype(jnp.float32), (k_all, v_all)
        logits = (x @ params["embed"].T).astype(jnp.float32)
        return logits, (k_all, v_all)
