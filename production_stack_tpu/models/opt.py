"""OPT decoder (facebook/opt-*) for the smoke-test config.

BASELINE config 1 is ``facebook/opt-125m`` single-pod; the reference deploys
it via CPU vLLM (``values-01-minimal-example.yaml``). Differences from the
Llama family: learned positional embeddings (offset by 2), LayerNorm instead
of RMSNorm, ReLU MLP, no RoPE, MHA only. Same paged-KV serving interface.
"""

from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from production_stack_tpu.models import decoder
from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.models.registry import Family
from production_stack_tpu.models.weights import (
    _iter_checkpoint_tensors,
    _to_dtype,
    report_incomplete,
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


def _layer(cfg: ModelConfig, mode: str, x, per_layer, kv, layer, batch):
    p, _no_lora = per_layer
    B, T, Hd = x.shape
    H, D = cfg.num_heads, cfg.head_dim

    # Scope names as in llama.attention_half (docs/profiling.md).
    with jax.named_scope("attn_proj"):
        h = layer_norm(x, p["ln1_w"], p["ln1_b"])
        q = (h @ p["wq"] + p["wq_b"]).reshape(B, T, H, D)
        k = (h @ p["wk"] + p["wk_b"]).reshape(B, T, H, D)
        v = (h @ p["wv"] + p["wv_b"]).reshape(B, T, H, D)
    attn, kv = decoder.attend(
        mode, q, k, v, kv, layer, batch, scale=1.0 / (D ** 0.5))
    with jax.named_scope("attn_proj"):
        x = x + attn.reshape(B, T, H * D) @ p["wo"] + p["wo_b"]

    with jax.named_scope("mlp"):
        h = layer_norm(x, p["ln2_w"], p["ln2_b"])
        h = jax.nn.relu(h @ p["fc1"] + p["fc1_b"])
        x = x + h @ p["fc2"] + p["fc2_b"]
    return x, kv


@jax.named_scope("embed")
def _embed(params: Dict, cfg: ModelConfig, token_ids, positions,
           adapter_ids):
    del adapter_ids  # no LoRA slots in this tree
    x = params["embed"][token_ids].astype(cfg.jnp_dtype)
    x = x + params["pos_embed"][positions + POS_OFFSET].astype(cfg.jnp_dtype)
    return x, None, None, None


@jax.named_scope("head")
def _head(params: Dict, cfg: ModelConfig, x, output_hidden: bool):
    x = layer_norm(x, params["final_ln_w"], params["final_ln_b"])
    if output_hidden:
        return x.astype(jnp.float32)
    return (x @ params["embed"].T).astype(jnp.float32)  # always tied


# HF leaf under ``model.decoder.layers.<i>.`` -> (our key, transpose).
_LAYER_LEAVES = {
    "self_attn_layer_norm.weight": ("ln1_w", False),
    "self_attn_layer_norm.bias": ("ln1_b", False),
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.q_proj.bias": ("wq_b", False),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.k_proj.bias": ("wk_b", False),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.v_proj.bias": ("wv_b", False),
    "self_attn.out_proj.weight": ("wo", True),
    "self_attn.out_proj.bias": ("wo_b", False),
    "final_layer_norm.weight": ("ln2_w", False),
    "final_layer_norm.bias": ("ln2_b", False),
    "fc1.weight": ("fc1", True),
    "fc1.bias": ("fc1_b", False),
    "fc2.weight": ("fc2", True),
    "fc2.bias": ("fc2_b", False),
}
_TOP_LEAVES = {
    "embed_tokens.weight": "embed",
    "embed_positions.weight": "pos_embed",
    "final_layer_norm.weight": "final_ln_w",
    "final_layer_norm.bias": "final_ln_b",
}


def load_checkpoint(cfg: ModelConfig, path: str) -> Dict:
    L = cfg.num_layers
    dtype = cfg.jnp_dtype
    per_layer: Dict[str, List] = {
        k: [None] * L for k, _ in _LAYER_LEAVES.values()}
    top: Dict[str, jnp.ndarray] = {}
    unmapped = []

    prefix = "model.decoder."
    for name, arr in _iter_checkpoint_tensors(path):
        short = name[len(prefix):] if name.startswith(prefix) else name
        if short in _TOP_LEAVES:
            top[_TOP_LEAVES[short]] = _to_dtype(arr, dtype)
        elif short == "lm_head.weight":
            continue  # OPT ties lm_head to embeddings
        elif short.startswith("layers."):
            rest = short[len("layers."):]
            idx_str, leaf = rest.split(".", 1)
            i = int(idx_str)
            entry = _LAYER_LEAVES.get(leaf)
            if entry is None or i >= L:
                unmapped.append(name)
                continue
            key, transpose = entry
            per_layer[key][i] = _to_dtype(
                arr.T if transpose else arr, dtype)
        else:
            unmapped.append(name)

    missing = [
        f"layers.{k}[{i}]" for k, v in per_layer.items()
        for i, leaf in enumerate(v) if leaf is None
    ] + [k for k in _TOP_LEAVES.values() if k not in top]
    report_incomplete(path, missing, unmapped)
    return {**top,
            "layers": {k: jnp.stack(v) for k, v in per_layer.items()}}


FAMILY = Family(
    model_types=("opt",),
    init_params=init_params,
    embed=_embed,
    layer=_layer,
    head=_head,
    load=load_checkpoint,
    specs={
        ("embed",): P(None, None),
        ("pos_embed",): P(None, None),
        ("final_ln_w",): P(None),
        ("final_ln_b",): P(None),
        ("layers", "ln1_w"): P(None, None),
        ("layers", "ln1_b"): P(None, None),
        ("layers", "ln2_w"): P(None, None),
        ("layers", "ln2_b"): P(None, None),
        # qkv and fc1 column-parallel (their biases with them), wo and
        # fc2 row-parallel (biases added after the all-reduce).
        ("layers", "wq"): P(None, None, "tp"),
        ("layers", "wq_b"): P(None, "tp"),
        ("layers", "wk"): P(None, None, "tp"),
        ("layers", "wk_b"): P(None, "tp"),
        ("layers", "wv"): P(None, None, "tp"),
        ("layers", "wv_b"): P(None, "tp"),
        ("layers", "wo"): P(None, "tp", None),
        ("layers", "wo_b"): P(None, None),
        ("layers", "fc1"): P(None, None, "tp"),
        ("layers", "fc1_b"): P(None, "tp"),
        ("layers", "fc2"): P(None, "tp", None),
        ("layers", "fc2_b"): P(None, None),
    },
)

apply = functools.partial(decoder.apply, FAMILY)
