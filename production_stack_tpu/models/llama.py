"""Llama-family decoder (covers Llama 2/3, Mistral, TinyLlama via config).

Functional JAX implementation built for serving with a paged KV cache. The
layer loop, the page write and the three attention modes are the shared
skeleton's (models/decoder.py); this module is what makes a model Llama:
its tree, its layer, its checkpoint names, and its record
(models/registry.py::Family) at the foot of the file.

- parameters are a pytree with per-layer leaves stacked on a leading axis so
  the decoder runs as one ``lax.scan`` over layers (single-layer trace →
  fast XLA compiles even at 80 layers);
- the attention block's three input projections are ONE leaf,
  ``layers/wqkv`` ``[L, Hd, KVH * (G + 2) * D]`` (:func:`fuse_qkv`: for
  each KV head its ``G = H / KVH`` query heads, then its key, then its
  value), read by one flat matmul whose weight operand is a slice of that
  leaf in place, like ``wo`` and the MLP matrices. Three leaves, each
  reshaped to heads right after its matmul, made the TPU compiler copy
  and transpose all three out of the stack in every layer of every
  forward (PERF.md section 6, PR 30);
- weights use bfloat16 by default; all norms/softmax accumulate in float32.

The reference stack runs these models inside vLLM CUDA images
(``helm/templates/deployment-vllm-multi.yaml:108-199``); this module is the
TPU-native replacement at the engine layer.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from production_stack_tpu.models import decoder
from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.models.registry import Family
from production_stack_tpu.models.weights import (
    _iter_checkpoint_tensors,
    _to_dtype,
    report_incomplete,
)


rms_norm = decoder.rms_norm  # the skeleton's own layer parts norm with it


def rope(
    x: jax.Array,  # [B, T, H, D]
    positions: jax.Array,  # [B, T]
    theta: float,
) -> jax.Array:
    D = x.shape[-1]
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, D // 2, dtype=jnp.float32) / (D // 2))
    )
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B,T,D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def fuse_qkv(wq, wk, wv, num_kv_heads: int):
    """The ``wqkv`` leaf from the three projections ``[..., Hd, H * D]``,
    ``[..., Hd, KVH * D]`` x 2: columns grouped by KV head, for each its
    query heads (in the standard order: query head ``h`` belongs to KV
    head ``h // G``), then its key, then its value. A re-arrangement of
    columns and nothing else, so per-column int8 scales and every
    contraction over ``Hd`` are what they were, and a ``tp`` shard of the
    last axis holds whole groups. Works on host (numpy) and device arrays
    alike: the checkpoint loader joins on the host."""
    lead = wq.shape[:-1]
    head_dim = wk.shape[-1] // num_kv_heads
    xp = np if isinstance(wq, np.ndarray) else jnp
    groups = [w.reshape(lead + (num_kv_heads, -1, head_dim))
              for w in (wq, wk, wv)]
    return xp.concatenate(groups, axis=-2).reshape(lead + (-1,))


def _split_qkv(y: jax.Array, cfg: ModelConfig, heads: int | None = None):
    """Flat q, k, v ``[B, T, heads * D]`` from the fused projection's
    output ``[B, T, KVH * (G + 2) * D]``. The barrier keeps the matmul
    flat: without it the compiler folds the reshape into the dot, wants
    the weight with ``Hd`` minor, and copies + transposes it out of the
    stacked leaf in every layer (PERF.md section 6, PR 30). What is split
    here is the activations, 6,144 values a token at Mistral's widths.
    ``heads``: the layer's own query heads where a family's layers differ
    (models/laguna.py)."""
    B, T, _ = y.shape
    H, KVH, D = heads or cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // KVH
    y = jax.lax.optimization_barrier(y).reshape(B, T, KVH, G + 2, D)
    return (y[:, :, :, :G].reshape(B, T, H * D),
            y[:, :, :, G].reshape(B, T, KVH * D),
            y[:, :, :, G + 1].reshape(B, T, KVH * D))


def init_params(
    cfg: ModelConfig,
    rng: jax.Array,
    *,
    lora_slots: int = 0,
    lora_rank: int = 16,
) -> Dict:
    """Random-init parameter pytree with layer-stacked leaves.

    With ``lora_slots > 0`` the pytree carries fixed-shape LoRA slot tensors
    (zero-initialised = identity adapters) applied to the q/v projections —
    adapters hot-swap by writing a slot, never by recompiling (SURVEY §7
    "LoRA hot-swap under jit").
    """
    dtype = cfg.jnp_dtype
    H, KVH, D, Hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.hidden_size
    I, L, V = cfg.intermediate_size, cfg.num_layers, cfg.vocab_size
    keys = jax.random.split(rng, 10)

    def winit(key, shape, fan_in):
        return (
            jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)
        ).astype(dtype)

    def stack(key, shape, fan_in):
        return winit(key, (L,) + shape, fan_in)

    params = {
        "embed": (0.02 * jax.random.normal(keys[0], (V, Hd), jnp.float32)).astype(dtype),
        "layers": {
            "attn_norm": jnp.ones((L, Hd), dtype),
            # Drawn as three matrices (the recipe chipbench's reference
            # redraws by itself), served as one leaf.
            "wqkv": fuse_qkv(
                stack(keys[1], (Hd, H * D), Hd),
                stack(keys[2], (Hd, KVH * D), Hd),
                stack(keys[3], (Hd, KVH * D), Hd), KVH),
            "wo": stack(keys[4], (H * D, Hd), H * D),
            "mlp_norm": jnp.ones((L, Hd), dtype),
            "w_gate": stack(keys[5], (Hd, I), Hd),
            "w_up": stack(keys[6], (Hd, I), Hd),
            "w_down": stack(keys[7], (I, Hd), I),
        },
        "final_norm": jnp.ones((Hd,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = winit(keys[8], (Hd, V), Hd)
    if lora_slots > 0:
        S, R = lora_slots, lora_rank
        params["lora"] = {
            "wq_a": jnp.zeros((L, S, Hd, R), dtype),
            "wq_b": jnp.zeros((L, S, R, H * D), dtype),
            "wv_a": jnp.zeros((L, S, Hd, R), dtype),
            "wv_b": jnp.zeros((L, S, R, KVH * D), dtype),
            "scaling": jnp.zeros((S,), jnp.float32),
        }
    return params


def _proj(h: jax.Array, p: Dict, name: str) -> jax.Array:
    """``h @ W`` for a weight leaf that may be int8-quantized
    (models/quantize.py): int8 storage halves the HBM weight read and the
    ``astype`` dequant fuses into the matmul operand; the per-output-
    channel scale applies to the [B, T, out] result."""
    w = p[name]
    if w.dtype == jnp.int8:
        out = h @ w.astype(h.dtype)
        return out * p[name + "_scale"][0].astype(h.dtype)
    return h @ w


@jax.named_scope("lora")
def _lora_delta(h, a, b, scaling, adapter_ids):
    """Per-sequence LoRA delta: h [B,T,Hd] @ A[sel] @ B[sel] * scale."""
    a_sel = a[adapter_ids]  # [B, Hd, R]
    b_sel = b[adapter_ids]  # [B, R, out]
    s_sel = scaling[adapter_ids]  # [B]
    mid = jnp.einsum("bth,bhr->btr", h, a_sel)
    out = jnp.einsum("btr,bro->bto", mid, b_sel)
    return out * s_sel[:, None, None].astype(out.dtype)


def attention_half(
    cfg: ModelConfig,
    mode: str,
    x: jax.Array,  # [B, T, Hd]
    p: Dict,  # one layer's un-stacked leaves
    lora: Dict | None,  # un-stacked per-layer LoRA leaves, or None
    kv: Tuple,  # STACKED pages [L, NB, bs, KVH, D]
    layer: jax.Array,  # scalar PAGE-layer index
    batch: decoder.Batch, *, out=lambda y: y,  # on wo's output (ouro)
):
    """RMS norm, the fused ``wqkv`` projection behind the barrier, LoRA
    deltas if slots are given, rotary, ``decoder.attend``, ``wo``, ``out``
    before the residual: the stream after attention, and the pages.
    Mixtral's layer is this plus its expert MLP (models/mixtral.py)."""
    B, T, Hd = x.shape
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    # The named scopes are what a profiler trace files the device's time
    # under (docs/profiling.md): metadata only, no operation changes.
    with jax.named_scope("attn_proj"):
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        q_flat, k_flat, v_flat = _split_qkv(_proj(h, p, "wqkv"), cfg)
        if lora is not None:
            q_flat = q_flat + _lora_delta(
                h, lora["wq_a"], lora["wq_b"], batch.lora_scaling,
                batch.adapter_ids
            )
            v_flat = v_flat + _lora_delta(
                h, lora["wv_a"], lora["wv_b"], batch.lora_scaling,
                batch.adapter_ids
            )
        q = q_flat.reshape(B, T, H, D)
        k = k_flat.reshape(B, T, KVH, D)
        v = v_flat.reshape(B, T, KVH, D)
        q = rope(q, batch.positions, cfg.rope_theta)
        k = rope(k, batch.positions, cfg.rope_theta)

    attn, kv = decoder.attend(
        mode, q, k, v, kv, layer, batch, scale=1.0 / (D ** 0.5))
    with jax.named_scope("attn_proj"):
        x = x + out(_proj(attn.reshape(B, T, H * D), p, "wo"))
    return x, kv


def _layer(cfg: ModelConfig, mode: str, x: jax.Array, per_layer, kv,
           layer: jax.Array, batch: decoder.Batch):
    p, lora = per_layer
    x, kv = attention_half(cfg, mode, x, p, lora, kv, layer, batch)
    with jax.named_scope("mlp"):
        h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
        gate = jax.nn.silu(
            _proj(h, p, "w_gate").astype(jnp.float32)).astype(h.dtype)
        x = x + _proj(gate * _proj(h, p, "w_up"), p, "w_down")
    return x, kv


@jax.named_scope("embed")
def embed_tokens(params: Dict, cfg: ModelConfig, token_ids: jax.Array,
                 adapter_ids: jax.Array | None):
    """Shared forward preamble: input embeddings + LoRA leaf plumbing.

    Used by both the single-program ``apply`` and the pipeline-parallel
    wrapper (``parallel/pp_serving.py``) so the two paths cannot diverge.
    Returns (x, lora_layers, lora_scaling, adapter_ids).
    """
    emb = params["embed"]
    if emb.dtype == jnp.int8:
        # Row-quantized table: dequant only the gathered rows.
        x = (emb[token_ids].astype(cfg.jnp_dtype)
             * params["embed_scale"][token_ids].astype(cfg.jnp_dtype))
    else:
        x = emb[token_ids].astype(cfg.jnp_dtype)
    lora = params.get("lora")
    lora_scaling = lora["scaling"] if lora is not None else None
    if lora is not None and adapter_ids is None:
        adapter_ids = jnp.zeros((token_ids.shape[0],), jnp.int32)
    lora_layers = (
        {k: v for k, v in lora.items() if k != "scaling"}
        if lora is not None else None
    )
    return x, lora_layers, lora_scaling, adapter_ids


@jax.named_scope("head")
def project_out(params: Dict, cfg: ModelConfig, x: jax.Array,
                output_hidden: bool, norm: bool = True) -> jax.Array:
    """Shared forward tail: final norm (``norm``), then states or logits."""
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps) if norm else x
    if output_hidden:
        return x.astype(jnp.float32)
    head = params.get("lm_head")
    if head is not None:
        if head.dtype == jnp.int8:
            # [Hd, V] int8 with scale [1, V]: scale per vocab channel.
            logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
            return logits * params["lm_head_scale"][0]
        return (x @ head).astype(jnp.float32)
    emb = params["embed"]
    if emb.dtype == jnp.int8:
        # Tied head: embed [V, Hd] row scales [V, 1] become per-vocab
        # output scales of embed.T.
        logits = (x @ emb.T.astype(x.dtype)).astype(jnp.float32)
        return logits * params["embed_scale"][:, 0]
    return (x @ emb.T).astype(jnp.float32)




# --------------------------------------------------------------------- #
# Checkpoint (HF llama / mistral)
# --------------------------------------------------------------------- #

# HF leaf under ``model.layers.<i>.`` -> (our key, transpose [out, in] to
# ``x @ W``'s [in, out]). "q" / "k" / "v" are joined into ``wqkv``.
_ATTN_LEAVES = {
    "input_layernorm.weight": ("attn_norm", False),
    "self_attn.q_proj.weight": ("q", True),
    "self_attn.k_proj.weight": ("k", True),
    "self_attn.v_proj.weight": ("v", True),
    "self_attn.o_proj.weight": ("wo", True),
    "post_attention_layernorm.weight": ("mlp_norm", False),
}
_MLP_LEAVES = {
    "mlp.gate_proj.weight": ("w_gate", True),
    "mlp.up_proj.weight": ("w_up", True),
    "mlp.down_proj.weight": ("w_down", True),
}


def load_checkpoint(
    cfg: ModelConfig,
    path: str,
    *,
    mlp_leaves: Dict = _MLP_LEAVES,
    other_leaf: Callable | None = None,
    head_required: bool = False,
) -> Dict:
    """The tree of an HF Llama-shaped checkpoint. Each layer's ``q_proj``
    / ``k_proj`` / ``v_proj`` are joined on the host into the one
    ``wqkv`` leaf the model reads (:func:`fuse_qkv`), so the three never
    sit beside it on the device.

    A family whose attention half is Llama's names its own MLP leaves:
    ``mlp_leaves`` for the ones stacked per layer, ``other_leaf(i, leaf,
    arr) -> bool`` for what it gathers itself (Mixtral's experts)."""
    L = cfg.num_layers
    dtype = cfg.jnp_dtype
    layer_map = {**_ATTN_LEAVES, **mlp_leaves}
    per_layer: Dict[str, List] = {
        k: [None] * L for k in ["wqkv"] + [
            k for k, _ in layer_map.values() if k not in ("q", "k", "v")]
    }
    # q/k/v of a layer wait here, on the host, until all three are read.
    qkv_parts: List[Dict[str, np.ndarray]] = [{} for _ in range(L)]
    top: Dict[str, jnp.ndarray] = {}
    unmapped = []

    for name, arr in _iter_checkpoint_tensors(path):
        if name == "model.embed_tokens.weight":
            top["embed"] = _to_dtype(arr, dtype)
        elif name == "model.norm.weight":
            top["final_norm"] = _to_dtype(arr, dtype)
        elif name == "lm_head.weight":
            top["lm_head"] = _to_dtype(arr.T, dtype)
        elif name.startswith("model.layers."):
            rest = name[len("model.layers."):]
            idx_str, leaf = rest.split(".", 1)
            i = int(idx_str)
            entry = layer_map.get(leaf)
            if entry is None or i >= L:
                if not (other_leaf and i < L and other_leaf(i, leaf, arr)):
                    unmapped.append(name)
                continue
            key, transpose = entry
            if transpose:
                arr = arr.T
            if key in ("q", "k", "v"):
                parts = qkv_parts[i]
                parts[key] = arr
                if len(parts) == 3:
                    per_layer["wqkv"][i] = _to_dtype(
                        fuse_qkv(parts.pop("q"), parts.pop("k"),
                                 parts.pop("v"), cfg.num_kv_heads), dtype)
                continue
            per_layer[key][i] = _to_dtype(arr, dtype)
        elif name.endswith("rotary_emb.inv_freq"):
            continue  # computed, not a parameter
        else:
            unmapped.append(name)

    missing = [
        f"layers.{k}[{i}]" for k, v in per_layer.items() if k != "wqkv"
        for i, leaf in enumerate(v) if leaf is None
    ] + [
        f"layers.{k}_proj[{i}]" for i, parts in enumerate(qkv_parts)
        if per_layer["wqkv"][i] is None for k in "qkv" if k not in parts
    ]
    required = ("embed", "final_norm") + (
        ("lm_head",) if head_required else ())
    missing += [k for k in required if k not in top]
    report_incomplete(path, missing, unmapped)

    params: Dict = {
        "embed": top["embed"],
        "final_norm": top["final_norm"],
        "layers": {k: jnp.stack(v) for k, v in per_layer.items()},
    }
    # Without a head in the tree ``project_out`` falls back to embed.T.
    if "lm_head" in top and (head_required or not cfg.tie_word_embeddings):
        params["lm_head"] = top["lm_head"]
    return params


# --------------------------------------------------------------------- #
# The family's record
# --------------------------------------------------------------------- #

# The attention half's leaves, for any family that calls it: qkv
# column-parallel on the head dimension (``wqkv``'s columns are grouped
# by KV head, so a shard of its last axis holds whole groups: a KV head
# with its query heads, like the KV pages' shard), ``wo`` row-parallel
# (all-reduce after); embeddings replicated, the head vocab-sharded.
ATTN_SPECS = {
    ("embed",): P(None, None),
    ("final_norm",): P(None),
    ("lm_head",): P(None, "tp"),
    ("layers", "attn_norm"): P(None, None),
    ("layers", "mlp_norm"): P(None, None),
    ("layers", "wqkv"): P(None, None, "tp"),
    ("layers", "wo"): P(None, "tp", None),
}

def _embed(params, cfg, token_ids, positions, adapter_ids):
    del positions  # rotary: they enter in the layers
    return embed_tokens(params, cfg, token_ids, adapter_ids)


FAMILY = Family(
    model_types=("llama", "mistral"),
    init_params=init_params,
    embed=_embed,
    layer=_layer,
    head=project_out,
    load=load_checkpoint,
    specs={
        **ATTN_SPECS,
        # MLP up/gate column-parallel on intermediate, down row-parallel.
        ("layers", "w_gate"): P(None, None, "tp"),
        ("layers", "w_up"): P(None, None, "tp"),
        ("layers", "w_down"): P(None, "tp", None),
        # LoRA slot tensors follow their base projections.
        ("lora", "wq_a"): P(None, None, None, None),
        ("lora", "wq_b"): P(None, None, None, "tp"),
        ("lora", "wv_a"): P(None, None, None, None),
        ("lora", "wv_b"): P(None, None, None, "tp"),
        ("lora", "scaling"): P(None),
    },
    # Everything else (norms, LoRA slots) stays bf16: a rounding error of
    # the total bytes. ``wqkv`` is the three projections' columns joined:
    # a scale is per output column over Hd, so each column's int8 values
    # and scale are what its own matrix would give.
    quant_keys=("wqkv", "wo", "w_gate", "w_up", "w_down"),
    lora=True,
    pipeline=True,
    head_may_tie=True,
)

apply = functools.partial(decoder.apply, FAMILY)
