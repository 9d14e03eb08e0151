"""Llama-family decoder (covers Llama 2/3, Mistral, TinyLlama via config).

Functional JAX implementation built for serving with a paged KV cache:

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
- every forward writes fresh K/V into HBM pages (``ops.write_kv_pages``) and
  attends either causally within the prompt (prefill) or over the pages via
  paged attention (decode);
- weights use bfloat16 by default; all norms/softmax accumulate in float32.

The reference stack runs these models inside vLLM CUDA images
(``helm/templates/deployment-vllm-multi.yaml:108-199``); this module is the
TPU-native replacement at the engine layer.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.ops.attention import (
    context_prefill_attention,
    paged_decode_attention,
    prefill_attention,
    write_kv_pages,
)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


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


def _split_qkv(y: jax.Array, cfg: ModelConfig):
    """Flat q, k, v ``[B, T, heads * D]`` from the fused projection's
    output ``[B, T, KVH * (G + 2) * D]``. The barrier keeps the matmul
    flat: without it the compiler folds the reshape into the dot, wants
    the weight with ``Hd`` minor, and copies + transposes it out of the
    stacked leaf in every layer (PERF.md section 6, PR 30). What is split
    here is the activations, 6,144 values a token at Mistral's widths."""
    B, T, _ = y.shape
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
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


def _layer(
    cfg: ModelConfig,
    mode: str,
    x: jax.Array,  # [B, T, Hd]
    layer_params: Dict,  # un-stacked (one layer's leaves)
    lora: Dict | None,  # un-stacked per-layer LoRA leaves, or None
    kv: Tuple[jax.Array, jax.Array],  # STACKED pages [L, NB, bs, KVH, D]
    layer: jax.Array,  # scalar layer index
    positions: jax.Array,
    slot_mapping: jax.Array,
    block_tables: jax.Array,
    context_lens: jax.Array,
    seq_lens: jax.Array,
    lora_scaling: jax.Array | None,
    adapter_ids: jax.Array | None,
):
    p = layer_params
    B, T, Hd = x.shape
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    scale = 1.0 / (D ** 0.5)
    k_pages, v_pages = kv

    # The named scopes are what a profiler trace files the device's time
    # under (docs/profiling.md): metadata only, no operation changes.
    with jax.named_scope("attn_proj"):
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        q_flat, k_flat, v_flat = _split_qkv(_proj(h, p, "wqkv"), cfg)
        if lora is not None:
            q_flat = q_flat + _lora_delta(
                h, lora["wq_a"], lora["wq_b"], lora_scaling, adapter_ids
            )
            v_flat = v_flat + _lora_delta(
                h, lora["wv_a"], lora["wv_b"], lora_scaling, adapter_ids
            )
        q = q_flat.reshape(B, T, H, D)
        k = k_flat.reshape(B, T, KVH, D)
        v = v_flat.reshape(B, T, KVH, D)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    k_pages, v_pages = write_kv_pages(
        k_pages, v_pages, k, v, slot_mapping, layer)

    with jax.named_scope("attention"):
        if mode == "prefill":
            attn = prefill_attention(
                q, k, v, scale=scale, seq_lens=seq_lens)
        elif mode == "prefill_cached":
            # Suffix prefill after a prefix-cache hit: attend over HBM
            # pages (cached prefix + just-written suffix). The chunk's own
            # fresh k/v ride along so the flash kernel can serve the
            # suffix from VMEM and stream only the cached prefix pages.
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
        x = x + _proj(attn.reshape(B, T, H * D), p, "wo")

    with jax.named_scope("mlp"):
        h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
        gate = jax.nn.silu(
            _proj(h, p, "w_gate").astype(jnp.float32)).astype(h.dtype)
        x = x + _proj(gate * _proj(h, p, "w_up"), p, "w_down")
    return x, (k_pages, v_pages)


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
                output_hidden: bool) -> jax.Array:
    """Shared forward tail: final norm, then hidden states or logits."""
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
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


def apply(
    params: Dict,
    cfg: ModelConfig,
    token_ids: jax.Array,  # [B, T]
    positions: jax.Array,  # [B, T]
    kv_pages: Tuple[jax.Array, jax.Array],  # ([L,NB,bs,KVH,D], [L,NB,bs,KVH,D])
    slot_mapping: jax.Array,  # [B, T]
    block_tables: jax.Array,  # [B, MAXB]
    context_lens: jax.Array,  # [B]
    seq_lens: jax.Array,  # [B] valid prompt lengths (prefill padding mask)
    *,
    mode: str,  # "prefill" | "prefill_cached" | "decode"  (static)
    adapter_ids: jax.Array | None = None,  # [B] LoRA slot per sequence
    output_hidden: bool = False,  # return final hidden states, not logits
    last_token: jax.Array | None = None,  # [B] position whose logits to keep
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full forward. Returns (logits [B, T, V], updated kv_pages), or the
    post-norm hidden states [B, T, Hd] instead of logits when
    ``output_hidden`` (the /v1/embeddings pass). With ``last_token``
    (prefill sampling: only one position's logits are ever read), the
    hidden states are sliced to that position BEFORE the norm + head, so
    the vocab projection runs on [B, 1, Hd] instead of the whole chunk —
    for a 128k-vocab model that removes a multi-GB f32 logits temp and
    ~0.8 TFLOP per 2048-token chunk, with bit-identical results."""
    x, lora_layers, lora_scaling, adapter_ids = embed_tokens(
        params, cfg, token_ids, adapter_ids)
    k_all, v_all = kv_pages

    layer_fn = functools.partial(
        _layer, cfg, mode,
        positions=positions, slot_mapping=slot_mapping,
        block_tables=block_tables, context_lens=context_lens,
        seq_lens=seq_lens, lora_scaling=lora_scaling, adapter_ids=adapter_ids,
    )

    # The STACKED KV pages ride the scan carry whole; every op addresses
    # them through the scalar layer index (flat scatter / page-level
    # gather). Loop carries alias in place under XLA, so only the touched
    # pages move — per-layer slices (or pages in the scan ys) would copy
    # the entire pool every forward step. With an int8 cache each side is
    # a (data, scales) tuple that rides the carry the same way.
    L = (k_all[0] if isinstance(k_all, tuple) else k_all).shape[0]

    if lora_layers is not None:
        def scan_body(carry, per_layer):
            x, k_all, v_all, l = carry
            layer_params, lora_p = per_layer
            x, (k_all, v_all) = layer_fn(
                x, layer_params, lora_p, (k_all, v_all), l
            )
            return (x, k_all, v_all, l + 1), None

        (x, k_all, v_all, _), _ = jax.lax.scan(
            scan_body, (x, k_all, v_all, jnp.int32(0)),
            (params["layers"], lora_layers), length=L,
        )
    else:
        def scan_body(carry, layer_params):
            x, k_all, v_all, l = carry
            x, (k_all, v_all) = layer_fn(
                x, layer_params, None, (k_all, v_all), l
            )
            return (x, k_all, v_all, l + 1), None

        (x, k_all, v_all, _), _ = jax.lax.scan(
            scan_body, (x, k_all, v_all, jnp.int32(0)),
            params["layers"], length=L,
        )
    if last_token is not None:
        with jax.named_scope("head"):
            x = jnp.take_along_axis(x, last_token[:, None, None], axis=1)
    return project_out(params, cfg, x, output_hidden), (k_all, v_all)
