"""LFM2-MoE-style decoder: gated short convolutions and grouped-query
attention layers side by side, two leading dense MLPs, then sparse
layers of sigmoid-routed experts (LiquidAI/LFM2-24B-A2B's
``config.json``, ``model_type: lfm2_moe``; what the config does not spell
is listed under ``assumed`` of ``chipbench/configs/lfm2-24b-a2b-l10.json``
and marked below).

Layer ``l``: ``x = x + op_l(rmsnorm(x))``, then ``x = x + ffn_l(rmsnorm(x))``.

- ``op_l`` of a ``conv`` layer: ``[B, C, X] = split3(h W_in)``; ``u = B * X``;
  ``c_t = sum_j w_j u_{t - (K-1) + j}`` per channel (depthwise, causal,
  ``K = conv_L_cache`` taps, no bias); ``op = (C * c) W_out``. What the
  layer has to remember of a sequence is its last ``K - 1`` inputs ``u``.
- ``op_l`` of a ``full_attention`` layer: grouped-query attention whose
  ``q`` and ``k`` are RMS-normed per head, with a learned weight, *before*
  the rotation (assumed (e)); RoPE over all of a head's dims.
- ``ffn_l``: SwiGLU at ``intermediate_size`` in the first
  ``num_dense_layers`` layers; after them the expert layer of
  models/moe.py with every expert held: float32 sigmoid scores, the top
  ``k`` of score plus a per-expert bias, weighted by the scores without
  it over their sum ``+ 1e-6``.

**The convolution's state lives in the cache block.** The pool has a
third side ``[conv layers, NB, K - 1, hidden]`` beside ``k`` and ``v``
(``Family.block_state``): a block's entry is the ``u`` of the last
``K - 1`` positions up to the last one written into it. A chunk reads its
halo from the block of the position before its first
(``decoder.read_block_state``) and writes the entry of every block it
puts a token into (``decoder.write_block_state``); a decode step does
the same with one token. A full block's entry is so the state at its
boundary, and a prefix hit (always whole blocks) brings pages and state
together with nothing to copy and nothing to keep track of: allocation,
registration, eviction, preemption by recompute and same-rung prefill
groups know blocks only, as before. Only the ``full_attention`` layers
hold pages (``Family.page_layers``), numbered by their own count.

Leaves are stacked per kind (``conv``, ``attn``, ``dense``, ``moe``) and
the layers are one stretch of ``decoder.scan_layers`` whose body holds
each operator and each MLP once behind ``decoder.by_layer``.

Not yet, and refused at start-up by the engine (``Family.block_state``'s
note): LoRA slots, int8 weights, pipeline stages, tensor-parallel rules,
speculation (a rolled-back token would leave a block's state ahead of
its sequence), host offload and the cache server (they move pages, not
the state). A recurrent or state-space layer whose state needs a scan
over the chunk is another mechanism (ROADMAP M5).
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.models import decoder, llama, moe
from production_stack_tpu.models.config import (
    FULL_ATTENTION,
    SHORT_CONV,
    ModelConfig,
)
from production_stack_tpu.models.registry import Family, replicated
from production_stack_tpu.ops.attention import kv_page_data

ROUTER_EPS = 1e-6
# assumed (b): the activation is silu (the config names none): the gate
# of the dense MLP and of every routed expert.
ACTIVATION = "silu"
# The spread of the router's selection bias and of the q/k norm weights
# around one in a random tree: a trained checkpoint's are not zero and
# one, and a program that dropped either would pass with those.
BIAS_SPREAD = 0.1


def _kinds(cfg: ModelConfig):
    return [cfg.layer_kind(l) for l in range(cfg.num_layers)]


def conv_layers(cfg: ModelConfig) -> int:
    return _kinds(cfg).count(SHORT_CONV)


def attention_layers(cfg: ModelConfig) -> int:
    return _kinds(cfg).count(FULL_ATTENTION)


def block_state(cfg: ModelConfig):
    """(layers, rows, width) of the pool's third side."""
    return conv_layers(cfg), cfg.conv_kernel - 1, cfg.hidden_size


def config_fields(hf: dict, layers: int) -> dict:
    """The ``ModelConfig`` fields this family reads of its own keys."""
    kinds = tuple(hf["layer_types"][:layers])
    unknown = set(kinds) - {SHORT_CONV, FULL_ATTENTION}
    if unknown:
        raise ValueError(f"lfm2 layers are conv or full_attention; got "
                         f"{sorted(unknown)}")
    if hf.get("conv_bias"):
        raise ValueError("conv_bias is not implemented")
    if not hf.get("norm_topk_prob", True):
        raise ValueError("norm_topk_prob: false is not implemented")
    if int(hf.get("conv_L_cache", 3)) < 2:
        raise ValueError("conv_L_cache must be at least 2")
    rope = hf.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r} is not "
                         "implemented for lfm2")
    return dict(
        layer_types=kinds,
        conv_kernel=int(hf.get("conv_L_cache", 3)),
        rms_norm_eps=hf.get("norm_eps", 1e-5),
        rope_theta=float(rope.get("rope_theta",
                                  hf.get("rope_theta", 1000000.0))),
        dense_layers=min(int(hf.get("num_dense_layers", 0)), layers),
        moe_intermediate_size=hf.get("moe_intermediate_size", 0),
        routed_scaling=float(hf.get("routed_scaling_factor", 1.0)),
        router_scoring="sigmoid",
        router_bias=bool(hf.get("use_expert_bias", False)),
        # assumed (a): the family ties its head to the embedding; the
        # catalog's copy of the config drops the key.
        tie_word_embeddings=hf.get("tie_word_embeddings", True),
    )


# --------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------- #

def init_params(cfg: ModelConfig, rng: jax.Array, **_unused) -> Dict:
    """Random tree: normal / sqrt(fan_in) in float32, rounded to the
    served dtype (``chipbench/reference/lfm2.py`` redraws it by its own
    copy of this recipe: key ``i`` of 20, element ``n`` of the stacked
    leaf); the q/k norm weights ``1 + 0.1 normal``, the router's bias
    ``0.1 normal`` in float32."""
    dtype = cfg.jnp_dtype
    H, KVH, D, Hd, V = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                        cfg.hidden_size, cfg.vocab_size)
    I, Im, K = cfg.intermediate_size, cfg.moe_intermediate_size, cfg.conv_kernel
    nc, na = conv_layers(cfg), attention_layers(cfg)
    nd = cfg.dense_layers
    ns = cfg.num_layers - nd
    keys = jax.random.split(rng, 20)

    def winit(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / jnp.sqrt(fan_in)).astype(dtype)

    def near_one(key, shape):
        return (1.0 + BIAS_SPREAD * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    params = {
        "embed": (0.02 * jax.random.normal(keys[0], (V, Hd), jnp.float32)
                  ).astype(dtype),
        "final_norm": jnp.ones((Hd,), dtype),
        # One pair of norms a layer, whatever its operator.
        "norms": {"op_norm": jnp.ones((cfg.num_layers, Hd), dtype),
                  "ffn_norm": jnp.ones((cfg.num_layers, Hd), dtype)},
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = winit(keys[1], (Hd, V), Hd)
    if nc:
        params["conv"] = {
            "w_in": winit(keys[2], (nc, Hd, 3 * Hd), Hd),
            "w_conv": winit(keys[3], (nc, Hd, K), K),
            "w_out": winit(keys[4], (nc, Hd, Hd), Hd),
        }
    if na:
        params["attn"] = {
            # Drawn as three matrices, served as one leaf (llama.fuse_qkv).
            "wqkv": llama.fuse_qkv(
                winit(keys[5], (na, Hd, H * D), Hd),
                winit(keys[6], (na, Hd, KVH * D), Hd),
                winit(keys[7], (na, Hd, KVH * D), Hd), KVH),
            "wo": winit(keys[8], (na, H * D, Hd), H * D),
            "q_norm": near_one(keys[9], (na, D)),
            "k_norm": near_one(keys[10], (na, D)),
        }
    if nd:
        params["dense"] = {
            "w_gate": winit(keys[11], (nd, Hd, I), Hd),
            "w_up": winit(keys[12], (nd, Hd, I), Hd),
            "w_down": winit(keys[13], (nd, I, Hd), I),
        }
    if ns:
        E = cfg.num_experts
        params["moe"] = {
            "router": winit(keys[14], (ns, Hd, E), Hd),
            "w_gate": winit(keys[15], (ns, E, Hd, Im), Hd),
            "w_up": winit(keys[16], (ns, E, Hd, Im), Hd),
            "w_down": winit(keys[17], (ns, E, Im, Hd), Im),
        }
        if cfg.router_bias:
            # assumed (d): a checkpoint's bias is trained; a zero one
            # would let a program that drops it pass.
            params["moe"]["router_bias"] = BIAS_SPREAD * jax.random.normal(
                keys[18], (ns, E), jnp.float32)
    return params


# --------------------------------------------------------------------- #
# The two operators
# --------------------------------------------------------------------- #

def _short_conv(cfg: ModelConfig, h, p: Dict, state, at, batch, block_size):
    """The gated short convolution on the normed ``h [B, T, Hd]``; the
    pool's state side with this layer's entries written."""
    K = cfg.conv_kernel
    T = h.shape[1]
    # assumed (c): the three chunks of W_in's output are B, C, X in this
    # order (Hugging Face's Lfm2ShortConv).
    gate_in, gate_out, x = jnp.split(h @ p["w_in"], 3, axis=-1)
    u = gate_in * x
    with jax.named_scope("conv_state"):
        halo = decoder.read_block_state(state, at, batch, block_size)
        inputs = jnp.concatenate([halo.astype(u.dtype), u], axis=1)
        state = decoder.write_block_state(state, at, batch, block_size,
                                          inputs)
    # inputs[:, j + t] is u at position t - (K - 1) + j: K shifted adds.
    taps = p["w_conv"].astype(jnp.float32)  # [Hd, K]
    conv = sum(inputs[:, j:j + T].astype(jnp.float32) * taps[:, j]
               for j in range(K))
    return (gate_out * conv.astype(h.dtype)) @ p["w_out"], state


def _attention(cfg: ModelConfig, mode: str, h, p: Dict, kv, at, batch):
    B, T, _ = h.shape
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with jax.named_scope("attn_proj"):
        q, k, v = llama._split_qkv(h @ p["wqkv"], cfg)
        # assumed (e): q and k are RMS-normed over each head's dims with
        # a learned weight before the rotation (Hugging Face's
        # Lfm2Attention).
        q = llama.rms_norm(q.reshape(B, T, H, D), p["q_norm"],
                           cfg.rms_norm_eps)
        k = llama.rms_norm(k.reshape(B, T, KVH, D), p["k_norm"],
                           cfg.rms_norm_eps)
        q = llama.rope(q, batch.positions, cfg.rope_theta)
        k = llama.rope(k, batch.positions, cfg.rope_theta)
        v = v.reshape(B, T, KVH, D)
    attn, kv = decoder.attend(mode, q, k, v, kv, at, batch,
                              scale=1.0 / (D ** 0.5))
    with jax.named_scope("attn_proj"):
        return attn.reshape(B, T, H * D) @ p["wo"], kv


# --------------------------------------------------------------------- #
# The layer loop
# --------------------------------------------------------------------- #

def run_layers(cfg: ModelConfig, mode: str, x, params: Dict, kv_pages,
               batch: decoder.Batch):
    """What the layers are (``Family.loop``): a short convolution or an
    attention (each hands back the sides of the pool it does not use as
    it got them: ``decoder.by_layer``), then the dense MLP in the
    ``cfg.dense_layers`` leading layers and the expert layer after them,
    as one stretch. ``kv_pages`` is ``(k, v, state)``; returns (x, the
    three updated, the expert layers' stats summed over layers)."""
    L, d = cfg.num_layers, cfg.dense_layers
    kinds = _kinds(cfg)
    is_conv = np.asarray([kind == SHORT_CONV for kind in kinds])
    dense = np.arange(L) < d
    at = decoder.index_in_kind(kinds)
    block_size = kv_page_data(kv_pages[0]).shape[2]

    def conv_op(h, sides, layer):
        k_all, v_all, state = sides
        with jax.named_scope("short_conv"):
            out, state = _short_conv(
                cfg, h, decoder.take(params["conv"], at[layer]), state,
                at[layer], batch, block_size)
        return out, (k_all, v_all, state)

    def attention_op(h, sides, layer):
        k_all, v_all, state = sides
        out, (k_all, v_all) = _attention(
            cfg, mode, h, decoder.take(params["attn"], at[layer]),
            (k_all, v_all), at[layer], batch)
        return out, (k_all, v_all, state)

    def dense_mlp(h, layer):
        return moe.dense_layer(h, params["dense"], layer,
                               activation=ACTIVATION)

    def sparse_mlp(h, layer):
        w, p = moe.sparse_leaves(params["moe"], layer - d)
        return moe.expert_layer(
            h, p, at=layer - d, k=cfg.experts_per_token,
            scaling=cfg.routed_scaling, valid=batch.slot_mapping >= 0,
            routing={"scoring": cfg.router_scoring,
                     "bias": w.get("router_bias"), "eps": ROUTER_EPS},
            activation=ACTIVATION)

    def layer_step(x, sides, layer, _):
        norms = decoder.take(params["norms"], layer)
        h = llama.rms_norm(x, norms["op_norm"], cfg.rms_norm_eps)
        out, sides = decoder.by_layer(is_conv, layer, conv_op, attention_op,
                                      h, sides, layer)
        x = x + out
        with jax.named_scope("mlp"):
            h = llama.rms_norm(x, norms["ffn_norm"], cfg.rms_norm_eps)
            out, s = decoder.by_layer(dense, layer, dense_mlp, sparse_mlp,
                                      h, layer)
        return x + out, sides, s

    x, sides, stats, _ = decoder.scan_layers(
        layer_step, decoder.first_carry(x, kv_pages, moe.STATS), L)
    return x, sides, stats


FAMILY = Family(
    model_types=("lfm2_moe",),
    init_params=init_params,
    embed=llama.FAMILY.embed,
    loop=run_layers,
    head=llama.project_out,
    # Every leaf replicated: no tensor-parallel rules yet, and the engine
    # refuses a mesh of several devices for a family with a block state.
    specs=replicated(
        (("embed",), 2), (("final_norm",), 1), (("lm_head",), 2),
        (("norms", "op_norm"), 2), (("norms", "ffn_norm"), 2),
        *((("conv", leaf), 3) for leaf in ("w_in", "w_conv", "w_out")),
        (("attn", "wqkv"), 3), (("attn", "wo"), 3),
        (("attn", "q_norm"), 2), (("attn", "k_norm"), 2),
        *((("dense", leaf), 3) for leaf in ("w_gate", "w_up", "w_down")),
        (("moe", "router"), 3), (("moe", "router_bias"), 2),
        *((("moe", leaf), 4) for leaf in ("w_gate", "w_up", "w_down"))),
    head_may_tie=True,
    per_layer_keys=("layer_types",),
    stats=moe.STATS,
    config_fields=config_fields,
    page_layers=attention_layers,
    block_state=block_state,
)

apply = functools.partial(decoder.apply, FAMILY)
