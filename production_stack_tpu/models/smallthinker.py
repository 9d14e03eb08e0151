"""SmallThinker-style decoder (``model_type: smallthinker``;
PowerInfer/SmallThinker-21BA3B-Instruct's ``config.json``): every layer
alike but for two per-layer switches, and an expert layer whose router
reads the layer's input *before* attention.

For layer ``l`` (each ``assumed`` item of
``chipbench/configs/smallthinker-21b-a3b-l8.json`` is marked at its line)::

    h = RMS(x; g_in)
    p, e = softmax over the top k of (h Wr)            # scope moe_router
    q, k, v = h Wq, h Wk, h Wv                         # one fused leaf
    rope_layout[l] == 1: rotate q, k (plain frequencies, rope_theta)
    rope_layout[l] == 0: NO positional encoding: nothing is rotated
    causal attention; sliding_window_layout[l] == 1: the last
        sliding_window_size keys only                  # scope attention
    x = x + attn Wo
    m = RMS(x; g_post)
    x = x + sum_e p_e (relu(m Wgate_e) * (m Wup_e)) Wdown_e   # moe_experts

The experts compute on the post-attention ``m`` under the weights the
router read from the pre-attention ``h``: the routing of a layer is known
an attention earlier than it is used, which is what the family is for
(an expert's weights can be fetched while attention runs).

What is the skeleton's stays the skeleton's: ``decoder.attend`` with the
kind's static ``window``, ``llama.rms_norm``, the fused ``wqkv`` leaf with
its ``_split_qkv`` barrier, ``llama.rope``, embedding and head;
``moe.route`` and ``moe.expert_layer`` (handed the routing: ``routed=``)
with the gate activation ``relu``. What this module brings:

- **One stack of every leaf** (all layers have the same shapes): the
  small leaves under ``layers`` ``[L, ...]``, sliced a layer at a time by
  the scan itself; the experts' under ``moe`` ``[L, E, ...]``, handed to
  the grouped matmul whole with the layer's number (models/moe.py).
- **The two switches are read separately.** A layer's kind is the pair
  (window or not, rotation or not), one of four names
  (``config.FULL_ATTENTION`` / ``SLIDING_ATTENTION`` and their ``NOPE_``
  twins); ``cfg.rope_of`` gives None for a kind without rotation and
  ``cfg.window_of`` the window of one that has it. The published layout
  has two of the four (NoPE full, rotary window); any of them runs.
- **The conditional holds what differs and nothing else**: rotation and
  ``decoder.attend``. The projections on either side of it are the same
  matmuls in every layer and stay outside, so no weight is an operand of
  a ``lax.cond`` (docs/engine.md, "Layers of several kinds").

No LoRA slots, no pipeline stages, no int8 weights, no tensor-parallel
rules yet (every leaf is replicated over a mesh): the record at the foot
of the file says so, and the engine refuses what the record lacks.
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
    NOPE_FULL_ATTENTION,
    NOPE_SLIDING_ATTENTION,
    SLIDING_ATTENTION,
    ModelConfig,
)
from production_stack_tpu.models.registry import Family, replicated

# assumed (b): the experts are ReGLU, ``relu`` on the gate (the source's
# description says "sparse ReGLU"; the config has no ``hidden_act``),
# computed densely over the expert's columns.
ACTIVATION = "relu"

# (window, rotation) -> the kind's name.
KINDS = {(0, 1): FULL_ATTENTION, (1, 1): SLIDING_ATTENTION,
         (0, 0): NOPE_FULL_ATTENTION, (1, 0): NOPE_SLIDING_ATTENTION}


# --------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------- #

def init_params(cfg: ModelConfig, rng: jax.Array, **_unused) -> Dict:
    """Random tree: normal / sqrt(fan_in) in float32, rounded to the
    served dtype (``chipbench/reference/smallthinker.py`` redraws it by
    its own copy of this recipe: key ``i`` of 12, layer ``n`` of a
    stacked leaf is elements ``n * size ..`` of the key's array)."""
    dtype = cfg.jnp_dtype
    H, KVH, D, Hd = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                     cfg.hidden_size)
    L, V, E, Im = (cfg.num_layers, cfg.vocab_size, cfg.num_experts,
                   cfg.moe_intermediate_size)
    keys = jax.random.split(rng, 12)

    def winit(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / jnp.sqrt(fan_in)).astype(dtype)

    return {
        "embed": (0.02 * jax.random.normal(keys[0], (V, Hd), jnp.float32)
                  ).astype(dtype),
        "final_norm": jnp.ones((Hd,), dtype),
        "lm_head": winit(keys[1], (Hd, V), Hd),
        "layers": {
            "attn_norm": jnp.ones((L, Hd), dtype),
            # Drawn as three matrices, served as one leaf (llama.fuse_qkv).
            # assumed (e): no bias, no QK norm.
            "wqkv": llama.fuse_qkv(
                winit(keys[2], (L, Hd, H * D), Hd),
                winit(keys[3], (L, Hd, KVH * D), Hd),
                winit(keys[4], (L, Hd, KVH * D), Hd), KVH),
            "wo": winit(keys[5], (L, H * D, Hd), H * D),
            "mlp_norm": jnp.ones((L, Hd), dtype),
            "router": winit(keys[6], (L, Hd, E), Hd),
        },
        # assumed (c): primary experts only; no shared expert.
        "moe": {
            "w_gate": winit(keys[7], (L, E, Hd, Im), Hd),
            "w_up": winit(keys[8], (L, E, Hd, Im), Hd),
            "w_down": winit(keys[9], (L, E, Im, Hd), Im),
        },
    }


# --------------------------------------------------------------------- #
# The layers
# --------------------------------------------------------------------- #

def _attend(cfg: ModelConfig, mode: str, kind: str, batch: decoder.Batch):
    """``(q, k, v, kv, layer) -> (attention output, kv)`` of a layer of
    ``kind``: what a kind decides, and all a ``lax.cond`` holds."""
    rp = cfg.rope_of(kind)

    def run(q, k, v, kv, layer):
        if rp is not None:  # NoPE: queries and keys go as projected
            with jax.named_scope("attn_proj"):
                q = llama.rope(q, batch.positions, rp.rope_theta)
                k = llama.rope(k, batch.positions, rp.rope_theta)
        return decoder.attend(
            mode, q, k, v, kv, layer, batch,
            scale=1.0 / (cfg.head_dim ** 0.5), window=cfg.window_of(kind))

    return run


def run_layers(cfg: ModelConfig, mode: str, x, params: Dict, kv_pages,
               batch: decoder.Batch):
    """What the layers are (``Family.loop``): one stretch, every layer the
    same body but for the attention of its kind. Returns (x, kv_pages,
    the expert layers' stats summed over layers)."""
    L = cfg.num_layers
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kinds = [cfg.layer_kind(l) for l in range(L)]
    valid = batch.slot_mapping >= 0

    def attend_of(present):
        """The attention of the layer's kind among ``present``: a chain
        of ``decoder.by_layer``, one ``cond`` fewer than kinds."""
        first, *rest = present
        if not rest:
            return _attend(cfg, mode, first, batch)
        flags = np.asarray([kind == first for kind in kinds])
        others = attend_of(rest)
        return lambda q, k, v, kv, layer: decoder.by_layer(
            flags, layer, _attend(cfg, mode, first, batch), others,
            q, k, v, kv, layer)

    attend = attend_of(sorted(set(kinds)))

    def layer_step(x, kv, layer, p):
        B, T, Hd = x.shape
        with jax.named_scope("attn_proj"):
            h = llama.rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        # assumed (a): the router reads the layer's normed input, ahead of
        # attention; its weights reach the experts one attention later.
        with jax.named_scope("moe_router"):
            routed = moe.route(h.reshape(B * T, Hd), p["router"],
                               cfg.experts_per_token, scoring="softmax",
                               renormalise=True)
        with jax.named_scope("attn_proj"):
            q, k, v = llama._split_qkv(h @ p["wqkv"], cfg)
        attn, kv = attend(q.reshape(B, T, H, D), k.reshape(B, T, KVH, D),
                          v.reshape(B, T, KVH, D), kv, layer)
        with jax.named_scope("attn_proj"):
            x = x + attn.reshape(B, T, H * D) @ p["wo"]
        with jax.named_scope("mlp"):
            m = llama.rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
            y, stats = moe.expert_layer(
                m, params["moe"], at=layer, k=cfg.experts_per_token,
                valid=valid, activation=ACTIVATION, routed=routed)
        return x + y, kv, stats

    x, kv, stats, _ = decoder.scan_layers(
        layer_step, decoder.first_carry(x, kv_pages, moe.STATS), L,
        xs=params["layers"])
    return x, kv, stats


def config_fields(hf: dict, layers: int) -> dict:
    """The ``ModelConfig`` fields this family reads of its own keys. The
    two per-layer lists are read separately: a layer may have a window,
    a rotation, both or neither."""
    for key, why in (("moe_primary_router_apply_softmax",
                      "a router without its softmax"),
                     ("norm_topk_prob", "weights that are not renormalised "
                                        "over the selected experts")):
        if not hf.get(key, True):
            raise ValueError(f"{key} false is another function ({why}), "
                             "not implemented")
    if hf.get("rope_scaling"):
        raise ValueError("rope_scaling is not implemented for smallthinker "
                         "(published: null)")
    if hf.get("tie_word_embeddings"):
        raise ValueError("a tied head is not implemented for smallthinker")
    secondary = [k for k, v in hf.items() if "secondary" in k and v]
    if secondary:
        # assumed (c): the config is trusted: primary experts only.
        raise ValueError(f"secondary experts ({secondary}) are not "
                         "implemented")
    windowed = hf["sliding_window_layout"][:layers]
    rotated = hf["rope_layout"][:layers]
    if any(flag not in (0, 1) for flag in (*windowed, *rotated)):
        raise ValueError("sliding_window_layout and rope_layout hold 0 or 1 "
                         "a layer")
    window = hf.get("sliding_window_size") or 0
    if any(windowed) and window <= 0:
        raise ValueError("sliding_window_layout marks a layer and "
                         "sliding_window_size gives no window")
    kinds = tuple(KINDS[w, r] for w, r in zip(windowed, rotated))
    return dict(
        layer_types=kinds,
        sliding_window=window,
        rope_by_kind=tuple(
            (kind, None) for kind in sorted(set(kinds))
            if kind in (NOPE_FULL_ATTENTION, NOPE_SLIDING_ATTENTION)),
        num_experts=hf["moe_num_primary_experts"],
        experts_per_token=hf["moe_num_active_primary_experts"],
        moe_intermediate_size=hf["moe_ffn_hidden_size"],
        intermediate_size=0,  # no dense MLP anywhere
    )


FAMILY = Family(
    model_types=("smallthinker",),
    init_params=init_params,
    embed=llama.FAMILY.embed,
    loop=run_layers,
    head=llama.project_out,
    # Every leaf, each replicated over a mesh: no tensor-parallel rules
    # yet (ROADMAP M1's ``ep`` axis would split ``moe/w_*``'s second axis).
    specs=replicated(
        (("embed",), 2), (("final_norm",), 1), (("lm_head",), 2),
        (("layers", "attn_norm"), 2), (("layers", "mlp_norm"), 2),
        (("layers", "wqkv"), 3), (("layers", "wo"), 3),
        (("layers", "router"), 3),
        *((("moe", leaf), 4) for leaf in moe.EXPERT_STACKS)),
    per_layer_keys=("sliding_window_layout", "rope_layout"),
    config_fields=config_fields,
    stats=moe.STATS,
)

apply = functools.partial(decoder.apply, FAMILY)
