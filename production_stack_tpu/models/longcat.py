"""LongCat-Flash-style decoder: two latent-attention (MLA) sublayers a
layer, each with its own dense MLP, and one shortcut-connected expert
layer with zero-compute experts (meituan-longcat/LongCat-Flash-Chat's
``config.json``; where it is silent the form is that of the public
``modeling_longcat_flash.py``, each choice listed under ``assumed`` of
``chipbench/configs/longcat-flash-l4e16.json``).

Layer ``l``, input ``x``, sublayers ``i = 0, 1`` (``RMS`` is RMSNorm)::

    a = x + MLA[l,i](RMS(x; in_norm[l,i]))
    h = RMS(a; post_norm[l,i])
    if i == 0:  s = MoE[l](h)                  # the shortcut's branch
    x = a + SwiGLU(h; W[l,i])
    after i == 1:  x = x + s

What the skeleton owns stays the skeleton's: ``decoder.attend_latent``
(the latent pages' write and the three attention modes: up-projected in
both prefills, absorbed in decode), ``llama.rms_norm``, embedding and
head. What this module brings:

- **A latent cache.** A token keeps, per sublayer, the normed latent
  ``c`` (``kv_lora_rank`` wide, before its ``mla_scale_kv_lora`` factor)
  and the rotated key ``k_r`` (``qk_rope_head_dim``) all heads share:
  ``Family.page_sides`` says so and ``Family.page_layers`` is ``2 x
  num_layers``; page layer ``2l + i`` is sublayer ``i`` of layer ``l``.
- **The shortcut.** The expert layer reads the first sublayer's normed
  state and its result joins the residual after the second sublayer's
  MLP, so between the two nothing waits on it: on one chip the
  compiler is free to order it, across chips it is the window the
  experts' exchange hides in (ROADMAP M1).
- **The expert layer** is models/moe.py's: the router scores
  ``n_routed_experts x chips_per_layer`` experts with weights and
  ``zero_expert_num`` identities behind them, selection by score plus a
  float32 bias (zeros at init), weights ``routed_scaling x`` the softmax
  score, not renormalised. This chip holds ``cfg.num_experts`` experts,
  block ``cfg.layer_share``, and every identity.
- **One stretch of ``decoder.scan_layers`` over the sublayers**, the
  layer-stacked leaves (a layer's two sublayers are the second axis of
  every leaf) as its ``xs``; the expert stacks reach ``expert_layer``
  whole, with the layer's index.

No LoRA slots, no pipeline stages, no int8 weights, no tensor-parallel
rules, no checkpoint loader yet: the record at the foot says so, and the
engine refuses the page-moving surfaces that are not taught two page
shapes (engine/core.py).
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from production_stack_tpu.models import decoder, llama, moe
from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.models.registry import Family, replicated

EXPERT_STACKS = ("e_gate", "e_up", "e_down")
# assumed: hidden_act is silu (``config_fields`` refuses another): the
# gate of both dense MLPs and of every routed expert.
ACTIVATION = "silu"


# --------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------- #

def init_params(cfg: ModelConfig, rng: jax.Array, **_unused) -> Dict:
    """Random tree: normal / sqrt(fan_in) in float32, rounded to the
    served dtype (``chipbench/reference/longcat.py`` redraws it by its
    own copy of this recipe: key ``i`` of 16, element ``n`` of the
    stacked leaf). Every per-sublayer leaf is ``[layers, 2, ...]``."""
    dtype = cfg.jnp_dtype
    L, Hd, V, H = (cfg.num_layers, cfg.hidden_size, cfg.vocab_size,
                   cfg.num_heads)
    Q, C = cfg.q_lora_rank, cfg.kv_lora_rank
    N, R, Vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    I, Im, held = (cfg.intermediate_size, cfg.moe_intermediate_size,
                   cfg.num_experts)
    E = cfg.published_experts + cfg.zero_experts
    keys = jax.random.split(rng, 16)

    def winit(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / jnp.sqrt(fan_in)).astype(dtype)

    return {
        "embed": (0.02 * jax.random.normal(keys[0], (V, Hd), jnp.float32)
                  ).astype(dtype),
        "final_norm": jnp.ones((Hd,), dtype),
        "lm_head": winit(keys[1], (Hd, V), Hd),
        "layers": {
            "in_norm": jnp.ones((L, 2, Hd), dtype),
            "post_norm": jnp.ones((L, 2, Hd), dtype),
            "wq_a": winit(keys[2], (L, 2, Hd, Q), Hd),
            "q_norm": jnp.ones((L, 2, Q), dtype),
            # [out, in]: the layout the compiler gives it anyway (a
            # stack stored [in, out] is transposed whole in every
            # program: 0.3 GB of temporaries at the published widths).
            "wq_b": winit(keys[3], (L, 2, H * (N + R), Q), Q),
            "wkv_a": winit(keys[4], (L, 2, Hd, C + R), Hd),
            "kv_norm": jnp.ones((L, 2, C), dtype),
            "wkv_b": winit(keys[5], (L, 2, H, C, N + Vd), C),  # per head
            "wo": winit(keys[6], (L, 2, H * Vd, Hd), H * Vd),
            "w_gate": winit(keys[7], (L, 2, Hd, I), Hd),
            "w_up": winit(keys[8], (L, 2, Hd, I), Hd),
            "w_down": winit(keys[9], (L, 2, I, Hd), I),
            # The router keeps its published width: every expert of the
            # layer, held here or not, then the identities.
            "router": winit(keys[10], (L, Hd, E), Hd),
            # A checkpoint's e_score_correction_bias; zeros at init.
            "router_bias": jnp.zeros((L, E), jnp.float32),
            "e_gate": winit(keys[11], (L, held, Hd, Im), Hd),
            "e_up": winit(keys[12], (L, held, Hd, Im), Hd),
            "e_down": winit(keys[13], (L, held, Im, Hd), Im),
        },
    }


# --------------------------------------------------------------------- #
# The layers
# --------------------------------------------------------------------- #

def run_layers(cfg: ModelConfig, mode: str, x, params: Dict, kv_pages,
               batch: decoder.Batch):
    """What the layers are (``Family.loop``; the module's docstring): one
    stretch over the ``2 x layers`` sublayers, the ``[2L, ...]`` leaves
    its ``xs``, whose body holds the latent attention and the dense MLP
    once and the expert layer once behind a ``lax.cond`` that only a
    layer's first sublayer takes; the shortcut's branch rides the carry
    to the second, where it joins. One body a sublayer, not a layer's two
    unrolled: those programs wrote 213 MB of a 201.3 MB compile cache (PR
    41). Returns (x, kv_pages, the expert layers' stats summed over
    layers)."""
    layers = params["layers"]
    stacks = {"w_gate": layers["e_gate"], "w_up": layers["e_up"],
              "w_down": layers["e_down"]}
    routers = {k: layers[k] for k in ("router", "router_bias")}
    # [L, 2, ...] -> [2L, ...]: the leading dims of a stacked leaf.
    sliced = {k: v.reshape((-1,) + v.shape[2:]) for k, v in layers.items()
              if k not in routers and k not in EXPERT_STACKS}
    no_stats = jnp.zeros((len(FAMILY.stats),), jnp.int32)

    def experts(h, layer):
        w = decoder.take(routers, layer)
        return moe.expert_layer(
            h, {"router": w["router"], **stacks}, at=layer,
            k=cfg.experts_per_token, share=cfg.layer_share,
            scaling=cfg.routed_scaling, valid=batch.slot_mapping >= 0,
            routing={"bias": w["router_bias"],
                     "renormalise": False},  # assumed: weights as scored
            zero_experts=cfg.zero_experts, activation=ACTIVATION)

    def sublayer(x, sides, j, p, shortcut):
        first = j % 2 == 0
        x, sides = decoder.latent_attention(cfg, mode, x, p, sides, j, batch)
        with jax.named_scope("mlp"):
            h = llama.rms_norm(x, p["post_norm"], cfg.rms_norm_eps)
        # The shortcut's branch: from the first sublayer's state, joined
        # after the second sublayer's MLP. Not ``decoder.by_layer``: no
        # choice between kinds of layer but whether the one expert layer
        # starts here; the other side hands on the carry's ``shortcut``.
        shortcut, s = jax.lax.cond(
            first, experts, lambda h, layer: (shortcut, no_stats), h, j // 2)
        with jax.named_scope("mlp"):
            x = x + moe.swiglu(h, p["w_gate"], p["w_up"], p["w_down"],
                               activation=ACTIVATION)
            x = jnp.where(first, x, x + shortcut)
        return x, sides, s, shortcut

    x, sides, _, stats, _ = decoder.scan_layers(
        sublayer, decoder.first_carry(x, kv_pages, FAMILY.stats,
                                      jnp.zeros_like(x)), xs=sliced)
    return x, sides, stats


def config_fields(hf: dict, layers: int) -> dict:
    """The ``ModelConfig`` fields this family reads of its own keys."""
    if hf.get("attention_method", "MLA") != "MLA":
        raise ValueError("the longcat family serves attention_method MLA")
    if hf.get("zero_expert_type", "identity") != "identity":
        raise ValueError("zero-compute experts are identities here; got "
                         f"zero_expert_type {hf['zero_expert_type']!r}")
    if hf.get("attention_bias"):
        raise ValueError("attention biases are not implemented")
    if hf.get("hidden_act", "silu") != "silu":
        raise ValueError("hidden_act silu only")
    if hf.get("norm_topk_prob"):
        raise ValueError("norm_topk_prob: true is not implemented: this "
                         "router's top-k weights are not renormalised")
    nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    return dict(
        num_kv_heads=1,  # one latent and one rotated key for all heads
        head_dim=nope + rope,
        intermediate_size=hf["ffn_hidden_size"],
        moe_intermediate_size=hf["expert_ffn_hidden_size"],
        num_experts=hf["n_routed_experts"],  # held here
        experts_per_token=hf["moe_topk"],
        routed_scaling=float(hf.get("routed_scaling_factor", 1.0)),
        zero_experts=hf.get("zero_expert_num", 0),
        router_bias=True,
        q_lora_rank=hf["q_lora_rank"],
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=nope,
        qk_rope_head_dim=rope,
        v_head_dim=hf["v_head_dim"],
        mla_scale_q_lora=bool(hf.get("mla_scale_q_lora", False)),
        mla_scale_kv_lora=bool(hf.get("mla_scale_kv_lora", False)),
        chips_per_layer=hf.get("chips_per_layer", 1),
        layer_share=hf.get("layer_share", 0),
    )


FAMILY = Family(
    model_types=("longcat_flash",),
    init_params=init_params,
    embed=llama.FAMILY.embed,
    loop=run_layers,
    head=llama.project_out,
    # Every leaf replicated: no tensor-parallel rules yet, and the engine
    # refuses a mesh of several devices for a family with its own page
    # sides (a latent has no heads to shard; the ``ep`` axis of ROADMAP
    # M1 would split the expert stacks' second axis).
    specs=replicated(
        (("embed",), 2), (("final_norm",), 1), (("lm_head",), 2),
        *((("layers", leaf), 3)
          for leaf in ("in_norm", "post_norm", "q_norm", "kv_norm",
                       "router")),
        *((("layers", leaf), 4)
          for leaf in ("wq_a", "wq_b", "wkv_a", "wo", "w_gate", "w_up",
                       "w_down", *EXPERT_STACKS)),
        (("layers", "wkv_b"), 5), (("layers", "router_bias"), 2)),
    stats=moe.STATS + moe.ZERO_STATS,
    config_fields=config_fields,
    page_layers=lambda cfg: 2 * cfg.num_layers,
    page_sides=decoder.latent_page_sides,
)

apply = functools.partial(decoder.apply, FAMILY)
