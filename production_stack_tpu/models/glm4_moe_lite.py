"""GLM-4.7-Flash-style decoder (``model_type: glm4_moe_lite``): one
latent-attention (MLA) sublayer a layer, a leading dense layer, then
sparse layers of sigmoid-routed experts beside a shared one, and a
next-token prediction module (zai-org/GLM-4.7-Flash's ``config.json``;
where it is silent the form is the DeepSeek-V3 convention the family
follows, each choice listed under ``assumed`` of
``chipbench/configs/glm-4.7-flash-e8v8.json`` and marked below).

Layer ``l``, input ``x`` (``RMS`` is RMSNorm with a learned weight)::

    h = x + MLA[l](RMS(x; in_norm[l]))
    y = h + F_l(RMS(h; post_norm[l]))
    F_l = SwiGLU(width intermediate_size)       for l < first_k_dense_replace
    F_l = sum_e w_e SwiGLU_e + SwiGLU_shared    after them

``MLA(u)`` with ``H`` heads, no bias, no factor on either latent::

    c_q = RMS(u W_qa);  q = c_q W_qb -> H x [nope | rope]
    [c | k_r] = u W_kva;  c = RMS(c)
    rotary (all ``rope`` lanes) on each head's rope part and on k_r
    [k_n | v] = c W_kvb -> H x [nope | v_head_dim]
    scores (q_n . k_n + q_r . k_r) / sqrt(nope + rope), causal

The expert layer: ``s = sigmoid(u W_r)`` in float32 over all
``n_routed_experts x chips_per_layer`` outputs, the top ``k`` of ``s +
b`` (``e_score_correction_bias``), weights ``s[chosen] / (sum + 1e-20) x
routed_scaling_factor``; the shared expert is added whole.

What the skeleton owns stays the skeleton's: ``decoder.attend_latent``
(the latent pages' write, the two forms of a cached prefill and the
absorbed decode: what models/longcat.py attends through too),
``models/moe.py`` (``route`` and ``expert_layer``: this chip holds
``cfg.num_experts`` of the router's outputs, block ``cfg.layer_share``),
``llama.rms_norm``, embedding and head, and ``decoder.latent_attention``
(the layer part LongCat shares). The layers are two stretches of
``decoder.scan_layers``, the leading dense layers and the sparse layers
after them (46 of the 47 here), with no ``lax.cond``: behind one, the
dense MLP's three matrices (126 MB) were prefetched into VMEM in every
layer of the engine's decode burst though only layer 0 reads them (PR
46; the rule is ``scan_layers``').

**The prediction module** (``num_nextn_predict_layers`` 1) is
mathematics only: :func:`init_mtp_params` and :func:`mtp_logits`. The
engine neither allocates nor calls it, and ``--speculative-num-tokens``
is refused for this family at start-up like every surface that rolls
pages back (engine/core.py::_refuse_what_the_page_sides_are_not_taught):
with weights drawn from a seed the module agrees with the trunk one time
in the vocabulary, so no cell could judge drafting with it (ROADMAP M8).

No LoRA slots, no pipeline stages, no int8 weights, no tensor-parallel
rules, no checkpoint loader yet: the record at the foot says so.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from production_stack_tpu.models import decoder, llama, moe
from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.models.moe import EXPERT_STACKS
from production_stack_tpu.models.registry import Family, replicated

ROUTER_EPS = 1e-20
# assumed: hidden_act silu in every SwiGLU of the model (``config_fields``
# refuses another): dense MLP, routed experts, shared expert.
ACTIVATION = "silu"
# The spread of the router's selection bias around zero and of every
# norm weight around one in a random tree: a trained checkpoint's are
# not zero and one, and a program that dropped the bias, or applied a
# norm's weight on the wrong side of an operation, would pass with those.
SPREAD = 0.1

ATTN_LEAVES = (("in_norm", 2), ("post_norm", 2), ("wq_a", 3), ("q_norm", 2),
               ("wq_b", 3), ("wkv_a", 3), ("kv_norm", 2), ("wkv_b", 4),
               ("wo", 3))


# --------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------- #

def _winit(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            / jnp.sqrt(fan_in)).astype(dtype)


def _near_one(key, shape, dtype):
    return (1.0 + SPREAD * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def _sparse_leaves(cfg: ModelConfig, keys, layers: int) -> Dict:
    """Router, bias, held experts and the shared expert of ``layers``
    sparse layers, from eight keys."""
    dtype, Hd = cfg.jnp_dtype, cfg.hidden_size
    Im, Is = cfg.moe_intermediate_size, cfg.shared_expert_size
    E, held = cfg.published_experts, cfg.num_experts
    return {
        # The router keeps its published width: it scores every expert
        # of the layer, held here or not.
        "router": _winit(keys[0], (layers, Hd, E), Hd, dtype),
        # assumed: a checkpoint's e_score_correction_bias is trained; a
        # zero one would let a program that drops it pass.
        "router_bias": SPREAD * jax.random.normal(
            keys[1], (layers, E), jnp.float32),
        "w_gate": _winit(keys[2], (layers, held, Hd, Im), Hd, dtype),
        "w_up": _winit(keys[3], (layers, held, Hd, Im), Hd, dtype),
        "w_down": _winit(keys[4], (layers, held, Im, Hd), Im, dtype),
        "shared_gate": _winit(keys[5], (layers, Hd, Is), Hd, dtype),
        "shared_up": _winit(keys[6], (layers, Hd, Is), Hd, dtype),
        "shared_down": _winit(keys[7], (layers, Is, Hd), Is, dtype),
    }


def _attn_leaves(cfg: ModelConfig, keys, layers: int) -> Dict:
    """The latent attention and the two norms of ``layers`` layers, from
    nine keys."""
    dtype, Hd, H = cfg.jnp_dtype, cfg.hidden_size, cfg.num_heads
    Q, C = cfg.q_lora_rank, cfg.kv_lora_rank
    N, R, Vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "in_norm": _near_one(keys[0], (layers, Hd), dtype),
        "post_norm": _near_one(keys[1], (layers, Hd), dtype),
        "wq_a": _winit(keys[2], (layers, Hd, Q), Hd, dtype),
        "q_norm": _near_one(keys[3], (layers, Q), dtype),
        # [out, in], as models/longcat.py stores it.
        "wq_b": _winit(keys[4], (layers, H * (N + R), Q), Q, dtype),
        "wkv_a": _winit(keys[5], (layers, Hd, C + R), Hd, dtype),
        "kv_norm": _near_one(keys[6], (layers, C), dtype),
        "wkv_b": _winit(keys[7], (layers, H, C, N + Vd), C, dtype),
        "wo": _winit(keys[8], (layers, H * Vd, Hd), H * Vd, dtype),
    }


def init_params(cfg: ModelConfig, rng: jax.Array, **_unused) -> Dict:
    """Random tree: normal / sqrt(fan_in) in float32, rounded to the
    served dtype; norm weights ``1 + 0.1 normal``, the router's bias
    ``0.1 normal`` in float32 (``chipbench/reference/glm4_moe_lite.py``
    redraws it by its own copy of this recipe: key ``i`` of 24, element
    ``n`` of the stacked leaf)."""
    dtype, Hd, V = cfg.jnp_dtype, cfg.hidden_size, cfg.vocab_size
    I, L, nd = cfg.intermediate_size, cfg.num_layers, cfg.dense_layers
    keys = jax.random.split(rng, 24)
    params = {
        "embed": (0.02 * jax.random.normal(keys[0], (V, Hd), jnp.float32)
                  ).astype(dtype),
        "final_norm": _near_one(keys[1], (Hd,), dtype),
        "lm_head": _winit(keys[2], (Hd, V), Hd, dtype),
        "attn": _attn_leaves(cfg, keys[3:12], L),
    }
    if nd:
        params["dense"] = {
            "w_gate": _winit(keys[12], (nd, Hd, I), Hd, dtype),
            "w_up": _winit(keys[13], (nd, Hd, I), Hd, dtype),
            "w_down": _winit(keys[14], (nd, I, Hd), I, dtype),
        }
    if L > nd:
        params["moe"] = _sparse_leaves(cfg, keys[15:23], L - nd)
    return params


def init_mtp_params(cfg: ModelConfig, rng: jax.Array) -> Dict:
    """The prediction module's own weights (one sparse layer, the two
    norms of its input, the projection of their concatenation, its own
    final norm), drawn as :func:`init_params` draws the trunk's, from 21
    keys. The embedding and the head are the trunk's."""
    dtype, Hd = cfg.jnp_dtype, cfg.hidden_size
    keys = jax.random.split(rng, 21)
    return {
        "embed_norm": _near_one(keys[0], (Hd,), dtype),
        "hidden_norm": _near_one(keys[1], (Hd,), dtype),
        "eh_proj": _winit(keys[2], (2 * Hd, Hd), 2 * Hd, dtype),
        "final_norm": _near_one(keys[3], (Hd,), dtype),
        "attn": _attn_leaves(cfg, keys[4:13], 1),
        "moe": _sparse_leaves(cfg, keys[13:21], 1),
    }


# --------------------------------------------------------------------- #
# One layer
# --------------------------------------------------------------------- #

def _experts(cfg: ModelConfig, h, layers: Dict, at, valid):
    """Expert layer ``at`` of the stacked leaves ``layers`` on the normed
    ``h``: the held experts' weighted sum (the experts' stacks reach the
    grouped matmul whole, with the layer's index: models/moe.py), the
    shared expert whole."""
    p, weights = moe.sparse_leaves(layers, at)
    routed, stats = moe.expert_layer(
        h, weights, at=at, k=cfg.experts_per_token, share=cfg.layer_share,
        scaling=cfg.routed_scaling, valid=valid,
        routing={"scoring": cfg.router_scoring, "bias": p["router_bias"],
                 "eps": ROUTER_EPS}, activation=ACTIVATION)
    with jax.named_scope("moe_shared"):
        out = routed + moe.swiglu(h, p["shared_gate"], p["shared_up"],
                                  p["shared_down"], activation=ACTIVATION)
    return out, stats


def run_layers(cfg: ModelConfig, mode: str, x, params: Dict, kv_pages,
               batch: decoder.Batch):
    """What the layers are (``Family.loop``; the module's docstring): the
    latent attention, then the dense MLP in the ``cfg.dense_layers``
    leading layers and the expert layer after them, a stretch each.
    Returns (x, kv_pages, the expert layers' stats summed over layers)."""
    L, d = cfg.num_layers, cfg.dense_layers
    valid = batch.slot_mapping >= 0

    def dense_mlp(h, layer):
        with jax.named_scope("mlp"):
            return moe.dense_layer(h, params["dense"], layer,
                                   activation=ACTIVATION)

    def sparse_mlp(h, layer):
        return _experts(cfg, h, params["moe"], layer - d, valid)

    def layer_with(mlp):
        def layer_step(x, sides, layer, _):
            p = decoder.take(params["attn"], layer)
            x, sides = decoder.latent_attention(cfg, mode, x, p, sides,
                                                layer, batch)
            with jax.named_scope("mlp"):
                h = llama.rms_norm(x, p["post_norm"], cfg.rms_norm_eps)
            out, s = mlp(h, layer)
            return x + out, sides, s
        return layer_step

    carry = decoder.first_carry(x, kv_pages, moe.STATS)
    carry = decoder.scan_layers(layer_with(dense_mlp), carry, d)
    x, sides, stats, _ = decoder.scan_layers(layer_with(sparse_mlp), carry,
                                             L - d)
    return x, sides, stats


# --------------------------------------------------------------------- #
# The prediction module
# --------------------------------------------------------------------- #

def mtp_logits(params: Dict, mtp: Dict, cfg: ModelConfig, next_tokens,
               hidden, positions):
    """Logits ``[B, T, V]`` of the token after next: position ``i`` reads
    the trunk's hidden state ``hidden[:, i]`` (``[B, T, Hd]``, as
    ``apply(..., output_hidden=True)`` gives it: after the trunk's final
    norm) and the embedding of the token that follows it, ``next_tokens[:, i]``::

        h' = [RMS(emb(t_{i+1}); embed_norm) ; RMS(h_i; hidden_norm)] W_eh

    then one sparse layer of its own weights (causal latent attention
    over the ``T`` positions of ``h'``, no cache), its own final norm and
    the trunk's head. assumed: the concatenation order (embedding first),
    the module's own final norm, the shared embedding and head, and the
    trunk's state read after its final norm (the DeepSeek-V3 convention
    as the public servers implement it)."""
    B, T = next_tokens.shape
    eps = cfg.rms_norm_eps
    x = jnp.concatenate(
        [llama.rms_norm(params["embed"][next_tokens], mtp["embed_norm"], eps),
         llama.rms_norm(hidden, mtp["hidden_norm"], eps)],
        axis=-1) @ mtp["eh_proj"]
    # The attention of a plain prefill, writing no page: one throwaway
    # block per row's tokens, every slot dropped.
    sides = tuple(
        jnp.zeros((1, 1, 1, 1, width), x.dtype)
        for width in (cfg.kv_lora_rank, -(-cfg.qk_rope_head_dim // 128) * 128))
    batch = decoder.Batch(
        positions, jnp.full((B, T), -1, jnp.int32),
        jnp.zeros((B, 1), jnp.int32), jnp.full((B,), T, jnp.int32),
        jnp.full((B,), T, jnp.int32))
    layer = decoder.take(mtp["attn"], 0)
    x, _ = decoder.latent_attention(cfg, "prefill", x, layer, sides,
                                    jnp.int32(0), batch)
    h = llama.rms_norm(x, layer["post_norm"], eps)
    out, _ = _experts(cfg, h, mtp["moe"], 0, None)
    return llama.project_out(
        {"final_norm": mtp["final_norm"], "lm_head": params["lm_head"]},
        cfg, x + out, False)


# --------------------------------------------------------------------- #
# The record
# --------------------------------------------------------------------- #

def config_fields(hf: dict, layers: int) -> dict:
    """The ``ModelConfig`` fields this family reads of its own keys. A
    key that names a mechanism the program does not have is refused by
    name, not dropped."""
    for key, served in (("n_group", 1), ("topk_group", 1),
                        ("topk_method", "noaux_tc"), ("hidden_act", "silu"),
                        ("norm_topk_prob", True), ("attention_bias", False),
                        ("partial_rotary_factor", 1), ("rope_scaling", None),
                        ("tie_word_embeddings", False)):
        if hf.get(key, served) != served:
            raise ValueError(
                f"the glm4_moe_lite family serves {key} {served!r} (no "
                f"group-limited routing, no other scoring or activation, "
                f"no rope scaling); got {hf[key]!r}")
    if hf.get("num_nextn_predict_layers", 0) not in (0, 1):
        raise ValueError("num_nextn_predict_layers: one prediction module "
                         "at most (models/glm4_moe_lite.py::mtp_logits)")
    nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    return dict(
        num_kv_heads=1,  # one latent and one rotated key for all heads
        head_dim=nope + rope,
        moe_intermediate_size=hf["moe_intermediate_size"],
        shared_expert_size=(hf.get("n_shared_experts", 0)
                            * hf["moe_intermediate_size"]),
        num_experts=hf["n_routed_experts"],  # held here
        experts_per_token=hf["num_experts_per_tok"],
        routed_scaling=float(hf.get("routed_scaling_factor", 1.0)),
        dense_layers=min(int(hf.get("first_k_dense_replace", 0)), layers),
        router_scoring="sigmoid",
        router_bias=True,
        q_lora_rank=hf["q_lora_rank"],
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=nope,
        qk_rope_head_dim=rope,
        v_head_dim=hf["v_head_dim"],
        chips_per_layer=hf.get("chips_per_layer", 1),
        layer_share=hf.get("layer_share", 0),
    )


FAMILY = Family(
    model_types=("glm4_moe_lite",),
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
        *((("attn", leaf), rank) for leaf, rank in ATTN_LEAVES),
        *((("dense", leaf), 3) for leaf in EXPERT_STACKS),
        (("moe", "router"), 3), (("moe", "router_bias"), 2),
        *((("moe", leaf), 4) for leaf in EXPERT_STACKS),
        *((("moe", leaf), 3)
          for leaf in ("shared_gate", "shared_up", "shared_down"))),
    stats=moe.STATS,
    config_fields=config_fields,
    page_sides=decoder.latent_page_sides,
)

apply = functools.partial(decoder.apply, FAMILY)
