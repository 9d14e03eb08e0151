"""Laguna-style decoder: window and full attention layers with their own
head counts and rotary blocks, a per-head output gate, a leading dense
layer, then sparse layers of routed experts beside a shared one
(poolside/Laguna-S-2.1's ``config.json``; the three functions the config
names without spelling them are listed in ``assumed`` of
``chipbench/configs/laguna-s-2.1-l8e64.json`` and marked below).

What is the skeleton's stays the skeleton's: ``decoder.attend`` (page
write + the three attention modes, with this family's static ``window``
for the sliding layers), ``llama.rms_norm``, the fused ``wqkv`` leaf with
its ``_split_qkv`` barrier, embedding and head. What this module brings:

- **Leaves stacked per layer kind.** Full layers have 48 query heads and
  sliding ones 72, so ``wqkv`` / ``wg`` / ``wo`` are ``[n_full, ...]`` and
  ``[n_sliding, ...]`` under ``attn/<kind>``; the dense MLP's under
  ``dense`` ``[dense_layers, ...]``; router, routed and shared experts
  under ``moe`` ``[sparse layers, ...]``. No kind is padded to another's
  width.
- **One program body per kind of layer, whatever the depth**: one
  stretch of ``decoder.scan_layers`` whose body holds each kind of
  attention and each kind of MLP once behind ``decoder.by_layer``, a
  layer reading its leaves at its own index of its kind's stack.
- **The expert layer** is models/moe.py's: this chip holds
  ``cfg.num_experts`` of the ``cfg.published_experts`` the router scores,
  block ``cfg.layer_share``. The shared expert and the dense layer are
  whole on every chip.

No LoRA slots, no pipeline stages, no int8 weights, no tensor-parallel
rules yet (every leaf is replicated over a mesh): the record at the foot
of the file says so.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.models import decoder, llama, moe
from production_stack_tpu.models.config import (
    FULL_ATTENTION,
    SLIDING_ATTENTION,
    ModelConfig,
    RopeParams,
    rope_params,
)
from production_stack_tpu.models.registry import Family, replicated

# assumed (c): hidden_act is silu (the catalog's copy of the config has no
# such key): the gate of the dense MLP, the routed and the shared experts.
ACTIVATION = "silu"

# --------------------------------------------------------------------- #
# Which layers there are
# --------------------------------------------------------------------- #

def kind_heads(cfg: ModelConfig) -> Dict[str, Tuple[int, int]]:
    """kind -> (layers of it, its query heads); one head count a kind."""
    out: Dict[str, Tuple[int, int]] = {}
    for l in range(cfg.num_layers):
        kind, heads = cfg.layer_kind(l), cfg.layer_heads(l)
        n, seen = out.get(kind, (0, heads))
        if seen != heads:
            raise ValueError(
                f"{kind} layers with {seen} and {heads} query heads: the "
                "leaves are stacked per kind, one head count each")
        out[kind] = (n + 1, heads)
    return out


# --------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------- #

def init_params(cfg: ModelConfig, rng: jax.Array, **_unused) -> Dict:
    """Random tree: normal / sqrt(fan_in) in float32, rounded to the
    served dtype (``chipbench/reference/laguna.py`` redraws it by its own
    copy of this recipe: key ``i`` of 24, element ``n`` of the stacked
    leaf)."""
    dtype = cfg.jnp_dtype
    KVH, D, Hd, V = (cfg.num_kv_heads, cfg.head_dim, cfg.hidden_size,
                     cfg.vocab_size)
    I, Im, Is = (cfg.intermediate_size, cfg.moe_intermediate_size,
                 cfg.shared_expert_size)
    nd = cfg.dense_layers
    ns = cfg.num_layers - nd
    keys = jax.random.split(rng, 24)

    def winit(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / jnp.sqrt(fan_in)).astype(dtype)

    attn = {}
    for ki, (kind, (n, H)) in enumerate(sorted(kind_heads(cfg).items())):
        k = keys[2 + 5 * ki:7 + 5 * ki]
        attn[kind] = {
            "attn_norm": jnp.ones((n, Hd), dtype),
            # Drawn as three matrices, served as one leaf (llama.fuse_qkv).
            "wqkv": llama.fuse_qkv(
                winit(k[0], (n, Hd, H * D), Hd),
                winit(k[1], (n, Hd, KVH * D), Hd),
                winit(k[2], (n, Hd, KVH * D), Hd), KVH),
            "wg": winit(k[3], (n, Hd, H), Hd),
            "wo": winit(k[4], (n, H * D, Hd), H * D),
            "mlp_norm": jnp.ones((n, Hd), dtype),
        }
    params = {
        "embed": (0.02 * jax.random.normal(keys[0], (V, Hd), jnp.float32)
                  ).astype(dtype),
        "final_norm": jnp.ones((Hd,), dtype),
        "lm_head": winit(keys[1], (Hd, V), Hd),
        "attn": attn,
    }
    if nd:
        params["dense"] = {
            "w_gate": winit(keys[12], (nd, Hd, I), Hd),
            "w_up": winit(keys[13], (nd, Hd, I), Hd),
            "w_down": winit(keys[14], (nd, I, Hd), I),
        }
    if ns:
        E, held = cfg.published_experts, cfg.num_experts
        params["moe"] = {
            # The router keeps its published width: it scores every
            # expert of the layer, held here or not.
            "router": winit(keys[15], (ns, Hd, E), Hd),
            "w_gate": winit(keys[16], (ns, held, Hd, Im), Hd),
            "w_up": winit(keys[17], (ns, held, Hd, Im), Hd),
            "w_down": winit(keys[18], (ns, held, Im, Hd), Im),
        }
        if Is:
            params["moe"].update({
                "shared_gate": winit(keys[19], (ns, Hd, Is), Hd),
                "shared_up": winit(keys[20], (ns, Hd, Is), Hd),
                "shared_down": winit(keys[21], (ns, Is, Hd), Is),
            })
    return params


# --------------------------------------------------------------------- #
# Rotary embedding
# --------------------------------------------------------------------- #

def rope_frequencies(rp: RopeParams, head_dim: int):
    """(inverse frequencies [rotary dims / 2] as numpy float32, the
    factor cos and sin are multiplied by). ``yarn`` is Hugging Face's
    ``_compute_yarn_parameters``: extrapolated and interpolated
    frequencies blended by the linear ramp between the two correction
    dims."""
    dim = int(head_dim * rp.partial_rotary_factor)
    pos_freqs = rp.rope_theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp.rope_type == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    if rp.rope_type != "yarn":
        raise ValueError(f"rope_type {rp.rope_type!r} is not implemented")
    factor = rp.factor
    attention_factor = (rp.attention_factor if rp.attention_factor is not None
                        else 0.1 * math.log(factor) + 1.0)

    def correction_dim(rotations):
        return (dim * math.log(rp.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                ) / (2 * math.log(rp.rope_theta))

    low = max(math.floor(correction_dim(rp.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rp.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    extrapolation = 1.0 - ramp
    inv_freq = ((1.0 / (factor * pos_freqs)) * (1 - extrapolation)
                + (1.0 / pos_freqs) * extrapolation)
    return inv_freq.astype(np.float32), float(attention_factor)


def rope(x: jax.Array, positions: jax.Array, rp: RopeParams) -> jax.Array:
    """Rotary embedding over the first ``partial_rotary_factor`` of each
    head's dims (half-split layout within them); the rest pass."""
    inv_freq, factor = rope_frequencies(rp, x.shape[-1])
    rot = 2 * inv_freq.shape[0]
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos = (jnp.cos(angles) * factor)[:, :, None, :]
    sin = (jnp.sin(angles) * factor)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., :rot // 2], xf[..., rot // 2:rot], xf[..., rot:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- #
# One layer
# --------------------------------------------------------------------- #

def _attention(cfg: ModelConfig, mode: str, x, p: Dict, kv, layer, batch,
               kind: str, H: int):
    B, T, _ = x.shape
    KVH, D = cfg.num_kv_heads, cfg.head_dim
    rp = cfg.rope_of(kind)
    with jax.named_scope("attn_proj"):
        h = llama.rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        q_flat, k_flat, v_flat = llama._split_qkv(h @ p["wqkv"], cfg, heads=H)
        # assumed (a): the per-head gate is a sigmoid of a linear map of
        # the layer's normed input.
        gate = jax.nn.sigmoid(
            jnp.dot(h, p["wg"], preferred_element_type=jnp.float32))
        q = rope(q_flat.reshape(B, T, H, D), batch.positions, rp)
        k = rope(k_flat.reshape(B, T, KVH, D), batch.positions, rp)
        v = v_flat.reshape(B, T, KVH, D)
    attn, kv = decoder.attend(
        mode, q, k, v, kv, layer, batch, scale=1.0 / (D ** 0.5),
        window=cfg.window_of(kind))
    with jax.named_scope("attn_proj"):
        attn = (attn.astype(jnp.float32) * gate[..., None]).astype(x.dtype)
        x = x + attn.reshape(B, T, H * D) @ p["wo"]
    return x, kv


def run_layers(cfg: ModelConfig, mode: str, x, params: Dict, kv_pages,
               batch: decoder.Batch):
    """What the layers are (``Family.loop``): full or sliding attention,
    then the dense MLP in the ``cfg.dense_layers`` leading layers and the
    expert layer after them, as one stretch. Returns (x, kv_pages, the
    expert layers' stats summed over layers)."""
    L, d = cfg.num_layers, cfg.dense_layers
    kinds = [cfg.layer_kind(l) for l in range(L)]
    full = np.asarray([kind == FULL_ATTENTION for kind in kinds])
    dense = np.arange(L) < d
    at = decoder.index_in_kind(kinds)

    def attention(kind):
        def run(x, kv, layer):
            p = decoder.take(params["attn"][kind], at[layer])
            x, kv = _attention(cfg, mode, x, p, kv, layer, batch, kind,
                               p["wg"].shape[-1])
            return x, kv, p["mlp_norm"]
        return run

    def dense_mlp(x, h, layer):
        out, s = moe.dense_layer(h, params["dense"], layer,
                                 activation=ACTIVATION)
        return x + out, s

    def sparse_mlp(x, h, layer):
        w, p = moe.sparse_leaves(params["moe"], layer - d)
        # assumed (b): the router's scores are a softmax over all
        # published experts.
        routed, stats = moe.expert_layer(
            h, p, at=layer - d, k=cfg.experts_per_token,
            share=cfg.layer_share, scaling=cfg.routed_scaling,
            valid=batch.slot_mapping >= 0, activation=ACTIVATION)
        x = x + routed
        if "shared_gate" in w:
            with jax.named_scope("moe_shared"):
                x = x + moe.swiglu(h, w["shared_gate"], w["shared_up"],
                                   w["shared_down"], activation=ACTIVATION)
        return x, stats

    def layer_step(x, kv, layer, _):
        x, kv, mlp_norm = decoder.by_layer(
            full, layer, attention(FULL_ATTENTION),
            attention(SLIDING_ATTENTION), x, kv, layer)
        with jax.named_scope("mlp"):
            h = llama.rms_norm(x, mlp_norm, cfg.rms_norm_eps)
            x, s = decoder.by_layer(dense, layer, dense_mlp, sparse_mlp,
                                    x, h, layer)
        return x, kv, s

    x, kv, stats, _ = decoder.scan_layers(
        layer_step, decoder.first_carry(x, kv_pages, moe.STATS), L)
    return x, kv, stats


def config_fields(hf: dict, layers: int) -> dict:
    """The ``ModelConfig`` fields this family reads of its own keys."""
    mlp_kinds = hf["mlp_layer_types"][:layers]
    dense = next((i for i, kind in enumerate(mlp_kinds) if kind != "dense"),
                 layers)
    if "dense" in mlp_kinds[dense:] or sorted(
            hf.get("mlp_only_layers", range(dense))) != list(range(dense)):
        raise ValueError("dense MLP layers are served as a leading run "
                         f"only; got {mlp_kinds}")
    if hf.get("moe_router_logit_softcapping") or hf.get(
            "moe_apply_router_weight_on_input"):
        raise ValueError("router soft-capping and router weights on the "
                         "expert's input are not implemented")
    return dict(
        layer_types=tuple(hf["layer_types"][:layers]),
        heads_per_layer=tuple(hf["num_attention_heads_per_layer"][:layers]),
        sliding_window=hf.get("sliding_window") or 0,
        rope_by_kind=tuple(
            (kind, rope_params(block))
            for kind, block in sorted(hf.get("rope_parameters", {}).items())
            if isinstance(block, dict)),
        moe_intermediate_size=hf.get("moe_intermediate_size", 0),
        shared_expert_size=hf.get("shared_expert_intermediate_size", 0),
        routed_scaling=float(hf.get("moe_routed_scaling_factor", 1.0)),
        dense_layers=dense,
        chips_per_layer=hf.get("chips_per_layer", 1),
        layer_share=hf.get("layer_share", 0),
    )


FAMILY = Family(
    model_types=("laguna",),
    init_params=init_params,
    embed=llama.FAMILY.embed,
    loop=run_layers,
    head=llama.project_out,
    # Every leaf, each replicated over a mesh: no tensor-parallel rules
    # yet (the ``ep`` axis of ROADMAP M1 would split ``moe/w_*``'s second
    # axis, as ``layer_share`` splits it across processes today).
    specs=replicated(
        (("embed",), 2), (("final_norm",), 1), (("lm_head",), 2),
        *(((("attn", kind, leaf), rank))
          for kind in (FULL_ATTENTION, SLIDING_ATTENTION)
          for leaf, rank in (("attn_norm", 2), ("mlp_norm", 2), ("wqkv", 3),
                             ("wg", 3), ("wo", 3))),
        *((("dense", leaf), 3) for leaf in ("w_gate", "w_up", "w_down")),
        (("moe", "router"), 3),
        *((("moe", leaf), 4) for leaf in ("w_gate", "w_up", "w_down")),
        *((("moe", leaf), 3)
          for leaf in ("shared_gate", "shared_up", "shared_down"))),
    per_layer_keys=("layer_types", "mlp_layer_types",
                    "num_attention_heads_per_layer"),
    config_fields=config_fields,
    stats=moe.STATS,
)

apply = functools.partial(decoder.apply, FAMILY)
