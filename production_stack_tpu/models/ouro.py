"""Ouro-style looped decoder (``model_type: ouro``; ByteDance/Ouro-2.6B's
``config.json``, arXiv:2510.25741): ONE stack of sandwich-normed layers
applied ``total_ut_steps`` times over the same weights. Where the config
is silent the form is the published modeling file's as recalled without
a network, each choice listed under ``assumed`` of
``chipbench/configs/ouro-2.6b.json`` and marked below.

``RMS`` is RMSNorm (float32, ``rms_norm_eps``) with a weight of its own::

    h_0 = E[tokens]
    for u in 0 .. total_ut_steps - 1:        # the same weights every pass
        x = h_u
        for l in 0 .. L - 1:
            a = x + RMS(Attn_l(RMS(x; n1_l)); n2_l)      # assumed: n2, n4 on
            x = a + RMS(SwiGLU_l(RMS(a; n3_l)); n4_l)    # the sublayer's output
        h_{u+1} = RMS(x; norm_f)     # assumed: the final norm closes EVERY pass
    logits = h_U W_head              # assumed: and the head applies none again

``Attn_l`` is Llama's (no bias; rotary over all ``head_dim`` lanes in the
half-split layout; the token's own position in every pass; float32
softmax over ``q . k / sqrt(head_dim)``, causal), and **pass ``u``
attends over the keys and values pass ``u`` wrote**: a token keeps
``total_ut_steps`` keys and values a layer. Pass ``u`` of layer ``l``
owns page layer ``l x total_ut_steps + u`` of the pool (a layer's passes
side by side; ``Family.page_layers`` = ``total_ut_steps x L``). The
paper's reuse of the last pass's pages at decode is an approximation, a
different result, and is not applied.

The exit gate is mathematics only (:func:`exit_pdf`): at the published
``early_exit_threshold`` 1 every token takes every pass, the step
programs neither compute nor allocate it, and no flag turns it on.

What the skeleton owns stays the skeleton's: ``llama.attention_half``
(the fused ``wqkv`` leaf, its barrier, rotary, ``decoder.attend``; the
sublayer's output norm goes in through ``out``), ``llama._proj`` (int8
weights), ``llama.embed_tokens``, ``llama.project_out`` without its norm,
and ``decoder.scan_passes`` around ``decoder.scan_layers``: one body of
the layer in every step program, the stack read in place by the scan's
own slices, the pool on both carries.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from production_stack_tpu.models import decoder, llama
from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.models.registry import Family

# The spread of every norm weight around one in a random tree: at one,
# ``norm_f`` twice in a row is ``norm_f`` once and a norm on the wrong
# side of a residual nearly so (models/glm4_moe_lite.py has the reason).
SPREAD = 0.1
STATS = ("loop_passes",)
NORMS = ("attn_norm", "attn_out_norm", "mlp_norm", "mlp_out_norm")


def init_params(cfg: ModelConfig, rng: jax.Array, **_unused) -> Dict:
    """Random tree. Keys 0-8 of 16 are Llama's (embedding, q, k, v, o,
    gate, up, down, head: normal / sqrt(fan_in), rounded to the served
    dtype), 9 the final norm, 10-13 a layer's four norms (``1 + 0.1
    normal``), 14 and 15 the exit gate's weight and bias
    (``chipbench/reference/ouro.py`` redraws all of it by its own copy
    of this recipe: key ``i``, element ``n`` of the stacked leaf)."""
    dtype = cfg.jnp_dtype
    H, KVH, D, Hd = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                     cfg.hidden_size)
    I, L, V = cfg.intermediate_size, cfg.num_layers, cfg.vocab_size
    keys = jax.random.split(rng, 16)

    def winit(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / jnp.sqrt(fan_in)).astype(dtype)

    def near_one(key, shape):
        return (1.0 + SPREAD * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    return {
        "embed": (0.02 * jax.random.normal(keys[0], (V, Hd), jnp.float32)
                  ).astype(dtype),
        "layers": {
            "wqkv": llama.fuse_qkv(
                winit(keys[1], (L, Hd, H * D), Hd),
                winit(keys[2], (L, Hd, KVH * D), Hd),
                winit(keys[3], (L, Hd, KVH * D), Hd), KVH),
            "wo": winit(keys[4], (L, H * D, Hd), H * D),
            "w_gate": winit(keys[5], (L, Hd, I), Hd),
            "w_up": winit(keys[6], (L, Hd, I), Hd),
            "w_down": winit(keys[7], (L, I, Hd), I),
            **{name: near_one(keys[10 + i], (L, Hd))
               for i, name in enumerate(NORMS)},
        },
        "lm_head": winit(keys[8], (Hd, V), Hd),
        "final_norm": near_one(keys[9], (Hd,)),
        # assumed: a ``hidden_size -> 1`` linear map with a bias.
        "exit_gate": {
            "w": winit(keys[14], (Hd,), Hd),
            "b": SPREAD * jax.random.normal(keys[15], (), jnp.float32),
        },
    }


def _layer(cfg: ModelConfig, mode: str, x, p: Dict, kv, page_layer,
           batch: decoder.Batch):
    """One sandwich-normed layer on its un-stacked leaves ``p``, its
    keys and values in ``page_layer`` of the pool."""
    eps = cfg.rms_norm_eps
    x, kv = llama.attention_half(
        cfg, mode, x, p, None, kv, page_layer, batch,
        out=lambda y: llama.rms_norm(y, p["attn_out_norm"], eps))
    with jax.named_scope("mlp"):
        h = llama.rms_norm(x, p["mlp_norm"], eps)
        gate = jax.nn.silu(
            llama._proj(h, p, "w_gate").astype(jnp.float32)).astype(h.dtype)
        y = llama._proj(gate * llama._proj(h, p, "w_up"), p, "w_down")
        x = x + llama.rms_norm(y, p["mlp_out_norm"], eps)
    return x, kv


def run_layers(cfg: ModelConfig, mode: str, x, params: Dict, kv_pages,
               batch: decoder.Batch):
    """``Family.loop``: the stack ``cfg.loop_passes`` times, the final
    norm closing each pass (scope ``loop_norm``). Returns (x, kv_pages,
    [passes run])."""
    passes = cfg.loop_passes

    def layer_step(x, sides, layer, p, u):
        return _layer(cfg, mode, x, p, sides, layer * passes + u, batch)

    def close(x):
        with jax.named_scope("loop_norm"):
            return llama.rms_norm(x, params["final_norm"], cfg.rms_norm_eps)

    x, sides, done = decoder.scan_passes(
        layer_step, x, kv_pages, passes, params["layers"], close)
    return x, sides, done[None]


def _head(params: Dict, cfg: ModelConfig, x, output_hidden: bool):
    # The last pass's closing norm is the model's final norm: none again.
    return llama.project_out(params, cfg, x, output_hidden, norm=False)


def exit_pdf(gate: Dict, states: jax.Array, threshold: float = 1.0):
    """The exit gate over the passes' closing states ``[U, ..., Hd]``
    (``h_1 .. h_U``), in float32: ``lambda_u = sigmoid(h_{u+1} . w +
    b)``, ``p_u = lambda_u prod_{j<u} (1 - lambda_j)`` for ``u < U - 1``
    and the remainder at the last pass. Returns (``p [U, ...]``, the
    first pass at which the running sum of ``p`` reaches ``threshold``).
    No step program calls it (the module's docstring)."""
    lam = jax.nn.sigmoid(
        states.astype(jnp.float32) @ gate["w"].astype(jnp.float32)
        + gate["b"])
    stay = jnp.cumprod(1.0 - lam, axis=0)  # prod_{j<=u} (1 - lambda_j)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    pdf = jnp.concatenate([(lam * before)[:-1], before[-1:]], axis=0)
    reached = jnp.cumsum(pdf, axis=0) >= threshold
    last = pdf.shape[0] - 1
    return pdf, jnp.where(reached.any(axis=0), jnp.argmax(reached, axis=0),
                          last)


def config_fields(hf: dict, layers: int) -> dict:
    """The ``ModelConfig`` fields this family reads of its own keys. A
    key that names a mechanism the program does not have is refused by
    name, not dropped."""
    del layers
    for key, served in (("hidden_act", "silu"), ("attention_bias", False),
                        ("use_sliding_window", False),
                        ("rope_scaling", None),
                        ("tie_word_embeddings", False)):
        if hf.get(key, served) != served:
            raise ValueError(
                f"the ouro family serves {key} {served!r} (no bias on a "
                f"projection, no window, no rope scaling, an untied "
                f"head); got {hf[key]!r}")
    passes = int(hf.get("total_ut_steps", 1))
    threshold = float(hf.get("early_exit_threshold", 1.0))
    if passes < 1:
        raise ValueError(f"total_ut_steps {passes}: at least one pass")
    if threshold < 1.0:
        raise ValueError(
            f"early_exit_threshold {threshold}: the step programs run "
            "every pass for every token (the published 1.0); an early "
            "exit is mathematics only (models/ouro.py::exit_pdf)")
    return dict(loop_passes=passes, early_exit_threshold=threshold)


FAMILY = Family(
    model_types=("ouro",),
    init_params=init_params,
    embed=llama.FAMILY.embed,
    loop=run_layers,
    head=_head,
    specs={
        **llama.ATTN_SPECS,
        ("layers", "w_gate"): P(None, None, "tp"),
        ("layers", "w_up"): P(None, None, "tp"),
        ("layers", "w_down"): P(None, "tp", None),
        ("layers", "attn_out_norm"): P(None, None),
        ("layers", "mlp_out_norm"): P(None, None),
        ("exit_gate", "w"): P(None),
        ("exit_gate", "b"): P(),
    },
    # The matrices are Llama's, and so is what int8 takes of them.
    quant_keys=llama.FAMILY.quant_keys,
    # No LoRA slots (the loop hands ``attention_half`` none) and no
    # pipeline stages: a stage would send the state back round
    # ``total_ut_steps`` times, which parallel/pp_serving.py is not taught.
    stats=STATS,
    config_fields=config_fields,
    page_layers=lambda cfg: cfg.loop_passes * cfg.num_layers,
    layer_passes=lambda cfg: cfg.loop_passes,
)

apply = functools.partial(decoder.apply, FAMILY)
