"""Plain float32 reference of the GLM-4.7-Flash decoder
(``model_type: glm4_moe_lite``), for one chip's share of a layer.

Straight ``jax.numpy``: full causal attention over the whole sequence
with per-head keys and values up-projected from the latent (nothing
absorbed), no cache, no kernels, no grouped matmul, every matmul at
``highest`` precision. It takes nothing the program made: the weights
are drawn here from the seed by this file's own copy of the program's
init recipe (``models/glm4_moe_lite.py::init_params``: 24 keys split
from the seed, a per-layer leaf stacked ``[layers, ...]`` so that layer
``l`` is element ``l`` of its key's normal array and expert ``e`` of
sparse layer ``s`` element ``s x held + e``; normal / sqrt(fan_in)
rounded to the served dtype, norm weights ``1 + 0.1 normal`` rounded
likewise, the router's selection bias ``0.1 normal`` in float32), one
matrix, one block of the dense MLP's columns or one expert at a time;
the layer's number is an argument, so each function is one program for
all 47. The counter-based generator and the small helpers are
``chipbench/reference/llama.py``'s and ``longcat.py``'s (reference
files, not the program: the generator, the embedding lookup, SwiGLU
with ``hidden_act`` silu, the rotation of adjacent lanes).

The equations, for layer ``l`` of ``num_hidden_layers``, input ``x``
(``RMS(.; w)`` is RMSNorm with weight ``w``, eps ``rms_norm_eps``)::

    h = x + MLA[l](RMS(x; in_norm[l]))
    y = h + F_l(RMS(h; post_norm[l]))
    F_l = SwiGLU of width intermediate_size     for l < first_k_dense_replace
    F_l = sum_e w_e SwiGLU_e(u) + SwiGLU_shared(u)    after them

``MLA(u)`` with ``H = num_attention_heads`` heads, no biases, no factor
on either latent::

    c_q = RMS(u Wqa; q_norm);  q = (c_q Wqb).reshape(H, nope + rope)
    t = u Wkva;  c = RMS(t[:kv_lora_rank]; kv_norm);  k_r = t[kv_lora_rank:]
    q_rope, k_r <- RoPE(rope_theta, all rope lanes, lanes (2j, 2j+1)
                        rotate together and stay where they are)
    kv = c Wkvb -> [H, nope + v_head_dim]
    p = softmax_causal((q_nope . k_nope + q_rope . k_r) * (nope + rope) ** -0.5)
    out = concat_heads(p v) Wo

The cache holds, per token and layer, ``c`` (after its norm) and ``k_r``
(after its rotation).

The expert layer: ``s = sigmoid(float32(u) Wr)`` over all
``n_routed_experts x chips_per_layer`` outputs; the
``num_experts_per_tok`` experts are the top of ``s + b``; weights
``s[chosen] / (sum + 1e-20) x routed_scaling_factor``; experts
``moe_intermediate_size`` wide, the shared one ``n_shared_experts`` times
that. One chip of ``chips_per_layer``: this file computes the experts of
block ``layer_share`` (``n_routed_experts`` of them), the shared expert
whole, and the ``vocab_size`` rows held here; what the other chips'
experts would add is left out, here as in the program.

The prediction module (:func:`mtp_logprobs`; ``num_nextn_predict_layers``
1)::

    h' = [RMS(emb(t_{i+1}); embed_norm) ; RMS(h_i; hidden_norm)] W_eh

then one sparse layer as above with its own weights (21 keys split from
the module's own seed), its own final norm, the trunk's embedding and
head.

``assumed`` (each marked at its line): the rotary convention; ``b``
drawn with a spread of 0.1; ``hidden_act`` silu; the module's
concatenation order (embedding first), its own final norm, the shared
embedding and head, and the trunk's state read after the trunk's final
norm; norm weights drawn ``1 + 0.1 normal``.

``activations`` names a lower-precision type to which every activation is
rounded on its way between operations: a control, not the reference.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.llama import (
    HIGHEST,
    _mm,
    _rounded,
    normal_rows,
    seed_key,
    split,
)
from chipbench.reference.longcat import (
    _columns,
    _embed,
    _stacked,
    _swiglu,
    rope_pairs,
)

# Columns of the dense MLP computed at once: two [2048, 2048] and one
# [2048, 2048] matrix in float32 are 50 MB beside a serving engine that
# has the chip nearly full.
MLP_BLOCK = 2048
SPREAD = 0.1  # assumed: of the selection bias and of the norm weights
ROUTER_EPS = 1e-20

# Which of the trunk's 24 keys draws what (models/glm4_moe_lite.py).
EMBED, FINAL_NORM, HEAD = 0, 1, 2
ATTN = slice(3, 12)  # in_norm post_norm wq_a q_norm wq_b wkv_a kv_norm wkv_b wo
DENSE = slice(12, 15)  # w_gate w_up w_down
SPARSE = slice(15, 23)  # router bias w_gate w_up w_down shared_{gate,up,down}


def _norm_weight(key, index, width, dtype):
    """Entry ``index`` of a ``[n, width]`` leaf of norm weights."""
    w = normal_rows(key, jnp.asarray(index).astype(jnp.uint32)
                    * jnp.uint32(width), width)
    # assumed: drawn 1 + 0.1 normal, so that the side of an operation a
    # weight is applied on shows
    return (1.0 + SPREAD * w).astype(dtype).astype(jnp.float32)


def rms_norm(x, weight, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * weight)


@functools.partial(jax.jit, static_argnames=("dims", "activations"))
def _mla(keys, x, lens, at, *, dims, activations):
    """``(x + MLA(RMS(x)), RMS of that by post_norm, c, k_r)`` of layer
    ``at`` drawn from the nine ``keys``; ``c`` and ``k_r`` as the cache
    holds them, ``[S, T, 1, width]``."""
    (hidden, heads, q_rank, kv_rank, nope, rope, v_dim, theta, eps,
     dtype) = dims
    S, T, _ = x.shape
    dt = jnp.dtype(dtype)
    mat = functools.partial(_stacked, index=at, dtype=dt)
    norm = functools.partial(_norm_weight, index=at, dtype=dt)
    wq_a = mat(keys[2], shape=(hidden, q_rank), fan_in=hidden)
    # drawn [out, in] and per head, as the program stores them
    wq_b = mat(keys[4], shape=(heads * (nope + rope), q_rank),
               fan_in=q_rank).T
    wkv_a = mat(keys[5], shape=(hidden, kv_rank + rope), fan_in=hidden)
    wkv_b = mat(keys[7], shape=(heads, kv_rank, nope + v_dim),
                fan_in=kv_rank)
    wo = mat(keys[8], shape=(heads * v_dim, hidden), fan_in=heads * v_dim)
    r = functools.partial(_rounded, activations=activations)
    h = r(rms_norm(x, norm(keys[0], width=hidden), eps))
    cq = r(rms_norm(_mm(h, wq_a), norm(keys[3], width=q_rank), eps))
    q = _mm(cq, wq_b).reshape(S, T, heads, nope + rope)
    t = _mm(h, wkv_a)
    c = r(rms_norm(t[..., :kv_rank], norm(keys[6], width=kv_rank), eps))
    # assumed: the rotary convention (adjacent lanes, in place)
    k_r = r(rope_pairs(t[..., kv_rank:], theta))
    q_nope = r(q[..., :nope])
    q_rope = r(rope_pairs(q[..., nope:], theta))
    kv = jnp.einsum("stc,hcd->sthd", c, wkv_b, precision=HIGHEST)
    k_nope, v = r(kv[..., :nope]), r(kv[..., nope:])
    t_ = jnp.arange(T)
    seen = t_[None, :] <= t_[:, None]

    def one(args):  # a sequence at a time: the scores are [heads, T, T]
        qn, qr, kn, kr, v1, n = args
        scores = (jnp.einsum("thd,uhd->htu", qn, kn, precision=HIGHEST)
                  + jnp.einsum("thd,ud->htu", qr, kr, precision=HIGHEST)
                  ) * (nope + rope) ** -0.5
        mask = seen & (t_[None, :] < n)
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return jnp.einsum("htu,uhd->thd", jax.nn.softmax(scores, -1), v1,
                          precision=HIGHEST)

    attn = jax.lax.map(one, (q_nope, q_rope, k_nope, k_r, v, lens))
    attn = r(attn).reshape(S, T, heads * v_dim)
    out = r(x + _mm(attn, wo))
    normed = r(rms_norm(out, norm(keys[1], width=hidden), eps))
    return out, normed, c[:, :, None, :], k_r[:, :, None, :]


@functools.partial(jax.jit, static_argnames=("dims", "activations"))
def _dense_mlp(keys, h, at, *, dims, activations):
    """SwiGLU of dense layer ``at`` on the normed ``h``, the MLP's
    columns a block at a time."""
    hidden, inter, dtype = dims
    dt = jnp.dtype(dtype)
    r = functools.partial(_rounded, activations=activations)
    step = math.gcd(inter, MLP_BLOCK)

    def block(total, first):
        gate = _columns(keys[0], at, (hidden, inter), first, step, hidden, dt)
        up = _columns(keys[1], at, (hidden, inter), first, step, hidden, dt)
        # rows [first, first + step) of w_down [inter, hidden]: a run
        down = (normal_rows(
            keys[2], jnp.asarray(at).astype(jnp.uint32)
            * jnp.uint32(inter * hidden)
            + first.astype(jnp.uint32) * jnp.uint32(hidden), step * hidden
        ).reshape(step, hidden) / jnp.sqrt(jnp.float32(inter))
        ).astype(dt).astype(jnp.float32)
        # assumed: hidden_act is silu
        return total + _mm(r(jax.nn.silu(_mm(h, gate)) * _mm(h, up)),
                           down), None

    out, _ = jax.lax.scan(block, jnp.zeros_like(h),
                          jnp.arange(0, inter, step, dtype=jnp.int32))
    return out


def _route(h, router, bias, top_k, scaling):
    """[N, outputs] float32: each token's weight on each router output,
    zero where it was not picked."""
    scores = jax.nn.sigmoid(_mm(h, router))
    # selected by score plus bias, weighted by the scores without it over
    # their sum, times the scaling factor
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                       + ROUTER_EPS) * scaling
    return jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(picked)


def moe_layer(h, router, bias, experts, shared, *, first, top_k, scaling):
    """Over explicit weights: ``h [N, hidden]``; ``router [hidden, E]``;
    ``experts`` the (w_gate, w_up, w_down) of the router's outputs
    ``[first, first + len(experts))``; ``shared`` the shared expert's
    three. Returns (the given experts' part, the shared expert's), so
    that shares can be added up with the shared expert counted once
    (tests/test_glm4_moe_lite.py)."""
    weights = _route(h, router, bias, top_k, scaling)
    routed = jnp.zeros_like(h)
    for e, (w_gate, w_up, w_down) in enumerate(experts):
        routed = routed + weights[:, first + e, None] * _swiglu(
            h, w_gate, w_up, w_down, lambda x: x)
    return routed, _swiglu(h, *shared, lambda x: x)


@functools.partial(jax.jit, static_argnames=("dims", "activations"))
def _moe(keys, flat, at, *, dims, activations):
    """The expert layer ``at`` (counted over the sparse layers) drawn
    from the eight ``keys``, on the normed state ``flat [N, hidden]``,
    the tokens of every sequence in one axis: the held experts one at a
    time, then the shared one. (It takes the tokens flat and reshapes
    nothing itself: PERF.md section 6, PR 41, finding (1).)"""
    (hidden, width, shared_width, held, chips, share, top_k, scaling,
     dtype) = dims
    dt = jnp.dtype(dtype)
    r = functools.partial(_rounded, activations=activations)
    outputs = held * chips
    router = _stacked(keys[0], at, (hidden, outputs), hidden, dt)
    # assumed: e_score_correction_bias drawn 0.1 normal, float32
    bias = SPREAD * normal_rows(
        keys[1], jnp.asarray(at).astype(jnp.uint32) * jnp.uint32(outputs),
        outputs)
    weights = _route(flat, router, bias, top_k, scaling)
    mine = weights[:, share * held:(share + 1) * held]

    def expert(total, e):
        index = at * held + e
        out = _swiglu(
            flat, _stacked(keys[2], index, (hidden, width), hidden, dt),
            _stacked(keys[3], index, (hidden, width), hidden, dt),
            _stacked(keys[4], index, (width, hidden), width, dt), r)
        w = jax.lax.dynamic_slice_in_dim(mine, e, 1, axis=1)
        return total + w * out, None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(flat),
                             jnp.arange(held, dtype=jnp.int32))
    shared = _swiglu(
        flat, _stacked(keys[5], at, (hidden, shared_width), hidden, dt),
        _stacked(keys[6], at, (hidden, shared_width), hidden, dt),
        _stacked(keys[7], at, (shared_width, hidden), shared_width, dt), r)
    return routed + shared


@functools.partial(jax.jit,
                   static_argnames=("vocab", "eps", "dtype", "activations"))
def _logprobs(head_key, norm_key, x, *, vocab, eps, dtype, activations=None):
    hidden = x.shape[-1]
    dt = jnp.dtype(dtype)
    head = _stacked(head_key, 0, (hidden, vocab), hidden, dt)
    h = _rounded(rms_norm(x, _norm_weight(norm_key, 0, hidden, dt), eps),
                 activations)
    return jax.nn.log_softmax(_mm(h, head), axis=-1)


def _dims(hf: dict, dtype: str):
    """(attention dims, expert-layer dims) from the published keys."""
    if (hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1
            or not hf.get("norm_topk_prob", True)
            or hf.get("attention_bias") or hf.get("rope_scaling")
            or hf.get("partial_rotary_factor", 1) != 1
            or hf.get("hidden_act", "silu") != "silu"):
        raise ValueError("the reference has the published block only: no "
                         "group-limited routing, renormalised top-k "
                         "weights, no biases, no rope scaling, silu")
    hidden, width = hf["hidden_size"], hf["moe_intermediate_size"]
    mla = (hidden, hf["num_attention_heads"], hf["q_lora_rank"],
           hf["kv_lora_rank"], hf["qk_nope_head_dim"],
           hf["qk_rope_head_dim"], hf["v_head_dim"],
           float(hf["rope_theta"]), float(hf["rms_norm_eps"]), dtype)
    moe = (hidden, width, hf.get("n_shared_experts", 0) * width,
           hf["n_routed_experts"], hf.get("chips_per_layer", 1),
           hf.get("layer_share", 0), hf["num_experts_per_tok"],
           float(hf.get("routed_scaling_factor", 1.0)), dtype)
    return mla, moe


def forward(hf: dict, seed: int, tokens, lens, *, keep_from: int,
            quantization=None, dtype="bfloat16", kv_layers=(0,),
            activations=None, output_hidden: bool = False):
    """Log-probabilities [S, T - keep_from, rows held] of the next token
    after each position from ``keep_from`` on, and {layer: (c, k_r)} of
    the listed layers as ``[S, T, 1, kv_lora_rank]`` and ``[S, T, 1,
    qk_rope_head_dim]``, for right-padded ``tokens`` [S, T] of lengths
    ``lens``. ``hf`` holds the sizes under their published keys, the cut
    ones at what is held here. ``output_hidden``: the residual stream
    after the last layer, ``[S, T, hidden]``, in place of the
    log-probabilities."""
    if quantization is not None:
        raise ValueError(f"no reference for quantization {quantization!r}")
    mla_dims, moe_dims = _dims(hf, dtype)
    layers, hidden = hf["num_hidden_layers"], hf["hidden_size"]
    dense = min(hf.get("first_k_dense_replace", 0), layers)
    keys = split(seed_key(seed), 24)
    tokens = jnp.asarray(tokens, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    r = functools.partial(_rounded, activations=activations)
    with jax.default_matmul_precision("highest"):
        x = r(_embed(keys[EMBED], tokens, vocab=hf["vocab_size"],
                     hidden=hidden, dtype=dtype))
        kept = {}
        for layer in range(layers):
            x, h, c, k_r = _mla(keys[ATTN], x, lens, layer, dims=mla_dims,
                                activations=activations)
            if layer in kv_layers:
                kept[layer] = (np.asarray(c), np.asarray(k_r))
            if layer < dense:
                out = _dense_mlp(
                    keys[DENSE], h, layer,
                    dims=(hidden, hf["intermediate_size"], dtype),
                    activations=activations)
            else:
                out = _moe(keys[SPARSE], h.reshape(-1, hidden),
                           layer - dense, dims=moe_dims,
                           activations=activations).reshape(h.shape)
            x = r(x + out)
        if output_hidden:
            return np.asarray(x), kept
        logp = _logprobs(keys[HEAD], keys[FINAL_NORM], x[:, keep_from:],
                         vocab=hf["vocab_size"],
                         eps=float(hf["rms_norm_eps"]), dtype=dtype,
                         activations=activations)
    return np.asarray(logp), kept


def mtp_logprobs(hf: dict, seed: int, mtp_seed: int, next_tokens, hidden,
                 lens, *, dtype="bfloat16"):
    """The prediction module's log-probabilities ``[S, T, rows held]`` of
    the token after next: position ``i`` reads ``hidden[:, i]`` (the
    trunk's state after its final norm, given) and the embedding of
    ``next_tokens[:, i]``. The module's weights come from ``mtp_seed``
    (21 keys: the two input norms, ``W_eh``, its final norm, nine of
    attention, eight of the expert layer), the embedding and the head
    from the trunk's ``seed``."""
    mla_dims, moe_dims = _dims(hf, dtype)
    width, eps = hf["hidden_size"], float(hf["rms_norm_eps"])
    dt = jnp.dtype(dtype)
    trunk = split(seed_key(seed), 24)
    keys = split(seed_key(mtp_seed), 21)
    hidden = jnp.asarray(hidden, jnp.float32)
    with jax.default_matmul_precision("highest"):
        emb = _embed(trunk[EMBED], jnp.asarray(next_tokens, jnp.int32),
                     vocab=hf["vocab_size"], hidden=width, dtype=dtype)
        # assumed: the embedding first, each half under its own norm
        joined = jnp.concatenate(
            [rms_norm(emb, _norm_weight(keys[0], 0, width, dt), eps),
             rms_norm(hidden, _norm_weight(keys[1], 0, width, dt), eps)],
            axis=-1)
        x = _mm(joined, _stacked(keys[2], 0, (2 * width, width), 2 * width,
                                 dt))
        x, h, _, _ = _mla(keys[4:13], x, jnp.asarray(lens, jnp.int32), 0,
                          dims=mla_dims, activations=None)
        x = x + _moe(keys[13:21], h.reshape(-1, width), 0, dims=moe_dims,
                     activations=None).reshape(h.shape)
        # assumed: the module's own final norm, the trunk's head
        logp = _logprobs(trunk[HEAD], keys[3], x, vocab=hf["vocab_size"],
                         eps=eps, dtype=dtype)
    return np.asarray(logp)
