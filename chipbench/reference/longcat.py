"""Plain float32 reference of the LongCat-Flash decoder block, for one
chip's share of a layer.

Straight ``jax.numpy``: full causal attention over the whole sequence
with per-head keys and values up-projected from the latent (the first
form of the equations: nothing absorbed), no cache, no kernels, no
grouped matmul, every matmul at ``highest`` precision. It takes nothing
the program made: the weights are drawn here from the seed by this
file's own copy of the program's init recipe
(``models/longcat.py::init_params``: 16 keys split from the seed; a
per-sublayer leaf is stacked ``[layers, 2, ...]``, so sublayer ``i`` of
layer ``l`` is element ``2l + i`` of its key's normal array, and expert
``e`` of layer ``l`` element ``l x held + e``; normal / sqrt(fan_in),
rounded to the served dtype), one matrix, one block of a dense MLP's
columns or one expert at a time; the sublayer's number is an argument,
so each of the three functions is one program for all of them. The counter-based generator and the
small helpers are ``chipbench/reference/llama.py``'s (a reference file,
not the program).

The equations, for layer ``l`` of ``num_layers``, input ``x``,
sublayers ``i = 0, 1`` (``RMS`` is RMSNorm, eps ``rms_norm_eps``, its
weights one at init)::

    a = x + MLA[l,i](RMS(x))
    h = RMS(a)
    if i == 0:  s = MoE[l](h)                      # the shortcut's branch
    x = a + SwiGLU(h; W[l,i], width ffn_hidden_size)
    after i == 1:  x = x + s

``MLA(h)`` with ``H = num_attention_heads`` heads, no biases::

    cq = RMS(h Wqa)                                           # [q_lora_rank]
    q  = (cq Wqb).reshape(H, nope + rope) * sqrt(hidden / q_lora_rank)
    t  = h Wkva;  c = RMS(t[:kv_lora_rank]);  k_r = t[kv_lora_rank:]
    q_rope, k_r <- RoPE(rope_theta, all rope dims, lanes (2j, 2j+1)
                        rotate together and stay where they are)
    kv = (c * sqrt(hidden / kv_lora_rank)) Wkvb -> [H, nope + v]
    p  = softmax_causal((q_nope . k_nope + q_rope . k_r) * (nope + rope) ** -0.5)
    out = concat_heads(p v) Wo

(the two ``sqrt`` factors where ``mla_scale_q_lora`` /
``mla_scale_kv_lora`` are true). The cache holds, per token and
sublayer, ``c`` (after its norm, before its factor) and ``k_r`` (after
its rotation): page layer ``2l + i`` is sublayer ``i`` of layer ``l``.

``MoE(h)``: ``sc = softmax(float32(h) Wr)`` over all ``n_routed_experts x
chips_per_layer + zero_expert_num`` outputs; the ``moe_topk`` experts
are the top of ``sc + b`` (``b`` zeros at init); weights
``routed_scaling_factor x sc[picked]``, not renormalised. An expert with
weights is a SwiGLU of width ``expert_ffn_hidden_size``; the last
``zero_expert_num`` outputs are the identity. One chip of
``chips_per_layer``: this file computes the experts of block
``layer_share`` (``n_routed_experts`` of them), every identity, and the
``vocab_size`` rows held here; what the other chips' experts would add
is left out, here as in the program.

``assumed`` (each marked at its line): ``hidden_act`` is silu; the top-k
weights are not renormalised; the correction bias is zero at init; the
rotary convention above.

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
    rms_norm,
    seed_key,
    split,
    threefry2x32,
)

# Columns of a dense MLP computed at once: two [6144, 3072] and one
# [3072, 6144] matrix in float32 are 226 MB, which fits beside a serving
# engine where the three whole ones (906 MB) might not.
MLP_BLOCK = 3072


def _stacked(key, index, shape, fan_in, dtype):
    """Entry ``index`` of the leaf ``[n, *shape]`` that ``key`` draws."""
    size = math.prod(shape)
    w = normal_rows(key, jnp.asarray(index).astype(jnp.uint32)
                    * jnp.uint32(size), size)
    return (w.reshape(shape) / jnp.sqrt(jnp.float32(fan_in))
            ).astype(dtype).astype(jnp.float32)


def _normal_at(key, counters):
    """The standard-normal values at flat indices ``counters`` (uint32,
    any shape) of the array ``key`` generates: ``normal_rows`` for
    indices that are not a run."""
    b0, b1 = threefry2x32(key[0], key[1], jnp.zeros_like(counters), counters)
    bits = ((b0 ^ b1) >> np.uint32(9)) | np.uint32(0x3F800000)
    unit = jax.lax.bitcast_convert_type(bits, jnp.float32) - 1.0
    low = np.nextafter(np.float32(-1.0), np.float32(0.0))
    u = jnp.maximum(low, unit * (np.float32(1.0) - low) + low)
    return np.float32(np.sqrt(2)) * jax.lax.erf_inv(u)


def _columns(key, index, shape, first, width, fan_in, dtype):
    """Columns ``[first, first + width)`` of entry ``index`` of the leaf
    ``[n, *shape]``: ``[shape[0], width]``."""
    rows, cols = shape
    base = jnp.asarray(index).astype(jnp.uint32) * jnp.uint32(rows * cols)
    counters = (base + jnp.arange(rows, dtype=jnp.uint32)[:, None]
                * jnp.uint32(cols) + jnp.asarray(first).astype(jnp.uint32)
                + jnp.arange(width, dtype=jnp.uint32)[None, :])
    return (_normal_at(key, counters) / jnp.sqrt(jnp.float32(fan_in))
            ).astype(dtype).astype(jnp.float32)


def rope_pairs(x, theta: float):
    """x [S, T, ..., R]: every lane rotated, lanes (2j, 2j + 1) together,
    each staying where it is."""
    rot = x.shape[-1]
    inv_freq = (float(theta) ** (-np.arange(0, rot, 2, dtype=np.float64)
                                 / rot)).astype(np.float32)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    angles = angles.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3)
                            + (rot // 2,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    pairs = x.reshape(x.shape[:-1] + (rot // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def _swiglu(h, w_gate, w_up, w_down, r):
    # assumed: hidden_act is silu
    return _mm(r(jax.nn.silu(_mm(h, w_gate)) * _mm(h, w_up)), w_down)


@functools.partial(jax.jit, static_argnames=("dims", "activations"))
def _mla(keys, x, lens, at, *, dims, activations):
    """x + MLA(RMS(x)) of sublayer ``at`` (= 2l + i); also (c, k_r) as
    the cache holds them, ``[S, T, 1, width]``."""
    (hidden, heads, q_rank, kv_rank, nope, rope, v_dim, scale_q, scale_kv,
     theta, eps, dtype) = dims
    S, T, _ = x.shape
    mat = functools.partial(_stacked, index=at, dtype=jnp.dtype(dtype))
    wq_a = mat(keys[2], shape=(hidden, q_rank), fan_in=hidden)
    # drawn [out, in] and per head, as the program stores them
    wq_b = mat(keys[3], shape=(heads * (nope + rope), q_rank),
               fan_in=q_rank).T
    wkv_a = mat(keys[4], shape=(hidden, kv_rank + rope), fan_in=hidden)
    wkv_b = mat(keys[5], shape=(heads, kv_rank, nope + v_dim),
                fan_in=kv_rank)
    wo = mat(keys[6], shape=(heads * v_dim, hidden), fan_in=heads * v_dim)
    r = functools.partial(_rounded, activations=activations)
    h = r(rms_norm(x, eps))  # the norm weights are initialised to one
    cq = r(rms_norm(_mm(h, wq_a), eps))
    q = _mm(cq, wq_b).reshape(S, T, heads, nope + rope)
    if scale_q:
        q = q * math.sqrt(hidden / q_rank)
    t = _mm(h, wkv_a)
    c = r(rms_norm(t[..., :kv_rank], eps))
    # assumed: the rotary convention (adjacent lanes, in place)
    k_r = r(rope_pairs(t[..., kv_rank:], theta))
    q_nope = r(q[..., :nope])
    q_rope = r(rope_pairs(q[..., nope:], theta))
    kv = jnp.einsum("stc,hcd->sthd",
                    c * math.sqrt(hidden / kv_rank) if scale_kv else c,
                    wkv_b, precision=HIGHEST)
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
    return r(x + _mm(attn, wo)), c[:, :, None, :], k_r[:, :, None, :]


@functools.partial(jax.jit, static_argnames=("dims", "activations"))
def _dense_mlp(keys, a, at, *, dims, activations):
    """(a + SwiGLU(RMS(a)), RMS(a)) of sublayer ``at``, the MLP's columns
    a block at a time."""
    hidden, inter, eps, dtype = dims
    dt = jnp.dtype(dtype)
    r = functools.partial(_rounded, activations=activations)
    h = r(rms_norm(a, eps))
    step = math.gcd(inter, MLP_BLOCK)

    def block(total, first):
        gate = _columns(keys[7], at, (hidden, inter), first, step, hidden, dt)
        up = _columns(keys[8], at, (hidden, inter), first, step, hidden, dt)
        # rows [first, first + step) of w_down [inter, hidden]: a run
        size = step * hidden
        down = (normal_rows(
            keys[9], jnp.asarray(at).astype(jnp.uint32)
            * jnp.uint32(inter * hidden)
            + first.astype(jnp.uint32) * jnp.uint32(hidden), size
        ).reshape(step, hidden) / jnp.sqrt(jnp.float32(inter))
        ).astype(dt).astype(jnp.float32)
        # assumed: hidden_act is silu
        return total + _mm(r(jax.nn.silu(_mm(h, gate)) * _mm(h, up)),
                           down), None

    out, _ = jax.lax.scan(block, jnp.zeros_like(h),
                          jnp.arange(0, inter, step, dtype=jnp.int32))
    return r(a + out), h


def _route(h, router, bias, top_k, scaling):
    """[N, outputs] float32: each token's weight on each router output,
    zero where it was not picked."""
    scores = jax.nn.softmax(_mm(h, router), axis=-1)
    # assumed: selected by score plus bias, weighted by the score times
    # the scaling factor, not renormalised
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1) * scaling
    return jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(picked)


def moe_layer(h, router, bias, experts, *, first, zero, top_k, scaling):
    """``sum_e w_e E_e(h)`` over explicit weights: ``h [N, hidden]``;
    ``router [hidden, E + zero]``; ``experts`` the (w_gate, w_up, w_down)
    of the router's outputs ``[first, first + len(experts))``; the last
    ``zero`` outputs are the identity. Returns (the given experts' part,
    the identities' part), so that shares can be added up with the
    identities counted once (tests/test_longcat.py)."""
    weights = _route(h, router, bias, top_k, scaling)
    routed = jnp.zeros_like(h)
    for e, (w_gate, w_up, w_down) in enumerate(experts):
        routed = routed + weights[:, first + e, None] * _swiglu(
            h, w_gate, w_up, w_down, lambda x: x)
    identity = jnp.sum(weights[:, router.shape[-1] - zero:], axis=-1,
                       keepdims=True) * h
    return routed, identity


@functools.partial(jax.jit, static_argnames=("dims", "activations"))
def _moe(keys, flat, at, *, dims, activations):
    """The expert layer ``at`` on the normed state ``flat [N, hidden]``,
    the tokens of every sequence in one axis: the held experts one at a
    time, then the identities. (It takes the tokens flat and reshapes
    nothing itself: compiled for the TPU with a ``[3, 716, hidden] ->
    [2148, hidden]`` reshape inside, this function returned NaN in every
    entry, where the same steps one by one, or on ``[1, 2148, hidden]``,
    did not; my chip runs, PR 41, PERF.md section 6.)"""
    (hidden, width, held, chips, share, zero, top_k, scaling, dtype) = dims
    dt = jnp.dtype(dtype)
    r = functools.partial(_rounded, activations=activations)
    outputs = held * chips + zero
    router = _stacked(keys[10], at, (hidden, outputs), hidden, dt)
    # assumed: the correction bias is zero at init
    weights = _route(flat, router, jnp.zeros((outputs,)), top_k, scaling)
    mine = weights[:, share * held:(share + 1) * held]

    def expert(total, e):
        index = at * held + e
        out = _swiglu(
            flat, _stacked(keys[11], index, (hidden, width), hidden, dt),
            _stacked(keys[12], index, (hidden, width), hidden, dt),
            _stacked(keys[13], index, (width, hidden), width, dt), r)
        w = jax.lax.dynamic_slice_in_dim(mine, e, 1, axis=1)
        return total + w * out, None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(flat),
                             jnp.arange(held, dtype=jnp.int32))
    identity = jnp.sum(weights[:, outputs - zero:], axis=-1,
                       keepdims=True) * flat
    return r(routed + identity)


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "dtype"))
def _embed(key, tokens, *, vocab, hidden, dtype):
    table = (0.02 * normal_rows(key, jnp.uint32(0), vocab * hidden)
             .reshape(vocab, hidden)).astype(jnp.dtype(dtype))
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("vocab", "eps", "dtype", "activations"))
def _logprobs(key, x, *, vocab, eps, dtype, activations=None):
    hidden = x.shape[-1]
    head = _stacked(key, 0, (hidden, vocab), hidden, jnp.dtype(dtype))
    h = _rounded(rms_norm(x, eps), activations)
    return jax.nn.log_softmax(_mm(h, head), axis=-1)


def forward(hf: dict, seed: int, tokens, lens, *, keep_from: int,
            quantization=None, dtype="bfloat16", kv_layers=(0,),
            activations=None):
    """Log-probabilities [S, T - keep_from, rows held] of the next token
    after each position from ``keep_from`` on, and {page layer: (c, k_r)}
    of the listed page layers (``2l + i``: sublayer ``i`` of layer ``l``)
    as ``[S, T, 1, kv_lora_rank]`` and ``[S, T, 1, qk_rope_head_dim]``,
    for right-padded ``tokens`` [S, T] of lengths ``lens``. ``hf`` holds
    the sizes under their published keys, the cut ones at what is held
    here."""
    if quantization is not None:
        raise ValueError(f"no reference for quantization {quantization!r}")
    if hf.get("attention_method", "MLA") != "MLA" or hf.get(
            "zero_expert_type", "identity") != "identity" or hf.get(
            "attention_bias") or hf.get("norm_topk_prob"):
        raise ValueError("the reference has the published block only: MLA, "
                         "identity zero experts, no biases, no "
                         "renormalisation of the top-k weights")
    layers, hidden = hf["num_layers"], hf["hidden_size"]
    eps, vocab = float(hf["rms_norm_eps"]), hf["vocab_size"]
    mla_dims = (hidden, hf["num_attention_heads"], hf["q_lora_rank"],
                hf["kv_lora_rank"], hf["qk_nope_head_dim"],
                hf["qk_rope_head_dim"], hf["v_head_dim"],
                bool(hf.get("mla_scale_q_lora")),
                bool(hf.get("mla_scale_kv_lora")),
                float(hf["rope_theta"]), eps, dtype)
    moe_dims = (hidden, hf["expert_ffn_hidden_size"], hf["n_routed_experts"],
                hf.get("chips_per_layer", 1), hf.get("layer_share", 0),
                hf.get("zero_expert_num", 0), hf["moe_topk"],
                float(hf.get("routed_scaling_factor", 1.0)), dtype)
    keys = split(seed_key(seed), 16)
    tokens = jnp.asarray(tokens, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = _rounded(_embed(keys[0], tokens, vocab=vocab, hidden=hidden,
                            dtype=dtype), activations)
        kept = {}
        for layer in range(layers):
            shortcut = None
            for i in range(2):
                at = 2 * layer + i
                a, c, k_r = _mla(keys, x, lens, at, dims=mla_dims,
                                 activations=activations)
                if at in kv_layers:
                    kept[at] = (np.asarray(c), np.asarray(k_r))
                x, h = _dense_mlp(
                    keys, a, at,
                    dims=(hidden, hf["ffn_hidden_size"], eps, dtype),
                    activations=activations)
                if i == 0:
                    shortcut = _moe(keys, h.reshape(-1, hidden), layer,
                                    dims=moe_dims, activations=activations
                                    ).reshape(h.shape)
            x = _rounded(x + shortcut, activations)
        logp = _logprobs(keys[1], x[:, keep_from:], vocab=vocab, eps=eps,
                         dtype=dtype, activations=activations)
    return np.asarray(logp), kept
