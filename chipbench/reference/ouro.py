"""Plain float32 reference of the Ouro looped decoder (``model_type:
ouro``; ByteDance/Ouro-2.6B, arXiv:2510.25741).

Straight ``jax.numpy``: ``total_ut_steps`` full passes over each whole
sequence, full causal attention, no cache, no kernels, no batching,
every matmul at ``highest`` precision. It takes nothing the program
made: the weights are drawn here from the seed with this package's copy
of the counter-based generator (``reference/llama.py``) and this file's
own copy of the program's init recipe (``models/ouro.py::init_params``:
sixteen keys split from the seed; 0-8 Llama's nine, 9 the final norm,
10-13 a layer's four norms, 14 and 15 the exit gate), one layer at a
time and again in every pass, so the 48 layers never exist twice on the
device. The equations, each ``assumed`` item at its line::

    h_0 = E[tokens]
    pass u = 0 .. U-1, the same weights:   x = h_u
        layer l:  a = x + RMS(Attn_l(RMS(x; n1)); n2)
                  x = a + RMS(SwiGLU_l(RMS(a; n3)); n4)
        h_{u+1} = RMS(x; norm_f)
    logits = h_U W_head                      (no norm again)

Pass ``u`` attends over the keys and values of pass ``u`` alone (here:
over the sequence it is computing, which is the same thing). The keys
and values it returns are numbered as the program numbers its page
layers: pass ``u`` of layer ``l`` is ``l x U + u``.

``quantization="int8"`` and ``activations`` as ``reference/llama.py``
has them (the norms' weights stay as drawn: int8 takes the matrices).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.llama import (
    HIGHEST,
    _embed,
    _matrix,
    _mm,
    _rounded,
    _served,
    normal_rows,
    rope,
    seed_key,
    split,
)

SPREAD = 0.1  # of a norm's weight around one, of the gate's bias around 0
FINAL_NORM, NORMS, GATE_W, GATE_B = 9, (10, 11, 12, 13), 14, 15


def _near_one(key, offset, n, dtype):
    """Elements ``offset .. offset + n`` of a norm weight's leaf: ``1 +
    0.1 normal``, rounded to the served dtype."""
    return (1.0 + SPREAD * normal_rows(key, offset, n)).astype(
        jnp.dtype(dtype)).astype(jnp.float32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


@functools.partial(jax.jit,
                   static_argnames=("dims", "quantization", "activations"))
def _layer(keys, layer, x, lens, *, dims, quantization, activations=None):
    """One sandwich-normed layer on x [S, T, hidden]; returns (x, k, v)
    with the keys after the rotary embedding, as the cache holds them."""
    hidden, heads, kv_heads, head_dim, inter, theta, eps, dtype = dims
    S, T, _ = x.shape
    mat = functools.partial(_matrix, layer=layer, dtype=jnp.dtype(dtype))
    n1, n2, n3, n4 = (_near_one(keys[i], layer * hidden, hidden, dtype)
                      for i in NORMS)
    q_out, kv_out = heads * head_dim, kv_heads * head_dim
    # assumed: no bias on any projection.
    wq = _served(mat(keys[1], shape=(hidden, q_out), fan_in=hidden),
                 quantization)
    wk = _served(mat(keys[2], shape=(hidden, kv_out), fan_in=hidden),
                 quantization)
    wv = _served(mat(keys[3], shape=(hidden, kv_out), fan_in=hidden),
                 quantization)
    wo = _served(mat(keys[4], shape=(q_out, hidden), fan_in=q_out),
                 quantization)
    # The token's own position in every pass.
    positions = jnp.broadcast_to(jnp.arange(T), (S, T))
    r = functools.partial(_rounded, activations=activations)
    h = r(rms_norm(x, n1, eps))
    q = r(rope(_mm(h, wq).reshape(S, T, heads, head_dim), positions, theta))
    k = r(rope(_mm(h, wk).reshape(S, T, kv_heads, head_dim), positions,
               theta))
    v = r(_mm(h, wv).reshape(S, T, kv_heads, head_dim))
    group = heads // kv_heads
    qg = q.reshape(S, T, kv_heads, group, head_dim)
    scores = jnp.einsum("stkgd,sukd->skgtu", qg, k,
                        precision=HIGHEST) / math.sqrt(head_dim)
    t = jnp.arange(T)
    mask = (t[None, :] <= t[:, None])[None] & (
        t[None, None, :] < lens[:, None, None])
    scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
    attn = r(jnp.einsum("skgtu,sukd->stkgd", jax.nn.softmax(scores, -1), v,
                        precision=HIGHEST).reshape(S, T, q_out))
    # assumed: the sublayer's output is normed before the residual.
    a = r(x + r(rms_norm(r(_mm(attn, wo)), n2, eps)))
    w_gate = _served(mat(keys[5], shape=(hidden, inter), fan_in=hidden),
                     quantization)
    w_up = _served(mat(keys[6], shape=(hidden, inter), fan_in=hidden),
                   quantization)
    h = r(rms_norm(a, n3, eps))
    act = r(jax.nn.silu(_mm(h, w_gate)) * _mm(h, w_up))
    del w_gate, w_up
    w_down = _served(mat(keys[7], shape=(inter, hidden), fan_in=inter),
                     quantization)
    return r(a + r(rms_norm(r(_mm(act, w_down)), n4, eps))), k, v


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "activations"))
def _close(key, x, *, eps, dtype, activations=None):
    """assumed: the model's final norm, at the end of every pass."""
    weight = _near_one(key, jnp.uint32(0), x.shape[-1], dtype)
    return _rounded(rms_norm(x, weight, eps), activations)


@functools.partial(jax.jit, static_argnames=("vocab", "dtype"))
def _logprobs(key, x, *, vocab, dtype):
    """assumed: the head applies no norm of its own."""
    hidden = x.shape[-1]
    head = _matrix(key, jnp.uint32(0), (hidden, vocab), hidden,
                   jnp.dtype(dtype)).astype(jnp.float32)
    return jax.nn.log_softmax(_mm(x, head), axis=-1)


def _dims(hf: dict, dtype: str):
    for key, served in (("hidden_act", "silu"), ("attention_bias", False),
                        ("use_sliding_window", False), ("rope_scaling", None),
                        ("tie_word_embeddings", False)):
        if hf.get(key, served) != served:
            raise ValueError(f"the reference has no path for {key} "
                             f"{hf[key]!r}")
    hidden, heads = hf["hidden_size"], hf["num_attention_heads"]
    return (hidden, heads, hf.get("num_key_value_heads", heads),
            hf.get("head_dim") or hidden // heads, hf["intermediate_size"],
            float(hf.get("rope_theta", 10000.0)),
            float(hf.get("rms_norm_eps", 1e-6)), dtype)


def forward(hf: dict, seed: int, tokens, lens, *, keep_from: int,
            quantization=None, dtype="bfloat16", kv_layers=(0,),
            activations=None, states: bool = False):
    """Log-probabilities [S, T - keep_from, vocab] of the next token
    after each position from ``keep_from`` on, and {page layer: (k, v)}
    of the listed page layers (``l x total_ut_steps + u``), for
    right-padded ``tokens`` [S, T] of lengths ``lens``. ``hf`` holds the
    sizes under their published keys. ``states``: the passes' closing
    states ``[U, S, T, hidden]`` (what the exit gate reads) in place of
    the log-probabilities."""
    dims = _dims(hf, dtype)
    hidden, eps, vocab = dims[0], dims[6], hf["vocab_size"]
    passes = int(hf.get("total_ut_steps", 1))
    keys = split(seed_key(seed), 16)
    tokens, lens = jnp.asarray(tokens, jnp.int32), jnp.asarray(lens,
                                                               jnp.int32)
    x = _rounded(_embed(keys[0], tokens, vocab=vocab, hidden=hidden,
                        dtype=dtype), activations)
    kept, closing = {}, []
    for u in range(passes):
        for layer in range(hf["num_hidden_layers"]):
            x, k, v = _layer(keys, jnp.uint32(layer), x, lens, dims=dims,
                             quantization=quantization,
                             activations=activations)
            if layer * passes + u in kv_layers:
                kept[layer * passes + u] = (np.asarray(k), np.asarray(v))
        x = _close(keys[FINAL_NORM], x, eps=eps, dtype=dtype,
                   activations=activations)
        if states:
            closing.append(np.asarray(x))
    if states:
        return np.stack(closing), kept
    logp = _logprobs(keys[8], x[:, keep_from:], vocab=vocab, dtype=dtype)
    return np.asarray(logp), kept


def exit_pdf(hf: dict, seed: int, states, *, dtype="bfloat16"):
    """The exit gate over the passes' closing states ``[U, ..., hidden]``
    (assumed: a ``hidden_size -> 1`` linear map with a bias): (``p [U,
    ...]``, the first pass at which the running sum of ``p`` reaches
    ``early_exit_threshold``), by a loop over the passes in float64."""
    hidden = hf["hidden_size"]
    keys = split(seed_key(seed), 16)
    w = np.asarray((normal_rows(keys[GATE_W], jnp.uint32(0), hidden)
                    / jnp.sqrt(jnp.float32(hidden))).astype(jnp.dtype(dtype))
                   .astype(jnp.float32), np.float64)
    b = float(SPREAD * normal_rows(keys[GATE_B], jnp.uint32(0), 1)[0])
    states = np.asarray(states, np.float64)
    threshold = float(hf.get("early_exit_threshold", 1.0))
    passes = states.shape[0]
    stay = np.ones(states.shape[1:-1])
    total = np.zeros_like(stay)
    pdf, leaves = [], np.full(stay.shape, passes - 1)
    for u in range(passes):
        lam = 1.0 / (1.0 + np.exp(-(states[u] @ w + b)))
        p = stay * lam if u < passes - 1 else stay
        stay = stay * (1.0 - lam)
        reached = (total < threshold) & (total + p >= threshold)
        leaves = np.where(reached, u, leaves)
        total = total + p
        pdf.append(p)
    return np.stack(pdf), leaves
