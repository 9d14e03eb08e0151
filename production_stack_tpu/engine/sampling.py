"""On-device batched sampling: greedy / temperature / top-k / top-p.

One jitted function with static batch width samples the whole decode batch:
per-sequence temperature, top-k, top-p and seeds are *data*, not trace
constants, so mixed sampling configs never recompile. Top-k/top-p operate on
the top ``max_top_k`` logits only (one ``lax.top_k``), which keeps the
sort lane-friendly and bounds VMEM.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from production_stack_tpu.structured.api import parse_structured


def _strict_int(body: dict, key: str) -> Optional[int]:
    """JSON-typed integer field: present -> must be an actual integer.
    ``int()`` coercion accepted "7.9", True and floats here before —
    the QoS admission estimator then charged the coerced value while
    the client believed the literal one (the PR 8 gaming surface)."""
    value = body.get(key)
    if value is None:
        return None
    if isinstance(value, bool) or not isinstance(value, int):
        raise ValueError(f"'{key}' must be an integer")
    return value


@dataclasses.dataclass
class SamplingParams:
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0  # 0 = disabled
    max_tokens: int = 16
    stop: Optional[list] = None
    seed: Optional[int] = None
    ignore_eos: bool = False
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    n: int = 1
    # None = no logprobs; an int = return the sampled token's logprob plus
    # that many top alternatives (raw log-softmax, OpenAI semantics).
    logprobs: Optional[int] = None
    # EOS is suppressed (logit-masked in the fused programs) until this
    # many output tokens exist — vLLM's min_tokens.
    min_tokens: int = 0
    # Extra token ids that finish the request like EOS (vLLM ext).
    stop_token_ids: Optional[list] = None
    # token id -> additive logit bias (OpenAI logit_bias; applied in the
    # fused programs, capped at MAX_LOGIT_BIAS entries).
    logit_bias: Optional[dict] = None
    # Completions-only: prepend the prompt text to the output.
    echo: bool = False
    # Structured output: a StructuredSpec (guided_json / guided_regex /
    # response_format), compiled by the engine to a token FSM whose mask
    # joins the in-program logit shaping.
    structured: Optional[object] = None

    @staticmethod
    def from_request(body: dict, default_max_tokens: int = 16) -> "SamplingParams":
        stop = body.get("stop")
        if isinstance(stop, str):
            stop = [stop]
        t = body.get("temperature")
        p = body.get("top_p")
        # completions: logprobs is an int (top-N); chat: logprobs is a
        # bool gated by top_logprobs (OpenAI schema).
        lp_raw = body.get("logprobs")
        if isinstance(lp_raw, bool):
            logprobs = (int(body.get("top_logprobs") or 0)
                        if lp_raw else None)
        elif lp_raw is None:
            logprobs = None
        else:
            logprobs = int(lp_raw)
        bias_raw = body.get("logit_bias") or {}
        if not isinstance(bias_raw, dict):
            raise ValueError("'logit_bias' must be an object")
        logit_bias = {}
        for k, v in bias_raw.items():
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                raise ValueError(
                    "'logit_bias' values must be numbers")
            try:
                logit_bias[int(k)] = float(v)
            except (TypeError, ValueError):
                raise ValueError(
                    "'logit_bias' keys must be token ids")
        structured = parse_structured(body)
        min_tokens = _strict_int(body, "min_tokens") or 0
        if structured is not None and min_tokens > 0:
            # The grammar dictates termination: in a completed FSM state
            # only EOS is legal, while min_tokens masks EOS — the two
            # constraints are jointly unsatisfiable in-program.
            raise ValueError(
                "'min_tokens' is incompatible with structured output")
        return SamplingParams(
            temperature=1.0 if t is None else float(t),
            top_p=1.0 if p is None else float(p),
            top_k=int(body.get("top_k") or 0),
            max_tokens=(
                _strict_int(body, "max_tokens")
                or _strict_int(body, "max_completion_tokens")
                or default_max_tokens
            ),
            stop=stop,
            seed=body.get("seed"),
            ignore_eos=bool(body.get("ignore_eos", False)),
            presence_penalty=float(body.get("presence_penalty") or 0.0),
            frequency_penalty=float(body.get("frequency_penalty") or 0.0),
            n=max(int(body.get("n") or 1), 1),
            logprobs=logprobs,
            min_tokens=min_tokens,
            stop_token_ids=[int(t) for t in
                            (body.get("stop_token_ids") or [])] or None,
            logit_bias=logit_bias or None,
            echo=bool(body.get("echo", False)),
            structured=structured,
        )


@functools.partial(jax.jit, static_argnames=("max_top_k",))
def sample_tokens(
    logits: jax.Array,       # [B, V] float32
    rng_keys: jax.Array,     # [B, 2] uint32 (one PRNG key per sequence)
    temperature: jax.Array,  # [B] float32; <=0 means greedy
    top_k: jax.Array,        # [B] int32; 0 disables
    top_p: jax.Array,        # [B] float32
    *,
    max_top_k: int = 64,
) -> jax.Array:
    """Return sampled token ids [B]."""
    B, V = logits.shape
    greedy_ids = jnp.argmax(logits, axis=-1)

    # Work on the top max_top_k candidates only.
    top_vals, top_idx = jax.lax.top_k(logits, max_top_k)  # [B, K]
    K = max_top_k
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = top_vals / temp

    # Per-sequence top-k mask (0 = disabled = keep all K candidates).
    ranks = jnp.arange(K)[None, :]
    k_eff = jnp.where(top_k[:, None] <= 0, K, jnp.minimum(top_k[:, None], K))
    keep_k = ranks < k_eff

    # Top-p (nucleus) mask over the sorted candidates.
    probs = jax.nn.softmax(jnp.where(keep_k, scaled, -jnp.inf), axis=-1)
    cumprobs = jnp.cumsum(probs, axis=-1)
    keep_p = (cumprobs - probs) < top_p[:, None]  # always keeps rank 0
    masked = jnp.where(keep_k & keep_p, scaled, -jnp.inf)

    def sample_one(key, row):
        return jax.random.categorical(key, row)

    choice = jax.vmap(sample_one)(rng_keys, masked)  # [B] in [0, K)
    sampled_ids = jnp.take_along_axis(top_idx, choice[:, None], axis=-1)[:, 0]
    return jnp.where(temperature <= 0.0, greedy_ids, sampled_ids)


# Sparse logit_bias capacity baked into the serving programs (OpenAI caps
# requests at 300 entries; 32 covers real use — requests exceeding it are
# rejected with a 400 at the API layer rather than silently truncated).
MAX_LOGIT_BIAS = 32

# stop_token_ids capacity in the serving programs (masked alongside EOS
# while min_tokens is unmet, vLLM semantics).
MAX_STOP_IDS = 8


# Structured-output FSM mask: finite large-negative (like the stop-id
# term) so temperature scaling can't produce NaNs the way -inf can.
FSM_MASK_NEG = -1e30


def apply_fsm_mask(logits: jax.Array, mask_bits: jax.Array,
                   mask_on: jax.Array) -> jax.Array:
    """Dense packed-bitmask grammar term for the fused programs.

    ``mask_bits`` is ``uint8 [B, ceil(V/8)]`` with bit ``v`` of row
    ``b`` (little bitorder, ``numpy.packbits`` layout) = token ``v``
    allowed; ``mask_on [B] bool`` gates rows so unconstrained sequences
    pass through bit-identically. Dense rather than sparse: a grammar
    state routinely allows hundreds of tokens, far past the
    ``MAX_LOGIT_BIAS`` sparse capacity, and the packed row is only
    ``V/8`` bytes of host->device traffic. A data-shaped input, so
    adding it compiles zero new program variants."""
    V = logits.shape[-1]
    B, MB = mask_bits.shape
    # Shift-and-reshape unpack (no gather): byte v//8 bit v%8 -> token v.
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (mask_bits[:, :, None] >> shifts[None, None, :]) & jnp.uint8(1)
    bits = bits.reshape(B, MB * 8)[:, :V]
    allowed = (bits != 0) | (~mask_on)[:, None]
    return jnp.where(allowed, logits, FSM_MASK_NEG)


# Static top-K for logprob outputs baked into the serving programs
# (requests clamp their top_logprobs to this; computing it always costs
# ~nothing next to the forward, so no recompile per request).
LOGPROB_K = 8


def logprob_outputs(logits: jax.Array, sampled: jax.Array,
                    k: int = LOGPROB_K):
    """Raw log-softmax stats for the OpenAI logprobs surface:
    (chosen_lp [B], top_lp [B, k], top_ids [B, k])."""
    lse = jax.scipy.special.logsumexp(
        logits.astype(jnp.float32), axis=-1, keepdims=True)
    lp = logits.astype(jnp.float32) - lse
    chosen = jnp.take_along_axis(lp, sampled[:, None], axis=-1)[:, 0]
    top_lp, top_ids = jax.lax.top_k(lp, k)
    return chosen, top_lp, top_ids


def accepted_prefix_len(draft, sampled_row) -> int:
    """Speculative-verify acceptance: number of draft tokens accepted.

    ``sampled_row[s]`` is what the verify program sampled at draft
    position ``s`` using the SAME rng key / logit shaping the plain
    decode scan would use for that step — so a draft token is correct
    exactly when it equals that sample, and the longest matching prefix
    is the set of drafts whose acceptance keeps the emitted stream
    identical to non-speculative decoding. The caller emits
    ``sampled_row[:j + 1]`` (the ``j`` accepted drafts ARE those
    samples, plus the first mismatch as the corrected/bonus token)."""
    j = 0
    for d in draft:
        if int(sampled_row[j]) != int(d):
            break
        j += 1
    return j


def make_rng_keys(seed: int, step, seq_seeds: jax.Array) -> jax.Array:
    """Per-sequence PRNG keys derived from (engine seed, step, seq seed);
    ``step`` is one number for all sequences or one a sequence."""
    base = jax.random.key(seed)

    def per_seq(st, s):
        return jax.random.key_data(
            jax.random.fold_in(jax.random.fold_in(base, st), s))

    return jax.vmap(per_seq, in_axes=(0 if jnp.ndim(step) else None, 0))(
        step, seq_seeds)
