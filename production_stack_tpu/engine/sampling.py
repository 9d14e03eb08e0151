"""On-device batched sampling: greedy / temperature / top-k / top-p, the
logit shaping before it and the logprob outputs beside it.

Per-sequence temperature, top-k, top-p, seeds, ``logit_bias``, stop ids,
penalties and the structured mask are *data*, not trace constants, so
mixed sampling configs never recompile, and every step computes all of
it for all ``B`` rows whether or not a request asked. At one live row of a
65,536-entry vocabulary that is not "nothing next to the forward": on a
v5e the ``sample`` scope of ``lfm2-sessions`` took 610 us of a 3.15 ms
decode step (the head's own 268 MB another 359 us; PERF.md section 6,
PR 49), because a step passed over its ``[B, V]`` logits a dozen times.
So the tail of a step is held to this shape:

- what a burst holds constant is built once a burst (``burst_terms``:
  the bias, the stop ids and the mask in dense ``[B, V]`` forms, before
  the step loop), and a step applies it in ONE element-wise pass
  (``shape_logits``), which the compiler fuses behind the head's dot;
- ONE selection, the ``max(max_top_k, LOGPROB_K)`` best in order
  (``sample_with_logprobs``), answers the greedy token, the sampler's
  candidates and the top logprobs; top-k/top-p operate on those
  candidates only, which keeps the sort lane-friendly and bounds VMEM.
  The chip's ``TopK`` of 64 over the whole row was 460 of those 610 us,
  so the selection reads the row once for a maximum a group of 128 ids
  and hands ``TopK`` the 64 best groups' candidates (``exact_top_k``:
  the same list to the bit and to the tie);
- ONE reduction, the log-sum-exp's sum (its maximum is the selection's
  rank 0);
- the only scatter left in a step is the penalty counts' one entry a row.

``tests/test_sampling_tail.py`` holds this tail to the one it replaced bit
for bit; ``tests/test_chip_compile.py`` holds the compiled step loop to
a ``TopK`` of candidates, one scatter and one float32 ``[B, V]``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

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


# Static top-K for logprob outputs baked into the serving programs
# (requests clamp their top_logprobs to this, so no recompile per request).
# The outputs are computed for every row of every step whether or not a
# request asked: they ride on the sampler's one selection and the one
# log-sum-exp reduction, see ``sample_with_logprobs``.
LOGPROB_K = 8

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

# What a suppressed stop id's logit is lowered by (finite: ``-inf * 0``
# padding would make NaNs).
STOP_ID_NEG = -1e30


def allowed_tokens(mask_bits: jax.Array, mask_on: jax.Array,
                   vocab: int) -> jax.Array:
    """The structured-output mask unpacked: ``bool [B, vocab]``, token
    ``v`` of row ``b`` allowed.

    ``mask_bits`` is ``uint8 [B, ceil(V/8)]`` with bit ``v`` of row
    ``b`` (little bitorder, ``numpy.packbits`` layout) = token ``v``
    allowed; ``mask_on [B] bool`` gates rows so unconstrained sequences
    allow everything. Dense rather than sparse: a grammar state
    routinely allows hundreds of tokens, far past the ``MAX_LOGIT_BIAS``
    sparse capacity, and the packed row is only ``V/8`` bytes of
    host->device traffic. A data-shaped input, so adding it compiles
    zero new program variants."""
    B, MB = mask_bits.shape
    # Shift-and-reshape unpack (no gather): byte v//8 bit v%8 -> token v.
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (mask_bits[:, :, None] >> shifts[None, None, :]) & jnp.uint8(1)
    bits = bits.reshape(B, MB * 8)[:, :vocab]
    return (bits != 0) | (~mask_on)[:, None]


def apply_fsm_mask(logits: jax.Array, mask_bits: jax.Array,
                   mask_on: jax.Array) -> jax.Array:
    """``logits`` with the tokens the grammar forbids at ``FSM_MASK_NEG``;
    rows with ``mask_on`` False pass through bit-identically."""
    allowed = allowed_tokens(mask_bits, mask_on, logits.shape[-1])
    return jnp.where(allowed, logits, FSM_MASK_NEG)


class BurstTerms(NamedTuple):
    """What a burst holds constant of a request's logit shaping, in the
    dense ``[B, V]`` forms a step applies element-wise."""
    bias: jax.Array     # f32: the row's logit_bias, -0.0 where it has none
    stops: jax.Array    # u8: how many of the row's stop ids name the token
    allowed: jax.Array  # bool: ``allowed_tokens``


def burst_terms(vocab: int, bias_ids, bias_vals, stop_ids, stop_valid,
                mask_bits, mask_on) -> BurstTerms:
    """The sparse ``logit_bias`` (``[B, MAX_LOGIT_BIAS]`` ids and values,
    padded with id 0 / 0.0) and ``stop_token_ids`` (``[B, MAX_STOP_IDS]``
    ids, ``stop_valid`` 1.0 / 0.0) and the packed structured mask, each
    scattered or unpacked to ``[B, vocab]`` ONCE: they are arguments of a
    burst, not of a step (the host advances a grammar's automaton only
    between bursts), so the step loop calls this before ``lax.scan`` and
    its body holds no scatter and no unpack of them. The bias starts
    from -0.0, the one float that changes no bit of what it is added to,
    so ``x + bias`` is ``x`` wherever a row has no entry and ``x + v``
    where it has one, as the in-place scatter gave (ids are distinct:
    they are a dict's keys)."""
    rows = jnp.arange(bias_ids.shape[0])[:, None]
    bias = jnp.full((bias_ids.shape[0], vocab), -0.0, jnp.float32)
    bias = bias.at[rows, bias_ids].add(bias_vals)
    stops = jnp.zeros((stop_ids.shape[0], vocab), jnp.uint8)
    stops = stops.at[rows, stop_ids].add(stop_valid.astype(jnp.uint8))
    # Behind a barrier: what a loop holds constant and could fuse into its
    # body the compiler sinks into the loop again (the mask's unpack, with
    # its transposing copy, in every step).
    return jax.lax.optimization_barrier(BurstTerms(
        bias, stops, allowed_tokens(mask_bits, mask_on, vocab)))


def apply_penalties(raw: jax.Array, counts: jax.Array,
                    frequency_penalty: jax.Array,
                    presence_penalty: jax.Array) -> jax.Array:
    """OpenAI presence / frequency penalties (``[B]`` each) over the
    slot's OUTPUT tokens so far (``counts [B, V]`` int32)."""
    return (raw - frequency_penalty[:, None] * counts
            - presence_penalty[:, None] * (counts > 0))


def shape_logits(logits: jax.Array, terms: BurstTerms, suppress: jax.Array,
                 eos_id: int) -> jax.Array:
    """One element-wise pass from ``[B, V]`` float32 logits (the head's,
    penalised where the program keeps counts) to the distribution a step
    samples from and reports logprobs of (OpenAI/vLLM post-processor
    semantics), in this order: ``logit_bias``, then while ``suppress
    [B]`` (min_tokens unmet) EOS at -inf and the stop ids lowered by
    ``STOP_ID_NEG`` each, then the structured mask. ``eos_id`` < 0: the
    tokenizer has none."""
    shaped = logits + terms.bias
    if eos_id >= 0:
        shaped = jnp.where(
            suppress[:, None]
            & (jnp.arange(shaped.shape[1])[None, :] == eos_id),
            -jnp.inf, shaped)
    # 0 stops or not suppressed: + -0.0, which changes no bit
    shaped = shaped + STOP_ID_NEG * (
        terms.stops * suppress[:, None]).astype(jnp.float32)
    return jnp.where(terms.allowed, shaped, FSM_MASK_NEG)


# A vocabulary is selected from in groups of this many consecutive ids:
# one row of lanes of a float32 tile.
TOP_K_GROUP = 128


def exact_top_k(logits: jax.Array, k: int):
    """``lax.top_k(logits, k)`` over ``[B, V]``, values and ids, to the
    bit and to the tie (of equal values the lower id first), at a
    fraction of its cost where the vocabulary is wide. On a v5e the
    TPU's ``TopK`` of the 64 best of ``[32, 65536]`` takes 460 us (of
    8 there 62 us, of 64 of ``[32, 8192]`` 159 us, of ``[32, 512]``
    nothing that shows): three quarters of what the ``sample`` scope of
    ``lfm2-sessions`` cost (PERF.md section 6, PR 49). So it is handed
    ``k x 128`` candidates a row instead of ``V``.

    The ids are cut into groups of ``TOP_K_GROUP`` consecutive ones.
    The ``k`` groups with the largest maxima (ties to the lower group)
    hold the whole answer: an element of any other group is preceded,
    in the order (value falling, id rising), by the maximum of each of
    those ``k`` groups (a larger value, or an equal one at a lower id,
    since a lower group is lower ids). So: one max a group, ``top_k`` of
    the maxima, the chosen groups gathered in the vocabulary's order
    (which keeps ties among candidates on the lower id), ``top_k`` of
    the ``k x 128`` candidates, and their positions mapped back to ids
    (by comparison with the ``k`` chosen groups: a gather of ``B x k``
    single elements is a loop on the chip, 97 us at ``B`` 32). A ``V``
    that is no multiple of the group is padded with -inf, which no id
    of the vocabulary loses a tie to. Where the groups are fewer than
    ``2 k`` the candidates would be the row: plain ``lax.top_k``."""
    B, V = logits.shape
    groups = -(-V // TOP_K_GROUP)
    if groups < 2 * k:
        return jax.lax.top_k(logits, k)
    rows = jnp.pad(logits, ((0, 0), (0, groups * TOP_K_GROUP - V)),
                   constant_values=-jnp.inf).reshape(B, groups, TOP_K_GROUP)
    _, chosen = jax.lax.top_k(rows.max(axis=-1), k)
    chosen = jnp.sort(chosen, axis=-1)
    candidates = jnp.take_along_axis(rows, chosen[:, :, None], axis=1)
    vals, at = jax.lax.top_k(candidates.reshape(B, k * TOP_K_GROUP), k)
    of_group = (at // TOP_K_GROUP)[:, :, None] == jnp.arange(k)
    group = jnp.sum(jnp.where(of_group, chosen[:, None, :], 0), axis=-1)
    return vals, group * TOP_K_GROUP + at % TOP_K_GROUP


@functools.partial(jax.jit, static_argnames=("max_top_k",))
def sample_with_logprobs(
    logits: jax.Array,       # [B, V] float32, shaped
    rng_keys: jax.Array,     # [B, 2] uint32 (one PRNG key per sequence)
    temperature: jax.Array,  # [B] float32; <=0 means greedy
    top_k: jax.Array,        # [B] int32; 0 disables
    top_p: jax.Array,        # [B] float32
    *,
    max_top_k: int = 64,
):
    """``(sampled [B], chosen_lp [B], top_lp [B, LOGPROB_K], top_ids
    [B, LOGPROB_K])``: the sampled token ids and the raw log-softmax
    stats of the OpenAI logprobs surface, from ONE selection and ONE
    reduction over ``[B, V]``.

    The selection is the ``max(max_top_k, LOGPROB_K)`` best in order
    (``exact_top_k``: ``lax.top_k``'s answer, from the candidates of the
    chosen groups where the vocabulary is wide), a list that answers
    three consumers. Its rank 0 is the
    greedy token. Its first ``max_top_k`` are the sampler's candidates.
    Its first ``LOGPROB_K`` values less the log-sum-exp, and their ids,
    are the top logprobs: ``x - lse`` is monotone in ``x`` and is the
    same float subtraction on the same operands as a log-softmax
    written out at ``[B, V]`` and selected from, so the values are
    those bit for bit (two logits that round to ONE logprob keep the
    logits' order here, where a selection from the rounded values
    ordered them by id). The reduction is the log-sum-exp's sum of
    exponentials, stabilised by the selection's rank 0 (the row's
    maximum), as ``jax.scipy.special.logsumexp`` stabilises it by a
    ``max`` pass of its own. The chosen token's logprob is one gathered
    logit a row less the same ``lse``.

    **Exact ties.** ``lax.top_k`` is stable: of equal values the lower
    id comes first (JAX's contract; the ``TopK`` custom call of the
    TPU compiler keeps it, read on a v5e at ``[32, 65536]`` with the
    maximum tied 2 to 4,096 times, ``benchmarks/sampling_tail_step0.py``,
    PR 49), and ``exact_top_k`` keeps that order through its groups.
    It is ``argmax``'s rule, so rank 0 is the ``argmax`` token;
    ``tests/test_sampling_tail.py`` holds both."""
    # [B, K], best first. Behind barriers: the compiler makes a top-k of
    # a sort and a slice and then looks for that pair to put its ``TopK``
    # in; a consumer's slice of the list, merged into the pair's, hides
    # it, and the whole row is sorted. (The temperatures ride along so
    # that the barrier takes the top-k's two results apart: as the only
    # user of the pair it is something the CPU's partitioner aborts on,
    # and one barrier an array hides the pair again.)
    top_vals, top_idx, temperature = jax.lax.optimization_barrier(
        (*exact_top_k(logits, max(max_top_k, LOGPROB_K)), temperature))
    greedy_ids = top_idx[:, 0]

    # Top-k/top-p work on the top max_top_k candidates only.
    K = max_top_k
    cand_vals, cand_idx = top_vals[:, :K], top_idx[:, :K]
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = cand_vals / temp

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
    sampled_ids = jnp.take_along_axis(cand_idx, choice[:, None], axis=-1)[:, 0]
    sampled = jnp.where(temperature <= 0.0, greedy_ids, sampled_ids)

    # log-sum-exp as jax.scipy.special.logsumexp computes it, its
    # maximum read off the selection
    amax = top_vals[:, :1]
    amax = jnp.where(jnp.isfinite(amax), amax, 0)
    lse = jnp.log(jnp.sum(jnp.exp(logits - amax), axis=-1,
                          keepdims=True)) + amax
    chosen = jnp.take_along_axis(logits, sampled[:, None], axis=-1) - lse
    return (sampled, chosen[:, 0], top_vals[:, :LOGPROB_K] - lse,
            top_idx[:, :LOGPROB_K])


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
