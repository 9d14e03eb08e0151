"""The tail of a decode step, from the head's ``[B, V]`` logits to a token
and its logprobs a row, against the tail it replaced (PR 49).

``_reference_burst`` is the tail as ``engine/core.py::_make_multi_decode``
ran it until PR 49, kept here line for line: in EVERY step three scatters
into ``[B, V]`` (``logit_bias``, the stop ids, the penalty counts), the
structured mask unpacked anew, an ``argmax``, a ``top_k`` of 64 for the
sampler, a log-softmax written out at ``[B, V]`` and a second ``top_k``
over it. ``_burst`` is the engine's tail now: what a burst holds constant
built once before the scan (``sampling.burst_terms``), one element-wise
pass (``shape_logits``), one selection and one reduction a step
(``sample_with_logprobs``). Same mathematics: tokens, logprob values and
ids equal bit for bit over an 8-step burst, one case a request feature.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine import sampling
from production_stack_tpu.engine.sampling import (
    FSM_MASK_NEG,
    LOGPROB_K,
    MAX_LOGIT_BIAS,
    MAX_STOP_IDS,
    make_rng_keys,
)

B, V, K, EOS, SEED = 6, 320, 8, 2, 7


# -- the tail until PR 49 ----------------------------------------------

def _old_sample_tokens(logits, rng_keys, temperature, top_k, top_p,
                       max_top_k):
    greedy_ids = jnp.argmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(logits, max_top_k)
    Kc = max_top_k
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = top_vals / temp
    ranks = jnp.arange(Kc)[None, :]
    k_eff = jnp.where(top_k[:, None] <= 0, Kc,
                      jnp.minimum(top_k[:, None], Kc))
    keep_k = ranks < k_eff
    probs = jax.nn.softmax(jnp.where(keep_k, scaled, -jnp.inf), axis=-1)
    cumprobs = jnp.cumsum(probs, axis=-1)
    keep_p = (cumprobs - probs) < top_p[:, None]
    masked = jnp.where(keep_k & keep_p, scaled, -jnp.inf)
    choice = jax.vmap(jax.random.categorical)(rng_keys, masked)
    sampled_ids = jnp.take_along_axis(top_idx, choice[:, None], axis=-1)[:, 0]
    return jnp.where(temperature <= 0.0, greedy_ids, sampled_ids)


def _old_apply_fsm_mask(logits, mask_bits, mask_on):
    rows, MB = mask_bits.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (mask_bits[:, :, None] >> shifts[None, None, :]) & jnp.uint8(1)
    bits = bits.reshape(rows, MB * 8)[:, :logits.shape[-1]]
    allowed = (bits != 0) | (~mask_on)[:, None]
    return jnp.where(allowed, logits, FSM_MASK_NEG)


def _old_logprob_outputs(logits, sampled, k=LOGPROB_K):
    lse = jax.scipy.special.logsumexp(
        logits.astype(jnp.float32), axis=-1, keepdims=True)
    lp = logits.astype(jnp.float32) - lse
    chosen = jnp.take_along_axis(lp, sampled[:, None], axis=-1)[:, 0]
    top_lp, top_ids = jax.lax.top_k(lp, k)
    return chosen, top_lp, top_ids


@functools.partial(jax.jit, static_argnames=("max_top_k",))
def _reference_burst(logits, counts, slots, temperature, top_k, top_p,
                     seed_base, presence_penalty, frequency_penalty,
                     min_tokens, out_len0, bias_ids, bias_vals, stop_ids,
                     stop_valid, mask_bits, mask_on, *, max_top_k):
    rows = logits.shape[1]

    def body(carry, xs):
        counts, s = carry
        raw, step_slots = xs
        penalized = (
            raw
            - frequency_penalty[:, None] * counts
            - presence_penalty[:, None] * (counts > 0)
        )
        penalized = penalized.at[
            jnp.arange(rows)[:, None], bias_ids].add(bias_vals)
        suppress = (out_len0 + s) < min_tokens
        penalized = jnp.where(
            suppress[:, None]
            & (jnp.arange(penalized.shape[1])[None, :] == EOS),
            -jnp.inf, penalized)
        penalized = penalized.at[
            jnp.arange(rows)[:, None], stop_ids].add(
            -1e30 * stop_valid * suppress.astype(jnp.float32)[:, None])
        penalized = _old_apply_fsm_mask(penalized, mask_bits, mask_on)
        keys = make_rng_keys(SEED, 0, seed_base + s)
        sampled = _old_sample_tokens(
            penalized, keys, temperature, top_k, top_p, max_top_k)
        lp, top_lp, top_ids = _old_logprob_outputs(penalized, sampled)
        live = (step_slots >= 0).astype(jnp.int32)
        counts = counts.at[jnp.arange(rows), sampled].add(live)
        return (counts, s + 1), (sampled, lp, top_lp, top_ids)

    (counts, _), outs = jax.lax.scan(
        body, (counts, jnp.int32(0)), (logits, slots.T))
    return outs, counts


# -- the engine's tail ---------------------------------------------------

@functools.partial(jax.jit, static_argnames=("max_top_k",))
def _burst(logits, counts, slots, temperature, top_k, top_p, seed_base,
           presence_penalty, frequency_penalty, min_tokens, out_len0,
           bias_ids, bias_vals, stop_ids, stop_valid, mask_bits, mask_on,
           *, max_top_k):
    rows = logits.shape[1]
    terms = sampling.burst_terms(
        logits.shape[2], bias_ids, bias_vals, stop_ids, stop_valid,
        mask_bits, mask_on)

    def body(carry, xs):
        counts, s = carry
        raw, step_slots = xs
        shaped = sampling.shape_logits(
            sampling.apply_penalties(
                raw, counts, frequency_penalty, presence_penalty),
            terms, (out_len0 + s) < min_tokens, EOS)
        keys = make_rng_keys(SEED, 0, seed_base + s)
        sampled, lp, top_lp, top_ids = sampling.sample_with_logprobs(
            shaped, keys, temperature, top_k, top_p, max_top_k=max_top_k)
        live = (step_slots >= 0).astype(jnp.int32)
        counts = counts.at[jnp.arange(rows), sampled].add(live)
        return (counts, s + 1), (sampled, lp, top_lp, top_ids)

    (counts, _), outs = jax.lax.scan(
        body, (counts, jnp.int32(0)), (logits, slots.T))
    return outs, counts


# -- cases -----------------------------------------------------------------

def _inputs(rows=B, vocab=V, seed=11, **over):
    """A burst's arguments with no feature asked for (the benchmark's
    traffic: temperature 1.0 and a seed); a case overrides what it tests."""
    rng = np.random.default_rng(seed)
    args = dict(
        logits=(3.0 * rng.standard_normal((K, rows, vocab))).astype(
            np.float32),
        counts=np.zeros((rows, vocab), np.int32),
        slots=np.arange(rows * K, dtype=np.int32).reshape(rows, K),
        temperature=np.ones((rows,), np.float32),
        top_k=np.zeros((rows,), np.int32),
        top_p=np.ones((rows,), np.float32),
        seed_base=np.arange(100, 100 + rows, dtype=np.int32),
        presence_penalty=np.zeros((rows,), np.float32),
        frequency_penalty=np.zeros((rows,), np.float32),
        min_tokens=np.zeros((rows,), np.int32),
        out_len0=np.zeros((rows,), np.int32),
        bias_ids=np.zeros((rows, MAX_LOGIT_BIAS), np.int32),
        bias_vals=np.zeros((rows, MAX_LOGIT_BIAS), np.float32),
        stop_ids=np.zeros((rows, MAX_STOP_IDS), np.int32),
        stop_valid=np.zeros((rows, MAX_STOP_IDS), np.float32),
        mask_bits=np.zeros((rows, (vocab + 7) // 8), np.uint8),
        mask_on=np.zeros((rows,), bool),
    )
    args.update(over)
    return args


def _greedy():
    return _inputs(temperature=np.zeros((B,), np.float32))


def _seeded():
    return _inputs()


def _top_k_top_p():
    return _inputs(
        temperature=np.array([0.7, 1.0, 1.3, 0.0, 1.0, 2.0], np.float32),
        top_k=np.array([0, 1, 5, 3, 64, 200], np.int32),
        top_p=np.array([1.0, 0.9, 0.5, 0.3, 0.05, 0.95], np.float32))


def _logit_bias():
    rng = np.random.default_rng(12)
    ids = np.zeros((B, MAX_LOGIT_BIAS), np.int32)
    vals = np.zeros((B, MAX_LOGIT_BIAS), np.float32)
    for b, n in enumerate([0, 1, 3, MAX_LOGIT_BIAS, 2, 5]):
        ids[b, :n] = rng.choice(V, size=n, replace=False)  # a dict's keys
        vals[b, :n] = rng.uniform(-100, 100, size=n)
    ids[4, :2], vals[4, :2] = (0, 9), (7.5, -3.25)  # id 0 is the padding's
    return _inputs(bias_ids=ids, bias_vals=vals,
                   temperature=np.array([1, 0, 1, 0, 1, 0], np.float32))


def _min_tokens_and_stop_ids():
    """EOS and the stop ids would win every step (biased up) and are
    masked until ``min_tokens`` outputs exist: the threshold is crossed
    inside the burst at a different step in every row."""
    ids = np.zeros((B, MAX_LOGIT_BIAS), np.int32)
    vals = np.zeros((B, MAX_LOGIT_BIAS), np.float32)
    stop_ids = np.zeros((B, MAX_STOP_IDS), np.int32)
    stop_valid = np.zeros((B, MAX_STOP_IDS), np.float32)
    for b in range(B):
        ids[b, :3], vals[b, :3] = (EOS, 40 + b, 90 + b), (50.0, 49.0, 48.0)
        stop_ids[b, :2], stop_valid[b, :2] = (40 + b, 90 + b), 1.0
    stop_ids[5, :3], stop_valid[5, :3] = (45, 45, 95), 1.0  # a repeated id
    return _inputs(
        bias_ids=ids, bias_vals=vals, stop_ids=stop_ids,
        stop_valid=stop_valid,
        min_tokens=np.array([0, 3, 5, 7, 12, 6], np.int32),
        out_len0=np.array([0, 0, 2, 1, 0, 4], np.int32),
        temperature=np.array([0, 0, 1, 0, 1, 0], np.float32))


def _structured_mask():
    rng = np.random.default_rng(13)
    allowed = rng.random((B, V)) < 0.05
    allowed[:, 5] = True
    return _inputs(
        mask_bits=np.packbits(allowed, axis=1, bitorder="little"),
        mask_on=np.array([True, False, True, True, False, True]),
        temperature=np.array([0, 0, 1, 1, 1, 0], np.float32))


def _penalties():
    """Flat-ish logits and strong penalties, so a token once sampled is
    not sampled again and the counts decide the later steps; row 2's
    slots are dead from step 3 on (its counts stop there)."""
    rng = np.random.default_rng(14)
    logits = np.repeat(
        (0.5 * rng.standard_normal((1, B, V))).astype(np.float32), K, 0)
    counts = np.zeros((B, V), np.int32)
    counts[1, 7], counts[3, 9] = 2, 1
    slots = np.arange(B * K, dtype=np.int32).reshape(B, K)
    slots[2, 3:] = -1
    return _inputs(
        logits=logits, counts=counts, slots=slots,
        presence_penalty=np.array([0, 1.5, 0.5, 0, 2.0, 1.0], np.float32),
        frequency_penalty=np.array([1.0, 0, 0.5, 2.0, 0, 1.0], np.float32),
        temperature=np.array([0, 0, 0, 1, 1, 0], np.float32))


def _tie_at_the_maximum():
    """The row's largest logit twice, three times, in every column: the
    lowest id is the greedy token and the first of the top logprobs."""
    logits = _inputs()["logits"].copy()
    top = logits.max(axis=-1) + 1.0
    logits[:, 0, 300], logits[:, 0, 17] = top[:, 0], top[:, 0]
    for col in (250, 31, 4):
        logits[:, 1, col] = top[:, 1]
    logits[:, 2, :] = 0.25
    return _inputs(logits=logits,
                   temperature=np.array([0, 0, 0, 1, 0, 0], np.float32))


CASES = {
    "greedy": (_greedy, 64),
    "temperature_1_with_seeds": (_seeded, 64),
    "per_row_top_k_top_p": (_top_k_top_p, 64),
    "logit_bias_distinct_ids": (_logit_bias, 64),
    "min_tokens_eos_and_stop_ids_mid_burst": (_min_tokens_and_stop_ids, 64),
    "structured_mask_on_some_rows": (_structured_mask, 64),
    "penalties_accumulating": (_penalties, 64),
    "logprobs": (_seeded, 16),
    "max_top_k_4_under_logprob_k": (_top_k_top_p, 4),
    "tie_at_the_maximum": (_tie_at_the_maximum, 64),
}


def _wide(make):
    """A case at a vocabulary wide enough for the selection's two stages
    (``exact_top_k``: 33 groups of 128, the last one padded, for the
    8 best: ``max_top_k`` 4 under ``LOGPROB_K``), its ids spread over it."""
    def case():
        args = make()
        rng = np.random.default_rng(16)
        wide = 33 * sampling.TOP_K_GROUP - 60
        spread = np.sort(rng.choice(wide, size=V, replace=False))
        spread[:8] = np.arange(8)  # EOS and the padding's id stay put
        out = dict(args)
        logits = (3.0 * rng.standard_normal((K, B, wide))).astype(np.float32)
        logits[:, :, spread] = args["logits"] + 4.0
        counts = np.zeros((B, wide), np.int32)
        counts[:, spread] = args["counts"]
        allowed = np.zeros((B, wide), bool)
        allowed[:, spread] = np.unpackbits(
            args["mask_bits"], axis=1, bitorder="little")[:, :V].astype(bool)
        out.update(
            logits=logits, counts=counts,
            bias_ids=spread[args["bias_ids"]].astype(np.int32),
            stop_ids=spread[args["stop_ids"]].astype(np.int32),
            mask_bits=np.packbits(allowed, axis=1, bitorder="little"))
        return out
    return case


CASES.update({
    "wide_" + name: (_wide(CASES[name][0]), 4) for name in (
        "temperature_1_with_seeds", "logit_bias_distinct_ids",
        "min_tokens_eos_and_stop_ids_mid_burst",
        "structured_mask_on_some_rows", "penalties_accumulating",
        "tie_at_the_maximum")})


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("case", CASES)
def test_the_tail_gives_what_the_tail_it_replaced_gave(case):
    make, max_top_k = CASES[case]
    args = {k: jnp.asarray(v) for k, v in make().items()}
    want, want_counts = _reference_burst(**args, max_top_k=max_top_k)
    got, got_counts = _burst(**args, max_top_k=max_top_k)
    for name, w, g in zip(("sampled", "chosen_lp", "top_lp", "top_ids"),
                          want, got):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(_bits(g), _bits(w), err_msg=name)
    np.testing.assert_array_equal(np.asarray(got_counts),
                                  np.asarray(want_counts))
    sampled, chosen, top_lp, top_ids = (np.asarray(x) for x in got)
    assert top_lp.shape == (K, B, LOGPROB_K)
    assert np.isfinite(chosen).all()
    # the features bite: a case whose inputs change nothing tests nothing
    plain = _wide(_seeded) if case.startswith("wide_") else _seeded
    plain, _ = _burst(**{k: jnp.asarray(v) for k, v in plain().items()},
                      max_top_k=max_top_k)
    if not case.endswith(("temperature_1_with_seeds", "logprobs")):
        assert not np.array_equal(np.asarray(plain[0]), sampled)


def test_rank_0_of_the_selection_is_argmax_on_exact_ties():
    """``argmax`` takes the lowest id of equal maxima and ``lax.top_k``
    is stable, so the greedy token read off the selection is the token
    ``argmax`` gave, whatever the number of ties (here 2 to the whole
    row), and the top logprobs list tied ids in rising order."""
    rng = np.random.default_rng(15)
    logits = rng.standard_normal((5, V)).astype(np.float32)
    logits[0, [200, 3]] = 9.0
    logits[1, 100:] = 9.0
    logits[2, :] = -1.5
    logits[3, [319, 0]] = 9.0
    logits[4, [8, 9, 10, 11, 12, 13, 14, 15, 16, 17]] = np.float32(9.0)
    zeros = jnp.zeros((5,), jnp.float32)
    sampled, _, top_lp, top_ids = sampling.sample_with_logprobs(
        jnp.asarray(logits), make_rng_keys(0, 0, jnp.arange(5)), zeros,
        jnp.zeros((5,), jnp.int32), zeros + 1.0)
    np.testing.assert_array_equal(np.asarray(sampled),
                                  np.argmax(logits, axis=-1))
    np.testing.assert_array_equal(np.asarray(sampled), [3, 100, 0, 0, 8])
    ids = np.asarray(top_ids)
    np.testing.assert_array_equal(ids[:, 0], np.asarray(sampled))
    np.testing.assert_array_equal(ids[2], np.arange(LOGPROB_K))
    np.testing.assert_array_equal(ids[4], np.arange(8, 8 + LOGPROB_K))
    lp = np.asarray(top_lp)
    assert (lp[4] == lp[4, 0]).all() and (np.diff(lp, axis=1) <= 0).all()


def test_a_row_without_entries_passes_through_bit_for_bit():
    """Adding the dense bias where a row has no entry, and the stop ids'
    term where none is suppressed, changes no bit, signed zeros
    included: the dense forms rest on -0.0."""
    raw = np.array([[0.0, -0.0, 1.5, -2.25, 1e-30, -1e30]],
                   np.float32)
    n = raw.shape[1]
    terms = sampling.burst_terms(
        n, jnp.zeros((1, MAX_LOGIT_BIAS), jnp.int32),
        jnp.zeros((1, MAX_LOGIT_BIAS), jnp.float32),
        jnp.asarray([[3] + [0] * (MAX_STOP_IDS - 1)], jnp.int32),
        jnp.asarray([[1.0] + [0.0] * (MAX_STOP_IDS - 1)], jnp.float32),
        jnp.zeros((1, 1), jnp.uint8), jnp.zeros((1,), bool))
    for suppress in (False, True):
        shaped = np.asarray(sampling.shape_logits(
            jnp.asarray(raw), terms, jnp.asarray([suppress]), EOS))
        want = raw.copy()
        if suppress:
            want[0, EOS] = -np.inf
            want[0, 3] = np.float32(-2.25) + np.float32(-1e30)
        # the padding's id 0 takes the scatter's + 0.0, as it always did
        np.testing.assert_array_equal(_bits(shaped)[0, 1:], _bits(want)[0, 1:])
        assert shaped[0, 0] == 0.0


@pytest.mark.parametrize("rows,vocab,k", [
    (4, 33 * 128, 8), (4, 16 * 128 - 60, 8), (2, 16384, 64), (3, 19360, 32),
    (2, 40000, 64), (3, 320, 64), (3, 15 * 128, 8)])
def test_the_two_stage_selection_is_lax_top_k_to_the_bit_and_to_the_tie(
        rows, vocab, k):
    """``exact_top_k`` against ``lax.top_k``, values and ids, on rows
    made to break a selection by groups: plain noise; values rounded so
    that hundreds tie; a structured mask's row (five live ids, the rest
    at the mask's one value, so the list is filled from the lowest ids);
    one value everywhere; the maximum tied across the upper half's
    groups; -inf inside the row and the largest values in its last
    three ids (the padded group's)."""
    rng = np.random.default_rng(17)
    cases = []
    for kind in range(6):
        x = rng.standard_normal((rows, vocab)).astype(np.float32)
        if kind == 1:
            x = np.round(x * 2) / 2
        elif kind == 2:
            x[:] = FSM_MASK_NEG
            x[:, rng.choice(vocab, 5, replace=False)] = rng.standard_normal(5)
        elif kind == 3:
            x[:] = 0.25
        elif kind == 4:
            x[:, vocab // 2:] = 7.0
        elif kind == 5:
            x[:, 2] = -np.inf
            x[:, -3:] = x.max() + 1
        cases.append(x)
    select = jax.jit(sampling.exact_top_k, static_argnums=1)
    for kind, x in enumerate(cases):
        want = jax.lax.top_k(jnp.asarray(x), k)
        got = select(jnp.asarray(x), k)
        for name, w, g in zip(("values", "ids"), want, got):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(
                _bits(g), _bits(w), err_msg=f"{name}, rows of kind {kind}")
