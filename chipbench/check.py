"""What decides ``correct``: the engine against the plain reference.

A seeded sample of sequences goes through the engine as requests do
(``EngineCore.add_request``: uncached prefill, prefill behind a cached
prefix, then decode steps through the paged cache, two rows decoding
together) with greedy sampling and the top log-probabilities asked for.
The reference (``reference/<arch>.py``) then computes, in float32 and
from the seed alone, the log-probabilities of the same tokens at the same
positions by one full forward pass over each sequence, and the keys and
values of the layers the ``check`` block names under ``kv_layers`` (the
first layer alone where it names none). Two numbers are compared with
their limits (the configuration's ``check`` block, with the readings
they came from in ``PERF.md``):

- ``logprob_rms``: root mean square of (engine - reference) over every
  reported log-probability of every generated position. Covers the whole
  model path: weights, activations, both attention kernels, the head.
- ``kv_rel_rms``: root mean square of (pages - reference) over the first
  layer's cached keys and values of the prompts, relative to their root
  mean square. The program gives the pages out through its KV-transfer
  surface (``extract_kv``). Deeper layers add the error of the
  activations before them, the same in any page format, so the first
  layer is where the page format itself shows. A model whose layers are
  of several kinds (a full layer at 0, window layers after it) names one
  layer of each kind under ``kv_layers``; every ``kv_*`` number is then
  the largest over the layers named, and ``kv_small_rel_rms_layer<n>``
  is each layer's own, which ``limits`` may name: a sound run reads a
  deeper layer higher than the first.

- ``kv_small_rel_rms``: the same error over the entries whose reference
  value is under half the root mean square, still relative to the root
  mean square of all entries. A float format's error shrinks with the
  value and an integer format's does not, so this is the number that
  tells bf16 pages from int8 pages most sharply.

All are means over some hundreds (log-probabilities) or millions
(page entries) of numbers, so they are steady from seed to seed. The
configuration's ``controls`` (``chipbench.control``) each have to fail a
limit: the program's own int8 pages and int8 weights, and the reference
itself in the program's place with fp8 activations
(:func:`reference_in_place`).
"""

from __future__ import annotations

import math
import random
import threading

import numpy as np

from chipbench.registry import model_keys

# entries under this share of their tensor's root mean square are "small"
SMALL = 0.5


def sample_prompts(check: dict, vocab: int, seed: int):
    """The sample: ``shared_prefix`` tokens that the first two prompts
    have in common (the second one's prefill finds them cached), then
    each prompt's own tokens."""
    rng = random.Random(seed * 7919 + 17)
    draw = lambda n: [rng.randrange(259, vocab) for _ in range(n)]  # noqa: E731
    prefix = draw(check["shared_prefix"])
    lengths = check["prompt_tokens"]
    return ([prefix + draw(lengths[0] - len(prefix)),
             prefix + draw(lengths[1] - len(prefix))]
            + [draw(n) for n in lengths[2:]])


def engine_outputs(core, prompts, gen_tokens: int, top: int):
    """[(tokens, [[(token, logprob), ...] per position])] from the
    engine: the first prompt alone (its pages then hold the shared
    prefix), then the others together."""
    from production_stack_tpu.engine.sampling import SamplingParams

    results = [None] * len(prompts)

    def submit(i):
        tokens, tops, done = [], [], threading.Event()

        def on_token(payload, finish):
            if payload is not None:
                tok, lp = payload
                tokens.append(int(tok))
                entries = {int(t): float(v) for t, v in lp["top"]}
                entries[int(tok)] = float(lp["logprob"])
                tops.append(sorted(entries.items()))
            if finish is not None:
                results[i] = (tokens, tops, finish)
                done.set()

        core.add_request(
            f"check-{i}", list(prompts[i]),
            SamplingParams(temperature=0.0, max_tokens=gen_tokens,
                           ignore_eos=True, logprobs=top), on_token)
        return done

    if not submit(0).wait(600):
        raise TimeoutError("the engine did not answer the check's "
                           "first request")
    waits = [submit(i) for i in range(1, len(prompts))]
    for w in waits:
        if not w.wait(600):
            raise TimeoutError("the engine did not answer the check")
    for tokens, _, finish in results:
        if finish != "length" or len(tokens) != gen_tokens:
            raise RuntimeError(
                f"check request ended {finish!r} after {len(tokens)} of "
                f"{gen_tokens} tokens")
    return [(t, p) for t, p, _ in results]


def _pages_to_tokens(side, layer: int = 0) -> np.ndarray:
    """[N, L, bs, KVH, D] pages (or (int8 data, scales)) of ``layer`` ->
    float32 [N*bs, KVH, D]."""
    if isinstance(side, tuple):
        data, scales = side
        data = np.asarray(data)[:, layer].astype(np.float32)
        n, bs, kvh, d = data.shape
        scales = np.asarray(scales)[:, layer].astype(np.float32).reshape(
            n, bs, kvh, 1)
        return (data * scales).reshape(n * bs, kvh, d)
    data = np.asarray(side)[:, layer].astype(np.float32)
    return data.reshape(-1, *data.shape[2:])


def engine_pages(core, prompts):
    """(i, layer) -> (keys, values) of prompt i's tokens in the engine's
    pages of that layer, float32 [n, KVH, D]."""
    last = {}  # the prompt asked for last: its layers are read in turn

    def pages(i, layer=0):
        if i not in last:
            last.clear()
            last[i] = core.extract_kv(list(prompts[i]))
        got = last[i]
        if got is None:
            raise RuntimeError("the engine holds no pages of a check prompt")
        n = got["num_tokens"]
        return (_pages_to_tokens(got["k"], layer)[:n],
                _pages_to_tokens(got["v"], layer)[:n])
    return pages


def kv_layers_of(check: dict) -> tuple:
    """The layers whose cache the check compares: ``kv_layers`` of the
    ``check`` block, the first layer alone where it names none."""
    return tuple(check.get("kv_layers", (0,)))


def reference_in_place(reference, hf: dict, seed: int, check: dict, prompts,
                       activations: str):
    """(outputs, pages) as the engine gives them, from the reference put
    in the program's place with its activations rounded to
    ``activations``: a control. A plain forward generates nothing, so the
    tokens after each prompt are drawn from the seed and the top
    log-probabilities are read at their positions."""
    gen, top = check["gen_tokens"], check["top_logprobs"]
    rng = random.Random(seed * 104729 + 5)
    answers = [[rng.randrange(259, hf["vocab_size"]) for _ in range(gen)]
               for _ in prompts]
    lens = [len(p) + gen for p in prompts]
    tokens = np.zeros((len(prompts), max(lens)), np.int32)
    for i, (p, a) in enumerate(zip(prompts, answers)):
        tokens[i, :lens[i]] = list(p) + a
    keep_from = min(len(p) for p in prompts) - 1
    logp, kv = reference.forward(hf, seed, tokens, lens, keep_from=keep_from,
                                 quantization=check.get("quantization"),
                                 kv_layers=kv_layers_of(check),
                                 activations=activations)
    outputs = []
    for i, (p, a) in enumerate(zip(prompts, answers)):
        tops = []
        for j, tok in enumerate(a):
            row = logp[i, len(p) - 1 + j - keep_from]
            best = {int(t) for t in np.argsort(row)[-top:]} | {tok}
            tops.append(sorted((t, float(row[t])) for t in best))
        outputs.append((a, tops))
    return outputs, lambda i, layer=0: tuple(
        side[i, :len(prompts[i])].astype(np.float32) for side in kv[layer])


class _PageError:
    """Sums of one layer's cache error over the prompts."""

    def __init__(self):
        self.err2 = {"k": 0.0, "v": 0.0}
        self.ref2 = {"k": 0.0, "v": 0.0}
        self.small_err2 = self.small_n = self.entries = 0.0

    def add(self, side: str, want, mine) -> None:
        sq = (mine - want).astype(np.float64) ** 2
        self.err2[side] += float(sq.sum())
        self.ref2[side] += float(np.sum(want.astype(np.float64) ** 2))
        small = np.abs(want) < SMALL * math.sqrt(
            float(np.mean(want.astype(np.float64) ** 2)))
        self.small_err2 += float(sq[small].sum())
        self.small_n += float(small.sum())
        self.entries += want.size

    def numbers(self) -> dict:
        total_ref2 = self.ref2["k"] + self.ref2["v"]
        return {"kv_rel_rms": math.sqrt(
                    (self.err2["k"] + self.err2["v"]) / total_ref2),
                "kv_k_rel_rms": math.sqrt(self.err2["k"] / self.ref2["k"]),
                "kv_v_rel_rms": math.sqrt(self.err2["v"] / self.ref2["v"]),
                "kv_small_rel_rms": math.sqrt(
                    (self.small_err2 / self.small_n)
                    / (total_ref2 / self.entries))}


def compare(reference, hf: dict, seed: int, quantization, prompts, outputs,
            pages, kv_layers=(0,)) -> dict:
    """The numbers, with their parts. ``outputs`` and ``pages`` are the
    program's (or a control's in its place). Each ``kv_*`` number is the
    largest over ``kv_layers``; with more than one layer named,
    ``kv_small_rel_rms_layer<n>`` gives each layer's, for ``limits`` to
    name one by one."""
    gen = len(outputs[0][0])
    lens = [len(p) + gen for p in prompts]
    width = max(lens)
    tokens = np.zeros((len(prompts), width), np.int32)
    for i, (p, (out, _)) in enumerate(zip(prompts, outputs)):
        tokens[i, :lens[i]] = list(p) + out
    keep_from = min(len(p) for p in prompts) - 1
    logp, kv = reference.forward(hf, seed, tokens, lens,
                                 keep_from=keep_from,
                                 quantization=quantization,
                                 kv_layers=tuple(kv_layers))
    diffs = []
    for i, (p, (_, tops)) in enumerate(zip(prompts, outputs)):
        for j, entries in enumerate(tops):
            row = logp[i, len(p) - 1 + j - keep_from]
            diffs.extend(v - float(row[t]) for t, v in entries)
    diffs = np.asarray(diffs, np.float64)
    errors = {layer: _PageError() for layer in kv_layers}
    for i in range(len(prompts)):
        for layer, error in errors.items():
            for side, ref, mine in zip("kv", kv[layer], pages(i, layer)):
                error.add(side, ref[i, :len(mine)].astype(np.float32), mine)
    by_layer = {layer: error.numbers() for layer, error in errors.items()}
    numbers = {"logprob_rms": float(np.sqrt(np.mean(diffs ** 2))),
               "logprob_max": float(np.max(np.abs(diffs))),
               "logprobs_compared": int(diffs.size)}
    for name in ("kv_rel_rms", "kv_k_rel_rms", "kv_v_rel_rms",
                 "kv_small_rel_rms"):
        numbers[name] = max(n[name] for n in by_layer.values())
    numbers["kv_entries_compared"] = int(sum(
        e.entries for e in errors.values()))
    if len(by_layer) > 1:
        for layer, n in by_layer.items():
            numbers[f"kv_small_rel_rms_layer{layer}"] = n["kv_small_rel_rms"]
    return numbers


def run_check(registry, config: dict, seed: int, core, *,
              reference_activations=None) -> dict:
    """{"numbers": {...}, "limits": {...}, "ok": bool}; prints nothing.
    With ``reference_activations`` no engine is asked: the reference with
    its activations rounded to that type stands in its place."""
    check = config["check"]
    reference = registry.module("reference", config["reference"])
    hf = model_keys(config)
    prompts = sample_prompts(check, hf["vocab_size"], seed)
    if reference_activations is None:
        outputs = engine_outputs(core, prompts, check["gen_tokens"],
                                 check["top_logprobs"])
        pages = engine_pages(core, prompts)
    else:
        outputs, pages = reference_in_place(reference, hf, seed, check,
                                            prompts, reference_activations)
    numbers = compare(reference, hf, seed, check.get("quantization"),
                      prompts, outputs, pages, kv_layers_of(check))
    limits = check["limits"]
    ok = all(numbers[name] <= limit for name, limit in limits.items())
    return {"numbers": numbers, "limits": limits, "ok": ok}
