"""What every configuration and every cell of the benchmark keeps to,
as functions of a :class:`Registry`: the tests run them on the repo's
root and on roots in which a later PR has added files.

Each function returns the list of faults it found, as sentences; an
empty list is a pass. Nothing here knows a configuration by name but
``PINNED``, the table of configurations whose widths are held to their
source's numbers one by one.
"""

import re

from chipbench import schedule
from chipbench.registry import model_keys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")

# Published widths of the configurations the benchmark has. No width is
# ever cut (the sizing section of the model-configs guide); these are
# held number by number because every accepted line of the ledger rests
# on them.
PINNED = {
    "mistral-7b-l16": {"hidden_size": 4096, "intermediate_size": 14336,
                       "num_attention_heads": 32, "num_key_value_heads": 8,
                       "vocab_size": 32000},
}

# The keys ``reduced`` may name: they count what is held on this chip
# (layers, heads, experts, rows of the vocabulary), not how wide
# anything is. Only a ``benchmark`` PR extends these sets.
LAYER_KEYS = ("num_hidden_layers", "num_layers", "n_layer")
HEAD_KEYS = ("num_attention_heads", "num_key_value_heads", "n_head")
EXPERT_KEYS = ("n_routed_experts", "num_local_experts", "num_experts")
VOCAB_KEYS = ("vocab_size",)
HELD_HERE = frozenset(LAYER_KEYS + HEAD_KEYS + EXPERT_KEYS + VOCAB_KEYS)

# A width by its name: hidden, feed-forward and expert widths, head
# sizes, ranks, latent, state and window sizes, expansion factors,
# experts per token. Never in ``reduced``, never in ``published``.
WIDTH = re.compile(
    r"_dim$|_rank$|_width$"
    r"|(^|_)(hidden|intermediate|ffn|latent|state|window|conv|expand"
    r"|expansion|factor)(_|$)"
    r"|head_size|per_tok|top_?k|^d_[a-z]+$")

# A number that ``check.compare`` gives for one layer of several compared.
PER_LAYER_NUMBER = re.compile(r"^kv_small_rel_rms_layer(\d+)$")

# Floors of a cut, so that what is left is still the model.
MIN_LAYERS_AFTER_DENSE = 4
MIN_ROUTED_EXPERTS = 8
MIN_VOCAB_SHARE = 8  # held x 8 >= published


def is_width(key: str) -> bool:
    return key not in HELD_HERE and bool(WIDTH.search(key))


def _first(body: dict, keys):
    return next((k for k in keys if k in body), None)


def leading_dense_layers(body: dict) -> int:
    """How many of the held layers are dense ones ahead of the sparse
    ones, by whichever key the file has: ``first_k_dense_replace``, the
    entries of ``mlp_only_layers``, or the leading run of ``"dense"`` in
    ``mlp_layer_types``; the largest where it has several."""
    kinds = body.get("mlp_layer_types") or []
    run = next((i for i, kind in enumerate(kinds) if kind != "dense"),
               len(kinds))
    return max(body.get("first_k_dense_replace") or 0,
               len(body.get("mlp_only_layers") or ()), run)


def check_block_faults(check: dict, layers_held: int) -> list:
    """Faults of a ``check`` block's ``kv_layers`` (the layers whose
    cache is compared: held ones, each once; ``[0]`` where it names
    none) and of the per-layer numbers its ``limits`` name, which
    ``check.compare`` gives only for several layers named."""
    named = check.get("kv_layers", [0])
    faults = []
    if (not named or len(set(named)) != len(named)
            or any(not isinstance(n, int) or not 0 <= n < layers_held
                   for n in named)):
        faults.append(f"check.kv_layers is {named}: not a list of "
                      f"different layers among the {layers_held} held")
    for number in check.get("limits", {}):
        layer = PER_LAYER_NUMBER.match(number)
        if layer and (len(named) < 2 or int(layer.group(1)) not in named):
            faults.append(f"check.limits names {number}, which the check "
                          f"gives only for several kv_layers that hold "
                          f"layer {layer.group(1)}")
    return faults


def config_faults(reg, entry: dict) -> list:
    """Faults of one entry of ``configs`` and of the file it names."""
    faults = []
    name = entry.get("name", "?")

    def fault(text):
        faults.append(f"{name}: {text}")

    if set(entry) != {"name", "source", "file", "reduced", "why"}:
        fault(f"entry has the keys {sorted(entry)}")
    if not NAME.match(name):
        fault("not a name")
    if not any(entry["file"].startswith(p + "/") for p in reg.bench["paths"]):
        fault(f"file {entry['file']} lies under none of paths")
    body = reg.config(name)
    if body.get("reduced") != entry["reduced"]:
        fault("reduced differs between the file and BENCHMARK.json")
    if body.get("source") != entry["source"]:
        fault("source differs between the file and BENCHMARK.json")
    if not str(body.get("stands_for") or "").strip():
        fault("stands_for is empty: which deployment is this chip one of?")
    try:
        reg.find("reference", f"{body.get('reference')}.py")
    except FileNotFoundError:
        fault(f"reference names {body.get('reference')!r}: no such module "
              f"under reference/")
    if not any(w["config"] == name for w in reg.bench["workloads"]):
        fault("no cell runs it")
    published = body.get("published")
    if not isinstance(published, dict):
        fault("no published block: the source's value of each changed key")
        published = {}
    assumed = body.get("assumed") or {}

    # what may be cut, and what it was
    for key in entry["reduced"]:
        if key not in HELD_HERE:
            what = "a width" if is_width(key) else "no count of what is held here"
            fault(f"reduced names {key}: {what}")
        if key not in published:
            fault(f"reduced names {key}, published does not give its value")
        elif not body.get(key, 0) < published[key]:
            fault(f"{key} is reduced and holds {body.get(key)}, not less "
                  f"than the published {published[key]}")
    for key in published:
        if is_width(key):
            fault(f"published names {key}: a width, which is never changed")
        if key not in entry["reduced"] and key not in assumed:
            fault(f"published names {key}, which is neither in reduced nor "
                  f"explained under assumed")

    # the floors of a cut; a source that has less than a floor keeps all
    layers = _first(body, LAYER_KEYS)
    if layers is None:
        fault(f"none of {LAYER_KEYS} says how many layers are held")
    else:
        dense = leading_dense_layers(body)
        all_layers = published.get(layers, body[layers])
        floor = min(MIN_LAYERS_AFTER_DENSE, all_layers - dense)
        if body[layers] - dense < floor:
            fault(f"{body[layers]} layers of which {dense} dense: fewer than "
                  f"{floor} after the leading dense ones")
        for key, value in model_keys(body).items():
            if (body[layers] < all_layers and isinstance(value, list)
                    and len(value) == body[layers]):
                fault(f"{key} has {len(value)} entries, one for each layer "
                      f"held: keep per-layer lists as published "
                      f"({all_layers} entries): the program and the "
                      f"reference read the first {layers} entries")
        for text in check_block_faults(body.get("check") or {},
                                       body[layers]):
            fault(text)
    experts = _first(body, EXPERT_KEYS)
    if experts is not None and body[experts]:
        floor = min(MIN_ROUTED_EXPERTS, published.get(experts, body[experts]))
        if body[experts] < floor:
            fault(f"{body[experts]} routed experts held: fewer than {floor}")
    vocab = _first(body, VOCAB_KEYS)
    if vocab is None:
        fault("no vocab_size")
    elif body[vocab] * MIN_VOCAB_SHARE < published.get(vocab, body[vocab]):
        fault(f"{body[vocab]} rows of the vocabulary: under an eighth of "
              f"the published {published[vocab]}")

    for key, want in PINNED.get(name, {}).items():
        if body.get(key) != want:
            fault(f"{key} is {body.get(key)}, its source says {want}")
    return faults


def contract_faults(reg) -> list:
    """Faults of every configuration the root's BENCHMARK.json lists."""
    return [f for entry in reg.bench["configs"]
            for f in config_faults(reg, entry)]


def max_model_len(config: dict) -> int:
    flags = config["server_flags"]
    return int(flags[flags.index("--max-model-len") + 1])


def window_binds(config: dict) -> bool:
    """The configuration publishes a ``sliding_window`` and states under
    ``assumed`` that the program does not apply it: then no context may
    pass it. A window the program implements bounds nothing."""
    return (model_keys(config).get("sliding_window") is not None
            and "sliding_window" in (config.get("assumed") or {}))


def schedule_faults(reg, seconds: float = 15) -> list:
    """Faults of every cell's schedule against its configuration."""
    faults = []
    for cell in reg.bench["workloads"]:
        config = reg.config(cell["config"])
        s = schedule.build(reg, reg.traffic(cell["traffic"]), seconds,
                           config["vocab_size"])
        longest = max(len(r["prompt"]) + r["max_tokens"]
                      for r in s["requests"])
        if longest > max_model_len(config):
            faults.append(f"{cell['name']}: a context of {longest} passes "
                          f"--max-model-len {max_model_len(config)}")
        if window_binds(config) and longest > config["sliding_window"]:
            faults.append(f"{cell['name']}: a context of {longest} passes the "
                          f"sliding_window {config['sliding_window']} that "
                          f"the program does not apply")
        if any(not 0 <= t < config["vocab_size"]
               for r in s["requests"] for t in r["prompt"]):
            faults.append(f"{cell['name']}: a token outside the "
                          f"{config['vocab_size']} rows held")
    return faults
