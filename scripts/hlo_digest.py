"""Optimised v5e HLO of models.llama.apply at mistral-7b-l16's widths (and
of models.laguna.apply at laguna-s-2.1-l8e64's, bf16: PR 36; of
models.lfm2.apply at lfm2-24b-a2b-l10's: PR 40; of models.longcat.apply
at longcat-flash-l4e16's: PR 44; of models.glm4_moe_lite.apply at
glm-4.7-flash-e8v8's and LongCat's cached tails: PR 45; of
models.ouro.apply at ouro-2.6b's, 48 layers x 4 passes: PR 48; of
models.smallthinker.apply at smallthinker-21b-a3b-l8's: PR 51), for
a refactor that must not change the program (PR 31): run it on a copy of
the parent and on the change and compare the digests; no chip needed.
Each of the four later families also as the engine's decode burst nests
it (``<name>.decode_k8``: ``apply(mode="decode")`` inside an outer scan
of 8 steps, the pool in the carry: PR 46), because the compiler places
operands differently in the nested program, and ``apply`` alone hid 25%
of a cell's device time for two PRs (GLM's dense weights prefetched into
VMEM in every layer of the burst and in no layer of ``glm.decode``).
The bursts take each step's argmax; the sampling tail the engine runs
there is hashed alone (``tail.decode_k8.<rows>x<vocab>``: PR 49).

    JAX_PLATFORMS=cpu python scripts/hlo_digest.py <repo-root> <out-dir> \
        [--only mistral|laguna|lfm2|longcat|glm|ouro|smallthinker|tail]

Three modes (decode 32 x 1, plain prefill 1 x 512, cached prefill 1 x 256,
the server's default 8 LoRA slots) x {bf16, int8 weights}, compiled by the
TPU compiler for a described v5e. Stripped before hashing, as metadata:
``metadata={...}`` of every instruction, the header's source-location
tables, and the MLIR locations inside each Mosaic kernel's serialized body.
Writes ``<mode>.<weights>.hlo``, ``<name>.ops.json`` and ``digests.json``,
which holds three maps by program (PR 47):

- ``text``: the sha of the stripped text, instruction names and their
  numbering included. Equal digests prove the compiler was handed the same
  program and made the same one of it; unequal ones prove nothing (a part
  put under ``jax.jit`` renumbers every instruction behind it).
- ``ops``: the sha of the sorted list of (opcode, result type with its
  layout) of every instruction, fused ones included, a tuple type's
  entries sorted too: blind to names, numbering, operand order and the
  order of a loop's carry. Equal ``ops`` under unequal ``text`` is the
  same work laid out the same way; where they differ ``<name>.ops.json``
  (count by ``"opcode type"``) of both trees names each instruction.
- ``memory``: ``temp_size_in_bytes`` and ``generated_code_size_in_bytes``
  of ``compiled.memory_analysis()`` (the second is what the machine's
  192 MiB compile cache has to hold).

``--only`` hashes one family's programs (under a minute, GLM's ~2) and
writes only those into ``digests.json``.

    python scripts/hlo_digest.py --compare <out-dir-a> <out-dir-b>

prints, for each program of both, whether ``text`` and ``ops`` agree, b's
``memory`` over a's, and each ``"opcode type"`` whose count differs (b's
count less a's).
"""
import collections
import hashlib
import json
import os
import re
import sys

if sys.argv[1] == "--compare":
    dirs = sys.argv[2:4]

    def read(directory, name):
        with open(os.path.join(directory, name)) as f:
            return json.load(f)

    a, b = (read(d, "digests.json") for d in dirs)
    for name in sorted(set(a["text"]) & set(b["text"])):
        same = ["%s %s" % (key, "same" if a[key][name] == b[key][name]
                           else "DIFFERS") for key in ("text", "ops")]
        sizes = ["%s %d -> %d (%+.2f%%)" % (
            key.split("_size")[0], was, b["memory"][name][key],
            100.0 * (b["memory"][name][key] - was) / max(was, 1))
            for key, was in a["memory"][name].items()]
        print(name, *same, *sizes)
        if a["ops"][name] != b["ops"][name]:
            was, now = (collections.Counter(read(d, name + ".ops.json"))
                        for d in dirs)
            for op in sorted(set(was) | set(now)):
                if was[op] != now[op]:
                    print("   %+d  %s" % (now[op] - was[op], op))
    sys.exit(0)
root, out = sys.argv[1], sys.argv[2]
only = sys.argv[sys.argv.index("--only") + 1] if "--only" in sys.argv else None
sys.path.insert(0, root)
os.makedirs(out, exist_ok=True)

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

jax.config.update("jax_enable_compilation_cache", False)

from production_stack_tpu.models import llama
from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.models.quantize import quantize_tree
from production_stack_tpu.ops import attention as att

assert os.path.realpath(llama.__file__).startswith(os.path.realpath(root)), llama.__file__

att._use_pallas = lambda: True
try:  # since PR 37 the expert layers choose a path too (a tree before it
    # has no such module: its Laguna programs hold ragged_dot)
    from production_stack_tpu.ops import pallas_grouped_matmul as gmm

    gmm._platform = lambda: "tpu"
except ImportError:
    pass
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])

cfg = ModelConfig(
    name="mistral-7b-l16", arch="llama", vocab_size=32000, hidden_size=4096,
    num_layers=16, num_heads=32, num_kv_heads=8, head_dim=128,
    intermediate_size=14336, max_position=32768, rope_theta=10000.0)
BS, NB, MAXB = 64, 2048, 64


def _body_without_locations(m):
    import base64
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib.mlir import ir
    from jax._src.lib import tpu
    ctx = jmlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    tpu.register_dialect(ctx)  # its memory-space attributes (SMEM scratch)
    with ctx:
        asm = ir.Module.parse(base64.b64decode(m.group(1))) \
            .operation.get_asm(enable_debug_info=False)
    return '"body_sha256":"%s"' % hashlib.sha256(asm.encode()).hexdigest()


def spec(shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one)


def params_spec(int8):
    def init():
        p = llama.init_params(cfg, jax.random.key(0), lora_slots=8, lora_rank=16)
        return quantize_tree(p, "llama") if int8 else p
    return jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype), jax.eval_shape(init))


# ``[ROOT] %name = <result type> <opcode>(``: a type holds no lower-case
# word before a parenthesis (tiles and memory spaces are ``T(8,128)S(1)``).
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([a-z][a-z0-9\-]*)\(",
                         re.M)


def _unordered(kind):
    """A result type with the entries of a tuple type sorted."""
    if not kind.startswith("("):
        return kind
    entries, depth, start = [], 0, 1
    for i, char in enumerate(kind):
        depth += (char in "([{") - (char in ")]}")
        if (char == "," and depth == 1) or depth == 0:
            entries.append(_unordered(kind[start:i].strip()))
            start = i + 1
    return "(%s)" % ", ".join(sorted(entries))


def digest(name, fn, donate, *operands):
    compiled = jax.jit(fn, donate_argnums=(donate,)).lower(
        *operands).compile()
    text = compiled.as_text()
    held = compiled.memory_analysis()
    memory[name] = {key: getattr(held, key) for key in (
        "temp_size_in_bytes", "generated_code_size_in_bytes")}
    # The source-location tables of the header are metadata too.
    text = re.sub(
        r"\nFileNames\n.*?\nStackFrames\n.*?\n\n", "\n", text, flags=re.S)
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"metadata=\{[^}]*\}", "", text)
    # A Mosaic kernel's serialized body carries the MLIR locations of
    # its call stack (file, line of every frame): parse it and print
    # it without them.
    text = re.sub(r'"body":"([^"]*)"', _body_without_locations, text)
    with open(os.path.join(out, name + ".hlo"), "w") as f:
        f.write(text)
    digests[name] = hashlib.sha256(text.encode()).hexdigest()
    counted = collections.Counter(
        f"{opcode} {_unordered(kind)}" for kind, opcode in INSTRUCTION.findall(
            re.sub(r"/\*.*?\*/", "", text)))
    with open(os.path.join(out, name + ".ops.json"), "w") as f:
        json.dump(dict(sorted(counted.items())), f, indent=0)
    ops[name] = hashlib.sha256(
        "\n".join(sorted(counted.elements())).encode()).hexdigest()
    print(name, digests[name][:16], ops[name][:16], sum(counted.values()),
          memory[name], flush=True)


pages = spec((cfg.num_layers, NB, BS, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16)
digests, ops, memory = {}, {}, {}
for weights in ("bf16", "int8") if only in (None, "mistral") else ():
    params = params_spec(weights == "int8")
    for mode, rows, width in (("decode", 32, 1), ("prefill", 1, 512),
                              ("prefill_cached", 1, 256)):
        last = mode != "decode"

        def fn(p, tok, pos, kv, slot, bt, cl, sl, aid, lt):
            return llama.apply(
                p, cfg, tok, pos, kv, slot, bt, cl, sl, mode=mode,
                adapter_ids=aid, last_token=lt if last else None)

        digest(f"{mode}.{weights}", fn, 3,
               params, spec((rows, width)), spec((rows, width)), (pages, pages),
               spec((rows, width)), spec((rows, MAXB)), spec((rows,)),
               spec((rows,)), spec((rows,)), spec((rows,)))

# The two later families as the benchmark serves them: the model keys of
# the configuration's file (read from <repo-root>), the three programs
# with the expert layer's counts.
from chipbench.registry import model_keys
from production_stack_tpu.models import get_model_config


BURST = "decode_k8"


def family_digests(name, config, module, pool, shapes):
    """``<name>.<mode>`` for each of ``shapes`` (mode, rows, width, table
    width); ``pool(cfg)`` gives the sides of the cache. The mode
    ``decode_k8`` is the decode step inside a scan over ``width`` steps
    (engine/core.py::_make_multi_decode without its sampling: each
    step's argmax is the next one's token)."""
    if only not in (None, name):
        return
    with open(os.path.join(root, "chipbench", "configs",
                           config + ".json")) as f:
        os.makedirs(os.path.join(out, name), exist_ok=True)
        with open(os.path.join(out, name, "config.json"), "w") as g:
            json.dump(model_keys(json.load(f)), g)
    fcfg = get_model_config(os.path.join(out, name))
    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(lambda: module.init_params(fcfg, jax.random.key(0))))
    seen = set()
    for mode, rows, width, tables in shapes:
        last = mode != "decode"
        # A mode's first shape keeps the plain name (what earlier trees'
        # digests are compared by); a further one names its shape.
        label = (f"{name}.{mode}" if mode not in seen
                 else f"{name}.{mode}.{width}x{tables}")
        seen.add(mode)

        def fn(p, kv, tok, pos, slot, bt, cl, sl):
            return module.apply(
                p, fcfg, tok, pos, kv, slot, bt, cl, sl, mode=mode,
                last_token=jnp.maximum(sl - 1, 0) if last else None,
                with_stats=True)

        def burst(p, kv, tok, pos, slots, bt, cl):
            def step(carry, step_slots):
                tokens, kv, s = carry
                logits, kv, stats = module.apply(
                    p, fcfg, tokens[:, None], (pos + s)[:, None], kv,
                    step_slots[:, None], bt, cl + s, jnp.ones_like(cl),
                    mode="decode", with_stats=True)
                sampled = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                return (sampled, kv, s + 1), (sampled, stats)

            (_, kv, _), (out, stats) = jax.lax.scan(
                step, (tok, kv, jnp.int32(0)), slots.T)
            return out.T, kv, stats.sum(axis=0)

        if mode == BURST:
            digest(label, burst, 1, params, pool(fcfg), spec((rows,)),
                   spec((rows,)), spec((rows, width)), spec((rows, tables)),
                   spec((rows,)))
            continue
        digest(label, fn, 1, params, pool(fcfg),
               spec((rows, width)), spec((rows, width)), spec((rows, width)),
               spec((rows, tables)), spec((rows,)), spec((rows,)))


def laguna_pool(c):
    pages = spec((c.num_layers, 256, BS, c.num_kv_heads, c.head_dim),
                 jnp.bfloat16)
    return pages, pages


def lfm2_pool(c):
    """Packed pages in the attention layers and, the pool's third side,
    a state per block in the convolution layers."""
    from production_stack_tpu.engine.core import kv_page_dims
    from production_stack_tpu.models.registry import block_state_shape

    layers, page_rows, lanes = kv_page_dims(c)
    pages = spec((layers, 1024, BS, page_rows, lanes), jnp.bfloat16)
    held = block_state_shape(c)
    return pages, pages, spec((held[0], 1024) + held[1:], jnp.bfloat16)


from production_stack_tpu.models import laguna

family_digests("laguna", "laguna-s-2.1-l8e64", laguna, laguna_pool,
               (("decode", 128, 1, 16), ("prefill", 4, 512, 16),
                ("prefill_cached", 1, 256, 32), (BURST, 128, 8, 16)))
try:  # a tree before PR 36 has no such family
    from production_stack_tpu.models import lfm2
except ImportError:
    lfm2 = None
if lfm2 is not None:  # (PR 40)
    family_digests("lfm2", "lfm2-24b-a2b-l10", lfm2, lfm2_pool,
                   (("decode", 32, 1, 64), ("prefill", 4, 512, 8),
                    ("prefill_cached", 1, 1024, 64), (BURST, 32, 8, 64)))


def latent_pool(c):
    """The two unequal sides of a latent cache (Family.page_sides)."""
    from production_stack_tpu.engine.core import kv_page_sides

    layers, *sides = kv_page_sides(c)
    return tuple(spec((layers, 1024, BS) + side, jnp.bfloat16)
                 for side in sides)


try:  # a tree before PR 41 has no such family
    from production_stack_tpu.models import longcat
except ImportError:
    longcat = None
if longcat is not None:  # (PR 44) the cell's chunk is 1,024 positions;
    # (PR 45) a whole chunk under the two shorter tables its prompts take,
    # and the tails on both sides of the cached prefill's crossover
    family_digests("longcat", "longcat-flash-l4e16", longcat, latent_pool,
                   (("decode", 128, 1, 64), ("prefill", 1, 1024, 16),
                    ("prefill_cached", 1, 1024, 128),
                    ("prefill_cached", 1, 1024, 64),
                    ("prefill_cached", 1, 1024, 32),
                    ("prefill_cached", 1, 512, 32),
                    ("prefill_cached", 1, 256, 32),
                    ("prefill_cached", 1, 128, 32), (BURST, 128, 8, 64)))
try:  # a tree before PR 44 has no such family
    from production_stack_tpu.models import glm4_moe_lite
except ImportError:
    glm4_moe_lite = None
if glm4_moe_lite is not None:  # (PR 45) every cached bucket the agent
    # cell's turns take under its 128-block table
    family_digests("glm", "glm-4.7-flash-e8v8", glm4_moe_lite, latent_pool,
                   (("decode", 32, 1, 128), ("prefill", 1, 512, 8),
                    ("prefill_cached", 1, 256, 128),
                    ("prefill_cached", 1, 512, 128),
                    ("prefill_cached", 1, 1024, 128), (BURST, 32, 8, 128)))
try:  # a tree before PR 48 has no such family
    from production_stack_tpu.models import ouro
except ImportError:
    ouro = None


def ouro_pool(c):
    """A page layer for every pass of every layer (192 at Ouro-2.6B's
    sizes), 72 blocks of 64 tokens: about what one chip holds."""
    from production_stack_tpu.engine.core import kv_page_dims

    layers, rows, lanes = kv_page_dims(c)
    pages = spec((layers, 72, BS, rows, lanes), jnp.bfloat16)
    return pages, pages


if ouro is not None:  # (PR 48) the reasoning cell's rows, its usual
    # cached bucket under the 32-block table, a whole plain chunk
    family_digests("ouro", "ouro-2.6b", ouro, ouro_pool,
                   (("decode", 8, 1, 32), ("prefill", 1, 512, 32),
                    ("prefill_cached", 1, 256, 32), (BURST, 8, 8, 32)))


try:  # a tree before PR 51 has no such family
    from production_stack_tpu.models import smallthinker
except ImportError:
    smallthinker = None


def smallthinker_pool(c):
    """Four kv heads of 128 a token and layer, 5,120 blocks of 64 tokens:
    about what one chip holds beside the 7.93 GB of weights."""
    pages = spec((c.num_layers, 5120, BS, c.num_kv_heads, c.head_dim),
                 jnp.bfloat16)
    return pages, pages


if smallthinker is not None:  # (PR 51) the mixed-sessions cell's rows
    # under the tables of a 4k and a 16k context, a whole plain chunk, a
    # whole cached chunk under the widest table
    family_digests("smallthinker", "smallthinker-21b-a3b-l8", smallthinker,
                   smallthinker_pool,
                   (("decode", 32, 1, 64), ("prefill", 1, 1024, 16),
                    ("prefill_cached", 1, 1024, 256),
                    ("decode", 32, 1, 256), (BURST, 32, 8, 256)))


def tail_digests(shapes):
    """``tail.decode_k8.<rows>x<vocab>`` (PR 49): the sampling tail of a
    decode burst alone, 8 steps from ``[rows, vocab]`` logits to a token
    and its logprobs a row, which the families' ``decode_k8`` leave out.
    The bursts are those of this script's ``tests/test_sampling_tail.py``:
    ``_burst`` drives the root's ``engine/sampling.py``; a root from
    before PR 49 has no such functions and is given the tail it ran,
    which that file keeps as its reference."""
    if only not in (None, "tail"):
        return
    sys.path.append(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "tests"))
    import test_sampling_tail as tail
    from production_stack_tpu.engine import sampling

    burst = (tail._burst if hasattr(sampling, "burst_terms")
             else tail._reference_burst)
    f32 = jnp.float32
    for rows, vocab in shapes:
        digest(f"tail.{BURST}.{rows}x{vocab}",
               lambda *operands: burst(*operands, max_top_k=64), 1,
               spec((8, rows, vocab), f32), spec((rows, vocab)),
               spec((rows, 8)), spec((rows,), f32), spec((rows,)),
               spec((rows,), f32), spec((rows,)), spec((rows,), f32),
               spec((rows,), f32), spec((rows,)), spec((rows,)),
               spec((rows, sampling.MAX_LOGIT_BIAS)),
               spec((rows, sampling.MAX_LOGIT_BIAS), f32),
               spec((rows, sampling.MAX_STOP_IDS)),
               spec((rows, sampling.MAX_STOP_IDS), f32),
               spec((rows, (vocab + 7) // 8), jnp.uint8),
               spec((rows,), jnp.bool_))


# the widest vocabulary (lfm2-sessions), the largest logits (Laguna's
# 128 rows), the fullest chip (LongCat's)
tail_digests(((32, 65536), (128, 25088), (128, 16384)))
with open(os.path.join(out, "digests.json"), "w") as f:
    json.dump({"text": digests, "ops": ops, "memory": memory}, f, indent=1)
