"""The one layer loop every model family runs (models/decoder.py:
``scan_layers``, ``by_layer``, ``take``, ``index_in_kind``), on a toy of
five layers with two operators and two MLPs: what a family with layers of
several kinds hands it (docs/engine.md, "Layers of several kinds").
Integers throughout, so that "equal" is exact.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models import decoder

KINDS = ["a", "b", "a", "a", "b"]  # the operator of each layer
L, DENSE = len(KINDS), 2  # two leading dense MLPs, then three sparse
ROWS, WIDTH, BLOCKS = 8, 128, 4  # a side: [layers of a kind, BLOCKS, ROWS, WIDTH]
IS_A = np.asarray([kind == "a" for kind in KINDS])
IS_DENSE = np.arange(L) < DENSE
COUNTED = ("dense_layers", "positives")


def _toy(seed=0):
    """(x, the pool's two sides: one entry per layer of a kind, params)."""
    rng = np.random.default_rng(seed)

    def ints(*shape):
        return jnp.asarray(rng.integers(-2, 3, shape), jnp.int32)

    params = {"a": {"w": ints(KINDS.count("a"), WIDTH, WIDTH)},
              "b": {"w": ints(KINDS.count("b"), WIDTH, WIDTH),
                    "bias": ints(KINDS.count("b"), WIDTH)},
              "dense": {"w": ints(DENSE, WIDTH, WIDTH)},
              "sparse": {"w": ints(L - DENSE, WIDTH, WIDTH)}}
    sides = (ints(KINDS.count("a"), BLOCKS, ROWS, WIDTH),
             ints(KINDS.count("b"), BLOCKS, ROWS, WIDTH))
    return ints(ROWS, WIDTH), sides, params


def _layers(params, take):
    """The toy's two operators and two MLPs; ``take(stack, index)``
    reads a layer's leaves. An operator writes block 1 of its own side at
    its kind's count and hands the other side back as it got it."""
    at = decoder.index_in_kind(KINDS)

    def op_a(x, sides, layer):
        mine, other = sides
        out = x @ take(params["a"], at[layer])["w"]
        return out, (mine.at[at[layer], 1].set(out), other)

    def op_b(x, sides, layer):
        other, mine = sides
        p = take(params["b"], at[layer])
        out = x @ p["w"] + p["bias"]
        return out, (other, mine.at[at[layer], 1].add(out))

    def dense_mlp(x, layer):
        return (x @ take(params["dense"], layer)["w"],
                jnp.asarray([1, 0], jnp.int32))

    def sparse_mlp(x, layer):
        out = jnp.maximum(x @ take(params["sparse"], layer - DENSE)["w"], 0)
        return out, jnp.stack([jnp.int32(0), jnp.sum(out > 0, dtype=jnp.int32)])

    return op_a, op_b, dense_mlp, sparse_mlp


def _unrolled(x, sides, params):
    """The layers one by one in Python: no scan, no ``cond``."""
    op_a, op_b, dense_mlp, sparse_mlp = _layers(
        params, lambda stack, i: {k: v[i] for k, v in stack.items()})
    counts = jnp.zeros((len(COUNTED),), jnp.int32)
    for layer, kind in enumerate(KINDS):
        out, sides = (op_a if kind == "a" else op_b)(x, sides, layer)
        x = x + out
        out, s = (dense_mlp if layer < DENSE else sparse_mlp)(x, layer)
        x, counts = x + out, counts + s
    return x, sides, counts


def _step(params, mlp=None, choose=decoder.by_layer):
    """The toy's step for ``scan_layers``: both MLPs behind ``by_layer``,
    or the one kind ``mlp`` names (a stretch of its own)."""
    op_a, op_b, dense_mlp, sparse_mlp = _layers(params, decoder.take)

    def step(x, sides, layer, _):
        out, sides = choose(IS_A, layer, op_a, op_b, x, sides, layer)
        x = x + out
        if mlp is None:
            out, s = choose(IS_DENSE, layer, dense_mlp, sparse_mlp, x, layer)
        else:
            out, s = {"dense": dense_mlp, "sparse": sparse_mlp}[mlp](x, layer)
        return x + out, sides, s

    return step


def _one_stretch(x, sides, params):
    return decoder.scan_layers(
        _step(params), decoder.first_carry(x, sides, COUNTED), L)


def _two_stretches(x, sides, params):
    carry = decoder.first_carry(x, sides, COUNTED)
    carry = decoder.scan_layers(_step(params, "dense"), carry, DENSE)
    return decoder.scan_layers(_step(params, "sparse"), carry, L - DENSE)


def _same(got, wanted):
    for mine, theirs in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(wanted), strict=True):
        np.testing.assert_array_equal(mine, theirs)


@pytest.mark.parametrize("loop", [_one_stretch, _two_stretches],
                         ids=["one-stretch", "two-stretches"])
def test_the_loop_is_the_layers_one_by_one(loop):
    """One stretch with every choice behind ``by_layer``, and the dense
    prefix and the sparse layers as a stretch each (no ``cond`` between
    the MLPs, the layer's number running on): both give exactly what the
    unrolled layers give, activations, both sides of the pool and the
    counts summed over all layers."""
    x, sides, params = _toy()
    wanted = _unrolled(x, sides, params)
    assert wanted[2][0] == DENSE and wanted[2][1] > 0
    *got, layer = jax.jit(loop)(x, sides, params)
    _same(got, wanted)
    assert layer == L


def test_a_stretch_of_no_layers_returns_its_carry():
    x, sides, params = _toy()
    carry = decoder.first_carry(x, sides, COUNTED)
    assert decoder.scan_layers(_step(params, "dense"), carry, 0) is carry
    # ... and a carry that counts nothing holds no counts at all
    assert decoder.first_carry(x, sides)[2] is None


def test_xs_and_an_extra_entry_ride_beside_the_layers_number():
    """What Llama's and LongCat's loops use: leaves sliced a layer at a
    time as ``xs`` (no counts), and a value one layer hands the next."""
    x, sides, params = _toy()

    def step(x, sides, layer, w, handed):
        return x @ w + handed, sides, None, handed + layer

    got, _, handed, counts, layer = decoder.scan_layers(
        step, decoder.first_carry(x, sides, (), jnp.int32(0)),
        xs=params["a"]["w"])
    wanted, given = x, 0
    for n, w in enumerate(params["a"]["w"]):
        wanted, given = wanted @ w + given, given + n
    np.testing.assert_array_equal(got, wanted)
    assert (handed, counts, layer) == (given, None, KINDS.count("a"))
