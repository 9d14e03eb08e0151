"""The expert layer where its assignments are one row tile (a decode step
of 32 rows x top 4: ``models/moe.py::expert_layer`` on the path
``pallas_one_tile`` of ``ops/pallas_grouped_matmul.py``): the same bits as
the same tokens forced down the sorted path, ``moe_idle_layers`` against
a recount of the routing on the host, and the rule that names the path
from the shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models import moe
from production_stack_tpu.ops import pallas_grouped_matmul as gmm

BF16, F32 = jnp.bfloat16, jnp.float32
HIDDEN, WIDTH, HELD, LAYERS = 128, 256, 4, 3

# name: (chips that share the layer, this chip's share, top k, live tokens
# of the 16 (32 row slots at top 2, 64 at top 4), zero-compute experts)
LAYERS_OF = {
    "every_expert_held": (1, 0, 2, 16, 0),
    "a_block_of_two_chips": (2, 1, 2, 14, 0),
    "one_live_row_one_chip_of_eight": (8, 3, 2, 1, 0),
    "two_live_rows_one_chip_of_eight": (8, 0, 2, 2, 0),
    "no_live_row": (2, 0, 2, 0, 0),
    "top_four": (4, 2, 4, 9, 0),
    "zero_compute_experts_behind": (2, 0, 2, 12, 4),
}


def _layer(name):
    chips, share, top, live, zero = LAYERS_OF[name]
    keys = jax.random.split(jax.random.key(len(name)), 5)
    p = {"router": jax.random.normal(
            keys[0], (HIDDEN, HELD * chips + zero), F32).astype(BF16),
         "w_gate": (jax.random.normal(keys[1], (LAYERS, HELD, HIDDEN, WIDTH))
                    / np.sqrt(HIDDEN)).astype(BF16),
         "w_up": (jax.random.normal(keys[2], (LAYERS, HELD, HIDDEN, WIDTH))
                  / np.sqrt(HIDDEN)).astype(BF16),
         "w_down": (jax.random.normal(keys[3], (LAYERS, HELD, WIDTH, HIDDEN))
                    / np.sqrt(WIDTH)).astype(BF16)}
    h = jax.random.normal(keys[4], (16, 1, HIDDEN), F32).astype(BF16)
    valid = (jnp.arange(16) < live)[:, None]
    return p, h, valid, dict(k=top, share=share, scaling=2.5,
                             zero_experts=zero)


@pytest.mark.parametrize("at", [0, 2])
@pytest.mark.parametrize("name", LAYERS_OF)
def test_one_tile_is_the_sorted_path_bit_for_bit(monkeypatch, name, at):
    """``expert_layer`` at ``m == tm`` against the same tokens forced down
    the sorted path (the kernel interpreted on both): the same bits out
    and the same counts, ``moe_idle_layers`` as the routing recounted on
    the host has it, and nothing undefined in a layer that had no row."""
    p, h, valid, kwargs = _layer(name)
    monkeypatch.setattr(gmm, "_use_pallas", lambda: True)
    path_of_shapes = gmm.grouped_matmul_path

    def run(path_of):
        monkeypatch.setattr(gmm, "grouped_matmul_path", path_of)
        gmm.TRACED_PATHS.clear()
        y, stats = jax.jit(lambda h: moe.expert_layer(
            h, p, at=jnp.int32(at), valid=valid, **kwargs))(h)
        (_, path), = gmm.TRACED_PATHS
        return np.asarray(y.astype(F32)), np.asarray(stats).tolist(), path

    y, stats, path = run(path_of_shapes)
    assert path == "pallas_one_tile"
    y_sorted, stats_sorted, path = run(lambda *a, **k: "pallas")
    assert path == "pallas"
    assert np.array_equal(y, y_sorted) and not np.isnan(y).any()
    assert stats == stats_sorted

    _, experts = moe.route(h.reshape(16, HIDDEN), p["router"], kwargs["k"],
                           scaling=2.5)
    local = np.asarray(experts) - kwargs["share"] * HELD
    landed = local[(local >= 0) & (local < HELD) & np.asarray(valid)]
    counted = dict(zip(moe.STATS, stats))
    assert counted["moe_assignments"] == landed.size
    assert counted["moe_experts_hit"] == np.unique(landed).size
    assert counted["moe_idle_layers"] == (landed.size == 0)
    if not landed.size:
        assert not y.any()
    assert (LAYERS_OF[name][3] == 0) <= (landed.size == 0)


def test_idle_layers_add_up_over_a_forward(monkeypatch):
    """A family sums each layer's :data:`moe.STATS` on its scan's carry:
    over a stack of layers with routers of their own, ``moe_idle_layers``
    is the number of them whose held experts received no row, and a dense
    layer adds none."""
    p, h, valid, kwargs = _layer("two_live_rows_one_chip_of_eight")
    monkeypatch.setattr(gmm, "_use_pallas", lambda: True)
    depth = 4 * LAYERS  # each stacked layer four times, a router a time
    routers = jax.random.normal(
        jax.random.key(50), (depth,) + p["router"].shape, F32).astype(BF16)

    @jax.jit
    def forward(h):
        def layer(total, xs):
            at, router = xs
            _, stats = moe.expert_layer(
                h, {**p, "router": router}, at=at % LAYERS, valid=valid,
                **kwargs)
            return total + stats, stats

        dense = moe.dense_layer(
            h, {"w_gate": p["w_gate"][:, 0], "w_up": p["w_up"][:, 0],
                "w_down": p["w_down"][:, 0]}, 0)[1]
        return jax.lax.scan(layer, dense, (jnp.arange(depth), routers))

    total, each = forward(h)
    idle = moe.STATS.index("moe_idle_layers")
    want = 0
    for router in routers:
        _, experts = moe.route(h.reshape(16, HIDDEN), router, kwargs["k"])
        local = np.asarray(experts)[:2] - kwargs["share"] * HELD
        want += not ((local >= 0) & (local < HELD)).any()
    assert int(total[idle]) == want == int(np.asarray(each)[:, idle].sum())
    assert 0 < want < depth  # one chip of eight: some idle, some not


# (hidden, expert width, held, top k) of the configurations with a cell.
GLM, LFM2, LAGUNA, LONGCAT = ((2048, 1536, 8, 4), (2048, 1536, 64, 4),
                              (3072, 1024, 64, 10), (6144, 2048, 16, 12))


@pytest.mark.parametrize("widths,tokens,path", [
    (GLM, 32, "pallas_one_tile"), (LFM2, 32, "pallas_one_tile"),
    (GLM, 256, "pallas"), (LFM2, 128, "pallas"), (GLM, 64, "pallas"),
    (LAGUNA, 128, "pallas"), (LAGUNA, 32, "pallas"),
    (LONGCAT, 128, "pallas"), (LONGCAT, 16, "pallas"),
    (GLM, 16, "pallas_one_tile"),  # 64 row slots: one tile of 64
    (GLM, 3, "xla"),  # 12 row slots do not tile
])
def test_the_path_is_one_tile_exactly_where_the_rows_are(monkeypatch, widths,
                                                         tokens, path):
    """On the TPU's rules ``grouped_matmul_path`` says ``pallas_one_tile``
    where ``tokens x top k`` is the row tile the shapes give, and nowhere
    else: the sessions cells' decode programs, none of the backlog cells'
    and no prefill rung; off the TPU and across devices ``xla`` as ever."""
    hidden, width, held, top = widths
    m = tokens * top
    monkeypatch.setattr(gmm, "_use_pallas", lambda: True)
    assert gmm.grouped_matmul_path(m, hidden, width, BF16, held) == path
    tiles = gmm.grouped_matmul_tiles(m, hidden, width, BF16, held)
    assert (path == "pallas_one_tile") == bool(tiles and tiles[0] == m)
    assert gmm.grouped_matmul_path(m, hidden, width, BF16, held,
                                   devices=4) == "xla"
    monkeypatch.setattr(gmm, "_use_pallas", lambda: False)
    assert gmm.grouped_matmul_path(m, hidden, width, BF16, held) == "xla"
