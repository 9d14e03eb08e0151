"""Tensor-parallel serving parity: the same engine config sharded over a
tp=2 mesh must greedy-generate exactly what the tp=1 engine does (the
sharding rules + GSPMD collectives change the layout, not the math)."""

import threading

import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.core import EngineCore
from production_stack_tpu.engine.sampling import SamplingParams


def _run(core, prompt_ids, max_tokens=8, rid="r"):
    done = threading.Event()
    out = []

    def on_token(tok, finish):
        if tok is not None:
            out.append(tok)
        if finish is not None:
            done.set()

    core.add_request(
        rid, list(prompt_ids),
        SamplingParams(temperature=0.0, max_tokens=max_tokens,
                       ignore_eos=True),
        on_token,
    )
    assert done.wait(timeout=180)
    return out


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_sharded_matches_single_device(tp):
    import jax

    if len(jax.devices()) < tp:
        pytest.skip(f"needs {tp} devices")

    def build(tp_size):
        return EngineCore(
            EngineConfig(
                model="tiny-llama", dtype="float32", max_model_len=128,
                max_num_seqs=2, block_size=8, num_blocks=64, max_loras=0,
                tensor_parallel_size=tp_size, data_parallel_size=1,
                seed=0,
            ),
            devices=jax.devices()[:tp_size],
        )

    rng = np.random.default_rng(21)
    prompt = [int(t) for t in rng.integers(0, 500, size=37)]

    single = build(1)
    single.start()
    try:
        out_single = _run(single, prompt)
    finally:
        single.stop()

    sharded = build(tp)
    # Sanity: the mesh really has tp devices and weights really shard.
    assert sharded.mesh.shape["tp"] == tp
    wqkv_shard = sharded.params["layers"]["wqkv"].sharding
    assert "tp" in str(wqkv_shard.spec)
    sharded.start()
    try:
        out_sharded = _run(sharded, prompt)
    finally:
        sharded.stop()

    assert out_sharded == out_single
