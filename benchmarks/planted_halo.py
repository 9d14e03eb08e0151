#!/usr/bin/env python3
"""The correctness check with a fault planted: a cached prefill (and a
decode step) that begins from zeros and not from its cache block's state
(``models/decoder.py::read_block_state``). Shown once on the chip for
``lfm2-24b-a2b-l10`` (PERF.md section 6, PR 36): the check's second prompt
prefills behind a cached block and every decode row resumes from its
own, so a limit has to fail.

    python benchmarks/planted_halo.py <configuration> <seed>[,<seed>...]
"""
import json
import os
import sys

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")))

import jax.numpy as jnp  # noqa: E402

from chipbench.control import read_seeds  # noqa: E402
from production_stack_tpu.models import decoder  # noqa: E402


def zero_halo(state, at, batch, block_size):
    return jnp.zeros((batch.positions.shape[0],) + state.shape[2:],
                     state.dtype)


if __name__ == "__main__":
    decoder.read_block_state = zero_halo
    print(json.dumps({"planted": "zero_halo"}), flush=True)
    # read_seeds prints a line a seed
    list(read_seeds(sys.argv[1], [int(s) for s in sys.argv[2].split(",")]))
