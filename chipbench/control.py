"""The correctness check read over many seeds, sound and control, in one
process: the readings the limits in a configuration's ``check`` block are
set from (``PERF.md`` has them).

    python -m chipbench.control --config <name> --seeds 1,2,3 [--control <name>]

Without ``--control`` the sound program is read. ``--control`` names an
entry of the configuration's ``controls``: the next precision below the
one the configuration states, which has to come out as not correct.
An entry with ``server_flags`` is the program's own lower-precision path
(int8 KV pages, int8 weights held against the bf16 reference); one with
``reference_activations`` puts the reference in the program's place with
its activations rounded to that type, and builds no engine.

For each seed an engine is built from the configuration's server flags
without warm-up (only the check's few shapes compile), the check's sample
is served and compared with the reference, and the device memory is
given back. One line of JSON per seed.
"""

from __future__ import annotations

import argparse
import json
import os
import time

from chipbench.registry import REPO, Registry


def read_seeds(config_name: str, seeds, control=None, *, root: str = REPO,
               platform: str = "tpu"):
    import jax

    from chipbench.check import run_check
    from chipbench.stack import Stack, write_model_dir
    from production_stack_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    if jax.devices()[0].platform != platform:
        raise RuntimeError(f"needs a {platform}, JAX found "
                           f"{jax.devices()[0].platform}")
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    registry = Registry(root)
    config = registry.config(config_name)
    spec = config["controls"][control] if control else {}
    work = os.path.join(registry.root, ".chipbench_work", "control")
    model_dir = write_model_dir(config, work, config_name)
    flags = [f for f in config["server_flags"] if f != "--no-warmup"]
    flags += ["--no-warmup"] + spec.get("server_flags", [])
    for seed in seeds:
        t0 = time.time()
        if "reference_activations" in spec:
            verdict = run_check(
                registry, config, seed, None,
                reference_activations=spec["reference_activations"])
        else:
            stack = Stack(model_dir, config_name, flags, seed,
                          devices=jax.devices()[:1])
            try:
                verdict = run_check(registry, config, seed, stack.core)
            finally:
                stack.core.stop()
                stack.free_device_memory()
        line = {"config": config_name, "control": control, "seed": seed,
                "correct": verdict["ok"], **verdict["numbers"],
                "limits": verdict["limits"],
                "seconds": round(time.time() - t0, 1)}
        print(json.dumps(line), flush=True)
        yield line


def main(argv=None, **kwargs) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", default=None,
                   help="a name under the configuration's controls")
    p.add_argument("--root", default=REPO)
    a = p.parse_args(argv)
    seeds = [int(s) for s in a.seeds.split(",")]
    list(read_seeds(a.config, seeds, a.control, root=a.root, **kwargs))


if __name__ == "__main__":
    main()
