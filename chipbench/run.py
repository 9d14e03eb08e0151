"""One run of one cell of the chip benchmark.

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process builds the engine and the router (``stack``) and so holds
the chip; the load generator is a child process that never imports JAX
(``loadgen``). Order of a run: weights from ``--seed`` and warm-up from
the compile cache, the traffic's preload (histories that exist when the
window opens are prefilled), traffic start, ``ramp_s`` of ramp, the
window of ``--seconds`` (all of that before the window is ``setup_s``),
the drain, then the correctness check against the plain reference. The
last line of standard output is the result object; each number compared
stands beside its limit under its last key, ``compared``, and on the last
lines of standard error. Without a TPU the run
fails: there is no CPU leg (the tests steer a tiny model through the
same code by calling :func:`run_cell` with ``platform="cpu"``).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import random
import sys
import time
import types

from chipbench import schedule, timeline
from chipbench.registry import REPO, Registry

TRACE_START_S = 2.0  # into the window
# Long enough for some tens of decode bursts, short enough that the trace
# stays a few tens of MB. The Python tracer is off: under serving load it
# writes some hundred thousand events a second, and a trace of that size
# comes back without the device's plane (chip run, PR 24).
TRACE_SECONDS = 2.0


def process_start_unix() -> float:
    """When this process started, from /proc (Linux); else now."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            boot = next(int(line.split()[1]) for line in f
                        if line.startswith("btime"))
        return boot + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration, IndexError):
        return time.time()


class CompileWatch:
    """Times at which JAX compiled (or fetched from its cache) a program,
    from ``jax.monitoring``'s duration events."""

    NAMES = ("/jax/core/compile/backend_compile_duration",
             "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring

        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, seconds, **kwargs):
        if name in self.NAMES:
            self.events.append((time.time(), name))

    def between(self, start: float, end: float) -> int:
        return sum(1 for t, _ in self.events if start <= t < end)


def router_overhead(text: str):
    """(sum, count) of ``vllm_router:router_overhead_seconds``."""
    total = count = 0.0
    for line in text.splitlines():
        if line.startswith("vllm_router:router_overhead_seconds_sum"):
            total += float(line.rsplit(" ", 1)[1])
        elif line.startswith("vllm_router:router_overhead_seconds_count"):
            count += float(line.rsplit(" ", 1)[1])
    return total, count


async def _snapshot(stack, session) -> dict:
    async with session.get(stack.url + "/metrics") as resp:
        router_text = await resp.text()
    return {"unix": time.time(), "engine": stack.core.stats(),
            "router_overhead": router_overhead(router_text)}


async def _prefill_only(stack, session, prompts, served_name,
                        at_once: int) -> None:
    """Serve each prompt with two tokens of answer (a prefill and one
    decode burst), ``at_once`` at a time."""
    gate = asyncio.Semaphore(at_once)

    async def one(tokens):
        async with gate:
            body = {"model": served_name, "prompt": tokens, "max_tokens": 2,
                    "temperature": 0.0, "ignore_eos": True}
            async with session.post(stack.url + "/v1/completions",
                                    json=body) as resp:
                if resp.status != 200:
                    raise RuntimeError(
                        f"set-up request failed: {resp.status} "
                        f"{(await resp.text())[:200]}")
                await resp.read()

    await asyncio.gather(*[one(t) for t in prompts])


async def _drive(stack, plan, sched, traffic, seconds, trace, work):
    """Preload, start the generator child, watch the window; returns the
    measurement's raw material."""
    import aiohttp

    start, end = sched["window"]
    timeout = aiohttp.ClientTimeout(total=900)
    async with aiohttp.ClientSession(timeout=timeout) as session:
        rng = random.Random(traffic["traffic_seed"] + 1)
        warm = [[rng.randrange(259, plan["vocab"]) for _ in range(n)]
                for n in traffic.get("warm_prompt_tokens", [])]
        # ``warm_prompt_tokens``: unshared prompts of those lengths, one
        # at a time, so that each runs its prefill program and a decode
        # burst at its own block-table width. The engine's warm-up leaves
        # out the decode programs as serving calls them (the previous
        # burst's tokens arrive as a device array, warm-up passes a host
        # array: another program) and plain prefill at tables wider than
        # its chunk; without this they are fetched inside the window.
        await _prefill_only(stack, session, warm, plan["model"], 1)
        # the histories that exist when traffic starts
        await _prefill_only(stack, session, sched["preload"], plan["model"],
                            4)
        plan["start_unix"] = t0 = time.time() + 2.0
        plan_path = os.path.join(work, "plan.json")
        result_path = os.path.join(work, "result.json")
        with open(plan_path, "w") as f:
            json.dump(plan, f)
        if os.path.exists(result_path):
            os.remove(result_path)
        env = dict(os.environ, PYTHONPATH=os.pathsep.join(
            [REPO] + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
        child = await asyncio.create_subprocess_exec(
            sys.executable, "-m", "chipbench.loadgen", plan_path,
            result_path, env=env, cwd=REPO)
        try:
            await asyncio.sleep(max(0.0, t0 + start - time.time()))
            before = await _snapshot(stack, session)
            profile = traced = None
            if trace:
                import jax

                await asyncio.sleep(TRACE_START_S)
                profile = os.path.join(work, "profile",
                                       time.strftime("%Y%m%d-%H%M%S"))
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(profile, profiler_options=options)
                traced = [time.time()]
                await asyncio.sleep(min(TRACE_SECONDS, seconds / 2))
                traced.append(time.time())
                await asyncio.get_running_loop().run_in_executor(
                    None, jax.profiler.stop_trace)
            await asyncio.sleep(max(0.0, t0 + end - time.time()))
            after = await _snapshot(stack, session)
            rc = await asyncio.wait_for(child.wait(), 240)
        finally:
            if child.returncode is None:
                child.kill()
                await child.wait()
        if rc != 0:
            raise RuntimeError(f"the load generator exited with {rc}")
    with open(result_path) as f:
        result = json.load(f)
    if result["generator_modules_jax"]:
        raise RuntimeError("the load generator imported JAX")
    return {"t0": t0, "before": before, "after": after,
            "records": result["records"], "profile": profile,
            "traced": traced}


def _device_block(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


class Cell:
    """One cell's engine and router, built once; ``measure`` drives a
    window through them (the sweep drives several)."""

    def __init__(self, workload: str, seed: int, *, root: str = REPO,
                 platform: str = "tpu"):
        self.born = process_start_unix()
        if not 0 <= seed < 2 ** 32:
            raise ValueError("--seed must be in [0, 2**32)")
        self.seed = seed
        self.registry = Registry(root)
        self.cell = self.registry.workload(workload)
        self.config = self.registry.config(self.cell["config"])
        self.traffic = self.registry.traffic(self.cell["traffic"])

        import jax
        import production_stack_tpu

        from production_stack_tpu.utils.compile_cache import (
            configure_compile_cache,
        )

        program = os.path.dirname(os.path.dirname(os.path.abspath(
            production_stack_tpu.__file__)))
        if program != REPO:
            raise RuntimeError(f"the program under test was imported from "
                               f"{program}, not from this checkout ({REPO})")
        devices = jax.devices()
        if devices[0].platform != platform:
            raise RuntimeError(f"needs a {platform}, JAX found "
                               f"{devices[0].platform}: no result")
        if len(devices) < self.cell["chips"]:
            raise RuntimeError(f"the cell needs {self.cell['chips']} chips, "
                               f"JAX found {len(devices)}: no result")
        self.devices = devices[:self.cell["chips"]]
        if os.environ.get("TPU_STACK_FORCE_XLA_ATTENTION"):
            raise RuntimeError("TPU_STACK_FORCE_XLA_ATTENTION is set: the "
                               "kernels would be bypassed")
        configure_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_log_compiles", True)  # to stderr
        self.watch = CompileWatch()
        self.work = os.path.join(self.registry.root, ".chipbench_work",
                                 workload)
        os.makedirs(self.work, exist_ok=True)
        from chipbench.stack import Stack, write_model_dir

        model_dir = write_model_dir(self.config, self.work,
                                    self.cell["config"])
        flags = [*self.config["server_flags"], "--profile-dir",
                 os.path.join(self.work, "profile")]
        self.stack = Stack(model_dir, self.cell["config"], flags, seed,
                           devices=self.devices)

    async def measure(self, seconds: float, trace: bool, *, traffic=None,
                      check: bool = True) -> dict:
        """One window (the servers must be started); the result object."""
        traffic = traffic or self.traffic
        sched = schedule.build(self.registry, traffic, seconds,
                               self.config["vocab_size"])
        plan = {"url": self.stack.url, "model": self.cell["config"],
                "vocab": self.config["vocab_size"],
                "window": sched["window"],
                "max_outstanding": traffic.get("max_outstanding", 0),
                "stop_at": (sched["window"][1] + 0.5
                            if traffic.get("stop_after_window") else None),
                "requests": sched["requests"]}
        raw = await _drive(self.stack, plan, sched, traffic, seconds, trace,
                           self.work)
        verdict = None
        if check:
            from chipbench.check import run_check

            await asyncio.sleep(1.0)  # dropped requests leave their rows
            verdict = await asyncio.get_running_loop().run_in_executor(
                None, run_check, self.registry, self.config, self.seed,
                self.stack.core)
        return self._reduce(traffic, sched, raw, verdict, trace)

    def _reduce(self, traffic, sched, raw, verdict, trace) -> dict:
        """The result object from a window's raw material."""
        registry, stack, devices = self.registry, self.stack, self.devices
        start, end = sched["window"]
        t0 = raw["t0"]
        records = raw["records"]
        due = timeline.in_window(records, start, end)
        # a backlog drops what is still running when the window has closed
        judged = due if not traffic.get("stop_after_window") else [
            r for r in records if r["error"] != "dropped at stop_at"
            and (r["end"] is not None or r["error"])]
        e2e = timeline.end_to_end(records, start, end)
        e2e["setup_s"] = t0 + start - self.born

        # every answer the run finished has exactly its scheduled length
        recorder = stack.server.trace_recorder
        traces = {r["id"]: recorder.get(r["id"]) for r in judged + due
                  } if recorder is not None else {}
        wrong_length = [
            r["id"] for r in judged
            if r["ok"] and traces.get(r["id"]) is not None
            and traces[r["id"]].root.attributes.get("tokens")
            not in (None, r["out_tokens"])]
        failed = sum(1 for r in judged if not r["ok"])

        ctx = types.SimpleNamespace(
            records=records, due=due, window=(start, end), traffic=traffic,
            config=self.config,
            before=raw["before"], after=raw["after"],
            steps=[s for s in stack.core.step_recorder.snapshot()
                   if t0 + start <= s["ts_unix"] < t0 + end]
            if stack.core.step_recorder is not None else [],
            traces=[traces[r["id"]].to_dict() for r in due
                    if traces.get(r["id"]) is not None],
            compiles_in_window=self.watch.between(t0 + start, t0 + end),
            device_kind=devices[0].device_kind, device=None, traced_steps=[],
            kv_cache_dtype=stack.core.config.kv_cache_dtype)
        device = _device_block(devices)
        out = {"correct": None, "attempted": len(judged), "failed": failed}
        group = "per_layer" if trace else "end_to_end"
        if trace:
            from chipbench import xplane

            ctx.device = xplane.reduce(xplane.load(raw["profile"]))
            ctx.traced_steps = [s for s in ctx.steps if raw["traced"][0]
                                <= s["ts_unix"] < raw["traced"][1]]
            device["busy_s"] = ctx.device["busy_s"]
            device["window_s"] = ctx.device["window_s"]
            out["breakdown"] = {"device_ops": ctx.device["device_ops"],
                                "idle_gaps": ctx.device["idle_gaps"]}
        metrics = {}
        for m in registry.metrics_for(group, self.cell["name"]):
            if group == "end_to_end":
                value = e2e.get(m["name"])
            else:
                spec = registry.load_json("metrics", m["name"])
                reader = registry.module("readers", spec["reader"])
                value = reader.read(ctx, spec.get("params", {}))
            if value is None or (isinstance(value, float) and math.isnan(value)):
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

        compared = []
        if verdict is not None:
            for name, limit in verdict["limits"].items():
                compared.append((name, verdict["numbers"][name], limit))
        compared.append(("failed_requests", failed, 0))
        compared.append(("answers_not_of_scheduled_length", len(wrong_length), 0))
        print(f"setup: weights from the seed {stack.weights_seconds:.1f} s, "
              f"engine and weights {stack.start_seconds:.1f} s of which warm-up "
              f"{stack.core.warmup_seconds:.1f} s, to window open "
              f"{e2e['setup_s']:.1f} s", flush=True)
        for name, value, limit in compared:
            print(f"compared: {name} = {value} (limit {limit})", flush=True)
        if verdict is not None:
            print("check numbers: " + json.dumps(verdict["numbers"]), flush=True)
        out["correct"] = bool(all(v <= lim for _, v, lim in compared)
                              and len(judged) > 0
                              and all(math.isfinite(m["value"])
                                      for m in metrics.values()))
        out["metrics"] = metrics
        out["device"] = device
        limits = traffic.get("limits") or {}
        if due and "ttft_limit_s" in limits:
            out["extra"] = {
                "requests_due": len(due),
                "slo_met_pct": timeline.slo_met_pct(
                    due, limits["ttft_limit_s"], limits["tpot_limit_s"]),
                "ttft_p50_s": e2e.get("ttft_p50_s"),
                "ttft_p95_s": e2e.get("ttft_p95_s")}
        # last in the line: what a record of a run that is not correct keeps
        out["compared"] = {name: {"value": value, "limit": limit}
                           for name, value, limit in compared}
        return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             **kwargs) -> dict:
    """Run the cell once; returns the result object (see the module
    text)."""
    cell = Cell(workload, seed, **kwargs)

    async def session_main():
        await cell.stack.start()
        try:
            return await cell.measure(seconds, trace)
        finally:
            await cell.stack.stop()

    return asyncio.run(session_main())


def main(argv=None, **kwargs) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--root", default=REPO,
                   help="directory that holds BENCHMARK.json")
    a = p.parse_args(argv)
    result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                      root=a.root, **kwargs)
    print(json.dumps(result), flush=True)
    for name, c in result["compared"].items():
        print(f"compared: {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
