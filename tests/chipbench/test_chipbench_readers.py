"""The readers of the engine loop's own instrumentation, each on hand-made
``ctx`` data, on what a program without the instrumentation gives (nothing,
and no exception), and, for those that read the trace file, on an extended
copy of the small recorded-format trace (``data/phases.xplane.pbtxt``):

  XLA Modules   jit_prefill_cached(1)  0 .. 12000 us
                jit_decode_k8(2)       14000 .. 20000 us
  XLA Ops       while.3 0..10000 (parent of the next two, scope-less)
                pallas_paged_attention.7 1000..4000  (attention; a kernel)
                fusion.12 5000..7000                 (mlp)
                fusion.40 10020..12000               (attn_proj/lora)
                pallas_paged_attention.7 14000..15000
                fusion.50 19000..20000               (head)
  host          engine.step 11000..19500, engine.readback 12500..13500,
                engine.build 15500..18500

``reg`` (``conftest``) is the repo's own root, then its copy with a later
PR's addition: the metric files come from the root, the readers from
beside the module.
"""

import os
import types

import pytest

from chipbench import xplane

DATA = os.path.join(os.path.dirname(__file__), "data")


def _read(reg, metric, ctx):
    spec = reg.load_json("metrics", metric)
    return reg.module("readers", spec["reader"]).read(
        ctx, spec.get("params", {}))


def _ctx(**over):
    base = dict(steps=[], traced_steps=[], traces=[], device=None,
                records=[], due=[], window=(0.0, 51.0))
    base.update(over)
    return types.SimpleNamespace(**base)


def _trace(queue_ms, decode_ms, prefill_ms, first_ms):
    other = queue_ms - decode_ms - prefill_ms
    return {"spans": [
        {"name": "engine.queue", "duration_s": queue_ms / 1e3,
         "attributes": {"behind_decode_s": decode_ms / 1e3,
                        "behind_prefill_s": prefill_ms / 1e3,
                        "behind_other_s": other / 1e3, "steps_waited": 2}},
        {"name": "engine.first_token", "duration_s": first_ms / 1e3,
         "attributes": {}}]}


def _step(kind, wall, phases, gap, live, cached, free):
    return {"kind": kind, "wall_s": wall, "forwards": 1, "phases": phases,
            "gap_phases": gap, "kv_blocks_live": live,
            "kv_blocks_cached": cached, "kv_blocks_free": free}


# what the parent commit's program gives: no attributes, no phases
OLD_TRACE = {"spans": [{"name": "engine.queue", "duration_s": 0.2,
                        "attributes": {}}]}
OLD_STEP = {"kind": "decode_burst", "wall_s": 0.1, "forwards": 8}


def test_queue_causes_and_first_token(reg):
    traces = [_trace(100 + 10 * i, 50 + i, 20 + 2 * i, 60 + 5 * i)
              for i in range(11)]
    ctx = _ctx(traces=traces)
    assert _read(reg, "queue_behind_decode_p90_ms", ctx) == pytest.approx(59)
    assert _read(reg, "queue_behind_prefill_p90_ms", ctx) == pytest.approx(38)
    assert _read(reg, "first_token_defer_p90_ms", ctx) == pytest.approx(105)
    old = _ctx(traces=[OLD_TRACE] * 3)
    for metric in ("queue_behind_decode_p90_ms",
                   "queue_behind_prefill_p90_ms", "first_token_defer_p90_ms"):
        assert _read(reg, metric, old) is None
        assert _read(reg, metric, _ctx()) is None


@pytest.mark.parametrize("suffix", ["serve", "batch"])
def test_loop_host_and_pool(reg, suffix):
    steps = [
        _step("decode_burst", 0.1,
              {"build": 0.002, "enqueue": 0.001, "readback": 0.09,
               "emit": 0.003}, {"schedule": 0.0005, "idle_wait": 0.5},
              300, 600, 100),
        _step("prefill", 0.06, {"build": 0.001, "enqueue": 0.0005},
              {"schedule": 0.0002}, 100, 800, 100)]
    ctx = _ctx(steps=steps)
    # (2 + 1 + 3 + 0.5) and (1 + 0.5 + 0.2) ms over two steps: readback and
    # idle_wait are the device's and the traffic's time, not the host's.
    assert _read(reg, f"loop_host_ms_per_step.{suffix}", ctx) == \
        pytest.approx((6.5 + 1.7) / 2)
    assert _read(reg, f"kv_pool_live_pct.{suffix}", ctx) == \
        pytest.approx((30.0 + 10.0) / 2)
    old = _ctx(steps=[OLD_STEP] * 4)
    assert _read(reg, f"loop_host_ms_per_step.{suffix}", old) is None
    assert _read(reg, f"kv_pool_live_pct.{suffix}", old) is None


@pytest.mark.parametrize("suffix", ["serve", "batch"])
def test_idle_attributed(reg, suffix):
    device = {"idle_gaps": [["host:engine.readback", 0.020],
                            ["host:engine.step", 0.004],
                            ["host:TpuClient::LinearizeIntoImpl", 0.002],
                            ["after:fusion", 0.003],
                            ["short_gaps", 0.001]]}
    step = _step("decode_burst", 0.1, {"build": 0.001}, {}, 1, 1, 1)
    ctx = _ctx(device=device, steps=[step])
    assert _read(reg, f"idle_attributed_pct.{suffix}", ctx) == \
        pytest.approx(100 * 0.024 / 0.030)
    # not a traced run; a program that annotates nothing
    assert _read(reg, f"idle_attributed_pct.{suffix}",
                 _ctx(steps=[step])) is None
    assert _read(reg, f"idle_attributed_pct.{suffix}",
                 _ctx(device=device, steps=[OLD_STEP])) is None


# -- the readers of the trace file ---------------------------------------

@pytest.fixture(scope="module")
def phases_trace(tmp_path_factory):
    """``data/phases.xplane.pbtxt`` as the profiler would have written it:
    (path of the .xplane.pb, its reduction by chipbench.xplane)."""
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "phases.xplane.pbtxt")) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path_factory.mktemp("prof") / "phases.xplane.pb"
    path.write_bytes(raw)
    return str(path), xplane.reduce(xplane.load(str(path)))


def test_tracefile_agrees_with_profile_data(phases_trace):
    """The wire-format reader against ``jax.profiler.ProfileData`` on the
    same bytes, and the name stacks only it can give."""
    from jax.profiler import ProfileData

    from chipbench import tracefile

    path, _ = phases_trace
    (plane,) = tracefile.load(path)
    data = ProfileData.from_file(path)
    device = next(p for p in data.planes if p.name == "/device:TPU:0")
    lines = {line.name: list(line.events) for line in device.lines}
    assert plane["name"] == "/device:TPU:0"
    assert len(plane["ops"]) == len(lines["XLA Ops"]) == 6
    for (name, start, dur, _), ev in zip(plane["ops"], lines["XLA Ops"]):
        assert xplane.op_family(name) == xplane.op_family(ev.name)
        assert start == pytest.approx(ev.start_ns * 1e-9, abs=1e-12)
        assert dur == pytest.approx(ev.duration_ns * 1e-9, abs=1e-12)
    assert [(n, round(s * 1e6), round(d * 1e6))
            for n, s, d in plane["modules"]] == [
        ("jit_prefill_cached(1)", 1000, 12000),
        ("jit_decode_k8(2)", 15000, 6000)]
    assert [tracefile.scope_of(s) for _, _, _, s in plane["ops"]] == [
        "", "attention", "mlp", "lora", "attention", "head"]
    assert tracefile.program("jit_decode_k8(7922272190438085914)") == \
        "decode_k8"
    assert tracefile.program("main") == "main"


def _traced_ctx(phases_trace, steps):
    path, reduced = phases_trace
    return _ctx(device=reduced, profile=path, steps=steps)


NEW_STEP = {"kind": "decode_burst", "wall_s": 0.1, "forwards": 8,
            "phases": {"build": 0.002}, "gap_phases": {}}


@pytest.mark.parametrize("metric,expected", [
    # while 5000 + kernel 3000 + mlp 2000 + lora 1980 of 13980 us busy
    ("prefill_device_share_pct.serve", 100 * 11980 / 13980),
    ("prefill_device_share_pct.batch", 100 * 11980 / 13980),
    ("lora_share_pct.serve", 100 * 1980 / 13980),
    ("lora_share_pct.batch", 100 * 1980 / 13980),
    ("head_sample_share_pct.serve", 100 * 1000 / 13980),
    ("head_sample_share_pct.batch", 100 * 1000 / 13980),
    # the adapter's fusion lies under attn_proj/lora: it is lora's
    ("weights_matmul_share_pct.batch", 100 * 2000 / 13980),
    # the while's own 5000 us: neither under a part nor a kernel
    ("unscoped_share_pct.serve", 100 * 5000 / 13980),
    ("unscoped_share_pct.batch", 100 * 5000 / 13980),
    # 2000 us under engine.readback, 4000 under engine.build, 20 short
    ("idle_attributed_pct.serve", 100 * 6000 / 6020),
    ("idle_attributed_pct.batch", 100 * 6000 / 6020),
])
def test_trace_readers_on_the_recorded_trace(reg, phases_trace, metric,
                                             expected):
    ctx = _traced_ctx(phases_trace, [NEW_STEP])
    assert _read(reg, metric, ctx) == pytest.approx(expected, rel=1e-6)
    # the same trace not given: an untraced run reads nothing
    assert _read(reg, metric, _ctx(steps=[NEW_STEP])) is None


def test_idle_gaps_name_the_phase(phases_trace):
    gaps = dict(phases_trace[1]["idle_gaps"])
    assert gaps["host:engine.readback"] == pytest.approx(2000e-6, rel=1e-6)
    assert gaps["host:engine.build"] == pytest.approx(4000e-6, rel=1e-6)


@pytest.mark.parametrize("metric", [
    "prefill_device_share_pct.serve", "lora_share_pct.serve",
    "head_sample_share_pct.batch", "weights_matmul_share_pct.batch",
    "unscoped_share_pct.batch"])
def test_trace_readers_give_nothing_for_a_program_without_names(
        reg, tmp_path_factory, metric):
    """The accepted small trace is what the parent commit's program
    writes: one module called ``jit_step``, no name stacks."""
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "small.xplane.pbtxt")) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path_factory.mktemp("old") / "small.xplane.pb"
    path.write_bytes(raw)
    ctx = _ctx(device=xplane.reduce(xplane.load(str(path))),
               profile=str(path), steps=[OLD_STEP])
    assert _read(reg, metric, ctx) is None
