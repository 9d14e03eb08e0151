"""The readers of the host's half of a token (PR 39), each on hand-made
``ctx`` data with and without its fields (the parent commit's program
writes none of them: nothing, and no exception), and the reader that puts
the device's idle gaps down to the loop's phases on an extended copy of
the recorded-format trace (``data/phases.xplane.pbtxt``, drawn in
``test_chipbench_readers``):

  XLA Ops   busy 0..10000, 10020..12000, 14000..15000, 19000..20000 us
  host      engine.step 11000..19500 holding engine.readback 12500..13500
            and engine.build 15500..18500; this file nests
            engine.enqueue 16000..17500 in the build and a runtime event
            inside that, and adds an engine.step with no phase open

``reg`` (``conftest``) is the repo's own root, then its copy with a later
PR's addition.
"""

import os
import types

import pytest

from chipbench import xplane

DATA = os.path.join(os.path.dirname(__file__), "data")
SUFFIXES = ("batch", "serve")


def _read(reg, metric, ctx):
    spec = reg.load_json("metrics", metric)
    return reg.module("readers", spec["reader"]).read(
        ctx, spec.get("params", {}))


def _ctx(**over):
    base = dict(steps=[], traced_steps=[], traces=[], device=None,
                records=[], due=[], window=(0.0, 51.0))
    base.update(over)
    return types.SimpleNamespace(**base)


# what the parent commit's program gives
OLD_STEP = {"kind": "decode_burst", "wall_s": 0.1, "forwards": 8,
            "phases": {"build": 0.002, "emit": 0.004},
            "gap_before_s": 0.001, "gap_phases": {"schedule": 0.0005}}
OLD_TRACE = {"spans": [{"name": "engine.decode", "duration_s": 0.2,
                        "attributes": {"tokens": 9}}]}


def _step(emit, emit_cpu, tokens, samples, callback_s, **over):
    step = {"kind": "decode_burst", "wall_s": 0.1, "forwards": 8,
            "phases": {"build": 0.002, "enqueue": 0.001, "readback": 0.08,
                       "emit": emit},
            "phases_cpu": {"build": 0.002, "enqueue": 0.0005,
                           "readback": 0.001, "emit": emit_cpu},
            "gap_before_s": 0.003,
            "gap_phases": {"schedule": 0.001, "idle_wait": 0.0015},
            "gap_phases_cpu": {"schedule": 0.001, "idle_wait": 0.0},
            "emit_tokens": tokens, "emit_rows": samples, "emit_finished": 0,
            "emit_callback_s": callback_s, "emit_callback_samples": samples}
    step.update(over)
    return step


STEPS = [
    _step(0.100, 0.075, 1000, 125, 0.010, deliver_wake_s=0.002,
          deliver_drain_s=0.050),
    _step(0.020, 0.015, 200, 25, 0.0015, deliver_wake_s=0.004,
          deliver_drain_s=0.030, gap_before_s=0.040),
    # a prefill's flush: emit time, a row, no burst token, no sample
    {**OLD_STEP, "phases_cpu": {"build": 0.002, "emit": 0.004},
     "gap_phases_cpu": {"schedule": 0.0005}, "emit_tokens": 0,
     "emit_rows": 1, "emit_finished": 0},
]


@pytest.mark.parametrize("suffix", SUFFIXES)
def test_emit_readers(reg, suffix):
    ctx, old = _ctx(steps=STEPS), _ctx(steps=[OLD_STEP] * 3)
    expected = {
        # 124 ms of emit over 1,200 tokens
        "emit_us_per_token": 1e6 * 0.124 / 1200,
        "emit_off_cpu_pct": 100 * (1 - 0.094 / 0.124),
        # schedule + build + enqueue + emit, the gap's part included
        "loop_off_cpu_pct": 100 * (1 - (
            0.094 + 0.006 + 0.001 + 0.0025) / (0.124 + 0.006 + 0.002
                                               + 0.0025)),
        # 80 us a callback x 1,000 + 60 us x 200 of the 120 ms sampled
        "emit_callback_share_pct": 100 * (0.080 + 0.012) / 0.120,
        "deliver_drain_mean_ms": 40.0,
        "deliver_wake_mean_ms": 3.0,
        # the longest gap less the loop asleep in it
        "loop_gap_max_ms": 38.5,
    }
    for base, value in expected.items():
        assert _read(reg, f"{base}.{suffix}", ctx) == pytest.approx(value)
        assert _read(reg, f"{base}.{suffix}", _ctx()) is None
        if base != "loop_gap_max_ms":  # the parent's records hold gaps
            assert _read(reg, f"{base}.{suffix}", old) is None
    assert _read(reg, f"loop_gap_max_ms.{suffix}", old) == pytest.approx(1.0)
    # the accepted reader beside them, on the same records
    assert _read(reg, f"loop_host_ms_per_step.{suffix}", ctx) == \
        pytest.approx(1000 * (0.124 + 0.006 + 0.002 + 0.0025) / 3)


def _gap_trace(ms, prefill_ms, decode_ms, at):
    return {"spans": [
        {"name": "engine.decode", "duration_s": 1.0, "attributes": {}},
        {"name": "engine.stream_gap", "duration_s": ms / 1e3,
         "attributes": {"behind_prefill_s": prefill_ms / 1e3,
                        "behind_decode_s": decode_ms / 1e3, "steps": 2,
                        "at_token": at}}]}


def test_stream_gap_readers(reg):
    traces = [_gap_trace(100 + 10 * i, 40 + 4 * i, 30 + 2 * i, 8)
              for i in range(11)] + [OLD_TRACE]  # one delivery: no gap
    ctx = _ctx(traces=traces)
    assert _read(reg, "stream_gap_max_p90_ms.serve", ctx) == \
        pytest.approx(190)
    assert _read(reg, "stream_gap_behind_prefill_p90_ms.serve", ctx) == \
        pytest.approx(76)
    assert _read(reg, "stream_gap_behind_decode_p90_ms.serve", ctx) == \
        pytest.approx(48)
    for metric in ("stream_gap_max_p90_ms.serve",
                   "stream_gap_behind_prefill_p90_ms.serve",
                   "stream_gap_behind_decode_p90_ms.serve"):
        assert _read(reg, metric, _ctx(traces=[OLD_TRACE] * 3)) is None
        assert _read(reg, metric, _ctx()) is None


# -- idle gaps under the loop's phases -----------------------------------

HOST_MORE = """
    events { metadata_id: 4 offset_ps: 16000000000 duration_ps: 1500000000 }
    events { metadata_id: 5 offset_ps: 16200000000 duration_ps: 1000000000 }
    events { metadata_id: 1 offset_ps: 20000000000 duration_ps: 500000000 }
  }
  event_metadata { key: 4 value { id: 4 name: "engine.enqueue" } }
  event_metadata { key: 5 value { id: 5 name: "TpuClient::LinearizeIntoImpl" } }
"""


def _as_file(tmp_path_factory, text, name):
    from jax.profiler import ProfileData

    path = tmp_path_factory.mktemp(name) / f"{name}.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


@pytest.fixture(scope="module")
def nested_trace(tmp_path_factory):
    """The recorded trace with an ``engine.enqueue`` and a runtime event
    nested in its ``engine.build``: (path, chipbench.xplane's
    reduction)."""
    with open(os.path.join(DATA, "phases.xplane.pbtxt")) as f:
        text = f.read()
    host = text.index('name: "/host:CPU"')
    end_of_line = text.index("  }\n", text.index("events {", host))
    text = text[:end_of_line] + HOST_MORE.lstrip("\n") + text[
        end_of_line + len("  }\n"):]
    path = _as_file(tmp_path_factory, text, "nested")
    return path, xplane.reduce(xplane.load(path))


def test_the_fixture_holds_what_the_header_says(nested_trace):
    planes = xplane.load(nested_trace[0])
    (host,) = [p for n, p in planes.items() if n.startswith("/host:")]
    events = sorted(e for line in host.values() for e in line)
    # (the file's lines begin at 1000 us)
    assert [(n, round(s * 1e6) - 1000, round(d * 1e6)) for n, s, d in events
            if n.startswith("engine.")] == [
        ("engine.build", 15500, 3000), ("engine.enqueue", 16000, 1500),
        ("engine.readback", 12500, 1000), ("engine.step", 11000, 8500),
        ("engine.step", 20000, 500)]
    # the accepted reduction names the middle gap after the runtime's event
    assert dict(nested_trace[1]["idle_gaps"])[
        "host:TpuClient::LinearizeIntoImpl"] == pytest.approx(4000e-6)


@pytest.mark.parametrize("suffix", SUFFIXES)
def test_idle_gaps_go_to_the_phase_open_at_their_midpoint(
        reg, nested_trace, suffix):
    """Gaps 10000..10020 (before the step: outside), 12000..14000 (its
    midpoint 13000 under readback), 15000..19000 (midpoint 17000: under
    the runtime's event, inside enqueue, inside build: enqueue's) of a
    20000 us span."""
    path, reduced = nested_trace
    ctx = _ctx(device=reduced, profile=path, steps=[OLD_STEP])
    expected = {"readback": 100 * 2000 / 20000, "enqueue": 100 * 4000 / 20000,
                "build": 0.0, "emit": 0.0}
    for phase, value in expected.items():
        metric = f"idle_under_{phase}_pct.{suffix}"
        assert _read(reg, metric, ctx) == pytest.approx(value, rel=1e-6)
        # an untraced run
        assert _read(reg, metric, _ctx(steps=[OLD_STEP])) is None
    module = reg.module("readers", "idle_under_phase")
    found = module.idle_by_phase(xplane.load(path))
    assert found["idle"]["outside"] == pytest.approx(20e-6)
    assert sum(found["idle"].values()) / found["window_s"] == pytest.approx(
        1 - reduced["busy_s"] / reduced["window_s"])


def test_innermost_of_nested_events(reg):
    module = reg.module("readers", "idle_under_phase")
    assert module.innermost([("a", 0.0, 10.0), ("b", 2.0, 3.0),
                             ("c", 3.0, 1.0), ("d", 12.0, 1.0)]) == [
        (0.0, 2.0, "a"), (2.0, 3.0, "b"), (3.0, 4.0, "c"), (4.0, 5.0, "b"),
        (5.0, 10.0, "a"), (12.0, 13.0, "d")]


def test_a_trace_without_phases_reads_nothing(reg, tmp_path_factory):
    """The accepted small trace: a program that annotates nothing."""
    with open(os.path.join(DATA, "small.xplane.pbtxt")) as f:
        path = _as_file(tmp_path_factory, f.read(), "small")
    ctx = _ctx(device=xplane.reduce(xplane.load(path)), profile=path,
               steps=[OLD_STEP])
    for suffix in SUFFIXES:
        assert _read(reg, f"idle_under_emit_pct.{suffix}", ctx) is None
