"""The trace reducer on a small recorded-format trace with known sums.

``data/small.xplane.pbtxt`` is an XSpace in text form: one TPU plane whose
``XLA Ops`` line holds, in microseconds from the line's start,
  while.3                    0 .. 10000   (parent of the next two)
  pallas_paged_attention.7   1000 .. 4000
  fusion.12                  5000 .. 7000
  fusion.40                  10020 .. 12000  (20 us after the while: short gap)
  pallas_paged_attention.7   14000 .. 15000  (2000 us gap: host in np.asarray)
  pallas_prefill_attention   19000 .. 20000  (4000 us gap: host in _loop)
and a host plane with the two host events.
"""

import os

import pytest

from chipbench import xplane

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "small.xplane.pbtxt")) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path_factory.mktemp("prof") / "small.xplane.pb"
    path.write_bytes(raw)
    return xplane.reduce(xplane.load(str(path.parent)))


def test_busy_and_window(reduced):
    # 10000 + 1980 + 1000 + 1000 us busy over 20000 us
    assert reduced["busy_s"] == pytest.approx(13980e-6, rel=1e-6)
    assert reduced["window_s"] == pytest.approx(20000e-6, rel=1e-6)
    assert reduced["devices"] == 1


@pytest.mark.parametrize("family,seconds,count", [
    ("while", 5000e-6, 1),          # 10000 less its children's 3000 + 2000
    ("pallas_paged_attention", 4000e-6, 2),
    ("fusion", 3980e-6, 2),
    ("pallas_prefill_attention", 1000e-6, 1),
])
def test_self_seconds_by_family(reduced, family, seconds, count):
    assert reduced["op_seconds"][family] == pytest.approx(seconds, rel=1e-6)
    assert reduced["op_counts"][family] == count


def test_kernel_seconds(reduced):
    both = xplane.kernel_seconds(reduced, ["pallas_paged_attention",
                                           "pallas_prefill_attention"])
    assert both == pytest.approx(5000e-6, rel=1e-6)
    assert xplane.kernel_seconds(reduced, ["nothing"]) == 0


def test_idle_gaps_by_cause(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert gaps["short_gaps"] == pytest.approx(20e-6, rel=1e-6)
    assert gaps["host:np.asarray"] == pytest.approx(2000e-6, rel=1e-6)
    assert gaps["host:EngineCore._loop"] == pytest.approx(4000e-6, rel=1e-6)
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_top_operations_are_ranked(reduced):
    names = [n for n, _ in reduced["device_ops"]]
    assert names[0] == "while" and set(names) == set(reduced["op_seconds"])


def test_union_and_self_times_on_hand_made_events():
    assert xplane.union_s([(0, 2), (1, 3), (5, 6)]) == pytest.approx(4)
    assert xplane.union_s([]) == 0
    got = dict(xplane.self_times([("a", 0, 10), ("b", 1, 2), ("c", 4, 5),
                                  ("d", 5, 1)]))
    assert got == {"a": pytest.approx(3), "b": pytest.approx(2),
                   "c": pytest.approx(4), "d": pytest.approx(1)}


@pytest.mark.parametrize("name,family", [
    ("fusion.123", "fusion"), ("%fusion.7 = f32[] fusion(...)", "fusion"),
    ("pallas_paged_attention", "pallas_paged_attention"),
    ("copy.1.2", "copy"), ("while", "while")])
def test_op_family(name, family):
    assert xplane.op_family(name) == family


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        xplane.reduce({"/host:CPU": {"t": [("x", 0.0, 1.0)]}})


def test_roofline_counts_the_bursts_of_the_traced_span_only(reduced, reg):
    """Bytes from the step recorder's bursts inside the traced span, time
    from the trace: bursts elsewhere in the window do not enter. On the
    repo's own root and on its copy with a later PR's addition."""
    import types

    spec = reg.load_json("metrics", "paged_attn_roofline_pct.batch")
    reader = reg.module("readers", spec["reader"])
    burst = {"kind": "decode_burst", "forwards": 8, "kv_read_tokens": 8 * 4096}
    other = {"kind": "decode_burst", "forwards": 8, "kv_read_tokens": 8 * 99}
    ctx = types.SimpleNamespace(
        device=reduced, steps=[burst, other], traced_steps=[burst],
        config=reg.config("mistral-7b-l16"), device_kind="TPU v5 lite",
        kv_cache_dtype="bfloat16")
    # 4096 tokens x 4096 B / 819 GB/s over the kernel's 2000 us per call
    want = 100.0 * (4096 * 4096 / 819e9) / 2000e-6
    assert reader.read(ctx, spec["params"]) == pytest.approx(want, rel=1e-6)
