"""The readers a mixed-attention MoE configuration brings (PR 33), each
on hand-made step records and, for those that read the trace file, on a
small trace in the recorded format (``data/moe.xplane.pbtxt``):

  XLA Modules   jit_prefill(1)    0 .. 1500 us
                jit_decode_k8(2)  2000 .. 18000 us
  XLA Ops       ragged-dot-none.2 0..1000           (prefill)
                while.3 2000..18000 (parent of the rest; 4000 us its own)
                fusion.7 2000..2200                  (attn_proj)
                pallas_paged_attention.7 3000..4000  (attention; a kernel)
                fusion.21 5000..5600                 (mlp/moe_router)
                fusion.25 5700..5900                 (mlp/moe_experts: a gather)
                ragged-dot-none.5 6000..11400
                fusion.30 12000..12400               (mlp/moe_shared)
                ragged-dot-none.5 13000..17200

The grouped matmuls are as the chip's trace has them (my chip run, PR 33):
the TPU compiler makes them of ``jax.lax.ragged_dot`` and leaves
``ragged-dot-none`` as their whole name stack, under no scope of the program;

and on what a program without the counters or the scopes gives: nothing,
and no exception. ``reg`` (``conftest``) is the repo's own root, then its
copy with a later PR's addition."""

import os
import types

import pytest

from chipbench import schedule, xplane

DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "laguna-s-backlog-wide"
CONFIG = "laguna-s-2.1-l8e64"
BUSY_US = 17000.0  # 1000 + the while's 16000


def _read(reg, metric, ctx):
    spec = reg.load_json("metrics", metric)
    return reg.module("readers", spec["reader"]).read(
        ctx, spec.get("params", {}))


def _ctx(reg, **over):
    base = dict(steps=[], traced_steps=[], device=None,
                device_kind="TPU v5 lite", kv_cache_dtype="bfloat16",
                config=reg.config(CONFIG))
    base.update(over)
    return types.SimpleNamespace(**base)


def _trace(tmp_path_factory, name):
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, name)) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path_factory.mktemp("prof") / (name[:-6] + ".pb")
    path.write_bytes(raw)
    return str(path), xplane.reduce(xplane.load(str(path)))


@pytest.fixture(scope="module")
def moe_trace(tmp_path_factory):
    return _trace(tmp_path_factory, "moe.xplane.pbtxt")


def _burst(forwards=8, assignments=0, hit=0, largest=0, **more):
    rec = {"kind": "decode_burst", "forwards": forwards, "wall_s": 0.1,
           "rows": 128, **more}
    if assignments:
        rec.update(stats_forwards=forwards, moe_assignments=assignments,
                   moe_experts_hit=hit, moe_max_expert_load=largest)
    return rec


# 7 sparse layers x 8 forwards = 56 layer calls a burst
COUNTED = [_burst(8, 56 * 320, 56 * 63, 56 * 12),
           _burst(8, 56 * 300, 56 * 61, 56 * 14),
           # a prefill step's record may carry a finished burst's counts
           {"kind": "prefill", "forwards": 1, "wall_s": 0.05,
            "stats_forwards": 8, "moe_assignments": 56 * 340,
            "moe_experts_hit": 56 * 64, "moe_max_expert_load": 56 * 10,
            "prefill_stats_forwards": 1, "prefill_moe_assignments": 9000,
            "prefill_moe_experts_hit": 448, "prefill_moe_max_expert_load": 90}]


def test_expert_counters(reg):
    ctx = _ctx(reg, steps=COUNTED)
    assert _read(reg, "experts_hit_pct.batch", ctx) == pytest.approx(
        100.0 * (63 + 61 + 64) / (3 * 64))
    # mean load of a held expert per layer call: assignments / 64
    assert _read(reg, "expert_load_max_over_mean.batch", ctx) == pytest.approx(
        64 * (12 + 14 + 10) / (320 + 300 + 340))
    for metric in ("experts_hit_pct.batch", "expert_load_max_over_mean.batch"):
        # the parent's records carry no counts; a window may be empty
        assert _read(reg, metric, _ctx(reg, steps=[_burst()])) is None
        assert _read(reg, metric, _ctx(reg)) is None


def test_moe_share_reads_the_three_scopes_anywhere_on_the_stack(
        reg, moe_trace):
    path, reduced = moe_trace
    ctx = _ctx(reg, device=reduced, profile=path)
    # grouped matmuls 1000 + 5400 + 4200, router 600, gather 200, shared 400
    assert _read(reg, "moe_share_pct.batch", ctx) == pytest.approx(
        100 * 11800 / BUSY_US, rel=1e-6)
    # the scopes nest in ``mlp``, so the accepted share counts what lies
    # under them (200 of attn_proj beside it); the grouped matmuls carry
    # no scope and read as unscoped there, beside the while's own 4000
    assert _read(reg, "weights_matmul_share_pct.batch", ctx) == pytest.approx(
        100 * 1400 / BUSY_US, rel=1e-6)
    assert _read(reg, "unscoped_share_pct.batch", ctx) == pytest.approx(
        100 * (4000 + 10600) / BUSY_US, rel=1e-6)
    assert _read(reg, "moe_share_pct.batch", _ctx(reg)) is None


def test_expert_matmul_roofline(reg, moe_trace):
    path, reduced = moe_trace
    steps = [_burst(8, 56 * 320, 56 * 60, 56 * 12)]
    ctx = _ctx(reg, device=reduced, profile=path, traced_steps=steps,
               steps=steps)
    # one traced decode_k8 program = 8 forwards x 7 sparse layers; the
    # decode programs' grouped matmuls and moe_experts operations took
    # 5400 + 4200 + 200 us (the prefill program's are not counted)
    seconds = 9800e-6 / 56
    weights = 60 * 3 * 3072 * 1024 * 2
    rows = 320 * (3 * 3072 + 4 * 1024) * 2
    flops = 2 * 3 * 3072 * 1024 * 320
    floor = max((weights + rows) / 819e9, flops / 197e12)
    assert floor == (weights + rows) / 819e9  # bandwidth bound at 128 rows
    assert _read(reg, "expert_matmul_roofline_pct.batch", ctx) == \
        pytest.approx(100 * floor / seconds, rel=1e-6)
    # no counts in the traced span: the window's stand in
    ctx.traced_steps = []
    assert _read(reg, "expert_matmul_roofline_pct.batch", ctx) == \
        pytest.approx(100 * floor / seconds, rel=1e-6)
    # a program without the counters, a run without a trace
    ctx.steps = [_burst()]
    assert _read(reg, "expert_matmul_roofline_pct.batch", ctx) is None
    assert _read(reg, "expert_matmul_roofline_pct.batch",
                 _ctx(reg, steps=steps)) is None


def test_mixed_attention_roofline(reg, moe_trace):
    path, reduced = moe_trace
    live, window = 8 * 85000, 8 * 60000
    steps = [_burst(8, kv_live_tokens=live, kv_live_tokens_window=window,
                    kv_read_tokens=2 * live)]
    ctx = _ctx(reg, device=reduced, profile=path, traced_steps=steps)
    # 2 full + 6 sliding layers held, 4096 B a token and layer; the
    # trace's one kernel call took 1000 us
    per_call = (2 * live + 6 * window) / 8 / 8 * 4096
    assert _read(reg, "mixed_attn_roofline_pct.batch", ctx) == pytest.approx(
        100 * (per_call / 819e9) / 1000e-6, rel=1e-6)
    # records without the window's count (the XLA path, the parent)
    ctx.traced_steps = [_burst(8, kv_live_tokens=live)]
    assert _read(reg, "mixed_attn_roofline_pct.batch", ctx) is None
    assert _read(reg, "mixed_attn_roofline_pct.batch",
                 _ctx(reg, traced_steps=steps)) is None


@pytest.mark.parametrize("metric", [
    "moe_share_pct.batch", "expert_matmul_roofline_pct.batch",
    "mixed_attn_roofline_pct.batch"])
def test_trace_readers_give_nothing_for_a_program_without_the_scopes(
        reg, tmp_path_factory, metric):
    """The accepted recorded trace is what a program without an expert
    layer writes: no operation under the new scopes, records without the
    counters."""
    path, reduced = _trace(tmp_path_factory, "phases.xplane.pbtxt")
    steps = [_burst(8, kv_live_tokens=1000, kv_read_tokens=1000)]
    ctx = _ctx(reg, device=reduced, profile=path, steps=steps,
               traced_steps=steps, config=reg.config("mistral-7b-l16"))
    assert _read(reg, metric, ctx) is None


def test_the_new_metrics_are_in_the_benchmark_with_their_cell(reg):
    per_layer = {m["name"]: m for m in reg.bench["per_layer"]}
    reports = {m["name"]: m for m in reg.bench["end_to_end"]}
    for name in ("moe_share_pct.batch", "expert_matmul_roofline_pct.batch",
                 "expert_load_max_over_mean.batch", "experts_hit_pct.batch",
                 "mixed_attn_roofline_pct.batch"):
        entry = per_layer[name]
        assert CELL in entry["workloads"]
        assert entry["moves"] == "out_tokens_per_s"
        assert CELL in reports["out_tokens_per_s"]["workloads"]
    # the cell stays out of the metric whose bytes assume that every
    # layer reads its whole context
    assert CELL not in per_layer["paged_attn_roofline_pct.batch"]["workloads"]
    assert CELL not in per_layer["lora_share_pct.batch"]["workloads"]


def test_backlog_wide_stays_inside_the_model_length(reg):
    cell = reg.workload(CELL)
    config = reg.config(cell["config"])
    traffic = reg.traffic(cell["traffic"])
    flags = config["server_flags"]
    longest = int(flags[flags.index("--max-model-len") + 1])
    rows = int(flags[flags.index("--max-num-seqs") + 1])
    assert traffic["max_outstanding"] == 3 * rows == 384
    plan = schedule.build(reg, traffic, 51, config["vocab_size"])
    assert len(plan["requests"]) == traffic["params"]["requests"] >= 6000
    assert all(r["due"] == 0.0 for r in plan["requests"])
    assert max(len(r["prompt"]) + r["max_tokens"]
               for r in plan["requests"]) <= longest
    assert all(259 <= t < config["vocab_size"]
               for r in plan["requests"] for t in r["prompt"])
    # no two prompts share their first token's page... nor a prefix
    assert len({tuple(r["prompt"][:8]) for r in plan["requests"]}) == 6000
    # every warm prompt with its two tokens of answer fits as well
    assert max(traffic["warm_prompt_tokens"]) + 2 <= longest
