"""The benchmark's reader for the expert layers and the latent read
(``benchmark/moe_trace.py``) on a hand-made trace: a decode program is
told from a prefill chunk by its own ops (the gather of every row's
table against one slot's), whichever host span it falls in; the two mechanisms' ops are told by their result shapes; the
counters come from the program's ``engine_step`` records over the steps
that dispatched a decode batch and no chunk; and a program that writes
no counter (the parent, another family) gives every reader None."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, moe_trace, serve  # noqa: E402

CELL = "glm47-flash.reasoning-offline"
MS = 1_000_000


def _op(name, res, op="fusion"):
    return f"%{name} = {res}{{2,1,0:T(8,128)}} {op}(%p0), kind=kLoop"


# one decode dispatch's ops (ns each) and one prefill chunk's
DECODE_OPS = [
    (_op("fusion.1", "bf16[8192,16,640]"), 700_000),            # gather
    (_op("fusion.2", "f32[64,20,2048]"), 200_000),              # scores
    (_op("fusion.3", "(f32[64,20], f32[64,20,2048])"), 50_000),  # softmax
    (_op("fusion.4", "bf16[64,20,640]"), 150_000),              # values
    (_op("fusion.5", "f32[64,64,1536]"), 500_000),              # gate
    (_op("fusion.6", "bf16[64,64,1536]"), 500_000),             # up, act
    (_op("fusion.7", "f32[64,2048,1]"), 600_000),               # down
    (_op("fusion.8", "f32[64,1536]"), 20_000),                  # shared
    (_op("sort.1", "(f32[64,64], s32[64,64])", "sort"), 10_000),
    (_op("fusion.9", "(f32[64], f32[64,2048])"), 300_000),      # W_o: neither
    (_op("fusion.10", "(pred[64], f32[64,154880])"), 400_000),  # head
]
PREFILL_OPS = [
    (_op("fusion.21", "bf16[128,16,640]"), 30_000),
    (_op("fusion.22", "f32[16,20,2048]"), 40_000),      # rows in front too
    (_op("fusion.25", "f32[16,64,1536]"), 500_000),
]


def _ctx(counters=True, lead_ns=0):
    """Two traced steps: one with a chunk and a decode batch, one with a
    decode batch alone. ``lead_ns`` shifts the device plane ahead of the
    host spans, as the profiler's planes are."""
    cell = harness.load_cell(CELL)
    mods, ops, spans, t = [], [], [], 10 * MS
    for n_pre in (1, 0):
        s0 = t
        for kind in (["prefill"] * n_pre) + ["decode"]:
            evs = PREFILL_OPS if kind == "prefill" else DECODE_OPS
            start = t - lead_ns
            for name, dur in evs:
                ops.append([name, t - lead_ns, dur, ""])
                t += dur
            mods.append([f"jit_run({7 if kind == 'decode' else 9})", start,
                         t - lead_ns - start, ""])
            t += 100_000
        spans.append(["bench:engine.step", s0 - 50_000, t - s0 + 60_000, ""])
        t += 200_000
    trace = {"planes": {"/device:TPU:0": {"XLA Ops": ops,
                                          "XLA Modules": mods},
                        "/host:CPU": {"t": spans}}}
    steps = [serve.Step(0, 0, 64, 100 + 64 * i, 70_000, n_pre, 1, True)
             for i, n_pre in enumerate((1, 0))]
    recs = [{"span": "engine_step", "tokens_generated": st.tokens,
             "phases": [], "start_ns": 0, "end_ns": 1} for st in steps]
    if counters:
        recs[0].update(expert_rows=6 * 320, experts_touched=6 * 64 + 240,
                       expert_rows_max=12)
        recs[1].update(expert_rows=6 * 256, experts_touched=378,
                       expert_rows_max=10)
    return {"cell": cell, "device": {"kind": "TPU v5 lite"}, "spans": recs,
            "trace": {"trace": trace, "lo": 0, "hi": t + MS},
            "values": {"traced_steps": steps, "kv_bytes_per_token": 8960.0,
                       "traced_mean_live_tokens": 70_000.0,
                       "weight_bytes": 8_427_000_000}}


@pytest.mark.parametrize("lead_ns", [0, 900_000])
def test_decode_program_is_told_by_its_own_ops(lead_ns):
    """Both decode dispatches are found and no prefill chunk, also when
    the device plane leads the host spans by 0.9 ms (the join by span
    would then hand the chunk's time to the decode program)."""
    ctx = _ctx(lead_ns=lead_ns)
    spans = moe_trace.decode_events(ctx)
    want = sum(d for _, d in DECODE_OPS)
    assert [b - a for a, b in spans] == [want, want]
    assert moe_trace.decode_ms(ctx) == pytest.approx(want / 1e6)


def test_the_two_mechanisms_by_shape():
    ctx = _ctx()
    assert harness.read_layer_metric("latent_attn_device_ms",
                                     ctx) == pytest.approx(1.1)
    assert harness.read_layer_metric("moe_ffn_device_ms",
                                     ctx) == pytest.approx(1.63)
    labels = set(moe_trace.part_ops(ctx, "moe"))
    assert len(labels) == 5 and not any("154880" in k for k in labels)


def test_counters_from_decode_only_steps_and_the_shares():
    """The step with a chunk is left out of the counters' mean; the
    shares divide the bytes functions kept here by the timed ops."""
    ctx = _ctx()
    got = moe_trace.decode_counters(ctx)
    assert got == {"expert_rows": 1536, "experts_touched": 378,
                   "expert_rows_max": 10}
    z = moe_trace.sizes(ctx)
    assert z["row"] == 640 and z["expert_layers"] == 6
    assert moe_trace.expert_bytes(z) == 18_874_368
    assert harness.read_layer_metric(
        "expert_rows_max_over_mean", ctx) == pytest.approx(10 / 4)
    need = moe_trace.moe_ffn_bytes(z, 378)
    assert need == 378 * 18_874_368 + 6 * (4 * 64 * 2049 + 2 * 2048 * 1536 * 2)
    moe = harness.read_layer_metric("moe_ffn_roofline", ctx)
    assert moe == pytest.approx(100 * need / 819e9 / 1.63e-3)
    lat = harness.read_layer_metric("latent_attn_roofline", ctx)
    assert lat == pytest.approx(100 * 8960 * 70_000 / 819e9 / 1.1e-3)
    step = harness.read_layer_metric("moe_decode_step_roofline", ctx)
    whole = (8_427_000_000 - 6 * 18_874_368 + 8960 * 70_000)
    assert step == pytest.approx(
        100 * whole / 819e9 / (sum(d for _, d in DECODE_OPS) / 1e9))


NEW = ["moe_ffn_device_ms", "latent_attn_device_ms", "moe_ffn_roofline",
       "latent_attn_roofline", "moe_decode_step_roofline",
       "expert_rows_max_over_mean"]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_counters_reads_nothing(name):
    """The parent of PR 31 writes no expert counter, and a run without a
    trace has no device events: the reader returns None and the line
    leaves the metric out; none raises."""
    assert harness.read_layer_metric(name, _ctx(counters=False)) is None
    bare = dict(_ctx(), trace=None)
    if name != "expert_rows_max_over_mean":
        assert harness.read_layer_metric(name, bare) is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert listed[name]["workloads"] == [CELL]
    assert listed[name]["moves"] == "out_tokens_per_s"
