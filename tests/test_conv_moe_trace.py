"""The benchmark's reader for the routed experts and the gated short
convolutions (``benchmark/conv_moe_trace.py``) on a hand-made trace: a
decode program is told from a prefill chunk by its own ops (the kernel
call over a batch's rows, or the K/V gather of every row's table against
one slot's), whichever host span it falls in; the two mechanisms' ops are
told by the shapes of their results AND operands (the down product over
the experts is fused with the residual add); the counters come from the
program's ``engine_step`` records over the steps that dispatched a
decode batch and no chunk; each bytes function is held to a count by
hand; and a program that writes no counter (the parent, another family)
gives every reader None."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import conv_moe_trace, harness, serve  # noqa: E402

CELL = "lfm2-24b-a2b.reasoning-offline"
MS = 1_000_000
LAYOUT = "{2,1,0:T(8,128)}"


def _op(name, res, operands=(), op="fusion", attrs="kind=kLoop"):
    args = ", ".join(f"{shape}{LAYOUT} %p{i}"
                     for i, shape in enumerate(operands)) or "%p0"
    return f"%{name} = {res}{LAYOUT} {op}({args}), {attrs}"


KERNEL = _op("conv.7", "(f32[64,1,2048], f32[7,65,1,4096])",
             ("s32[64]", "f32[64,1,2048]", "f32[3,2048]",
              "f32[7,65,1,4096]"), op="custom-call",
             attrs='custom_call_target="tpu_custom_call", '
                   "operand_layout_constraints={f32[64,1,2048]}")
# one decode dispatch's ops (ns each) and one prefill chunk's, as the
# compiler for the described v5e writes them at the cell's widths
DECODE_OPS = [
    (_op("fusion.1", "bf16[8192,16,512]", ("bf16[2,8193,16,512]",)),
     300_000),                                                  # K gather
    (_op("fusion.2", "f32[64,32,2048]"), 100_000),              # scores
    (_op("fusion.3", "f32[64,32,512]"), 100_000),               # values
    (_op("fusion.4", "f32[64,6144]", ("bf16[7,6144,2048]",)), 60_000),
    (_op("fusion.5", "f32[64,2048]", ("f32[64,6144]",)), 5_000),   # B * X
    (_op("copy-done.1", "f32[7,65,1,4096]", ("(f32[7,65,1,4096], u32[])",),
         op="copy-done", attrs="x=1"), 4_000),                  # staging
    (KERNEL, 20_000),
    (_op("multiply_reduce_fusion.19", "(f32[64], f32[64,2048])",
         ("bf16[7,2048,2048]", "f32[64,6144]", "f32[64,1,2048]")),
     25_000),                                                   # C*v, W_out
    (_op("broadcast_add_fusion.7", "(f32[64,64], f32[64,64])",
         ("f32[8,64,2048]", "f32[64,2048]")), 15_000),          # router
    (_op("sort.1", "(f32[64,64], s32[64,64])", op="sort",
         attrs="dimensions={1}"), 10_000),
    (_op("fusion.9", "s32[64,4,1]"), 2_000),
    (_op("fusion.74", "f32[64,64,1536]", ("bf16[8,64,1536,2048]",)),
     500_000),                                                  # gate
    (_op("fusion.112", "bf16[64,64,1536]", ("bf16[8,64,1536,2048]",)),
     500_000),                                                  # up, act
    (_op("multiply_reduce_fusion.16", "(f32[64], f32[64,2048])",
         ("f32[64,2048]", "bf16[64,64,1536]", "bf16[8,64,2048,1536]")),
     600_000),                                                  # down
    (_op("fusion.30", "f32[64,11776]"), 90_000),                # dense: neither
    (_op("multiply_reduce_fusion.2", "(f32[64], f32[64,2048])",
         ("bf16[2,2048,2048]", "bf16[64,2048]")), 30_000),      # W_o: neither
    (_op("fusion.10", "(pred[64], f32[64,65536])"), 400_000),   # head
]
ROUTED_NS = 15_000 + 10_000 + 2_000 + 500_000 + 500_000 + 600_000
CONV_NS = 60_000 + 5_000 + 4_000 + 20_000 + 25_000
PREFILL_OPS = [
    (_op("fusion.21", "bf16[128,16,512]", ("bf16[2,8193,16,512]",)), 30_000),
    (_op("fusion.22", "f32[16,32,2048]"), 40_000),      # rows in front too
    (_op("fusion.116", "f32[16,6144]", ("bf16[7,6144,2048]",)), 60_000),
    (_op("fusion.607", "(f32[7,65,1,4096], f32[4096])",
         ("f32[7,65,1,4096]", "f32[1,1,1,4096]")), 5_000),  # the row's write
    (_op("custom-call.44", "bf16[2,2048,2048]", ("bf16[1,2048,2048]",),
         op="custom-call", attrs='custom_call_target="ConcatBitcast"'),
     1_000),                                            # no kernel call
    (_op("fusion.25", "f32[16,64,1536]", ("bf16[8,64,1536,2048]",)),
     500_000),
]


def _ctx(counters=True, lead_ns=0, decode_ops=DECODE_OPS):
    """Two traced steps: one with a chunk and a decode batch, one with a
    decode batch alone. ``lead_ns`` shifts the device plane ahead of the
    host spans, as the profiler's planes are."""
    cell = harness.load_cell(CELL)
    mods, ops, spans, t = [], [], [], 10 * MS
    for n_pre in (1, 0):
        s0 = t
        for kind in (["prefill"] * n_pre) + ["decode"]:
            evs = PREFILL_OPS if kind == "prefill" else decode_ops
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
        recs[0].update(expert_rows=8 * 320, experts_touched=8 * 64 + 300,
                       expert_rows_max=14, state_bytes=65 * 114_688)
        recs[1].update(expert_rows=8 * 256, experts_touched=500,
                       expert_rows_max=11, state_bytes=64 * 114_688)
    return {"cell": cell, "device": {"kind": "TPU v5 lite"}, "spans": recs,
            "trace": {"trace": trace, "lo": 0, "hi": t + MS},
            "values": {"traced_steps": steps, "kv_bytes_per_token": 4096.0,
                       "traced_mean_live_tokens": 70_000.0,
                       "weight_bytes": 10_358_000_128}}


@pytest.mark.parametrize("lead_ns", [0, 900_000])
def test_decode_program_is_told_by_its_own_ops(lead_ns):
    """Both decode dispatches are found and no prefill chunk, also when
    the device plane leads the host spans by 0.9 ms (the join by span
    would then hand the chunk's time to the decode program)."""
    ctx = _ctx(lead_ns=lead_ns)
    spans = conv_moe_trace.decode_events(ctx)
    want = sum(d for _, d in DECODE_OPS)
    assert [b - a for a, b in spans] == [want, want]
    assert conv_moe_trace.decode_ms(ctx) == pytest.approx(want / 1e6)
    assert harness.read_layer_metric(
        "routed_ffn_device_ms", ctx) == pytest.approx(ROUTED_NS / 1e6)


@pytest.mark.parametrize("mark", ["kernel", "gather"])
def test_either_mark_alone_names_a_decode_dispatch(mark):
    """A decode batch of one row gathers what a chunk gathers and still
    calls the kernel; a program whose kernel call went would still
    gather every row's table."""
    drop = "fusion.1 " if mark == "kernel" else "conv.7 "
    ops = [(n, d) for n, d in DECODE_OPS if not n.startswith("%" + drop)]
    assert len(ops) == len(DECODE_OPS) - 1
    spans = conv_moe_trace.decode_events(_ctx(decode_ops=ops))
    assert len(spans) == 2
    neither = [(n, d) for n, d in ops
               if not n.startswith(("%fusion.1 ", "%conv.7 "))]
    assert conv_moe_trace.decode_events(_ctx(decode_ops=neither)) is None


def test_the_two_mechanisms_by_result_and_operand_shapes():
    ctx = _ctx()
    assert harness.read_layer_metric(
        "routed_ffn_device_ms", ctx) == pytest.approx(ROUTED_NS / 1e6)
    assert harness.read_layer_metric(
        "gated_conv_device_ms", ctx) == pytest.approx(CONV_NS / 1e6)
    routed = set(conv_moe_trace.part_ops(ctx, "routed"))
    conv = set(conv_moe_trace.part_ops(ctx, "conv"))
    # (``xplane.op_kind`` labels an op by its FIRST result: the experts'
    # down product and the convolution's ``W_out`` fusion share a label
    # and are told apart by their operands)
    assert len(routed) == 6 and len(conv) == 5
    assert routed & conv == {"fusion multiply_reduce_fusion (f32[64]"}
    for labels in (routed, conv):
        assert not any("65536" in k or "11776" in k or "8192" in k
                       for k in labels)
    # the largest part of a decode dispatch, as the reader finds it
    assert ROUTED_NS > sum(d for _, d in DECODE_OPS) / 2


def test_bytes_functions_by_hand():
    """At the cell's sizes and at toy sizes, against counts by hand."""
    z = conv_moe_trace.sizes(_ctx())
    assert z == {"d": 2048, "d3": 6144, "experts": 64, "top_k": 4,
                 "ffn": 1536, "expert_layers": 8, "conv_layers": 7,
                 "taps": 3, "tail": 4096, "kv_row": 512, "positions": 2048}
    assert conv_moe_trace.expert_bytes(z) == 18_874_368
    toy = dict(z, d=8, ffn=4, experts=4, expert_layers=2, conv_layers=3,
               taps=3)
    # an expert 3 x 8 x 4 x 2 B = 192; a router 4 x (8 + 1) x 4 B = 144
    assert conv_moe_trace.expert_bytes(toy) == 192
    assert conv_moe_trace.routed_ffn_bytes(toy, 5) == 5 * 192 + 2 * 144
    # a conv mixer (3*64 + 3*8 + 64) x 2 B = 560; tails read and written
    assert conv_moe_trace.gated_conv_bytes(toy, 1000) == 2000 + 3 * 560
    # every expert 2 x 4 x 192 = 1536 of 5000 B of weights; 5 touched
    assert conv_moe_trace.decode_step_bytes(
        toy, 5000, 5, 16.0, 10, 1000) == 5000 - 1536 + 960 + 160 + 2000


def test_counters_from_decode_only_steps_and_the_shares():
    """The step with a chunk is left out of the counters' mean; the
    shares divide the bytes functions kept here by the timed ops."""
    ctx = _ctx()
    got = conv_moe_trace.counters(ctx)
    assert got == {"expert_rows": 2048, "experts_touched": 500,
                   "expert_rows_max": 11, "state_bytes": 64 * 114_688}
    z = conv_moe_trace.sizes(ctx)
    assert harness.read_layer_metric(
        "routed_rows_max_over_mean", ctx) == pytest.approx(11 / 4)
    need = 500 * 18_874_368 + 8 * 4 * 64 * 2049
    assert conv_moe_trace.routed_ffn_bytes(z, 500) == need
    assert harness.read_layer_metric("routed_ffn_roofline", ctx) == (
        pytest.approx(100 * need / 819e9 / (ROUTED_NS / 1e9)))
    conv = 2 * 64 * 114_688 + 7 * 16_783_360 * 2
    assert conv_moe_trace.gated_conv_bytes(z, 64 * 114_688) == conv
    assert harness.read_layer_metric("gated_conv_roofline", ctx) == (
        pytest.approx(100 * conv / 819e9 / (CONV_NS / 1e9)))
    whole = (10_358_000_128 - (512 - 500) * 18_874_368 + 4096 * 70_000
             + 2 * 64 * 114_688)
    assert harness.read_layer_metric(
        "conv_moe_decode_step_roofline", ctx) == pytest.approx(
            100 * whole / 819e9 / (sum(d for _, d in DECODE_OPS) / 1e9))
    # the older readers this cell is listed under read its records too
    assert harness.read_layer_metric(
        "state_bytes_live", ctx) == pytest.approx(64.5 * 114_688)


NEW = ["routed_ffn_device_ms", "routed_ffn_roofline", "gated_conv_device_ms",
       "gated_conv_roofline", "conv_moe_decode_step_roofline",
       "routed_rows_max_over_mean"]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_counters_reads_nothing(name):
    """A commit before this family writes no such record, and a run
    without a trace has no device events: the reader returns None and
    the line leaves the metric out; none raises."""
    assert harness.read_layer_metric(name, _ctx(counters=False)) is None
    bare = dict(_ctx(), trace=None)
    if name != "routed_rows_max_over_mean":
        assert harness.read_layer_metric(name, bare) is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert listed[name]["workloads"] == [CELL]
    assert listed[name]["moves"] == "out_tokens_per_s"
    assert name in {m["name"] for m in harness.load_cell(CELL)["per_layer"]}
