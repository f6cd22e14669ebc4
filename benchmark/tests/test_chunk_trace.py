"""The new cell's readers (``benchmark/chunk_trace.py`` and the six
``layer_metrics`` files that read through it) on a hand-made trace and
hand-made ``engine_step`` records: the decode-side programs are found by
the records' ``dispatches``, by ordinal, whatever their ops look like —
one case has the ring's read as a kernel call. Nothing here is a
measurement."""

import json
import os

import pytest

from benchmark import chunk_trace, harness
from benchmark.serve import Step

CELL = "evabyte.bytereason-offline"
DEV = "/device:TPU:0"
NEW = ("ring_attn_device_ms", "summary_attn_device_ms", "ring_attn_roofline",
       "summary_attn_roofline", "chunked_decode_step_roofline",
       "summary_rows_share")
US = 1_000

# a decode program's ops as the profiler names them (HLO text), by
# store, microseconds each; the shapes are the cell's: 24 rows, rings of
# 130 blocks of 16 rows of 4,096 lanes, tables of 36 blocks of summaries
RING_PLAIN = [
    ("%fusion.1 = bf16[3120,16,4096]{2,1,0} fusion(bf16[8,3121,16,4096] "
     "%p, s32[3120] %i), kind=kCustom", 3000, "ring"),
    ("%fusion.2 = f32[24,32,2080]{2,1,0} fusion(bf16[24,2080,4096] %k, "
     "bf16[24,4096,32] %q), kind=kOutput", 1500, "ring"),
    ("%fusion.3 = f32[24,32,4096]{2,1,0} fusion(bf16[24,32,2080] %p, "
     "bf16[24,2080,4096] %v), kind=kOutput", 1480, "ring"),
    ("%fusion.30 = bf16[24,4096,32]{1,2,0} fusion(f32[24,32,128] %q), "
     "kind=kLoop", 20, "ring"),
]
# ... and with the ring read by ONE kernel call over the ring's pool
RING_KERNEL = [
    ("%attn.9 = (f32[24,32,4096]{2,1,0}, f32[24,32,128]{2,1,0}, "
     "f32[24,32,128]{2,1,0}) custom-call(s32[1] %l, s32[24,130] %t, "
     "s32[24] %n, bf16[24,32,4096] %q, bf16[8,3121,16,4096] %k, "
     "bf16[8,3121,16,4096] %v), custom_call_target=\"tpu_custom_call\"",
     4500, "ring"),
]
REST = [
    # the ring's own K/V write: no read
    ("%fusion.40 = bf16[8,3121,16,4096]{3,2,1,0} fusion(bf16[8,3121,16,4096] "
     "%p, bf16[24,4096] %k), kind=kLoop", 10, None),
    # the summaries: the kernel over their pool, the finished chunks'
    # blocks summarised and written, the join
    ("%attn.5 = (f32[24,32,4096]{2,1,0}, f32[24,32,128]{2,1,0}, "
     "f32[24,32,128]{2,1,0}) custom-call(s32[1] %l, s32[24,36] %t, "
     "s32[24] %n, bf16[24,32,4096] %q, bf16[8,865,16,4096] %k, "
     "bf16[8,865,16,4096] %v), custom_call_target=\"tpu_custom_call\"",
     1200, "summary"),
    ("%gather.7 = bf16[24,16,4096]{2,1,0} gather(bf16[8,3121,16,4096] %p, "
     "s32[24,2] %i), offset_dims={1,2}", 40, "summary"),
    ("%fusion.8 = f32[24,32,128]{2,1,0} fusion(bf16[24,16,4096] %kb, "
     "f32[24,16,32] %alpha), kind=kLoop", 30, "summary"),
    ("%fusion.9 = bf16[8,865,16,4096]{3,2,1,0} fusion(bf16[8,865,16,4096] "
     "%p, f32[24,32,128] %kt), kind=kLoop", 10, "summary"),
    ("%fusion.10 = f32[24,32,128]{2,1,0} fusion(f32[24,32,4096] %ring, "
     "f32[24,32,4096] %summ, f32[24,32] %m), kind=kLoop", 20, "summary"),
    # neither: projections, the MLP, the head over head 0's rows
    ("%fusion.11 = f32[24,4096]{1,0} fusion(bf16[24,4096] %a, "
     "bf16[8,4096,4096] %wq), kind=kOutput", 600, None),
    ("%fusion.12 = f32[24,11008]{1,0} fusion(bf16[24,4096] %a, "
     "bf16[8,11008,4096] %g), kind=kOutput", 3000, None),
    ("%fusion.13 = f32[24,320]{1,0} fusion(bf16[24,4096] %x, "
     "bf16[320,4096] %head), kind=kOutput", 30, None),
    ("%fusion.14 = f32[24,32,128]{2,1,0} fusion(f32[24,32,128] %q, "
     "f32[24,64] %cos), kind=kLoop", 5, None),
]
# a prefill chunk's: ONE slot's gathers (a store's own, where a chunk
# rides in a decode-side program; a ``prefill`` program is none)
CHUNK_OPS = [
    ("%gather.20 = bf16[130,16,4096]{2,1,0} gather(bf16[8,3121,16,4096] "
     "%p, s32[130,2] %i), offset_dims={1,2}", 300, "ring"),
    ("%gather.21 = bf16[36,16,4096]{2,1,0} gather(bf16[8,865,16,4096] "
     "%p, s32[36,2] %i), offset_dims={1,2}", 100, "summary"),
]


def make_ctx(programs=("decode", "prefill", "decode", "mixed", "decode"),
             ring=RING_PLAIN, counters=True, late=True):
    """A traced window of ``programs`` back to back on one device, one
    step and one record a program; ``late``: every launch but the first
    is read by the NEXT record (the first reads a launch from before
    the window, the last launch is read after it)."""
    cell = harness.load_cell(CELL)
    ops, mods, recs, steps, t = [], [], [], [], 10_000
    for k, kind in enumerate(programs):
        start = t
        for name, us, _ in (CHUNK_OPS if kind == "prefill" else ring + REST):
            ops.append([name, t, us * US, ""])
            t += us * US
        mods.append(["jit_run(123)", start, t - start, ""])
        t += 5 * US
        rec = {"span": "engine_step", "uid": None, "step": k,
               "tokens_generated": 100 + 24 * k, "start_ns": start,
               "end_ns": t,
               "phases": [[kind + ".dispatch", start, start + 1],
                          [kind + ".readback", start + 2, t]],
               "dispatches": [[kind, 24 if kind != "prefill" else 16]],
               "readbacks": [50 + k - 1 if late else 50 + k],
               "launches": 50 + k + 1}
        if counters:
            rec.update(window_rows=24 * 1000, full_rows=24 * 4000,
                       summary_rows=24 * 256, summaries_written=2,
                       window_blocks_released=0, window_blocks_live=3000)
        recs.append(rec)
        steps.append(Step(0.0, 0.0, 24, 100 + 24 * k, 0,
                          int(kind != "decode"), int(kind == "decode"), True))
    trace = {"planes": {DEV: {"XLA Ops": ops, "XLA Modules": mods}}}
    return {"cell": cell, "device": {"kind": "TPU v5 lite"},
            "values": {"traced_steps": steps, "weight_bytes": 3_240_894_464,
                       "kv_bytes_per_token": 131_072},
            "trace": {"trace": trace, "lo": 0, "hi": t + 1},
            "spans": recs}


def test_the_cells_shapes_come_from_its_configuration():
    z = chunk_trace.sizes(make_ctx())
    assert (z["block"], z["entries"], z["table"]) == (16, 130, 36)
    assert (z["row"], z["heads"], z["dh"], z["layers"]) == (4096, 32, 128, 8)
    assert (z["window"], z["chunk"], z["slots"]) == (2048, 16, 24)
    assert chunk_trace.row_bytes(z) == 16_384


@pytest.mark.parametrize("ring", [RING_PLAIN, RING_KERNEL],
                         ids=["gathered", "kernel"])
def test_every_op_is_booked_under_its_store_and_no_other(ring):
    keep = chunk_trace.classify(chunk_trace.sizes(make_ctx()))
    for name, _, want in ring + REST + CHUNK_OPS:
        assert keep(name) == want, name


@pytest.mark.parametrize("ring", [RING_PLAIN, RING_KERNEL],
                         ids=["gathered", "kernel"])
def test_the_programs_are_found_by_ordinal_whatever_their_ops(ring):
    """Five launches, the second a prefill chunk; the last is read
    after the window: three decode-side programs, each paired with the
    record that launched it. A ring read by ONE kernel call changes
    nothing of which programs they are."""
    ctx = make_ctx(ring=ring)
    got = chunk_trace.pairs(ctx)
    assert [p.kind for p in got] == ["decode", "decode", "mixed"]
    assert [p.rec["step"] for p in got] == [0, 2, 3]
    ring_us = sum(us for _, us, _ in ring)
    summ_us = sum(us for _, us, k in REST if k == "summary")
    all_us = sum(us for _, us, _ in ring + REST)
    assert chunk_trace.part_ms(ctx, "ring") == pytest.approx(ring_us / 1e3)
    assert chunk_trace.part_ms(ctx, "summary") == pytest.approx(
        summ_us / 1e3)
    assert chunk_trace.decode_ms(ctx) == pytest.approx(all_us / 1e3)


def test_the_metrics_read_what_the_bytes_functions_say():
    ctx = make_ctx()
    z = chunk_trace.sizes(ctx)
    got = chunk_trace.counters(ctx)
    assert got == {"window_rows": 24_000, "summary_rows": 6_144,
                   "summaries_written": 2}
    assert chunk_trace.ring_bytes(z, got) == 24_000 * 16_384 * 8
    assert chunk_trace.summary_bytes(z, got) == (
        (6_144 + 2 * 17) * 16_384 * 8)
    bw = 819e9
    ring_ms = harness.read_layer_metric("ring_attn_device_ms", ctx)
    assert ring_ms == pytest.approx(6.0)
    assert harness.read_layer_metric("ring_attn_roofline", ctx) == (
        pytest.approx(100 * 24_000 * 16_384 * 8 / bw / 6.0e-3))
    summ_ms = harness.read_layer_metric("summary_attn_device_ms", ctx)
    assert summ_ms == pytest.approx(1.3)
    assert harness.read_layer_metric("summary_attn_roofline", ctx) == (
        pytest.approx(100 * (6_144 + 34) * 16_384 * 8 / bw / 1.3e-3))
    step = harness.read_layer_metric("chunked_decode_step_roofline", ctx)
    need = 3_240_894_464 + (24_000 + 6_144 + 34) * 16_384 * 8
    assert step == pytest.approx(100 * need / bw / 10.945e-3)
    for name in NEW[2:5]:
        assert 0 < harness.read_layer_metric(name, ctx) < 100, name
    assert harness.read_layer_metric("summary_rows_share", ctx) == (
        pytest.approx(100 * 6_144 / (6_144 + 24_000)))


def test_nothing_to_read_is_none_and_never_raises():
    """A program that writes no ``summary_rows`` (the parent, another
    family), no trace, or another count of programs than of dispatches:
    every reader returns None."""
    for ctx in (make_ctx(counters=False), dict(make_ctx(), spans=[])):
        for name in NEW:
            assert harness.read_layer_metric(name, ctx) is None, name
    short = make_ctx()
    short["trace"]["trace"]["planes"][DEV]["XLA Modules"].pop()
    for ctx in (short, dict(make_ctx(), trace=None)):
        for name in NEW[:5]:
            assert harness.read_layer_metric(name, ctx) is None, name
        # the counter's share needs no trace of the device
        assert harness.read_layer_metric("summary_rows_share", ctx) > 0
    # records from before the late read (no ``readbacks``): every
    # decode-side program is paired
    ctx = make_ctx()
    for rec in ctx["spans"]:
        del rec["readbacks"]
    assert len(chunk_trace.pairs(ctx)) == 4


def test_the_metrics_are_listed_for_the_cell_and_only_for_it():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "out_tokens_per_s"
        spec = harness.read_json("layer_metrics", name + ".json")
        assert spec["layer"] == listed[name]["layer"]
        assert spec["unit"] == listed[name]["unit"]
    mine = {m["name"] for m in harness.load_cell(CELL)["per_layer"]}
    assert set(NEW) <= mine and "compile_s" in mine
    assert not {"window_attn_roofline", "window_pool_util",
                "decode_program_device_ms"} & mine
