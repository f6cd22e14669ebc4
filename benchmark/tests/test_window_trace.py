"""The new cell's readers (``benchmark/window_trace.py`` and the nine
``layer_metrics`` files that read through it) on a hand-made trace and
hand-made ``engine_step`` records, and the cell's whole control flow
rehearsed on the CPU at toy size with a clock that moves by STEPS
(``shrink_laguna.StepClock``): the window is a count of steps and no
wall-clock span, so which requests ``correct`` compares does not follow
the machine's load. Nothing here is a measurement."""

import json
import os

import pytest

from benchmark import flops, harness, window_trace
from benchmark.serve import Step

CELL = "laguna-s-2.1.longreason-offline"
DEV = "/device:TPU:0"
NEW = ("window_attn_device_ms", "full_attn_device_ms",
       "window_attn_roofline", "full_attn_roofline", "held_ffn_device_ms",
       "held_ffn_roofline", "window_moe_decode_step_roofline",
       "window_pool_util", "held_rows_max_over_mean")

# a decode program's ops as the profiler names them (HLO text), by
# mechanism, microseconds each; the shapes are the cell's
US = 1_000
DECODE_OPS = [
    # the gathers as the v5e's compiler writes them: a fusion whose
    # result is every row's blocks, flattened
    ("%fusion.1 = bf16[12288,16,1024]{2,1,0} fusion(bf16[3,12289,16,1024] "
     "%p, s32[64,192] %i), kind=kLoop", 1500, "full"),
    ("%fusion.2 = f32[64,48,3072]{2,1,0} fusion(bf16[64,3072,1024] %k, "
     "bf16[64,1024,48] %q), kind=kOutput", 900, "full"),
    ("%fusion.3 = f32[64,48,1024]{2,1,0} fusion(bf16[64,48,3072] %p, "
     "bf16[64,3072,1024] %v), kind=kOutput", 800, "full"),
    ("%fusion.4 = bf16[2176,16,1024]{2,1,0} fusion(bf16[9,2177,16,1024] "
     "%p, s32[64,34] %i), kind=kLoop", 1500, "window"),
    # the pools' in-place writes: a pool is no gather and no view
    ("%fusion.40 = bf16[9,2177,16,1024]{3,2,1,0} fusion(bf16[9,2177,16,1024] "
     "%p, bf16[64,1024] %k), kind=kLoop", 10, None),
    ("%fusion.41 = bf16[3,12289,16,1024]{3,2,1,0} fusion("
     "bf16[3,12289,16,1024] %p, bf16[64,1024] %k), kind=kLoop", 10, None),
    ("%fusion.5 = f32[64,72,544]{2,1,0} fusion(bf16[64,544,1024] %k, "
     "bf16[64,1024,72] %q), kind=kOutput", 500, "window"),
    ("%fusion.6 = f32[64,72,1024]{2,1,0} fusion(bf16[64,72,544] %p, "
     "bf16[64,544,1024] %v), kind=kOutput", 400, "window"),
    ("%fusion.7 = f32[64,256]{1,0} fusion(f32[64,3072] %a, "
     "f32[11,256,3072] %r), kind=kOutput", 100, "held"),
    ("%fusion.8 = bf16[64,32,1024]{2,1,0} fusion(bf16[64,3072] %a, "
     "bf16[11,32,1024,3072] %g, bf16[11,32,1024,3072] %u), kind=kOutput",
     6000, "held"),
    ("%fusion.9 = f32[64,3072]{1,0} fusion(bf16[64,32,1024] %h, "
     "bf16[11,32,3072,1024] %d, bf16[11,3072,1024] %s), kind=kOutput",
     3900, "held"),
    # neither: the K projection's result is [64, 1024] like the shared
    # expert's hidden row, and is NOT booked under the experts
    ("%fusion.10 = f32[64,1024]{1,0} fusion(bf16[64,3072] %a, "
     "bf16[9,1024,3072] %wk), kind=kOutput", 60, None),
    ("%fusion.11 = f32[64,12544]{1,0} fusion(bf16[64,3072] %x, "
     "bf16[12544,3072] %head), kind=kOutput", 90, None),
]
# a prefill chunk's: ONE slot's gather (three-dimensional)
CHUNK_OPS = [
    ("%gather.20 = bf16[192,16,1024]{2,1,0} gather(bf16[3,12289,16,1024] "
     "%p, s32[192,2] %i), offset_dims={1,2}", 300, None),
    ("%fusion.22 = bf16[9,2177,16,1024]{3,2,1,0} fusion(bf16[9,2177,16,1024] "
     "%p, bf16[16,1024] %k), kind=kLoop", 10, None),
    ("%fusion.21 = f32[8,16,3072]{2,1,0} fusion(f32[8,6,16,128] %q, "
     "f32[8,3072,128] %k), kind=kOutput", 200, None),
]


def make_ctx(programs=("decode", "chunk", "decode", "decode"),
             counters=True, experts=True):
    """A traced window of ``programs`` back to back on one device, one
    step and one record a program."""
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")):
        cell = harness.load_cell(CELL)
    ops, mods, recs, steps, t = [], [], [], [], 10_000
    for k, kind in enumerate(programs):
        start = t
        for name, us, _ in (DECODE_OPS if kind == "decode" else CHUNK_OPS):
            ops.append([name, t, us * US, ""])
            t += us * US
        mods.append(["jit_run(123)", start, t - start, ""])
        t += 5 * US
        rec = {"span": "engine_step", "uid": None, "step": k,
               "tokens_generated": 100 + 64 * k, "start_ns": start,
               "end_ns": t, "phases": [], "dispatches": [], "readbacks": [],
               "launches": k + 1}
        if experts:
            rec.update(expert_rows=640, experts_touched=300,
                       expert_rows_max=6)
        if counters:
            rec.update(window_rows=64 * 500, full_rows=64 * 1000,
                       window_blocks_released=3, window_blocks_live=2000)
        recs.append(rec)
        steps.append(Step(0.0, 0.0, 64, 100 + 64 * k, 0,
                          int(kind == "chunk"), int(kind == "decode"), True))
    trace = {"planes": {DEV: {"XLA Ops": ops, "XLA Modules": mods}}}
    return {"cell": cell, "device": {"kind": "TPU v5 lite"},
            "values": {"traced_steps": steps, "weight_bytes": 8_629_817_344,
                       "kv_bytes_per_token": 12_288},
            "trace": {"trace": trace, "lo": 0, "hi": t + 1},
            "spans": recs}


def test_the_cells_shapes_come_from_its_configuration():
    z = window_trace.sizes(make_ctx())
    assert (z["row"], z["positions"], z["window"]) == (1024, 3072, 512)
    assert (z["block"], z["ring"]) == (16, 544)
    assert (z["full_layers"], z["window_layers"]) == (3, 9)
    assert (z["full_heads"], z["window_heads"]) == (48, 72)
    assert (z["experts"], z["routed"], z["top_k"]) == (32, 256, 10)
    assert (z["expert_layers"], z["ffn"], z["shared_ffn"]) == (11, 1024,
                                                               1024)


def test_every_op_is_booked_under_its_mechanism_and_no_other():
    z = window_trace.sizes(make_ctx())
    tests = {"full": window_trace.attn_op(z, "full"),
             "window": window_trace.attn_op(z, "window"),
             "held": window_trace.held_op(z)}
    for name, _, want in DECODE_OPS + CHUNK_OPS:
        got = [k for k, t in tests.items() if t(name)]
        assert got == ([want] if want else []), name


def test_decode_side_events_are_told_by_the_batchs_gather():
    ctx = make_ctx()
    spans = window_trace.decode_events(ctx)
    assert len(spans) == 3                  # the chunk's program is not one
    want = sum(us for _, us, _ in DECODE_OPS) / 1e3
    assert window_trace.decode_ms(ctx) == pytest.approx(want)
    assert window_trace.decode_events(make_ctx(("chunk", "chunk"))) is None


def test_device_times_and_shares():
    ctx = make_ctx()
    read = lambda name: harness.read_layer_metric(name, ctx)
    assert read("full_attn_device_ms") == pytest.approx(3.2)
    assert read("window_attn_device_ms") == pytest.approx(2.4)
    assert read("held_ffn_device_ms") == pytest.approx(10.0)
    bw = flops.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    # 64 rows x 1000 positions x 4,096 B x 3 layers over 3.2 ms
    assert read("full_attn_roofline") == pytest.approx(
        100 * (64_000 * 4096 * 3 / bw) / 3.2e-3)
    assert read("window_attn_roofline") == pytest.approx(
        100 * (32_000 * 4096 * 9 / bw) / 2.4e-3)
    expert = 3 * 3072 * 1024 * 2
    held = 300 * expert + 11 * (4 * 256 * 3072 + expert)
    assert read("held_ffn_roofline") == pytest.approx(
        100 * (held / bw) / 10.0e-3)
    step = (8_629_817_344 - 11 * 32 * expert + 300 * expert
            + 64_000 * 4096 * 3 + 32_000 * 4096 * 9)
    total = sum(us for _, us, _ in DECODE_OPS) / 1e6
    assert read("window_moe_decode_step_roofline") == pytest.approx(
        100 * (step / bw) / total)
    assert read("window_pool_util") == pytest.approx(100 * 2000 / (64 * 34))
    assert read("held_rows_max_over_mean") == pytest.approx(
        6 / (640 / (11 * 32)))
    for name in NEW:
        assert 0 < read(name) <= (100 if name.endswith(
            ("_roofline", "_util")) else 1e9), name


def test_a_program_without_the_counters_gives_nothing_to_read():
    """The parent's records (no ``full_rows``), another family's trace,
    an untraced line: every new reader returns None and raises
    nothing."""
    for ctx in (make_ctx(counters=False), dict(make_ctx(), spans=[])):
        for name in NEW:
            assert harness.read_layer_metric(name, ctx) is None, name
    # no device trace (the CPU's rehearsal): the program's counters
    # still have their readers, the device metrics none
    ctx = dict(make_ctx(), trace=None)
    for name in NEW:
        got = harness.read_layer_metric(name, ctx)
        assert (got is not None) == (name in (
            "window_pool_util", "held_rows_max_over_mean")), name
    # the experts' counters missing (another engine's records): the
    # attention readers still read, the experts' return None
    ctx = make_ctx(experts=False)
    assert harness.read_layer_metric("full_attn_roofline", ctx) > 0
    for name in ("held_ffn_roofline", "window_moe_decode_step_roofline",
                 "held_rows_max_over_mean"):
        assert harness.read_layer_metric(name, ctx) is None


def test_the_metrics_are_listed_for_the_cell_and_only_for_it():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "out_tokens_per_s"
        spec = harness.read_json("layer_metrics", name + ".json")
        assert spec["layer"] == listed[name]["layer"]
    mine = {m["name"] for m in harness.load_cell(CELL)["per_layer"]}
    assert set(NEW) <= mine and "compile_s" in mine
    assert not {"moe_ffn_roofline", "routed_ffn_roofline",
                "decode_program_device_ms"} & mine


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed_by_step_count(monkeypatch, trace):
    """The whole control flow at toy size under ``StepClock``: a window
    of 1.5 "seconds" is 150 steps on any machine, every served token is
    the float32 reference's, and the program's own counters have their
    readers (device metrics need a device trace: none on the CPU)."""
    from benchmark import run, serve
    from benchmark.tests import shrink_laguna
    clock = shrink_laguna.StepClock(tick=0.01)
    monkeypatch.setattr(serve, "time", clock)
    monkeypatch.setattr(harness, "time", clock)
    real_driver = harness.driver_module

    def watched(config):
        sut = real_driver(config)
        build = sut.build_engine
        sut.build_engine = lambda *a, **k: clock.watch(build(*a, **k))
        return sut

    monkeypatch.setattr(harness, "driver_module", watched)
    real = flops.peaks
    monkeypatch.setattr(flops, "peaks", lambda kind: real("TPU v5 lite"))
    line = run.run_cell(CELL, 2**31 + 39, 1.5, bool(trace),
                        check_device=False, shrink=shrink_laguna.serve)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    if not trace:
        assert set(line["metrics"]) == {"out_tokens_per_s", "setup_s"}
        return
    got = line["metrics"]
    assert 0 < got["window_pool_util"]["value"] <= 100
    assert 0 < got["kv_pool_util"]["value"] <= 100
    assert got["held_rows_max_over_mean"]["value"] >= 1
    assert 0 <= got["chunk_ride_share.offline"]["value"] <= 100
