"""The sink-softmax cell's readers (``benchmark/sink_window_trace.py``
and the eight ``layer_metrics`` files that read through it) on a
hand-made trace and hand-made ``engine_step`` records of an engine that
launches, then collects (``test_dispatch_join_late.make_ctx``), and on a
recorded window of the cell from the chip
(``record_window_on_chip.py`` made ``fixture_mimo-v2-flash.longreason-
offline.json.gz``): the decode-side programs are found by ORDINAL, each
attention kind's device time is its walk's kernel calls and what stands
round them — told by the store's two row widths and by the tables,
since both kinds have 64 query heads — and a read's bytes are the
program's positions times the program's own bytes a position. Nothing
here is a measurement."""

import os

import pytest

from benchmark import flops, harness, sink_window_trace as t
from benchmark.tests import test_dispatch_join_late as late

CELL = "mimo-v2-flash.longreason-offline"
NEW = ("sink_window_attn_device_ms", "split_kv_full_attn_device_ms",
       "sink_window_attn_roofline", "split_kv_full_attn_roofline",
       "share_ffn_device_ms", "share_ffn_roofline",
       "sink_moe_decode_step_roofline", "share_rows_max_over_mean")
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       f"fixture_{CELL}.json.gz")

US = 1_000
LAYOUT = "{2,1,0:T(8,128)(2,1)S(1)}"
POOLS = {"full": ("2,12289", 768, 512, 192), "ring": ("9,641", 1536, 1024,
                                                      10)}


def _walk(n, kind):
    """A walk's kernel call as the profiler names it on the chip: the
    result as wide as V's row, the query as wide as K's, both sides of
    the kind's pool whole, and for a ring the layer's sinks."""
    pool, k_row, v_row, tables = POOLS[kind]
    sink = f", f32[64,1]{{1,0:T(8,128)}} %sink.{n}" if kind == "ring" else ""
    return (f"%_walk.{n} = f32[64,64,{v_row}]{{2,1,0:T(8,128)S(1)}} "
            f"custom-call(s32[1]{{0:T(128)}} %constant.1, s32[64,{tables}]"
            f"{{1,0:T(8,128)S(1)}} %copy-done.{n}, s32[64]{{0:T(128)S(1)}} "
            f"%copy-done.1{n}, s32[64]{{0:T(128)S(1)}} %copy-done.2{n}, "
            f"bf16[64,64,{k_row}]{LAYOUT} %q.{n}, bf16[{pool},16,{k_row}]"
            f"{{3,2,1,0:T(8,128)(2,1)}} %fusion.{n}, bf16[{pool},16,{v_row}]"
            f"{{3,2,1,0:T(8,128)(2,1)}} %fusion.1{n}{sink}), "
            'custom_call_target="tpu_custom_call", '
            f"operand_layout_constraints={{s32[1]{{0}}, s32[64,{tables}]"
            "{1,0}}")


# a decode program's ops as the profiler names them (HLO text), by
# mechanism, microseconds each; the shapes are the cell's
DECODE_OPS = [
    (_walk(12, "full"), 500, "full"),
    (_walk(13, "ring"), 600, "window"),
    (_walk(14, "ring"), 600, "window"),
    # round a kernel: the query laid out for the stored K rows, the head
    # pick of a result as wide as the V row
    (f"%select_convert_fusion.11 = bf16[64,64,768]{LAYOUT} fusion("
     "f32[64,64,192] %q, pred[64,768] %m), kind=kLoop", 30, "full"),
    (f"%select_convert_fusion.12 = bf16[64,64,1536]{LAYOUT} fusion("
     "f32[64,64,192] %q, pred[64,1536] %m), kind=kLoop", 50, "window"),
    ("%fusion.300 = f32[64,64,128]{2,1,0} fusion(f32[64,64,1024] %_walk.13), "
     "kind=kLoop", 20, "window"),
    ("%fusion.301 = f32[64,64,128]{2,1,0} fusion(f32[64,64,512] %_walk.12), "
     "kind=kLoop", 10, "full"),
    # the pools' in-place writes: a pool is no gather and no view
    ("%fusion.40 = bf16[9,641,16,1536]{3,2,1,0} fusion(bf16[9,641,16,1536] "
     "%p, bf16[64,1536] %k), kind=kLoop", 10, None),
    ("%fusion.41 = bf16[2,12289,16,512]{3,2,1,0} fusion("
     "bf16[2,12289,16,512] %p, bf16[64,512] %v), kind=kLoop", 10, None),
    # the share of the experts: the router with its bias, the choice,
    # the held experts' three products
    ("%fusion.7 = f32[64,256]{1,0} fusion(f32[64,4096] %a, "
     "f32[10,256,4096] %r), kind=kOutput", 100, "share"),
    ("%fusion.70 = s32[64,8]{1,0} fusion(f32[64,256] %s, f32[10,256] %bias), "
     "kind=kLoop", 40, "share"),
    ("%fusion.8 = bf16[64,16,2048]{2,1,0} fusion(bf16[64,4096] %a, "
     "bf16[10,16,2048,4096] %g, bf16[10,16,2048,4096] %u), kind=kOutput",
     8000, "share"),
    ("%fusion.9 = f32[64,4096]{1,0} fusion(bf16[64,16,2048] %h, "
     "bf16[10,16,4096,2048] %d), kind=kOutput", 4000, "share"),
    # none: a window layer's query stack sliced for a layer, the K and V
    # projections' results, the dense MLP, the head
    ("%fusion.1081 = bf16[1,12288,4096]{2,1,0} fusion(bf16[9,12288,4096] "
     "%p_window_wq.1), kind=kLoop", 170, None),
    ("%fusion.10 = f32[64,1536]{1,0} fusion(bf16[64,4096] %a, "
     "bf16[9,1536,4096] %wk), kind=kOutput", 60, None),
    ("%fusion.12 = f32[64,1024]{1,0} fusion(bf16[64,4096] %a, "
     "bf16[9,1024,4096] %wv), kind=kOutput", 40, None),
    ("%fusion.13 = bf16[64,16384]{1,0} fusion(bf16[64,4096] %a, "
     "bf16[1,16384,4096] %g), kind=kOutput", 300, None),
    ("%fusion.11 = f32[64,19072]{1,0} fusion(bf16[64,4096] %x, "
     "bf16[19072,4096] %head), kind=kOutput", 190, None),
]
# a prefill chunk's: ONE slot's gathers (three-dimensional), booked
# under neither kind
CHUNK_OPS = [
    ("%gather.20 = bf16[192,16,768]{2,1,0} gather(bf16[2,12289,16,768] "
     "%p, s32[192,2] %i), offset_dims={1,2}", 300, None),
    ("%gather.21 = bf16[10,16,1536]{2,1,0} gather(bf16[9,641,16,1536] "
     "%p, s32[10,2] %i), offset_dims={1,2}", 60, None),
    ("%gather.22 = bf16[10,16,1024]{2,1,0} gather(bf16[9,641,16,1024] "
     "%p, s32[10,2] %i), offset_dims={1,2}", 40, None),
    ("%fusion.21 = f32[8,8,16,160]{3,2,1,0} fusion(f32[8,8,16,192] %q, "
     "f32[8,160,192] %k), kind=kOutput", 200, None),
]
OPS = {"decode": DECODE_OPS, "prefill": CHUNK_OPS,
       "mixed": DECODE_OPS + CHUNK_OPS}       # the chunk rides
STEPS = [[("decode", 64)], [("prefill", 4), ("decode", 64)],
         [("mixed", 64)], [("decode", 64)], [("decode", 64)]]
ROWS = dict(window_rows=64 * 128, full_rows=64 * 1100)
WEIGHTS = 10_865_544_320 - 19_072 * 4096 * 2


def make_ctx(steps=STEPS, counters=True, row_bytes=True, experts=True,
             ops=OPS):
    """A traced window of ``steps`` as the engine runs them: a launch,
    then the read of the launch before it."""
    ctx = late.make_ctx(0.9, steps, ops=lambda kind: [
        (name, us * US) for name, us, _ in ops[kind]])
    for rec in ctx["spans"]:
        rec.update(uid=None)
        if experts:
            rec.update(expert_rows=320, experts_touched=130,
                       expert_rows_max=7)
        if counters:
            rec.update(ROWS, window_blocks_released=3,
                       window_blocks_live=600)
        if row_bytes:
            rec.update(kv_row_bytes=2560, window_row_bytes=5120)
    ctx.update(cell=harness.load_cell(CELL),
               device={"kind": "TPU v5 lite"})
    ctx["values"].update(weight_bytes=WEIGHTS, kv_bytes_per_token=5_120)
    return ctx


def test_the_cells_shapes_come_from_its_configuration():
    z = t.sizes(make_ctx())
    assert (z["block"], z["positions"], z["ring"]) == (16, 3072, 160)
    assert (z["full_rows"], z["window_rows"]) == ((768, 512), (1536, 1024))
    assert (z["full_layers"], z["window_layers"], z["heads"]) == (2, 9, 64)
    assert (z["experts"], z["routed"], z["top_k"]) == (16, 256, 8)
    assert (z["expert_layers"], z["ffn"], z["d"]) == (10, 2048, 4096)


def test_every_op_is_booked_under_its_mechanism_and_no_other():
    z = t.sizes(make_ctx())
    tests = {"full": t.attn_op(z, "full"), "window": t.attn_op(z, "window"),
             "share": t.share_op(z)}
    for name, _, want in DECODE_OPS + CHUNK_OPS:
        got = [k for k, test in tests.items() if test(name)]
        assert got == ([want] if want else []), name


def test_decode_side_events_are_found_by_ordinal():
    ctx = make_ctx()
    spans = t.decode_events(ctx)
    one = sum(us for _, us, _ in DECODE_OPS)
    chunk = sum(us for _, us, _ in CHUNK_OPS)
    assert sorted(b - a for a, b in spans) == [
        one * US, one * US, one * US, (one + chunk) * US]
    assert t.decode_ms(ctx) == pytest.approx((4 * one + chunk) / 4 / 1e3)
    assert t.decode_events(
        make_ctx([[("prefill", 4)], [("prefill", 4)]])) is None


def test_a_reads_bytes_are_the_programs_positions_times_its_bytes():
    """Each store's bytes a position come from the records
    (``kv_row_bytes``, ``window_row_bytes``), never from the
    configuration's head counts: a program that said other widths would
    read other shares."""
    ctx = make_ctx([[("decode", 64)]] * 4)
    read = lambda name: harness.read_layer_metric(name, ctx)
    assert read("split_kv_full_attn_device_ms") == pytest.approx(0.54)
    assert read("sink_window_attn_device_ms") == pytest.approx(1.27)
    assert read("share_ffn_device_ms") == pytest.approx(12.14)
    bw = flops.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    full = 64 * 1100 * 2560 * 2
    ring = 64 * 128 * 5120 * 9
    assert read("split_kv_full_attn_roofline") == pytest.approx(
        100 * (full / bw) / 0.54e-3)
    assert read("sink_window_attn_roofline") == pytest.approx(
        100 * (ring / bw) / 1.27e-3)
    expert = 3 * 4096 * 2048 * 2
    share = 130 * expert + 10 * 4 * 256 * 4097
    assert read("share_ffn_roofline") == pytest.approx(
        100 * (share / bw) / 12.14e-3)
    step = WEIGHTS - 10 * 16 * expert + 130 * expert + full + ring
    total = sum(us for _, us, _ in DECODE_OPS) / 1e6
    assert read("sink_moe_decode_step_roofline") == pytest.approx(
        100 * (step / bw) / total)
    assert read("share_rows_max_over_mean") == pytest.approx(
        7 / (320 / (10 * 16)))
    for name in NEW:
        assert 0 < read(name) <= (100 if name.endswith("_roofline")
                                  else 1e9), name
    for rec in ctx["spans"]:
        rec.update(kv_row_bytes=5120)
    assert read("split_kv_full_attn_roofline") == pytest.approx(
        100 * (2 * full / bw) / 0.54e-3)


def test_a_program_without_the_row_bytes_gives_nothing_to_read():
    """The parent's records (no ``kv_row_bytes``), a window-less
    family's, an untraced line: every new reader returns None and
    raises nothing."""
    for ctx in (make_ctx(row_bytes=False), make_ctx(counters=False),
                dict(make_ctx(), spans=[])):
        for name in NEW:
            assert harness.read_layer_metric(name, ctx) is None, name
    # no device trace (the CPU's rehearsal): the program's counter still
    # has its reader, the device metrics none
    ctx = dict(make_ctx(), trace=None)
    for name in NEW:
        got = harness.read_layer_metric(name, ctx)
        assert (got is not None) == (name == "share_rows_max_over_mean")
    # the experts' counters missing: the attention readers still read
    ctx = make_ctx(experts=False)
    assert harness.read_layer_metric("split_kv_full_attn_roofline", ctx) > 0
    assert harness.read_layer_metric("share_ffn_roofline", ctx) is None
    assert harness.read_layer_metric("share_rows_max_over_mean", ctx) is None


def test_the_benchmark_lists_the_new_metrics_for_the_cell_alone():
    cell = harness.load_cell(CELL)
    listed = {m["name"]: m for m in cell["per_layer"]}
    assert set(NEW) <= set(listed) and "window_pool_util" not in listed
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["unit"] == harness.read_json(
            "layer_metrics", name + ".json")["unit"]
    assert [m["name"] for m in cell["end_to_end"]] == [
        "out_tokens_per_s", "setup_s"]
    assert len(listed) == len(NEW) + 13


# -- against what the chip really wrote --------------------------------------


def test_the_eight_read_on_a_recorded_window_of_the_cell():
    """The first eight traced steps of the cell's first traced window on
    a TPU v5e (``record_window_on_chip.py --cut 8``, PR 49, seed
    4900000101): the records of the program itself (its counters, its
    two stores' bytes a position), the programs' events and every op
    inside them as the profiler names it. The numbers are the recorded
    steps', no measurement of this machine."""
    from benchmark import dispatch_join
    from benchmark.tests import record_window_on_chip as recorded
    ctx = recorded.load(FIXTURE)
    pairs = dispatch_join.paired(ctx)
    kinds = [p.kind for p in pairs]
    assert set(kinds) <= {"decode", "mixed", "prefill"}
    assert kinds.count("decode") + kinds.count("mixed") >= 7
    got = {n: harness.read_layer_metric(n, ctx) for n in NEW}
    # nine window layers' kernel calls and what stands round them, two
    # full layers', the share of the experts' one read
    assert got["sink_window_attn_device_ms"] == pytest.approx(1.342, abs=5e-3)
    assert got["split_kv_full_attn_device_ms"] == pytest.approx(0.941,
                                                                abs=5e-3)
    assert got["share_ffn_device_ms"] == pytest.approx(11.44, abs=0.02)
    assert got["sink_window_attn_roofline"] == pytest.approx(33.2, abs=0.2)
    assert got["split_kv_full_attn_roofline"] == pytest.approx(48.6, abs=0.2)
    assert got["share_ffn_roofline"] == pytest.approx(73.7, abs=0.2)
    assert got["sink_moe_decode_step_roofline"] == pytest.approx(58.3,
                                                                 abs=0.2)
    assert got["share_rows_max_over_mean"] == pytest.approx(3.51, abs=0.02)
    for name in NEW:
        assert 0 < got[name] < (100 if name.endswith("roofline") else 20)
    # the program said its stores' widths: 4 x (192 + 128) x 2 bytes a
    # position in a full layer, 8 x 320 x 2 in a window layer; and a
    # live row is past the window (128 positions a row a window layer)
    c = t.counters(ctx)
    assert (c["kv_row_bytes"], c["window_row_bytes"]) == (2560, 5120)
    assert 120 * 64 < c["window_rows"] <= 128 * 64 < c["full_rows"]
    # the two kinds' kernels are told by their tables: both have 64
    # query heads; a window layer's call is handed its sinks
    z = t.sizes(ctx)
    trace = ctx["trace"]["trace"]
    calls = [e[0] for e in trace["planes"]["/device:TPU:0"]["XLA Ops"]
             if "tpu_custom_call" in e[0]]
    sides = sum(k in ("decode", "mixed") for k in kinds)
    for which, tables, layers, out in (("full", "s32[64,192]", 2, 512),
                                       ("window", "s32[64,10]", 9, 1024)):
        mine = [c for c in calls if t.attn_op(z, which)(c)]
        assert len(mine) == layers * sides
        assert all(tables in c and "f32[64,64,%d]" % out in c for c in mine)
        assert all(("f32[64,1]" in c) is (which == "window") for c in mine)
    assert len(calls) == 11 * sides
