"""The gated delta-rule cell's readers (``benchmark/delta_trace.py`` and
the six ``layer_metrics`` files that read through it) on a hand-made
trace and hand-made ``engine_step`` records of an engine that launches,
then collects (``test_dispatch_join_late.make_ctx``), and on a recorded
window of the cell from the chip (``record_window_on_chip.py`` made
``fixture_qwen3-next.longreason-offline.json.gz``): the decode-side
programs are found by ORDINAL, the delta rule's device time is its two
kernels a gated-delta layer and what stands between them and touches
the state — told by the stores' shapes and the state row's two widths —
and the state's bytes are the program's own ``state_bytes``, read and
written. Nothing here is a measurement."""

import os

import pytest

from benchmark import delta_trace as t, flops, harness
from benchmark.tests import test_dispatch_join_late as late

CELL = "qwen3-next.longreason-offline"
NEW = ("delta_rule_device_ms", "delta_rule_roofline", "fine_ffn_device_ms",
       "fine_ffn_roofline", "fine_rows_max_over_mean",
       "delta_moe_decode_step_roofline")
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       f"fixture_{CELL}.json.gz")

US = 1_000
ROWS_1 = "{2,1,0:T(1,128)S(1)}"
TAILS = "f32[9,129,1,24576]{3,2,1,0:T(1,128)}"
STATES = "f32[9,129,128,4096]{3,2,1,0:T(8,128)}"


def _conv(n):
    """A convolution kernel's call as the profiler names it on the chip
    (the compiled program's text, ``tests/test_chip_compile.py``): the
    rows' ``[b, 1, C]`` and the tails' store whole, in and out."""
    return (f"%ssm.{n} = (f32[128,1,8192]{ROWS_1}, {TAILS}) custom-call("
            f"s32[128]{{0}} %rows, f32[128,1,8192]{ROWS_1} %x.{n}, "
            f"f32[4,8192]{{1,0}} %w.{n}, {TAILS} %conv.{n}), "
            'custom_call_target="tpu_custom_call", '
            "operand_layout_constraints={s32[128]{0}, f32[128,1,8192]{2,1,0}}")


def _delta(n):
    """... and a delta kernel's: the three rows and the columns a batch
    row brings, the matrices' store whole, in and out."""
    return (f"%delta_step.{n} = (f32[128,1,4096]{ROWS_1}, {STATES}) "
            f"custom-call(s32[128]{{0}} %rows, f32[128,3,4096]"
            f"{{2,1,0:T(4,128)S(1)}} %copy.{n}, f32[128,2,128,32]"
            f"{{3,2,1,0:T(8,128)S(1)}} %custom-call.{n}, {STATES} "
            f'%ssm.{n}), custom_call_target="tpu_custom_call", '
            "operand_layout_constraints={s32[128]{0}, f32[128,3,4096]{2,1,0}}")


# a decode program's ops as the profiler names them (HLO text), by
# mechanism, microseconds each; the shapes are the cell's
DECODE_OPS = [
    (_conv(3), 150, "delta"), (_delta(3), 900, "delta"),
    (_conv(4), 150, "delta"), (_delta(4), 900, "delta"),
    # between the kernels: the rows and the columns a batch row brings
    ("%pad_maximum_fusion = f32[128,2,128,32]{3,2,1,0:T(8,128)} fusion("
     "f32[128,2,128,16] %k, f32[128,2,128,16] %q), kind=kLoop", 20, "delta"),
    ("%copy.153 = f32[128,3,4096]{2,1,0:T(4,128)S(1)} copy("
     "f32[128,3,4096] %pad_maximum_fusion.4)", 10, "delta"),
    ("%slice_bitcast_fusion.1 = f32[128,1,4096]{2,0,1:T(8,128)S(1)} fusion("
     "f32[128,32,128] %bitcast_multiply_fusion.2), kind=kLoop", 10, "delta"),
    # none: the gated norm fused into ``W_out``'s product takes a
    # kernel's result and moves weights, not state; the K projection's
    # result and the pool's write show a row as wide as the router
    # (512) and are the full layer's
    ("%multiply_reduce_fusion.15 = (f32[128]{0}, f32[128,2048]{1,0}) fusion("
     "f32[128,2048] %x, bf16[9,2048,4096]{2,1,0} %p_delta_w_out.1, "
     "f32[128,1,4096]{2,1,0:T(1,128)S(1)} %pallas_call.108, f32[128,4096] "
     "%z), kind=kOutput", 200, None),
    ("%fusion.561 = f32[128,512]{1,0} fusion(bf16[128,2048] %a, "
     "bf16[3,512,2048]{2,1,0} %p_full_wk.1), kind=kOutput", 15, None),
    ("%fusion.19 = bf16[3,24577,16,512]{3,2,1,0} fusion(bf16[3,24577,16,512] "
     "%p, s32[128] %i, bf16[128,512] %k), kind=kLoop", 10, None),
    ("%fusion.1141 = (f32[128,32]{0,1}, f32[128,32]{0,1}) fusion("
     "f32[128,64]{0,1} %ba, f32[32]{0} %a_log, f32[32]{0} %dt), kind=kLoop",
     5, None),
    # none: the mixer's weight products, the full layer's walk (heads of
    # 256 lanes: a row of 512 a side), its lane gate, the head
    ("%fusion.54 = f32[128,8192]{1,0} fusion(bf16[128,2048] %a, "
     "bf16[9,8192,2048] %p_delta_w_qkv), kind=kOutput", 120, None),
    ("%fusion.55 = f32[128,4096]{1,0} fusion(bf16[128,2048] %a, "
     "bf16[9,4096,2048] %p_delta_w_z), kind=kOutput", 60, None),
    ("%_walk.1 = f32[128,16,512]{2,1,0:T(8,128)S(1)} custom-call("
     "s32[128,192]{1,0} %tables, bf16[128,16,512] %q, "
     "bf16[3,24577,16,512]{3,2,1,0} %k, bf16[3,24577,16,512]{3,2,1,0} %v), "
     'custom_call_target="tpu_custom_call"', 400, None),
    ("%fusion.60 = f32[128,4096]{1,0} fusion(bf16[128,2048] %a, "
     "bf16[3,4096,2048] %p_w_gate), kind=kOutput", 60, None),
    ("%fusion.11 = f32[128,18992]{1,0} fusion(bf16[128,2048] %x, "
     "bf16[18992,2048] %head), kind=kOutput", 100, None),
    # the share of the experts: the router, the choice, the held
    # experts' three products, the shared expert's stacks
    ("%fusion.7 = f32[128,512]{1,0} fusion(f32[128,2048] %a, "
     "f32[12,512,2048] %r), kind=kOutput", 60, "fine"),
    ("%fusion.70 = s32[128,10]{1,0} fusion(f32[128,512] %s), kind=kLoop",
     30, "fine"),
    ("%fusion.8 = bf16[128,64,512]{2,1,0} fusion(bf16[128,2048] %a, "
     "bf16[12,64,512,2048] %g, bf16[12,64,512,2048] %u), kind=kOutput",
     400, "fine"),
    ("%fusion.9 = f32[128,2048]{1,0} fusion(bf16[128,64,512] %h, "
     "bf16[12,64,2048,512] %d), kind=kOutput", 200, "fine"),
    ("%fusion.90 = f32[128,2048]{1,0} fusion(bf16[128,512] %h, "
     "bf16[12,2048,512] %p_shared_w_down), kind=kOutput", 10, "fine"),
]
# a prefill chunk's: ONE slot's state sliced out of the stores and the
# scan over it, booked under the delta rule only where it moves the
# stores whole (it does not: a chunk's slices are rows)
CHUNK_OPS = [
    ("%fusion.21 = f32[16,4096]{1,0} fusion(f32[128,4096] %s, "
     "f32[16,32,128] %k), kind=kLoop", 200, None),
    ("%gather.20 = bf16[192,16,512]{2,1,0} gather(bf16[3,24577,16,512] "
     "%p, s32[192,2] %i), offset_dims={1,2}", 300, None),
]
OPS = {"decode": DECODE_OPS, "prefill": CHUNK_OPS,
       "mixed": DECODE_OPS + CHUNK_OPS}       # the chunk rides
STEPS = [[("decode", 128)], [("prefill", 4), ("decode", 128)],
         [("mixed", 128)], [("decode", 128)], [("decode", 128)]]
ROW = 4 * (128 * 4096 + 3 * 8192)             # a slot's bytes a layer
STATE = 128 * 9 * ROW
WEIGHTS = 5_883_914_624 - 18_992 * 2048 * 2


def make_ctx(steps=STEPS, state=True, row_bytes=True, experts=True,
             ops=OPS):
    """A traced window of ``steps`` as the engine runs them: a launch,
    then the read of the launch before it."""
    ctx = late.make_ctx(0.9, steps, ops=lambda kind: [
        (name, us * US) for name, us, _ in ops[kind]])
    for rec in ctx["spans"]:
        rec.update(uid=None)
        if experts:
            rec.update(expert_rows=1900, experts_touched=700,
                       expert_rows_max=9)
        if state:
            rec.update(state_bytes=STATE, state_row_bytes=2_097_152,
                       tail_row_bytes=98_304)
        if row_bytes:
            rec.update(kv_row_bytes=2048, kv_blocks_read=3 * 128 * 70,
                       kv_blocks_capacity=3 * 128 * 192)
    ctx.update(cell=harness.load_cell(CELL),
               device={"kind": "TPU v5 lite"})
    ctx["values"].update(weight_bytes=WEIGHTS, kv_bytes_per_token=6_144)
    return ctx


def test_the_cells_shapes_come_from_its_configuration():
    z = t.sizes(make_ctx())
    assert (z["block"], z["rows"], z["delta_layers"]) == (16, 129, 9)
    assert (z["key_dim"], z["value_dim"], z["lanes"]) == (128, 128, 4096)
    assert (z["conv"], z["tail"]) == (8192, 24576)
    assert (z["experts"], z["routed"], z["top_k"]) == (64, 512, 10)
    assert (z["expert_layers"], z["ffn"], z["shared_ffn"], z["d"]) == (
        12, 512, 512, 2048)
    # the row's bytes from the configuration's keys are the program's
    # (``tests/test_qwen3_next_lm.py`` counts them from the arrays)
    assert t.state_row_bytes(z) == ROW == 2_195_456
    # ... and the kernel's arithmetic is under an operation a byte moved
    flops = t.delta_rule_flops(z, STATE)
    assert flops == 7 * 128 * 9 * 128 * 4096
    assert flops / t.delta_rule_bytes(STATE) < 1


def test_every_op_is_booked_under_its_mechanism_and_no_other():
    z = t.sizes(make_ctx())
    tests = {"delta": t.delta_op(z), "fine": t.fine_op(z)}
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


def test_the_states_bytes_are_the_programs_read_and_written():
    """The delta rule's roofline takes the program's ``state_bytes``
    (the rows a step launched, once) twice, read and written; a program
    that launched half the rows would read half the share."""
    ctx = make_ctx([[("decode", 128)]] * 4)
    read = lambda name: harness.read_layer_metric(name, ctx)
    assert read("delta_rule_device_ms") == pytest.approx(2.14)
    assert read("fine_ffn_device_ms") == pytest.approx(0.70)
    bw = flops.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert read("delta_rule_roofline") == pytest.approx(
        100 * (2 * STATE / bw) / 2.14e-3)
    expert = 3 * 2048 * 512 * 2
    fine = 700 * expert + 12 * (4 * 512 * 2048 + expert)
    assert read("fine_ffn_roofline") == pytest.approx(
        100 * (fine / bw) / 0.70e-3)
    kv = 3 * 128 * 70 * 16 * 2048
    step = WEIGHTS - 12 * 64 * expert + 700 * expert + 2 * STATE + kv
    total = sum(us for _, us, _ in DECODE_OPS) / 1e6
    assert read("delta_moe_decode_step_roofline") == pytest.approx(
        100 * (step / bw) / total)
    assert read("fine_rows_max_over_mean") == pytest.approx(
        9 / (1900 / (12 * 64)))
    for rec in ctx["spans"]:
        rec.update(state_bytes=STATE // 2)
    assert read("delta_rule_roofline") == pytest.approx(
        100 * (STATE / bw) / 2.14e-3)


def test_a_program_without_the_state_bytes_gives_nothing_to_read():
    """The parent's records, a family with no recurrent layer, an
    untraced line: every new reader returns None and raises nothing."""
    for ctx in (make_ctx(state=False), make_ctx(row_bytes=False),
                dict(make_ctx(), spans=[])):
        for name in NEW:
            assert harness.read_layer_metric(name, ctx) is None, name
    # no device trace (the CPU's rehearsal): the program's counter still
    # has its reader, the device metrics none
    ctx = dict(make_ctx(), trace=None)
    for name in NEW:
        got = harness.read_layer_metric(name, ctx)
        assert (got is not None) == (name == "fine_rows_max_over_mean")
    # the experts' counters missing: the delta rule's readers still read
    ctx = make_ctx(experts=False)
    assert harness.read_layer_metric("delta_rule_roofline", ctx) > 0
    assert harness.read_layer_metric("fine_ffn_roofline", ctx) is None
    assert harness.read_layer_metric("fine_rows_max_over_mean", ctx) is None


def test_the_benchmark_lists_the_new_metrics_for_the_cell_alone():
    cell = harness.load_cell(CELL)
    listed = {m["name"]: m for m in cell["per_layer"]}
    assert set(NEW) <= set(listed) and "state_bytes_live" in listed
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["unit"] == harness.read_json(
            "layer_metrics", name + ".json")["unit"]
    assert [m["name"] for m in cell["end_to_end"]] == [
        "out_tokens_per_s", "setup_s"]
    assert len(listed) == len(NEW) + 14


# -- against what the chip really wrote --------------------------------------


def test_the_six_read_on_a_recorded_window_of_the_cell():
    """The first eight traced steps of the cell's first traced window on
    a TPU v5e (``record_window_on_chip.py --cut 8``, PR 52, seed
    5200000101): the records of the program itself (its counters, the
    state's bytes), the programs' events and every op inside them as the
    profiler names it. The numbers are the recorded steps', no
    measurement of this machine."""
    from benchmark import dispatch_join
    from benchmark.tests import record_window_on_chip as recorded
    ctx = recorded.load(FIXTURE)
    pairs = dispatch_join.paired(ctx)
    kinds = [p.kind for p in pairs]
    assert set(kinds) <= {"decode", "mixed", "prefill"}
    sides = sum(k in ("decode", "mixed") for k in kinds)
    assert sides >= 7
    got = {n: harness.read_layer_metric(n, ctx) for n in NEW}
    # nine layers' two kernels and what stands between them; the share
    # of the experts' one read beside the shared expert's
    assert got["delta_rule_device_ms"] == pytest.approx(8.572, abs=5e-3)
    assert got["delta_rule_roofline"] == pytest.approx(72.06, abs=0.1)
    assert got["fine_ffn_device_ms"] == pytest.approx(7.224, abs=5e-3)
    assert got["fine_ffn_roofline"] == pytest.approx(75.50, abs=0.1)
    assert got["delta_moe_decode_step_roofline"] == pytest.approx(68.58,
                                                                  abs=0.1)
    assert got["fine_rows_max_over_mean"] == pytest.approx(4.237, abs=0.01)
    for name in NEW:
        assert 0 < got[name] < (100 if name.endswith("roofline") else 20)
    # the program said what its launched rows hold: whole rows of nine
    # layers at 2,195,456 bytes, no more than the 128 slots'
    c = t.counters(ctx)
    assert c["state_bytes"] % (9 * ROW) == 0
    assert 100 * 9 * ROW < c["state_bytes"] <= STATE
    assert c["kv_row_bytes"] == 2048 and c["kv_blocks_read"] > 3 * 128
    # the two kernels are found by their own names and their stores,
    # one call each a gated-delta layer a decode-side program; the full
    # layers' walk is neither mechanism's
    z = t.sizes(ctx)
    trace = ctx["trace"]["trace"]
    calls = [e[0] for e in trace["planes"]["/device:TPU:0"]["XLA Ops"]
             if "tpu_custom_call" in e[0]]
    mine = [c for c in calls if t.delta_op(z)(c)]
    assert len(mine) == 2 * 9 * sides
    assert sum(c.startswith("%delta_step") and "f32[9,129,128,4096]" in c
               for c in mine) == 9 * sides
    assert sum(c.startswith("%ssm") and "f32[9,129,1,24576]" in c
               for c in mine) == 9 * sides
    walks = [c for c in calls if c not in mine]
    assert len(walks) == 3 * sides and not any(map(t.fine_op(z), walks))
    assert all("bf16[3,24577,16,512]" in c for c in walks)
