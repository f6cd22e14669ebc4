"""The trace reduction, checked on a hand-made trace whose answers are
known by construction and on a recorded one: a real chip trace of two
engine steps (``fixture_serve.json``)."""

import os

import pytest

from benchmark import harness, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"


def hand_made():
    ms = 1_000_000
    ops = [["fusion.1", 10 * ms, 20 * ms, "jit(run)/decode/attn"],
           ["fusion.2", 25 * ms, 10 * ms, "jit(run)/decode/head"],  # overlaps
           ["all-reduce.7", 50 * ms, 10 * ms, "jit(run)/comm"],
           ["fusion.3", 55 * ms, 10 * ms, "jit(run)/prefill/attn"],
           ["copy.4", 90 * ms, 5 * ms, ""]]
    host = [[harness.WINDOW, 0, 100 * ms, ""],
            [harness.ANNOTATION + "engine.step", 6 * ms, 39 * ms, ""],
            [harness.ANNOTATION + "stamp", 36 * ms, 8 * ms, ""],
            [harness.ANNOTATION + "wait_arrival", 66 * ms, 20 * ms, ""]]
    return {"planes": {DEV: {xplane.OPS_LINE: ops},
                       "/host:CPU": {"python": host}}}


def test_busy_is_the_union_of_op_intervals():
    tr = hand_made()
    lo, hi = xplane.window(tr, harness.WINDOW)
    assert (lo, hi) == (0.0, 100e6)
    got = xplane.busy(tr, lo, hi)[DEV]
    # [10,35] + [50,65] + [90,95] ms
    assert got["busy_s"] == pytest.approx(0.045)
    assert len(got["intervals"]) == 3


def test_op_seconds_group_and_clip():
    tr = hand_made()
    by_name = xplane.op_seconds(tr, 0, 100e6)
    assert by_name["fusion"][1] == 3
    assert by_name["fusion"][0] == pytest.approx(0.040)
    clipped = xplane.op_seconds(tr, 0, 20e6)          # half of fusion.1
    assert clipped["fusion"][0] == pytest.approx(0.010)
    decode = xplane.op_seconds(
        tr, 0, 100e6, key=lambda n, s: "d" if "/decode/" in s else None)
    assert decode == {"d": [pytest.approx(0.030), 2]}


def test_exposed_collective_time():
    tr = hand_made()
    total, exposed = xplane.exposed_seconds(
        tr, 0, 100e6, lambda n, s: n.startswith("all-reduce"))
    assert total == pytest.approx(0.010)
    assert exposed == pytest.approx(0.005)    # 50-55 ms uncovered


def test_idle_gaps_go_to_the_innermost_host_span():
    gaps = dict(xplane.idle_gaps(hand_made(), 0, 100e6, harness.ANNOTATION))
    # idle: [0,10] window only; [35,50] -> mid 42.5 inside stamp (inner)
    # and engine.step; [65,90] -> mid 77.5 in wait_arrival; [95,100]
    assert gaps[harness.ANNOTATION + "stamp"] == pytest.approx(0.015)
    assert gaps[harness.ANNOTATION + "wait_arrival"] == pytest.approx(0.025)
    assert gaps[harness.WINDOW] == pytest.approx(0.015)
    assert sum(gaps.values()) == pytest.approx(0.100 - 0.045)


def test_device_time_reader_divides_by_a_counter():
    tr = hand_made()
    ctx = {"trace": {"trace": tr, "lo": 0.0, "hi": 100e6},
           "values": {"traced_decode_dispatches": 2}}
    spec = {"pattern": "(^|/)decode(/|$)", "per": "traced_decode_dispatches",
            "scale": 1000.0}
    assert harness._device_time(spec, ctx) == pytest.approx(15.0)
    assert harness._device_time({"pattern": "nothing"}, ctx) is None


def test_span_quantile_reader():
    ctx = {"spans": [{"span": "queued", "duration_s": d}
                     for d in (0.1, 0.2, 0.3)] +
                    [{"span": "decode", "duration_s": 9.0}]}
    spec = {"span": "queued", "q": 0.5, "scale": 1000.0}
    assert harness._span_quantile(spec, ctx) == pytest.approx(200.0)
    assert harness._span_quantile({"span": "none", "q": 0.5}, ctx) is None


FIXTURE = os.path.join(HERE, "fixture_serve.json")


@pytest.fixture(scope="module")
def recorded():
    """Two engine steps of GPT-2 large at 20 slots x 1024 positions on
    the v5e (my chip run, PR 24: the diagnostic that found every layer
    re-laying-out the whole KV pool). Event names cut to 140 characters,
    the async-op line dropped; nothing else changed."""
    tr = xplane.load_json(FIXTURE)
    lo, hi = xplane.window(tr, harness.WINDOW)
    return tr, lo, hi


def test_recorded_busy_and_gaps(recorded):
    tr, lo, hi = recorded
    assert xplane.device_planes(tr) == [DEV]
    assert (hi - lo) / 1e9 == pytest.approx(6.497088907)
    b = xplane.busy(tr, lo, hi)[DEV]["busy_s"]
    assert b == pytest.approx(6.476941062)
    gaps = xplane.idle_gaps(tr, lo, hi, harness.ANNOTATION)
    assert sum(g[1] for g in gaps) == pytest.approx((hi - lo) / 1e9 - b)
    assert gaps[0][0] == harness.ANNOTATION + "engine.step"


def test_recorded_ops_fall_under_stable_labels(recorded):
    tr, lo, hi = recorded
    ops = xplane.op_seconds(tr, lo, hi)
    top = max(ops, key=lambda k: ops[k][0])
    # 282 whole-pool copies, 3.4 of the window's 6.5 s
    assert top == "copy fusion.remat_uncompressed bf16[36,1281,20,16,64]"
    assert ops[top][1] == 282
    assert ops[top][0] == pytest.approx(3.4091, abs=1e-3)
    assert not any(xplane.is_collective(e[0]) for e in
                   tr["planes"][DEV][xplane.OPS_LINE])


def test_recorded_programs_are_told_apart(recorded):
    """Each traced step ran one prefill chunk, then one decode batch."""
    from benchmark import engine_trace
    from benchmark.serve import Step
    tr, lo, hi = recorded
    step = Step(0, 0, 0, 0, 0, 1, 1, True)
    ctx = {"trace": {"trace": tr, "lo": lo, "hi": hi},
           "values": {"traced_steps": [step, step]}}
    got = engine_trace.program_seconds(ctx)
    assert got["prefill"][1] == 2 and got["decode"][1] == 2
    assert engine_trace.program_ms(ctx, "prefill") == pytest.approx(
        1629.975, abs=0.01)
    assert engine_trace.program_ms(ctx, "decode") == pytest.approx(
        1608.570, abs=0.01)
    # a step whose dispatch count does not match its events is skipped
    ctx["values"]["traced_steps"] = [step._replace(n_decode=2), step]
    assert engine_trace.program_seconds(ctx)["decode"][1] == 1
