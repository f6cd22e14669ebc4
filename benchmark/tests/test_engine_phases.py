"""The readers of the engine's own step spans (``engine_phases.py``), on
a hand-made ``ctx`` whose answers are known by construction: records
as the engine emits them, a small trace in ``xplane.py``'s flat
structure, and the driver's traced steps."""

import copy

import pytest

from benchmark import engine_phases, harness
from benchmark.serve import Step

MS = 1_000_000
DEV = "/device:TPU:0"
# the profiler's clock starts at the trace's start; the records are on
# the wall clock
TRACE_START = 1_790_000_000 * 1_000 * MS
READERS = ["step_host_ms.offline", "step_launch_ms.offline",
           "step_readback_ms.offline", "device_idle_host_pct.offline",
           "device_idle_launch_pct.offline"]
SPAN_ONLY, IDLE = READERS[:3], READERS[3:]
LEAD = 10_000       # ns from the driver's span opening to the engine's


def _step(k: int, t0: int):
    """One step on the profiler's clock from ``t0``: a record (shifted
    to the wall clock), its bench event, the device's ops. 60 ms:

      1-3 admit (host)  3-5 decode.upload  5-6 decode.dispatch
      6-56 decode.readback  56-59 decode.emit (host)  59-60 digest
      device busy 8-54
    """
    def at(ms):
        return t0 + int(ms * MS) + TRACE_START
    rec = {"span": "engine_step", "uid": None, "step": k,
           "tokens_generated": 100 + 3 * k,
           "start_ns": at(1), "end_ns": at(60),
           "phases": [["admit", at(1), at(3)],
                      ["decode.upload", at(3), at(5)],
                      ["decode.dispatch", at(5), at(6)],
                      ["decode.readback", at(6), at(56)],
                      ["decode.emit", at(56), at(59)],
                      ["digest", at(59), at(60)]]}
    # the driver's span: opens 10 us before the engine's, closes 30 us
    # after it (the record being built)
    event = [harness.ANNOTATION + "engine.step", t0 + MS - LEAD,
             59 * MS + LEAD + 30_000, ""]
    stamp = [harness.ANNOTATION + "stamp", t0 + 61 * MS, 2 * MS, ""]
    op = ["fusion.1", t0 + 8 * MS, 46 * MS, ""]
    step = Step(0.0, 0.0, 3, 100 + 3 * k, 0, 0, 1, True)
    return rec, event, stamp, op, step


def make_ctx(n=3, before=2):
    """``before`` untraced steps' records, then ``n`` traced steps of
    64 ms each inside a window that opens 1 ms before the first."""
    recs, events, ops, steps = [], [], [], []
    for k in range(before):
        recs.append(_step(k, -(before - k) * 64 * MS)[0])
    lo = 10 * MS
    for i in range(n):
        rec, event, stamp, op, step = _step(before + i,
                                            lo + MS + i * 64 * MS)
        recs.append(rec)
        events += [event, stamp]
        ops.append(op)
        steps.append(step)
    hi = lo + MS + n * 64 * MS
    host = [[harness.WINDOW, lo, hi - lo, ""]] + events
    trace = {"planes": {DEV: {"XLA Ops": ops},
                        "/host:CPU": {"python": host}}}
    return {"values": {"traced_steps": steps},
            "spans": [{"span": "decode", "uid": 4, "duration_s": 1.0}]
            + recs,
            "trace": {"trace": trace, "lo": float(lo), "hi": float(hi),
                      "busy_s": 0.0, "window_s": (hi - lo) / 1e9}}


def read_all(ctx):
    return {name: harness.read_layer_metric(name, ctx) for name in READERS}


def test_class_sums_and_gap_split():
    ctx = make_ctx()
    got = read_all(ctx)
    span_ms = engine_phases.mean_ms(ctx, "span")
    assert span_ms == pytest.approx(59.0)
    assert got["step_host_ms.offline"] == pytest.approx(2 + 3 + 1)
    assert got["step_launch_ms.offline"] == pytest.approx(2 + 1)
    assert got["step_readback_ms.offline"] == pytest.approx(50.0)
    assert (got["step_host_ms.offline"] + got["step_launch_ms.offline"]
            + got["step_readback_ms.offline"]) == pytest.approx(span_ms)
    # a step's idle: host 2 (admit) + 4 (emit, digest); launch 3, and
    # the readback's 2 before the device starts and 2 after it ends
    window_ms = 1 + 3 * 64
    assert got["device_idle_host_pct.offline"] == pytest.approx(
        100 * 3 * 6 / window_ms)
    assert got["device_idle_launch_pct.offline"] == pytest.approx(
        100 * 3 * 7 / window_ms)
    # the rest of the idle time is the driver's: no class has it
    idle_ms = window_ms - 3 * 46
    assert 3 * (6 + 7) < idle_ms


def test_a_gap_is_split_over_the_phases_that_overlap_it():
    """One gap from the device's last op of a step to the first of the
    next crosses six phases and the driver's part of the loop."""
    ctx = make_ctx(n=2)
    got = engine_phases.idle_ns(ctx)
    assert got[engine_phases.HOST] == pytest.approx(2 * 6 * MS)
    assert got[engine_phases.LAUNCH] == pytest.approx(2 * 3 * MS)
    assert got[engine_phases.WAIT] == pytest.approx(2 * 4 * MS)


def test_the_join_is_by_tokens_generated_not_by_position():
    ctx = make_ctx(n=3, before=5)
    recs = engine_phases.traced_records(ctx)
    assert [r["step"] for r in recs] == [5, 6, 7]
    assert [r["tokens_generated"] for r in recs] == [
        st.tokens for st in ctx["values"]["traced_steps"]]


def test_one_shift_for_every_record_is_the_trace_start():
    ctx = make_ctx()
    recs = engine_phases.traced_records(ctx)
    # exact but for the 10 us between the two spans' openings
    assert engine_phases.profiler_shift(ctx, recs) == TRACE_START + LEAD


def test_records_moved_against_their_events_give_no_idle_share():
    """The clock of some records 5 ms off the others': no one shift
    puts every record inside its event, so the two device-idle readers
    say nothing; the three span-only ones need no clock and still
    read."""
    ctx = make_ctx()
    rec = next(r for r in ctx["spans"] if r.get("step") == 3)
    rec["start_ns"] += 5 * MS
    rec["end_ns"] += 5 * MS
    rec["phases"] = [[n, s + 5 * MS, e + 5 * MS]
                     for n, s, e in rec["phases"]]
    got = read_all(ctx)
    assert all(got[name] is None for name in IDLE)
    assert got["step_readback_ms.offline"] == pytest.approx(50.0)
    assert all(got[name] is not None for name in SPAN_ONLY)


def test_a_uniform_shift_is_the_measured_offset_and_moves_nothing():
    """All records 5 ms later is just another trace start."""
    base, ctx = read_all(make_ctx()), make_ctx()
    for rec in ctx["spans"]:
        if rec["span"] == "engine_step":
            rec["start_ns"] += 5 * MS
            rec["end_ns"] += 5 * MS
            rec["phases"] = [[n, s + 5 * MS, e + 5 * MS]
                             for n, s, e in rec["phases"]]
    assert read_all(ctx) == pytest.approx(base)


def test_a_traced_step_with_no_record_gives_nothing():
    ctx = make_ctx()
    ctx["spans"] = [r for r in ctx["spans"] if r.get("step") != 3]
    assert all(v is None for v in read_all(ctx).values())


def test_an_ambiguous_join_gives_nothing():
    ctx = make_ctx(n=2, before=2)
    for rec in ctx["spans"]:
        if rec["span"] == "engine_step":
            rec["tokens_generated"] = 7
    for i, st in enumerate(ctx["values"]["traced_steps"]):
        ctx["values"]["traced_steps"][i] = st._replace(tokens=7)
    assert all(v is None for v in read_all(ctx).values())


@pytest.mark.parametrize("missing", ["records", "trace", "device"])
def test_nothing_to_read_is_none_not_an_error(missing):
    """A program that emits no ``engine_step`` record (the parent
    commit), an untraced run, a trace with no device plane (the CPU)."""
    ctx = copy.deepcopy(make_ctx())
    if missing == "records":
        ctx["spans"] = [s for s in ctx["spans"]
                        if s["span"] != "engine_step"]
        want_none = READERS
    elif missing == "trace":
        ctx["trace"] = None
        want_none = IDLE
    else:
        del ctx["trace"]["trace"]["planes"][DEV]
        want_none = IDLE
    got = read_all(ctx)
    assert [n for n in READERS if got[n] is None] == list(want_none)


def test_phase_classes():
    cls = engine_phases.phase_class
    assert cls("prefill.upload") == cls("decode.dispatch") == "launch"
    assert cls("prefill.readback") == cls("decode.readback") == "wait"
    for name in ("expire", "admit", "prefill.cow", "prefill.book",
                 "decode.marshal", "decode.cow", "decode.emit", "digest"):
        assert cls(name) == "host"


def test_cpu_rehearsal_reads_the_span_only_metrics(monkeypatch):
    """The serving cell's traced run at tiny size on the CPU, through
    the real engine and the real collector: the three span-only metrics
    are on the line; the two that lay phases against device events are
    absent (the CPU's trace has no device plane)."""
    from benchmark import flops, run
    from benchmark.tests import shrink
    real = flops.peaks
    monkeypatch.setattr(flops, "peaks", lambda kind: real("TPU v5 lite"))
    name = "gpt2-large.batch-offline"
    line = run.run_cell(name, 2**31 + 4242, 1.5, True, check_device=False,
                        shrink=shrink.for_cell(name))
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(SPAN_ONLY) <= set(got) and not set(IDLE) & set(got)
    assert all(got[name] > 0 for name in SPAN_ONLY)
    assert line["metrics"]["step_host_ms.offline"]["unit"] == "ms"


def test_the_clock_check_runs():
    """``phases_on_chip.py`` at tiny size on the CPU: on the CPU's own
    trace what it is there to show on the chip — the program's stamps
    and the profiler's events are one clock, shifted by the trace's
    start."""
    from benchmark.tests import phases_on_chip
    out = phases_on_chip.clock(steps=6, vocab=64, d_model=32, layers=2,
                               heads=4)
    assert out["steps"] == 6
    assert abs(out["record_minus_event_minus_trace_start_ns"]["median"]) \
        < 200_000
    assert abs(out["duration_record_minus_event_ns"]["median"]) < 200_000
