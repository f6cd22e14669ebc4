"""The benchmark's join of device programs to dispatches BY ORDER
(``benchmark/dispatch_join.py``) on hand-made traces in ``xplane``'s
flattened form: the k-th ``jit_run`` program is the k-th entry of the
traced records' ``dispatches``, so the three device times and the
dispatch overhead read the same whatever the profiler's device plane
leads its host plane by (where ``engine_trace.program_seconds``, which
compares the two planes' stamps, names the programs for each other);
the lead itself is recovered to within the interval the pairs allow;
and a trace the join cannot vouch for gives every reader None."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import dispatch_join, engine_trace, harness, serve  # noqa: E402

MS = 1_000_000
WALL0 = 1_790_000_000 * 1_000 * MS      # the trace began here (wall ns)
# device ms of each program, as the cells' builders read them
DEVICE = {("decode", 8): 8.15, ("mixed", 12): 9.6, ("prefill", 4): 3.1,
          ("prefill", 2): 2.7, ("verify", 8): 8.9}
LAUNCH, RETURN = 0.3, 0.9               # ms round a program, host side
METRICS = ["decode_program_device_ms", "mixed_program_device_ms",
           "chunk_program_device_ms", "dispatch_overhead_ms.offline",
           "device_plane_lead_ms", "launch_latency_ms.offline"]
# riding steps, a tail's two-program step among them, chunk-less steps
STEPS = [[("mixed", 12)], [("mixed", 12)],
         [("prefill", 4), ("decode", 8)], [("mixed", 12)],
         [("decode", 8)], [("prefill", 2), ("decode", 8)],
         [("decode", 8)], [("verify", 8)]]


def _ctx(lead_ms=0.0, steps=STEPS, jitter=(0.0, 0.05, 0.11)):
    """A traced window of ``steps`` (each a list of the step's
    dispatches). Host stamps are wall-clock ns, the trace's events are
    relative to ``WALL0``, and the device plane's are ``lead_ms``
    early. ``jitter`` varies the launch side from dispatch to dispatch
    so that the feasible interval is set by more than one pair."""
    t = 10 * MS                           # trace clock, ns
    recs, spans, mods, traced, tokens, k = [], [], [], [], 100, 0
    for i, step in enumerate(steps):
        s0 = t
        t += 150_000                      # expire, admit, marshal
        phases = [["admit", WALL0 + s0 + 20_000, WALL0 + t]]
        for kind, bucket in step:
            phase = "decode" if kind == "verify" else kind
            launch = int((LAUNCH + jitter[k % len(jitter)]) * MS)
            dur = int(DEVICE[kind, bucket] * MS)
            k += 1
            d0, d1 = t, t + 200_000       # the jitted call returns early
            ev0 = d0 + launch
            r1 = ev0 + dur + int(RETURN * MS)
            phases += [[phase + ".dispatch", WALL0 + d0, WALL0 + d1],
                       [phase + ".readback", WALL0 + d1, WALL0 + r1]]
            mods.append([f"jit_run({hash((kind, bucket)) % 10 ** 8})",
                         ev0 - int(lead_ms * MS), dur, ""])
            t = r1 + 100_000              # book, emit
        tokens += 12
        recs.append({"span": "engine_step", "tokens_generated": tokens,
                     "start_ns": WALL0 + s0 + 10_000, "end_ns": WALL0 + t,
                     "phases": phases,
                     "dispatches": [list(d) for d in step]})
        spans.append(["bench:engine.step", s0, t - s0 + 10_000, ""])
        n_chunk = sum(kind in ("prefill", "mixed") for kind, _ in step)
        traced.append(serve.Step(0, 0, 12, tokens, 4_000, n_chunk,
                                 len(step) - n_chunk, True))
        t += 300_000                      # the driver's own loop
    # a pool op is a program too, and no step program
    mods.append(["jit_copy_block(7)", 10 * MS + 400_000, 20_000, ""])
    trace = {"planes": {"/device:TPU:0": {"XLA Modules": mods,
                                          "XLA Ops": []},
                        "/host:CPU": {"main": spans}}}
    return {"values": {"traced_steps": traced}, "spans": recs,
            "trace": {"trace": trace, "lo": 9 * MS, "hi": t + MS}}


def _read(ctx):
    return {m: harness.read_layer_metric(m, ctx) for m in METRICS}


def _mean(pairs):
    return sum(DEVICE[p] for p in pairs) / len(pairs)


@pytest.mark.parametrize("lead_ms", [0.9, -0.15])
def test_device_times_and_overhead_do_not_depend_on_the_lead(lead_ms):
    got = _read(_ctx(lead_ms))
    flat = [d for step in STEPS for d in step]
    assert got["decode_program_device_ms"] == pytest.approx(8.15)
    assert got["mixed_program_device_ms"] == pytest.approx(9.6)
    # the two tails' programs, every chunk bucket in one mean
    assert got["chunk_program_device_ms"] == pytest.approx(
        _mean([("prefill", 4), ("prefill", 2)]))
    # a launch and a read beyond the program: verify dispatches count
    launches = [LAUNCH + (0.0, 0.05, 0.11)[k % 3] for k in range(len(flat))]
    assert got["dispatch_overhead_ms.offline"] == pytest.approx(
        sum(launches) / len(flat) + RETURN, abs=1e-6)
    # the lead: inside the feasible interval, which is as wide as the
    # shortest launch and the shortest return leave it
    join = dispatch_join.joined(_ctx(lead_ms))
    lo, hi = (x / MS for x in join.lead)
    assert lo <= lead_ms <= hi
    assert hi - lo == pytest.approx(LAUNCH + RETURN, abs=2e-3)
    assert abs(got["device_plane_lead_ms"] - lead_ms) <= (hi - lo) / 2
    # the launch side, known to half that width; the rest is the return
    assert abs(got["launch_latency_ms.offline"]
               - sum(launches) / len(flat)) <= (hi - lo) / 2 + 1e-6
    assert 0 <= got["launch_latency_ms.offline"] <= \
        got["dispatch_overhead_ms.offline"]


def test_new_readers_agree_where_the_span_readers_swap():
    """A lead of 0.9 ms puts a step's first program in the previous
    step's span: ``engine_trace`` then reads the tails' chunks as
    decode programs. The join by order reads both traces alike."""
    early, level = _ctx(0.9), _ctx(-0.15)
    old = [engine_trace.program_ms(c, "decode") for c in (early, level)]
    assert old[1] == pytest.approx(8.15 + (8.9 - 8.15) / 5)  # a verify too
    assert abs(old[0] - old[1]) > 0.5
    new = [_read(c) for c in (early, level)]
    for m in METRICS[:4]:
        assert new[0][m] == pytest.approx(new[1][m], abs=1e-6), m
    assert new[0]["device_plane_lead_ms"] - new[1]["device_plane_lead_ms"] \
        == pytest.approx(1.05, abs=1e-6)


def test_a_two_program_tail_step_among_riding_steps():
    steps = [[("mixed", 12)], [("prefill", 4), ("decode", 8)],
             [("mixed", 12)]]
    ctx = _ctx(0.9, steps)
    pairs = dispatch_join.joined(ctx).pairs
    assert [(p.kind, p.bucket, p.dur_ns / MS) for p in pairs] == [
        ("mixed", 12, 9.6), ("prefill", 4, 3.1), ("decode", 8, 8.15),
        ("mixed", 12, 9.6)]
    # the tail's two programs lie inside ONE step's record
    rec = ctx["spans"][1]
    assert rec["start_ns"] <= pairs[1].dispatch_ns < pairs[1].readback_ns \
        <= pairs[2].dispatch_ns < pairs[2].readback_ns <= rec["end_ns"]
    got = _read(ctx)
    assert got["chunk_program_device_ms"] == pytest.approx(3.1)
    assert got["decode_program_device_ms"] == pytest.approx(8.15)


def _drop_event(ctx):
    del ctx["trace"]["trace"]["planes"]["/device:TPU:0"]["XLA Modules"][3]


def _extra_event(ctx):
    mods = ctx["trace"]["trace"]["planes"]["/device:TPU:0"]["XLA Modules"]
    mods.append(["jit_run(1)", mods[0][1] - 9 * MS, 8 * MS, ""])


def _older_program(ctx):
    for rec in ctx["spans"]:            # what schema v18 wrote
        del rec["dispatches"]


def _no_lead_fits(ctx):
    # one program stamped after its dispatch's read had returned, with
    # the others where they were: no one lead puts all of them inside
    mods = ctx["trace"]["trace"]["planes"]["/device:TPU:0"]["XLA Modules"]
    mods[4][1] += 2 * MS


def _mispaired(ctx):
    # the counts agree and the entries name other programs than ran
    ctx["spans"][2]["dispatches"] = [["decode", 8], ["prefill", 4]]
    ctx["spans"][4]["dispatches"] = [["prefill", 4]]


def _entries_beside_phases(ctx):
    ctx["spans"][0]["dispatches"].append(["decode", 8])


def _no_trace(ctx):
    ctx["trace"] = None


def _no_device_plane(ctx):
    del ctx["trace"]["trace"]["planes"]["/device:TPU:0"]


@pytest.mark.parametrize("fault", [
    _drop_event, _extra_event, _older_program, _no_lead_fits, _mispaired,
    _entries_beside_phases, _no_trace, _no_device_plane])
def test_a_trace_the_join_cannot_vouch_for_reads_nothing(fault, capsys):
    ctx = _ctx(0.9)
    fault(ctx)
    assert _read(ctx) == dict.fromkeys(METRICS)
    notes = capsys.readouterr().err.count("[dispatch_join]")
    # the parent commit and an untraced run are silent; a fault in a
    # trace that should have joined is said once, not once a reader
    assert notes == (0 if fault in (_older_program, _no_trace,
                                    _no_device_plane) else 1)


def test_without_the_trace_clock_only_the_clock_free_readers_read():
    """Records that no one shift places inside their ``bench:`` events
    cannot be put on the trace's clock: the device times and the
    overhead need none and are read; the lead and the launch side are
    not."""
    ctx = _ctx(0.9)
    ctx["spans"][3]["start_ns"] -= 5 * MS
    got = _read(ctx)
    assert got["mixed_program_device_ms"] == pytest.approx(9.6)
    assert got["dispatch_overhead_ms.offline"] is not None
    assert got["device_plane_lead_ms"] is None
    assert got["launch_latency_ms.offline"] is None


def test_the_benchmark_lists_the_six_in_the_serving_cells():
    for cell in ("gpt2-large.batch-offline", "jamba2-3b.reasoning-offline",
                 "glm47-flash.reasoning-offline",
                 "lfm2-24b-a2b.reasoning-offline"):
        listed = [m["name"] for m in harness.load_cell(cell)["per_layer"]]
        # by name, in the order they were appended (later PRs append)
        assert [n for n in listed if n in METRICS] == METRICS, cell
    train = harness.load_cell("ffn-d8192.train-single")["per_layer"]
    assert not {m["name"] for m in train} & set(METRICS)
