"""Step phases (ISSUE 25): ``runtime/tracing.py::PhaseTimer`` at its two
sites — inside ``DecodeEngine.step()`` (stamps, the flight digest's
``phase_ms``, one ``engine_step`` span record a step when a writer is
attached) and round the trainer's calls (profiler annotations only).

What is proved: the phases TILE the step (every scheduler method runs
inside the phase named for it, children are ordered, nested, disjoint,
and what lies between them is small against the step), the record's
counts are the engine's own counters, tokens do not depend on a writer,
every dispatch says which program it ran (``dispatches``, schema v19:
one entry a ``*.dispatch`` phase, what ``_program`` was asked for, over
the four served families and the step kinds),
schema v19 takes the record and refuses a null uid anywhere else,
``report`` reads it without disturbing the per-request waterfall, and
the annotations reach a real ``jax.profiler`` trace by name.
"""

import glob
import json
import os
import statistics
import time

import jax
import numpy as np
import pytest

from distributed_llm_code_samples_tpu.decode import (DecodeEngine,
                                                     EngineConfig)
from distributed_llm_code_samples_tpu.models import init_lm
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, SCHEMA_VERSION, SPAN_NAMES, STEP_SPAN,
    TelemetryWriter, read_metrics, validate_record)
from distributed_llm_code_samples_tpu.runtime.tracing import PhaseTimer

from test_mixed_program import BASE as FAMILY_BASE, FAMILIES, LENS, backlog
from test_lfm2_moe_lm import TOY as TOY_CONV_MOE

V, D, L, H = 64, 32, 2, 4
BASE = dict(block_size=8, n_blocks=33, max_slots=3, max_blocks_per_seq=6,
            prefill_chunk=8)

HOST = {"expire", "admit", "prefill.cow", "prefill.book",
        "decode.marshal", "decode.cow", "decode.emit", "digest"}
LAUNCH = {"prefill.upload", "prefill.dispatch", "decode.upload",
          "decode.dispatch", "mixed.upload", "mixed.dispatch"}
WAIT = {"prefill.readback", "decode.readback", "mixed.readback"}
# one dispatch's phases, in the order the engine runs them: the launch
# side where the program is launched, the landing side wherever its
# result is read (after the NEXT launch, a step later where the read
# waits: ``tests/test_late_read.py``)
PREFILL = ["prefill.cow", "prefill.upload", "prefill.dispatch"]
DECODE = ["decode.cow", "decode.marshal", "decode.upload",
          "decode.dispatch"]
# a full chunk riding with the batch: both dispatches' host halves
# under their own names round ONE launch and ONE wait
MIXED = ["prefill.cow", "decode.cow", "decode.marshal", "mixed.upload",
         "mixed.dispatch"]
LANDING = {"prefill": ["prefill.readback", "prefill.book"],
           "decode": ["decode.readback", "decode.emit"],
           "mixed": ["mixed.readback", "prefill.book", "decode.emit"]}
LANDS = {n for names in LANDING.values() for n in names}


class Collector:
    """A writer that keeps span records in memory (what the benchmark
    attaches in its traced runs)."""

    def __init__(self):
        self.spans = []
        self.path = None

    def span(self, rec):
        self.spans.append(rec)

    def __getattr__(self, _name):
        return lambda *a, **k: None

    def steps(self):
        return [r for r in self.spans if r["span"] == STEP_SPAN]


@pytest.fixture(scope="module")
def lm_params():
    return init_lm(jax.random.PRNGKey(0), V, D, L, max_seq_len=64)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(1)
    return [rng.integers(0, V, size=n).tolist() for n in (5, 9, 13)]


def _slow_dispatch(eng, seconds=0.02):
    """Make every compiled program take ``seconds`` longer to return,
    as a device would: a CPU step at this size is a millisecond of
    Python, against which no share means anything."""
    real = eng._program

    def program(kind, bucket):
        fn = real(kind, bucket)

        def slowed(*args):
            time.sleep(seconds)
            return fn(*args)
        slowed.lower = fn.lower
        return slowed
    eng._program = program


def _dispatches(rec):
    """How many prefill, decode (or verify) and mixed programs
    ``rec``'s step dispatched, by its phases."""
    names = [p[0] for p in rec["phases"]]
    return tuple(names.count(kind + ".dispatch")
                 for kind in ("prefill", "decode", "mixed"))


def _expected(rec):
    """The LAUNCH-side phase names a step with ``rec``'s dispatches has
    to show, in order (the first ``decode.marshal`` is where the step
    looks whether its chunk can ride with the batch)."""
    n_prefill, n_decode, n_mixed = _dispatches(rec)
    return (["expire", "admit", "decode.marshal"] + MIXED * n_mixed
            + PREFILL * n_prefill + ["decode.marshal"]
            + DECODE * n_decode + ["digest"])


def _phase_of(recs):
    """``{launch ordinal: phase prefix}`` over consecutive records (a
    verify takes the ``decode.*`` names)."""
    out = {}
    for rec in recs:
        first = rec["launches"] - len(rec["dispatches"])
        for i, (kind, _) in enumerate(rec["dispatches"]):
            out[first + i] = "decode" if kind == "verify" else kind
    return out


def _run_scenario(name, lm_params, prompts):
    """The engine_step records of one scenario, each a list of steps of
    the kind the scenario is named for."""
    cfg = dict(BASE)
    if name == "verify":
        cfg["speculate"] = 2
    sink = Collector()
    eng = DecodeEngine(lm_params, H, EngineConfig(**cfg), metrics=sink)
    if name == "prefill_only":
        for p in prompts:
            eng.submit(p, 4)
        eng.run()                       # compile outside what is read
        sink.spans.clear()
        _slow_dispatch(eng)
        for p in prompts:
            eng.submit(p, 4)
        while eng.step(prefill_only=True):
            pass
        recs = sink.steps()
        assert recs and all(_dispatches(r) == (1, 0, 0) for r in recs)
        return recs, _phase_of(sink.steps())
    if name == "two_versions":
        other = init_lm(jax.random.PRNGKey(5), V, D, L, max_seq_len=64)
        eng.submit(prompts[0], 24)
        eng.run()
        sink.spans.clear()
        _slow_dispatch(eng)
        eng.submit(prompts[0], 24)
        for _ in range(4):
            eng.step()
        eng.load_weights(1, other)
        eng.set_serving_version(1)
        eng.submit(prompts[1], 12)
        eng.run()
        recs = [r for r in sink.steps() if _dispatches(r) == (0, 2, 0)]
        assert recs, "no step dispatched one decode per resident version"
        return recs, _phase_of(sink.steps())
    eng.generate(prompts, 8)            # compile outside what is read
    if name == "mixed":
        # prompts of several full chunks, admitted behind a ready slot
        # (and new to the prefix cache)
        rng = np.random.default_rng(2)
        prompts = prompts[:1] + [rng.integers(0, V, size=n).tolist()
                                 for n in (24, 17)]
    sink.spans.clear()
    _slow_dispatch(eng)
    eng.generate(prompts, 8)
    want = {"prefill_decode": (1, 1, 0), "mixed": (0, 0, 1)}.get(
        name, (0, 1, 0))
    recs = [r for r in sink.steps() if _dispatches(r) == want]
    assert len(recs) >= 3, (name, [_dispatches(r) for r in sink.steps()])
    if name == "verify":
        assert {k for k, _ in eng._programs} == {"prefill", "verify"}
    return recs, _phase_of(sink.steps())


@pytest.mark.parametrize("scenario", [
    "prefill_only", "decode_only", "prefill_decode", "two_versions",
    "verify", "mixed"])
def test_phases_tile_the_step(scenario, lm_params, prompts):
    """Children in order, inside the parent, none overlapping, named as
    the step's dispatches (the launch side) and its readbacks (the
    landing side: the launches it READ, its own or the step before's)
    say; and the time no child covers is within 2% (or 50 us) of the
    step in the scenario's median step."""
    recs, phase_of = _run_scenario(scenario, lm_params, prompts)
    uncovered = []
    for rec in recs:
        assert rec["uid"] is None and rec["step"] == rec["start_step"]
        phases = rec["phases"]
        names = [p[0] for p in phases]
        assert [n for n in names if n not in LANDS] == _expected(rec)
        assert [n for n in names if n in LANDS] == [
            n for o in rec["readbacks"] for n in LANDING[phase_of[o]]]
        # nothing is read before something was launched, or between
        # two steps: a read follows a launch of its own step
        if rec["readbacks"]:
            assert names.index(LANDING[phase_of[rec["readbacks"][0]]][0]) \
                > min(i for i, n in enumerate(names)
                      if n.endswith(".dispatch"))
        if scenario in ("prefill_only", "verify", "two_versions"):
            # what the host goes on with is read in the step itself
            first = rec["launches"] - len(rec["dispatches"])
            assert rec["readbacks"] == list(range(first, rec["launches"]))
        elif scenario in ("decode_only", "mixed"):
            # steady state: the step reads the step before's launch
            assert rec["readbacks"] in ([rec["launches"] - 2],
                                        [rec["launches"] - 2,
                                         rec["launches"] - 1])
        assert {p[0] for p in phases} <= HOST | LAUNCH | WAIT
        t = rec["start_ns"]
        for name, start, end in phases:
            assert t <= start <= end, (name, rec["step"])
            t = end
        assert t <= rec["end_ns"]
        step_ns = rec["end_ns"] - rec["start_ns"]
        assert rec["duration_s"] == pytest.approx(step_ns / 1e9, abs=1e-6)
        uncovered.append((step_ns - sum(e - s for _, s, e in phases),
                          step_ns))
    gap, step_ns = sorted(uncovered, key=lambda g: g[0] / g[1])[
        len(uncovered) // 2]
    assert gap <= max(0.02 * step_ns, 50_000), (gap, step_ns)


def test_scheduler_methods_run_inside_their_phase(lm_params, prompts):
    """Tiling by structure, not by the clock: every call the step makes
    into the scheduler's methods happens while the phase named for that
    work is open."""
    home = {"_expire_deadlines": {"expire"}, "_admit": {"admit"},
            "_cow_private": {"prefill.cow", "decode.cow"},
            "_marshal": {"decode.marshal"},
            "_version_groups": {"decode.marshal"},
            "_cache_full_blocks": {"prefill.book"},
            "_emit": {"prefill.book", "decode.emit"},
            "_flight_digest": {"digest"}}
    sink = Collector()
    eng = DecodeEngine(lm_params, H, EngineConfig(**BASE), metrics=sink)
    calls = []

    def spy(name):
        real = getattr(eng, name)

        def wrapped(*a, **k):
            calls.append((name, time.time_ns()))
            return real(*a, **k)
        setattr(eng, name, wrapped)
    for name in home:
        spy(name)
    eng.generate(prompts, 6)
    phases = sorted((s, e, n) for r in sink.steps()
                    for n, s, e in r["phases"])
    assert {n for n, _ in calls} == set(home)
    for name, t in calls:
        inside = [n for s, e, n in phases if s <= t <= e]
        assert inside and set(inside) <= home[name], (name, inside)


def test_tokens_do_not_depend_on_a_writer(lm_params, prompts):
    outs = []
    for sink in (None, Collector()):
        eng = DecodeEngine(lm_params, H, EngineConfig(**BASE),
                           metrics=sink)
        outs.append(eng.generate(prompts, 8))
    assert outs[0] == outs[1]


def test_no_writer_no_record_digest_carries_phase_ms(lm_params, prompts,
                                                     monkeypatch):
    eng = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    monkeypatch.setattr(eng, "_step_record", lambda *a: pytest.fail(
        "a record was built with no writer attached"))
    eng.generate(prompts, 4)
    assert len(eng.flight) == eng.steps
    for digest in eng.flight:
        ms = digest["phase_ms"]
        assert {"expire", "admit", "decode.marshal"} <= set(ms)
        assert "digest" not in ms       # summed while it is still open
        assert all(v >= 0 for v in ms.values())
    assert any("prefill.dispatch" in d["phase_ms"] for d in eng.flight)
    assert "decode.dispatch" in eng.flight[-1]["phase_ms"]
    # the engine drained: the last step read its own launch too
    assert eng.flight[-1]["readbacks"] == [eng.launches - 2,
                                           eng.launches - 1]
    json.dumps(list(eng.flight))            # the dump stays serialisable


def _launched_lengths(eng) -> list:
    """Spy on ``eng._launch``: the list it returns holds, for every
    decode-side program launched since it was last cleared, the rows'
    lengths as the program is handed them (a padded row's is 0: it
    attends over the one position it writes)."""
    launched, launch = [], eng._launch

    def spy(phase, bucket, fn, params, operand, land):
        if phase != "prefill":
            launched.append(eng.programs.wire(phase, bucket).unpack(
                operand)["lengths"])
        return launch(phase, bucket, fn, params, operand, land)

    eng._launch = spy
    return launched


@pytest.mark.parametrize("kv_dtype,speculate", [
    ("bf16", 0), ("f32", 2), ("int8", 0)])
def test_kv_blocks_read_is_what_the_decode_side_reads_fetch(
        lm_params, prompts, kv_dtype, speculate):
    """``kv_blocks_read`` of a step's record and digest (telemetry v22):
    over the rows of the decode-side programs the step LAUNCHED, a
    bucket's padded ones (one scratch block each) with them, the blocks
    that hold the positions each row attends over, times the pool's
    layers, where the read walks the rows' tables (a float pool; a
    verify program's rows read once a sub-step, each one position
    further); every table's capacity where it gathers (an int8 pool).
    ``kv_blocks_capacity`` beside it is the gather's in both; the
    ``decode`` record carries the two summed."""
    from distributed_llm_code_samples_tpu.decode.paged import walks
    sink = Collector()
    eng = DecodeEngine(lm_params, H, EngineConfig(
        **BASE, kv_dtype=kv_dtype, speculate=speculate), metrics=sink)
    assert walks(eng.pool) is (kv_dtype != "int8")
    for p in prompts:
        eng.submit(p, 6)
    blk, mb = BASE["block_size"], BASE["max_blocks_per_seq"]
    launched = _launched_lengths(eng)
    read = capacity = 0
    while eng.active or eng.waiting:
        del launched[:]
        eng.step()
        rec = sink.steps()[-1]
        held = sum(len(n) for n in launched) * (speculate + 1) * mb
        want = held if kv_dtype == "int8" else sum(
            int((-(-(n + 1 + sub) // blk)).sum())
            for n in launched for sub in range(speculate + 1))
        assert rec["kv_blocks_read"] == L * want
        assert rec["kv_blocks_capacity"] == L * held
        assert eng.flight[-1]["kv_blocks_read"] == L * want
        read, capacity = read + L * want, capacity + L * held
    assert 0 < read <= capacity and (read < capacity) is (kv_dtype != "int8")
    doc = eng.telemetry_record()
    assert (doc["kv_blocks_read"], doc["kv_blocks_capacity"]) == (
        read, capacity)


@pytest.mark.parametrize("walked", [True, False])
def test_latent_kv_blocks_read_is_the_rows_live_blocks(monkeypatch, walked):
    """A toy latent engine's ``kv_blocks_read`` (``engine._count_blocks``
    books by ``paged.walks`` of the pool: no counter of its own): since
    PR 50 the latent pool walks, so a step's digest counts the blocks
    that hold the positions each launched decode-side row attends over,
    a padded row's one scratch block with them, times the pool's layers
    — by hand from the lengths each program was handed — and the total
    stays below ``kv_blocks_capacity``, the tables' whole capacity,
    which is what the plain read fetched and still does where the pool
    does not take the walk (the chip's rule refuses the toy's ONE-tile
    row of 128 lanes: the two are equal there)."""
    import test_mla_moe_lm as toy
    from distributed_llm_code_samples_tpu.decode.paged import walks
    from distributed_llm_code_samples_tpu.ops import ssm
    driver = toy._load("glm_moe_engine_driver")
    params = driver._params(toy.TOY, driver.make_weights(toy.TOY, 11))
    if not walked:
        monkeypatch.setattr(ssm, "_interpreted", lambda: False)
    eng = toy.engine(params, slots=4, mbps=8)
    assert eng.pool.latent_rank and eng.pool.v.shape[-1] == 0
    assert walks(eng.pool) is walked and eng._walks is walked
    blk, mb = eng.cfg.block_size, eng.cfg.max_blocks_per_seq
    layers = eng.pool.k.shape[0]
    for p in toy.prompts_of([40, 5, 21], seed=3):
        eng.submit(p, 30)
    launched = _launched_lengths(eng)
    read = held = padded = 0
    while eng.active or eng.waiting:
        del launched[:]
        eng.step()
        # a row handed length ``n`` attends over ``n + 1`` positions
        want = sum(int((n // blk + 1).sum()) for n in launched) * layers
        cap = sum(len(n) for n in launched) * mb * layers
        padded += sum(int((n == 0).sum()) for n in launched)
        digest = eng.flight[-1]
        assert digest["kv_blocks_capacity"] == cap
        assert digest["kv_blocks_read"] == (want if walked else cap)
        read, held = read + want, held + cap
    assert padded and 0 < read < held
    doc = eng.telemetry_record()
    assert (doc["kv_blocks_read"], doc["kv_blocks_capacity"]) == (
        read if walked else held, held)


@pytest.mark.parametrize("walked", [True, False])
@pytest.mark.parametrize("family", ["laguna", "evabyte"])
def test_ring_blocks_read_is_what_the_window_layers_reads_fetch(
        monkeypatch, family, walked):
    """``ring_blocks_read`` of a step's digest and the engine's total
    (telemetry v24), counted by hand from the lengths each decode-side
    program was handed: a row that writes position ``t`` walks the
    blocks from the one that holds its first attendable position —
    ``t - window + 1`` under the sliding rule (Laguna's toy), the start
    of ``t``'s own window under the aligned one (EvaByte's) — to block
    ``t // block``, a bucket's padded row one scratch block, times the
    window layers; ``ring_blocks_capacity`` is every row's whole ring.
    Where the ring keeps the plain read (the chip's rule refuses the
    toys' one-tile rows) the two are equal."""
    import test_evabyte_lm
    import test_laguna_lm
    from distributed_llm_code_samples_tpu.ops import ssm
    toy = {"laguna": test_laguna_lm, "evabyte": test_evabyte_lm}[family]
    driver = toy._load(family + "_engine_driver")
    params = driver._params(toy.TOY, driver.make_weights(toy.TOY, 11))
    if not walked:
        monkeypatch.setattr(ssm, "_interpreted", lambda: False)
    eng = toy.engine(params, slots=4, mbps=8 if family == "laguna" else 2)
    blk, window = eng.cfg.block_size, eng.spec.window
    entries, layers = eng.programs.window_blocks, eng.wpool.k.shape[0]
    assert eng._ring_walks is walked and entries == window // blk + 2
    for p in toy.prompts_of([40, 5, 21], seed=3):
        eng.submit(p, 70)
    launched = _launched_lengths(eng)
    read = held = padded = 0
    while eng.active or eng.waiting:
        del launched[:]
        eng.step()
        want = cap = 0
        for t in launched:          # the position each row writes
            first = (t // window * window if family == "evabyte"
                     else np.maximum(t - window + 1, 0))
            want += int((t // blk - first // blk + 1).sum()) * layers
            cap += len(t) * entries * layers
            padded += int((t == 0).sum())
        digest = eng.flight[-1]
        assert digest["ring_blocks_capacity"] == cap
        assert digest["ring_blocks_read"] == (want if walked else cap)
        read, held = read + want, held + cap
    # rows past a window boundary, past the ring's first wrap, and a
    # padded row among them
    assert eng.lengths.max() == 0 and padded and 0 < read < held
    assert max(len(p) for p in eng.finished.values()) > max(
        window, entries * blk)
    doc = eng.telemetry_record()
    assert (doc["ring_blocks_read"], doc["ring_blocks_capacity"]) == (
        read if walked else held, held)


def test_the_sink_familys_record_counts_positions_and_states_bytes(
        tmp_path, capsys):
    """The ``engine_step`` record of the family whose two stores have
    rows of their own (``models/mimo_v2_flash_lm.py``'s toy), counted
    by hand from what each program was handed: ``full_rows`` the
    positions up to each row's own, ``window_rows`` at most the window
    of them, the rings' and the pool's blocks the walks fetch over 5
    window and 2 full layers, the experts' three over 6 expert layers of
    8 held experts — POSITIONS and blocks, not bytes — and, carried
    once a record beside them, each store's BYTES a position a layer
    from the arrays (``kv_row_bytes`` 2 heads x (24 + 16) lanes x 4,
    ``window_row_bytes`` twice that: the window layers have 4 KV
    heads). ``validate_record`` takes the pair together or not at all,
    and ``report`` prints the bytes on its cache-reads lines."""
    import test_mimo_v2_flash_lm as toy
    from distributed_llm_code_samples_tpu.report import report_main
    from distributed_llm_code_samples_tpu.runtime.telemetry import (
        STEP_SPAN_ROW_BYTES)
    driver = toy._load("mimo_v2_flash_engine_driver")
    params = driver._params(toy.TOY, driver.make_weights(toy.TOY, 11))
    mdir = str(tmp_path / "m")
    with TelemetryWriter(mdir) as w:
        eng = DecodeEngine(params, toy.HEADS, EngineConfig(
            max_slots=4, n_blocks=1 + 4 * 8, max_blocks_per_seq=8),
            metrics=w)
        blk, window = eng.cfg.block_size, eng.spec.window
        entries = eng.programs.window_blocks
        assert (window, entries, eng.wpool.k.shape[0],
                eng.pool.k.shape[0]) == (16, 3, 5, 2)
        for p in toy.prompts_of([40, 5, 21], seed=3):
            eng.submit(p, 50)
        launched, launch = [], eng._launch

        def spy(phase, bucket, fn, params, operand, land):
            launched.append((phase, eng.programs.wire(
                phase, bucket).unpack(operand)))
            return launch(phase, bucket, fn, params, operand, land)

        eng._launch = spy
        n = 0
        while eng.active or eng.waiting:
            del launched[:]
            eng.step()
            full = win = ring = ring_cap = kv = kv_cap = 0
            for phase, f in launched:
                if "wtable" in f:       # a chunk: ONE view, up to its end
                    c = len(f.get("chunk", f["tokens"]))
                    last = int(f["pos0"]) + c - 1
                    full, win = full + last + 1, win + min(last + 1, window)
                if phase == "prefill":
                    continue
                t = f["lengths"]        # the position each row writes
                live = (f["uids"] != 0) | (t != 0)
                full += int((t[live] + 1).sum())
                win += int(np.minimum(t[live] + 1, window).sum())
                first = np.maximum(t - window + 1, 0)
                ring += int((t // blk - first // blk + 1).sum()) * 5
                ring_cap += len(t) * entries * 5
                kv += int((t // blk + 1).sum()) * 2
                kv_cap += len(t) * 8 * 2
            d = eng.flight[-1]
            assert (d["full_rows"], d["window_rows"]) == (full, win)
            assert (d["ring_blocks_read"], d["ring_blocks_capacity"]) == (
                ring, ring_cap)
            assert (d["kv_blocks_read"], d["kv_blocks_capacity"]) == (
                kv, kv_cap)
            n += bool(launched)
        assert n > 50 and max(len(p) for p in eng.finished.values()) > 4 * (
            window)
    records, problems = read_metrics(os.path.join(mdir, METRICS_FILENAME))
    assert problems == []
    steps = [r for r in records if r.get("span") == STEP_SPAN]
    assert len(steps) == eng.steps
    for r in steps:
        assert (r["kv_row_bytes"], r["window_row_bytes"]) == (320, 640)
        assert r["window_rows"] <= r["full_rows"]
        assert 0 <= r["experts_touched"] <= 6 * 8 * len(r["readbacks"])
        assert r["expert_rows_max"] <= r["expert_rows"]
    # every row routes 4 of 16 experts in each of 6 layers, half of
    # which are held here: the held pairs are about half of all pairs
    rows = sum(b for r in steps for k, b in r["dispatches"]
               if k != "prefill") + 16 * sum(
        k in ("prefill", "mixed") for r in steps for k, _ in r["dispatches"])
    pairs = sum(r["expert_rows"] for r in steps)
    assert 0.3 < pairs / (rows * 4 * 6) < 0.7
    # the pair of byte counts: both or none, whole, not negative
    ok, reason = validate_record(steps[-1])
    assert ok, reason
    for over in ({"kv_row_bytes": None}, {"window_row_bytes": -1},
                 {"kv_row_bytes": 2.5}):
        rec = {k: v for k, v in dict(steps[-1], **over).items()
               if k not in over or v is not None}
        ok, reason = validate_record(rec)
        assert not ok and "bytes a position" in reason, (over, reason)
    bare = {k: v for k, v in steps[-1].items()
            if k not in STEP_SPAN_ROW_BYTES}
    assert validate_record(bare)[0]
    assert report_main([mdir]) == 0
    out = capsys.readouterr().out
    assert "a position is 320 bytes a layer" in out
    assert "a position is 640 bytes a layer" in out


def test_record_counts_are_the_engines_counters(lm_params, prompts):
    """One record an executed step, holding what is read and no more:
    the step number and ``tokens_generated`` after the step (what a
    reader joins on), and one dispatch phase per program dispatched."""
    sink = Collector()
    eng = DecodeEngine(lm_params, H, EngineConfig(**BASE), metrics=sink)
    for p in prompts:
        eng.submit(p, 6)
    n = 0
    while eng.active or eng.waiting:
        pre, dispatches = eng.prefill_dispatches, eng.dispatch_count
        mixed = eng.mixed_dispatches
        assert eng.step()
        n += 1
        rec = sink.steps()[-1]
        assert len(sink.steps()) == n
        assert set(rec) == {"uid", "span", "start_step", "step",
                            "start_ns", "end_ns", "t", "duration_s",
                            "phases", "tokens_generated",
                            "state_bytes", "expert_rows",
                            "experts_touched", "expert_rows_max",
                            "window_rows", "full_rows",
                            "window_blocks_released",
                            "window_blocks_live",
                            "summary_rows", "summaries_written",
                            "kv_blocks_read", "kv_blocks_capacity",
                            "ring_blocks_read", "ring_blocks_capacity",
                            "kv_row_bytes", "window_row_bytes",
                            "state_row_bytes", "tail_row_bytes",
                            "dispatches", "readbacks", "launches"}
        # a position's bytes in one layer of the pool, from its arrays;
        # no window pool here
        assert rec["kv_row_bytes"] == 2 * H * (D // H) * 4
        assert rec["window_row_bytes"] == 0
        # ... and no recurrent layer: no state row
        assert rec["state_row_bytes"] == rec["tail_row_bytes"] == 0
        assert rec["window_rows"] == rec["full_rows"] == 0  # nor window
        assert rec["summary_rows"] == rec["summaries_written"] == 0
        assert rec["ring_blocks_read"] == rec["ring_blocks_capacity"] == 0
        assert rec["launches"] == eng.launches
        assert rec["state_bytes"] == 0      # no recurrent layer here
        assert rec["expert_rows"] == rec["experts_touched"] == 0  # nor expert
        assert rec["step"] == eng.global_step == eng.flight[-1]["step"]
        assert rec["tokens_generated"] == eng.tokens_generated
        # a mixed dispatch is ONE dispatch that carried a chunk
        n_pre = eng.prefill_dispatches - pre
        n_mixed = eng.mixed_dispatches - mixed
        assert _dispatches(rec) == (
            n_pre - n_mixed, eng.dispatch_count - dispatches - n_pre,
            n_mixed)
        assert len(rec["dispatches"]) == sum(_dispatches(rec))
    assert eng.mixed_dispatches == 2        # the 9- and 13-token prompts
    assert not eng.step() and len(sink.steps()) == n    # idle: no record


# -- every dispatch says which program it ran (schema v19) ---------------

STEP_PROGRAMS = ("decode", "prefill", "mixed", "verify")
# the step kinds, by the programs a step of that kind launches in order
STEP_KINDS = {"decode_only": ("decode",), "riding_chunk": ("mixed",),
              "tail_chunk_and_batch": ("prefill", "decode"),
              "verify": ("verify",),
              "tail_chunk_and_verify": ("prefill", "verify")}


@pytest.fixture(scope="module")
def served_steps(toy_hybrid_config, toy_latent_config):
    """``(family, speculate) -> [one observation an executed step]`` of
    a toy engine of the family serving a backlog whose prompts have
    full chunks and tails: the step's record, its flight digest, what
    ``_program`` was asked for during it and how the engine's counters
    rose over it. Served once a pair and kept."""
    from distributed_llm_code_samples_tpu.decode.model_config import (
        engine_from_config)
    configs = {"hybrid": toy_hybrid_config, "latent": toy_latent_config,
               "conv_moe": TOY_CONV_MOE}
    kept = {}

    def serve(family, speculate):
        if (family, speculate) in kept:
            return kept[family, speculate]
        cfg = EngineConfig(**FAMILY_BASE, speculate=speculate)
        sink = Collector()
        if family == "gpt2":
            eng = DecodeEngine(
                init_lm(jax.random.PRNGKey(0), 96, 32, 2, 128, n_heads=4),
                4, cfg, metrics=sink)
        else:
            eng = engine_from_config(dict(configs[family]), seed=1,
                                     engine_config=cfg)
            eng.metrics = sink
        asked, real = [], eng._program

        def program(kind, bucket):
            asked.append([kind, bucket])
            return real(kind, bucket)
        eng._program = program
        for p in backlog(LENS):
            eng.submit(p, 12)
        seen = []
        while eng.active or eng.waiting:
            before = (eng.prefill_dispatches, eng.mixed_dispatches,
                      eng.dispatch_count)
            del asked[:]
            assert eng.step()
            seen.append({
                "rec": sink.steps()[-1], "digest": eng.flight[-1],
                "asked": list(asked),
                "rose": (eng.prefill_dispatches - before[0],
                         eng.mixed_dispatches - before[1],
                         eng.dispatch_count - before[2])})
        kept[family, speculate] = seen
        return seen

    return serve


@pytest.mark.parametrize("family,step_kind", [
    (f, k) for f in FAMILIES
    for k in ("decode_only", "riding_chunk", "tail_chunk_and_batch")
] + [  # the models that carry a state by slot refuse speculation
    (f, k) for f in ("gpt2", "latent")
    for k in ("verify", "tail_chunk_and_verify")])
def test_dispatches_name_the_programs_the_step_launched(
        served_steps, family, step_kind):
    """Every step of the kind: ``dispatches`` has one entry a
    ``*.dispatch`` phase, in order; kinds and buckets are what
    ``_program`` was asked for; the entries that carried a chunk are the
    rise of ``prefill_dispatches``, the ``mixed`` ones that of
    ``mixed_dispatches``; and the flight digest says the same."""
    speculate = 2 if "verify" in step_kind else 0
    steps = [o for o in served_steps(family, speculate)
             if tuple(k for k, _ in o["rec"]["dispatches"])
             == STEP_KINDS[step_kind]]
    assert steps, (family, step_kind)
    for o in steps:
        rec, said = o["rec"], o["rec"]["dispatches"]
        launched = [p[0] for p in rec["phases"]
                    if p[0].endswith(".dispatch")]
        # the speculative verify path takes the ``decode.*`` phase names
        assert launched == [
            ("decode" if kind == "verify" else kind) + ".dispatch"
            for kind, _ in said]
        assert said == [a for a in o["asked"] if a[0] in STEP_PROGRAMS]
        n_chunk, n_mixed, n_all = o["rose"]
        assert sum(k in ("prefill", "mixed") for k, _ in said) == n_chunk
        assert sum(k == "mixed" for k, _ in said) == n_mixed
        assert len(said) == n_all       # no pool op ran in these steps
        assert o["digest"]["dispatches"] == said
        assert o["digest"]["step"] == rec["step"]
        ok, reason = validate_record(dict(
            rec, schema=SCHEMA_VERSION, kind="span", trace_id=None,
            tenant=None))
        assert ok, reason
    # a batch's bucket is a slot bucket, a chunk's a chunk bucket; the
    # one mixed program is built for the largest slot bucket
    for kind, bucket in (d for o in steps for d in o["rec"]["dispatches"]):
        assert bucket in ((1, 2, 4, 8, 16) if kind == "prefill"
                          else (4,) if kind == "mixed" else (1, 2, 4))


def test_a_cow_program_adds_no_entry(lm_params):
    """A step with the write barrier armed: its ``cow`` program is a
    pool op, counted by ``dispatch_count`` and no step program, so the
    step's ``dispatches`` and ``*.dispatch`` phases do not know it."""
    rng = np.random.default_rng(7)
    stem = rng.integers(0, V, size=19).tolist()     # two shared blocks
    sink = Collector()
    eng = DecodeEngine(lm_params, H, EngineConfig(**BASE), metrics=sink)
    for uid in range(2):
        eng.submit(stem + [uid], 12, uid=uid)
        for _ in range(3):
            eng.step()
    slot = next(i for i, s in enumerate(eng.slots)
                if s is not None and s.uid == 1)
    assert eng.slots[slot].nodes[0].refs == 2
    real = eng._cow_batch

    def aimed_at_a_shared_block(ready):
        # no scheduler write ever aims at one: the trigger is by hand
        eng._cow_private(slot, 0, 0)
        real(ready)
    eng._cow_batch = aimed_at_a_shared_block
    count = eng.dispatch_count
    assert eng.step()
    rec = sink.steps()[-1]
    assert eng.cow_copies == 1 and ("cow", 0) in eng._programs
    assert rec["dispatches"] == [["decode", 2]]
    assert _dispatches(rec) == (0, 1, 0)
    assert eng.dispatch_count - count == 2          # the copy and the batch
    assert eng.flight[-1]["dispatches"] == rec["dispatches"]


def _step_record(**over):
    rec = {"schema": SCHEMA_VERSION, "kind": "span", "t": 2.0,
           "uid": None, "trace_id": None, "tenant": None,
           "span": STEP_SPAN, "start_step": 3, "step": 3,
           "duration_s": 1.0, "start_ns": 1_000_000_000,
           "end_ns": 2_000_000_000,
           "phases": [["admit", 1_000_000_100, 1_000_000_900]],
           "dispatches": [], "readbacks": [], "launches": 0}
    rec.update(over)
    return rec


def test_validate_record_takes_v20_engine_step():
    assert SCHEMA_VERSION >= 20 and STEP_SPAN in SPAN_NAMES
    ok, reason = validate_record(_step_record())
    assert ok, reason
    for key in ("phases", "start_ns", "end_ns", "dispatches", "readbacks",
                "launches"):
        rec = _step_record()
        del rec[key]
        ok, reason = validate_record(rec)
        assert not ok and key in reason and STEP_SPAN in reason


@pytest.mark.parametrize("span", [s for s in SPAN_NAMES if s != STEP_SPAN])
def test_validate_record_refuses_null_uid_elsewhere(span):
    ok, reason = validate_record(_step_record(span=span))
    assert not ok and "uid" in reason and span in reason
    ok, reason = validate_record(_step_record(span=span, uid=7))
    assert ok, reason


def test_writer_round_trips_engine_step_records(lm_params, prompts,
                                                tmp_path):
    mdir = str(tmp_path / "m")
    with TelemetryWriter(mdir) as w:
        eng = DecodeEngine(lm_params, H, EngineConfig(**BASE), metrics=w)
        eng.generate(prompts, 4)
    records, problems = read_metrics(os.path.join(mdir, METRICS_FILENAME))
    assert problems == []
    steps = [r for r in records if r.get("span") == STEP_SPAN]
    assert len(steps) == eng.steps
    assert [r["step"] for r in steps] == list(range(1, eng.steps + 1))
    assert all(r["uid"] is None and r["trace_id"] is None for r in steps)


def test_report_reads_step_phases_and_keeps_the_waterfall(
        lm_params, prompts, tmp_path, capsys):
    """``report`` over a stream with engine_step records: the
    per-request waterfall is what the same stream gives without them,
    and the step-phases table is there."""
    from distributed_llm_code_samples_tpu.report import report_main
    with_dir, without_dir = str(tmp_path / "a"), str(tmp_path / "b")
    with TelemetryWriter(with_dir, meta={"engine_id": "solo"}) as w:
        eng = DecodeEngine(lm_params, H, EngineConfig(**BASE), metrics=w)
        eng.generate(prompts, 6, log_every=2)
    os.makedirs(without_dir)
    with open(os.path.join(with_dir, METRICS_FILENAME)) as f, \
            open(os.path.join(without_dir, METRICS_FILENAME), "w") as g:
        lines = f.readlines()
        kept = [ln for ln in lines
                if json.loads(ln).get("span") != STEP_SPAN]
        assert len(lines) - len(kept) == eng.steps
        g.writelines(kept)
        read_in_a_step = sum(len(json.loads(ln)["readbacks"])
                             for ln in lines if ln not in kept)
    docs = []
    for mdir in (with_dir, without_dir):
        capsys.readouterr()
        assert report_main([mdir, "--json"]) == 0
        docs.append(json.loads(capsys.readouterr().out))
    assert docs[0]["problems"] == []
    assert docs[0]["waterfalls"] == docs[1]["waterfalls"]
    assert all(w["reconciled"] for w in docs[0]["waterfalls"].values())
    assert docs[0]["serving_reliability"] == docs[1]["serving_reliability"]
    assert "step_phases" not in docs[1]
    table = docs[0]["step_phases"]
    assert table["steps"] == eng.steps
    assert set(table["phases"]) <= HOST | LAUNCH | WAIT | {
        "(between phases)"}
    assert table["phases"]["admit"]["steps"] == eng.steps
    assert sum(p["share"] for p in table["phases"].values()) == \
        pytest.approx(1.0, abs=1e-3)
    # the step programs beside the waterfall: every dispatch whose
    # read lies in a step's record, once (``log_every``'s decode
    # record reads what is in flight BETWEEN two steps)
    assert {d["kind"] for d in table["dispatches"]} == {
        "prefill", "decode", "mixed"}
    assert sum(d["count"] for d in table["dispatches"]) == read_in_a_step
    assert eng.dispatch_count - eng.steps // 2 <= read_in_a_step \
        <= eng.dispatch_count
    # --trace UID stitches one request's spans and never meets a null uid
    assert report_main([with_dir, "--trace", "0"]) == 0
    capsys.readouterr()
    assert report_main([with_dir]) == 0
    text = capsys.readouterr().out
    assert "step phases:" in text and "decode.readback" in text
    assert "program (bucket)" in text and "mixed (3)" in text
    # the cache-reads line (v22): the blocks the decode-side reads
    # fetched a step beside their rows' tables, as the engine counted
    reads = table["cache_reads"]["blocks"]
    assert reads["kv_blocks_read_mean"] * reads["steps"] == pytest.approx(
        eng.kv_blocks_read, abs=0.01 * reads["steps"])
    assert 0 < reads["kv_blocks_read_mean"] < reads[
        "kv_blocks_capacity_mean"]
    assert "blocks a step fetched by the decode-side reads" in text
    assert "per-request waterfalls" in text


def test_report_prints_dispatches_by_kind_and_bucket(tmp_path, capsys):
    """The operator's view of ``dispatches``: per step program, by kind
    and bucket, its runs and the time from its launch to the end of the
    read that ``readbacks`` pairs it with, in its own step's record or
    in the next one's; the tail's two programs of one step told
    apart."""
    from distributed_llm_code_samples_tpu.report import report_main
    ms = 1_000_000
    launched, unread = [0], []      # the engine's count; [ordinal, kind, t]

    def step(n, programs, read_own=True):
        """A step at ``n * 100`` ms that launches ``programs`` (``kind,
        bucket, ms from launch to the end of its read``), each launch
        followed by the read of whatever was unread; its last launch is
        read in the step unless ``read_own`` is false."""
        t, phases, reads = n * 100 * ms, [], []

        def read(upto):
            ordinal, kind, t_launch, took = unread.pop(0)
            end = max(upto, t_launch + took * ms)
            phases.append([kind + ".readback", upto, end])
            reads.append(ordinal)
            return end

        for kind, _, took in programs:
            phases.append([kind + ".dispatch", t, t + ms])
            unread.append([launched[0], kind, t, took])
            launched[0] += 1
            t += ms
            if len(unread) > 1:
                t = read(t)
        if read_own:
            t = read(t)
        return _step_record(
            start_step=n, step=n, start_ns=n * 100 * ms, end_ns=t,
            duration_s=(t - n * 100 * ms) / 1e9, tokens_generated=n,
            phases=phases, dispatches=[[k, b] for k, b, _ in programs],
            readbacks=reads, launches=launched[0])
    mdir = str(tmp_path / "m")
    with TelemetryWriter(mdir) as w:
        w.span(step(1, [("mixed", 12, 10)], read_own=False))
        w.span(step(2, [("prefill", 4, 4), ("decode", 8, 9)],
                    read_own=False))
        w.span(step(3, [("mixed", 12, 12)], read_own=False))
        w.span(step(4, [("decode", 8, 9)]))
    capsys.readouterr()
    assert report_main([mdir, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["problems"] == []
    rows = {(d["kind"], d["bucket"]): d
            for d in doc["step_phases"]["dispatches"]}
    # a read that waited a step ends one step (100 ms) and the next
    # launch (1 ms) after its own launch: both mixed programs, the
    # first decode; the second decode and the tail's chunk were read
    # when their results were there (9 and 4 ms)
    assert {k: (d["count"], d["mean_ms"]) for k, d in rows.items()} == {
        ("mixed", 12): (2, 101.0), ("decode", 8): (2, (100 + 9) / 2),
        ("prefill", 4): (1, 4.0)}
    assert report_main([mdir]) == 0
    text = capsys.readouterr().out
    for line in ("mixed (12)", "decode (8)", "prefill (4)"):
        assert line in text


def test_phase_timer_keeps_nothing_until_begun():
    timer = PhaseTimer("train")
    with timer.phase("run"):
        pass
    assert timer.stamps is None and timer.phase_ms() == {}
    timer.begin(4)
    with timer.phase("outer"):
        with timer.phase("a"):
            pass
        with timer.phase("a"):
            pass
    assert [s[0] for s in timer.stamps] == ["a", "a", "outer"]
    (_, a0, a1), (_, b0, b1), (_, o0, o1) = timer.stamps
    assert o0 <= a0 <= a1 <= b0 <= b1 <= o1
    assert timer.phase_ms()["a"] == pytest.approx(
        (a1 - a0 + b1 - b0) / 1e6, abs=1e-3)
    timer.begin(5)
    assert timer.stamps == [] and timer.step == 5


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    [path] = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                    "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("engine:", "train:", "launch:")):
                    out.append((ev.name, dict(ev.stats), ev.start_ns,
                                ev.duration_ns))
    return out


def test_profiler_trace_holds_the_program_spans(lm_params, prompts,
                                                tmp_path, mesh8):
    """A CPU ``jax.profiler`` trace of three engine steps, one
    ``train_single`` call and one sharded launch holds the program's
    annotations by name, the engine's with their step number."""
    from distributed_llm_code_samples_tpu.data import make_seed_schedule
    from distributed_llm_code_samples_tpu.models import init_ffn_stack
    from distributed_llm_code_samples_tpu.parallel import (train_ddp,
                                                           train_single)
    sink = Collector()
    eng = DecodeEngine(lm_params, H, EngineConfig(**BASE), metrics=sink)
    eng.generate(prompts, 4)
    ffn = init_ffn_stack(jax.random.PRNGKey(2), 16, 2)
    seeds = make_seed_schedule(8, random_seed=3)
    train_single(ffn, seeds[:2], 4, 16)
    sink.spans.clear()
    for p in prompts:
        eng.submit(p, 4)
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        for _ in range(3):
            assert eng.step()
        jax.block_until_ready(train_single(ffn, seeds[:2], 4, 16))
        jax.block_until_ready(train_ddp(ffn, seeds, 4, 16, mesh8))
    finally:
        jax.profiler.stop_trace()
    events = _host_events(trace_dir)
    names = [e[0] for e in events]
    steps = [e for e in events if e[0] == "engine:step"]
    recs = sink.steps()
    assert [e[1].get("step") for e in steps] == [r["step"] for r in recs]
    assert len(steps) == 3
    for want in ("engine:admit", "engine:prefill.dispatch",
                 "engine:decode.dispatch", "engine:decode.readback",
                 "engine:digest", "train:clone", "train:run",
                 "launch:build", "launch:run"):
        assert want in names, want
    # the stamps and the profiler's events are one clock, shifted by the
    # trace's start: the shift is the same for every step
    shifts = [r["start_ns"] - e[2] for r, e in zip(recs, steps)]
    assert max(shifts) - min(shifts) < 200_000, shifts
    assert statistics.median(
        abs((r["end_ns"] - r["start_ns"]) - e[3])
        for r, e in zip(recs, steps)) < 200_000
