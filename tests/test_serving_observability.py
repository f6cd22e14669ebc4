"""Serving observability (ISSUE 7): request span tracing, decode cost
attribution, KV-pool telemetry, the fault flight recorder, and the
multi-stream report merge.

The proofs ride the repo's differential stance: span durations must
RECONCILE with the independently-recorded request latencies (two
instruments, one truth), the static KV accounting must equal the
device arrays byte-for-byte, and the named-scope contract is asserted
against the REAL compiled serving programs captured through the PR 2
launcher hook — never a reconstruction.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_code_samples_tpu.decode import (DecodeEngine,
                                                     EngineConfig,
                                                     FLIGHT_FILENAME,
                                                     ServePolicy)
from distributed_llm_code_samples_tpu.decode.engine import (
    FLIGHT_RECORDER_STEPS, POISON_ALL)
from distributed_llm_code_samples_tpu.models import init_lm
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, TelemetryWriter, read_metrics, validate_record)

V, D, L, H = 64, 32, 2, 4
BASE = dict(block_size=8, n_blocks=33, max_slots=3, max_blocks_per_seq=6,
            prefill_chunk=8)


@pytest.fixture(scope="module")
def lm_params():
    return init_lm(jax.random.PRNGKey(0), V, D, L, max_seq_len=64)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(1)
    return [rng.integers(0, V, size=n).tolist() for n in (5, 9, 13)]


def _span_sums(records):
    sums: dict = {}
    for s in records:
        if s["kind"] == "span":
            sums[s["uid"]] = sums.get(s["uid"], 0.0) + s["duration_s"]
    return sums


def _latencies(records):
    return {r["uid"]: r["latency_s"] for r in records
            if r["kind"] == "request" and r["event"] == "completed"}


# ---------------------------------------------------------------------------
# span tracing: the telescoping reconciliation contract


def test_span_stream_reconciles_with_latency(lm_params, prompts,
                                             tmp_path):
    """Every completed request's span durations sum to its recorded
    latency_s (the tracer's telescoping-clock contract) — and the
    instrumentation adds ZERO compiled programs (scopes and spans are
    metadata + host work; the serving surface is unchanged)."""
    mdir = str(tmp_path / "m")
    with TelemetryWriter(mdir, meta={"engine_id": "e0"}) as w:
        eng = DecodeEngine(lm_params, H, EngineConfig(**BASE), metrics=w)
        eng.generate(prompts, 8, log_every=2)
        warm = eng.compile_count
        # second wave reuses seen buckets (lens 4 and 5 -> chunks 4/1)
        eng.generate([[1, 2, 3, 4], [1, 2, 3, 4, 5]], 4, log_every=2)
        assert eng.compile_count == warm    # tracing never compiles
    records, problems = read_metrics(os.path.join(mdir,
                                                  METRICS_FILENAME))
    assert problems == []
    spans = [r for r in records if r["kind"] == "span"]
    assert spans and all(validate_record(s)[0] for s in spans)
    # v18: engine_step spans belong to a step, not a request
    steps = [s for s in spans if s["span"] == "engine_step"]
    assert len(steps) == eng.steps
    spans = [s for s in spans if s["span"] != "engine_step"]
    records = [r for r in records if r not in steps]
    lat = _latencies(records)
    sums = _span_sums(records)
    assert set(lat) <= set(sums)
    for uid, latency in lat.items():
        assert abs(sums[uid] - latency) <= 0.01, (uid, sums[uid],
                                                  latency)
    # phase structure: every uid queued first, decoded last
    by_uid: dict = {}
    for s in spans:
        by_uid.setdefault(s["uid"], []).append(s)
    for uid, ss in by_uid.items():
        ss.sort(key=lambda s: (s["start_t"], s["t"]))
        assert ss[0]["span"] == "queued"
        assert ss[-1]["span"] == "decode"
        assert any(s["span"] == "prefill" for s in ss)


def test_quarantine_retry_spans_and_flight_recorder(lm_params, prompts,
                                                    tmp_path):
    """A poisoned step produces the quarantine span arc (decode ->
    quarantine -> prefill -> replay -> decode), the retried request
    still reconciles, and the flight recorder dumps atomically with
    digests covering the steps UP TO the quarantine — non-finite
    evidence included."""
    mdir = str(tmp_path / "m")
    with TelemetryWriter(mdir, meta={"engine_id": "e0"}) as w:
        eng = DecodeEngine(lm_params, H, EngineConfig(**BASE),
                           metrics=w,
                           policy=ServePolicy(max_retries=1))
        for i, p in enumerate(prompts[:2]):
            eng.submit(p, 5, uid=i)
        for _ in range(3):
            eng.step()
        eng.arm_poison(POISON_ALL)
        eng.run()
    assert sorted(eng.finished) == [0, 1] and not eng.failed
    records, problems = read_metrics(os.path.join(mdir,
                                                  METRICS_FILENAME))
    assert problems == []
    spans = [r for r in records if r["kind"] == "span"]
    names0 = [s["span"] for s in sorted(
        (s for s in spans if s["uid"] == 0),
        key=lambda s: (s["start_t"], s["t"]))]
    assert "quarantine" in names0 and "replay" in names0
    # the quarantine gap hands off to the re-admission's prefill
    qi = names0.index("quarantine")
    assert names0[qi + 1] == "prefill"
    lat = _latencies(records)
    sums = _span_sums(records)
    for uid, latency in lat.items():
        assert abs(sums[uid] - latency) <= 0.01
    # flight recorder: dumped at the quarantine, digests cover the
    # steps up to (and including) the fault step
    fr = json.load(open(os.path.join(mdir, FLIGHT_FILENAME)))
    assert fr["version"] == 1 and "quarantine" in fr["reason"]
    steps = [d["step"] for d in fr["digests"]]
    assert steps == sorted(steps) and steps[-1] == fr["step"]
    last = fr["digests"][-1]
    assert last["finite"] is not None and not all(last["finite"])
    assert any("quarantined" in e for e in last["events"])
    assert eng.flight.maxlen == FLIGHT_RECORDER_STEPS


def test_preempt_gap_and_deadline_spans(lm_params, tmp_path):
    """Pool-pressure preemption emits a preempt_gap span that hands
    off to the re-admission (the churn is visible as wall time, not
    lost); a deadline expiry closes the victim's open span with the
    reason."""
    mdir = str(tmp_path / "m")
    cfg = EngineConfig(block_size=8, n_blocks=5, max_slots=3,
                       max_blocks_per_seq=2, prefill_chunk=8)
    with TelemetryWriter(mdir, meta={"engine_id": "e0"}) as w:
        eng = DecodeEngine(lm_params, H, cfg, metrics=w,
                           policy=ServePolicy(preempt_after_steps=2))
        eng.submit([1] * 9, 8, uid=0)      # 2 blocks
        eng.submit([1] * 9, 8, uid=1)      # 2 blocks: pool now full
        eng.submit([1] * 9, 8, uid=2)      # starved -> preemption
        eng.run()
        assert eng.preempted >= 1
    records, _ = read_metrics(os.path.join(mdir, METRICS_FILENAME))
    spans = [r for r in records if r["kind"] == "span"]
    gaps = [s for s in spans if s["span"] == "preempt_gap"]
    assert gaps
    lat = _latencies(records)
    sums = _span_sums(records)
    for uid, latency in lat.items():
        assert abs(sums[uid] - latency) <= 0.01

    mdir2 = str(tmp_path / "m2")
    with TelemetryWriter(mdir2, meta={"engine_id": "e0"}) as w:
        eng = DecodeEngine(lm_params, H, EngineConfig(**BASE),
                           metrics=w,
                           policy=ServePolicy(deadline_steps=3))
        eng.submit([1, 2, 3], 16, uid=0)
        eng.run()
        assert eng.failed[0]["reason"] == "deadline"
    records, _ = read_metrics(os.path.join(mdir2, METRICS_FILENAME))
    # (the step that expired it first read what was in flight: work,
    # so an ``engine_step`` record follows the request's last span)
    spans = [r for r in records if r["kind"] == "span"
             and r["span"] != "engine_step"]
    assert spans and spans[-1]["reason"] == "deadline"


# ---------------------------------------------------------------------------
# decode cost attribution: named scopes on the REAL compiled programs
# + the StepReport static fold vs the roofline's KV accounting


def test_decode_scope_contract_real_programs(lm_params, prompts):
    """Every region in SCOPES['decode'] / SCOPES['prefill'] appears in
    the optimized HLO of the engine's REAL dispatched programs —
    captured through the PR 2 launcher hook, the same contract the
    training strategies pin."""
    import distributed_llm_code_samples_tpu.parallel.launcher as launcher
    from distributed_llm_code_samples_tpu.utils.trace_analysis import (
        SCOPES)
    eng = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    launcher.CAPTURE_COMPILED = cap = []
    try:
        eng.generate(prompts[:2], 4)
    finally:
        launcher.CAPTURE_COMPILED = None
    assert cap, "engine dispatched no captured programs"
    text = "\n".join(cap)
    for key in ("decode", "prefill"):
        missing = [r for r in SCOPES[key] if r not in text]
        assert not missing, (f"{key}: compiled serving HLO lacks "
                             f"named-scope region(s) {missing}")


@pytest.mark.parametrize("kv_dtype", ["f32", "bf16", "int8"])
def test_decode_static_report_matches_roofline_bytes(lm_params,
                                                     kv_dtype):
    """The static attribution's hand cross-check: the pool arrays'
    device bytes equal kv_bytes_per_token * n_blocks * block_size —
    the DECODE roofline's per-dtype prediction — exactly, and the
    StepReport folds without error (single-device: no collectives in
    the lowered program)."""
    eng = DecodeEngine(lm_params, H,
                       EngineConfig(**BASE, kv_dtype=kv_dtype))
    rep = eng.decode_static_report()
    assert rep["kv_dtype"] == kv_dtype
    assert rep["kv_pool_bytes"] == rep["kv_pool_bytes_predicted"]
    assert rep["slot_bucket"] == BASE["max_slots"]
    assert rep["step_report"]["collectives"] == {}
    per_elt = {"f32": 4, "bf16": 2, "int8": 1}[kv_dtype]
    assert rep["kv_bytes_per_token"] == 2 * L * H * (D // H) * per_elt
    if kv_dtype == "int8":
        assert rep["kv_scale_bytes"] > 0
    else:
        assert rep["kv_scale_bytes"] == 0


def test_decode_static_report_tp_collectives(lm_params, mesh_model4):
    """Under the Megatron decode layout the static report counts the
    hand-rolled schedule: one attention-out + one FFN all_reduce per
    layer, plus the vocab-parallel head's logits all_gather."""
    eng = DecodeEngine(lm_params, H, EngineConfig(**BASE),
                       mesh=mesh_model4)
    rep = eng.decode_static_report()
    c = rep["step_report"]["collectives"]
    assert c.get("all_reduce", 0) >= 2 * L, c
    assert c.get("all_gather", 0) >= 1, c
    assert rep["kv_pool_bytes"] == rep["kv_pool_bytes_predicted"]


# ---------------------------------------------------------------------------
# the acceptance drill: two engines, one merged report, waterfalls +
# postmortem — end to end through the CLI


def test_observability_drill_end_to_end(tmp_path, capsys):
    """ISSUE 7 acceptance: `generate --chaos nan_logits@3` (engine A,
    quarantine + retry) plus a clean engine B, folded by `report A B`:
    (a) a reconciled per-request waterfall for every completed uid,
    (b) a flight-recorder dump covering the steps up to the quarantine
    rendered by --postmortem, (c) one merged two-engine timeline with
    per-engine latency percentiles."""
    import distributed_llm_code_samples_tpu.cli as cli
    from distributed_llm_code_samples_tpu.report import report_main

    a_dir = str(tmp_path / "A")
    b_dir = str(tmp_path / "B")
    shape = ["-d", "32", "-l", "2", "--heads", "4", "--vocab", "64",
             "--max_seq_len", "64", "--block_size", "8",
             "--prefill_chunk", "8", "--max_new", "5",
             "--log_every", "2"]
    rc = cli.main(["generate", "--prompt_lens", "5,9"] + shape
                  + ["--chaos", "nan_logits@3", "--max_retries", "1",
                     "--snapshot_dir", str(tmp_path / "snapA"),
                     "--metrics_dir", a_dir, "--engine_id", "A"])
    assert rc == 0
    rc = cli.main(["generate", "--prompt_lens", "4,6"] + shape
                  + ["--metrics_dir", b_dir, "--engine_id", "B"])
    assert rc == 0
    capsys.readouterr()

    # (a) + (c): the merged JSON doc
    assert report_main([a_dir, b_dir, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert set(doc["engines"]) == {"A", "B"}
    for eng_id in ("A", "B"):
        rel = doc["engines"][eng_id]["serving_reliability"]
        assert rel["completed"] == 2
        assert "latency_p50_s" in rel and "latency_p99_s" in rel
        wf = doc["waterfalls"][eng_id]
        assert len(wf) == 2
        for uid, w in wf.items():
            assert w["reconciled"], (eng_id, uid, w)
            assert w["latency_s"] is not None
    a_rel = doc["engines"]["A"]["serving_reliability"]
    assert a_rel["quarantined"] == 2 and a_rel["retried"] == 2
    # one merged timeline, every entry engine-tagged, sorted by time
    engines_seen = {r["engine"] for r in doc["timeline"]}
    assert engines_seen == {"A", "B"}
    ts = [r["t"] for r in doc["timeline"]]
    assert ts == sorted(ts)

    # (b): the postmortem render (text mode)
    assert report_main([a_dir, b_dir, "--postmortem"]) == 0
    text = capsys.readouterr().out
    assert "per-request waterfalls [A]" in text
    assert "(reconciled)" in text
    assert "postmortem [A]" in text and "quarantine" in text
    assert "FINITE" in text              # the non-finite evidence row
    assert "postmortem [B]: no flight-recorder dump" in text
    # the quarantined-and-retried arc is on the merged timeline
    assert "QUARANTINED" in text and "RETRIED" in text


def test_report_single_stream_waterfall_render(lm_params, prompts,
                                               tmp_path, capsys):
    """Single-dir report keeps its PR 2-era layout and adds the
    waterfall section when span records exist."""
    from distributed_llm_code_samples_tpu.report import report_main
    mdir = str(tmp_path / "m")
    with TelemetryWriter(mdir, meta={"engine_id": "solo"}) as w:
        DecodeEngine(lm_params, H, EngineConfig(**BASE),
                     metrics=w).generate(prompts, 6, log_every=2)
    capsys.readouterr()
    assert report_main([mdir, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    # single-stream: sections stay top-level (no engines envelope)
    assert "engines" not in doc and "serving" in doc
    assert doc["waterfalls"] and all(
        w["reconciled"] for w in doc["waterfalls"].values())
    assert report_main([mdir]) == 0
    text = capsys.readouterr().out
    assert "per-request waterfalls" in text and "queued" in text


def test_report_dedups_replayed_spans(tmp_path, capsys):
    """An in-process restart re-emits span records for replayed steps
    byte-identical in (uid, span, start_step, step) — the report keeps
    one copy, so waterfall sums don't double-count (the request-record
    dedup stance applied to spans)."""
    from distributed_llm_code_samples_tpu.report import report_main
    mdir = str(tmp_path / "m")
    span = {"uid": 0, "span": "decode", "start_step": 2, "step": 5,
            "start_t": 10.0, "t": 11.0, "duration_s": 1.0}
    queued = {"uid": 0, "span": "queued", "start_step": 0, "step": 2,
              "start_t": 9.0, "t": 10.0, "duration_s": 1.0}
    with TelemetryWriter(mdir) as w:
        w.span(queued)
        w.span(span)
        w.span(dict(span))          # the restart's replay
        w.request({"step": 5, "uid": 0, "event": "completed",
                   "reason": None, "latency_s": 2.0, "ttft_s": 1.0,
                   "t": 11.0})
    capsys.readouterr()
    assert report_main([mdir, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    w0 = doc["waterfalls"]["0"]
    assert len(w0["spans"]) == 2
    assert w0["span_sum_s"] == pytest.approx(2.0)
    assert w0["reconciled"]
    # the v9 decomposition: ttft + the (deduped) post-first-token span
    # telescopes to the latency too
    assert w0["ttft_s"] == 1.0
    assert w0["ttft_plus_post_s"] == pytest.approx(2.0)
    assert w0["ttft_reconciled"]
