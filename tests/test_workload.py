"""The trace-driven workload observatory (runtime/workload.py +
decode/workload_driver.py, DESIGN.md section 25): seeded trace
generation, the versioned trace file's rejection discipline, and the
replay contract — same (trace, seed) yields byte-identical tokens,
identical admission order, and identical schema-v13 ``workload``
records through the single engine AND the fleet, with chaos (a
mid-trace kill) composing on top token-identically and the migrated
requests' tenant attribution intact. Model/config shapes are the
shared test fixtures (V=64, D=32, L=2, H=4, BASE blocks) so compiled
programs hit the persistent XLA cache.
"""

import contextlib
import io
import json
import os

import jax
import numpy as np
import pytest

from distributed_llm_code_samples_tpu.checkpoint import save_checkpoint
from distributed_llm_code_samples_tpu.decode import (DecodeEngine,
                                                     EngineConfig,
                                                     FleetRouter,
                                                     ServePolicy)
from distributed_llm_code_samples_tpu.decode.workload_driver import (
    WorkloadDriver, replay_trace)
from distributed_llm_code_samples_tpu.models import init_lm
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, TelemetryWriter, read_metrics, validate_record)
from distributed_llm_code_samples_tpu.runtime.workload import (
    TRACE_VERSION, TraceError, generate_trace, materialize_prompt,
    parse_trace_spec, read_trace, trace_id_of, write_trace)

V, D, L, H = 64, 32, 2, 4
BASE = dict(block_size=8, n_blocks=33, max_slots=3, max_blocks_per_seq=6,
            prefill_chunk=8)

# the canonical 2-tenant bursty spec most tests replay (tiny but
# real: on/off bursts, heavy-tail lengths, a weighted tenant mix)
SPEC = ("n=10,arrival=bursty:40:0.2:0.3,plen=zipf:1.7:3:12,max_new=4,"
        "tenants=a:3;b:1,seed=5")


@pytest.fixture(scope="module")
def lm_params():
    return init_lm(jax.random.PRNGKey(0), V, D, L, max_seq_len=64)


def _cfg(**extra):
    return EngineConfig(**{**BASE, **extra})


def _strip_t(rec: dict) -> dict:
    """A workload record minus its wall-clock envelope — everything
    that must replay identically."""
    return {k: v for k, v in rec.items() if k not in ("t",)}


# ---------------------------------------------------------------------------
# the trace generator + file format (runtime/workload.py)


def test_trace_spec_rejections():
    """The --chaos parse-rejection discipline: every malformed spec is
    ONE ValueError naming the offense."""
    for bad, frag in [
        ("", "n=INT is required"),
        ("n=0", "must be >= 1"),
        ("n=banana", "integer"),
        ("n=2,arrival=weird:1", "arrival kind"),
        ("n=2,arrival=poisson", "poisson takes 1"),
        ("n=2,arrival=bursty:4:0.1", "bursty takes 3"),
        ("n=2,arrival=poisson:0", "must be > 0"),
        ("n=2,plen=zipf:0.5:1:4", "alpha"),
        ("n=2,plen=uniform:9:4", "hi 4 < lo 9"),
        ("n=2,plen=gauss:3", "known samplers"),
        ("n=2,tenants=a:0", "must be > 0"),
        ("n=2,tenants=a:1;a:2", "duplicate tenant"),
        ("n=2,tenants=", "empty mix"),
        ("n=2,sessions=0", "K >= 1"),
        ("n=2,sessions=2:0", "grow"),
        ("n=2,seed=x", "seed"),
        ("n=2,n=3", "duplicate key"),
        ("n=2,bogus=1", "known keys"),
        ("n=2,arrival", "key=value"),
    ]:
        with pytest.raises(ValueError) as e:
            parse_trace_spec(bad)
        assert frag in str(e.value), (bad, str(e.value))
        assert "\n" not in str(e.value)


def test_trace_generation_deterministic_and_file_round_trip(tmp_path):
    """Same (spec, seed) -> identical entries and the SAME stable
    trace id (no wall clock, no process entropy); the written file
    round-trips exactly."""
    h1, e1 = generate_trace(SPEC)
    h2, e2 = generate_trace(SPEC)
    assert (h1, e1) == (h2, e2)
    assert h1["id"] == trace_id_of(SPEC, 5)
    assert h1["trace_version"] == TRACE_VERSION and h1["n"] == 10
    # a different seed is a different identity
    assert generate_trace(SPEC.replace("seed=5", "seed=6"))[0]["id"] \
        != h1["id"]
    path = str(tmp_path / "t.jsonl")
    write_trace(path, h1, e1)
    h3, e3 = read_trace(path)
    assert (h3, e3) == (h1, e1)
    # offsets are non-decreasing, first at 0; tenants drawn from the mix
    offs = [x["t_offset_s"] for x in e1]
    assert offs[0] == 0.0 and offs == sorted(offs)
    assert {x["tenant"] for x in e1} <= {"a", "b"}
    assert all(3 <= x["prompt_len"] <= 12 for x in e1)


def test_trace_file_rejection_discipline(tmp_path):
    """A trace is a determinism proof's input: torn tails, version
    skew, missing keys, and non-monotonic offsets are one-line
    TraceErrors, never a best-effort parse."""
    header, entries = generate_trace("n=3,plen=fixed:4,max_new=2")
    path = str(tmp_path / "t.jsonl")
    write_trace(path, header, entries)

    def rewrite(mutate):
        h, es = json.loads(json.dumps(header)), \
            [dict(x) for x in entries]
        mutate(h, es)
        with open(path, "w") as f:
            f.write("\n".join([json.dumps(h)]
                              + [json.dumps(x) for x in es]) + "\n")

    with open(path, "a") as f:
        f.write('{"torn')
    with pytest.raises(TraceError, match="unparseable"):
        read_trace(path)
    rewrite(lambda h, es: h.update(trace_version=99))
    with pytest.raises(TraceError, match="trace_version"):
        read_trace(path)
    rewrite(lambda h, es: h.pop("id"))
    with pytest.raises(TraceError, match="header missing"):
        read_trace(path)
    rewrite(lambda h, es: es[1].pop("max_new"))
    with pytest.raises(TraceError, match="max_new"):
        read_trace(path)
    rewrite(lambda h, es: es[2].update(t_offset_s=-1.0))
    with pytest.raises(TraceError, match="non-decreasing"):
        read_trace(path)
    rewrite(lambda h, es: es.pop())
    with pytest.raises(TraceError, match="torn tail"):
        read_trace(path)
    with pytest.raises(TraceError, match="empty"):
        open(path, "w").close() or read_trace(path)
    with pytest.raises(TraceError):
        read_trace(str(tmp_path / "missing.jsonl"))


def test_arrival_processes_have_their_shapes():
    """bursty leaves OFF-window silences, ramp accelerates, zipf is
    bounded with a heavy tail — the shapes the fixed waves never had."""
    _, eb = generate_trace("n=40,arrival=bursty:50:0.1:0.5,"
                           "plen=fixed:4,max_new=2,seed=1")
    gaps = np.diff([x["t_offset_s"] for x in eb])
    assert (gaps >= 0.5).sum() >= 2, "no OFF-window silences"
    assert (gaps < 0.1).sum() >= 20, "no in-burst clustering"
    _, er = generate_trace("n=60,arrival=ramp:2:60,plen=fixed:4,"
                           "max_new=2,seed=1")
    rg = np.diff([x["t_offset_s"] for x in er])
    assert rg[:15].mean() > 3 * rg[-15:].mean(), "ramp not ramping"
    _, ez = generate_trace("n=200,plen=zipf:1.3:4:40,max_new=2,seed=2")
    lens = [x["prompt_len"] for x in ez]
    assert min(lens) >= 4 and max(lens) <= 40
    assert max(lens) >= 3 * int(np.median(lens)), "no heavy tail"


def test_session_prompts_regrow_shared_prefixes(lm_params):
    """A session's turn t+1 prompt literally startswith turn t's (one
    fixed per-session stream), and replaying the session trace through
    a prefix-cached engine HITS: the chat-shaped workload the radix
    cache exists for."""
    header, entries = generate_trace(
        "n=6,sessions=2:8,plen=fixed:8,max_new=2,seed=3")
    by_session = {}
    for e in entries:
        by_session.setdefault(e["session"], []).append(e)
    for ses, turns in by_session.items():
        assert [t["turn"] for t in turns] == list(range(len(turns)))
        toks = [materialize_prompt(header, t, V) for t in turns]
        for a, b in zip(toks, toks[1:]):
            assert b[:len(a)] == a and len(b) == len(a) + 8
    # distinct sessions diverge (different streams)
    t0 = materialize_prompt(header, by_session["s0"][0], V)
    t1 = materialize_prompt(header, by_session["s1"][0], V)
    assert t0 != t1
    eng = DecodeEngine(lm_params, H, _cfg(max_slots=1))
    replay_trace(eng, header, entries, vocab=V)
    assert eng.prefix_hit_blocks > 0
    assert len(eng.finished) == 6 and not eng.failed


# ---------------------------------------------------------------------------
# replay determinism (the tentpole contract)


def test_single_engine_replay_deterministic_and_host_side_only(
        lm_params, tmp_path):
    """Two replays of one (trace, seed): byte-identical tokens and
    identical admission order; and trace-driven admission is HOST-side
    only — zero new compiles vs the same prompts submitted by hand
    (the overhead criterion, asserted on compile_count)."""
    header, entries = generate_trace(SPEC)

    def run(mdir):
        m = TelemetryWriter(mdir)
        eng = DecodeEngine(lm_params, H, _cfg(), metrics=m)
        eng.warm()      # the whole program set: which buckets a
        # schedule happens to reach is not what is compared below
        summary = replay_trace(eng, header, entries, vocab=V,
                               log_every=4, metrics=m)
        m.close()
        recs, problems = read_metrics(os.path.join(mdir,
                                                   METRICS_FILENAME))
        assert not problems, problems
        return eng, summary, recs

    e1, s1, r1 = run(str(tmp_path / "m1"))
    e2, s2, r2 = run(str(tmp_path / "m2"))
    assert e1.finished == e2.finished and not e1.failed
    assert s1 == s2
    admits1 = [(r["uid"], r["step"]) for r in r1
               if r["kind"] == "request" and r["event"] == "admitted"]
    admits2 = [(r["uid"], r["step"]) for r in r2
               if r["kind"] == "request" and r["event"] == "admitted"]
    assert admits1 == admits2 and admits1
    wl1 = [_strip_t(r) for r in r1 if r["kind"] == "workload"]
    wl2 = [_strip_t(r) for r in r2 if r["kind"] == "workload"]
    assert wl1 == wl2 and wl1
    for r in r1:
        if r["kind"] == "workload":
            ok, reason = validate_record(r)
            assert ok, reason
    # every record for an admitted uid carries its tenant
    by_uid_tenant = {e["uid_hint"]: e["tenant"] for e in entries}
    for r in r1:
        if r["kind"] == "request" and r["event"] == "completed":
            assert r["tenant"] in ("a", "b")
    # the overhead criterion: hand-submit the SAME materialized
    # prompts — same program set, zero compiles the trace path adds
    hand = DecodeEngine(lm_params, H, _cfg())
    hand.warm()
    for e in entries:
        hand.submit(materialize_prompt(header, e, V),
                    int(e["max_new"]))
    hand.run()
    assert e1.compile_count == hand.compile_count
    assert hand.finished != {}  # sanity: the hand run really ran
    del by_uid_tenant


def test_fleet_replay_deterministic_with_identical_workload_records(
        lm_params, tmp_path):
    """The acceptance determinism drill, in-process: the same
    (trace, seed) through a 3-engine fleet twice — byte-identical
    tokens, identical admission order (router records), identical
    schema-v13 workload records — and the fleet's tokens equal the
    single-engine replay's (the routing layer moves placement, never
    content)."""
    header, entries = generate_trace(SPEC)

    def run(tag):
        mdir = str(tmp_path / tag)
        writers = []

        def mk(eid):
            m = TelemetryWriter(os.path.join(mdir, eid))
            writers.append(m)
            return DecodeEngine(lm_params, H, _cfg(), metrics=m)

        rm = TelemetryWriter(os.path.join(mdir, "router"))
        writers.append(rm)
        fl = FleetRouter(mk, 3, metrics=rm)
        summary = replay_trace(fl, header, entries, vocab=V,
                               log_every=4, metrics=rm)
        outs = fl.results()
        for w in writers:
            w.close()
        recs, problems = read_metrics(
            os.path.join(mdir, "router", METRICS_FILENAME))
        assert not problems, problems
        return outs, summary, recs

    o1, s1, r1 = run("f1")
    o2, s2, r2 = run("f2")
    assert o1 == o2 and s1 == s2
    routed1 = [(r["uid"], r["target"], r["step"]) for r in r1
               if r["kind"] == "router" and r["event"] == "routed"]
    routed2 = [(r["uid"], r["target"], r["step"]) for r in r2
               if r["kind"] == "router" and r["event"] == "routed"]
    assert routed1 == routed2 and len(routed1) == len(entries)
    wl1 = [_strip_t(r) for r in r1 if r["kind"] == "workload"]
    wl2 = [_strip_t(r) for r in r2 if r["kind"] == "workload"]
    assert wl1 == wl2 and wl1
    # single-engine replay of the same trace: same tokens
    eng = DecodeEngine(lm_params, H, _cfg())
    replay_trace(eng, header, entries, vocab=V)
    assert eng.finished == o1


def test_kill_mid_trace_token_identity_and_tenant_attribution(
        lm_params, tmp_path):
    """Chaos composes ON TOP of replay: the same trace with e1 killed
    mid-trace completes byte-identically to the unkilled replay, and
    the migrated requests' completed records keep their tenant tags
    (the per-tenant numbers survive the migration)."""
    header, entries = generate_trace(SPEC)
    oracle = DecodeEngine(lm_params, H, _cfg())
    replay_trace(oracle, header, entries, vocab=V)

    mdir = str(tmp_path / "killed")
    writers = []

    def mk(eid):
        m = TelemetryWriter(os.path.join(mdir, eid))
        writers.append(m)
        return DecodeEngine(lm_params, H, _cfg(), metrics=m)

    rm = TelemetryWriter(os.path.join(mdir, "router"))
    writers.append(rm)
    fl = FleetRouter(mk, 3, metrics=rm)
    fl.schedule_kill("e1", 4)
    summary = replay_trace(fl, header, entries, vocab=V, log_every=4,
                           metrics=rm)
    outs = fl.results()
    for w in writers:
        w.close()
    assert outs == oracle.finished, \
        "killed replay diverged from the unkilled oracle"
    assert not fl.failed()
    rrecs, problems = read_metrics(
        os.path.join(mdir, "router", METRICS_FILENAME))
    assert not problems, problems
    migrated = {r["uid"] for r in rrecs if r["kind"] == "router"
                and r["event"] == "migrated"}
    assert migrated, "the kill migrated nothing — drill vacuous"
    # the driver's uid->tenant book is authoritative for the trace;
    # every migrated uid's completed record (on whichever engine) must
    # carry that tenant verbatim
    tenant_of = {}
    recs_all = []
    for eid in ("e0", "e1", "e2"):
        recs, _ = read_metrics(os.path.join(mdir, eid,
                                            METRICS_FILENAME))
        recs_all.extend(recs)
    for r in recs_all:
        if r["kind"] == "request" and r["event"] == "admitted" \
                and r["uid"] not in tenant_of:
            tenant_of[r["uid"]] = r["tenant"]
    for r in recs_all:
        if r["kind"] == "request" and r["event"] == "completed" \
                and r["uid"] in migrated:
            assert r["tenant"] == tenant_of[r["uid"]] \
                and r["tenant"] in ("a", "b"), r
    # workload totals still reconcile after the kill
    last_wl = [r for r in
               read_metrics(os.path.join(
                   mdir, "router", METRICS_FILENAME))[0]
               if r["kind"] == "workload"][-1]
    per_tenant = {e["uid_hint"]: e["tenant"] for e in entries}
    want = {}
    for t in per_tenant.values():
        want[t] = want.get(t, 0) + 1
    got = {t: c["completed"] for t, c in last_wl["tenants"].items()}
    assert got == want, (got, want)
    del summary


# ---------------------------------------------------------------------------
# the noisy-tenant drill + report surfaces


def test_noisy_tenant_starvation_visible_and_reconciled(lm_params,
                                                        tmp_path):
    """One tenant floods at t=0, one trickles in behind: FCFS lets the
    flood starve the trickle, and the report's per-tenant numbers must
    RENDER that (quiet's TTFT p50 well above noisy's) while the
    per-tenant counts reconcile with the fleet totals — the baseline a
    future QoS scheduler PR must move."""
    from distributed_llm_code_samples_tpu.report import report_main
    header = {"trace_version": 1, "id": "trnoisy", "seed": 0,
              "spec": "hand", "n": 10}
    entries = (
        [{"t_offset_s": 0.0, "uid_hint": i, "tenant": "noisy",
          "session": None, "prompt_len": 6, "max_new": 6, "turn": 0}
         for i in range(8)]
        + [{"t_offset_s": 0.1, "uid_hint": 8 + j, "tenant": "quiet",
            "session": None, "prompt_len": 6, "max_new": 6, "turn": 0}
           for j in range(2)])
    mdir = str(tmp_path / "m")
    m = TelemetryWriter(mdir)
    eng = DecodeEngine(lm_params, H, _cfg(max_slots=2))
    # warm the program set FIRST (same shapes as the trace), with no
    # writer attached: the starvation assertion below compares
    # wall-clock TTFTs, and a cold compile inside the flood's service
    # would swamp the queueing signal being measured
    rng = np.random.default_rng(9)
    for _ in range(2):
        eng.submit(rng.integers(0, V, size=6).tolist(), 6)
    eng.run()
    eng.metrics = m
    replay_trace(eng, header, entries, vocab=V, log_every=4, metrics=m)
    m.close()
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = report_main([mdir, "--slo", "100:0.000001", "--json"])
    assert rc == 0
    doc = json.loads(buf.getvalue())
    wl = doc["workload"]
    assert wl["reconciled"], wl
    assert wl["tenants"]["noisy"]["completed"] == 8
    assert wl["tenants"]["quiet"]["completed"] == 2
    assert sum(e["completed"] for e in wl["tenants"].values()) \
        == wl["completed_total"] == 10
    # the starvation: the quiet requests queue behind the whole flood
    # (FCFS admits them last), so their median TTFT sits above the
    # noisy tenant's — the number a future QoS scheduler must move
    assert wl["tenants"]["quiet"]["ttft_p50_s"] > \
        wl["tenants"]["noisy"]["ttft_p50_s"], wl["tenants"]
    # the per-tenant SLO slice counts reconcile too
    bt = doc["slo"]["by_tenant"]
    assert bt["noisy"]["completed"] == 8
    assert bt["quiet"]["completed"] == 2
    assert sum(b["completed"] for b in bt.values()) \
        == doc["slo"]["completed"]
    # the text render names both tenants
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = report_main([mdir, "--slo", "100:0.000001"])
    assert rc == 0
    text = buf.getvalue()
    assert "tenant noisy" in text and "tenant quiet" in text
    assert "offered vs admitted" in text


def test_wfq_flips_noisy_tenant_baseline(lm_params, tmp_path):
    """The QoS scheduler moves the recorded FCFS baseline on the SAME
    (trace, seed): under weighted-fair scheduling (quiet:3;noisy:1)
    the quiet tenant's median TTFT is no longer above the noisy
    flood's, per-tenant counts still reconcile with the fleet totals,
    and every token is byte-identical to the FCFS run — fairness
    reorders ADMISSION, never sampling identity."""
    from distributed_llm_code_samples_tpu.report import report_main
    from distributed_llm_code_samples_tpu.runtime.policy import (
        QosPolicy)
    header = {"trace_version": 1, "id": "trnoisy", "seed": 0,
              "spec": "hand", "n": 10}
    entries = (
        [{"t_offset_s": 0.0, "uid_hint": i, "tenant": "noisy",
          "session": None, "prompt_len": 6, "max_new": 6, "turn": 0}
         for i in range(8)]
        + [{"t_offset_s": 0.1, "uid_hint": 8 + j, "tenant": "quiet",
            "session": None, "prompt_len": 6, "max_new": 6, "turn": 0}
           for j in range(2)])

    def warmed(qos=None):
        eng = DecodeEngine(lm_params, H, _cfg(max_slots=2), qos=qos)
        # warm the program set FIRST (same shapes), no writer: the
        # flip assertion compares wall-clock TTFTs — a cold compile
        # inside the flood would swamp the queueing signal
        rng = np.random.default_rng(9)
        for _ in range(2):
            eng.submit(rng.integers(0, V, size=6).tolist(), 6)
        eng.run()
        return eng

    fcfs = warmed()
    replay_trace(fcfs, header, entries, vocab=V)
    mdir = str(tmp_path / "m")
    m = TelemetryWriter(mdir)
    wfq = warmed(qos=QosPolicy(discipline="wfq",
                               weights=(("quiet", 3), ("noisy", 1))))
    wfq.metrics = m
    replay_trace(wfq, header, entries, vocab=V, log_every=4, metrics=m)
    m.close()
    # token identity across disciplines: keys fold (seed, uid,
    # position), so the fair schedule changed WHEN, never WHAT
    assert wfq.finished == fcfs.finished
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = report_main([mdir, "--slo", "100:0.000001", "--json"])
    assert rc == 0
    doc = json.loads(buf.getvalue())
    wl = doc["workload"]
    assert wl["reconciled"], wl
    assert wl["tenants"]["noisy"]["completed"] == 8
    assert wl["tenants"]["quiet"]["completed"] == 2
    assert sum(e["completed"] for e in wl["tenants"].values()) \
        == wl["completed_total"] == 10
    # THE FLIP: the baseline drill pins quiet's p50 ABOVE noisy's
    # under FCFS; weighted-fair admission must bring it down to at
    # most the flood's own median
    assert wl["tenants"]["quiet"]["ttft_p50_s"] <= \
        wl["tenants"]["noisy"]["ttft_p50_s"], wl["tenants"]
    bt = doc["slo"]["by_tenant"]
    assert sum(b["completed"] for b in bt.values()) \
        == doc["slo"]["completed"]
    # the scheduler's decisions are on the record: at least one
    # schema-valid wfq_pick naming the tenant it favored
    recs, problems = read_metrics(os.path.join(mdir, METRICS_FILENAME))
    assert not problems
    picks = [r for r in recs if r["kind"] == "qos"
             and r["event"] == "wfq_pick"]
    assert picks, "wfq run emitted no wfq_pick qos record"
    for r in picks:
        ok, reason = validate_record(r)
        assert ok, reason
        assert r["tenant"] in ("noisy", "quiet")


def test_queue_limit_sheds_count_per_tenant(lm_params, tmp_path):
    """Sheds at the door land in the DRIVER's per-tenant book (the
    engine's rejected record is the anonymous uid -1): the workload
    record and the report fold carry them by tenant."""
    header = {"trace_version": 1, "id": "trshed", "seed": 0,
              "spec": "hand", "n": 6}
    entries = [{"t_offset_s": 0.0, "uid_hint": i,
                "tenant": ("flood" if i < 5 else "late"),
                "session": None, "prompt_len": 4, "max_new": 4,
                "turn": 0} for i in range(6)]
    m = TelemetryWriter(str(tmp_path / "m"))
    eng = DecodeEngine(lm_params, H, _cfg(max_slots=1),
                       policy=ServePolicy(queue_limit=2), metrics=m)
    summary = replay_trace(eng, header, entries, vocab=V, log_every=2,
                           metrics=m)
    m.close()
    # queue_limit 2: flood0/1 queue, flood2..4 shed at the door, and
    # the late submission behind them sheds too — per tenant, exactly
    assert summary["shed"] == 4
    assert summary["tenants"]["flood"]["shed"] == 3
    assert summary["tenants"]["late"]["shed"] == 1
    assert summary["tenants"]["flood"]["offered"] == 5
    recs, problems = read_metrics(
        os.path.join(str(tmp_path / "m"), METRICS_FILENAME))
    assert not problems
    last_wl = [r for r in recs if r["kind"] == "workload"][-1]
    assert last_wl["tenants"]["flood"]["shed"] == 3
    assert last_wl["tenants"]["late"]["shed"] == 1
    # offered == admitted + shed interval accounting
    offered = sum(r["offered"] for r in recs
                  if r["kind"] == "workload")
    admitted = sum(r["admitted"] for r in recs
                   if r["kind"] == "workload")
    assert offered - admitted == summary["shed"]


# ---------------------------------------------------------------------------
# driver validation + wall pacing


def test_driver_validation_and_wall_pace(lm_params):
    header, entries = generate_trace("n=3,plen=fixed:4,max_new=2,"
                                     "arrival=poisson:200")
    eng = DecodeEngine(lm_params, H, _cfg())
    with pytest.raises(ValueError, match="pace"):
        WorkloadDriver(eng, header, entries, vocab=V, pace="warp")
    with pytest.raises(ValueError, match="steps_per_s"):
        WorkloadDriver(eng, header, entries, vocab=V, steps_per_s=0)
    # wall pacing: token identity holds (sampling never reads the
    # clock) even though admission timing is real seconds
    replay_trace(eng, header, entries, vocab=V, pace="wall")
    virt = DecodeEngine(lm_params, H, _cfg())
    replay_trace(virt, header, entries, vocab=V)
    assert eng.finished == virt.finished


def test_deploy_watch_rolls_on_real_mid_serve_publish(lm_params,
                                                      tmp_path):
    """The deploy-on-publish watcher (ROADMAP item 3 follow-on): a
    REAL checkpoint publish lands mid-serve, the watcher's poll sees
    ``latest_verified`` advance, and the fleet rolls forward with zero
    shed — no operator, no scheduled round."""
    ck = str(tmp_path / "ck")
    new_params = init_lm(jax.random.PRNGKey(7), V, D, L, max_seq_len=64)
    fl = FleetRouter(lambda eid: DecodeEngine(lm_params, H, _cfg()), 2)
    fl.deploy_watch(ck, poll_every_s=1e-6)
    with pytest.raises(ValueError, match="> 0"):
        fl.deploy_watch(ck, poll_every_s=0)
    fl.deploy_watch(ck, poll_every_s=1e-6)
    rng = np.random.default_rng(2)
    for n in (5, 9, 6, 7):
        fl.submit(rng.integers(0, V, size=n).tolist(), 10)
    for _ in range(3):
        fl.step()
    assert fl.deploys == 0      # nothing published yet: no deploy
    save_checkpoint(ck, new_params, 5)      # the REAL mid-serve publish
    fl.run()
    assert fl.deploys == 1 and fl.deploy_rollbacks == 0
    assert fl.sheds == 0 and not fl.failed()
    assert {h.serving_version for h in fl.alive_handles()} == {5}
    # idempotent: the watcher must not re-deploy an already-serving step
    fl.submit(rng.integers(0, V, size=4).tolist(), 4)
    fl.run()
    assert fl.deploys == 1


# ---------------------------------------------------------------------------
# the process transport (the acceptance criterion's second half)


@pytest.mark.serial
def test_process_transport_replay_matches_inprocess_with_kill(
        lm_params, tmp_path):
    """The same (trace, seed) through 3 engine WORKER PROCESSES with
    kill_worker@4:1 (a REAL SIGKILL mid-trace): tokens byte-identical
    to the in-process killed fleet AND to the unkilled oracle,
    identical admission order, identical workload records, and the
    migrated requests keep their tenant on the completed records."""
    from conftest import load_scaled_timeout
    from distributed_llm_code_samples_tpu.decode.worker import (
        spawn_fleet_handles)
    from distributed_llm_code_samples_tpu.runtime.chaos import (
        FaultPlan, validate_fleet_plan)
    header, entries = generate_trace(SPEC)
    oracle = DecodeEngine(lm_params, H, _cfg())
    replay_trace(oracle, header, entries, vocab=V)

    def killed_lane(tag, handles=None, chaos=None):
        mdir = str(tmp_path / tag)
        writers = []
        rm = TelemetryWriter(os.path.join(mdir, "router"))
        writers.append(rm)
        if handles is None:
            def mk(eid):
                m = TelemetryWriter(os.path.join(mdir, eid))
                writers.append(m)
                return DecodeEngine(lm_params, H, _cfg(), metrics=m)
            fl = FleetRouter(mk, 3, metrics=rm, fleet_chaos=chaos)
        else:
            fl = FleetRouter(None, 3, handles=handles, metrics=rm,
                             fleet_chaos=chaos)
        try:
            summary = replay_trace(fl, header, entries, vocab=V,
                                   log_every=4, metrics=rm)
            outs = fl.results()
            failed = fl.failed()
        finally:
            fl.close()
            for w in writers:
                w.close()
        recs, problems = read_metrics(
            os.path.join(mdir, "router", METRICS_FILENAME))
        assert not problems, problems
        return outs, failed, summary, recs

    plan_in = FaultPlan.parse("kill_worker@4:1")
    # in-process kill_worker is honored via the scheduled-kill path
    inp = FleetRouter(
        lambda eid: DecodeEngine(lm_params, H, _cfg()), 3)
    inp.schedule_kill("e1", 4)
    sum_in = replay_trace(inp, header, entries, vocab=V)
    outs_in = inp.results()
    assert outs_in == oracle.finished

    plan = FaultPlan.parse("kill_worker@4:1")
    validate_fleet_plan(plan)
    deadline = load_scaled_timeout(120.0)
    handles = spawn_fleet_handles(
        3, 0, str(tmp_path / "spool"),
        model=dict(vocab=V, model_size=D, layers=L, heads=H,
                   kv_heads=None, max_seq_len=64, random_seed=0),
        config=dict(BASE), policy={},
        metrics_root=str(tmp_path / "proc"),
        call_deadline_s=deadline, connect_deadline_s=deadline)
    outs_p, failed_p, sum_p, recs_p = killed_lane("proc",
                                                  handles=handles,
                                                  chaos=plan)
    assert outs_p == oracle.finished and not failed_p
    assert sum_p["tenants"] == sum_in["tenants"]
    migrated = {r["uid"] for r in recs_p if r["kind"] == "router"
                and r["event"] == "migrated"}
    assert migrated, "the SIGKILL migrated nothing — drill vacuous"
    wl = [r for r in recs_p if r["kind"] == "workload"]
    assert wl and all(validate_record(r)[0] for r in wl)
    # admission order across the process boundary == in-process
    routed_p = [(r["uid"], r["target"], r["step"]) for r in recs_p
                if r["kind"] == "router" and r["event"] == "routed"]
    assert [u for u, _t, _s in routed_p] ==         sorted(u for u, _t, _s in routed_p)
    # the migrated uids' completed records (in the workers' own
    # streams) kept their tenant attribution across the real SIGKILL
    tenant_want = {}
    comp_tenant = {}
    for eid in ("e0", "e1", "e2"):
        recs, _ = read_metrics(os.path.join(
            str(tmp_path / "proc"), eid, METRICS_FILENAME))
        for r in recs:
            if r["kind"] != "request":
                continue
            if r["event"] == "admitted" and r["uid"] not in tenant_want:
                tenant_want[r["uid"]] = r["tenant"]
            if r["event"] == "completed":
                comp_tenant[r["uid"]] = r["tenant"]
    for uid in migrated:
        assert comp_tenant.get(uid) == tenant_want[uid]             and comp_tenant.get(uid) in ("a", "b"), uid
    del plan_in


# ---------------------------------------------------------------------------
# CLI surface: one end-to-end run, then the rc-2 rejection discipline


def test_generate_cli_trace_gen_then_file_replay(tmp_path, capsys):
    """`--trace_gen SPEC --trace_out FILE` through a two-engine fleet,
    then `--trace FILE`: the file replays the generated trace to the
    same tokens, the same workload summary and the same workload
    records, and `report` over the replay's streams prints every
    tenant and audits clean."""
    from distributed_llm_code_samples_tpu.decode.generate_cli import (
        generate_main)
    from distributed_llm_code_samples_tpu.report import report_main
    trace = str(tmp_path / "trace.jsonl")
    shape = ["-d", "32", "-l", "2", "--heads", "4", "--vocab", "64",
             "--max_seq_len", "64", "--block_size", "8",
             "--prefill_chunk", "4", "--log_every", "2", "--fleet", "2"]
    spec = ("n=10,arrival=bursty:40:0.2:0.3,plen=zipf:1.7:3:12,"
            "max_new=4,tenants=a:3;b:1,seed=5")

    def run(source, tag):
        mdir = str(tmp_path / tag)
        assert generate_main(source + shape
                             + ["--metrics_dir", mdir]) == 0
        payload = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        recs, problems = read_metrics(
            os.path.join(mdir, "router", METRICS_FILENAME))
        assert not problems, problems
        return payload, [_strip_t(r) for r in recs
                         if r["kind"] == "workload"]

    r1, wl1 = run(["--trace_gen", spec, "--trace_out", trace], "m1")
    r2, wl2 = run(["--trace", trace], "m2")
    assert not r1["failed"] and not r2["failed"]
    assert ({s["uid"]: s["tokens"] for s in r1["sequences"]}
            == {s["uid"]: s["tokens"] for s in r2["sequences"]})
    assert r1["workload"] == r2["workload"]
    assert set(r1["workload"]["tenants"]) == {"a", "b"}
    assert wl1 and wl1 == wl2
    dirs = [str(tmp_path / "m2" / e) for e in ("router", "e0", "e1")]
    assert report_main(dirs) == 0
    text = capsys.readouterr().out
    assert "workload [trace" in text and "TTFT" in text
    assert "tenant a" in text and "tenant b" in text
    assert report_main(dirs + ["--audit"]) == 0
    capsys.readouterr()


def test_generate_cli_trace_rejections(tmp_path):
    from distributed_llm_code_samples_tpu.decode.generate_cli import (
        generate_main)
    trace = str(tmp_path / "t.jsonl")
    write_trace(trace, *generate_trace("n=2,plen=fixed:4,max_new=2"))
    shape = ["-d", "32", "-l", "2", "--heads", "4", "--vocab", "64",
             "--max_seq_len", "64", "--block_size", "8",
             "--prefill_chunk", "4"]
    for bad in (
        ["--trace_gen", "n=0"],                      # bad spec
        ["--trace_gen", "n=2,arrival=x:1"],          # bad arrival
        ["--trace", str(tmp_path / "missing.jsonl")],  # no file
        ["--trace", trace, "--prompts", "1,2"],      # two sources
        ["--trace", trace, "--trace_gen", "n=2"],    # two sources
        ["--trace_out", trace, "--prompt_lens", "3"],  # out w/o gen
        ["--trace_pace", "wall", "--prompt_lens", "3"],  # pace w/o trace
        ["--trace", trace, "--trace_steps_per_s", "0"],  # bad rate
        ["--trace", trace, "--snapshot_dir", str(tmp_path / "s")],
        # the watcher tracks latest_verified; a pinned step needs
        # --deploy_round (silently dropping it would be the
        # ignored-flag failure the guard block rejects)
        ["--prompt_lens", "3", "--fleet", "2", "--deploy_dir",
         str(tmp_path / "ck"), "--deploy_watch", "1",
         "--deploy_step", "7"],
    ):
        err = io.StringIO()
        with contextlib.redirect_stderr(err), \
                contextlib.redirect_stdout(io.StringIO()):
            rc = generate_main(bad + shape)
        assert rc == 2, (bad, err.getvalue())
        assert "error:" in err.getvalue(), bad
    # a torn trace file rejects rc 2 with the one-line reason
    with open(trace, "a") as f:
        f.write('{"torn')
    err = io.StringIO()
    with contextlib.redirect_stderr(err), \
            contextlib.redirect_stdout(io.StringIO()):
        rc = generate_main(["--trace", trace] + shape)
    assert rc == 2 and "unparseable" in err.getvalue()
