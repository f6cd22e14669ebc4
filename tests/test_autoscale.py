"""Closed-loop autoscaling + tenant QoS (decode/autoscale.py,
runtime/policy.py, DESIGN.md section 26): the policy spec grammar, the
between-rounds controller scaling a live fleet up (warmed before
traffic) and down (zero-shed drains), the chaos drill — a worker
killed mid-burst is replaced through the below-min floor repair and
the whole episode replays byte-identically — and the engine-level QoS
decisions (predictive deadline shed, token-budget deferral) landing as
schema-v14 records. Model/config shapes are the shared test fixtures
(V=64, D=32, L=2, H=4, BASE blocks) so compiled programs hit the
persistent XLA cache.
"""

import os

import jax
import pytest

from distributed_llm_code_samples_tpu.decode import (AdmissionError,
                                                     DecodeEngine,
                                                     EngineConfig,
                                                     FleetRouter,
                                                     ServePolicy)
from distributed_llm_code_samples_tpu.decode.autoscale import (
    AutoscaleController)
from distributed_llm_code_samples_tpu.decode.fleet import EngineHandle
from distributed_llm_code_samples_tpu.decode.workload_driver import (
    WorkloadDriver, replay_trace)
from distributed_llm_code_samples_tpu.models import init_lm
from distributed_llm_code_samples_tpu.runtime.policy import (
    AutoscalePolicy, QosPolicy, parse_autoscale_spec, parse_qos_spec)
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, TelemetryWriter, read_metrics, validate_record)
from distributed_llm_code_samples_tpu.runtime.workload import (
    generate_trace)

V, D, L, H = 64, 32, 2, 4
BASE = dict(block_size=8, n_blocks=33, max_slots=3,
            max_blocks_per_seq=6, prefill_chunk=8)


@pytest.fixture(scope="module")
def lm_params():
    return init_lm(jax.random.PRNGKey(0), V, D, L, max_seq_len=64)


def _cfg(**extra):
    return EngineConfig(**{**BASE, **extra})


# a 10-at-once burst with a 2-request tail 6 trace-seconds later: the
# burst pressures the controller UP, the quiet gap before the tail
# pressures it DOWN — one trace exercises the whole loop
_SCALE_HEADER = {"trace_version": 1, "id": "trscale", "seed": 0,
                 "spec": "hand", "n": 12}
_SCALE_ENTRIES = (
    [{"t_offset_s": 0.0, "uid_hint": i, "tenant": None,
      "session": None, "prompt_len": 5, "max_new": 4, "turn": 0}
     for i in range(10)]
    + [{"t_offset_s": 6.0, "uid_hint": 10 + j, "tenant": None,
        "session": None, "prompt_len": 5, "max_new": 4, "turn": 0}
       for j in range(2)])


# ---------------------------------------------------------------------------
# the policy spec grammar (runtime/policy.py)


def test_policy_spec_parsing_round_trip():
    p = parse_autoscale_spec(
        "min=2,max=5,up=6,down=2,hysteresis=3,cooldown=10")
    assert p == AutoscalePolicy(min_engines=2, max_engines=5,
                                up_queue=6, down_queue=2,
                                hysteresis=3, cooldown=10)
    assert parse_autoscale_spec("") == AutoscalePolicy()
    q = parse_qos_spec("discipline=wfq,weights=a:3;b:1,budget=64,"
                       "predictive_shed=0")
    assert q.discipline == "wfq" and q.token_budget == 64
    assert not q.predictive_shed
    assert q.weight_of("a") == 3.0 and q.weight_of("unlisted") == 1.0
    assert QosPolicy.from_dict(q.as_dict()) == q


def test_policy_spec_rejections():
    """The --trace_gen parse-rejection discipline: every malformed
    spec is ONE ValueError naming the offense."""
    for bad, frag in [
        ("min=0", "must be >= 1"),
        ("min=3,max=2", "must be >= min_engines"),
        ("up=1,down=1", "dead band"),
        ("up=1,down=2", "dead band"),
        ("hysteresis=0", "must be >= 1"),
        ("cooldown=-1", "must be >= 0"),
        ("min=1,min=2", "duplicate key"),
        ("bogus", "key=value"),
        ("turbo=9", "known keys"),
        ("min=x", "integer"),
    ]:
        with pytest.raises(ValueError) as e:
            parse_autoscale_spec(bad)
        assert frag in str(e.value), (bad, str(e.value))
        assert "\n" not in str(e.value)
    for bad, frag in [
        ("discipline=warp", "known disciplines"),
        ("weights=a:0", "must be > 0"),
        ("weights=a:1;a:2", "duplicate tenant"),
        ("weights=", "empty mix"),
        ("weights=a", "NAME:WEIGHT"),
        ("weights=a:x", "must be a number"),
        ("budget=-1", ">= 0"),
        ("predictive_shed=2", "0 or 1"),
        ("turbo=1", "known keys"),
        ("budget=1,budget=2", "duplicate key"),
    ]:
        with pytest.raises(ValueError) as e:
            parse_qos_spec(bad)
        assert frag in str(e.value), (bad, str(e.value))
        assert "\n" not in str(e.value)


def test_autoscale_requires_a_fleet_target(lm_params):
    eng = DecodeEngine(lm_params, H, _cfg())
    with pytest.raises(ValueError, match="fleet"):
        WorkloadDriver(eng, _SCALE_HEADER, _SCALE_ENTRIES, vocab=V,
                       autoscale=object())


# ---------------------------------------------------------------------------
# the closed loop: up under pressure, down at idle, zero-shed drains


def _run_scaled(lm_params, mdir, policy, n_start=1, kill=None):
    """One autoscaled replay of the burst trace; returns everything
    the assertions need."""
    writers = []
    spawned = {}

    def mk(eid):
        m = TelemetryWriter(os.path.join(mdir, eid))
        writers.append(m)
        return DecodeEngine(lm_params, H, _cfg(max_slots=2),
                            metrics=m)

    def spawn(eid):
        eng = mk(eid)
        h = EngineHandle(eid, eng, "decode")
        inner = h.warm

        def warm(**kw):
            n = inner(**kw)
            spawned[eid] = (eng, n)
            return n

        h.warm = warm
        return h

    rm = TelemetryWriter(os.path.join(mdir, "router"))
    writers.append(rm)
    fl = FleetRouter(mk, n_start, metrics=rm)
    if kill is not None:
        fl.schedule_kill(*kill)
    ctl = AutoscaleController(fl, policy, spawn, metrics=rm)
    summary = replay_trace(fl, _SCALE_HEADER, _SCALE_ENTRIES, vocab=V,
                           log_every=4, metrics=rm, autoscale=ctl)
    outs = fl.results()
    state = dict(fl.autoscale_state)
    sheds = fl.sheds
    handles = list(fl.handles)
    for w in writers:
        w.close()
    recs, problems = read_metrics(
        os.path.join(mdir, "router", METRICS_FILENAME))
    assert not problems, problems
    return outs, summary, ctl, recs, state, sheds, handles, spawned


def test_closed_loop_scales_up_and_down_zero_shed(lm_params, tmp_path):
    """The burst pressures a 1-engine fleet up (spawned members warmed
    BEFORE traffic — zero new compiles in steady state), the quiet gap
    scales it back down through the zero-shed drain, every decision
    lands as a schema-valid autoscale record, and the whole episode
    replays byte-identically."""
    policy = AutoscalePolicy(min_engines=1, max_engines=3, up_queue=2,
                             down_queue=1, hysteresis=2, cooldown=4)
    outs, summary, ctl, recs, state, sheds, handles, spawned = \
        _run_scaled(lm_params, str(tmp_path / "a"), policy)
    assert len(outs) == 12 and summary["shed"] == 0
    assert ctl.scale_ups >= 1, ctl.history
    assert ctl.scale_downs >= 1, ctl.history
    # the zero-shed drain contract: scaling down shed NOTHING (and the
    # controller enforces it with its own RuntimeError besides)
    assert sheds == 0
    # warmed before traffic, and nothing compiled after: the spawned
    # engine's program set never grew once it took load
    assert spawned, "no spawned engine recorded"
    for eid, (eng, warmed_count) in spawned.items():
        assert warmed_count > 0, eid
        assert eng.compile_count == warmed_count, \
            (eid, eng.compile_count, warmed_count)
    # a retired member is marked retired, not dead-by-kill
    retired = [h for h in handles if getattr(h, "retired", False)]
    assert retired and all(not h.alive for h in retired)
    # the status mirror the ops plane publishes
    assert state["scale_ups"] == ctl.scale_ups
    assert state["scale_downs"] == ctl.scale_downs
    assert state["min_engines"] == 1 and state["max_engines"] == 3
    # every decision is on the record, schema-valid, with its pins
    arecs = [r for r in recs if r["kind"] == "autoscale"]
    events = [r["event"] for r in arecs]
    assert "scale_up" in events and "scale_down" in events
    for r in arecs:
        ok, reason = validate_record(r)
        assert ok, reason
        if r["event"] == "scale_up":
            assert r["engine"].startswith("e") and r["compiled"] > 0
        if r["event"] == "scale_down":
            assert "drained" in r
    # byte-identity: same (trace, seed, policy) -> same tokens AND the
    # same scaling episode (the record stream minus wall-clock extras)
    outs2, summary2, ctl2, recs2, *_ = _run_scaled(
        lm_params, str(tmp_path / "b"), policy)
    assert outs2 == outs
    assert ctl2.history == ctl.history
    pinned = [(r["step"], r["event"], r["reason"], r["engines"],
               r["target_engines"]) for r in arecs]
    pinned2 = [(r["step"], r["event"], r["reason"], r["engines"],
                r["target_engines"]) for r in recs2
               if r["kind"] == "autoscale"]
    assert pinned == pinned2


def test_kill_mid_burst_floor_repair_drill(lm_params, tmp_path):
    """The acceptance drill: a worker dies mid-burst under a
    min_engines floor — the controller spawns a warmed replacement
    IMMEDIATELY (floor repair beats cooldown), the migrated requests
    complete, tokens match the unkilled single-engine oracle, and two
    replays of the whole episode agree byte for byte."""
    policy = AutoscalePolicy(min_engines=2, max_engines=3, up_queue=4,
                             down_queue=1, hysteresis=2, cooldown=6)
    oracle = DecodeEngine(lm_params, H, _cfg(max_slots=2))
    replay_trace(oracle, _SCALE_HEADER, _SCALE_ENTRIES, vocab=V)
    outs, summary, ctl, recs, _, sheds, _, spawned = _run_scaled(
        lm_params, str(tmp_path / "a"), policy, n_start=2,
        kill=("e1", 6))
    assert len(outs) == 12 and summary["shed"] == 0 and sheds == 0
    assert outs == oracle.finished, \
        "killed+autoscaled replay diverged from the unkilled oracle"
    repairs = [(rnd, ev, reason) for rnd, ev, reason in ctl.history
               if ev == "scale_up" and reason == "below_min_floor"]
    assert repairs, ctl.history
    assert "e2" in spawned       # the replacement, minted fresh
    migrated = [r for r in recs if r["kind"] == "router"
                and r["event"] == "migrated"]
    assert migrated, "the kill migrated nothing — drill vacuous"
    arecs = [r for r in recs if r["kind"] == "autoscale"]
    assert arecs
    for r in arecs:
        ok, reason = validate_record(r)
        assert ok, reason
    outs2, _, ctl2, *_ = _run_scaled(lm_params, str(tmp_path / "b"),
                                     policy, n_start=2,
                                     kill=("e1", 6))
    assert outs2 == outs and ctl2.history == ctl.history


# ---------------------------------------------------------------------------
# engine-level QoS decisions (decode/engine.py)


def test_predictive_deadline_shed_named_and_recorded(lm_params,
                                                     tmp_path):
    """Admission throttling by predicted deadline miss: when the
    optimistic queue ETA already blows deadline_steps the request is
    shed AT THE DOOR with the named reason — on the AdmissionError,
    the request record, and a schema-valid qos record."""
    m = TelemetryWriter(str(tmp_path / "m"))
    eng = DecodeEngine(lm_params, H, _cfg(max_slots=1),
                       policy=ServePolicy(deadline_steps=10),
                       qos=QosPolicy(), metrics=m)
    eng.submit(list(range(4)), 8, tenant="a")     # eta 9 < 10: admits
    with pytest.raises(AdmissionError) as e:
        eng.submit(list(range(4)), 8, tenant="b")  # eta 17 >= 10: shed
    assert e.value.reason == "predicted_deadline_miss"
    assert "predicted deadline miss" in str(e.value)
    eng.run()
    m.close()
    assert len(eng.finished) == 1
    recs, problems = read_metrics(
        os.path.join(str(tmp_path / "m"), METRICS_FILENAME))
    assert not problems
    qrecs = [r for r in recs if r["kind"] == "qos"]
    assert [r["event"] for r in qrecs] == ["predicted_miss_shed"]
    ok, reason = validate_record(qrecs[0])
    assert ok, reason
    assert qrecs[0]["tenant"] == "b" and qrecs[0]["deadline_steps"] == 10
    assert qrecs[0]["eta_steps"] >= 10
    rej = [r for r in recs if r["kind"] == "request"
           and r["event"] == "rejected"]
    assert rej and rej[0]["reason"] == "predicted_deadline_miss"
    # predictive_shed=0 turns the throttle OFF: same load admits
    quiet = DecodeEngine(lm_params, H, _cfg(max_slots=1),
                         policy=ServePolicy(deadline_steps=10),
                         qos=QosPolicy(predictive_shed=False))
    quiet.submit(list(range(4)), 8, tenant="a")
    quiet.submit(list(range(4)), 8, tenant="b")   # queues, no shed
    assert len(quiet.waiting) + sum(
        s is not None for s in quiet.slots) == 2


def test_token_budget_defers_and_never_deadlocks(lm_params, tmp_path):
    """The per-tenant token budget shapes admission order (the hog's
    next request defers while another tenant is under budget, recorded
    once) but never deadlocks: when EVERY candidate is over budget the
    gate opens."""
    m = TelemetryWriter(str(tmp_path / "m"))
    eng = DecodeEngine(
        lm_params, H, _cfg(max_slots=2),
        qos=QosPolicy(discipline="wfq", token_budget=8), metrics=m)
    eng.submit(list(range(4)), 6, tenant="hog")    # resident 6
    eng.submit(list(range(4)), 6, tenant="hog")    # 12 > 8: deferred
    eng.submit(list(range(4)), 6, tenant="meek")   # under: goes first
    eng.run()
    m.close()
    assert len(eng.finished) == 3                  # no deadlock
    recs, problems = read_metrics(
        os.path.join(str(tmp_path / "m"), METRICS_FILENAME))
    assert not problems
    deferred = [r for r in recs if r["kind"] == "qos"
                and r["event"] == "budget_deferred"]
    assert deferred, "the over-budget head was never recorded"
    for r in deferred:
        ok, reason = validate_record(r)
        assert ok, reason
        assert r["tenant"] == "hog" and r["token_budget"] == 8
    # admission order: meek's single request was admitted before the
    # hog's second (the budget's whole point)
    admits = [r for r in recs if r["kind"] == "request"
              and r["event"] == "admitted"]
    order = [r["uid"] for r in admits]
    assert order.index(2) < order.index(1), order


# ---------------------------------------------------------------------------
# CLI surface


def test_generate_cli_autoscale_qos_policy(tmp_path, capsys):
    """`generate --autoscale --qos --policy` over a bursty two-tenant
    trace through a two-engine fleet: the controller scales up and sheds
    nothing, the router stream holds its schema-valid autoscale records,
    the payload names the policy, and `report --slo` prints attainment
    by tenant and by policy."""
    import json

    from distributed_llm_code_samples_tpu.decode.generate_cli import (
        generate_main)
    from distributed_llm_code_samples_tpu.report import report_main
    mdir = str(tmp_path / "m")
    assert generate_main([
        "-d", "32", "-l", "2", "--heads", "4", "--vocab", "64",
        "--max_seq_len", "64", "--block_size", "8", "--prefill_chunk",
        "4", "--log_every", "2", "--fleet", "2", "--max_slots", "2",
        "--autoscale", "min=2,max=3,up=3,down=1,hysteresis=2,cooldown=6",
        "--qos", "discipline=wfq,weights=a:2;b:1", "--policy", "wfq",
        "--trace_gen", "n=10,arrival=bursty:40:0.2:0.3,"
        "plen=zipf:1.7:3:12,max_new=4,tenants=a:3;b:1,seed=5",
        "--metrics_dir", mdir]) == 0
    run = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert not run["failed"] and run["shed"] == 0
    assert run["policy"] == "wfq"
    assert run["autoscale"]["scale_ups"] >= 1, run["autoscale"]
    recs, problems = read_metrics(
        os.path.join(mdir, "router", METRICS_FILENAME))
    assert not problems, problems
    auto = [r for r in recs if r["kind"] == "autoscale"]
    assert any(r["event"] == "scale_up" for r in auto), auto
    assert all(validate_record(r)[0] for r in auto)
    dirs = [os.path.join(mdir, d) for d in sorted(os.listdir(mdir))
            if os.path.isdir(os.path.join(mdir, d)) and d != "spool"]
    assert report_main(dirs + ["--slo", "100:0.5"]) == 0
    text = capsys.readouterr().out
    assert "tenant a" in text and "tenant b" in text
    assert "policy wfq" in text and "goodput" in text
