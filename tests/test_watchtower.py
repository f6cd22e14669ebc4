"""Fleet watchtower (runtime/watch.py, report --audit / --diff,
scripts/stream_diff.py, DESIGN.md section 27): the --watch spec
grammar, the burn-rate page firing during a kill drill and RESOLVING
after migration while the healthy replay stays silent, the alert
history replaying byte-identically across replays AND across the
in-process/process transports (asserted through the golden-stream
differ), the offline percentile-drift detector on a seeded degraded
stream, the telemetry invariant auditor's clean/violation verdicts,
and the CLI rejection matrices. Model/config shapes are the shared
test fixtures (V=64, D=32, L=2, H=4) so compiled programs hit the
persistent XLA cache.
"""

import contextlib
import io
import json
import os
import subprocess
import sys

import jax
import pytest

from distributed_llm_code_samples_tpu.decode import (DecodeEngine,
                                                     EngineConfig,
                                                     FleetRouter)
from distributed_llm_code_samples_tpu.decode.workload_driver import (
    WorkloadDriver, replay_trace)
from distributed_llm_code_samples_tpu.models import init_lm
from distributed_llm_code_samples_tpu.report import (_alerts_active_at,
                                                     diff_streams,
                                                     load_diff_stream,
                                                     report_main)
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, TelemetryWriter, read_metrics, validate_record)
from distributed_llm_code_samples_tpu.runtime.watch import (
    WatchPolicy, Watchtower, fold_records, parse_watch_spec)
from distributed_llm_code_samples_tpu.runtime.workload import (
    generate_trace, write_trace)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

V, D, L, H = 64, 32, 2, 4
BASE = dict(block_size=8, n_blocks=33, max_slots=2,
            max_blocks_per_seq=6, prefill_chunk=8)

# the calibrated kill drill (same trace the tier-1 watchtower smoke
# and the bench watch lane replay): three bursts separated by long OFF
# gaps — the kill at round 4 lands under the opening burst, so the
# migrated requests blow the 8-round deadline (the page), and the gap
# after the burst drains the fast window while the replay is still
# live (the resolve)
DRILL_SPEC = ("n=8,arrival=bursty:30:0.15:2.5,plen=zipf:1.7:3:12,"
              "max_new=4,tenants=a:3;b:1,seed=7")
DRILL_POLICY = WatchPolicy(deadline=8, fast=4, slow=12, incidents=1)
KILL_ROUND = 4
# the pinned alert history the drill produces (round, event, detector)
DRILL_HISTORY = [(5, "fired", "incident_rate"),
                 (11, "fired", "burn_rate"),
                 (16, "resolved", "burn_rate"),
                 (17, "resolved", "incident_rate")]


@pytest.fixture(scope="module")
def lm_params():
    return init_lm(jax.random.PRNGKey(0), V, D, L, max_seq_len=64)


def _run_drill(lm_params, mdir, *, kill=None, policy=DRILL_POLICY,
               trace=None):
    """One watched replay of the drill trace; returns the tower, the
    replay summary, the outputs, and the router stream."""
    hdr, ents = trace if trace is not None else \
        generate_trace(DRILL_SPEC)
    writers = []

    def mk(eid):
        m = TelemetryWriter(os.path.join(mdir, eid))
        writers.append(m)
        return DecodeEngine(lm_params, H, EngineConfig(**BASE),
                            metrics=m)

    rm = TelemetryWriter(os.path.join(mdir, "router"))
    writers.append(rm)
    fl = FleetRouter(mk, 2, metrics=rm)
    if kill is not None:
        fl.schedule_kill("e1", kill)
    tower = Watchtower(fl, policy, metrics=rm)
    summary = replay_trace(fl, hdr, ents, vocab=V, steps_per_s=8.0,
                           log_every=4, metrics=rm, watch=tower)
    outs = fl.results()
    for w in writers:
        w.close()
    recs, problems = read_metrics(
        os.path.join(mdir, "router", METRICS_FILENAME))
    assert not problems, problems
    return tower, summary, outs, recs


# ---------------------------------------------------------------------------
# the --watch spec grammar (runtime/watch.py)


def test_watch_spec_parsing_round_trip():
    p = parse_watch_spec("deadline=24,budget=0.2,burn=1.5,fast=4,"
                         "slow=16,queue=12,imbalance=0.7,collapse=6,"
                         "incidents=3")
    assert p == WatchPolicy(deadline=24, budget=0.2, burn=1.5, fast=4,
                            slow=16, queue=12, imbalance=0.7,
                            collapse=6, incidents=3)
    assert set(p.enabled()) == {"burn_rate", "queue_growth",
                                "imbalance", "collapse",
                                "incident_rate"}
    assert WatchPolicy(**{k: v for k, v in p.as_dict().items()
                          if v is not None
                          or k.startswith("baseline")}) == p
    # baseline=TTFT:ITL enables drift with the 2.0x default multiple
    q = parse_watch_spec("baseline=0.5:0.05")
    assert q.baseline_ttft == 0.5 and q.baseline_itl == 0.05
    assert q.drift == 2.0 and q.enabled() == ("latency_drift",)


def test_watch_spec_rejections():
    """The --trace_gen parse-rejection discipline: every malformed
    spec is ONE ValueError naming the offense."""
    for bad, frag in [
        ("", "no detector enabled"),
        ("budget=0.5", "no detector enabled"),
        ("deadline=8,deadline=9", "duplicate key"),
        ("turbo=9", "known keys"),
        ("bogus", "key=value"),
        ("deadline=x", "integer"),
        ("burn=x", "a number"),
        ("deadline=-1", ">= 0"),
        ("deadline=8,fast=0", ">= 1"),
        ("deadline=8,fast=8,slow=8", "must be > fast"),
        ("deadline=8,budget=0", "(0, 1]"),
        ("deadline=8,budget=1.5", "(0, 1]"),
        ("deadline=8,burn=0", "must be > 0"),
        ("imbalance=1.5", "[0, 1)"),
        ("drift=3", "needs a declared baseline"),
        ("baseline=0.5", "TTFT_S:ITL_S"),
        ("baseline=0.5:x", "a number"),
        ("baseline=0:0.05", "> 0 seconds"),
    ]:
        with pytest.raises(ValueError) as e:
            parse_watch_spec(bad)
        assert frag in str(e.value), (bad, str(e.value))
        assert "\n" not in str(e.value)


def test_watch_requires_a_fleet_target(lm_params):
    hdr, ents = generate_trace("n=2,plen=fixed:4,max_new=2")
    eng = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    with pytest.raises(ValueError, match="fleet"):
        WorkloadDriver(eng, hdr, ents, vocab=V,
                       watch=Watchtower.__new__(Watchtower))


# ---------------------------------------------------------------------------
# the kill drill: fire during the burn, resolve after migration


def test_burn_rate_fires_on_kill_and_resolves(lm_params, tmp_path):
    """The acceptance drill: e1 dies at round 4 under the opening
    burst — the burn-rate page fires within the pinned reaction
    (round 11) once the migrated requests blow the deadline, and
    RESOLVES (round 16) once the post-burst gap drains the fast
    window; the healthy replay of the same trace never alerts; every
    transition lands as a schema-valid v15 alert record with the
    numbers that justified it."""
    t_healthy, _, _, _ = _run_drill(lm_params, str(tmp_path / "h"))
    assert t_healthy.history == [], t_healthy.history
    tower, summary, outs, recs = _run_drill(
        lm_params, str(tmp_path / "k"), kill=KILL_ROUND)
    assert len(outs) == 8 and summary["shed"] == 0
    assert tower.history == DRILL_HISTORY
    assert tower.fired == 2 and tower.resolved == 2
    # the kill migrated live requests BEFORE the resolve round — the
    # resolution is recovery, not drain-to-empty
    migrated = [r["step"] for r in recs if r["kind"] == "router"
                and r["event"] == "migrated"]
    assert migrated and max(migrated) < 16, migrated
    alerts = [r for r in recs if r["kind"] == "alert"]
    assert [(a["step"], a["event"], a["detector"]) for a in alerts] \
        == DRILL_HISTORY
    for a in alerts:
        ok, reason = validate_record(a)
        assert ok, reason
        lo, hi = a["window"]
        assert 0 <= lo <= hi == a["step"], a
    fired = next(a for a in alerts if a["detector"] == "burn_rate"
                 and a["event"] == "fired")
    assert fired["severity"] == "page"
    assert fired["burn_fast"] >= 1.0 and fired["burn_slow"] >= 1.0
    assert fired["violations"] >= 1
    resolved = next(a for a in alerts if a["detector"] == "burn_rate"
                    and a["event"] == "resolved")
    assert resolved["fired_step"] == fired["step"]
    assert resolved["burn_fast"] < 1.0
    # the live mirror the status doc publishes: drained clean
    assert tower.router.watch_state == {"active": [], "fired": 2,
                                        "resolved": 2}


def test_alert_history_replay_identity(lm_params, tmp_path):
    """Two replays of the drill agree byte for byte on the alert
    history — asserted the way the smokes assert it, through the
    golden-stream differ (and report --diff --kinds alert says
    "identical" with rc 0)."""
    trace = generate_trace(DRILL_SPEC)
    t1, _, outs1, _ = _run_drill(lm_params, str(tmp_path / "a"),
                                 kill=KILL_ROUND, trace=trace)
    t2, _, outs2, _ = _run_drill(lm_params, str(tmp_path / "b"),
                                 kill=KILL_ROUND, trace=trace)
    assert outs2 == outs1 and t2.history == t1.history
    ra = os.path.join(str(tmp_path / "a"), "router")
    rb = os.path.join(str(tmp_path / "b"), "router")
    res = diff_streams(load_diff_stream(ra, ("alert",)),
                       load_diff_stream(rb, ("alert",)))
    assert res["verdict"] == "identical" and res["n_a"] == 4, res
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = report_main([ra, rb, "--diff", "--kinds", "alert"])
    assert rc == 0 and "identical" in out.getvalue()
    # the full-stream diff localizes the ONE pinned key two honest
    # replays legitimately disagree on: the per-request trace identity
    # is minted fresh each run (runtime/tracing.py) — which is exactly
    # why the replay-identity check filters to --kinds alert
    res = diff_streams(load_diff_stream(ra), load_diff_stream(rb))
    assert res["verdict"] == "token-divergence", res
    assert res["keys"] == ["trace_id"], res


# ---------------------------------------------------------------------------
# the offline half: percentile drift over a seeded degraded stream


def _seeded_stream(degraded: bool) -> list[dict]:
    """A synthetic recorded run: 16 completions over 16 rounds, TTFT
    p95 at the declared baseline — or drifted to 5x it."""
    recs = []
    for i in range(16):
        ttft = 0.5 if (degraded and i >= 8) else 0.1
        recs.append({"kind": "router", "event": "routed", "uid": i,
                     "step": i})
        recs.append({"kind": "request", "event": "completed", "uid": i,
                     "step": i, "ttft_s": ttft,
                     "latency_s": ttft + 0.03, "n_new": 4})
        recs.append({"kind": "fleet", "step": i + 1,
                     "engines": {"e0": {"alive": True, "waiting": 0,
                                        "active": 1}},
                     "load_imbalance": 0.0})
    return recs


def test_latency_drift_fires_on_seeded_degraded_run():
    policy = WatchPolicy(drift=2.0, baseline_ttft=0.1,
                         baseline_itl=0.05)
    assert policy.enabled() == ("latency_drift",)
    assert fold_records(_seeded_stream(degraded=False), policy) == []
    transitions = fold_records(_seeded_stream(degraded=True), policy)
    drift = [t for t in transitions if t["detector"] == "latency_drift"
             and t["event"] == "fired" and t["metric"] == "ttft"]
    assert len(drift) == 1, transitions
    assert drift[0]["severity"] == "warn"
    assert drift[0]["p95_s"] > 2.0 * drift[0]["baseline_s"] == 0.2
    # the ITL lifecycle never fired — only the seeded metric pages
    assert not any(t["metric"] == "itl" for t in transitions)


def test_fold_records_replays_the_live_drill(lm_params, tmp_path):
    """The offline fold over the drill's own recorded streams — the
    router + both engines merged in envelope order, since completions
    land in the ENGINE streams — reconstructs the live tower's exact
    alert history: the two halves share one detector core."""
    tower, _, _, _ = _run_drill(lm_params, str(tmp_path), kill=KILL_ROUND)
    merged = []
    for sub in ("router", "e0", "e1"):
        recs, problems = read_metrics(
            os.path.join(str(tmp_path), sub, METRICS_FILENAME))
        assert not problems, problems
        merged += recs
    merged.sort(key=lambda r: r.get("t", 0.0))
    transitions = fold_records(merged, DRILL_POLICY)
    assert [(t["step"], t["event"], t["detector"])
            for t in transitions] == tower.history == DRILL_HISTORY
    # router-only folding still sees the router-visible half (the
    # kill incident), just not the engine-side completions
    router_only = fold_records(
        [r for r in merged if r["kind"] in ("fleet", "router",
                                            "event", "workload")],
        DRILL_POLICY)
    assert [(t["step"], t["event"], t["detector"])
            for t in router_only] == [(5, "fired", "incident_rate"),
                                      (17, "resolved", "incident_rate")]


# ---------------------------------------------------------------------------
# the golden-stream differ (report.py core + scripts/stream_diff.py)


def test_diff_streams_classification():
    base = {"schema": 15, "kind": "request", "step": 3, "uid": 1,
            "event": "completed", "latency_s": 1.5}
    assert diff_streams([base], [dict(base)])["verdict"] == "identical"
    # only wall-clock keys differ -> timing-only
    res = diff_streams([base], [{**base, "latency_s": 1.7}])
    assert res["verdict"] == "timing-only" and res["keys"] == \
        ["latency_s"], res
    # a pinned content key differs -> THE determinism break
    res = diff_streams([base], [{**base, "uid": 2, "latency_s": 9.9}])
    assert res["verdict"] == "token-divergence"
    assert res["keys"] == ["uid"] and res["index"] == 0
    # key-set / kind / schema disagreement -> different writers
    res = diff_streams([base], [{**base, "extra": 1}])
    assert res["verdict"] == "schema-drift" and res["keys"] == ["extra"]
    res = diff_streams([base], [{**base, "schema": 14}])
    assert res["verdict"] == "schema-drift"
    # one stream holds records the other lacks -> token-divergence at
    # the tail, localized with the sentinel key
    res = diff_streams([base, base], [base])
    assert res["verdict"] == "token-divergence"
    assert res["keys"] == ["<length>"] and res["index"] == 1
    assert res["n_a"] == 2 and res["n_b"] == 1
    # severity precedence: schema-drift outranks an earlier
    # token-divergence outranks timing-only
    res = diff_streams(
        [base, base, base],
        [{**base, "latency_s": 9.0}, {**base, "uid": 7},
         {**base, "extra": 1}])
    assert res["verdict"] == "schema-drift" and res["index"] == 2


def test_diff_streams_transport_mode_equivalence():
    """Two honest replays of ONE run on two transports (inproc vs
    tcp): the move records agree on every content key and differ only
    in the transport attribution's mode + its timing members — that is
    a timing-only verdict (rc 0 surface), NOT schema-drift. A
    transport dict that disagrees on a content member (bytes shipped,
    retries) is still a real divergence."""
    t_wire = {"mode": "wire", "bytes": 4096, "crc_verify_s": 0.0001,
              "retries": 0}
    t_tcp = {"mode": "tcp", "bytes": 4096, "crc_verify_s": 0.0009,
             "retries": 0}
    base = {"schema": 16, "kind": "router", "step": 3, "uid": 1,
            "event": "migrated", "source": "e0", "target": "e1",
            "blocks": 3, "bytes": 4096, "duration_s": 0.01,
            "ship_s": None, "catchup_tokens": 2}
    res = diff_streams([{**base, "transport": t_wire}],
                       [{**base, "transport": t_tcp,
                         "duration_s": 0.03}])
    assert res["verdict"] == "timing-only", res
    # both differing keys are localized, both classified benign
    assert res["keys"] == ["duration_s", "transport"], res
    # a plain-string transport tag (meta records) is mode-only too
    meta = {"schema": 16, "kind": "meta", "step": 0, "uid": -1}
    res = diff_streams([{**meta, "transport": "process"}],
                       [{**meta, "transport": "tcp"}])
    assert res["verdict"] == "timing-only", res
    # bytes disagreeing inside the attribution IS a divergence: the
    # two runs did not ship the same document
    res = diff_streams(
        [{**base, "transport": t_wire}],
        [{**base, "transport": {**t_tcp, "bytes": 9999}}])
    assert res["verdict"] == "token-divergence", res
    assert res["keys"] == ["transport"], res
    # same for retries: a replayed send is observable behavior
    res = diff_streams(
        [{**base, "transport": t_wire}],
        [{**base, "transport": {**t_tcp, "retries": 2}}])
    assert res["verdict"] == "token-divergence", res


def test_stream_diff_cli(lm_params, tmp_path):
    """The standalone differ: same rc discipline as report --diff,
    runnable without the report CLI's surface."""
    script = os.path.join(REPO, "scripts", "stream_diff.py")
    t1, _, _, _ = _run_drill(lm_params, str(tmp_path / "a"),
                             kill=KILL_ROUND)
    _run_drill(lm_params, str(tmp_path / "b"), kill=KILL_ROUND)
    ra = os.path.join(str(tmp_path / "a"), "router")
    rb = os.path.join(str(tmp_path / "b"), "router")
    r = subprocess.run([sys.executable, script, ra, rb, "--kinds",
                        "alert"], capture_output=True, text=True)
    assert r.returncode == 0 and "identical" in r.stdout, r.stderr
    # the healthy run's alert stream is EMPTY — against the drill's
    # four transitions the differ localizes the missing records: rc 2
    _run_drill(lm_params, str(tmp_path / "h"))
    rh = os.path.join(str(tmp_path / "h"), "router")
    r = subprocess.run([sys.executable, script, ra, rh, "--kinds",
                        "alert"], capture_output=True, text=True)
    assert r.returncode == 2 and "token-divergence" in r.stdout
    assert "<length>" in r.stdout, r.stdout
    # rejections: unknown kind, missing stream
    r = subprocess.run([sys.executable, script, ra, rb, "--kinds",
                        "bogus"], capture_output=True, text=True)
    assert r.returncode == 2 and "bogus" in r.stderr
    r = subprocess.run([sys.executable, script, ra,
                        str(tmp_path / "nope")],
                       capture_output=True, text=True)
    assert r.returncode == 2 and "no metrics stream" in r.stderr


# ---------------------------------------------------------------------------
# the telemetry invariant auditor (report --audit)


def test_audit_clean_on_the_drill(lm_params, tmp_path):
    """The auditor holds over a real run — router + both engine
    streams of the kill drill — and says what it checked."""
    _run_drill(lm_params, str(tmp_path), kill=KILL_ROUND)
    dirs = [os.path.join(str(tmp_path), d)
            for d in ("router", "e0", "e1")]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = report_main(dirs + ["--audit"])
    assert rc == 0, out.getvalue()
    assert "audit: clean" in out.getvalue()
    assert "7 invariant(s)" in out.getvalue()


def test_audit_names_first_violated_invariant(tmp_path):
    """rc 2 names the FIRST violated invariant in catalog order and
    the record that broke it — a red audit is a diagnosis."""
    mdir = str(tmp_path / "bad")
    w = TelemetryWriter(mdir)
    # a span that ends before it starts: span_reconciliation
    w.span({"step": 3, "uid": 1, "span": "decode", "start_step": 9,
            "duration_s": 0.5, "t": 10.0, "t_start": 9.5})
    w.close()
    err = io.StringIO()
    with contextlib.redirect_stderr(err):
        rc = report_main([mdir, "--audit"])
    assert rc == 2
    msg = err.getvalue()
    assert "VIOLATION [span_reconciliation]" in msg, msg
    assert "uid 1" in msg and "step 9" in msg
    # seed a SCHEMA problem into the same stream: schema is first in
    # the catalog, so the verdict must switch to it
    with open(os.path.join(mdir, METRICS_FILENAME), "a") as f:
        f.write(json.dumps({"schema": 1, "kind": "step", "t": 0.0})
                + "\n")
    err = io.StringIO()
    with contextlib.redirect_stderr(err):
        rc = report_main([mdir, "--audit"])
    assert rc == 2 and "VIOLATION [schema]" in err.getvalue()
    # tenant books that don't reconcile: completed+shed > offered
    mdir2 = str(tmp_path / "books")
    w = TelemetryWriter(mdir2)
    w.workload({"step": 4, "trace": "tr1", "offered": 1, "admitted": 1,
                "tenants": {"a": {"offered": 2, "completed": 2,
                                  "shed": 1}}})
    w.close()
    err = io.StringIO()
    with contextlib.redirect_stderr(err):
        rc = report_main([mdir2, "--audit"])
    assert rc == 2
    assert "VIOLATION [tenant_reconciliation]" in err.getvalue()


def test_report_cli_rejections(tmp_path):
    """The rc-2 rejection discipline for the new report surface."""
    mdir = str(tmp_path / "m")
    w = TelemetryWriter(mdir)
    w.close()
    for argv, frag in [
        ([mdir, "--audit", "--diff"], "pick one"),
        ([mdir, "--diff"], "exactly TWO"),
        ([mdir, mdir, mdir, "--diff"], "exactly TWO"),
        ([mdir, mdir, "--kinds", "alert"], "pass --diff"),
        ([mdir, mdir, "--diff", "--kinds", "bogus"], "bogus"),
        ([mdir, mdir, "--diff", "--kinds", ""], "--kinds"),
        ([str(tmp_path / "nope"), "--audit"], "no metrics stream"),
        ([mdir, str(tmp_path / "nope"), "--diff"],
         "no metrics stream"),
    ]:
        err = io.StringIO()
        with contextlib.redirect_stderr(err), \
                contextlib.redirect_stdout(io.StringIO()):
            rc = report_main(argv)
        assert rc == 2, (argv, err.getvalue())
        assert frag in err.getvalue(), (argv, err.getvalue())


# ---------------------------------------------------------------------------
# CLI surface: --watch wiring + transport parity


def _cli_shape():
    return ["-d", "32", "-l", "2", "--heads", "4", "--vocab", "64",
            "--max_seq_len", "64", "--block_size", "8",
            "--prefill_chunk", "4", "--max_slots", "2"]


# the drill at the CLI's shape: chunks of 4, so most chunks are full
# ones and ride with the other slot's decode row in one mixed dispatch;
# a prompt whose LAST chunk rode decodes its second token a step later
# than when the two programs ran in one step, which brings the page
# forward a round
CLI_DRILL_HISTORY = ([DRILL_HISTORY[0], (10, "fired", "burn_rate")]
                     + DRILL_HISTORY[2:])


def test_generate_cli_watch_rejections(tmp_path):
    from distributed_llm_code_samples_tpu.decode.generate_cli import (
        generate_main)
    trace = str(tmp_path / "t.jsonl")
    write_trace(trace, *generate_trace("n=2,plen=fixed:4,max_new=2"))
    for bad in (
        # --watch is a fleet flag
        ["--trace", trace, "--watch", "deadline=8"],
        # --watch folds the trace replay's round clock
        ["--prompt_lens", "4", "--fleet", "2", "--watch",
         "deadline=8"],
        # malformed specs reject before any engine is built
        ["--trace", trace, "--fleet", "2", "--watch", "turbo=9"],
        ["--trace", trace, "--fleet", "2", "--watch", "budget=0.5"],
        ["--trace", trace, "--fleet", "2", "--watch",
         "deadline=8,fast=9,slow=9"],
    ):
        err = io.StringIO()
        with contextlib.redirect_stderr(err), \
                contextlib.redirect_stdout(io.StringIO()):
            rc = generate_main(bad + _cli_shape())
        assert rc == 2, (bad, err.getvalue())
        msg = err.getvalue().strip()
        assert "error:" in msg and len(msg.splitlines()) == 1, \
            (bad, msg)


def test_watch_cli_transport_parity(tmp_path):
    """The end-to-end claim: the drill through the CLI emits the SAME
    alert history on the in-process and the process transports —
    asserted through report --diff --kinds alert, plus the payload's
    own watch block."""
    from distributed_llm_code_samples_tpu.decode.generate_cli import (
        generate_main)
    trace = str(tmp_path / "drill.jsonl")
    write_trace(trace, *generate_trace(DRILL_SPEC))
    payloads = {}
    for transport in ("inproc", "process"):
        mdir = str(tmp_path / transport)
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = generate_main(
                ["--trace", trace, "--fleet", "2", "--fleet_kill",
                 f"e1@{KILL_ROUND}", "--transport", transport,
                 "--watch", "deadline=8,fast=4,slow=12,incidents=1",
                 "--metrics_dir", mdir] + _cli_shape())
        assert rc == 0, out.getvalue()
        payloads[transport] = json.loads(
            out.getvalue().strip().splitlines()[-1])
    for transport, payload in payloads.items():
        watch = payload["watch"]
        assert watch["fired"] == 2 and watch["resolved"] == 2, \
            (transport, watch)
        assert [(h["round"], h["event"], h["detector"])
                for h in watch["history"]] == CLI_DRILL_HISTORY, transport
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = report_main([os.path.join(str(tmp_path / "inproc"),
                                       "router"),
                          os.path.join(str(tmp_path / "process"),
                                       "router"),
                          "--diff", "--kinds", "alert"])
    assert rc == 0 and "identical" in out.getvalue()


# ---------------------------------------------------------------------------
# live surfaces: fleetstat alert block, postmortem active-alert fold


def test_fleetstat_renders_alert_block(tmp_path):
    from distributed_llm_code_samples_tpu.fleetstat import (
        fleetstat_main, render)
    doc = {"t": 0.0, "round": 12, "tokens_generated": 40,
           "drained": False, "engines": {}, "counters": {},
           "alerts": {"active": [{"detector": "burn_rate",
                                  "severity": "page",
                                  "since_round": 11, "burn_fast": 4.0,
                                  "burn_slow": 1.0, "violations": 1,
                                  "completions": 1}],
                      "fired": 2, "resolved": 1}}
    text = render(doc)
    assert "alerts: 1 active  (2 fired / 1 resolved lifetime)" in text
    assert "ALERT burn_rate [page] since round 11" in text
    assert "burn fast 4.0 / slow 1.0" in text
    # no watchtower -> no alert block (older status docs render as
    # before)
    assert "alerts" not in render({k: v for k, v in doc.items()
                                  if k != "alerts"})
    # --follow_max_s is an alias of --max_s (name parity with report)
    err = io.StringIO()
    with contextlib.redirect_stderr(err), \
            contextlib.redirect_stdout(io.StringIO()):
        rc = fleetstat_main([str(tmp_path / "nope"), "--follow",
                             "--interval", "0.05",
                             "--follow_max_s", "0.2"])
    assert rc == 2 and "no status document" in err.getvalue()


def test_alerts_active_at_declaration():
    """The postmortem fold: which alerts were FIRING at a flight
    recorder's dump time — fired-before, not-yet-resolved, keyed per
    drift metric."""
    alerts = [
        {"t": 10.0, "step": 5, "event": "fired",
         "detector": "incident_rate", "severity": "page"},
        {"t": 11.0, "step": 11, "event": "fired",
         "detector": "burn_rate", "severity": "page"},
        {"t": 12.0, "step": 14, "event": "fired",
         "detector": "latency_drift", "severity": "warn",
         "metric": "ttft"},
        {"t": 13.0, "step": 16, "event": "resolved",
         "detector": "burn_rate", "severity": "page"},
    ]
    assert _alerts_active_at(alerts, 9.0) == []
    at = _alerts_active_at(alerts, 11.5)
    assert [(a["detector"], a["since_round"]) for a in at] == \
        [("burn_rate", 11), ("incident_rate", 5)]
    # after the resolve, burn_rate drops; the drift metric stays
    at = _alerts_active_at(alerts, 14.0)
    assert [a["detector"] for a in at] == ["incident_rate",
                                           "latency_drift"]
