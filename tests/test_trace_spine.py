"""The fleet trace spine + live ops plane (ISSUE 14, schema v12,
DESIGN.md section 24): cross-process trace-context propagation, the
``report --trace`` causal waterfall, RPC cost attribution, the live
fleet status surface, and the deterministic merged-timeline ordering.

The acceptance drill spawns a REAL 3-worker process fleet, rolls a
published checkpoint through it mid-serve, SIGKILLs one worker while
mixed-version requests are in flight, and asserts ``report --trace``
renders ONE reconciled causal chain for a migrated, version-pinned uid
— spans from both engines stitched by trace id, the kill's dead time
classified as a migration stall (never invented into a phase). The
module is ``serial``-marked for its worker subprocesses; shapes are
the shared test fixtures so compiled programs hit the XLA cache.
"""

import json
import os

import jax
import numpy as np
import pytest

from conftest import load_scaled_timeout
from distributed_llm_code_samples_tpu.checkpoint import save_checkpoint
from distributed_llm_code_samples_tpu.decode import (DecodeEngine,
                                                     EngineConfig,
                                                     FleetRouter)
from distributed_llm_code_samples_tpu.decode.supervise import (
    load_snapshot, restore_engine_state, write_snapshot)
from distributed_llm_code_samples_tpu.decode.worker import (
    spawn_fleet_handles)
from distributed_llm_code_samples_tpu.fleetstat import fleetstat_main
from distributed_llm_code_samples_tpu.models import init_lm
from distributed_llm_code_samples_tpu.report import report_main
from distributed_llm_code_samples_tpu.runtime.chaos import (
    FaultPlan, validate_fleet_plan)
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, STATUS_FILENAME, TelemetryWriter, read_metrics,
    validate_record)

pytestmark = pytest.mark.serial

V, D, L, H = 64, 32, 2, 4
BASE = dict(block_size=8, n_blocks=33, max_slots=3, max_blocks_per_seq=6,
            prefill_chunk=8)
MODEL = dict(vocab=V, model_size=D, layers=L, heads=H, kv_heads=None,
             max_seq_len=64, random_seed=0)
NEW_SEED, NEW_STEP = 7, 5


@pytest.fixture(scope="module")
def lm_params():
    return init_lm(jax.random.PRNGKey(0), V, D, L, max_seq_len=64)


@pytest.fixture(scope="module")
def new_params():
    return init_lm(jax.random.PRNGKey(NEW_SEED), V, D, L,
                   max_seq_len=64)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(1)
    return [rng.integers(0, V, size=n).tolist()
            for n in (5, 9, 13, 6, 7, 11)]


def _records(mdir):
    records, problems = read_metrics(os.path.join(mdir,
                                                  METRICS_FILENAME))
    assert not problems, problems
    return records


def _report(capsys, argv, rc=0):
    capsys.readouterr()
    assert report_main(argv) == rc
    return capsys.readouterr().out


def _report_json(capsys, argv):
    return json.loads(_report(capsys, argv + ["--json"]))


# ---------------------------------------------------------------------------
# trace-context propagation (engine-level, cheap)


def test_trace_id_consistent_across_record_kinds(lm_params, tmp_path):
    """One trace id per request, minted at submit and identical on
    every request AND span record the uid ever emits — including
    through a preemption re-admission (the churn must not fork the
    identity)."""
    mdir = str(tmp_path / "m")
    cfg = EngineConfig(block_size=8, n_blocks=5, max_slots=3,
                       max_blocks_per_seq=2, prefill_chunk=8)
    from distributed_llm_code_samples_tpu.decode import ServePolicy
    with TelemetryWriter(mdir, meta={"engine_id": "e0"}) as w:
        eng = DecodeEngine(lm_params, H, cfg, metrics=w,
                           policy=ServePolicy(preempt_after_steps=2))
        for i in range(3):
            eng.submit([1] * 9, 8, uid=i)
        eng.run()
        assert eng.preempted >= 1       # churn actually happened
    by_uid: dict = {}
    for r in _records(mdir):
        if r["kind"] in ("request", "span"):
            ok, reason = validate_record(r)
            assert ok, reason
            if r.get("span") == "engine_step":
                continue        # v18: a step's span, no request's
            assert r["trace_id"], r
            by_uid.setdefault(r["uid"], set()).add(r["trace_id"])
    assert set(by_uid) == {0, 1, 2}
    assert all(len(v) == 1 for v in by_uid.values()), by_uid
    assert len({next(iter(v)) for v in by_uid.values()}) == 3


def test_trace_id_survives_snapshot_resume(lm_params, tmp_path):
    """Snapshot v7 persists the trace id and a crash-resume keeps it:
    the resumed engine's records stitch into the SAME trace (the
    crash gap stays visibly unaccounted; the identity does not
    fork)."""
    snap_dir = str(tmp_path / "snap")
    eng = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    eng.submit([1, 2, 3, 4, 5], 8, uid=0)
    for _ in range(3):
        eng.step()
    want_trace = eng._traces[0]
    write_snapshot(eng, snap_dir)
    snap = load_snapshot(snap_dir)
    assert snap["version"] == 9     # v9 (round 23): + KV-spill set
    [entry] = [r for r in snap["requests"] if r["uid"] == 0]
    assert entry["trace_id"] == want_trace
    fresh = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    restore_engine_state(fresh, snap)
    assert fresh._traces[0] == want_trace
    # and the resumed sequence carries it (the handoff/export path
    # reads it off the _Seq)
    assert fresh.waiting[0].trace_id == want_trace


def test_zero_new_compiles_with_tracing_on(lm_params, prompts,
                                           tmp_path):
    """The overhead discipline: the trace spine is host metadata only
    — an engine serving WITH telemetry (trace ids, spans, status-doc
    inputs) builds exactly the program set of one serving without."""
    def run(metrics):
        eng = DecodeEngine(lm_params, H, EngineConfig(**BASE),
                           metrics=metrics)
        for p in prompts[:3]:
            eng.submit(p, 8)
        out = eng.run()
        return out, eng.compile_count
    plain_out, plain_compiles = run(None)
    with TelemetryWriter(str(tmp_path / "m")) as w:
        traced_out, traced_compiles = run(w)
    assert traced_out == plain_out
    assert traced_compiles == plain_compiles


# ---------------------------------------------------------------------------
# the cross-engine stitch (in-process fleet — cheap), by trace id


def test_report_trace_stitches_kill_migration(lm_params, prompts,
                                              tmp_path, capsys):
    """An in-process 3-engine fleet with a kill: ``report --trace``
    on a migrated uid renders ONE causal chain — spans from source
    AND survivor stitched by trace id, the dead time between them
    classified a migration stall (a router record explains it), and
    the span sum + migration gaps reconciling with the recorded
    latency. An unknown uid rejects rc 2."""
    base = tmp_path

    def mk(eid):
        w = TelemetryWriter(str(base / eid), meta={"engine_id": eid})
        return DecodeEngine(lm_params, H, EngineConfig(**BASE),
                            metrics=w)

    rm = TelemetryWriter(str(base / "router"),
                         meta={"engine_id": "router"})
    fl = FleetRouter(mk, 3, metrics=rm)
    fl.schedule_kill("e1", 4)
    for p in prompts[:4]:
        fl.submit(p, 10)
    fl.run()
    rm.close()
    routers = [r for r in _records(str(base / "router"))
               if r["kind"] == "router"]
    assert all(r["trace_id"] for r in routers), routers
    migs = [r for r in routers if r["event"] == "migrated"
            and r["reason"] == "engine_killed"]
    assert migs, "kill drill migrated nothing"
    uid = migs[0]["uid"]
    dirs = [str(base / x) for x in ("router", "e0", "e1", "e2")]
    doc = _report_json(capsys, dirs + ["--trace", str(uid)])
    tr = doc["trace"]
    assert tr["uid"] == uid and tr["trace_id"] == migs[0]["trace_id"]
    assert tr["completed"] and tr["reconciled"], tr
    assert tr["unreconciled_gap_s"] == 0.0, tr
    assert len(tr["engines"]) >= 2, tr["engines"]
    kinds = [c["type"] for c in tr["chain"]]
    assert "span" in kinds and "move" in kinds
    moves = [c for c in tr["chain"] if c["type"] == "move"]
    assert any(m["event"] == "migrated" for m in moves)
    # the text render names the stitch and the verdict
    text = _report(capsys, dirs + ["--trace", str(uid)])
    assert f"trace {tr['trace_id']}" in text
    assert "reconciled" in text and "MIGRATED" in text
    # rc 2 paths: unknown uid, malformed uid
    capsys.readouterr()
    assert report_main(dirs + ["--trace", "99999"]) == 2
    assert report_main(dirs + ["--trace", "banana"]) == 2


# ---------------------------------------------------------------------------
# deterministic merged-timeline ordering (satellite)


def test_merged_timeline_byte_identical_under_equal_timestamps(
        tmp_path, capsys):
    """Equal timestamps across streams break ties by (stream, record
    order): repeated merges of the same dirs render byte-identical
    timelines."""
    for eid in ("A", "B"):
        with TelemetryWriter(str(tmp_path / eid),
                             meta={"engine_id": eid, "t": 50.0}) as w:
            # identical timestamps across BOTH streams, several
            # entries per timestamp — the tie-break does all the work
            for t in (100.0, 100.0, 200.0):
                w.event({"event": "published", "step": 1, "t": t})
                w.event({"event": "resumed", "step": 2, "t": t})
    dirs = [str(tmp_path / "A"), str(tmp_path / "B")]
    first = _report(capsys, dirs)
    second = _report(capsys, dirs)
    assert first == second
    lines = [ln for ln in first.splitlines() if "[event" in ln]
    assert len(lines) == 12         # nothing dropped by the dedup


# ---------------------------------------------------------------------------
# the live status surface (fleetstat + report --follow)


def test_fleetstat_and_follow_on_drained_fleet(lm_params, prompts,
                                               tmp_path, capsys):
    """The router publishes an atomic status doc; ``fleetstat`` reads
    it rc 0 (text + --json), a missing doc rejects rc 2, and
    ``report --follow`` tails the finished run to its drained status
    and exits rc 0."""
    rm = TelemetryWriter(str(tmp_path / "router"),
                         meta={"engine_id": "router"})
    fl = FleetRouter(lambda eid: DecodeEngine(lm_params, H,
                                              EngineConfig(**BASE)),
                     2, metrics=rm)
    for p in prompts[:3]:
        fl.submit(p, 6)
    fl.run()
    rm.close()
    status_path = os.path.join(str(tmp_path / "router"),
                               STATUS_FILENAME)
    doc = json.load(open(status_path))
    assert doc["drained"] is True and doc["round"] == fl.rounds
    assert doc["tokens_generated"] == 18
    assert doc["counters"]["routed"] == 3
    capsys.readouterr()
    assert fleetstat_main([str(tmp_path / "router")]) == 0
    out = capsys.readouterr().out
    assert "DRAINED" in out and "e0" in out and "e1" in out
    assert fleetstat_main([status_path, "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["drained"] is True
    assert fleetstat_main([str(tmp_path / "nowhere")]) == 2
    # the tail: a finished run drains immediately (rc 0, prints the
    # timeline it caught up on + the drained line)
    capsys.readouterr()
    rc = report_main([str(tmp_path / "router"), "--follow",
                      "--follow_interval", "0.05",
                      "--follow_max_s",
                      str(load_scaled_timeout(20.0))])
    out = capsys.readouterr().out
    assert rc == 0
    assert "fleet drained" in out, out[-500:]


# ---------------------------------------------------------------------------
# THE acceptance drill: process fleet + rolling deploy + SIGKILL


def test_trace_spine_acceptance_drill(lm_params, new_params, prompts,
                                      tmp_path, capsys):
    """3 engine WORKER PROCESSES; a checkpoint publishes and rolls
    through the fleet at round 4 (mixed-version serving); worker e1 is
    SIGKILLed at round 6 with version-pinned requests in flight. The
    merged ``report --trace`` must render the migrated uid's FULL
    causal chain — queued -> prefill -> decode on the dead worker ->
    the migration -> replay -> decode on the survivor -> completion —
    stitched by one trace id across process boundaries, reconciled
    against the recorded latency with the kill's dead time classified
    migration (crash gaps are never invented into phases). The
    transport block and the router's dead-host postmortem render from
    the same streams."""
    ck = str(tmp_path / "ck")
    save_checkpoint(ck, new_params, NEW_STEP)
    plan = FaultPlan.parse("kill_worker@6:1")
    validate_fleet_plan(plan)
    rm = TelemetryWriter(str(tmp_path / "router"),
                         meta={"engine_id": "router"})
    deadline = load_scaled_timeout(120.0)
    handles = spawn_fleet_handles(
        3, 0, str(tmp_path / "spool"), model=MODEL, config=BASE,
        policy={}, metrics_root=str(tmp_path),
        call_deadline_s=deadline, connect_deadline_s=deadline)
    fl = FleetRouter(None, 3, handles=handles, metrics=rm,
                     fleet_chaos=plan)
    try:
        # pre-deploy admissions pin v0; the deploy fires at round 4;
        # the post-deploy admissions pin NEW_STEP — so the round-6
        # kill lands on a genuinely mixed-version fleet
        old_uids = [fl.submit(p, 12) for p in prompts[:4]]
        fl.schedule_deploy(ck, 4)
        for _ in range(5):
            fl.step()
        new_uids = [fl.submit(p, 12) for p in prompts[4:]]
        uids = old_uids + new_uids
        done = fl.run()
    finally:
        fl.close()
        rm.close()
    assert set(done) == set(uids) and not fl.failed()
    st = fl.fleet_stats()
    assert st["deploys"] == 1 and st["kills"] == 1

    routers = [r for r in _records(str(tmp_path / "router"))
               if r["kind"] == "router"]
    migs = [r for r in routers if r["event"] == "migrated"
            and r["reason"] == "engine_killed"]
    assert migs, "the kill migrated nothing — drill shape broke"
    uid = migs[0]["uid"]
    dirs = [str(tmp_path / x) for x in ("router", "e0", "e1", "e2")]
    doc = _report_json(capsys, dirs + ["--trace", str(uid)])
    tr = doc["trace"]
    # one identity across process boundaries, reqs/spans/moves alike
    assert tr["trace_id"] == migs[0]["trace_id"]
    assert tr["completed"] and tr["reconciled"], tr
    assert tr["unreconciled_gap_s"] == 0.0
    assert len(tr["engines"]) >= 2, tr["engines"]
    spans = [c["span"] for c in tr["chain"] if c["type"] == "span"]
    assert "queued" in spans and "prefill" in spans \
        and "decode" in spans, spans
    moves = [c for c in tr["chain"] if c["type"] == "move"]
    assert any(m["event"] == "migrated" for m in moves)
    # mixed-version run: the migrated uid kept its pin, and both
    # versions completed somewhere in the fleet (dedup by uid)
    comp_ver = {}
    for d in dirs:
        for r in _records(d):
            if r.get("kind") == "request" and r["event"] == "completed":
                comp_ver.setdefault(r["uid"], r["weights_version"])
    assert set(comp_ver.values()) == {0, NEW_STEP}, comp_ver
    assert tr["weights_version"] == comp_ver[uid]
    # the transport block folded from the drain-end stats event:
    # per-op percentiles + the overhead share of round wall
    tp = doc["transport"]
    assert tp["round_wall_s"] > 0
    assert 0 <= tp["rpc_overhead_share_of_round_wall"]
    alive_stats = [v for v in tp["engines"].values() if v]
    assert alive_stats
    for stt in alive_stats:
        assert stt["ops"].get("step", {}).get("n", 0) >= 1
        assert "overhead_p50_ms" in stt["ops"]["step"]
    # the router's own dead-host evidence renders under --postmortem
    text = _report(capsys, dirs + ["--postmortem"])
    assert "router postmortem" in text and "e1" in text
    pm = json.load(open(os.path.join(
        str(tmp_path / "router"), "router_postmortem_e1.json")))
    assert pm["engine"] == "e1" and pm["evidence"]["op_log"]
    # the status doc survived the drill and reads drained
    capsys.readouterr()
    assert fleetstat_main([str(tmp_path / "router")]) == 0
    out = capsys.readouterr().out
    assert "DRAINED" in out and "DEAD" in out
