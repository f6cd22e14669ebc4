"""Live weight hot-swap (runtime/weights.py + decode/engine.py +
decode/fleet.py, DESIGN.md section 23): the version ledger over the
trainer's checkpoint dir, double-buffered engine weights with
per-request version pins, the fleet's rolling deploy (drain by the
existing KV handoff, swap, re-admit — zero shed), and the failure
surfaces — a torn checkpoint rejected by the CRC ladder with a named
one-line rollback, a mid-roll failure leaving no engine mixed, a kill
mid-deploy resuming the mixed-version state token-identically.

The identity bar is per PIN: every request must match the
single-engine oracle running ITS pinned version's weights — old pins
against the boot weights, post-deploy admissions against the deployed
checkpoint's — at f32 and int8 (the KV requant history rides the
replay/handoff machinery unchanged).

Model/config shapes are the shared test fixtures (V=64, D=32, L=2,
H=4, BASE blocks) so every compiled program hits the persistent XLA
cache; the deployed version reuses the same shapes with a different
init seed — weights are program OPERANDS, so deploys compile nothing.
"""

import glob
import json
import os

import jax
import numpy as np
import pytest

from distributed_llm_code_samples_tpu.checkpoint import save_checkpoint
from distributed_llm_code_samples_tpu.decode import (DecodeEngine,
                                                     EngineConfig,
                                                     FleetRouter)
from distributed_llm_code_samples_tpu.decode.supervise import (
    load_snapshot, restore_engine_state, snapshot_state, write_snapshot)
from distributed_llm_code_samples_tpu.models import init_lm
from distributed_llm_code_samples_tpu.runtime.chaos import (
    FaultPlan, validate_fleet_plan)
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, TelemetryWriter, read_metrics, validate_record)
from distributed_llm_code_samples_tpu.runtime.weights import (
    BOOT_VERSION, VersionLedger, model_fingerprint)

V, D, L, H = 64, 32, 2, 4
BASE = dict(block_size=8, n_blocks=33, max_slots=3, max_blocks_per_seq=6,
            prefill_chunk=8)
NEW_SEED = 7        # the "trained" weights: same shapes, different init
NEW_STEP = 5        # the checkpoint step (= the deployed version id)


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


@pytest.fixture()
def ledger_dir(tmp_path, new_params):
    """A 'trainer' checkpoint dir: the existing atomic fsync+CRC
    publish IS the deploy input (no serving-side publish path)."""
    ck = str(tmp_path / "ck")
    save_checkpoint(ck, new_params, NEW_STEP)
    return ck


def _oracle(params, uids_prompts, max_new, **cfg_extra):
    """Per-uid single-engine reference on GIVEN weights — the
    pinned-version oracle (one fresh 1-slot engine per request)."""
    outs = {}
    for uid, p in uids_prompts:
        eng = DecodeEngine(params, H,
                           EngineConfig(**{**BASE, "max_slots": 1},
                                        **cfg_extra))
        eng.submit(p, max_new, uid=uid)
        outs[uid] = eng.run()[uid]
    return outs


def _mk(params, **cfg_extra):
    return lambda eid: DecodeEngine(params, H,
                                    EngineConfig(**BASE, **cfg_extra))


# ---------------------------------------------------------------------------
# the version ledger + fingerprint (runtime/weights.py)


def test_ledger_reads_the_checkpoint_ladder(lm_params, new_params,
                                            ledger_dir):
    led = VersionLedger(ledger_dir)
    assert led.latest_step() == NEW_STEP
    assert led.latest_verified() == NEW_STEP
    ok, _ = led.verify(NEW_STEP)
    assert ok
    ok, reason = led.verify(NEW_STEP + 1)
    assert not ok and "not published" in reason
    got = led.load(NEW_STEP, lm_params)
    np.testing.assert_array_equal(np.asarray(got.wte),
                                  np.asarray(new_params.wte))
    fp = led.fingerprint(NEW_STEP, got, H)
    assert fp == model_fingerprint(new_params, H)


def test_fingerprint_is_the_engine_model_meta(lm_params):
    """The dedup satellite: engine/snapshot/handoff all re-bind to the
    ONE runtime/weights.py definition."""
    eng = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    assert eng.model_meta() == model_fingerprint(lm_params, H)
    assert snapshot_state(eng)["model"] == model_fingerprint(lm_params,
                                                             H)


def test_engine_weight_lifecycle_guards(lm_params, new_params):
    eng = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    assert eng.serving_version == BOOT_VERSION
    # serving an unloaded version rejects
    with pytest.raises(ValueError, match="not loaded"):
        eng.set_serving_version(3)
    # architecture mismatch rejects (different layer count)
    other = init_lm(jax.random.PRNGKey(0), V, D, L + 1, max_seq_len=64)
    with pytest.raises(ValueError, match="architecture"):
        eng.load_weights(1, other)
    # a version id is immutable once loaded
    eng.load_weights(1, new_params)
    with pytest.raises(ValueError, match="immutable"):
        eng.load_weights(1, lm_params)
    # idempotent re-load of the identical weights is fine
    eng.load_weights(1, new_params)
    eng.set_serving_version(1)
    # double-buffer retirement: with nothing pinned, loading a third
    # version drops the unpinned non-serving boot weights
    eng.load_weights(2, lm_params)
    assert sorted(eng.weights) == [1, 2]
    # the architecture check survives boot-buffer retirement: a THIRD
    # deploy (version 0 long gone) must still validate and land — the
    # anchor is the stored boot fingerprint, not weights[0]
    eng.set_serving_version(2)
    third = init_lm(jax.random.PRNGKey(11), V, D, L, max_seq_len=64)
    eng.load_weights(3, third)
    eng.set_serving_version(3)
    assert sorted(eng.weights) == [2, 3]
    with pytest.raises(ValueError, match="architecture"):
        eng.load_weights(4, other)      # still rejected, boot retired
    # retiring the boot version rebinds the construction-time alias —
    # the retired buffers must not stay pinned by self.params (the
    # double-buffer memory budget is the point of retirement)
    assert any(eng.params is w for w in eng.weights.values())


def test_handoff_v4_rejects_unheld_version(lm_params, new_params,
                                           prompts):
    """A migrated request decodes on its PINNED version — an importer
    that doesn't hold it must reject before touching any state."""
    src = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    src.load_weights(NEW_STEP, new_params)
    src.set_serving_version(NEW_STEP)
    src.submit(prompts[0], 8, uid=3)
    for _ in range(3):
        src.step()
    doc = src.export_sequence(3)
    assert doc["handoff_version"] == 7      # v7 (round 23): prefix_partial numerics key
    assert doc["weights_version"] == NEW_STEP
    assert doc["model"] == model_fingerprint(new_params, H)
    dst = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    with pytest.raises(ValueError, match="does not hold weights "
                                         "version"):
        dst.import_sequence(doc)
    assert dst.active == 0 and not dst.waiting
    # load the version -> the same doc imports and finishes on it
    dst.load_weights(NEW_STEP, new_params)
    dst.import_sequence(doc)
    want = _oracle(new_params, [(3, prompts[0])], 8)[3]
    assert dst.run()[3] == want


def test_release_request_drains_waiting_and_mid_prefill(lm_params,
                                                        prompts):
    """The replay half of the drain primitive: waiting AND mid-prefill
    requests pop off with their pin and resume token-identically on a
    peer."""
    a = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    for uid in range(3):                # fills every slot (max 3)
        a.submit(prompts[uid], 8, uid=uid)
    a.submit(prompts[3], 8, uid=3)      # queued behind full slots
    a.step()                            # 13-token uid 2 mid-prefill
    assert any(s is not None and not s.prompt_done for s in a.slots)
    assert a.waiting and a.waiting[0].uid == 3
    entries = [a.release_request(2), a.release_request(3)]
    assert entries[0]["weights_version"] == BOOT_VERSION  # admitted
    assert entries[1]["weights_version"] is None    # never admitted
    b = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    for e in entries:
        b.resume_request(e["uid"], e["prompt"], e["max_new"],
                         out=e["out"], retries=e["retries"],
                         t_submit=e["t_submit"],
                         weights_version=e["weights_version"])
    done = b.run()
    want = _oracle(lm_params, [(2, prompts[2]), (3, prompts[3])], 8)
    assert done == want
    with pytest.raises(ValueError, match="not live"):
        a.release_request(2)


def test_prefix_cache_is_version_partitioned(lm_params, new_params):
    """A block prefilled under v0 must never be a hit for a v1
    admission (bytes are a function of the weights): same shared
    prompt before and after a swap, outputs match each version's
    oracle, and the v1 admission re-prefills instead of inheriting v0
    bytes."""
    shared = list(range(1, 17))         # 2 full 8-token blocks
    p_a = shared + [20, 21]
    p_b = shared + [30, 31]
    eng = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    eng.load_weights(1, new_params)
    eng.submit(p_a, 6, uid=0)
    done_first = None
    while any(s is not None for s in eng.slots) or eng.waiting:
        eng.step()
    hits_before = eng.prefix_hit_blocks
    eng.set_serving_version(1)
    eng.submit(p_b, 6, uid=1)
    eng.run()
    # the v1 admission saw a cold tree: no cross-version hit
    assert eng.prefix_hit_blocks == hits_before
    assert eng.finished[0] == _oracle(lm_params, [(0, p_a)], 6)[0]
    assert eng.finished[1] == _oracle(new_params, [(1, p_b)], 6)[1]
    # and a SECOND v1 sharer hits v1's own blocks
    eng.submit(shared + [40, 41], 6, uid=2)
    eng.run()
    assert eng.prefix_hit_blocks > hits_before
    assert eng.cow_copies == 0


def test_prefix_affinity_probe_follows_serving_version(lm_params,
                                                       new_params):
    """The router's warm-block probe reads the SERVING version's root:
    after a swap, retired-version cached blocks must not count as warm
    (a new admission can never hit them) and the new version's must."""
    from distributed_llm_code_samples_tpu.decode import EngineHandle
    shared = list(range(1, 17)) + [20, 21]      # 2 cacheable blocks
    eng = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    hd = EngineHandle("e0", eng, "decode")
    eng.submit(shared, 4, uid=0)
    eng.run()
    assert hd.warm_blocks(shared) == 2          # v0 blocks, serving v0
    eng.load_weights(1, new_params)
    eng.set_serving_version(1)
    assert hd.warm_blocks(shared) == 0          # v0 blocks invisible
    eng.submit(shared, 4, uid=1)
    eng.run()
    assert hd.warm_blocks(shared) == 2          # v1's own blocks warm


# ---------------------------------------------------------------------------
# the rolling deploy (decode/fleet.py)


@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
def test_rolling_deploy_zero_shed_pinned_identity(lm_params, new_params,
                                                  ledger_dir, prompts,
                                                  kv_dtype):
    """The acceptance drill, in-process: checkpoint published
    mid-serve -> the fleet rolls engine by engine via handoff-drain
    with zero shed -> in-flight requests finish token-identical to
    their PINNED-version oracle while new admissions decode on the new
    version."""
    router = FleetRouter(_mk(lm_params, kv_dtype=kv_dtype), 3)
    old_uids = [router.submit(p, 10) for p in prompts[:3]]
    for _ in range(4):
        router.step()
    res = router.rolling_deploy(ledger_dir)
    assert res["status"] == "completed"
    assert res["from_version"] == 0 and res["to_version"] == NEW_STEP
    assert res["drained"] >= 1          # the drain actually moved work
    new_uids = [router.submit(p, 10) for p in prompts[3:]]
    done = router.run()
    st = router.fleet_stats()
    assert st["sheds"] == 0 and not router.failed()
    assert st["deploys"] == 1 and st["deploy_rollbacks"] == 0
    assert all(v["serving_version"] == NEW_STEP
               for v in st["engines"].values())
    want_old = _oracle(lm_params,
                       [(u, prompts[i]) for i, u in
                        enumerate(old_uids)], 10, kv_dtype=kv_dtype)
    want_new = _oracle(new_params,
                       [(u, prompts[3 + i]) for i, u in
                        enumerate(new_uids)], 10, kv_dtype=kv_dtype)
    for u in old_uids:
        assert done[u] == want_old[u], f"old-pin uid {u}"
    for u in new_uids:
        assert done[u] == want_new[u], f"new-version uid {u}"


def test_rolling_deploy_over_wire_transport(lm_params, new_params,
                                            ledger_dir, prompts,
                                            tmp_path):
    """The wire lane (in-process + wire_dir): the deploy's live drain
    moves serialize through the versioned npz wire format — handoff
    doc v4's pin crosses the serialization boundary bit-exactly and
    the drained move records carry transport mode 'wire'."""
    w = TelemetryWriter(str(tmp_path / "router"))
    router = FleetRouter(_mk(lm_params), 2, metrics=w,
                         wire_dir=str(tmp_path / "wire"))
    old_uids = [router.submit(p, 10) for p in prompts[:2]]
    for _ in range(4):
        router.step()
    res = router.rolling_deploy(ledger_dir)
    assert res["status"] == "completed"
    new_uid = router.submit(prompts[4], 10)
    done = router.run()
    w.close()
    st = router.fleet_stats()
    assert st["sheds"] == 0 and not router.failed()
    records, problems = read_metrics(
        os.path.join(str(tmp_path / "router"), METRICS_FILENAME))
    assert not problems, problems
    drains = [r for r in records if r["kind"] == "router"
              and r["event"] == "migrated"
              and r["reason"] == "deploy_drain"]
    wired = [r for r in drains if r["transport"]["mode"] == "wire"]
    assert wired, drains        # >= 1 live move crossed as a wire file
    assert all(r["bytes"] > 0 and r["transport"]["crc_verify_s"] >= 0
               for r in wired)
    want_old = _oracle(lm_params,
                       [(u, prompts[i]) for i, u in
                        enumerate(old_uids)], 10)
    want_new = _oracle(new_params, [(new_uid, prompts[4])], 10)
    for u in old_uids:
        assert done[u] == want_old[u]
    assert done[new_uid] == want_new[new_uid]


def test_rolling_deploy_records_schema_valid(lm_params, ledger_dir,
                                             prompts, tmp_path):
    """One schema-v11 deploy record per lifecycle event on the
    router's stream; request records carry per-version pins; the
    drained moves are real router records with reason deploy_drain."""
    w = TelemetryWriter(str(tmp_path / "router"))
    engines = {}

    def mk(eid):
        engines[eid] = DecodeEngine(
            lm_params, H, EngineConfig(**BASE),
            metrics=TelemetryWriter(str(tmp_path / eid)))
        return engines[eid]

    router = FleetRouter(mk, 2, metrics=w)
    uids = [router.submit(p, 8) for p in prompts[:2]]
    for _ in range(4):
        router.step()
    router.schedule_deploy(ledger_dir, router.rounds + 1)
    new_uid = None
    router.step()                       # arms next round
    router.step()                       # fires the deploy
    new_uid = router.submit(prompts[4], 8)
    router.run()
    w.close()
    for e in engines.values():
        e.metrics.close()
    records, problems = read_metrics(
        os.path.join(str(tmp_path / "router"), METRICS_FILENAME))
    assert not problems, problems
    deps = [r for r in records if r["kind"] == "deploy"]
    assert [d["event"] for d in deps] == (
        ["started"] + ["engine_swapped"] * 2 + ["completed"])
    for d in deps:
        ok, reason = validate_record(d)
        assert ok, reason
        assert d["from_version"] == 0 and d["to_version"] == NEW_STEP
    drains = [r for r in records if r["kind"] == "router"
              and r["event"] == "migrated"
              and r["reason"] == "deploy_drain"]
    assert drains and all(validate_record(r)[0] for r in drains)
    # per-version pins on the engines' request records
    pins = {}
    for eid in engines:
        recs, probs = read_metrics(
            os.path.join(str(tmp_path / eid), METRICS_FILENAME))
        assert not probs, probs
        for r in recs:
            if r["kind"] == "request" and r["event"] == "completed":
                pins.setdefault(r["uid"], set()).add(
                    r["weights_version"])
    for u in uids:
        assert pins[u] == {0}, (u, pins)
    assert pins[new_uid] == {NEW_STEP}


def test_corrupt_deploy_rolls_back_with_named_reason(lm_params,
                                                     new_params,
                                                     prompts, tmp_path,
                                                     capsys):
    """chaos ``corrupt_deploy@R``: the torn target step is rejected by
    the CRC ladder, the rolled_back record names the reason in ONE
    line plus the latest_verified_step fallback, the deploy aborts
    with every engine still on the old version, and every request
    completes on it — nothing shed, nothing mixed."""
    ck = str(tmp_path / "ck")
    save_checkpoint(ck, lm_params, 2)       # the verified fallback
    save_checkpoint(ck, new_params, NEW_STEP)
    plan = FaultPlan.parse("corrupt_deploy@3")
    validate_fleet_plan(plan)
    w = TelemetryWriter(str(tmp_path / "router"))
    router = FleetRouter(_mk(lm_params), 3, metrics=w,
                         fleet_chaos=plan)
    router.schedule_deploy(ck, 3)
    uids = [router.submit(p, 8) for p in prompts[:3]]
    done = router.run()
    w.close()
    st = router.fleet_stats()
    assert st["deploys"] == 0 and st["deploy_rollbacks"] == 1
    assert st["sheds"] == 0 and not router.failed()
    assert all(v["serving_version"] == 0
               for v in st["engines"].values())
    records, problems = read_metrics(
        os.path.join(str(tmp_path / "router"), METRICS_FILENAME))
    assert not problems, problems
    [rb] = [r for r in records if r["kind"] == "deploy"]
    assert rb["event"] == "rolled_back"
    ok, reason = validate_record(rb)
    assert ok, reason
    assert "\n" not in rb["reason"]
    assert "checksum mismatch" in rb["reason"]
    assert "latest verified step: 2" in rb["reason"]
    assert rb["latest_verified"] == 2
    assert plan.faults[0].fired
    want = _oracle(lm_params,
                   [(u, prompts[i]) for i, u in enumerate(uids)], 8)
    assert {u: done[u] for u in uids} == want


def test_mid_roll_failure_leaves_no_engine_mixed(lm_params, ledger_dir,
                                                 prompts):
    """A load failure on engine K of N rolls engines 1..K-1 BACK to
    the old serving version (their old weights never left — the
    double buffer) — no engine admits on the refused version and the
    run completes on the old weights."""
    router = FleetRouter(_mk(lm_params), 3)
    uids = [router.submit(p, 8) for p in prompts[:3]]
    for _ in range(3):
        router.step()
    victim = router.handles[1]
    real = victim.load_weights

    def boom(version, ckpt_dir, step, params=None):
        raise RuntimeError("injected mid-roll load failure")

    victim.load_weights = boom
    res = router.rolling_deploy(ledger_dir)
    victim.load_weights = real
    assert res["status"] == "rolled_back"
    assert "injected mid-roll load failure" in res["reason"]
    assert "1 swapped engine(s) rolled back" in res["reason"]
    st = router.fleet_stats()
    assert all(v["serving_version"] == 0
               for v in st["engines"].values())
    done = router.run()
    assert st["sheds"] == 0 and not router.failed()
    want = _oracle(lm_params,
                   [(u, prompts[i]) for i, u in enumerate(uids)], 8)
    assert {u: done[u] for u in uids} == want


def test_kill_mid_deploy_resumes_mixed_version_state(lm_params,
                                                     new_params,
                                                     ledger_dir,
                                                     prompts):
    """kill an engine AFTER the deploy while the fleet is mixed-
    version: the dead engine's snapshot (v6 — per-request pins)
    migrates to survivors and EVERY request still matches its
    pinned-version oracle."""
    router = FleetRouter(_mk(lm_params), 3)
    old_uids = [router.submit(p, 12) for p in prompts[:3]]
    for _ in range(3):
        router.step()
    router.schedule_deploy(ledger_dir, 3)
    router.schedule_kill("e1", 5)       # mixed-version kill
    router.step()                       # round 3: the deploy fires
    new_uids = [router.submit(p, 12) for p in prompts[3:]]
    done = router.run()
    st = router.fleet_stats()
    assert st["kills"] == 1 and st["deploys"] == 1
    assert st["sheds"] == 0 and not router.failed()
    want_old = _oracle(lm_params,
                       [(u, prompts[i]) for i, u in
                        enumerate(old_uids)], 12)
    want_new = _oracle(new_params,
                       [(u, prompts[3 + i]) for i, u in
                        enumerate(new_uids)], 12)
    for u in old_uids:
        assert done[u] == want_old[u], f"old-pin uid {u}"
    for u in new_uids:
        assert done[u] == want_new[u], f"new-version uid {u}"


def test_snapshot_v6_pin_travel_and_version_guard(lm_params, new_params,
                                                  prompts, tmp_path):
    """Snapshot v6 carries serving_version + per-version fingerprints
    + per-request pins; restore onto an engine missing a pinned
    version rejects with the load_weights hint, and restore onto one
    holding it resumes token-identically per pin."""
    eng = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    eng.load_weights(NEW_STEP, new_params)
    eng.submit(prompts[0], 8, uid=0)            # pins v0 at admission
    eng.step()
    eng.set_serving_version(NEW_STEP)
    eng.submit(prompts[1], 8, uid=1)            # pins v5 at admission
    eng.step()
    sd = str(tmp_path / "snap")
    write_snapshot(eng, sd)
    snap = load_snapshot(sd)
    assert snap["serving_version"] == NEW_STEP
    assert set(snap["weights_versions"]) == {"0", str(NEW_STEP)}
    pins = {r["uid"]: r["weights_version"] for r in snap["requests"]}
    assert pins == {0: 0, 1: NEW_STEP}
    bare = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    with pytest.raises(ValueError, match="does not hold weights "
                                         "version"):
        restore_engine_state(bare, snap)
    fresh = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    fresh.load_weights(NEW_STEP, new_params)
    restore_engine_state(fresh, snap)
    assert fresh.serving_version == NEW_STEP
    done = fresh.run()
    assert done[0] == _oracle(lm_params, [(0, prompts[0])], 8)[0]
    assert done[1] == _oracle(new_params, [(1, prompts[1])], 8)[1]


# ---------------------------------------------------------------------------
# bounded wire-spool retention (satellite)


def test_wire_spool_retention_is_bounded(lm_params, prompts, tmp_path):
    """A corrupt_wire rejection loop must not grow the spool without
    bound: rejected docs are renamed *.rejected and pruned to
    keep_rejected, oldest first."""
    router = FleetRouter(_mk(lm_params), 2, prefill_engines=1,
                         wire_dir=str(tmp_path / "wire"),
                         keep_rejected=2)
    uids = [router.submit(p, 6) for p in prompts[:5]]
    rounds = 0
    while router.has_work and rounds < 200:
        router._corrupt_next_wire = True    # tear EVERY wire handoff
        router.step()
        rounds += 1
    done = router.results()
    assert router.wire_rejects >= 4
    assert not router.failed() and set(done) == set(uids)
    spool = str(tmp_path / "wire")
    assert not glob.glob(os.path.join(spool, "*.npz"))   # none live
    rejected = glob.glob(os.path.join(spool, "*.rejected"))
    assert 0 < len(rejected) <= 2, rejected
    # token identity survives every rejection (replay-rerouted)
    want = _oracle(lm_params,
                   [(u, prompts[i]) for i, u in enumerate(uids)], 6)
    assert done == want


def test_keep_rejected_validation(lm_params):
    with pytest.raises(ValueError, match="keep_rejected"):
        FleetRouter(_mk(lm_params), 2, keep_rejected=-1)


# ---------------------------------------------------------------------------
# mixed-version reporting (satellite)


def test_merged_report_per_version_completions_no_double_count(
        lm_params, new_params, ledger_dir, prompts, tmp_path, capsys):
    """The merged report over a mid-deploy fleet shows per-version
    completion counts and never double-counts a migrated-then-
    completed uid across versions (the PR 10 dedup-by-uid
    discipline)."""
    from distributed_llm_code_samples_tpu.report import report_main
    dirs = {}

    def mk(eid):
        dirs[eid] = str(tmp_path / eid)
        return DecodeEngine(lm_params, H, EngineConfig(**BASE),
                            metrics=TelemetryWriter(dirs[eid]))

    w = TelemetryWriter(str(tmp_path / "router"))
    router = FleetRouter(mk, 2, metrics=w)
    old_uids = [router.submit(p, 10) for p in prompts[:2]]
    for _ in range(4):
        router.step()
    res = router.rolling_deploy(ledger_dir)    # drains = migrations
    assert res["status"] == "completed" and res["drained"] >= 1
    new_uid = router.submit(prompts[4], 10)
    router.run()
    w.close()
    for h in router.handles:
        h.engine.metrics.close()
    out = str(tmp_path / "report.json")
    rc = report_main([str(tmp_path / "router"), dirs["e0"], dirs["e1"],
                      "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    fl = doc["fleet"]
    assert fl["deploys"] == 1
    # dedup by uid: 3 requests, 3 completions — a drained uid that
    # completed on its target engine counts ONCE, under ONE version
    assert fl["completed"] == 3
    assert fl["completed_by_version"] == {"v0": 2,
                                          f"v{NEW_STEP}": 1}
    assert sum(fl["completed_by_version"].values()) == fl["completed"]
    # the deploy renders on the merged timeline
    whats = [t["what"] for t in doc["timeline"]
             if t["source"] == "deploy"]
    assert any("DEPLOY STARTED v0 -> v5" in x for x in whats)
    assert any("DEPLOY COMPLETED" in x for x in whats)


# ---------------------------------------------------------------------------
# CLI flag surface (parse-rejection discipline)


def _gen(args):
    from distributed_llm_code_samples_tpu.decode.generate_cli import (
        generate_main)
    return generate_main(args)


GEN_BASE = ["--prompt_lens", "3", "--max_new", "2", "-d", "32", "-l",
            "2", "--heads", "4", "--vocab", "64", "--max_seq_len",
            "64", "--block_size", "8", "--prefill_chunk", "4"]


@pytest.mark.parametrize("extra", [
    ["--deploy_dir", "/tmp/nope"],                      # no --fleet
    ["--deploy_round", "3"],                            # no --fleet
    ["--fleet", "2", "--deploy_dir", "/tmp/nope"],      # no round
    ["--fleet", "2", "--deploy_round", "3"],            # no dir
    ["--fleet", "2", "--deploy_step", "4"],             # no dir
    ["--fleet", "2", "--deploy_dir", "/tmp/nope",
     "--deploy_round", "-1"],
    ["--weights_step", "3"],                            # no dir
    ["--fleet", "2", "--weights_from", "/tmp/nope"],    # fleet combo
    ["--weights_from", "/tmp/definitely_missing_ck"],   # no checkpoint
    # corrupt_deploy without a scheduled deploy can never fire
    ["--fleet", "2", "--fleet_chaos", "corrupt_deploy@3"],
    # bad truncation fraction
    ["--fleet", "2", "--deploy_dir", "/tmp/nope", "--deploy_round",
     "3", "--fleet_chaos", "corrupt_deploy@3:1.5"],
])
def test_cli_deploy_flag_rejections(extra):
    assert _gen(GEN_BASE + extra) == 2


def test_generate_cli_rolls_a_trainer_checkpoint(tmp_path, capsys):
    """The trainer is the publisher: `-m 11 --checkpoint_dir` leaves the
    ladder, `generate --weights_from` serves its newest step, and
    `generate --fleet 3 --deploy_dir --deploy_round 4` rolls it engine
    by engine mid-serve with nothing shed: every request equals one of
    the two pinned-version single-engine runs, both versions serve some,
    and the router stream holds the deploy's records in order."""
    import distributed_llm_code_samples_tpu.cli as cli
    ck = str(tmp_path / "ck")
    assert cli.main(["-m", "11", "-s", "4", "-bs", "2", "-n", "64", "-d",
                     "32", "-l", "2", "--heads", "4", "--vocab", "64",
                     "--checkpoint_dir", ck, "--checkpoint_every",
                     "2"]) == 0
    ladder = os.path.join(ck, "train_lm_tp")
    serve = ["generate", "--prompt_lens", "3,7,5,6,4,9", "--max_new", "8",
             "-d", "32", "-l", "2", "--heads", "4", "--vocab", "64",
             "--max_seq_len", "64", "--block_size", "8",
             "--prefill_chunk", "4", "--max_slots", "1", "--log_every",
             "2"]

    def run(extra):
        capsys.readouterr()
        assert cli.main(serve + extra) == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    def tokens(payload):
        return {s["uid"]: s["tokens"] for s in payload["sequences"]}

    old, new = tokens(run([])), tokens(run(["--weights_from", ladder]))
    mdir = str(tmp_path / "m")
    rolled = run(["--fleet", "3", "--deploy_dir", ladder,
                  "--deploy_round", "4", "--metrics_dir", mdir])
    assert not rolled["failed"] and rolled["shed"] == 0
    st = rolled["fleet"]
    assert st["deploys"] == 1 and st["deploy_rollbacks"] == 0
    assert {e["serving_version"] for e in st["engines"].values()} == {4}
    got = tokens(rolled)
    on_old = {u for u in got if got[u] == old[u]}
    on_new = {u for u in got if got[u] == new[u]}
    assert on_old and on_new and on_old | on_new == set(got) == set(old)
    recs, problems = read_metrics(
        os.path.join(mdir, "router", METRICS_FILENAME))
    assert not problems, problems
    deps = [r for r in recs if r["kind"] == "deploy"]
    assert [d["event"] for d in deps] == (
        ["started"] + ["engine_swapped"] * 3 + ["completed"])
    assert all(validate_record(d)[0] and d["from_version"] == 0
               and d["to_version"] == 4 for d in deps)
