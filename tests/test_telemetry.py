"""Unified run telemetry: schema contract, non-blocking writer,
named-scope presence per strategy, chunked-driving dispatch count, the
StepReport static fold, and the chaos-run report timeline.

The schema-contract stance mirrors the repo's artifact contracts
(tests/test_bench_contract.py): the JSONL stream is a persistent
artifact other tooling parses, so its key set is pinned — changing it
without bumping ``SCHEMA_VERSION`` fails here by design.
"""

import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, SCHEMA_VERSION, STEP_KEYS, StepReport,
    TelemetryWriter, ffn_model_flops, hand_flops_per_step, peak_flops,
    read_metrics, validate_record)


# ---------------------------------------------------------------------------
# schema contract


# The pinned (version, key-set) tuples. If you change STEP_KEYS or the
# anomaly/rollback/decode/request/span required sets you MUST bump
# SCHEMA_VERSION and update these pins in the same commit — that is the
# version-bump discipline this test enforces. v2 (round 8): the
# self-healing kinds — "anomaly" (in-graph guardrail counters) and
# "rollback" (ladder rungs). v3 (round 9): the serving kind — "decode"
# (engine cadence records: throughput, batch occupancy, KV-pool
# utilization; decode/engine.py). v4 (round 10): the serving-
# reliability kind — "request" (one record per request lifecycle
# transition: admitted/preempted/retried/quarantined/completed/
# rejected/expired; decode/engine.py). v5 (round 11): the "span" kind
# (per-request lifecycle phases, runtime/tracing.py) + the decode
# contract's KV-pool internals (watermarks, churn, fragmentation,
# stored bytes). v6 (round 12): the decode contract's speculative-
# decoding trio (drafted_tokens / accepted_tokens / accept_rate —
# decode/engine.py verify dispatches). v7 (round 13): the decode
# contract's shared-prefix set (prefix_hit_blocks /
# prefill_tokens_saved / shared_blocks / cow_copies — the radix
# prefix cache, decode/prefix.py). v8 (round 14): the "router" kind
# (one record per fleet-router decision: routed/handoff/migrated/shed
# with source/target engine ids — decode/fleet.py). v9 (round 15): the
# serving-SLO layer — completed "request" records conditionally pin
# latency_s + ttft_s, the "router" contract pins the placement
# "policy", and the "fleet" kind (one per-round fleet health record —
# per-engine waiting/active/free-blocks/utilization + load imbalance,
# decode/fleet.py) lands with FLEET_REQUIRED. v10 (round 16): the
# process-boundary transport — handoff/migrated router records
# conditionally pin blocks/bytes/duration_s + the ``transport``
# attribution ({mode, bytes, crc_verify_s, retries}; bytes = the
# SERIALIZED wire size), and the ``wire_rejected`` router event lands
# (a CRC/torn/version-rejected handoff doc, runtime/wire.py).
# v11 (round 17): the live-weight hot-swap layer — every "request"
# record pins ``weights_version`` (the uid's version pin, null before
# first admission) and the "deploy" kind lands (rolling-deploy
# lifecycle: started/engine_swapped/completed/rolled_back with the
# from/to version pair; engine_swapped conditionally pins ``engine``,
# completed/rolled_back pin ``duration_s``, rolled_back pins the
# one-line ``reason`` — decode/fleet.py rolling_deploy).
# v12 (round 18): the fleet trace spine — every per-request kind
# ("request", "span", "router") pins ``trace_id`` (the fleet-unique
# causal identity minted once at admission and carried through
# replay/migration/crash-resume; null only on the anonymous rejected
# uid -1), and "deploy" pins the key too (uniform envelope, value
# always null — a deploy event concerns the fleet, not one request).
# v13 (round 19): the trace-driven workload plane — "request" and
# "span" records pin ``tenant`` (the request's tenant tag, null
# single-tenant, carried like trace_id through replay/migration/
# crash-resume), and the "workload" kind lands (one record per
# trace-replay interval from decode/workload_driver.py: the trace
# identity, per-interval offered/admitted, cumulative per-tenant
# offered/completed/shed counts) with WORKLOAD_REQUIRED.
# v14 (round 20): the control plane — the "autoscale" kind (one record
# per decode-tier scale decision from decode/autoscale.py: scale_up /
# scale_down / held with the named trigger, alive count, and target;
# scale_up conditionally pins the spawned ``engine``, scale_down pins
# ``engine`` + ``drained``) and the "qos" kind (one record per tenant
# scheduling decision from decode/engine.py: predicted_miss_shed /
# budget_deferred / wfq_pick, each pinning exactly the numbers that
# justified it).
# v15 (round 21): the watchtower — the "alert" kind (one record per
# detector lifecycle transition from runtime/watch.py: fired /
# resolved on the router's round clock, with the detector name,
# severity class, and the folded [start, end] round window; each
# detector conditionally pins exactly the numbers that justified the
# transition, on BOTH fired and resolved records).
# v16 (round 22): the multi-host transport — the router-event
# vocabulary gains ``reconnected`` (a dropped worker connection healed
# under the reconnect ladder instead of becoming a dead-host
# declaration), ``transport.mode`` gains ``tcp``, and every
# ``migrated`` record conditionally pins the async-migration pair
# (``ship_s`` = the overlapped ship window, null when nothing
# overlapped; ``catchup_tokens`` = tokens the target teacher-forced
# to catch up) with ROUTER_MIGRATED_REQUIRED.
# v17 (round 23): the KV memory hierarchy — decode records pin the
# ``kv_spill`` key family (spilled_blocks / spill_bytes / restores /
# restore_tokens_saved cumulative and snapshot-persisted;
# restore_stall_s the cumulative implant-path wall clock;
# partial_hits cumulative sub-block CoW shares;
# host_tier_utilization the instantaneous spill-tier occupancy,
# 0.0 when the tier is off — zeros pinned even when disabled).
# v18 (PR 25): step phases — the span vocabulary gains ``engine_step``
# (one record per executed engine step: null uid, refused under any
# other span name; pins ``phases`` + ``start_ns`` / ``end_ns``,
# STEP_SPAN_REQUIRED).
# v19 (PR 37): every dispatch says which program it ran — the
# ``engine_step`` record pins ``dispatches`` (``[kind, bucket]`` a step
# program launched, one entry a ``*.dispatch`` phase, in order).
# v20 (PR 38): a step's results are read one step late — the
# ``engine_step`` record pins ``readbacks`` (the ordinal of the launch
# each ``*.readback`` phase read, one entry a phase, in order; it may
# be an earlier record's launch) and ``launches`` (the engine's
# launches up to and with the step's own).
# v21 (PR 39): a third paged kind — the ``engine_step`` record may
# carry the cache reads' four counters (``STEP_SPAN_WINDOW``), all or
# none.
# v22 (PR 40): the decode-side read walks each row's live blocks — the
# ``engine_step`` record may carry the blocks the launched rows' reads
# fetched beside their tables' capacity (``STEP_SPAN_KV``), both or
# none.
# v23 (PR 41): a fourth paged kind, chunk-summarised attention — the
# ``engine_step`` record may carry the summaries its launched rows
# attend over and those they wrote (``STEP_SPAN_CHUNKS``), both or none.
# v24 (PR 44): a window layer's ring is walked too — the ``engine_step``
# record may carry the ring's blocks fetched beside the rings' entries
# (``STEP_SPAN_RING``), both or none.
# v25 (PR 52): a recurrent layer's row has two widths — the
# ``engine_step`` record may carry what ONE sequence holds in ONE
# recurrent layer, its state and its convolution's tail
# (``STEP_SPAN_STATE_ROW``), both or none.
_PINNED_VERSION = 25
_PINNED_STEP_SPAN_RING = frozenset({"ring_blocks_read",
                                    "ring_blocks_capacity"})
_PINNED_STEP_SPAN_CHUNKS = frozenset({"summary_rows", "summaries_written"})
_PINNED_STEP_SPAN_KV = frozenset({"kv_blocks_read", "kv_blocks_capacity"})
_PINNED_STEP_SPAN_WINDOW = frozenset({
    "window_rows", "full_rows", "window_blocks_released",
    "window_blocks_live"})
_PINNED_STEP_SPAN_REQUIRED = frozenset({"phases", "start_ns", "end_ns",
                                        "dispatches", "readbacks",
                                        "launches"})
_PINNED_STEP_KEYS = frozenset({
    "schema", "kind", "t", "step", "strategy", "loss", "grad_norm",
    "tokens_per_sec", "step_time_s", "mfu", "hbm_high_water_bytes",
})
_PINNED_ANOMALY_REQUIRED = frozenset({"step", "skipped", "loss_scale"})
_PINNED_ROLLBACK_REQUIRED = frozenset({"rung", "resume_step"})
_PINNED_DECODE_REQUIRED = frozenset({
    "step", "tokens_per_sec", "batch_occupancy", "kv_pool_utilization",
    "free_blocks", "free_blocks_low_water", "free_blocks_high_water",
    "block_allocs", "block_frees", "block_scrubs", "kv_fragmentation",
    "kv_bytes_stored", "drafted_tokens", "accepted_tokens",
    "accept_rate", "prefix_hit_blocks", "prefill_tokens_saved",
    "shared_blocks", "cow_copies", "spilled_blocks", "spill_bytes",
    "restores", "restore_tokens_saved", "restore_stall_s",
    "partial_hits", "host_tier_utilization",
})
_PINNED_REQUEST_REQUIRED = frozenset({
    "step", "uid", "event", "reason", "weights_version", "trace_id",
    "tenant",
})
_PINNED_SPAN_REQUIRED = frozenset({
    "step", "uid", "span", "start_step", "duration_s", "trace_id",
    "tenant",
})
_PINNED_ROUTER_REQUIRED = frozenset({
    "step", "uid", "event", "source", "target", "policy", "trace_id",
})
_PINNED_REQUEST_COMPLETED_REQUIRED = frozenset({"latency_s", "ttft_s"})
_PINNED_FLEET_REQUIRED = frozenset({"step", "engines",
                                    "load_imbalance"})
_PINNED_ROUTER_MOVE_REQUIRED = frozenset({"blocks", "bytes",
                                          "duration_s", "transport"})
_PINNED_ROUTER_MIGRATED_REQUIRED = frozenset({"ship_s",
                                              "catchup_tokens"})
_PINNED_DEPLOY_REQUIRED = frozenset({
    "step", "event", "from_version", "to_version", "trace_id",
})
_PINNED_WORKLOAD_REQUIRED = frozenset({
    "step", "trace", "offered", "admitted", "tenants",
})
_PINNED_DEPLOY_EVENT_REQUIRED = {
    "engine_swapped": frozenset({"engine"}),
    "completed": frozenset({"duration_s"}),
    "rolled_back": frozenset({"duration_s", "reason"}),
}
_PINNED_AUTOSCALE_REQUIRED = frozenset({
    "step", "event", "reason", "engines", "target_engines",
})
_PINNED_AUTOSCALE_EVENT_REQUIRED = {
    "scale_up": frozenset({"engine"}),
    "scale_down": frozenset({"engine", "drained"}),
}
_PINNED_QOS_REQUIRED = frozenset({"step", "event", "tenant"})
_PINNED_QOS_EVENT_REQUIRED = {
    "predicted_miss_shed": frozenset({"uid", "eta_steps",
                                      "deadline_steps"}),
    "budget_deferred": frozenset({"uid", "resident_tokens",
                                  "token_budget"}),
    "wfq_pick": frozenset({"uid", "virtual_time"}),
}
_PINNED_ALERT_REQUIRED = frozenset({
    "step", "event", "detector", "severity", "window",
})
_PINNED_ALERT_DETECTOR_REQUIRED = {
    "burn_rate": frozenset({"burn_fast", "burn_slow", "violations",
                            "completions"}),
    "queue_growth": frozenset({"waiting", "threshold"}),
    "imbalance": frozenset({"imbalance", "threshold"}),
    "collapse": frozenset({"stalled_rounds", "live"}),
    "incident_rate": frozenset({"incidents", "threshold"}),
    "latency_drift": frozenset({"p95_s", "baseline_s", "metric"}),
}


def test_schema_version_bump_discipline():
    from distributed_llm_code_samples_tpu.runtime.telemetry import (
        ALERT_DETECTOR_REQUIRED, ALERT_REQUIRED, ANOMALY_REQUIRED,
        AUTOSCALE_EVENT_REQUIRED, AUTOSCALE_REQUIRED, DECODE_REQUIRED,
        DEPLOY_EVENT_REQUIRED, DEPLOY_REQUIRED, FLEET_REQUIRED,
        QOS_EVENT_REQUIRED, QOS_REQUIRED, RECORD_KINDS,
        REQUEST_COMPLETED_REQUIRED, REQUEST_REQUIRED, REQUIRED_KEYS,
        ROLLBACK_REQUIRED, ROUTER_EVENTS, ROUTER_MIGRATED_REQUIRED,
        ROUTER_MOVE_REQUIRED, ROUTER_REQUIRED, SPAN_REQUIRED,
        STEP_SPAN_REQUIRED, WORKLOAD_REQUIRED)
    assert SCHEMA_VERSION == _PINNED_VERSION and \
        frozenset(STEP_SPAN_REQUIRED) == _PINNED_STEP_SPAN_REQUIRED and \
        frozenset(STEP_KEYS) == _PINNED_STEP_KEYS and \
        frozenset(ANOMALY_REQUIRED) == _PINNED_ANOMALY_REQUIRED and \
        frozenset(ROLLBACK_REQUIRED) == _PINNED_ROLLBACK_REQUIRED and \
        frozenset(DECODE_REQUIRED) == _PINNED_DECODE_REQUIRED and \
        frozenset(REQUEST_REQUIRED) == _PINNED_REQUEST_REQUIRED and \
        frozenset(REQUEST_COMPLETED_REQUIRED) == \
        _PINNED_REQUEST_COMPLETED_REQUIRED and \
        frozenset(SPAN_REQUIRED) == _PINNED_SPAN_REQUIRED and \
        frozenset(ROUTER_REQUIRED) == _PINNED_ROUTER_REQUIRED and \
        frozenset(ROUTER_MOVE_REQUIRED) == \
        _PINNED_ROUTER_MOVE_REQUIRED and \
        frozenset(ROUTER_MIGRATED_REQUIRED) == \
        _PINNED_ROUTER_MIGRATED_REQUIRED and \
        "reconnected" in ROUTER_EVENTS and \
        frozenset(FLEET_REQUIRED) == _PINNED_FLEET_REQUIRED and \
        frozenset(DEPLOY_REQUIRED) == _PINNED_DEPLOY_REQUIRED and \
        frozenset(WORKLOAD_REQUIRED) == _PINNED_WORKLOAD_REQUIRED and \
        {k: frozenset(v) for k, v in DEPLOY_EVENT_REQUIRED.items()} \
        == _PINNED_DEPLOY_EVENT_REQUIRED and \
        frozenset(AUTOSCALE_REQUIRED) == _PINNED_AUTOSCALE_REQUIRED and \
        {k: frozenset(v) for k, v in AUTOSCALE_EVENT_REQUIRED.items()} \
        == _PINNED_AUTOSCALE_EVENT_REQUIRED and \
        frozenset(QOS_REQUIRED) == _PINNED_QOS_REQUIRED and \
        {k: frozenset(v) for k, v in QOS_EVENT_REQUIRED.items()} \
        == _PINNED_QOS_EVENT_REQUIRED and \
        frozenset(ALERT_REQUIRED) == _PINNED_ALERT_REQUIRED and \
        {k: frozenset(v) for k, v in ALERT_DETECTOR_REQUIRED.items()} \
        == _PINNED_ALERT_DETECTOR_REQUIRED, (
            "telemetry record schema changed: bump SCHEMA_VERSION "
            "and update the pinned sets here in the same commit")
    assert "anomaly" in RECORD_KINDS and "rollback" in RECORD_KINDS
    assert "request" in RECORD_KINDS
    assert "decode" in RECORD_KINDS
    assert "span" in RECORD_KINDS
    assert "router" in RECORD_KINDS
    assert "fleet" in RECORD_KINDS
    assert "deploy" in RECORD_KINDS
    assert "workload" in RECORD_KINDS
    assert "autoscale" in RECORD_KINDS
    assert "qos" in RECORD_KINDS
    assert "alert" in RECORD_KINDS
    # every contract-carrying kind routes through the one table
    # validate_record reads (a new kind that skips it validates
    # envelope-only silently — this catches the drift)
    for kind in ("step", "anomaly", "rollback", "decode", "request",
                 "span", "router", "fleet", "deploy", "workload",
                 "autoscale", "qos", "alert"):
        assert kind in REQUIRED_KEYS, kind


def test_step_record_round_trip(tmp_path):
    """A step record written through the writer parses back with exactly
    the contract keys, the version stamp, and the values (device scalars
    included — the writer thread does the readback)."""
    w = TelemetryWriter(str(tmp_path))
    w.step(3, loss=jax.numpy.float32(1.5), grad_norm=np.float64(0.25),
           step_time_s=0.1, tokens=1000, model_flops=2e9, peak=1e12)
    w.close()
    records, problems = read_metrics(os.path.join(str(tmp_path),
                                                  METRICS_FILENAME))
    assert problems == []
    [rec] = records
    assert set(rec) == set(STEP_KEYS)
    assert rec["schema"] == SCHEMA_VERSION
    assert rec["step"] == 3
    assert rec["loss"] == pytest.approx(1.5)
    assert rec["grad_norm"] == pytest.approx(0.25)
    assert rec["tokens_per_sec"] == pytest.approx(10000.0)
    assert rec["mfu"] == pytest.approx(2e9 / 0.1 / 1e12, rel=1e-3)


def test_validate_record_rejects_drift():
    ok, _ = validate_record({"schema": SCHEMA_VERSION, "kind": "step",
                             "t": 0.0, "step": 1})
    assert not ok  # missing contract keys
    ok, reason = validate_record({"schema": SCHEMA_VERSION + 1,
                                  "kind": "event", "t": 0.0})
    assert not ok and "version" in reason
    ok, _ = validate_record({"schema": SCHEMA_VERSION, "kind": "bogus",
                             "t": 0.0})
    assert not ok
    ok, _ = validate_record({"schema": SCHEMA_VERSION, "kind": "event",
                             "t": 0.0, "event": "published"})
    assert ok


def test_anomaly_and_rollback_records_round_trip(tmp_path):
    """The schema-v2 self-healing kinds: writer methods stamp the kind
    + envelope, records validate, and missing contract keys reject."""
    from distributed_llm_code_samples_tpu.runtime.telemetry import (
        TelemetryWriter)
    w = TelemetryWriter(str(tmp_path))
    w.anomaly({"step": 4, "strategy": "train_ddp", "steps": [1, 4],
               "skipped": 1, "total_skipped": 1, "overflows": 0,
               "loss_scale": 32768.0})
    w.rollback({"rung": "rollback", "rollback": 1, "resume_step": 2,
                "error": "LossSpikeError: ..."})
    w.close()
    records, problems = read_metrics(os.path.join(str(tmp_path),
                                                  METRICS_FILENAME))
    assert problems == []
    anom, roll = records
    assert anom["kind"] == "anomaly" and anom["schema"] == SCHEMA_VERSION
    assert anom["skipped"] == 1 and anom["loss_scale"] == 32768.0
    assert roll["kind"] == "rollback" and roll["resume_step"] == 2
    # contract: required keys reject when missing
    ok, reason = validate_record({"schema": SCHEMA_VERSION,
                                  "kind": "anomaly", "t": 0.0,
                                  "step": 4})
    assert not ok and "skipped" in reason
    ok, reason = validate_record({"schema": SCHEMA_VERSION,
                                  "kind": "rollback", "t": 0.0})
    assert not ok and "rung" in reason


def _engine_step(**over):
    """A v20 ``engine_step`` record as ``engine._step_record`` builds
    it: a tail chunk's program, then the batch's; the batch's launch
    (the engine's 12th) first reads the LAST step's (its 10th), then
    the chunk's, and its own result waits for the next step."""
    rec = {"uid": None, "span": "engine_step", "start_step": 7, "step": 7,
           "start_ns": 1_000, "end_ns": 9_000, "t": 9e-6,
           "duration_s": 8e-6, "tokens_generated": 40, "state_bytes": 0,
           "expert_rows": 0, "experts_touched": 0, "expert_rows_max": 0,
           "phases": [["admit", 1_000, 1_100],
                      ["prefill.dispatch", 1_200, 1_300],
                      ["decode.readback", 1_300, 4_000],
                      ["decode.dispatch", 4_200, 4_300],
                      ["prefill.readback", 4_300, 8_000]],
           "dispatches": [["prefill", 4], ["decode", 8]],
           "readbacks": [9, 10], "launches": 12}
    rec.update(over)
    return rec


def test_engine_step_v20_round_trips(tmp_path):
    """The record goes through the writer and comes back schema-valid
    with ``dispatches`` and ``readbacks`` entry for entry beside its
    phases; a step that only read what was in flight is one too."""
    w = TelemetryWriter(str(tmp_path))
    w.span(_engine_step())
    w.span(_engine_step(step=8, start_step=8, dispatches=[],
                        phases=[["decode.readback", 9_100, 9_900]],
                        readbacks=[11]))
    w.span(_engine_step(step=9, start_step=9, phases=[], dispatches=[],
                        readbacks=[]))
    w.close()
    records, problems = read_metrics(os.path.join(str(tmp_path),
                                                  METRICS_FILENAME))
    assert problems == []
    first, closing, idle = records
    assert first["schema"] == SCHEMA_VERSION == 25
    assert first["dispatches"] == [["prefill", 4], ["decode", 8]]
    assert [p[0] for p in first["phases"] if p[0].endswith(".dispatch")] \
        == [k + ".dispatch" for k, _ in first["dispatches"]]
    # the first read is of an EARLIER record's launch, the second of
    # this record's first (ordinals launches - 2 and launches - 1)
    assert first["readbacks"] == [9, 10] and first["launches"] == 12
    assert closing["dispatches"] == [] and closing["readbacks"] == [11]
    assert idle["dispatches"] == idle["readbacks"] == []


WINDOW_READS = dict(window_rows=544, full_rows=2100,
                    window_blocks_released=2, window_blocks_live=66)


@pytest.mark.parametrize("over,ok", [
    ({}, True),                              # a model with no window layer
    (WINDOW_READS, True),
    (dict.fromkeys(WINDOW_READS, 0), True),  # ... as the engine writes it
    ({"window_rows": 5}, False),             # all four or none
    ({k: v for k, v in WINDOW_READS.items() if k != "full_rows"}, False),
    (dict(WINDOW_READS, window_rows=2101), False),   # over a full layer's
    (dict(WINDOW_READS, window_blocks_live=-1), False),
    (dict(WINDOW_READS, full_rows=2100.5), False),
])
def test_engine_step_v21_cache_read_counters(over, ok):
    """The cache reads' counters of an ``engine_step`` record
    (``STEP_SPAN_WINDOW``): all four or none, whole, not negative, no
    more positions in a window layer than in a full one."""
    from distributed_llm_code_samples_tpu.runtime.telemetry import (
        STEP_SPAN_WINDOW)
    assert frozenset(STEP_SPAN_WINDOW) == _PINNED_STEP_SPAN_WINDOW
    rec = dict(_engine_step(**over), schema=SCHEMA_VERSION, kind="span",
               trace_id=None, tenant=None)
    got, reason = validate_record(rec)
    assert got is ok, reason
    if not ok:
        assert "window_rows" in reason and "\n" not in reason


KV_READS = dict(kv_blocks_read=2 * 70, kv_blocks_capacity=2 * 8 * 24)


@pytest.mark.parametrize("over,ok", [
    (KV_READS, True),
    (dict.fromkeys(KV_READS, 0), True),      # a step with no decode row
    (dict(KV_READS, kv_blocks_read=2 * 8 * 24), True),   # a gather's
    (dict(KV_READS, **WINDOW_READS), True),
    ({"kv_blocks_read": 5}, False),          # both or none
    ({"kv_blocks_capacity": 5}, False),
    (dict(KV_READS, kv_blocks_read=2 * 8 * 24 + 1), False),  # over it
    (dict(KV_READS, kv_blocks_capacity=-1), False),
    (dict(KV_READS, kv_blocks_read=140.0), False),
])
def test_engine_step_v22_kv_block_counters(over, ok):
    """The decode-side reads' blocks of an ``engine_step`` record
    (``STEP_SPAN_KV``): both or none, whole, not negative, no more read
    than the launched rows' tables hold."""
    from distributed_llm_code_samples_tpu.runtime.telemetry import (
        STEP_SPAN_KV)
    assert frozenset(STEP_SPAN_KV) == _PINNED_STEP_SPAN_KV
    rec = dict(_engine_step(**over), schema=SCHEMA_VERSION, kind="span",
               trace_id=None, tenant=None)
    got, reason = validate_record(rec)
    assert got is ok, reason
    if not ok:
        assert "kv_blocks_read" in reason and "\n" not in reason


# 24 rows of 8 layers: 587 positions a row in blocks of 16, of 130 entries
RING_READS = dict(ring_blocks_read=8 * 24 * 38,
                  ring_blocks_capacity=8 * 24 * 130)


@pytest.mark.parametrize("over,ok", [
    (RING_READS, True),
    (dict.fromkeys(RING_READS, 0), True),    # no window layer, or no row
    (dict(RING_READS, ring_blocks_read=8 * 24 * 130), True),  # a gather's
    (dict(RING_READS, **KV_READS, **WINDOW_READS), True),
    ({"ring_blocks_read": 5}, False),        # both or none
    ({"ring_blocks_capacity": 5}, False),
    (dict(RING_READS, ring_blocks_read=8 * 24 * 130 + 1), False),
    (dict(RING_READS, ring_blocks_capacity=-1), False),
    (dict(RING_READS, ring_blocks_read=7296.0), False),
])
def test_engine_step_v24_ring_block_counters(over, ok):
    """The rings' blocks of an ``engine_step`` record
    (``STEP_SPAN_RING``): both or none, whole, not negative, no more
    read than the launched rows' rings hold."""
    from distributed_llm_code_samples_tpu.runtime.telemetry import (
        STEP_SPAN_RING)
    assert frozenset(STEP_SPAN_RING) == _PINNED_STEP_SPAN_RING
    rec = dict(_engine_step(**over), schema=SCHEMA_VERSION, kind="span",
               trace_id=None, tenant=None)
    got, reason = validate_record(rec)
    assert got is ok, reason
    if not ok:
        assert "ring_blocks_read" in reason and "\n" not in reason


CHUNK_READS = dict(summary_rows=3072, summaries_written=2)


@pytest.mark.parametrize("over,ok", [
    ({}, True),                                     # none of the two
    (CHUNK_READS, True),
    (dict(summary_rows=0, summaries_written=0), True),
    (dict(summary_rows=128), False),                # one without the other
    (dict(CHUNK_READS, summaries_written=-1), False),
    (dict(CHUNK_READS, summary_rows=12.5), False),
])
def test_engine_step_v23_chunk_summary_counters(over, ok):
    """A chunked layer's counters of an ``engine_step`` record
    (``STEP_SPAN_CHUNKS``): both or none, whole, not negative."""
    from distributed_llm_code_samples_tpu.runtime.telemetry import (
        STEP_SPAN_CHUNKS)
    assert frozenset(STEP_SPAN_CHUNKS) == _PINNED_STEP_SPAN_CHUNKS
    rec = dict(_engine_step(**over), schema=SCHEMA_VERSION, kind="span",
               trace_id=None, tenant=None)
    got, reason = validate_record(rec)
    assert got is ok, reason
    if not ok:
        assert "summary_rows" in reason and "\n" not in reason


@pytest.mark.parametrize("case,named", [
    ("v19_stamp", "schema"),            # an older writer's record
    ("no_readbacks", "readbacks"),      # v19's key set under a v20 stamp
    ("no_launches", "launches"),
    ("one_read_short", "readbacks"),
    ("one_read_over", "readbacks"),
    ("reads_the_unlaunched", "launches"),
])
def test_engine_step_v19_shapes_are_refused(case, named):
    """What schema v19 wrote is refused as the contract says: by the
    version stamp, and under a v20 stamp by the missing ``readbacks`` /
    ``launches``; entries that do not match the ``*.readback`` phases
    one to one, or name a launch the engine had not made, are no record
    of what the step read."""
    rec = dict(_engine_step(), schema=SCHEMA_VERSION, kind="span",
               trace_id=None, tenant=None)
    ok, reason = validate_record(rec)
    assert ok, reason
    if case == "v19_stamp":
        rec["schema"] = 19
        del rec["readbacks"], rec["launches"]
    elif case == "no_readbacks":
        del rec["readbacks"]
    elif case == "no_launches":
        del rec["launches"]
    elif case == "one_read_short":
        rec["readbacks"] = rec["readbacks"][:1]
    elif case == "one_read_over":
        rec["readbacks"] = rec["readbacks"] + [11]
    else:
        rec["readbacks"] = [10, 12]
    ok, reason = validate_record(rec)
    assert not ok and named in reason and "\n" not in reason


@pytest.mark.parametrize("case,named", [
    ("v18_stamp", "schema"),            # an older writer's record
    ("no_dispatches", "dispatches"),    # v18's key set under a later stamp
    ("one_entry_short", "dispatches"),
    ("one_entry_over", "dispatches"),
])
def test_engine_step_v18_shapes_are_refused(case, named):
    """What schema v18 wrote is refused as the contract says: by the
    version stamp, and under a later stamp by the missing ``dispatches``;
    entries that do not match the ``*.dispatch`` phases one to one are
    no record of what the step launched."""
    rec = dict(_engine_step(), schema=SCHEMA_VERSION, kind="span",
               trace_id=None, tenant=None)
    ok, reason = validate_record(rec)
    assert ok, reason
    if case == "v18_stamp":
        rec["schema"] = 18
        del rec["dispatches"]
    elif case == "no_dispatches":
        del rec["dispatches"]
    elif case == "one_entry_short":
        rec["dispatches"] = rec["dispatches"][:1]
    else:
        rec["dispatches"] = rec["dispatches"] + [["decode", 8]]
    ok, reason = validate_record(rec)
    assert not ok and named in reason and "\n" not in reason


def test_span_record_round_trip_and_torn_tail(tmp_path):
    """The schema-v5 span kind (runtime/tracing.py): writer method
    stamps the kind + envelope, records validate, a torn tail after a
    span write is reported-not-fatal, and a missing contract key
    rejects with a one-line message naming kind and key."""
    from distributed_llm_code_samples_tpu.runtime.tracing import (
        SpanTracer)
    w = TelemetryWriter(str(tmp_path))
    tracer = SpanTracer(lambda: w)
    tracer.open(3, "queued", 0, t=100.0)
    tracer.transition(3, "prefill", 2, t=100.5)
    tracer.close(3, 5, t=101.25, n_new=4)
    w.close()
    path = os.path.join(str(tmp_path), METRICS_FILENAME)
    with open(path, "a") as f:
        f.write('{"schema": 5, "kind": "sp')  # torn write
    records, problems = read_metrics(path)
    assert len(problems) == 1 and "torn" in problems[0]
    assert [r["span"] for r in records] == ["queued", "prefill"]
    for r in records:
        assert r["schema"] == SCHEMA_VERSION
        ok, reason = validate_record(r)
        assert ok, reason
    queued, prefill = records
    # the telescoping contract: each span starts where its predecessor
    # ended, and durations are end - start exactly
    assert queued["start_t"] == 100.0 and queued["t"] == 100.5
    assert queued["duration_s"] == pytest.approx(0.5)
    assert prefill["start_t"] == 100.5 and prefill["t"] == 101.25
    assert prefill["duration_s"] == pytest.approx(0.75)
    assert queued["duration_s"] + prefill["duration_s"] == \
        pytest.approx(101.25 - 100.0)
    assert (queued["start_step"], queued["step"]) == (0, 2)
    assert prefill["n_new"] == 4        # extras ride along
    bad = {k: v for k, v in prefill.items() if k != "start_step"}
    ok, reason = validate_record(bad)
    assert not ok and "span record" in reason and "start_step" in reason


@pytest.mark.parametrize("kind,required", [
    ("step", _PINNED_STEP_KEYS - {"schema", "kind", "t"}),
    ("anomaly", _PINNED_ANOMALY_REQUIRED),
    ("rollback", _PINNED_ROLLBACK_REQUIRED),
    ("decode", _PINNED_DECODE_REQUIRED),
    ("request", _PINNED_REQUEST_REQUIRED),
    ("span", _PINNED_SPAN_REQUIRED),
    ("router", _PINNED_ROUTER_REQUIRED),
    ("fleet", _PINNED_FLEET_REQUIRED),
    ("deploy", _PINNED_DEPLOY_REQUIRED),
    ("workload", _PINNED_WORKLOAD_REQUIRED),
    ("autoscale", _PINNED_AUTOSCALE_REQUIRED),
    ("qos", _PINNED_QOS_REQUIRED),
])
def test_validate_record_names_kind_and_key(kind, required):
    """Satellite contract: every validate_record failure is ONE line
    naming the record kind and the missing key — per kind, per key."""
    base = {"schema": SCHEMA_VERSION, "kind": kind, "t": 0.0}
    for key in sorted(required):
        rec = dict(base)
        for k in required:
            rec.setdefault(k, 1)
        del rec[key]
        ok, reason = validate_record(rec)
        assert not ok and f"{kind} record" in reason and key in reason, \
            (kind, key, reason)
        assert "\n" not in reason
    # version mismatch names the kind too (was a generic string)
    ok, reason = validate_record({"schema": SCHEMA_VERSION + 1,
                                  "kind": kind, "t": 0.0})
    assert not ok and f"{kind} record" in reason and "schema" in reason


def test_router_record_round_trip(tmp_path):
    """A fleet-router decision record written through the writer parses
    back schema-valid with the contract keys; source/target/policy
    default to null for decisions that have none (a routed request has
    no source engine; a migration takes no placement policy)."""
    w = TelemetryWriter(str(tmp_path))
    transport = {"mode": "replay", "bytes": 0, "crc_verify_s": None,
                 "retries": 0}
    w.router({"step": 2, "uid": 7, "event": "migrated", "source": "e1",
              "target": "e0", "reason": "engine_killed",
              "blocks": 0, "bytes": 0, "duration_s": 0.001,
              "ship_s": None, "catchup_tokens": 3,
              "transport": transport})
    w.router({"step": 0, "uid": 3, "event": "routed", "target": "e2",
              "reason": "prefix", "policy": "prefix",
              "prefix_hit_blocks": 2})
    w.router({"step": 4, "uid": 7, "event": "wire_rejected",
              "source": "p0", "target": "e0",
              "reason": "array 'k' CRC-32 mismatch (0x1 != 0x2)"})
    w.close()
    path = os.path.join(str(tmp_path), METRICS_FILENAME)
    with open(path, "a") as f:
        f.write('{"schema": 10, "kind": "rou')  # torn write
    records, problems = read_metrics(path)
    assert len(problems) == 1 and "torn" in problems[0]
    mig, routed, rej = records
    assert mig["kind"] == "router" and mig["schema"] == SCHEMA_VERSION
    assert mig["source"] == "e1" and mig["target"] == "e0"
    assert mig["reason"] == "engine_killed"
    assert mig["policy"] is None        # writer default: no placement
    assert mig["duration_s"] == 0.001   # the stall instrumentation
    assert mig["transport"]["mode"] == "replay"
    # v16: the async-migration pair rides every migrated record
    assert mig["ship_s"] is None and mig["catchup_tokens"] == 3
    assert routed["source"] is None and routed["target"] == "e2"
    assert routed["policy"] == "prefix"
    assert routed["prefix_hit_blocks"] == 2
    # v10: the wire_rejected event carries the one-line WireError and
    # needs no transport (nothing moved)
    assert rej["event"] == "wire_rejected" and "CRC-32" in rej["reason"]
    for r in records:
        ok, reason = validate_record(r)
        assert ok, reason


def test_router_move_record_conditional_pin():
    """v10: a handoff/migrated router record must carry the move
    instrumentation (blocks/bytes/duration_s) AND the transport
    attribution; routed/shed/wire_rejected records move nothing and
    never pin them — per event, per key."""
    base = {"schema": SCHEMA_VERSION, "kind": "router", "t": 0.0,
            "step": 1, "uid": 2, "source": "p0", "target": "e0",
            "policy": None, "trace_id": "ab12-2"}
    move_keys = {"blocks": 3, "bytes": 4096, "duration_s": 0.01,
                 "transport": {"mode": "wire", "bytes": 4096,
                               "crc_verify_s": 0.0001, "retries": 0}}
    # v16: a migration additionally pins the async-migration pair —
    # a handoff never does (nothing catches up on a prefill handoff)
    mig_keys = {"ship_s": 0.42, "catchup_tokens": 2}
    for event in ("handoff", "migrated"):
        extra = mig_keys if event == "migrated" else {}
        ok, reason = validate_record({**base, "event": event,
                                      **move_keys, **extra})
        assert ok, reason
        for key in sorted({**move_keys, **extra}):
            rec = {**base, "event": event, **move_keys, **extra}
            del rec[key]
            ok, reason = validate_record(rec)
            assert not ok and event in reason and key in reason, \
                (event, key, reason)
            assert "\n" not in reason
    for event in ("routed", "shed", "wire_rejected", "reconnected"):
        ok, reason = validate_record({**base, "event": event})
        assert ok, (event, reason)


def test_fleet_record_round_trip_and_torn_tail(tmp_path):
    """The schema-v9 fleet health kind (decode/fleet.py): writer method
    stamps the kind + envelope, records validate, a torn tail after a
    fleet write is reported-not-fatal, and a missing contract key
    rejects naming kind and key."""
    w = TelemetryWriter(str(tmp_path))
    w.fleet({"step": 3, "engines": {
        "e0": {"alive": True, "role": "decode", "waiting": 1,
               "active": 2, "free_blocks": 10, "utilization": 0.5},
        "e1": {"alive": False}},
        "load_imbalance": 1.0})
    w.close()
    path = os.path.join(str(tmp_path), METRICS_FILENAME)
    with open(path, "a") as f:
        f.write('{"schema": 9, "kind": "fle')  # torn write
    records, problems = read_metrics(path)
    assert len(problems) == 1 and "torn" in problems[0]
    [rec] = records
    assert rec["kind"] == "fleet" and rec["schema"] == SCHEMA_VERSION
    assert rec["engines"]["e0"]["utilization"] == 0.5
    assert rec["engines"]["e1"] == {"alive": False}
    assert rec["load_imbalance"] == 1.0
    ok, reason = validate_record(rec)
    assert ok, reason
    bad = {k: v for k, v in rec.items() if k != "load_imbalance"}
    ok, reason = validate_record(bad)
    assert not ok and "fleet record" in reason \
        and "load_imbalance" in reason


def test_autoscale_record_round_trip_and_torn_tail(tmp_path):
    """The schema-v14 autoscale kind (decode/autoscale.py): writer
    method stamps the kind + envelope, records validate, a torn tail
    after an autoscale write is reported-not-fatal, and a missing
    contract key rejects naming kind and key."""
    w = TelemetryWriter(str(tmp_path))
    w.autoscale({"step": 6, "event": "scale_up",
                 "reason": "queue_pressure", "engines": 3,
                 "target_engines": 3, "engine": "e2", "compiled": 8,
                 "spawn_s": 0.42})
    w.qos({"step": 9, "event": "wfq_pick", "tenant": "quiet",
           "uid": 4, "virtual_time": 2.5})
    w.close()
    path = os.path.join(str(tmp_path), METRICS_FILENAME)
    with open(path, "a") as f:
        f.write('{"schema": 14, "kind": "auto')  # torn write
    records, problems = read_metrics(path)
    assert len(problems) == 1 and "torn" in problems[0]
    up, pick = records
    assert up["kind"] == "autoscale" and up["schema"] == SCHEMA_VERSION
    assert up["event"] == "scale_up" and up["engine"] == "e2"
    assert up["engines"] == 3 and up["target_engines"] == 3
    assert up["spawn_s"] == 0.42        # extras ride along, unpinned
    assert pick["kind"] == "qos" and pick["schema"] == SCHEMA_VERSION
    assert pick["tenant"] == "quiet" and pick["virtual_time"] == 2.5
    for r in records:
        ok, reason = validate_record(r)
        assert ok, reason
    bad = {k: v for k, v in up.items() if k != "target_engines"}
    ok, reason = validate_record(bad)
    assert not ok and "autoscale record" in reason \
        and "target_engines" in reason
    # qos tenant defaults to null (the single-tenant stance), never
    # silently absent
    w2 = TelemetryWriter(str(tmp_path / "single"))
    w2.qos({"step": 1, "event": "predicted_miss_shed", "uid": 7,
            "eta_steps": 30, "deadline_steps": 20})
    w2.close()
    [rec], problems = read_metrics(
        os.path.join(str(tmp_path / "single"), METRICS_FILENAME))
    assert problems == []
    assert rec["tenant"] is None
    ok, reason = validate_record(rec)
    assert ok, reason


def test_autoscale_event_conditional_pin():
    """v14: scale_up names the spawned engine, scale_down names the
    drained engine AND the drained-resident count; held pins nothing
    beyond the base contract — per event, per key."""
    base = {"schema": SCHEMA_VERSION, "kind": "autoscale", "t": 0.0,
            "step": 2, "reason": "queue_pressure", "engines": 2,
            "target_engines": 3}
    pins = {"scale_up": {"engine": "e2"},
            "scale_down": {"engine": "e1", "drained": 2}}
    for event, keys in pins.items():
        ok, reason = validate_record({**base, "event": event, **keys})
        assert ok, reason
        for key in sorted(keys):
            rec = {**base, "event": event, **keys}
            del rec[key]
            ok, reason = validate_record(rec)
            assert not ok and event in reason and key in reason, \
                (event, key, reason)
            assert "\n" not in reason
    ok, reason = validate_record({**base, "event": "held"})
    assert ok, reason


def test_qos_event_conditional_pin():
    """v14: each qos decision pins exactly the numbers that justified
    it (the ETA that blew the deadline, the budget that deferred, the
    virtual time that won) — per event, per key."""
    base = {"schema": SCHEMA_VERSION, "kind": "qos", "t": 0.0,
            "step": 5, "tenant": "noisy"}
    pins = {
        "predicted_miss_shed": {"uid": 3, "eta_steps": 40,
                                "deadline_steps": 24},
        "budget_deferred": {"uid": 4, "resident_tokens": 96,
                            "token_budget": 64},
        "wfq_pick": {"uid": 5, "virtual_time": 1.25},
    }
    for event, keys in pins.items():
        ok, reason = validate_record({**base, "event": event, **keys})
        assert ok, reason
        for key in sorted(keys):
            rec = {**base, "event": event, **keys}
            del rec[key]
            ok, reason = validate_record(rec)
            assert not ok and event in reason and key in reason, \
                (event, key, reason)
            assert "\n" not in reason


def test_alert_record_round_trip_and_torn_tail(tmp_path):
    """The schema-v15 alert kind (runtime/watch.py): writer method
    stamps the kind + envelope and defaults severity to "warn", records
    validate, a torn tail after an alert write is reported-not-fatal,
    and a missing contract key rejects naming kind and key."""
    w = TelemetryWriter(str(tmp_path))
    w.alert({"step": 11, "event": "fired", "detector": "burn_rate",
             "severity": "page", "window": [7, 11], "burn_fast": 4.0,
             "burn_slow": 1.0, "violations": 1, "completions": 1})
    w.alert({"step": 16, "event": "resolved", "detector": "burn_rate",
             "severity": "page", "window": [12, 16], "burn_fast": 0.0,
             "burn_slow": 0.5, "violations": 0, "completions": 2,
             "fired_step": 11})
    w.alert({"step": 3, "event": "fired", "detector": "queue_growth",
             "window": [0, 3], "waiting": 9, "threshold": 4})
    w.close()
    path = os.path.join(str(tmp_path), METRICS_FILENAME)
    with open(path, "a") as f:
        f.write('{"schema": 15, "kind": "aler')  # torn write
    records, problems = read_metrics(path)
    assert len(problems) == 1 and "torn" in problems[0]
    fired, resolved, queue = records
    assert fired["kind"] == "alert" and fired["schema"] == SCHEMA_VERSION
    assert fired["event"] == "fired" and fired["severity"] == "page"
    assert fired["window"] == [7, 11] and fired["burn_fast"] == 4.0
    assert resolved["event"] == "resolved"
    assert resolved["fired_step"] == 11  # extras ride along, unpinned
    # severity defaults to "warn" (an experimental detector need not
    # pick a page class), never silently absent
    assert queue["severity"] == "warn" and queue["waiting"] == 9
    for r in records:
        ok, reason = validate_record(r)
        assert ok, reason
    bad = {k: v for k, v in fired.items() if k != "violations"}
    ok, reason = validate_record(bad)
    assert not ok and "alert record" in reason and "violations" in reason


def test_alert_detector_conditional_pin():
    """v15: every detector transition pins exactly the numbers that
    justified it, on BOTH fired and resolved records (the resolved
    record shows the recovered reading) — per detector, per key."""
    base = {"schema": SCHEMA_VERSION, "kind": "alert", "t": 0.0,
            "step": 9, "severity": "page", "window": [5, 9]}
    pins = {
        "burn_rate": {"burn_fast": 2.0, "burn_slow": 1.5,
                      "violations": 3, "completions": 6},
        "queue_growth": {"waiting": 12, "threshold": 4},
        "imbalance": {"imbalance": 0.8, "threshold": 0.5},
        "collapse": {"stalled_rounds": 6, "live": 0},
        "incident_rate": {"incidents": 2, "threshold": 1},
        "latency_drift": {"p95_s": 1.9, "baseline_s": 0.6,
                          "metric": "ttft"},
    }
    for detector, keys in pins.items():
        for event in ("fired", "resolved"):
            ok, reason = validate_record({**base, "event": event,
                                          "detector": detector, **keys})
            assert ok, reason
            for key in sorted(keys):
                rec = {**base, "event": event, "detector": detector,
                       **keys}
                del rec[key]
                ok, reason = validate_record(rec)
                assert not ok and detector in reason and key in reason, \
                    (detector, event, key, reason)
                assert "\n" not in reason


def test_completed_request_record_conditional_pin():
    """v9: a completed request record must carry latency_s AND ttft_s
    (null ttft_s allowed — a crash-resumed first token is honestly
    unreconstructable); other request events never pin them."""
    base = {"schema": SCHEMA_VERSION, "kind": "request", "t": 0.0,
            "step": 3, "uid": 1, "reason": None,
            "weights_version": None, "trace_id": "ab12-1",
            "tenant": None}
    ok, reason = validate_record({**base, "event": "completed",
                                  "latency_s": 1.5, "ttft_s": 0.5})
    assert ok, reason
    ok, reason = validate_record({**base, "event": "completed",
                                  "latency_s": 1.5, "ttft_s": None})
    assert ok, reason                    # null is a value, not absence
    ok, reason = validate_record({**base, "event": "completed",
                                  "latency_s": 1.5})
    assert not ok and "completed" in reason and "ttft_s" in reason
    ok, reason = validate_record({**base, "event": "completed",
                                  "ttft_s": 0.5})
    assert not ok and "latency_s" in reason
    # an admitted record carries neither and stays valid
    ok, reason = validate_record({**base, "event": "admitted"})
    assert ok, reason
    # v11: the weights_version pin is part of the kind-wide contract —
    # a record missing it (not merely null) rejects naming the key
    bad = {k: v for k, v in base.items() if k != "weights_version"}
    ok, reason = validate_record({**bad, "event": "admitted"})
    assert not ok and "request record" in reason \
        and "weights_version" in reason


def test_workload_record_round_trip_and_torn_tail(tmp_path):
    """The schema-v13 workload kind (decode/workload_driver.py): the
    writer method stamps the kind + envelope, records validate, a torn
    tail after a workload write is reported-not-fatal, and a missing
    contract key rejects naming kind and key. The tenant pin on
    request records validates through the writer's default (null
    single-tenant) and rejects when the key is absent."""
    w = TelemetryWriter(str(tmp_path))
    w.workload({"step": 8, "trace": {"id": "trabc123", "version": 1},
                "offered": 5, "admitted": 4,
                "tenants": {"a": {"offered": 3, "completed": 1,
                                  "shed": 1},
                            "b": {"offered": 2, "completed": 0,
                                  "shed": 0}}})
    # a request record through the writer defaults tenant to null —
    # the single-tenant stance; the workload plane sets it explicitly
    w.request({"step": 8, "uid": 3, "event": "admitted"})
    w.request({"step": 9, "uid": 4, "event": "admitted",
               "tenant": "b"})
    w.close()
    path = os.path.join(str(tmp_path), METRICS_FILENAME)
    with open(path, "a") as f:
        f.write('{"schema": 13, "kind": "wor')    # torn write
    records, problems = read_metrics(path)
    assert len(problems) == 1 and "torn" in problems[0]
    wl, r1, r2 = records
    assert wl["kind"] == "workload" and wl["schema"] == SCHEMA_VERSION
    assert wl["trace"] == {"id": "trabc123", "version": 1}
    assert wl["offered"] == 5 and wl["admitted"] == 4
    assert wl["tenants"]["a"]["shed"] == 1
    assert r1["tenant"] is None and r2["tenant"] == "b"
    for r in records:
        ok, reason = validate_record(r)
        assert ok, reason
    # missing contract keys reject naming kind + key
    bad = {k: v for k, v in wl.items() if k != "tenants"}
    ok, reason = validate_record(bad)
    assert not ok and "workload record" in reason \
        and "tenants" in reason
    bad = {k: v for k, v in r1.items() if k != "tenant"}
    ok, reason = validate_record(bad)
    assert not ok and "request record" in reason \
        and "tenant" in reason


def test_deploy_record_round_trip_and_torn_tail(tmp_path):
    """The schema-v11 deploy kind (decode/fleet.py rolling_deploy):
    the writer method stamps the kind + envelope, the full lifecycle
    round-trips, a torn tail after a deploy write is reported-not-
    fatal, and a missing contract key rejects naming kind and key."""
    w = TelemetryWriter(str(tmp_path))
    w.deploy({"step": 4, "event": "started", "from_version": 0,
              "to_version": 3, "ckpt_dir": "/ck"})
    w.deploy({"step": 4, "event": "engine_swapped", "from_version": 0,
              "to_version": 3, "engine": "e1", "duration_s": 0.01})
    w.deploy({"step": 4, "event": "completed", "from_version": 0,
              "to_version": 3, "duration_s": 0.2, "engines": 3,
              "drained": 5})
    w.deploy({"step": 9, "event": "rolled_back", "from_version": 3,
              "to_version": 7, "duration_s": 0.05,
              "reason": "checkpoint step_7 rejected (arrays.npz "
                        "checksum mismatch)", "latest_verified": 3})
    w.close()
    path = os.path.join(str(tmp_path), METRICS_FILENAME)
    with open(path, "a") as f:
        f.write('{"schema": 11, "kind": "dep')    # torn write
    records, problems = read_metrics(path)
    assert len(problems) == 1 and "torn" in problems[0]
    assert [r["event"] for r in records] == [
        "started", "engine_swapped", "completed", "rolled_back"]
    for rec in records:
        assert rec["kind"] == "deploy" and rec["schema"] == SCHEMA_VERSION
        ok, reason = validate_record(rec)
        assert ok, reason
    assert records[3]["from_version"] == 3 \
        and records[3]["to_version"] == 7
    assert "\n" not in records[3]["reason"]
    bad = {k: v for k, v in records[0].items() if k != "to_version"}
    ok, reason = validate_record(bad)
    assert not ok and "deploy record" in reason and "to_version" in reason


def test_deploy_record_per_event_conditional_pins():
    """v11 per-event pins: engine_swapped names its engine, terminal
    events carry duration_s, a rollback carries its one-line reason —
    and ``started`` pins none of them (nothing has happened yet)."""
    base = {"schema": SCHEMA_VERSION, "kind": "deploy", "t": 0.0,
            "step": 2, "from_version": 0, "to_version": 5,
            "trace_id": None}
    ok, reason = validate_record({**base, "event": "started"})
    assert ok, reason
    ok, reason = validate_record({**base, "event": "engine_swapped"})
    assert not ok and "engine_swapped" in reason and "engine" in reason
    ok, reason = validate_record({**base, "event": "engine_swapped",
                                  "engine": "e0"})
    assert ok, reason
    ok, reason = validate_record({**base, "event": "completed"})
    assert not ok and "completed" in reason and "duration_s" in reason
    ok, reason = validate_record({**base, "event": "rolled_back",
                                  "duration_s": 0.1})
    assert not ok and "rolled_back" in reason and "reason" in reason
    ok, reason = validate_record({**base, "event": "rolled_back",
                                  "duration_s": 0.1, "reason": "torn"})
    assert ok, reason
    for rec in ({**base, "event": "started"},
                {**base, "event": "rolled_back", "duration_s": 0.1,
                 "reason": "x"}):
        assert "\n" not in validate_record(
            {k: v for k, v in rec.items() if k != "step"})[1]


def test_read_metrics_survives_torn_tail(tmp_path):
    """A crash mid-append leaves a torn final line; the reader reports
    it and keeps every whole record — recovery tooling must never lose a
    run's history to its last write."""
    w = TelemetryWriter(str(tmp_path))
    w.event({"event": "published", "step": 4})
    w.close()
    path = os.path.join(str(tmp_path), METRICS_FILENAME)
    with open(path, "a") as f:
        f.write('{"schema": 1, "kind": "st')  # torn write
    records, problems = read_metrics(path)
    assert len(records) == 1 and records[0]["event"] == "published"
    assert len(problems) == 1 and "torn" in problems[0]


def test_writer_readbacks_happen_off_thread(tmp_path):
    """The non-blocking contract: ``step()`` must not convert device
    values on the calling thread — the float() readback happens on the
    writer thread (steady-state steps stay dispatch-only; readbacks
    batch at the logging cadence)."""
    seen = {}

    class Scalar:
        def __float__(self):
            seen["thread"] = threading.current_thread().name
            return 2.0

        # numpy asks for an array interface first
        def __array__(self, dtype=None, copy=None):
            seen["thread"] = threading.current_thread().name
            return np.asarray(2.0, dtype or np.float64)

    w = TelemetryWriter(str(tmp_path))
    w.step(1, loss=Scalar(), step_time_s=0.5)
    w.close()
    assert seen["thread"] != threading.main_thread().name
    records, _ = read_metrics(os.path.join(str(tmp_path),
                                           METRICS_FILENAME))
    assert records[0]["loss"] == pytest.approx(2.0)


def test_flops_and_peak_helpers():
    # 12*T*d*f*L, the hand count
    assert ffn_model_flops(64, 8, 2) == 12 * 64 * 8 * 32 * 2
    assert hand_flops_per_step("ffn", tokens=64, model_size=8,
                               n_layers=2) == ffn_model_flops(64, 8, 2)
    # MoE has no honest static count yet
    assert hand_flops_per_step("moe", tokens=64, model_size=8,
                               n_layers=2) is None
    assert peak_flops("TPU v5 lite") == pytest.approx(197e12)
    assert peak_flops("cpu") is None  # honest null beats a guess


# ---------------------------------------------------------------------------
# named-scope presence: the compiled program of every strategy carries
# its region names (the utils/trace_analysis.SCOPES naming map)


def _capture_compiled(run):
    import distributed_llm_code_samples_tpu.parallel.launcher as launcher
    launcher.CAPTURE_COMPILED = cap = []
    try:
        jax.block_until_ready(run())
    finally:
        launcher.CAPTURE_COMPILED = None
    assert cap, "launch captured no compiled program"
    return "\n".join(cap)


def _strategy_runs():
    from distributed_llm_code_samples_tpu.data import make_seed_schedule
    from distributed_llm_code_samples_tpu.models import (
        init_ffn_stack, init_lm, init_moe_lm, init_moe_stack,
        init_moe_transformer, init_transformer)
    from distributed_llm_code_samples_tpu.optim import sgd_optimizer
    from distributed_llm_code_samples_tpu.parallel import (
        DATA_AXIS, EXPERT_AXIS, MODEL_AXIS, PIPE_AXIS, SEQ_AXIS,
        make_mesh, train_ddp, train_ddp_zero1, train_fsdp, train_hybrid,
        train_lm_ddp, train_moe_ep, train_moe_lm_ep,
        train_moe_transformer_ep, train_pp, train_tp,
        train_transformer_seq, train_transformer_tp)
    d = 16
    key = jax.random.PRNGKey(0)
    ffn = init_ffn_stack(key, d, 2)
    ffn4 = init_ffn_stack(key, d, 4)
    tf = init_transformer(key, d, 2)
    lm = init_lm(key, 16, d, 2, max_seq_len=8)
    moe = init_moe_stack(key, d, 2, 8)
    moe_lm = init_moe_lm(key, 16, d, 2, 8, max_seq_len=8)
    moe_tf = init_moe_transformer(key, d, 2, 8)
    s2 = make_seed_schedule(2, 1)
    s4 = make_seed_schedule(4, 1)
    m_d4 = make_mesh({DATA_AXIS: 4})
    m_m2 = make_mesh({MODEL_AXIS: 2})
    return {
        "ddp": lambda: train_ddp(ffn, s4, 32, d, m_d4),
        "fsdp": lambda: train_fsdp(ffn, s4, 32, d, m_d4),
        "tp": lambda: train_tp(ffn, s2, 32, d, m_m2),
        "hybrid": lambda: train_hybrid(
            ffn, s2, 32, d, make_mesh({DATA_AXIS: 2, MODEL_AXIS: 2})),
        "zero1": lambda: train_ddp_zero1(ffn4, s4, 32, d, m_d4,
                                         optimizer=sgd_optimizer()),
        "pp": lambda: train_pp(ffn4, s2, 8, d,
                               make_mesh({PIPE_AXIS: 4})),
        "ep": lambda: train_moe_ep(moe, s4, 32, d,
                                   make_mesh({EXPERT_AXIS: 4})),
        "tf": lambda: train_transformer_tp(tf, s2, 16, d, m_m2,
                                           seq_len=8, n_heads=4),
        "seq": lambda: train_transformer_seq(
            tf, s2, 16, d, make_mesh({SEQ_AXIS: 4}), seq_len=8,
            n_heads=4),
        "lm": lambda: train_lm_ddp(lm, s4, 16, d, m_d4, seq_len=8,
                                   n_heads=4),
        "moe_lm": lambda: train_moe_lm_ep(
            moe_lm, s4, 32, d, make_mesh({EXPERT_AXIS: 4}), seq_len=8,
            n_heads=4),
        "moe_tf": lambda: train_moe_transformer_ep(
            moe_tf, s4, 32, d, make_mesh({EXPERT_AXIS: 4}), seq_len=8,
            n_heads=4),
    }


@pytest.mark.parametrize("strategy", [
    "ddp", "fsdp", "tp", "hybrid", "zero1", "pp", "ep", "tf", "seq",
    "lm", "moe_lm", "moe_tf"])
def test_named_scopes_in_compiled_hlo(strategy):
    """Every parallel strategy's REAL launched program (captured through
    the launcher, not a reconstruction) carries its named-scope regions
    in the optimized HLO — the stable names Perfetto traces, HLO dumps,
    and utils/trace_analysis key on."""
    from distributed_llm_code_samples_tpu.utils.trace_analysis import (
        SCOPES)
    text = _capture_compiled(_strategy_runs()[strategy])
    missing = [r for r in SCOPES[strategy] if r not in text]
    assert not missing, (f"{strategy}: compiled HLO lacks named-scope "
                         f"region(s) {missing}")


def test_single_strategy_scopes():
    """The single-device trainer jits at module level (no launcher), so
    its scope presence is checked on its lowered step directly."""
    from distributed_llm_code_samples_tpu.models import init_ffn_stack
    from distributed_llm_code_samples_tpu.parallel.single import make_step
    from distributed_llm_code_samples_tpu.utils.trace_analysis import (
        SCOPES)
    p = init_ffn_stack(jax.random.PRNGKey(0), 16, 2)
    step = make_step(32, 16)
    text = jax.jit(step).lower(p, jax.numpy.int32(3)).compile().as_text()
    for region in SCOPES["single"]:
        assert region in text, region


# ---------------------------------------------------------------------------
# StepReport: the static fold (compiler cost + collectives + memory)


def test_step_report_folds_static_analyses(mesh4):
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from distributed_llm_code_samples_tpu.models import init_ffn_stack
    from distributed_llm_code_samples_tpu.parallel import DATA_AXIS
    from distributed_llm_code_samples_tpu.parallel.ddp import make_step

    tokens, d = 32, 16
    p = init_ffn_stack(jax.random.PRNGKey(0), d, 2)
    step = jax.shard_map(
        make_step(tokens, d), mesh=mesh4, in_specs=(P(), P()),
        out_specs=P())
    # the compiled program is ONE shard's SPMD step, so the cross-check
    # hand count is the per-shard (local-token) model FLOPs
    hand = ffn_model_flops(tokens, d, 2)
    report = StepReport.of(partial(step), p, jax.numpy.int32(3),
                           hand_flops=hand)
    # DDP's schedule: one grad psum per layer
    assert report.collectives.get("all_reduce", 0) >= 2
    assert report.hand_flops == hand
    if report.flops is not None:  # backend-dependent surface
        # executed FLOPs land within sanity range of the hand count
        # (recompute policy executes 14/12 of model FLOPs; RNG/update
        # add a little more)
        assert report.flops_vs_hand == pytest.approx(1.0, abs=0.75)
    d = report.as_dict()
    assert set(d) == {"collectives", "flops", "bytes_accessed", "memory",
                      "hand_flops", "flops_vs_hand"}


# ---------------------------------------------------------------------------
# chunked metrics driving: dispatch count + stream validity


def test_metrics_chunked_driving_dispatch_count(tmp_path, monkeypatch):
    """--log_every N drives the run as S/N compiled programs: steps
    inside a chunk stay dispatch-only (the no-per-step-host-sync
    guard — the trainer is invoked once per logged chunk, never per
    step), and every record in the stream is schema-valid."""
    import distributed_llm_code_samples_tpu.cli as cli
    import distributed_llm_code_samples_tpu.parallel as parallel

    calls = []
    real = parallel.STRATEGIES[2][1]

    def spy(params, seeds, *a, **kw):
        calls.append(len(seeds))
        return real(params, seeds, *a, **kw)

    monkeypatch.setitem(parallel.STRATEGIES, 2, ("train_ddp", spy))
    mdir = str(tmp_path / "metrics")
    rc = cli.main(["-m", "2", "-s", "16", "-bs", "4", "-n", "8", "-d",
                   "8", "-l", "2", "--metrics_dir", mdir,
                   "--log_every", "8"])
    assert rc == 0
    # 16 steps at log_every 8 = exactly 2 trainer invocations (8-device
    # mesh: 8 divides 8) — one compiled scan per chunk, no per-step host
    # round-trips
    assert calls == [8, 8]
    records, problems = read_metrics(os.path.join(mdir,
                                                  METRICS_FILENAME))
    assert problems == []
    steps = [r for r in records if r["kind"] == "step"]
    assert [s["step"] for s in steps] == [8, 16]
    for s in steps:
        assert s["step_time_s"] > 0 and s["tokens_per_sec"] > 0
        # the ffn probe fills grad_norm at the logging cadence
        assert s["grad_norm"] is not None and np.isfinite(s["grad_norm"])


# ---------------------------------------------------------------------------
# the acceptance scenario: chaos run -> schema-valid stream -> report
# timeline shows fault, recovery, and post-recovery steps


def test_chaos_run_report_timeline(tmp_path, capsys):
    import distributed_llm_code_samples_tpu.cli as cli
    from distributed_llm_code_samples_tpu.report import report_main

    mdir = str(tmp_path / "metrics")
    ck = str(tmp_path / "ck")
    rc = cli.main(["-m", "2", "-s", "8", "-bs", "4", "-n", "8", "-d",
                   "8", "-l", "2", "--chaos", "nan_grad@2",
                   "--checkpoint_dir", ck, "--checkpoint_every", "8",
                   "--metrics_dir", mdir])
    assert rc == 0
    records, problems = read_metrics(os.path.join(mdir,
                                                  METRICS_FILENAME))
    assert problems == [], problems  # schema-valid stream, every record
    steps = [r for r in records if r["kind"] == "step"]
    assert steps and steps[-1]["step"] == 8  # post-recovery progress
    capsys.readouterr()
    rc = report_main([mdir])
    out = capsys.readouterr().out
    assert rc == 0
    # the ladder (round 8): a poisoned segment takes the cheap rollback
    # rung — the timeline shows the rewind, not a process restart
    assert "ROLLBACK" in out and "NonFiniteParamsError" in out
    assert "RECOVERED" in out
    # ordering on the one timeline: fault -> recovery completion, with
    # the post-recovery step record present
    assert out.index("ROLLBACK") < out.index("RECOVERED")
    assert "step 8" in out


def test_report_handles_missing_and_empty(tmp_path, capsys):
    """A NONEXISTENT path is rc 2 (typo protection); an existing-but-
    empty or record-free metrics dir is rc 0 with an explicit "no
    records" summary — the run wrote nothing, which is an answer, not
    a tooling failure."""
    from distributed_llm_code_samples_tpu.report import report_main
    assert report_main([str(tmp_path / "nope")]) == 2
    capsys.readouterr()
    # empty dir: exists, no metrics.jsonl
    empty = tmp_path / "empty"
    empty.mkdir()
    assert report_main([str(empty)]) == 0
    out = capsys.readouterr().out
    assert "no records" in out and "empty metrics dir" in out
    # record-free: metrics.jsonl exists but nothing validates — the
    # summary names the problem instead of rendering an empty report
    bad = tmp_path / "m"
    bad.mkdir()
    (bad / METRICS_FILENAME).write_text('{"not": "valid"}\n')
    assert report_main([str(bad)]) == 0
    out = capsys.readouterr().out
    assert "no records" in out and "version mismatch" in out
    # --json carries the same verdict machine-readably
    assert report_main([str(bad), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["no_records"] and doc["streams"][0]["problems"]


def test_report_profile_folding(tmp_path, capsys):
    """--profile_dir folds a chrome trace through utils/trace_analysis:
    overlap numbers + per-named-scope totals appear in the report."""
    import gzip

    from distributed_llm_code_samples_tpu.report import report_main

    w = TelemetryWriter(str(tmp_path), meta={"strategy": "train_ddp"})
    w.step(1, step_time_s=0.1, tokens=32)
    w.close()
    prof = tmp_path / "prof"
    prof.mkdir()
    events = [
        {"ph": "X", "name": "all-reduce.1", "pid": 0, "ts": 0,
         "dur": 10},
        {"ph": "X", "name": "fusion.7 ddp/bwd/comm", "pid": 0, "ts": 5,
         "dur": 10},
    ]
    with gzip.open(prof / "x.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    capsys.readouterr()
    rc = report_main([str(tmp_path), "--profile_dir", str(prof)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "overlap 5.0 us" in out
    assert "ddp/bwd/comm" in out


# ---------------------------------------------------------------------------
# trace_analysis units


def test_trace_analysis_overlap_and_scopes():
    from distributed_llm_code_samples_tpu.utils.trace_analysis import (
        SCOPES, classify_span, comm_compute_overlap, scope_totals)
    spans = [
        {"ph": "X", "name": "all-gather-start.3", "pid": 1, "ts": 0,
         "dur": 100},
        {"ph": "X", "name": "fusion.12", "pid": 1, "ts": 50, "dur": 100},
        {"ph": "X", "name": "fusion.9", "pid": 2, "ts": 0, "dur": 100},
        {"ph": "X", "name": "dot.2 fsdp/fwd/comm", "pid": 2, "ts": 0,
         "dur": 7},
    ]
    n_comm, n_compute, overlap = comm_compute_overlap(spans)
    assert (n_comm, n_compute) == (1, 3)
    assert overlap == pytest.approx(50.0)  # same-lane intersection only
    assert classify_span("reduce-scatter.0") == "comm"
    assert classify_span("convolution.5") == "compute"
    assert classify_span("infeed") is None
    totals = scope_totals(spans, "fsdp")
    assert totals["fsdp/fwd/comm"] == pytest.approx(7.0)
    # every TRAINING strategy in the naming map carries the four-role
    # structure; the serving entries (decode/prefill) have no optimizer
    # and carry the decode-attribution roles instead
    from distributed_llm_code_samples_tpu.utils.trace_analysis import (
        SERVING_SCOPES)
    for strat, regions in SCOPES.items():
        if strat in SERVING_SCOPES:
            assert any("sample" in r for r in regions), strat
            assert any("gather" in r for r in regions), strat
        else:
            assert any("optim" in r for r in regions), strat
