"""Unified run telemetry: the per-step metrics stream every subsystem
shares.

The reference's observability surface is a rank-0 chrome trace plus
ad-hoc wall-clock prints (``train_ffns.py:129-141, :378-382``). This
repo had grown real instrumentation — collective counting
(``utils/hlo.py``), trace span analysis (``utils/trace_analysis.py``),
supervise's per-attempt JSONL (``runtime/failure.py``) — but each piece
was an island with its own format. This module is the common spine
(MegaScale's in-depth per-step observability stance): one
schema-versioned JSONL stream, one writer, one FLOP/peak accounting,
and a static ``StepReport`` that folds the compiler's own numbers
(``cost_analysis`` + collective counts + compiled memory) into a single
cross-checked object.

Design rules:

- **Non-blocking**: ``TelemetryWriter`` enqueues records (values may be
  live device scalars) and a daemon thread does the ``float()``
  readbacks + file appends — the training loop never blocks on
  telemetry I/O, and device readbacks happen at the logging cadence,
  never per step.
- **Schema-stable**: every record carries ``schema`` =
  ``SCHEMA_VERSION``; ``STEP_KEYS`` is the step-record contract and the
  schema-contract test (tests/test_telemetry.py) pins it — changing the
  key set without bumping the version fails the suite.
- **Crash-safe enough**: one JSON object per line, flushed per record;
  a torn final line is skipped by ``read_metrics``, never fatal.
"""

from __future__ import annotations

import json
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

# v2 (round 8): adds the self-healing record kinds — "anomaly"
# (in-graph guardrail counters per compiled chunk) and "rollback"
# (supervisor ladder rungs) — with their own pinned key contracts.
# v3 (round 9): adds the "decode" kind — the serving engine's per-cadence
# throughput/occupancy/KV-pool record (decode/engine.py) with its own
# pinned required-key contract (DECODE_REQUIRED).
# v4 (round 10): adds the "request" kind — one record per serving
# request lifecycle transition (admitted / preempted / retried /
# quarantined / completed / rejected / expired, decode/engine.py) with
# its own pinned required-key contract (REQUEST_REQUIRED).
# v5 (round 11): adds the "span" kind — per-request lifecycle spans
# (queued / prefill / replay / decode / quarantine / preempt_gap,
# runtime/tracing.py) with pinned SPAN_REQUIRED — and grows the
# "decode" contract with the KV-pool internals (free-block watermarks,
# block churn, fragmentation, per-dtype stored-KV bytes).
# v6 (round 12): grows the "decode" contract with the speculative-
# decoding trio — cumulative ``drafted_tokens`` / ``accepted_tokens``
# and the derived ``accept_rate`` (decode/engine.py verify dispatches;
# null-rate when nothing was drafted) — so a serving stream shows
# tokens-per-step > 1 as measured data, not inference.
# v7 (round 13): grows the "decode" contract with the shared-prefix
# set — cumulative ``prefix_hit_blocks`` (radix-cache hit blocks
# mapped at admission) / ``prefill_tokens_saved`` (prompt tokens those
# hits skipped) / ``cow_copies`` (copy-on-write privatizations; 0 is
# the write-barrier invariant) and the instantaneous
# ``shared_blocks`` (physical blocks named by >= 2 live tables) — the
# measured form of the prefix cache's capacity/throughput claim
# (decode/prefix.py, DESIGN.md section 19).
# v8 (round 14): adds the "router" kind — one record per fleet-router
# decision (routed / handoff / migrated / shed, decode/fleet.py) with
# its own pinned required-key contract (ROUTER_REQUIRED); source and
# target carry engine ids (null where the decision has none — a routed
# request has no source engine, a shed request no target).
# v9 (round 15): the serving-SLO measurement layer. (1) completed
# "request" records additionally pin ``latency_s`` AND ``ttft_s``
# (time to first token; null when the first token predates a
# crash-resume — the decomposition is then visibly unreconstructable,
# never invented). (2) the "router" contract pins ``policy`` — WHY the
# router placed a request where it did (session / prefix /
# least_loaded / spill on routed records; null on decisions that have
# no placement policy), with the candidate scores the decision saw
# riding as an extra key — and handoff/migrated records carry the
# migration-stall instrumentation (``blocks`` / ``bytes`` /
# ``duration_s`` measured around export_sequence/import_sequence).
# (3) adds the "fleet" kind — one per-round fleet health record
# (per-engine waiting/active/free-blocks/utilization + a
# load-imbalance scalar, decode/fleet.py) with its own pinned
# required-key contract (FLEET_REQUIRED).
# v10 (round 16): the process-boundary transport layer. "router"
# handoff/migrated records now PIN the move instrumentation —
# ``blocks`` / ``bytes`` / ``duration_s`` (extras since v9) plus the
# new ``transport`` attribution object ({mode: inproc|wire|replay,
# bytes: the SERIALIZED npz size — what actually crosses the boundary,
# never an in-memory nbytes sum; crc_verify_s: wire integrity-check
# wall clock, null off the wire; retries: wire rejections this uid
# survived before the move}) — enforced conditionally by
# validate_record (the REQUEST_COMPLETED_REQUIRED pattern: routed/shed
# decisions move nothing, so pinning kind-wide would force meaningless
# nulls). A rejected wire doc (CRC mismatch / torn npz / version skew,
# runtime/wire.py) emits a ``wire_rejected`` router record whose
# ``reason`` carries the one-line rejection.
# v11 (round 17): the live-weight hot-swap layer (DESIGN.md
# section 23). (1) adds the "deploy" kind — one record per rolling-
# deploy lifecycle event (started / engine_swapped / completed /
# rolled_back, decode/fleet.py) pinning the version pair
# (``from_version``/``to_version``); ``engine_swapped`` additionally
# pins ``engine``, ``completed`` and ``rolled_back`` pin
# ``duration_s``, and ``rolled_back`` pins ``reason`` (the one-line
# named cause + the ``latest_verified_step`` fallback) — enforced
# conditionally per event (the REQUEST_COMPLETED_REQUIRED pattern).
# (2) every "request" record grows ``weights_version`` — the uid's
# weights-version pin (null before first admission), the per-version
# attribution mixed-version fleet reports dedup completions by.
# v12 (round 18): the fleet trace spine (DESIGN.md section 24).
# Every per-request record kind — "request", "span", "router" — PINS
# ``trace_id``: the fleet-unique causal identity minted ONCE at
# admission (the router under a fleet, the engine itself single-
# engine) and carried through replay, preemption, quarantine,
# migration (handoff doc v5), crash-resume (snapshot v7), and version
# pins — so ``report --trace UID`` stitches one cross-engine,
# cross-process waterfall by the id itself instead of uid heuristics.
# Null only where the record concerns no traceable request (the
# anonymous rejected uid -1). "deploy" records pin the key too (the
# issue's uniform-envelope stance) with a null value — a deploy event
# concerns the fleet, not one request. Transport cost attribution
# rides the existing "event" kind (``transport_stats``: per-worker
# per-op RPC call/handle durations, decode/fleet.py) and the live
# status doc (STATUS_FILENAME) is a wire-published JSON document, not
# a stream record.
# v13 (round 19): the trace-driven workload plane (DESIGN.md
# section 25). (1) every "request" AND "span" record pins ``tenant``
# — the request's tenant tag (null single-tenant; null on the
# anonymous rejected uid -1), minted at submit and carried through
# replay, preemption, migration (handoff doc v6), and crash-resume
# (snapshot v8) exactly like ``trace_id`` — the per-tenant
# attribution ``report``'s workload block and the per-tenant SLO
# slice fold. (2) adds the "workload" kind — one record per replay
# interval from the workload driver (``decode/workload_driver.py``):
# ``trace`` pins the trace identity ({id, version} — the
# runtime/workload.py header), ``offered``/``admitted`` the
# PER-INTERVAL submission counts (offered - admitted = sheds this
# interval), and ``tenants`` the CUMULATIVE per-tenant
# offered/completed/shed counts (monotonic across the run, so the
# final record is the totals and the sums reconcile against the
# per-request records — pinned by test).
# v14 (round 20): the closed control loop (DESIGN.md section 26).
# (1) adds the "autoscale" kind — one record per decode-tier scale
# decision from the between-rounds controller
# (``decode/autoscale.py``): ``step`` the router's round clock,
# ``event`` one of AUTOSCALE_EVENTS (scale_up / scale_down / held),
# ``reason`` the named trigger (queue_pressure / queue_idle /
# below_min_floor / cooldown), ``engines`` the alive decode count
# AFTER the decision, ``target_engines`` what the controller wants.
# ``scale_up`` conditionally pins ``engine`` (the spawned id);
# ``scale_down`` pins ``engine`` + ``drained`` (the zero-shed drain's
# migrated-resident count) — the DEPLOY_EVENT_REQUIRED pattern.
# (2) adds the "qos" kind — one record per tenant-QoS scheduling
# decision (``decode/engine.py``): ``step`` the engine step, ``event``
# one of QOS_EVENTS, ``tenant`` the tenant acted on (null
# single-tenant). Per-event pins: ``predicted_miss_shed`` carries
# ``uid``/``eta_steps``/``deadline_steps`` (the admission-time ETA
# that blew the deadline), ``budget_deferred`` carries
# ``uid``/``resident_tokens``/``token_budget`` (the budget that
# deferred the admit), ``wfq_pick`` carries ``uid``/``virtual_time``
# (the virtual-time value that won a NON-head-of-line admit).
# Every pinned value is derived from the deterministic round/step
# clocks and served-token counters — never the wall clock — so qos
# and autoscale decision streams replay identically with the tokens.
# v15 (round 21): the watchtower plane (DESIGN.md section 27). Adds
# the "alert" kind — one record per streaming-detector lifecycle
# transition (``runtime/watch.py``, ticked on the fleet round clock):
# ``step`` the router's round clock at the transition, ``event`` one
# of ALERT_EVENTS (fired / resolved), ``detector`` the detector that
# transitioned (ALERT_DETECTORS), ``severity`` its page/warn class,
# ``window`` the [start_round, end_round) round window that justified
# the transition. Per-detector conditional pins
# (ALERT_DETECTOR_REQUIRED, the QOS_EVENT_REQUIRED pattern): each
# alert carries exactly the numbers that justified it — the fast/slow
# burn rates with the violation/completion counts behind them, the
# queue depth vs its threshold, the imbalance reading, the stalled
# round count, the incident count, the drifted percentile vs its
# declared baseline. Every pinned value is ROUND-denominated (counts
# and round arithmetic only — wall clock lives in the unpinned ``t``
# envelope and in the latency_drift detector, which only runs against
# an explicitly declared wall-clock baseline), so the alert history of
# a virtual-clock replay is byte-identical across replays and
# transports, exactly like the autoscale/qos decision streams.
# v16 (round 22): the network boundary (DESIGN.md section 28). The
# router-record vocabulary gains the ``reconnected`` event — one
# record per transport reconnect (the liveness ladder's non-death
# verdict: a dropped connection that healed under bounded backoff and
# sequence-numbered replay, with ``attempts`` / ``gap_s`` / the
# replayed op list as extras and the anonymous uid -1 — a reconnect
# belongs to the link, not a request). ``transport.mode`` on move
# records gains "tcp" (a handoff streamed over the length-prefixed
# TCP side channel, CRC-verified at the target). ``migrated`` records
# now ALSO pin ``ship_s`` (the async-migration ship window: export to
# commit wall clock; null on a sync or replay migration — nothing
# overlapped) and ``catchup_tokens`` (tokens teacher-forced on the
# target after arrival: the delta emitted during an async ship
# window, the full replay length on a replay-migration, 0 on a sync
# handoff) — the numbers behind the "a handoff costs the moving
# request one replay, never a source-engine stall" contract.
# v17 (round 23): the KV memory hierarchy (DESIGN.md section 29).
# Decode records gain the ``kv_spill`` key family —
# ``spilled_blocks`` / ``spill_bytes`` / ``restores`` /
# ``restore_tokens_saved`` cumulative (snapshot-persisted, monotonic
# across crash-resume like the churn trio; the BYTES are not — the
# host tier dies with the process and resume rebuilds via replay),
# ``restore_stall_s`` the cumulative wall clock spent inside the
# donated implant path (the stall budget the restore-per-step cap
# bounds), ``partial_hits`` cumulative sub-block CoW shares, and
# ``host_tier_utilization`` the instantaneous spill-tier occupancy
# fraction (0.0 when the tier is off). All keys are pinned even with
# the tier disabled (zeros) — the uniform-envelope stance.
# v18 (PR 25): step phases. The span vocabulary gains ``engine_step``
# — ONE record per executed engine step (``decode/engine.py``), the
# only span that belongs to no request: ``uid`` is null (refused under
# every other span name), ``step`` == ``start_step`` the engine's
# global step, and the record pins ``phases`` — the step's host phases
# as ``[name, start_ns, end_ns]`` in the order they closed
# (``runtime/tracing.py`` PhaseTimer has the vocabulary and each
# phase's class) — with ``start_ns`` / ``end_ns`` of the parent span
# on the same ``time.time_ns()`` clock — and ``tokens_generated``
# after the step, what a reader joins a step on. Per-request readers
# skip the record.
# v19 (PR 37): every dispatch says which program it ran. The
# ``engine_step`` record pins ``dispatches``: the step programs the
# step launched as ``[kind, bucket]`` in launch order, the i-th entry
# belonging to the i-th ``*.dispatch`` phase of ``phases`` and the
# ``*.readback`` after it (``runtime/tracing.py`` has the contract), so
# as many entries as the step has ``*.dispatch`` phases. Each key of
# the record has a reader (``PERF.md`` section 3 names it); the bump
# dropped none.
# v20 (PR 38): a step's results are read one step late. The engine
# launches a step's program and only then reads the program launched
# before it, so a ``*.readback`` phase no longer follows its own
# ``*.dispatch``: the ``engine_step`` record pins ``readbacks``, the
# ordinals (among the engine's launches, from 0) of the launches its
# ``*.readback`` phases read, the i-th entry the i-th phase's, and
# ``launches``, the engine's launches up to and with the step's own,
# so the record's ``dispatches`` are the ordinals ``launches -
# len(dispatches) ..< launches`` and a readback below them lies a step
# (or more) after its launch (``runtime/tracing.py`` has the
# contract). The expert counters of a record are those of the results
# it READ. Readers: ``report.py``'s programs table,
# ``benchmark/layer_metrics/late_read_share.offline.py``.
# v21 (PR 39): a third paged kind — the ``engine_step`` record may carry
# the cache reads' counters (``STEP_SPAN_WINDOW``; the engine writes all
# four, 0 for a model with no window layer): ``window_rows`` /
# ``full_rows``, the cached positions the rows the step LAUNCHED attend
# over in a window layer and in a full one (a chunk's one view counted
# once), ``window_blocks_released`` (window blocks that left a sequence
# in the step: overwritten in its ring, or handed back with its slot)
# and ``window_blocks_live`` (held at the step's end). All four or
# none, whole and not negative, and no more positions in a window layer
# than in a full one (``validate_record``). Readers: ``report.py``'s
# cache-reads line, ``benchmark/window_trace.py`` (the two attention
# rooflines, ``window_pool_util``).
# v22 (PR 40): the decode-side read of the full kind walks each row's
# live blocks — the ``engine_step`` record may carry how much it
# fetched (``STEP_SPAN_KV``; the engine writes both): ``kv_blocks_read``,
# the pool's blocks the reads of the rows the step LAUNCHED in its
# ``decode`` / ``mixed`` / ``verify`` programs fetched, over the pool's
# layers (a row's live blocks where the read walks its table, a padded
# row the scratch block; every table's capacity where it gathers:
# ``decode/paged.py::walks``), and ``kv_blocks_capacity``, what a gather
# of every such row's whole table reads. Both or none, whole, not
# negative, no more read than the capacity (``validate_record``); the
# ``decode`` record carries the two as cumulative extras. Readers:
# ``report.py``'s cache-reads line.
# v23 (PR 41): a fourth paged kind, chunk-summarised attention, whose
# layer keeps two stores — the ``engine_step`` record may carry its
# counters (``STEP_SPAN_CHUNKS``; the engine writes both, 0 for a model
# with no chunked layer): ``summary_rows``, the chunk summaries the rows
# the step LAUNCHED attend over (one a chunk of every window before a
# row's own; a chunk's view counted once), and ``summaries_written``,
# the launched writes that finished a chunk. For such a model
# ``window_rows`` counts the positions of a row's own ALIGNED window.
# Both or none, whole and not negative (``validate_record``). Readers:
# ``report.py``'s cache-reads line, ``benchmark/chunk_trace.py`` (the
# two stores' rooflines, ``summary_rows_share``).
# v24 (PR 44): a window layer's ring is walked too — the ``engine_step``
# record may carry, beside the full kind's pair, the RING's
# (``STEP_SPAN_RING``; the engine writes both, 0 for a model with no
# window layer): ``ring_blocks_read``, the window pool's blocks the
# reads of the rows the step LAUNCHED fetched over its layers (where the
# read walks: from the block that holds a row's first attendable
# position, ``decode/paged.py::ring_start``, to the one it writes, a
# padded row the scratch block; every ring's entries where it gathers),
# and ``ring_blocks_capacity``, the entries of those rows' rings. Both
# or none, whole, not negative, no more read than the capacity
# (``validate_record``); the ``decode`` record carries the two as
# cumulative extras. Readers: ``report.py``'s cache-reads line.
# v24, additive (PR 49; no bump: a record without the group reads as
# before): each store's BYTES a position — the ``engine_step`` record
# and the ``decode`` record may carry ``kv_row_bytes`` and
# ``window_row_bytes`` (``STEP_SPAN_ROW_BYTES``; the engine writes
# both), what ONE cached position takes in ONE layer of the full kind's
# pool and of the window layers', its K row and its V row as the arrays
# hold them (the two sides' rows may differ in width, and the two
# stores' in KV heads: ``models/face.py::KVRow``); 0 for a store the
# model has not. Both or none, whole, not negative
# (``validate_record``). Readers: ``report.py``'s cache-reads line
# (bytes beside blocks), ``benchmark/sink_window_trace.py``.
# v25, additive (PR 52): a recurrent layer's BYTES a sequence — the
# ``engine_step`` record and the ``decode`` record may carry
# ``state_row_bytes`` and ``tail_row_bytes`` (``STEP_SPAN_STATE_ROW``;
# the engine writes both), what ONE sequence holds in ONE recurrent
# layer: its state (a Mamba scan state, a delta rule's matrix a head)
# and its convolution's last inputs, the two widths of
# ``models/face.py::StateRow``; 0 and 0 for a model with no recurrent
# layer. ``state_bytes`` is a step's launched rows times their sum over
# the recurrent layers, counted once. Both or none, whole, not negative
# (``validate_record``). Readers: ``report.py``'s state-row line.
SCHEMA_VERSION = 25

METRICS_FILENAME = "metrics.jsonl"

# the atomic fleet status document the router publishes each round
# (throttled; decode/fleet.py via wire.publish_json) — defined here so
# the router, the `fleetstat` entry point, and `report --follow` share
# one name without the readers importing the (jax-heavy) fleet module
STATUS_FILENAME = "fleet_status.json"

# router-side dead-host postmortem dumps (decode/fleet.py publishes
# one per declared-dead engine; report --postmortem discovers them by
# this prefix next to the router's metrics stream)
ROUTER_POSTMORTEM_PREFIX = "router_postmortem_"

# the flight-recorder dump the decode engine publishes next to the
# metrics stream (decode/engine.py writes it; report --postmortem
# discovers it) — defined here so the writer and the reader share one
# name without the report tool importing the (jax-heavy) engine
FLIGHT_FILENAME = "flight_recorder.json"

# The step-record contract: every "step" record carries exactly these
# keys (values may be null when a source can't measure them — a CPU run
# has no HBM stats, the FFN family has no scalar loss). Adding/removing
# a key REQUIRES a SCHEMA_VERSION bump; tests/test_telemetry.py pins
# the (version, key-set) pair.
STEP_KEYS = (
    "schema", "kind", "t", "step", "strategy", "loss", "grad_norm",
    "tokens_per_sec", "step_time_s", "mfu", "hbm_high_water_bytes",
)

# The anomaly-record contract: keys every "anomaly" record MUST carry
# (it may carry more — e.g. the [a, b] step window). Same version-bump
# discipline as STEP_KEYS.
ANOMALY_REQUIRED = ("step", "skipped", "loss_scale")

# The rollback-record contract: "rung" names the ladder rung taken
# (rollback / restart), "resume_step" the verified checkpoint it
# rewound to (null when none existed yet).
ROLLBACK_REQUIRED = ("rung", "resume_step")

# The decode-record contract: keys every "decode" record MUST carry
# (``tokens_per_sec`` may be null on a record with no throughput delta
# — the null stance of STEP_KEYS). ``batch_occupancy`` is active slots
# over max slots; ``kv_pool_utilization`` is NON-RECLAIMABLE
# non-scratch blocks over usable blocks (decode/engine.py) — refs-0
# prefix-cached blocks count as free since v7 (admission reclaims them
# on demand; the extra ``prefix_evictable_blocks`` key reconciles this
# reading with the literal free-list keys below). Same version-bump
# discipline as STEP_KEYS.
#
# v5 KV-pool internals (decode/engine.py ``telemetry_record``):
# ``free_blocks`` the instantaneous free count,
# ``free_blocks_low_water``/``free_blocks_high_water`` the min/max free
# count since the previous decode record (the pressure envelope a
# cadence record would otherwise alias over), ``block_allocs`` /
# ``block_frees`` / ``block_scrubs`` cumulative churn counters
# (snapshot-persisted, so they stay monotonic across crash-resume),
# ``kv_fragmentation`` the unused fraction of RESERVED block capacity
# (1 - live tokens / (live blocks * block_size); reserve-on-admit means
# a young sequence holds its whole reservation), and
# ``kv_bytes_stored`` the live-token KV bytes at the engine's dtype
# (``paged.kv_bytes_per_token`` — the roofline's kv_bytes numerator).
#
# v6 speculation keys (decode/engine.py verify dispatches):
# ``drafted_tokens`` / ``accepted_tokens`` cumulative (snapshot-
# persisted, monotonic across crash-resume like the churn trio) and
# ``accept_rate`` = accepted / drafted (null when nothing drafted —
# speculation off, or no drafter hits yet). Both count the LIVE
# n-gram drafter only: replay teacher-forced tokens are accepted by
# construction, so counting them would inflate accept_rate toward
# 1.0 on exactly the churn-heavy runs where the drafter's real score
# matters (and double-count across a crash-resume).
# v7 shared-prefix keys (decode/engine.py ``telemetry_record``):
# ``prefix_hit_blocks`` / ``prefill_tokens_saved`` cumulative
# (snapshot-persisted, monotonic across crash-resume like the churn
# trio), ``shared_blocks`` the instantaneous >= 2-live-table block
# count, ``cow_copies`` cumulative copy-on-write privatizations (the
# tests pin 0 in steady state — no scheduler write ever aims at a
# shared block).
DECODE_REQUIRED = ("step", "tokens_per_sec", "batch_occupancy",
                   "kv_pool_utilization", "free_blocks",
                   "free_blocks_low_water", "free_blocks_high_water",
                   "block_allocs", "block_frees", "block_scrubs",
                   "kv_fragmentation", "kv_bytes_stored",
                   "drafted_tokens", "accepted_tokens", "accept_rate",
                   "prefix_hit_blocks", "prefill_tokens_saved",
                   "shared_blocks", "cow_copies",
                   "spilled_blocks", "spill_bytes", "restores",
                   "restore_tokens_saved", "restore_stall_s",
                   "partial_hits", "host_tier_utilization")

# The request-record contract: one record per serving-request lifecycle
# transition (``decode/engine.py``). ``step`` is the GLOBAL engine step
# (snapshot ``step_base`` + in-process steps — stable across
# crash-resume), ``uid`` the request's sequence uid, ``event`` the
# transition (admitted / preempted / retried / quarantined / completed
# / rejected / expired), ``reason`` why (null where the transition
# needs none — e.g. admitted). Completed records additionally PIN
# (since v9) ``latency_s`` (submit -> finish wall clock; the report
# tool's per-request latency percentiles read it) and ``ttft_s``
# (submit -> first emitted token; null when the first token predates a
# crash-resume, in which case the decomposition is honestly
# unreconstructable). Same version-bump discipline as STEP_KEYS.
# v11: ``weights_version`` — the uid's weights-version pin (null
# before first admission pins it; the anonymous rejected uid -1 is
# always null) — so a mixed-version fleet's per-version completion
# counts are recorded data, not inference.
# v12: ``trace_id`` — the request's fleet-unique causal identity
# (minted once at admission, carried through every move; null only on
# the anonymous rejected uid -1).
# v13: ``tenant`` — the request's tenant tag (null single-tenant and
# on the anonymous rejected uid -1), set at submit and carried like
# ``trace_id`` — the per-tenant accounting key the workload plane
# slices on.
REQUEST_REQUIRED = ("step", "uid", "event", "reason",
                    "weights_version", "trace_id", "tenant")

# the extra keys a COMPLETED request record must also carry (v9) —
# enforced conditionally by validate_record (other events never
# measure a completion, so pinning them kind-wide would force
# meaningless nulls onto every admitted/preempted/... record)
REQUEST_COMPLETED_REQUIRED = ("latency_s", "ttft_s")

# The span-record contract (``runtime/tracing.py``): one record per
# CLOSED per-request lifecycle span. ``span`` names the phase (queued /
# prefill / replay / decode / quarantine / preempt_gap), ``step`` the
# GLOBAL engine step the span closed at, ``start_step`` where it
# opened, ``duration_s`` its wall-clock length. Spans tile a request's
# life (each opens exactly when its predecessor closes, the first at
# submit time), so a completed request's span durations sum to its
# ``latency_s`` — the reconciliation ``report``'s waterfall view pins.
# Replayed spans after a snapshot-resume restart are deduplicated by
# ``(uid, span, start_step, step)``, the request-record dedup stance.
# v12: ``trace_id`` — the owning request's causal identity (the
# stitch key of the cross-process trace waterfall).
# v13: ``tenant`` — the owning request's tenant tag (null
# single-tenant), so per-tenant ITL percentiles come straight off the
# decode-segment spans.
# v18: ``engine_step`` spans belong to a step, not a request: null
# ``uid``, and STEP_SPAN_REQUIRED on top (validate_record); v19 adds
# ``dispatches`` to it, one entry a ``*.dispatch`` phase; v20
# ``readbacks``, one entry a ``*.readback`` phase, and ``launches``.
# Same version-bump discipline as STEP_KEYS.
SPAN_REQUIRED = ("step", "uid", "span", "start_step", "duration_s",
                 "trace_id", "tenant")

# The span vocabulary (runtime/tracing.py callers use these; report
# renders any name, so a new phase is additive)
SPAN_NAMES = ("queued", "prefill", "replay", "decode", "quarantine",
              "preempt_gap", "engine_step")

# the one span that belongs to a STEP, not a request (v18): null uid,
# and the extra keys it must carry (v19: ``dispatches``; v20:
# ``readbacks``, ``launches``)
STEP_SPAN = "engine_step"
STEP_SPAN_REQUIRED = ("phases", "start_ns", "end_ns", "dispatches",
                      "readbacks", "launches")
# ... and the four it carries together or not at all (v21)
STEP_SPAN_WINDOW = ("window_rows", "full_rows", "window_blocks_released",
                    "window_blocks_live")
# ... and the two of the decode-side reads' blocks, likewise (v22)
STEP_SPAN_KV = ("kv_blocks_read", "kv_blocks_capacity")
# ... and the same two of a window layer's ring (v24)
STEP_SPAN_RING = ("ring_blocks_read", "ring_blocks_capacity")
# ... and a chunked layer's two, likewise (v23)
STEP_SPAN_CHUNKS = ("summary_rows", "summaries_written")
# ... and each store's bytes a position a layer (v24, additive)
STEP_SPAN_ROW_BYTES = ("kv_row_bytes", "window_row_bytes")
# ... and a recurrent layer's bytes a sequence (v25, additive)
STEP_SPAN_STATE_ROW = ("state_row_bytes", "tail_row_bytes")

# The router-record contract (``decode/fleet.py``): one record per
# fleet-router decision. ``step`` is the ROUTER's step clock (fleet
# scheduling rounds — each engine keeps its own engine-step clock),
# ``uid`` the fleet-global request uid, ``event`` the decision
# (routed / handoff / migrated / shed), ``source``/``target`` the
# engine ids involved — null where the decision has none: a freshly
# routed request has no source engine, a shed request no target.
# ``reason`` rides as an extra key (least_loaded / session / prefix /
# pool_pressure / engine_killed / queue_full).
#
# v9 decision attribution: ``policy`` is pinned — the placement policy
# a ``routed`` decision took (one of ROUTER_POLICIES; null on events
# that place nothing: handoff / migrated / shed) — and routed records
# carry ``candidates`` as an extra (the per-engine scores the decision
# saw: warm-block depth, queue depth, active slots, pool utilization).
# ``handoff``/``migrated`` records carry the migration-stall
# instrumentation as extras: ``blocks`` / ``bytes`` shipped and
# ``duration_s`` measured around export_sequence/import_sequence
# (0 blocks/bytes on a replay-migration off a dead engine's snapshot —
# nothing ships but the token history). Same version-bump discipline
# as STEP_KEYS.
# v12: ``trace_id`` — the moved/placed request's causal identity.
ROUTER_REQUIRED = ("step", "uid", "event", "source", "target", "policy",
                   "trace_id")

# The router decision vocabulary (decode/fleet.py emits these; report
# renders any name, so a new decision kind is additive).
# ``wire_rejected`` (v10): a handoff wire doc failed integrity checks
# (reason = the one-line WireError) and the request was replay-rerouted
# ``reconnected`` (v16): a dropped worker connection healed under the
# reconnect ladder instead of becoming a dead-host declaration
ROUTER_EVENTS = ("routed", "handoff", "migrated", "shed",
                 "wire_rejected", "reconnected")

# the extra keys a HANDOFF or MIGRATED router record must also carry
# (v10) — the migration-stall + transport attribution, enforced
# conditionally by validate_record (other router events move nothing)
ROUTER_MOVE_REQUIRED = ("blocks", "bytes", "duration_s", "transport")

# the extra keys a MIGRATED record must ALSO carry (v16) — the async-
# migration contract: how long the snapshot shipped while the source
# kept decoding (``ship_s``, null when nothing overlapped) and how
# many tokens the target teacher-forced to catch up
# (``catchup_tokens``) — enforced conditionally by validate_record
ROUTER_MIGRATED_REQUIRED = ("ship_s", "catchup_tokens")

# The routed-record policy vocabulary: session / prefix affinity,
# least-loaded admission, or spill (the probed target shed and the
# request landed on the next engine by load — affinity lost)
ROUTER_POLICIES = ("session", "prefix", "least_loaded", "spill")

# The fleet-health-record contract (``decode/fleet.py``): one record
# per fleet scheduling round from the router's own writer. ``step`` is
# the router's round clock, ``engines`` maps engine id -> per-engine
# health ({alive, role, waiting, active, free_blocks, utilization};
# dead engines report {alive: false}), ``load_imbalance`` is the
# (max - min) / max load spread over alive decode engines (load =
# active + waiting; 0.0 = balanced or idle, -> 1.0 = one engine holds
# everything). Same version-bump discipline as STEP_KEYS.
FLEET_REQUIRED = ("step", "engines", "load_imbalance")

# The deploy-record contract (``decode/fleet.py`` rolling_deploy,
# v11): one record per rolling-deploy lifecycle event. ``step`` is the
# router's round clock, ``event`` one of DEPLOY_EVENTS,
# ``from_version``/``to_version`` the weights-version pair (the
# checkpoint step being deployed; ``to_version`` may be null when no
# checkpoint was ever published). Per-event conditional pins (the
# REQUEST_COMPLETED_REQUIRED pattern, enforced by validate_record):
# ``engine_swapped`` carries ``engine``; ``completed`` and
# ``rolled_back`` carry ``duration_s``; ``rolled_back`` carries
# ``reason`` — the ONE-line named cause naming the CRC rejection or
# mid-roll failure plus the latest_verified_step fallback. Same
# version-bump discipline as STEP_KEYS.
# v12: ``trace_id`` pinned for the uniform per-kind envelope — always
# null (a deploy event concerns the fleet, not one request; the
# per-request deploy-drain moves carry theirs on ``migrated`` router
# records).
DEPLOY_REQUIRED = ("step", "event", "from_version", "to_version",
                   "trace_id")

# the deploy lifecycle vocabulary (report renders any name; a new
# event is additive)
DEPLOY_EVENTS = ("started", "engine_swapped", "completed",
                 "rolled_back")

# per-event conditional pins for deploy records (validate_record)
DEPLOY_EVENT_REQUIRED = {
    "engine_swapped": ("engine",),
    "completed": ("duration_s",),
    "rolled_back": ("duration_s", "reason"),
}

# The workload-record contract (``decode/workload_driver.py``, v13):
# one record per trace-replay interval. ``step`` is the driver's
# virtual round clock at emit time, ``trace`` the trace identity
# ({id, version} — the runtime/workload.py header's stable hash, so
# two replays of one trace pin the same identity), ``offered`` /
# ``admitted`` the PER-INTERVAL submission counts (offered - admitted
# = sheds this interval), ``tenants`` the CUMULATIVE per-tenant
# {offered, completed, shed} counts (monotonic — the final record is
# the run's totals, and the per-tenant sums must reconcile with the
# request records' per-tenant counts). Same version-bump discipline
# as STEP_KEYS.
WORKLOAD_REQUIRED = ("step", "trace", "offered", "admitted",
                     "tenants")

# The autoscale-record contract (``decode/autoscale.py``, v14): one
# record per decode-tier scale decision. ``step`` is the router's
# round clock, ``event`` one of AUTOSCALE_EVENTS, ``reason`` the named
# trigger, ``engines`` the alive decode-engine count AFTER the
# decision, ``target_engines`` the controller's target. Deterministic
# by construction (round clock + queue-depth counters — wall clock
# only in the unpinned ``t`` envelope and extras like ``spawn_s``), so
# the decision stream replays identically with the tokens. Same
# version-bump discipline as STEP_KEYS.
AUTOSCALE_REQUIRED = ("step", "event", "reason", "engines",
                      "target_engines")

# the autoscale decision vocabulary (report renders any name; a new
# event is additive)
AUTOSCALE_EVENTS = ("scale_up", "scale_down", "held")

# per-event conditional pins for autoscale records (validate_record;
# the DEPLOY_EVENT_REQUIRED pattern): only a scale names the engine it
# spawned/drained, and only a scale-down measures a drain
AUTOSCALE_EVENT_REQUIRED = {
    "scale_up": ("engine",),
    "scale_down": ("engine", "drained"),
}

# The qos-record contract (``decode/engine.py``, v14): one record per
# tenant-QoS scheduling decision. ``step`` is the GLOBAL engine step,
# ``event`` one of QOS_EVENTS, ``tenant`` the tenant acted on (null
# single-tenant). Same version-bump discipline as STEP_KEYS.
QOS_REQUIRED = ("step", "event", "tenant")

# the qos decision vocabulary (report renders any name; a new event is
# additive)
QOS_EVENTS = ("predicted_miss_shed", "budget_deferred", "wfq_pick")

# per-event conditional pins for qos records (validate_record): each
# decision pins exactly the numbers that justified it — the ETA that
# blew the deadline, the budget that deferred, the virtual time that
# won a non-FIFO admit
QOS_EVENT_REQUIRED = {
    "predicted_miss_shed": ("uid", "eta_steps", "deadline_steps"),
    "budget_deferred": ("uid", "resident_tokens", "token_budget"),
    "wfq_pick": ("uid", "virtual_time"),
}

# The alert-record contract (``runtime/watch.py``, v15): one record
# per detector lifecycle transition. ``step`` is the router's round
# clock at the transition, ``event`` one of ALERT_EVENTS, ``detector``
# the detector name, ``severity`` its class, ``window`` the
# [start_round, end_round) window the justifying numbers were folded
# over. Deterministic by construction (round clock + integer counters
# — wall clock only in the unpinned ``t`` envelope), so the alert
# history replays identically with the tokens; the one wall-clock
# detector (latency_drift) only runs against an explicitly declared
# baseline. Same version-bump discipline as STEP_KEYS.
ALERT_REQUIRED = ("step", "event", "detector", "severity", "window")

# the alert lifecycle vocabulary: a detector FIRES once when its
# windows cross threshold and RESOLVES once when they recover — never
# a per-round repeat (report renders any name; a new event is
# additive)
ALERT_EVENTS = ("fired", "resolved")

# the detector vocabulary (runtime/watch.py; report renders any name,
# so a new detector is additive)
ALERT_DETECTORS = ("burn_rate", "queue_growth", "imbalance",
                   "collapse", "incident_rate", "latency_drift")

# the severity vocabulary: "page" = goodput is burning NOW (SLO
# budget, dead capacity, stalled tokens), "warn" = trending toward it
ALERT_SEVERITIES = ("warn", "page")

# per-detector conditional pins for alert records (validate_record;
# the QOS_EVENT_REQUIRED pattern): every transition pins exactly the
# numbers that justified it, on BOTH fired and resolved records (the
# resolved record shows the recovered reading)
ALERT_DETECTOR_REQUIRED = {
    "burn_rate": ("burn_fast", "burn_slow", "violations",
                  "completions"),
    "queue_growth": ("waiting", "threshold"),
    "imbalance": ("imbalance", "threshold"),
    "collapse": ("stalled_rounds", "live"),
    "incident_rate": ("incidents", "threshold"),
    "latency_drift": ("p95_s", "baseline_s", "metric"),
}

# Non-step record kinds the stream also carries: run headers ("meta"),
# recovery/chaos/checkpoint events ("event"), measurement rows
# ("bench": no writer is left in the tree, ROADMAP C2; the benchmark's
# numbers go to the driver's ledger), the self-healing kinds
# ("anomaly", "rollback"), and the
# serving engine's "decode" cadence + "request" lifecycle + "span"
# per-request phase records.
RECORD_KINDS = ("step", "meta", "event", "bench", "anomaly", "rollback",
                "decode", "request", "span", "router", "fleet",
                "deploy", "workload", "autoscale", "qos", "alert")

# kind -> the pinned required-key set validate_record enforces (step
# records additionally pin their FULL key set via STEP_KEYS)
REQUIRED_KEYS = {
    "step": STEP_KEYS,
    "anomaly": ANOMALY_REQUIRED,
    "rollback": ROLLBACK_REQUIRED,
    "decode": DECODE_REQUIRED,
    "request": REQUEST_REQUIRED,
    "span": SPAN_REQUIRED,
    "router": ROUTER_REQUIRED,
    "fleet": FLEET_REQUIRED,
    "deploy": DEPLOY_REQUIRED,
    "workload": WORKLOAD_REQUIRED,
    "autoscale": AUTOSCALE_REQUIRED,
    "qos": QOS_REQUIRED,
    "alert": ALERT_REQUIRED,
}

# bf16 peak matmul FLOP/s by chip generation (public spec sheets; the
# default f32 jnp matmul on TPU lowers to single-pass bf16 MXU ops, so
# bf16 peak is the honest MFU denominator of the step records' `mfu`;
# the benchmark keeps its own table, benchmark/peaks.json). Unknown kinds (CPU, new chips) return None: an honest null
# MFU beats a guessed one in a persistent artifact.
PEAK_BF16_FLOPS = {
    "v2": 45e12, "v3": 123e12, "v4": 275e12,
    "v5 lite": 197e12, "v5e": 197e12, "v5p": 459e12, "v5": 459e12,
    "v6 lite": 918e12, "v6e": 918e12,
}


def peak_flops(device_kind: str) -> float | None:
    """bf16 peak FLOP/s for a ``device_kind`` string, or None when the
    chip generation is unrecognized (CPU hosts, future TPUs)."""
    kind = (device_kind or "").lower()
    for key in sorted(PEAK_BF16_FLOPS, key=len, reverse=True):
        if key in kind:
            return PEAK_BF16_FLOPS[key]
    return None


def ffn_model_flops(tokens: int, model_size: int, n_layers: int,
                    ffn_dim: int | None = None) -> int:
    """Hand-counted model matmul FLOPs of ONE training step of the
    reference FFN stack: fwd 2 matmuls = 4Tdf, bwd 4 matmuls = 8Tdf per
    layer (the 12Tdf convention — the recompute policy's extra
    executed matmul is HFU, never MFU; ``benchmark/flops.py`` counts
    the products a step has to run, two fewer a stack)."""
    f = 4 * model_size if ffn_dim is None else ffn_dim
    return 12 * tokens * model_size * f * n_layers


def transformer_model_flops(tokens: int, model_size: int, n_layers: int,
                            seq_len: int) -> int:
    """Per-step model FLOPs of the pre-LN transformer family:
    attention projections 8Td^2, scores+AV 2T^2d (no causal halving),
    FFN 16Td^2; fwd 1x + bwd 2x."""
    b = tokens // seq_len
    per_layer = (8 * seq_len * model_size ** 2
                 + 2 * seq_len ** 2 * model_size
                 + 16 * model_size ** 2 * seq_len)
    return 3 * b * n_layers * per_layer


def lm_model_flops(tokens: int, model_size: int, n_layers: int,
                   seq_len: int, vocab: int) -> int:
    """Transformer blocks + the tied LM head (2TdV, fwd 1x + bwd 2x)."""
    return (transformer_model_flops(tokens, model_size, n_layers, seq_len)
            + 3 * 2 * tokens * model_size * vocab)


def hand_flops_per_step(family: str, *, tokens: int, model_size: int,
                        n_layers: int, seq_len: int = 0,
                        vocab: int = 0) -> int | None:
    """The hand FLOP count for a CLI model family, or None for families
    without an agreed accounting yet (MoE variants: routed FLOPs depend
    on capacity/dropping, so a static count would be dishonest)."""
    if family == "ffn":
        return ffn_model_flops(tokens, model_size, n_layers)
    if family == "transformer" and seq_len:
        return transformer_model_flops(tokens, model_size, n_layers,
                                       seq_len)
    if family == "lm" and seq_len and vocab:
        return lm_model_flops(tokens, model_size, n_layers, seq_len, vocab)
    return None


def hbm_high_water() -> dict[str, int] | None:
    """Per-device HBM high-water (``peak_bytes_in_use``) from
    ``memory_stats()``, or None where the backend doesn't track it
    (CPU). Keys are device ids as strings (JSON object keys)."""
    import jax
    stats = {}
    for d in jax.devices():
        try:
            m = d.memory_stats()
        except Exception:  # noqa: BLE001 — per-backend API surface
            m = None
        if not m:
            continue
        peak = m.get("peak_bytes_in_use", m.get("bytes_in_use"))
        if peak is not None:
            stats[str(d.id)] = int(peak)
    return stats or None


def _json_default(o):
    """Last-resort JSON coercion for event payloads from other
    subsystems: numpy scalars/arrays become numbers/lists, anything
    else its repr — a stringly-typed field beats a dropped record."""
    if isinstance(o, np.integer):
        return int(o)
    if isinstance(o, np.floating):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    return repr(o)


def _scalar(v) -> float | None:
    """Host float of a (possibly device) scalar — the readback the
    writer thread performs OFF the training thread."""
    if v is None:
        return None
    try:
        # NaN/Inf pass through deliberately: a poisoned loss is exactly
        # what a chaos-run record should show (json round-trips them)
        return float(np.asarray(v))
    except (TypeError, ValueError):
        return None


class TelemetryWriter:
    """Non-blocking JSONL metrics writer.

    ``step()``/``event()``/``bench()`` enqueue and return immediately;
    a daemon thread performs device readbacks (``float()`` of any jax
    scalar in the record) and the file append. ``close()`` drains the
    queue — records enqueued before close are never lost (the flush is
    the batched host sync, at call sites that already sync).

    One writer owns one ``metrics.jsonl``; a fresh writer APPENDS (a
    supervised run restarts the process mid-stream — the record stream
    spans attempts, which is exactly what the report tool wants).
    """

    def __init__(self, metrics_dir: str, meta: dict | None = None,
                 filename: str = METRICS_FILENAME):
        os.makedirs(metrics_dir, exist_ok=True)
        self.path = os.path.join(metrics_dir, filename)
        self._q: queue.Queue = queue.Queue()
        self._err: str | None = None
        self._closed = False
        self._thread = threading.Thread(target=self._drain, daemon=True)
        self._thread.start()
        # a run that dies mid-stream (supervise exhausting its restarts,
        # an uncaught trainer error) must not lose its tail — the final
        # fault evidence is exactly what the report tool folds. close()
        # is idempotent, so the normal explicit close stays cheap.
        import atexit
        atexit.register(self.close)
        if meta is not None:
            self.meta(meta)

    # -- producers (training thread; never block on I/O or readbacks) --

    def step(self, step: int, *, strategy=None, loss=None, grad_norm=None,
             step_time_s=None, tokens=None, model_flops=None,
             peak=None, hbm=None, t=None) -> None:
        """Enqueue one per-logged-step record. ``strategy`` names the
        trainer the step belongs to (multi-method CLI runs share one
        stream); ``loss``/``grad_norm`` may be live device scalars (read
        back on the writer thread); ``tokens``/``model_flops`` are
        per-step counts from which throughput and MFU are derived;
        ``hbm`` is a pre-collected ``hbm_high_water()`` dict (collect it
        at the logging cadence — it is itself a host call)."""
        self._put({"kind": "step", "t": time.time() if t is None else t,
                   "step": int(step), "strategy": strategy, "loss": loss,
                   "grad_norm": grad_norm,
                   "step_time_s": step_time_s, "_tokens": tokens,
                   "_model_flops": model_flops, "_peak": peak,
                   "hbm_high_water_bytes": hbm})

    def event(self, record: dict) -> None:
        """Enqueue a recovery/chaos/checkpoint event record (the
        supervise/checkpoint ``on_event`` stream, verbatim plus the
        schema envelope)."""
        rec = dict(record)
        rec.setdefault("t", time.time())
        rec["kind"] = "event"
        self._put(rec)

    def bench(self, record: dict) -> None:
        """Enqueue one measurement row (metric name, value, unit,
        shape). Nothing in the tree calls it since the bench scripts
        went (ROADMAP C2)."""
        rec = dict(record)
        rec.setdefault("t", time.time())
        rec["kind"] = "bench"
        self._put(rec)

    def anomaly(self, record: dict) -> None:
        """Enqueue one in-graph guardrail anomaly record: the per-chunk
        skip/overflow counters + live loss scale
        (``runtime/guardrails.py``; ``ANOMALY_REQUIRED`` contract)."""
        rec = dict(record)
        rec.setdefault("t", time.time())
        rec["kind"] = "anomaly"
        self._put(rec)

    def rollback(self, record: dict) -> None:
        """Enqueue one supervisor ladder record (a rollback or restart
        rung, ``runtime/failure.py``; ``ROLLBACK_REQUIRED`` contract)."""
        rec = dict(record)
        rec.setdefault("t", time.time())
        rec.setdefault("resume_step", None)
        rec["kind"] = "rollback"
        self._put(rec)

    def decode(self, record: dict) -> None:
        """Enqueue one serving-engine cadence record: tokens/s, batch
        occupancy, KV-pool utilization (``decode/engine.py``;
        ``DECODE_REQUIRED`` contract)."""
        rec = dict(record)
        rec.setdefault("t", time.time())
        rec["kind"] = "decode"
        self._put(rec)

    def request(self, record: dict) -> None:
        """Enqueue one serving-request lifecycle record: admitted /
        preempted / retried / quarantined / completed / rejected /
        expired (``decode/engine.py``; ``REQUEST_REQUIRED`` contract)."""
        rec = dict(record)
        rec.setdefault("t", time.time())
        rec.setdefault("reason", None)
        rec.setdefault("weights_version", None)
        rec.setdefault("trace_id", None)
        rec.setdefault("tenant", None)
        rec["kind"] = "request"
        self._put(rec)

    def deploy(self, record: dict) -> None:
        """Enqueue one rolling-deploy lifecycle record: started /
        engine_swapped / completed / rolled_back
        (``decode/fleet.py``; ``DEPLOY_REQUIRED`` contract plus the
        per-event conditional pins)."""
        rec = dict(record)
        rec.setdefault("t", time.time())
        rec.setdefault("trace_id", None)
        rec["kind"] = "deploy"
        self._put(rec)

    def router(self, record: dict) -> None:
        """Enqueue one fleet-router decision record: routed / handoff /
        migrated / shed (``decode/fleet.py``; ``ROUTER_REQUIRED``
        contract — source/target/policy default to null so a caller
        only names the engines and the placement policy the decision
        involves)."""
        rec = dict(record)
        rec.setdefault("t", time.time())
        rec.setdefault("source", None)
        rec.setdefault("target", None)
        rec.setdefault("policy", None)
        rec.setdefault("trace_id", None)
        rec["kind"] = "router"
        self._put(rec)

    def workload(self, record: dict) -> None:
        """Enqueue one trace-replay interval record: trace identity,
        per-interval offered/admitted, cumulative per-tenant counts
        (``decode/workload_driver.py``; ``WORKLOAD_REQUIRED``
        contract)."""
        rec = dict(record)
        rec.setdefault("t", time.time())
        rec["kind"] = "workload"
        self._put(rec)

    def autoscale(self, record: dict) -> None:
        """Enqueue one decode-tier scale decision record: scale_up /
        scale_down / held (``decode/autoscale.py``;
        ``AUTOSCALE_REQUIRED`` contract plus the per-event conditional
        pins)."""
        rec = dict(record)
        rec.setdefault("t", time.time())
        rec["kind"] = "autoscale"
        self._put(rec)

    def qos(self, record: dict) -> None:
        """Enqueue one tenant-QoS scheduling decision record:
        predicted_miss_shed / budget_deferred / wfq_pick
        (``decode/engine.py``; ``QOS_REQUIRED`` contract plus the
        per-event conditional pins — tenant defaults to null, the
        single-tenant stance of request records)."""
        rec = dict(record)
        rec.setdefault("t", time.time())
        rec.setdefault("tenant", None)
        rec["kind"] = "qos"
        self._put(rec)

    def alert(self, record: dict) -> None:
        """Enqueue one watchtower detector transition record: fired /
        resolved (``runtime/watch.py``; ``ALERT_REQUIRED`` contract
        plus the per-detector conditional pins — severity defaults to
        "warn" so an experimental detector need not pick a page
        class)."""
        rec = dict(record)
        rec.setdefault("t", time.time())
        rec.setdefault("severity", "warn")
        rec["kind"] = "alert"
        self._put(rec)

    def fleet(self, record: dict) -> None:
        """Enqueue one per-round fleet health record: per-engine
        waiting/active/free-blocks/utilization plus the load-imbalance
        scalar (``decode/fleet.py``; ``FLEET_REQUIRED`` contract)."""
        rec = dict(record)
        rec.setdefault("t", time.time())
        rec["kind"] = "fleet"
        self._put(rec)

    def span(self, record: dict) -> None:
        """Enqueue one span record: a CLOSED per-request lifecycle
        phase (queued / prefill / replay / decode / quarantine /
        preempt_gap) or one executed engine step with its host phases
        (``engine_step``, null uid; ``runtime/tracing.py``;
        ``SPAN_REQUIRED`` contract). Callers pass ``t`` explicitly
        (the span's close time) so span sums reconcile with request
        latencies."""
        rec = dict(record)
        rec.setdefault("t", time.time())
        rec.setdefault("trace_id", None)
        rec.setdefault("tenant", None)
        rec["kind"] = "span"
        self._put(rec)

    def meta(self, record: dict) -> None:
        """Enqueue a run-header record (shapes, strategy, flags, paths
        to sibling logs — the report tool reads these to fold streams)."""
        rec = dict(record)
        rec.setdefault("t", time.time())
        rec["kind"] = "meta"
        self._put(rec)

    # -- lifecycle --

    def flush(self) -> None:
        """Block until every enqueued record is on disk."""
        self._q.join()

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.flush()
        self._q.put(None)
        self._thread.join(timeout=10)
        if self._err is not None:
            # telemetry never kills a run, but a lossy stream must not
            # stay silent either: name the last drop on the way out
            import sys
            print(f"telemetry: record(s) dropped while writing "
                  f"{self.path} (last error: {self._err})",
                  file=sys.stderr)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- writer thread --

    def _put(self, rec: dict) -> None:
        if self._closed:
            raise RuntimeError("TelemetryWriter is closed")
        rec["schema"] = SCHEMA_VERSION
        self._q.put(rec)

    def _finalize(self, rec: dict) -> dict:
        """Readbacks + derived fields — runs on the writer thread."""
        if rec.get("kind") == "step":
            rec["loss"] = _scalar(rec.get("loss"))
            rec["grad_norm"] = _scalar(rec.get("grad_norm"))
            rec["step_time_s"] = _scalar(rec.get("step_time_s"))
            tokens = rec.pop("_tokens", None)
            flops = rec.pop("_model_flops", None)
            peak = rec.pop("_peak", None)
            dt = rec["step_time_s"]
            rec["tokens_per_sec"] = (
                round(tokens / dt, 2) if tokens and dt else None)
            rec["mfu"] = (round(flops / dt / peak, 4)
                          if flops and dt and peak else None)
            # contract: a step record carries exactly STEP_KEYS
            rec = {k: rec.get(k) for k in STEP_KEYS}
        return rec

    def _drain(self) -> None:
        while True:
            rec = self._q.get()
            if rec is None:
                self._q.task_done()
                return
            try:
                # default=: event payloads originate in other subsystems
                # (checkpoint/supervise) and may carry numpy scalars —
                # coerce instead of dropping the record
                line = json.dumps(self._finalize(rec),
                                  default=_json_default)
                with open(self.path, "a") as f:
                    f.write(line + "\n")
            except Exception as e:  # noqa: BLE001 — telemetry never kills a run
                self._err = f"{type(e).__name__}: {e}"
            finally:
                self._q.task_done()


def validate_record(rec: Any) -> tuple[bool, str]:
    """Schema check for one parsed record: the envelope (``schema``,
    ``kind``, ``t``) on every record, plus the kind's pinned
    ``REQUIRED_KEYS`` contract. Every failure message is ONE line
    naming the record kind and the offending/missing key — the problems
    list a report renders must be actionable without opening the file."""
    if not isinstance(rec, dict):
        return False, "record is not a JSON object"
    kind = rec.get("kind")
    label = f"{kind} record" if kind in RECORD_KINDS else "record"
    if rec.get("schema") != SCHEMA_VERSION:
        return False, (f"{label}: key 'schema' is {rec.get('schema')!r}, "
                       f"expected {SCHEMA_VERSION} (version mismatch)")
    if kind not in RECORD_KINDS:
        return False, (f"record: key 'kind' is {kind!r}, not one of "
                       f"{RECORD_KINDS}")
    if "t" not in rec:
        return False, f"{label} missing key 't' (timestamp)"
    missing = [k for k in REQUIRED_KEYS.get(kind, ()) if k not in rec]
    if missing:
        return False, f"{label} missing required key(s) {missing}"
    if kind == "request" and rec.get("event") == "completed":
        # v9 conditional pin: only a completion measures a latency, so
        # the decomposition pair is required there and nowhere else
        missing = [k for k in REQUEST_COMPLETED_REQUIRED if k not in rec]
        if missing:
            return False, (f"request record (event completed) missing "
                           f"required key(s) {missing}")
    if kind == "span":
        # v18 conditional pins: a step's span names its phases and no
        # request; every other span is some request's
        if rec["span"] == STEP_SPAN:
            missing = [k for k in STEP_SPAN_REQUIRED if k not in rec]
            if missing:
                return False, (f"span record (span {STEP_SPAN}) missing "
                               f"required key(s) {missing}")
            for key, suffix in (("dispatches", ".dispatch"),
                                ("readbacks", ".readback")):
                n = sum(p[0].endswith(suffix) for p in rec["phases"])
                if len(rec[key]) != n:
                    return False, (f"span record (span {STEP_SPAN}) has "
                                   f"{len(rec[key])} {key!r} for {n} "
                                   f"'*{suffix}' phase(s)")
            if any(not 0 <= o < rec["launches"]
                   for o in rec["readbacks"]):
                return False, (f"span record (span {STEP_SPAN}) has "
                               f"'readbacks' {rec['readbacks']} outside "
                               f"its 'launches' ({rec['launches']})")
            got = [k for k in STEP_SPAN_WINDOW if k in rec]
            if got and (len(got) != len(STEP_SPAN_WINDOW) or any(
                    not isinstance(rec[k], int) or rec[k] < 0
                    for k in got) or rec["window_rows"] > rec["full_rows"]):
                return False, (f"span record (span {STEP_SPAN}) has the "
                               f"cache reads' counters "
                               f"{ {k: rec[k] for k in got} }: all of "
                               f"{list(STEP_SPAN_WINDOW)} or none, whole, "
                               "not negative, window_rows <= full_rows")
            for (read, held), what in (
                    (STEP_SPAN_KV, "decode-side reads' blocks"),
                    (STEP_SPAN_RING, "rings' blocks read")):
                got = [k for k in (read, held) if k in rec]
                if got and (len(got) != 2 or any(
                        not isinstance(rec[k], int) or rec[k] < 0
                        for k in got) or rec[read] > rec[held]):
                    return False, (
                        f"span record (span {STEP_SPAN}) has the {what} "
                        f"{ {k: rec[k] for k in got} }: both of "
                        f"{[read, held]} or none, whole, not negative, "
                        f"{read} <= {held}")
            for group, what in (
                    (STEP_SPAN_CHUNKS, "chunk summaries' counters"),
                    (STEP_SPAN_ROW_BYTES, "stores' bytes a position"),
                    (STEP_SPAN_STATE_ROW,
                     "recurrent layer's bytes a sequence")):
                got = [k for k in group if k in rec]
                if got and (len(got) != len(group) or any(
                        not isinstance(rec[k], int) or rec[k] < 0
                        for k in got)):
                    return False, (
                        f"span record (span {STEP_SPAN}) has the {what} "
                        f"{ {k: rec[k] for k in got} }: both of "
                        f"{list(group)} or none, whole, not negative")
        elif rec["uid"] is None:
            return False, (f"span record (span {rec['span']}) has a "
                           f"null 'uid': only {STEP_SPAN} belongs to "
                           "no request")
    if kind == "router" and rec.get("event") in ("handoff", "migrated"):
        # v10 conditional pin: only a move ships blocks/bytes and has a
        # transport to attribute — routed/shed records place or drop a
        # request without moving KV
        missing = [k for k in ROUTER_MOVE_REQUIRED if k not in rec]
        if missing:
            return False, (f"router record (event {rec['event']}) "
                           f"missing required key(s) {missing}")
    if kind == "router" and rec.get("event") == "migrated":
        # v16 conditional pin: every migration names its ship window
        # and catch-up cost — the async-migration contract's numbers
        missing = [k for k in ROUTER_MIGRATED_REQUIRED if k not in rec]
        if missing:
            return False, (f"router record (event migrated) missing "
                           f"required key(s) {missing}")
    if kind == "deploy" and rec.get("event") in DEPLOY_EVENT_REQUIRED:
        # v11 conditional pins: only a swap names an engine, only a
        # terminal event measures a duration, only a rollback has a
        # named reason — pinning kind-wide would force nulls
        missing = [k for k in DEPLOY_EVENT_REQUIRED[rec["event"]]
                   if k not in rec]
        if missing:
            return False, (f"deploy record (event {rec['event']}) "
                           f"missing required key(s) {missing}")
    if kind == "autoscale" and rec.get("event") in \
            AUTOSCALE_EVENT_REQUIRED:
        # v14 conditional pins: only a scale names the engine it
        # spawned/drained, only a scale-down measures a drain
        missing = [k for k in AUTOSCALE_EVENT_REQUIRED[rec["event"]]
                   if k not in rec]
        if missing:
            return False, (f"autoscale record (event {rec['event']}) "
                           f"missing required key(s) {missing}")
    if kind == "qos" and rec.get("event") in QOS_EVENT_REQUIRED:
        # v14 conditional pins: each qos decision carries exactly the
        # numbers that justified it
        missing = [k for k in QOS_EVENT_REQUIRED[rec["event"]]
                   if k not in rec]
        if missing:
            return False, (f"qos record (event {rec['event']}) "
                           f"missing required key(s) {missing}")
    if kind == "alert" and rec.get("detector") in \
            ALERT_DETECTOR_REQUIRED:
        # v15 conditional pins: every detector transition carries
        # exactly the numbers that justified it (fired AND resolved —
        # the resolved record shows the recovered reading)
        missing = [k for k in ALERT_DETECTOR_REQUIRED[rec["detector"]]
                   if k not in rec]
        if missing:
            return False, (f"alert record (detector {rec['detector']}) "
                           f"missing required key(s) {missing}")
    if kind == "step" and not isinstance(rec["step"], int):
        return False, (f"step record key 'step' is "
                       f"{type(rec['step']).__name__}, not int")
    return True, "ok"


def read_metrics(path: str) -> tuple[list[dict], list[str]]:
    """Parse a metrics JSONL: ``(records, problems)``. A torn final
    line (crash mid-append) is reported, not fatal; schema-invalid
    records are reported and skipped — the report tool renders what
    verifies and names what doesn't."""
    records, problems = [], []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                problems.append(f"line {i}: unparseable JSON "
                                "(torn write?)")
                continue
            ok, reason = validate_record(rec)
            if not ok:
                problems.append(f"line {i}: {reason}")
                continue
            records.append(rec)
    return records, problems


@dataclass(frozen=True)
class StepReport:
    """Static (compile-time) report of one training step program: the
    compiler's own cost/memory numbers and the lowered collective
    schedule in one object, cross-checked against the hand FLOP count.

    ``flops`` is XLA's ``cost_analysis()["flops"]`` (None where the
    backend doesn't report it); ``hand_flops`` is the model's
    hand-counted matmul FLOPs (the MFU numerator); ``flops_vs_hand``
    is their ratio — ~1x for saved-activation policies, >1x for
    recompute policies (executed > model FLOPs), and a number far from
    either flags a broken accounting before a single step runs."""

    collectives: dict[str, int] = field(default_factory=dict)
    flops: float | None = None
    bytes_accessed: float | None = None
    memory: dict[str, Any] | None = None
    hand_flops: int | None = None
    flops_vs_hand: float | None = None

    @classmethod
    def of(cls, fn: Callable, *args, hand_flops: int | None = None,
           **kwargs) -> "StepReport":
        """Lower + compile ``fn`` for ``args`` and fold the static
        analyses. One lowering feeds both the collective count and the
        compile (the ``utils/hlo.py`` helpers re-lower per call — this
        path does the work once)."""
        import jax

        from ..utils.hlo import count_collectives_text

        lowered = jax.jit(fn).lower(*args, **kwargs)
        collectives = {op: n for op, n
                       in count_collectives_text(lowered.as_text()).items()
                       if n}
        compiled = lowered.compile()
        flops = bytes_accessed = None
        try:
            cost = compiled.cost_analysis()
            # older jax returns a list of dicts (one per program)
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            if cost:
                flops = float(cost.get("flops", 0)) or None
                bytes_accessed = float(cost.get("bytes accessed", 0)) or None
        except Exception:  # noqa: BLE001 — per-backend API surface
            pass
        memory = None
        try:
            m = compiled.memory_analysis()
            if m is not None:
                memory = {
                    "argument_bytes": m.argument_size_in_bytes,
                    "output_bytes": m.output_size_in_bytes,
                    "temp_bytes": m.temp_size_in_bytes,
                    "peak_bytes": getattr(m, "peak_memory_in_bytes", None),
                }
        except Exception:  # noqa: BLE001
            pass
        ratio = (round(flops / hand_flops, 4)
                 if flops and hand_flops else None)
        return cls(collectives=collectives, flops=flops,
                   bytes_accessed=bytes_accessed, memory=memory,
                   hand_flops=hand_flops, flops_vs_hand=ratio)

    def as_dict(self) -> dict:
        return {"collectives": dict(self.collectives), "flops": self.flops,
                "bytes_accessed": self.bytes_accessed,
                "memory": self.memory, "hand_flops": self.hand_flops,
                "flops_vs_hand": self.flops_vs_hand}
