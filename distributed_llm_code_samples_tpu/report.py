"""`report` — fold one or more runs' telemetry streams into one
human-readable run report.

Inputs (all optional except at least one metrics dir):

- one or MORE metrics dirs (``report A B ...``): each is the JSONL a
  ``--metrics_dir`` run wrote (``runtime/telemetry.py`` schema).
  Serving runs stamp an ``engine_id`` in their meta records
  (``generate --engine_id``); the multi-stream merge keys per-engine
  stats on it (falling back to the dir basename) and folds every
  stream's events onto ONE wall-clock timeline — the per-engine
  latency/shed-percentile contract the fleet-scale router (ROADMAP
  item 3) is measured against,
- supervise's per-attempt JSONL (``runtime/failure.py``) — passed with
  ``--attempt_log`` or auto-discovered from each run's meta records,
- a profile directory (``--profile_dir``) captured with
  ``jax.profiler.trace`` — folded through ``utils/trace_analysis``
  into comm/compute overlap and per-named-scope region totals,
- ``--postmortem``: render each stream's flight-recorder dump
  (``decode/engine.py`` ``flight_recorder.json`` — the bounded ring of
  per-step scheduler digests persisted on quarantine/watchdog/kill),
- ``--slo TTFT_S:ITL_S``: goodput accounting (schema v9, DESIGN.md
  section 21) — SLO attainment over completed requests with each
  violation attributed to its dominant span (queued / prefill /
  replay / decode / preempt_gap / quarantine / migration), computed
  on the MERGED streams so a migrated request's life re-assembles
  across engines; crash-resumed requests render UNRECONCILED, never
  silently as attainment. Malformed specs reject rc 2.
- ``--trace UID``: ONE request's cross-engine, cross-process causal
  waterfall (schema v12, DESIGN.md section 24) — every span, router
  move, and lifecycle event for the uid across the merged streams,
  stitched by its ``trace_id`` (minted once at admission, carried
  through migration/replay/crash-resume) instead of uid heuristics,
  rendered in causal order with per-engine attribution. Wall-clock
  gaps the spans don't cover are labeled ``migration`` only when a
  router move record explains them; an unexplained gap renders
  UNRECONCILED — dead time is never invented into a phase. A
  non-integer uid (or one no stream knows) rejects rc 2.
- ``--follow``: tail mode — poll the streams, print NEW timeline
  entries as they land, and exit rc 0 once the fleet status doc
  (``fleet_status.json``, published atomically by the router next to
  its stream) reports the fleet drained — or when ``--follow_max_s``
  elapses. Works mid-drill: records flush per line and the status doc
  only ever replaces atomically, so a SIGKILL storm can't tear what
  the tail reads.

The merged timeline is byte-deterministic: entries sort by
``(t, stream index, per-stream record order)``, so repeated merges of
the same dirs render identical output even under equal timestamps.

Output: step-time percentiles, throughput, MFU, HBM high-water, the
serving summary + reliability block per engine, a **step phases**
table (schema-v24 ``engine_step`` span records: per host phase of
``engine.step()`` the count, mean, p99 and share of step time, per
step program, by kind and bucket, its runs and the time from its launch
to the end of its read, and for a model with window layers what the
steps' rows read of each kind of cache), a
per-request **waterfall** (schema-v5 ``span`` records: queued / prefill / replay /
decode / quarantine / preempt_gap, whose summed durations RECONCILE
with each completed request's recorded ``latency_s``), and ONE merged
timeline carrying every stream's progress, faults, and recoveries in
wall-clock order.

Exit codes: 0 = report rendered (schema problems are listed, not
fatal; a record-free stream renders an explicit "no records" summary);
2 = no metrics stream exists at any given path.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from .runtime.telemetry import (FLIGHT_FILENAME, METRICS_FILENAME,
                                RECORD_KINDS,
                                ROUTER_POSTMORTEM_PREFIX,
                                STATUS_FILENAME, STEP_SPAN, STEP_SPAN_KV,
                                STEP_SPAN_RING, STEP_SPAN_ROW_BYTES,
                                STEP_SPAN_STATE_ROW, read_metrics)

# a completed request's span durations telescope to its latency by
# construction (runtime/tracing.py); the tolerance only absorbs the
# per-record rounding (latency 4 decimals, durations 6)
RECONCILE_TOL_S = 0.01

# slack when splitting a request's spans at its first-token instant
# (t_first is reconstructed from two 4-decimal-rounded record fields,
# so a boundary span's end can sit ~1e-4 off the reconstruction)
_FIRST_TOKEN_EPS_S = 5e-3

# the SLO attribution vocabulary (DESIGN.md section 21): the span
# categories a violation can be attributed to. "migration" is not a
# span kind — it is the unaccounted wall-clock gap of a uid the router
# moved (plus the re-admission churn that follows a kill-migration),
# reconstructed from the merged streams; a gap WITHOUT a router
# migration record stays "unreconciled" (a crash, not a measured
# phase) and is never counted as attainment
SLO_SPAN_CATEGORIES = ("queued", "prefill", "replay", "decode",
                       "preempt_gap", "quarantine", "migration")


def _pct3(vals, ndigits=4):
    """(p50, p90, p99) of a non-empty value list, rounded."""
    q = np.percentile(np.asarray(vals, np.float64), [50, 90, 99])
    return tuple(round(float(x), ndigits) for x in q)


def _fmt_bytes(n: int | None) -> str:
    if n is None:
        return "n/a"
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024:
            return f"{n:.1f} {unit}"
        n /= 1024
    return f"{n:.1f} PiB"


def _row_bytes(n: int | None) -> str:
    """The tail of a cache-reads line: what a position of the store
    takes in one layer, where the program's records said."""
    return f"; a position is {n} bytes a layer" if n else ""


def _fmt_t(t: float, t0: float) -> str:
    return f"+{t - t0:8.2f}s"


def _load_attempt_log(path: str) -> list[dict]:
    records = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    records.append(json.loads(line))
                except ValueError:
                    pass  # torn line — the stream survives a crash
    except OSError:
        return []
    return records


def _describe_step(rec: dict) -> str:
    bits = [f"step {rec['step']}"]
    if rec.get("strategy"):
        bits[0] = f"{rec['strategy']} {bits[0]}"
    if rec.get("loss") is not None:
        bits.append(f"loss {rec['loss']:.4f}")
    if rec.get("grad_norm") is not None:
        bits.append(f"|g| {rec['grad_norm']:.4f}")
    if rec.get("step_time_s") is not None:
        bits.append(f"{rec['step_time_s'] * 1e3:.1f} ms/step")
    if rec.get("tokens_per_sec") is not None:
        bits.append(f"{rec['tokens_per_sec']:.0f} tok/s")
    if rec.get("mfu") is not None:
        bits.append(f"mfu {rec['mfu']:.3f}")
    return "  ".join(bits)


def _describe_event(rec: dict) -> str:
    ev = rec.get("event", "?")
    if ev == "published":
        a, b = rec.get("steps", (None, None))
        return f"checkpoint published @ step {rec.get('step')} " \
               f"(steps {a}..{b})"
    if ev == "nonfinite_skip":
        a, b = rec.get("steps", (None, None))
        return f"NON-FINITE params after steps {a}..{b} — segment " \
               "skipped, not checkpointed"
    if ev == "anomaly" or rec.get("kind") == "anomaly":
        a, b = rec.get("steps", (None, None))
        return (f"ANOMALY: {rec.get('skipped')} step(s) skipped "
                f"in-graph in {a}..{b} (total "
                f"{rec.get('total_skipped')}, loss scale "
                f"{rec.get('loss_scale')})")
    if ev == "loss_spike":
        a, b = rec.get("steps", (None, None))
        return (f"LOSS SPIKE: update norm {rec.get('delta')} after "
                f"steps {a}..{b} vs baseline {rec.get('baseline')} "
                f"(> {rec.get('factor')}x) — segment not checkpointed")
    if ev == "rollback" or rec.get("kind") == "rollback":
        return (f"ROLLBACK #{rec.get('rollback')}: rewound to verified "
                f"step {rec.get('resume_step')} in-process — "
                f"{rec.get('error')} ({rec.get('max_rollbacks')} max)")
    if ev == "elastic_resume":
        return (f"ELASTIC RESUME @ step {rec.get('step')}: "
                f"{rec.get('saved_shards')} -> "
                f"{rec.get('current_shards')} data shard(s), "
                f"seed_accum {rec.get('seed_accum')} "
                f"({rec.get('n_devices')} device(s))")
    if ev == "attempt_failed":
        extra = " [watchdog expired]" if rec.get("watchdog_expired") else ""
        return (f"FAULT: attempt {rec.get('attempt')} failed after "
                f"{rec.get('elapsed_s')}s — {rec.get('error')}"
                f"{extra}; {rec.get('restarts_left')} restart(s) left, "
                f"backoff {rec.get('backoff_s')}s")
    if ev == "completed":
        return (f"RECOVERED: attempt {rec.get('attempt')} completed "
                f"after {rec.get('elapsed_s')}s"
                + (f" ({rec.get('rollbacks')} rollback(s))"
                   if rec.get("rollbacks") else ""))
    if ev == "chaos_corrupt_ckpt":
        return (f"CHAOS: checkpoint corruption injected at "
                f"step {rec.get('step')}")
    if ev == "hung_step":
        return (f"HUNG STEP @ engine step {rec.get('step')} — watchdog "
                f"{rec.get('watchdog_ms')}ms expired")
    if ev == "resumed":
        return (f"RESUMED from engine snapshot step {rec.get('step')} "
                f"({rec.get('live_requests')} live request(s), "
                f"{rec.get('finished')} already finished)")
    if ev == "chaos_kill":
        return f"CHAOS: SIGKILL after engine snapshot step {rec.get('step')}"
    return f"{ev}: " + ", ".join(
        f"{k}={v}" for k, v in rec.items()
        if k not in ("event", "t", "kind", "schema"))


def _stats_of(group):
    """Per-strategy step statistics (multi-method runs interleave
    strategies in one stream; pooled percentiles would describe no
    actual run)."""
    times = [s["step_time_s"] for s in group
             if s.get("step_time_s") is not None]
    # the first logged chunk usually carries compile time; report
    # steady-state percentiles over the rest when there is a rest
    steady = times[1:] if len(times) > 1 else times
    tps = [s["tokens_per_sec"] for s in group
           if s.get("tokens_per_sec") is not None]
    mfus = [s["mfu"] for s in group if s.get("mfu") is not None]
    losses = [s["loss"] for s in group if s.get("loss") is not None]
    hbm = [max(s["hbm_high_water_bytes"].values())
           for s in group if s.get("hbm_high_water_bytes")]
    stats = {
        "logged_steps": len(group),
        "first_step": group[0]["step"],
        "last_step": group[-1]["step"],
    }
    if steady:
        q = np.percentile(np.asarray(steady, np.float64), [50, 90, 99])
        stats["step_time_p50_ms"] = round(float(q[0]) * 1e3, 3)
        stats["step_time_p90_ms"] = round(float(q[1]) * 1e3, 3)
        stats["step_time_p99_ms"] = round(float(q[2]) * 1e3, 3)
    if tps:
        stats["tokens_per_sec_mean"] = round(float(np.mean(tps)), 1)
        stats["tokens_per_sec_best"] = round(float(np.max(tps)), 1)
    if mfus:
        stats["mfu_mean"] = round(float(np.mean(mfus)), 4)
        stats["mfu_best"] = round(float(np.max(mfus)), 4)
    if losses:
        stats["first_loss"] = round(losses[0], 4)
        stats["last_loss"] = round(losses[-1], 4)
    if hbm:
        stats["hbm_high_water_bytes"] = int(max(hbm))
    return stats


class _Stream:
    """One metrics dir's parsed state + its folded report sections."""

    def __init__(self, metrics_dir: str, attempt_log: str | None):
        self.dir = metrics_dir
        path = metrics_dir
        if os.path.isdir(path):
            path = os.path.join(path, METRICS_FILENAME)
        self.path = path
        self.exists = os.path.exists(path)
        # an EXISTING dir with no metrics.jsonl is a run that wrote
        # nothing — a record-free answer (rc 0), not a bad path (rc 2)
        self.dir_exists = self.exists or os.path.isdir(metrics_dir)
        self.records: list[dict] = []
        self.problems: list[str] = []
        if self.exists:
            self.records, self.problems = read_metrics(path)
        elif self.dir_exists:
            self.problems.append(f"no {METRICS_FILENAME} in "
                                 f"{metrics_dir} (empty metrics dir)")
        by = {}
        for r in self.records:
            by.setdefault(r["kind"], []).append(r)
        self.metas = by.get("meta", [])
        self.steps = by.get("step", [])
        self.events = by.get("event", [])
        self.benches = by.get("bench", [])
        self.anomalies = by.get("anomaly", [])
        self.rollbacks = by.get("rollback", [])
        self.decodes = by.get("decode", [])
        # fleet-router decision records (decode/fleet.py); the router
        # process never resumes, so no replay dedup applies
        self.routers = by.get("router", [])
        # schema-v9 per-round fleet health records (decode/fleet.py)
        self.fleets = by.get("fleet", [])
        # schema-v11 rolling-deploy lifecycle records (decode/fleet.py)
        self.deploys = by.get("deploy", [])
        # schema-v13 trace-replay interval records (the workload
        # driver, decode/workload_driver.py)
        self.workloads = by.get("workload", [])
        # schema-v15 watchtower alert records (runtime/watch.py):
        # fired/resolved detector transitions on the fleet round clock
        self.alerts = by.get("alert", [])
        # request records: drop exact replays — an in-process
        # supervisor restart resumes from a snapshot that may PREDATE
        # records already emitted, so the replayed steps re-emit
        # identical (uid, event, step) transitions (the global step is
        # stable across restarts). Legitimate repeats — a re-admission
        # after preemption, a second quarantine — land at different
        # global steps; anonymous rejected records (uid -1) are kept
        # verbatim (distinct sheds can share a step). Same stance as
        # the attempt-log dedup below.
        self.requests = []
        seen_req = set()
        for r in by.get("request", []):
            key = (r.get("uid"), r.get("event"), r.get("step"))
            if r.get("event") != "rejected" and key in seen_req:
                continue
            seen_req.add(key)
            self.requests.append(r)
        # span records: the same replay-dedup, keyed on the span's full
        # step window (two prefill-chunk spans can share a start_step —
        # admission and the first chunk land in one engine step).
        # ``engine_step`` spans (v18) belong to a step, not a request:
        # they are kept apart, so every per-request reader of
        # ``self.spans`` (waterfall, --trace, ITL slices) never sees a
        # null uid
        self.spans = []
        self.step_spans = []
        seen_span = set()
        for s in by.get("span", []):
            key = (s.get("uid"), s.get("span"), s.get("start_step"),
                   s.get("step"))
            if key in seen_span:
                continue
            seen_span.add(key)
            (self.step_spans if s.get("span") == STEP_SPAN
             else self.spans).append(s)

        # run header: later metas refine earlier ones
        self.header = {}
        for m in self.metas:
            self.header.update({k: v for k, v in m.items()
                                if k not in ("kind", "t", "schema")})
        self.label = self.header.get("engine_id") or os.path.basename(
            os.path.normpath(metrics_dir))

        # attempt log: flag wins; else the newest meta that names one
        self.attempt_path = attempt_log
        if self.attempt_path is None:
            for m in reversed(self.metas):
                if m.get("attempt_log"):
                    self.attempt_path = m["attempt_log"]
                    break
        self.attempts = (_load_attempt_log(self.attempt_path)
                         if self.attempt_path else [])
        if self.attempt_path and not self.attempts \
                and not os.path.exists(self.attempt_path):
            self.problems.append(
                f"attempt log {self.attempt_path} unreadable — "
                "recovery events missing from the timeline")

    # ---- folded sections -------------------------------------------

    def step_stats(self) -> dict:
        by_strategy: dict = {}
        for s in self.steps:
            by_strategy.setdefault(s.get("strategy") or "run",
                                   []).append(s)
        return {k: _stats_of(v) for k, v in by_strategy.items()}

    def serving(self) -> dict | None:
        decodes = self.decodes
        if not decodes:
            return None
        tps = [d["tokens_per_sec"] for d in decodes
               if d.get("tokens_per_sec") is not None]
        occ = [d["batch_occupancy"] for d in decodes
               if d.get("batch_occupancy") is not None]
        util = [d["kv_pool_utilization"] for d in decodes
                if d.get("kv_pool_utilization") is not None]
        last = decodes[-1]
        serving = {
            "records": len(decodes),
            "engine_steps": last.get("step"),
            "tokens_generated": last.get("tokens_generated"),
            "kv_dtype": last.get("kv_dtype"),
            "compiled_programs": last.get("compiled_programs"),
        }
        if tps:
            serving["tokens_per_sec_mean"] = round(float(np.mean(tps)), 1)
            serving["tokens_per_sec_best"] = round(float(np.max(tps)), 1)
        if occ:
            serving["batch_occupancy_mean"] = round(float(np.mean(occ)), 4)
        if util:
            serving["kv_pool_utilization_max"] = round(
                float(np.max(util)), 4)
        # schema-v5 KV-pool internals (older v4-era streams fail schema
        # validation wholesale, so presence here is all-or-nothing)
        lows = [d["free_blocks_low_water"] for d in decodes
                if d.get("free_blocks_low_water") is not None]
        frags = [d["kv_fragmentation"] for d in decodes
                 if d.get("kv_fragmentation") is not None]
        stored = [d["kv_bytes_stored"] for d in decodes
                  if d.get("kv_bytes_stored") is not None]
        if lows:
            serving["free_blocks_low_water"] = int(min(lows))
        if frags:
            serving["kv_fragmentation_max"] = round(float(np.max(frags)),
                                                    4)
        if stored:
            serving["kv_bytes_stored_max"] = int(max(stored))
        for key in ("block_allocs", "block_frees", "block_scrubs"):
            if last.get(key) is not None:
                serving[key] = last[key]
        # schema-v6 speculation keys: acceptance rate + measured
        # tokens-per-step (generated tokens over engine steps — > 1
        # exactly when verify dispatches emitted multi-token steps)
        if last.get("drafted_tokens"):
            serving["drafted_tokens"] = last["drafted_tokens"]
            serving["accepted_tokens"] = last.get("accepted_tokens")
            serving["accept_rate"] = last.get("accept_rate")
            if last.get("step") and last.get("tokens_generated") \
                    is not None:
                serving["tokens_per_step"] = round(
                    last["tokens_generated"] / last["step"], 3)
        # schema-v7 shared-prefix keys: cumulative admission hits and
        # the prompt tokens they skipped (the prefill the pool never
        # paid), plus the CoW trigger count (0 = the write-barrier
        # invariant held) and the peak instantaneous sharing
        if last.get("prefix_hit_blocks"):
            serving["prefix_hit_blocks"] = last["prefix_hit_blocks"]
            serving["prefill_tokens_saved"] = last.get(
                "prefill_tokens_saved")
            if last.get("prefix_hit_rate") is not None:
                serving["prefix_hit_rate"] = last["prefix_hit_rate"]
            shared = [d["shared_blocks"] for d in decodes
                      if d.get("shared_blocks") is not None]
            if shared:
                serving["shared_blocks_max"] = int(max(shared))
        if last.get("cow_copies") is not None:
            serving["cow_copies"] = last["cow_copies"]
        # schema-v17 KV spill keys: cumulative demotions/promotions
        # through the host-RAM tier, the prefill tokens restores
        # skipped, the wall clock the donated implant path cost, and
        # the peak host-tier occupancy — only when the tier ever held
        # a block (a tier-less run's summary stays pre-v17)
        if last.get("spilled_blocks"):
            serving["spilled_blocks"] = last["spilled_blocks"]
            serving["spill_bytes"] = last.get("spill_bytes")
            serving["restores"] = last.get("restores")
            serving["restore_tokens_saved"] = last.get(
                "restore_tokens_saved")
            serving["restore_stall_s"] = last.get("restore_stall_s")
            util = [d["host_tier_utilization"] for d in decodes
                    if d.get("host_tier_utilization") is not None]
            if util:
                serving["host_tier_utilization_max"] = max(util)
        if last.get("partial_hits"):
            serving["partial_hits"] = last["partial_hits"]
        return serving

    def reliability(self) -> dict | None:
        requests = self.requests
        if not requests:
            return None
        by_event: dict[str, int] = {}
        for r in requests:
            by_event[r["event"]] = by_event.get(r["event"], 0) + 1
        rel = {
            "records": len(requests),
            "admitted": by_event.get("admitted", 0),
            "completed": by_event.get("completed", 0),
            "quarantined": by_event.get("quarantined", 0),
            "retried": by_event.get("retried", 0),
            "preempted": by_event.get("preempted", 0),
            # shed = load the system refused or gave up on (admission
            # rejects + deadline expiries) — the graceful-degradation
            # counter
            "shed": (by_event.get("rejected", 0)
                     + by_event.get("expired", 0)),
            "rejected": by_event.get("rejected", 0),
            "expired": by_event.get("expired", 0),
            "failed_uids": sorted({
                r["uid"] for r in requests
                if (r["event"] == "expired"
                    or (r["event"] == "quarantined"
                        and not r.get("retrying")))}),
        }
        lat = [r["latency_s"] for r in requests
               if r["event"] == "completed"
               and r.get("latency_s") is not None]
        if lat:
            q = np.percentile(np.asarray(lat, np.float64), [50, 90, 99])
            rel["latency_p50_s"] = round(float(q[0]), 4)
            rel["latency_p90_s"] = round(float(q[1]), 4)
            rel["latency_p99_s"] = round(float(q[2]), 4)
        # schema-v9 latency decomposition: TTFT straight off the
        # completed records, ITL from the per-decode-segment spans
        # (duration/tokens — the segment's mean inter-token gap; the
        # segment's first token lands at its open instant)
        ttfts = [r["ttft_s"] for r in requests
                 if r["event"] == "completed"
                 and r.get("ttft_s") is not None]
        if ttfts:
            (rel["ttft_p50_s"], rel["ttft_p90_s"],
             rel["ttft_p99_s"]) = _pct3(ttfts)
        gaps = [s["duration_s"] / s["tokens"] for s in self.spans
                if s["span"] == "decode" and s.get("tokens")
                and s.get("duration_s") is not None]
        if gaps:
            (rel["itl_p50_s"], rel["itl_p90_s"],
             rel["itl_p99_s"]) = _pct3(gaps, 6)
        # v11 per-version completions: each uid completed exactly once
        # per stream (the replay dedup above), counted under its
        # weights-version pin — a mid-deploy stream shows both
        vers: dict[str, int] = {}
        for r in requests:
            if r["event"] == "completed" \
                    and r.get("weights_version") is not None:
                key = f"v{r['weights_version']}"
                vers[key] = vers.get(key, 0) + 1
        if vers:
            rel["completed_by_version"] = vers
        return rel

    def recovery(self) -> dict:
        fails = [a for a in self.attempts
                 if a.get("event") == "attempt_failed"]
        return {
            "attempt_log": self.attempt_path,
            "attempts_failed": len(fails),
            "completed": any(a.get("event") == "completed"
                             for a in self.attempts),
            "nonfinite_skips": sum(1 for e in self.events
                                   if e.get("event") == "nonfinite_skip"),
            "publishes": sum(1 for e in self.events
                             if e.get("event") == "published"),
            # the self-healing ladder's cheap rungs (schema v2 kinds)
            "in_graph_skips": sum(int(a.get("skipped") or 0)
                                  for a in self.anomalies),
            "rollbacks": len(self.rollbacks),
            "loss_spikes": sum(1 for e in self.events
                               if e.get("event") == "loss_spike"),
        }

    def step_phases(self) -> dict | None:
        """Where the engine's steps spent their host time: per phase
        of the ``engine_step`` records (runtime/tracing.py PhaseTimer)
        how many steps had it, its mean and p99 per step that had it
        (a phase repeated in a step is summed first), and its share of
        all step time. ``(between phases)`` is what no phase covers.
        ``dispatches``: the step programs those steps launched, by
        kind and bucket (the record's i-th entry is its i-th
        ``*.dispatch`` phase), each with how often and how long from
        the launch to the end of the blocking read that read it (v20:
        the i-th ``*.readback`` phase read the launch its
        ``readbacks`` names, which may lie in an earlier record; a
        launch whose read is in no record is not counted)."""
        if not self.step_spans:
            return None
        per_phase: dict[str, list[float]] = {}
        per_program: dict[tuple, list[float]] = {}
        unread: dict[int, tuple] = {}   # ordinal -> (program, t_launch)
        total_ms = 0.0
        for rec in self.step_spans:
            step_ms = (rec["end_ns"] - rec["start_ns"]) / 1e6
            total_ms += step_ms
            mine: dict[str, float] = {}
            launched = iter(rec["dispatches"])
            ordinal = rec["launches"] - len(rec["dispatches"])
            read = iter(rec["readbacks"])
            for name, t0, t1 in rec["phases"]:
                mine[name] = mine.get(name, 0.0) + (t1 - t0) / 1e6
                if name.endswith(".dispatch"):
                    unread[ordinal] = (tuple(next(launched)), t0)
                    ordinal += 1
                elif name.endswith(".readback"):
                    program, t_launch = unread.pop(next(read),
                                                   (None, None))
                    if program is not None:
                        per_program.setdefault(program, []).append(
                            (t1 - t_launch) / 1e6)
            mine["(between phases)"] = step_ms - sum(mine.values())
            for name, ms in mine.items():
                per_phase.setdefault(name, []).append(ms)
        return {
            "steps": len(self.step_spans),
            "step_mean_ms": round(total_ms / len(self.step_spans), 4),
            "cache_reads": self._cache_reads(),
            "state_row": self._state_row(),
            "dispatches": [
                {"kind": kind, "bucket": bucket, "count": len(ms),
                 "mean_ms": round(float(np.mean(ms)), 4),
                 "p99_ms": round(float(np.percentile(ms, 99)), 4)}
                for (kind, bucket), ms in sorted(
                    per_program.items(), key=lambda kv: -len(kv[1]))],
            "phases": {
                name: {"steps": len(ms),
                       "mean_ms": round(float(np.mean(ms)), 4),
                       "p99_ms": round(float(np.percentile(ms, 99)), 4),
                       "share": round(sum(ms) / total_ms, 4)
                       if total_ms else None}
                for name, ms in per_phase.items()},
        }

    def _state_row(self) -> dict | None:
        """What a sequence holds in ONE recurrent layer (v25:
        ``STEP_SPAN_STATE_ROW``, constants of the engine carried by
        every record), or None where no record says or the model has no
        recurrent layer."""
        rec = next((r for r in self.step_spans
                    if any(r.get(k) for k in STEP_SPAN_STATE_ROW)), None)
        return rec and {k: rec[k] for k in STEP_SPAN_STATE_ROW}

    def _cache_reads(self) -> dict | None:
        """What the steps' rows read of the cache. ``blocks`` (v22):
        the pool's blocks the decode-side reads of a step's launched
        rows fetched (``kv_blocks_read``) beside the capacity a gather
        of their whole tables reads, and the same two of a window
        layer's rings (v24: ``ring_blocks_read`` /
        ``ring_blocks_capacity``, 0 with none), a step's mean over the
        steps that launched such rows; None where no record counted
        one. Where a
        record counted a window layer's read (v21), also ``window_rows``
        / ``full_rows``, the cached positions a step's launched rows
        attend over in a window layer and in a full one, and the window
        blocks' turnover (``window_blocks_released`` in all,
        ``window_blocks_live`` at most) and, of a chunked layer (v23),
        the chunk summaries a step's rows attend over and those
        written. ``blocks`` also holds each store's bytes a position a
        layer where the program wrote them (``STEP_SPAN_ROW_BYTES``,
        None from an older stream). None where the records hold
        neither."""
        kv = [r for r in self.step_spans if r.get("kv_blocks_capacity")]
        blocks = None if not kv else {
            "steps": len(kv),
            **{f"{key}_mean": round(float(np.mean(
                [r.get(key, 0) for r in kv])), 2)
               for key in STEP_SPAN_KV + STEP_SPAN_RING},
            **{key: kv[0].get(key) for key in STEP_SPAN_ROW_BYTES}}
        recs = [r for r in self.step_spans if r.get("window_rows")]
        if not recs:
            return None if blocks is None else {"blocks": blocks}
        return {
            "blocks": blocks,
            "steps": len(recs),
            "window_rows_mean": round(float(np.mean(
                [r["window_rows"] for r in recs])), 2),
            "full_rows_mean": round(float(np.mean(
                [r["full_rows"] for r in recs])), 2),
            "window_blocks_released": sum(
                r["window_blocks_released"] for r in self.step_spans
                if "window_blocks_released" in r),
            "window_blocks_live_max": max(
                r["window_blocks_live"] for r in recs),
            # v23: a chunked layer's summaries (0 with none)
            "summary_rows_mean": round(float(np.mean(
                [r.get("summary_rows", 0) for r in recs])), 2),
            "summaries_written": sum(
                r.get("summaries_written", 0) for r in recs)}

    def waterfalls(self) -> dict:
        """Per-uid span waterfall: phase breakdown + the span-sum vs
        latency reconciliation (runtime/tracing.py's telescoping
        contract — a completed request whose spans DON'T sum to its
        latency had unaccounted wall time, e.g. a crash gap)."""
        if not self.spans:
            return {}
        comp = {r["uid"]: r for r in self.requests
                if r["event"] == "completed"}
        by_uid: dict = {}
        for s in self.spans:
            by_uid.setdefault(s["uid"], []).append(s)
        out = {}
        for uid in sorted(by_uid):
            ss = sorted(by_uid[uid],
                        key=lambda s: (s.get("start_t") or 0.0,
                                       s.get("t") or 0.0))
            total = round(sum(s.get("duration_s") or 0.0 for s in ss), 4)
            rec = comp.get(uid)
            latency = rec.get("latency_s") if rec else None
            ttft = rec.get("ttft_s") if rec else None
            entry = {
                "spans": [{
                    "span": s["span"],
                    "duration_s": s.get("duration_s"),
                    "start_step": s.get("start_step"),
                    "end_step": s.get("step"),
                } for s in ss],
                "span_sum_s": total,
                "latency_s": latency,
                "ttft_s": ttft,
                "reconciled": (latency is not None
                               and abs(total - latency)
                               <= RECONCILE_TOL_S),
            }
            if latency is not None and ttft is not None and rec:
                # the v9 decomposition reconciliation: the first-token
                # mark sits exactly on a span boundary, so ttft + the
                # post-first-token span sum telescopes to the latency
                t_first = rec.get("t", 0.0) - latency + ttft
                post = sum(s.get("duration_s") or 0.0 for s in ss
                           if (s.get("t") or 0.0)
                           > t_first + _FIRST_TOKEN_EPS_S)
                entry["ttft_plus_post_s"] = round(ttft + post, 4)
                entry["ttft_reconciled"] = (
                    abs(ttft + post - latency) <= RECONCILE_TOL_S)
            out[str(uid)] = entry
        return out

    def router_postmortems(self) -> list[dict]:
        """Router-side dead-host evidence dumps published next to this
        stream (``decode/fleet.py`` publishes one per declared-dead
        engine: last digests, pending call ids, op/backoff/ping
        history, declaration reason — the half of the post-mortem the
        SIGKILLed worker's own flight recorder cannot hold)."""
        out = []
        base = os.path.dirname(self.path)
        try:
            names = sorted(os.listdir(base))
        except OSError:
            return out
        for name in names:
            if not (name.startswith(ROUTER_POSTMORTEM_PREFIX)
                    and name.endswith(".json")):
                continue
            path = os.path.join(base, name)
            try:
                with open(path) as f:
                    doc = json.load(f)
            except ValueError:
                doc = {"error": f"unparseable router postmortem at "
                                f"{path}"}
            doc["path"] = path
            out.append(doc)
        return out

    def flight_recorder(self) -> dict | None:
        """The stream's flight-recorder dump, if one was persisted
        (decode/engine.py dumps on quarantine; the supervisor on
        watchdog latch and chaos kill)."""
        path = os.path.join(os.path.dirname(self.path), FLIGHT_FILENAME)
        if not os.path.exists(path):
            return None
        try:
            with open(path) as f:
                doc = json.load(f)
        except ValueError:
            return {"error": f"unparseable flight recorder at {path}"}
        doc["path"] = path
        return doc

    def timeline_entries(self) -> list[tuple[float, str, str]]:
        timeline = []
        for s in self.steps:
            timeline.append((s["t"], "step", _describe_step(s)))
        seen_events = {(e.get("t"), e.get("event")) for e in self.events}
        for e in self.events:
            timeline.append((e["t"], "event", _describe_event(e)))
        for a in self.anomalies:
            timeline.append((a["t"], "anomaly", _describe_event(a)))
            seen_events.add((a.get("t"), "anomaly"))
        for r in self.rollbacks:
            timeline.append((r["t"], "rollbck", _describe_event(r)))
            seen_events.add((r.get("t"), "rollback"))
        for d in self.decodes:
            bits = [f"engine step {d.get('step')}"]
            if d.get("tokens_per_sec") is not None:
                bits.append(f"{d['tokens_per_sec']:.0f} tok/s")
            if d.get("batch_occupancy") is not None:
                bits.append(f"occ {d['batch_occupancy']:.2f}")
            if d.get("kv_pool_utilization") is not None:
                bits.append(f"kv {d['kv_pool_utilization']:.2f}")
            if d.get("kv_fragmentation"):
                bits.append(f"frag {d['kv_fragmentation']:.2f}")
            if d.get("waiting"):
                bits.append(f"{d['waiting']} waiting")
            timeline.append((d["t"], "decode", "  ".join(bits)))
        for r in self.routers:
            ev = r["event"]
            arrow = ""
            if r.get("source") is not None and r.get("target") is not None:
                arrow = f" {r['source']} -> {r['target']}"
            elif r.get("target") is not None:
                arrow = f" -> {r['target']}"
            elif r.get("source") is not None:
                arrow = f" from {r['source']}"
            bits = [f"request {r.get('uid')} {ev.upper()}{arrow}"
                    + (f" ({r['reason']})" if r.get("reason") else "")
                    + f" @ fleet round {r.get('step')}"]
            if r.get("replay"):
                bits.append(f"replay {r['replay']} token(s)")
            if r.get("prefix_hit_blocks"):
                bits.append(f"{r['prefix_hit_blocks']} warm block(s)")
            timeline.append((r["t"], "router", "  ".join(bits)))
        for d in self.deploys:
            ev = d["event"]
            pair = (f"v{d.get('from_version')} -> "
                    f"v{d.get('to_version')}")
            if ev == "started":
                what = f"DEPLOY STARTED {pair}"
            elif ev == "engine_swapped":
                what = (f"DEPLOY {pair}: engine {d.get('engine')} "
                        "drained + swapped")
            elif ev == "completed":
                what = (f"DEPLOY COMPLETED {pair} across "
                        f"{d.get('engines')} engine(s) in "
                        f"{d.get('duration_s')}s "
                        f"({d.get('drained')} request(s) migrated, "
                        "zero shed)")
            elif ev == "rolled_back":
                what = f"DEPLOY ROLLED BACK — {d.get('reason')}"
            else:
                what = f"DEPLOY {ev} {pair}"
            timeline.append((d["t"], "deploy",
                             what + f" @ fleet round {d.get('step')}"))
        for wrec in self.workloads:
            tb = ", ".join(
                f"{t}:{c.get('completed')}/{c.get('offered')}"
                for t, c in sorted((wrec.get("tenants") or {}).items()))
            timeline.append((
                wrec["t"], "workld",
                f"interval offered {wrec.get('offered')} admitted "
                f"{wrec.get('admitted')} @ round {wrec.get('step')}"
                + (f"  [{tb}]" if tb else "")))
        for a in self.alerts:
            ev = a["event"]
            bits = [f"ALERT {a.get('detector')} {ev.upper()} "
                    f"[{a.get('severity')}] @ fleet round "
                    f"{a.get('step')}"]
            if ev == "resolved" and a.get("fired_step") is not None:
                bits.append(f"fired @ {a['fired_step']}")
            if a.get("burn_fast") is not None:
                bits.append(f"burn fast {a['burn_fast']} / slow "
                            f"{a['burn_slow']}")
            for k in ("waiting", "imbalance", "stalled_rounds",
                      "incidents", "p95_s"):
                if a.get(k) is not None:
                    bits.append(f"{k} {a[k]}")
            timeline.append((a["t"], "alert", "  ".join(bits)))
        for r in self.requests:
            ev = r["event"]
            bits = [f"request {r.get('uid')} {ev.upper()}"
                    + (f" ({r['reason']})" if r.get("reason") else "")
                    + f" @ engine step {r.get('step')}"]
            if ev == "completed":
                if r.get("latency_s") is not None:
                    bits.append(f"latency {r['latency_s']:.3f}s")
                if r.get("n_new") is not None:
                    bits.append(f"{r['n_new']} token(s)")
                if r.get("retries"):
                    bits.append(f"{r['retries']} retry(ies)")
            elif ev == "retried":
                bits.append(f"attempt {r.get('attempt')}/"
                            f"{r.get('max_retries')}")
            elif ev == "quarantined" and not r.get("retrying"):
                bits.append("FAILED")
            timeline.append((r["t"], "request", "  ".join(bits)))
        for a in self.attempts:
            # supervise forwards checkpoint-layer events to its log
            # too; drop exact duplicates of what the metrics stream
            # already has
            if (a.get("t"), a.get("event")) in seen_events:
                continue
            timeline.append((a.get("t", 0.0), "attempt",
                             _describe_event(a)))
        return timeline


def _merged_completions(streams) -> dict:
    """uid -> its FIRST completion record across every stream (a
    request completed on an engine after its last snapshot re-completes
    on a survivor when that engine dies — same tokens, two records;
    the caller saw the first one)."""
    comp: dict = {}
    for r in sorted((r for s in streams for r in s.requests
                     if r["event"] == "completed"),
                    key=lambda r: r.get("t", 0.0)):
        comp.setdefault(r["uid"], r)
    return comp


def _merged_spans(streams) -> dict:
    """uid -> its deduped spans pooled across every stream (the
    per-stream replay dedup applied once more across streams — a
    migrated request's life is split over several engines' files)."""
    by_uid: dict = {}
    seen = set()
    for s in streams:
        for sp in s.spans:
            key = (sp.get("uid"), sp.get("span"), sp.get("start_step"),
                   sp.get("step"))
            if key in seen:
                continue
            seen.add(key)
            by_uid.setdefault(sp["uid"], []).append(sp)
    for ss in by_uid.values():
        ss.sort(key=lambda s: (s.get("start_t") or 0.0,
                               s.get("t") or 0.0))
    return by_uid


def _merged_decode_gaps(streams) -> list:
    """Per-decode-segment mean inter-token gaps (duration/tokens)
    pooled across streams — the fleet-wide ITL sample set."""
    return [s.get("duration_s") / s["tokens"]
            for ss in _merged_spans(streams).values() for s in ss
            if s["span"] == "decode" and s.get("tokens")
            and s.get("duration_s") is not None]


def _slo_accounting(streams, slo_ttft: float, slo_itl: float) -> dict:
    """Goodput accounting over the merged streams (DESIGN.md §21).

    A completed request ATTAINS the SLO when its decomposition
    reconciles AND ``ttft_s <= slo_ttft`` AND its observed inter-token
    latency ``(latency_s - ttft_s) / (n_new - 1)`` — stalls included,
    what the caller actually experienced — is ``<= slo_itl``. Each
    violation is attributed to its dominant span category:

    - post-first-token spans fold by kind (decode / preempt_gap /
      quarantine), with the re-admission churn after a stall (queued /
      prefill / replay spans) charged to the stall's CAUSE — a
      kill-migration's replay is migration cost, not an innocent
      "replay" line item;
    - a wall-clock gap the spans don't cover is ``migration`` when the
      router has a handoff/migrated record for the uid (the span
      clock deliberately restarts on the target engine — the gap IS
      the migration stall). A gap with NO migration record is a crash:
      the request is UNRECONCILED and never counted as attainment.
    """
    comp = _merged_completions(streams)
    spans_by_uid = _merged_spans(streams)
    # per-policy attribution (v14): one run serves one policy, so each
    # completion inherits the ``--policy`` label of the stream (run)
    # that emitted it — merged with the same first-completion-wins
    # ordering as ``_merged_completions`` so the label matches the
    # record the numbers came from
    policy_of: dict = {}
    for r, label in sorted(((r, s.header.get("policy"))
                            for s in streams for r in s.requests
                            if r["event"] == "completed"),
                           key=lambda rl: rl[0].get("t", 0.0)):
        policy_of.setdefault(r["uid"], label)
    moved_t: dict = {}
    for s in streams:
        for r in s.routers:
            if r["event"] in ("handoff", "migrated"):
                t = r.get("t", 0.0)
                moved_t[r["uid"]] = min(moved_t.get(r["uid"], t), t)
    per_uid = []
    counts = {"attained": 0, "violated": 0, "unreconciled": 0}
    by_span: dict = {}
    for uid in sorted(comp):
        rec = comp[uid]
        latency = rec.get("latency_s")
        ttft = rec.get("ttft_s")
        n_new = rec.get("n_new")
        entry = {"uid": uid, "latency_s": latency, "ttft_s": ttft,
                 "n_new": n_new, "migrated": uid in moved_t,
                 "tenant": _tenant_of(rec)}
        if policy_of.get(uid) is not None:
            entry["policy"] = policy_of[uid]
        spans = spans_by_uid.get(uid, [])
        if latency is None or ttft is None:
            entry["status"] = "unreconciled"
            entry["why"] = ("no TTFT decomposition (first token "
                            "predates a crash-resume)")
            counts["unreconciled"] += 1
            per_uid.append(entry)
            continue
        t_first = rec.get("t", 0.0) - latency + ttft
        pre = [s for s in spans
               if (s.get("t") or 0.0) <= t_first + _FIRST_TOKEN_EPS_S]
        post = [s for s in spans
                if (s.get("t") or 0.0) > t_first + _FIRST_TOKEN_EPS_S]
        mig_t = moved_t.get(uid)

        def fold(side_spans: list) -> dict:
            """Category totals with the cause-tracking rules (the same
            walk on both sides of the first token — a kill BEFORE the
            first token stalls the TTFT side, DESIGN.md §21)."""
            cats: dict = {}
            cause = None
            for s in side_spans:
                name = s["span"]
                if name == "decode":
                    cat, cause = "decode", None
                elif name == "preempt_gap":
                    cat = cause = "preempt_gap"
                elif name == "quarantine":
                    cat = cause = "quarantine"
                elif (cause is None and mig_t is not None
                      and (s.get("start_t") or 0.0)
                      >= mig_t - _FIRST_TOKEN_EPS_S):
                    # queued/prefill/replay after the migration with no
                    # closer stall cause: the kill-migration's catch-up
                    cat = cause = "migration"
                elif cause is not None:
                    cat = cause      # re-admission churn -> its cause
                else:
                    cat = name
                cats[cat] = cats.get(cat, 0.0) + (s.get("duration_s")
                                                  or 0.0)
            return cats

        cats = fold(post)
        pre_cats = fold(pre)
        post_sum = sum(s.get("duration_s") or 0.0 for s in post)
        pre_sum = sum(s.get("duration_s") or 0.0 for s in pre)
        # gaps the spans don't cover, on EACH side of the first token:
        # ttft == pre-span sum by construction, so a pre-side gap is a
        # stall whose spans died with an engine (a kill before the
        # first token), exactly like the post-side gap of a mid-decode
        # kill — migration when the router recorded the move, a crash
        # (UNRECONCILED) otherwise
        post_gap = latency - ttft - post_sum
        pre_gap = ttft - pre_sum
        entry["post_span_sum_s"] = round(post_sum, 4)
        entry["gap_s"] = round(post_gap, 4)
        if abs(pre_gap) > RECONCILE_TOL_S:
            entry["pre_gap_s"] = round(pre_gap, 4)
        unaccounted = None
        for side_cats, gap in ((cats, post_gap), (pre_cats, pre_gap)):
            if gap > RECONCILE_TOL_S and uid in moved_t:
                side_cats["migration"] = (
                    side_cats.get("migration", 0.0) + gap)
            elif abs(gap) > RECONCILE_TOL_S:
                unaccounted = gap
        if unaccounted is not None:
            entry["status"] = "unreconciled"
            entry["why"] = (f"{round(unaccounted, 4)}s unaccounted "
                            "and no router migration record — a crash "
                            "gap, not a measured phase")
            counts["unreconciled"] += 1
            per_uid.append(entry)
            continue
        mig_total = (cats.get("migration", 0.0)
                     + pre_cats.get("migration", 0.0))
        if mig_total:
            entry["migration_s"] = round(mig_total, 4)
        itl = ((latency - ttft) / (n_new - 1)
               if n_new and n_new > 1 else None)
        entry["itl_s"] = None if itl is None else round(itl, 6)
        entry["breakdown"] = {k: round(v, 4) for k, v in
                              sorted(cats.items(),
                                     key=lambda kv: -kv[1])}
        entry["ttft_breakdown"] = {k: round(v, 4) for k, v in
                                   sorted(pre_cats.items(),
                                          key=lambda kv: -kv[1])}
        ttft_viol = ttft > slo_ttft + 1e-9
        itl_viol = itl is not None and itl > slo_itl + 1e-9
        if not (ttft_viol or itl_viol):
            entry["status"] = "attained"
            counts["attained"] += 1
        else:
            entry["status"] = "violated"
            entry["violates"] = [d for d, v in (("ttft", ttft_viol),
                                                ("itl", itl_viol)) if v]
            pool: dict = {}
            if itl_viol:
                pool.update(cats)
            if ttft_viol:
                for k, v in pre_cats.items():
                    pool[k] = pool.get(k, 0.0) + v
            attributed = (max(pool.items(), key=lambda kv: kv[1])[0]
                          if pool else "decode")
            entry["attributed"] = attributed
            by_span[attributed] = by_span.get(attributed, 0) + 1
            counts["violated"] += 1
        per_uid.append(entry)
    total = len(per_uid)
    # the per-tenant goodput slice (v13): the same fold, grouped by
    # the completed record's tenant — the noisy-tenant drill's numbers
    by_tenant: dict = {}
    for e in per_uid:
        b = by_tenant.setdefault(e["tenant"], {
            "completed": 0, "attained": 0, "violated": 0,
            "unreconciled": 0})
        b["completed"] += 1
        b[e["status"]] += 1
    for b in by_tenant.values():
        b["attainment"] = (round(b["attained"] / b["completed"], 4)
                           if b["completed"] else None)
    # the per-policy goodput slice (v14): the offline policy search's
    # comparison surface — group by the run's ``--policy`` label (a
    # report over two labelled runs of the same trace prints both
    # policies' attainment side by side); unlabelled runs fold nowhere
    by_policy: dict = {}
    for e in per_uid:
        label = e.get("policy")
        if label is None:
            continue
        b = by_policy.setdefault(label, {
            "completed": 0, "attained": 0, "violated": 0,
            "unreconciled": 0})
        b["completed"] += 1
        b[e["status"]] += 1
    for b in by_policy.values():
        b["attainment"] = (round(b["attained"] / b["completed"], 4)
                           if b["completed"] else None)
    return {
        "slo_ttft_s": slo_ttft, "slo_itl_s": slo_itl,
        "completed": total, **counts,
        "attainment": (round(counts["attained"] / total, 4)
                       if total else None),
        "violations_by_span": by_span,
        "by_tenant": by_tenant,
        "by_policy": by_policy,
        "requests": per_uid,
    }


def _trace_doc(streams, uid: int) -> dict | None:
    """ONE request's cross-engine causal waterfall (schema v12,
    DESIGN.md section 24): every span, router move, and lifecycle
    event for ``uid`` across the merged streams, stitched by the
    request's ``trace_id`` (records carrying a DIFFERENT trace id are
    another life of a reused uid and are excluded — the stitch key is
    the id, not the uid). Wall-clock gaps the spans don't cover are
    classified ``migration`` only when a router move record explains
    them; an unexplained gap renders UNRECONCILED and the whole
    request is flagged — dead time is never invented into a phase."""
    reqs, spans, moves = [], [], []
    for s in streams:
        for r in s.requests:
            if r.get("uid") == uid:
                reqs.append((s.label, r))
        for sp in s.spans:
            if sp.get("uid") == uid:
                spans.append((s.label, sp))
        for r in s.routers:
            if r.get("uid") == uid:
                moves.append((s.label, r))
    if not (reqs or spans or moves):
        return None
    problems = []
    traces = {r.get("trace_id") for _, r in reqs + spans + moves
              if r.get("trace_id")}
    trace_id = None
    if traces:
        # the NEWEST life by record timestamp — the nonce prefix is
        # random and carries no temporal order, so a lexicographic
        # pick could stitch an old life of a reused uid
        trace_id = max(
            (r for _, r in reqs + spans + moves if r.get("trace_id")),
            key=lambda r: r.get("t", 0.0)).get("trace_id")
    if len(traces) > 1:
        problems.append(
            f"uid {uid} appears under {len(traces)} trace ids "
            f"{sorted(traces)} — stitching the newest-by-timestamp "
            f"({trace_id}); an older id is a different request's "
            "life behind a reused uid")
    if trace_id is not None:
        keep = (trace_id, None)
        reqs = [(l, r) for l, r in reqs if r.get("trace_id") in keep]
        spans = [(l, r) for l, r in spans if r.get("trace_id") in keep]
        moves = [(l, r) for l, r in moves if r.get("trace_id") in keep]
    # spans were already replay-deduped PER STREAM (_Stream); across
    # streams every span is genuine — two engines can emit spans with
    # coincident (span, step) windows (fleet rounds keep global steps
    # comparable), so the dedup key must include the engine or a real
    # span gets dropped and renders a false UNRECONCILED gap
    spans_d, seen = [], set()
    for label, sp in sorted(spans,
                            key=lambda x: (x[1].get("start_t") or 0.0,
                                           x[1].get("t") or 0.0)):
        key = (label, sp.get("span"), sp.get("start_step"),
               sp.get("step"))
        if key in seen:
            continue
        seen.add(key)
        spans_d.append((label, sp))
    moves_sorted = sorted(moves, key=lambda x: x[1].get("t", 0.0))
    comp = None
    for _label, r in sorted(reqs, key=lambda x: x[1].get("t", 0.0)):
        if r["event"] == "completed":
            comp = r
            break

    def move_row(label, mr):
        row = {"type": "move", "event": mr["event"], "t": mr.get("t"),
               "source": mr.get("source"), "target": mr.get("target"),
               "reason": mr.get("reason"), "round": mr.get("step")}
        for k in ("blocks", "bytes", "duration_s", "replay",
                  "transport", "policy"):
            if mr.get(k) is not None:
                row[k] = mr[k]
        return row

    chain = []
    span_sum = mig_gap = unrec_gap = 0.0
    prev_end = None
    mi = 0
    eps = _FIRST_TOKEN_EPS_S
    for label, sp in spans_d:
        st = sp.get("start_t") or 0.0
        while (mi < len(moves_sorted)
               and moves_sorted[mi][1].get("t", 0.0) <= st + eps):
            chain.append(move_row(*moves_sorted[mi]))
            mi += 1
        if prev_end is not None and st - prev_end > RECONCILE_TOL_S:
            gap = st - prev_end
            explained = any(
                mr["event"] in ("handoff", "migrated", "wire_rejected")
                and prev_end - eps <= mr.get("t", 0.0) <= st + eps
                for _l, mr in moves)
            cause = "migration" if explained else "UNRECONCILED"
            if explained:
                mig_gap += gap
            else:
                unrec_gap += gap
            chain.append({"type": "gap", "cause": cause,
                          "duration_s": round(gap, 4)})
        row = {"type": "span", "engine": label, "span": sp["span"],
               "duration_s": sp.get("duration_s"),
               "start_step": sp.get("start_step"),
               "end_step": sp.get("step")}
        if sp.get("tokens") is not None:
            row["tokens"] = sp["tokens"]
        chain.append(row)
        span_sum += sp.get("duration_s") or 0.0
        end = sp.get("t") or st
        prev_end = end if prev_end is None else max(prev_end, end)
    while mi < len(moves_sorted):
        chain.append(move_row(*moves_sorted[mi]))
        mi += 1
    latency = comp.get("latency_s") if comp else None
    # the acceptance identity: covered span time + router-explained
    # migration gaps telescope to the recorded latency (the first
    # span opens at t_submit, the last closes on the completion
    # timestamp); any residual is unaccounted crash time
    reconciled = (latency is not None
                  and unrec_gap <= RECONCILE_TOL_S
                  and abs(span_sum + mig_gap + unrec_gap - latency)
                  <= RECONCILE_TOL_S)
    events = [{"engine": label, "event": r["event"],
               "step": r.get("step"), "t": r.get("t"),
               "reason": r.get("reason"),
               "weights_version": r.get("weights_version")}
              for label, r in sorted(reqs,
                                     key=lambda x: x[1].get("t", 0.0))]
    return {
        "uid": uid,
        "trace_id": trace_id,
        "engines": sorted({l for l, _ in spans_d}
                          | {e["engine"] for e in events}),
        "chain": chain,
        "events": events,
        "span_sum_s": round(span_sum, 4),
        "migration_gap_s": round(mig_gap, 4),
        "unreconciled_gap_s": round(unrec_gap, 4),
        "latency_s": latency,
        "ttft_s": comp.get("ttft_s") if comp else None,
        "weights_version": (comp or {}).get("weights_version"),
        "completed": comp is not None,
        "reconciled": reconciled,
        "problems": problems,
    }


def _render_trace(out: list, tr: dict) -> None:
    out.append("")
    out.append(f"trace {tr['trace_id']} — uid {tr['uid']} across "
               + (", ".join(tr["engines"]) or "(no engine)"))
    for row in tr["chain"]:
        if row["type"] == "span":
            toks = (f"  {row['tokens']} token(s)"
                    if row.get("tokens") else "")
            dur = row.get("duration_s")
            out.append(f"  [{row['engine']}] {row['span']:12s} "
                       f"{dur if dur is not None else '?':>9}s  steps "
                       f"{row.get('start_step')}.."
                       f"{row.get('end_step')}{toks}")
        elif row["type"] == "move":
            arrow = ""
            if row.get("source") or row.get("target"):
                arrow = (f" {row.get('source') or '?'} -> "
                         f"{row.get('target') or '?'}")
            bits = [f"  >> {row['event'].upper()}{arrow}"
                    + (f" ({row['reason']})" if row.get("reason")
                       else "")
                    + f" @ fleet round {row.get('round')}"]
            if row.get("blocks") is not None:
                bits.append(f"{row['blocks']} block(s) / "
                            + _fmt_bytes(row.get("bytes")))
            tp = row.get("transport") or {}
            if tp.get("crc_verify_s") is not None:
                bits.append(f"crc_verify "
                            f"{tp['crc_verify_s'] * 1e3:.2f} ms")
            if row.get("replay"):
                bits.append(f"replay {row['replay']} token(s)")
            out.append("  ".join(bits))
        else:   # gap
            tag = ("migration stall (router move explains it)"
                   if row["cause"] == "migration" else
                   "UNRECONCILED — no router record explains this "
                   "dead time (a crash gap, never invented into a "
                   "phase)")
            out.append(f"  ~~ gap {row['duration_s']:>9}s  {tag}")
    if tr["completed"]:
        verdict = ("reconciled" if tr["reconciled"] else
                   "NOT RECONCILED")
        out.append(f"  span sum {tr['span_sum_s']}s + migration gaps "
                   f"{tr['migration_gap_s']}s vs latency "
                   f"{tr['latency_s']}s ({verdict}"
                   + (f"; {tr['unreconciled_gap_s']}s unaccounted)"
                      if tr["unreconciled_gap_s"] > 0 else ")"))
        if tr.get("ttft_s") is not None:
            out.append(f"  ttft {tr['ttft_s']}s  weights version "
                       f"v{tr.get('weights_version')}")
    else:
        out.append("  (no completion record — the request did not "
                   "finish in these streams)")
    for prob in tr["problems"]:
        out.append(f"  note: {prob}")


def _transport_fold(streams) -> dict | None:
    """The latest ``transport_stats`` event across the streams
    (decode/fleet.py emits one at drain end): per-worker per-op RPC
    call/overhead percentiles + the overhead share of round wall."""
    recs = [e for s in streams for e in s.events
            if e.get("event") == "transport_stats"]
    if not recs:
        return None
    rec = max(recs, key=lambda r: r.get("t", 0.0))
    engines = {k: v for k, v in (rec.get("engines") or {}).items() if v}
    if not engines:
        return None
    wall = rec.get("round_wall_s") or 0.0
    overhead = sum(v.get("overhead_total_s") or 0.0
                   for v in engines.values())
    return {
        "rounds": rec.get("rounds"),
        "round_wall_s": wall,
        "rpc_overhead_total_s": round(overhead, 6),
        "rpc_overhead_share_of_round_wall": (
            round(overhead / wall, 4) if wall else None),
        "engines": engines,
    }


def _render_transport(out: list, tr: dict) -> None:
    out.append("")
    share = tr.get("rpc_overhead_share_of_round_wall")
    out.append(f"transport: RPC overhead "
               f"{tr['rpc_overhead_total_s']}s over "
               f"{tr['round_wall_s']}s of round wall"
               + (f" ({share * 100:.1f}%)" if share is not None
                  else ""))
    for eid, st in sorted(tr["engines"].items()):
        hb = ""
        if st.get("heartbeat_rtt_p50_ms") is not None:
            hb = (f"  heartbeat RTT p50 {st['heartbeat_rtt_p50_ms']} "
                  f"ms / p99 {st['heartbeat_rtt_p99_ms']} ms "
                  f"({st.get('heartbeats')} ping(s))")
        out.append(f"  {eid}:{hb}")
        for op, o in (st.get("ops") or {}).items():
            line = (f"    {op:12s} x{o['n']:<5d} call p50 "
                    f"{o['call_p50_ms']} ms  p99 {o['call_p99_ms']} ms")
            if "overhead_p50_ms" in o:
                line += (f"  overhead p50 {o['overhead_p50_ms']} ms  "
                         f"p99 {o['overhead_p99_ms']} ms")
            out.append(line)


def _render_router_postmortem(out: list, label: str | None,
                              docs: list) -> None:
    tag = f" [{label}]" if label else ""
    for doc in docs:
        out.append("")
        if doc.get("error"):
            out.append(f"router postmortem{tag}: {doc['error']}")
            continue
        out.append(f"router postmortem{tag}: engine "
                   f"{doc.get('engine')} declared dead @ round "
                   f"{doc.get('round')} — {doc.get('reason')} "
                   f"({doc.get('path')})")
        al = (doc.get("alerts") or {}).get("active") or []
        if al:
            out.append("  active alert(s) at declaration: " + ", ".join(
                f"{a['detector']} [{a['severity']}] since round "
                f"{a['since_round']}" for a in al))
        ev = doc.get("evidence") or {}
        d = ev.get("last_digest")
        if d:
            out.append(f"  last digest (call id "
                       f"{ev.get('last_digest_call_id')}): waiting "
                       f"{d.get('waiting')}, active {d.get('active')},"
                       f" free blocks {d.get('free_blocks')}, serving "
                       f"v{d.get('serving_version')}")
        if ev.get("pending_call_ids"):
            out.append(f"  pending call id(s): "
                       f"{ev['pending_call_ids']}")
        if ev.get("ping_rtt_ms"):
            out.append(f"  heartbeat RTTs (ms): {ev['ping_rtt_ms']}")
        if ev.get("backoff_log"):
            out.append(f"  backoff retries before the verdict: "
                       f"{len(ev['backoff_log'])}")
        for op in (ev.get("op_log") or [])[-8:]:
            out.append(f"    op {op.get('op'):12s} id {op.get('id')}"
                       f"  {op.get('call_ms')} ms  "
                       f"{'ok' if op.get('ok') else 'ERROR'}")
        if ev.get("last_snapshot_step") is not None:
            out.append(f"  last router-held snapshot: step "
                       f"{ev['last_snapshot_step']} with "
                       f"{ev.get('last_snapshot_requests')} live "
                       "request(s) (migration source)")


def _follow(metrics_dirs: list, interval: float, max_s: float) -> int:
    """Tail mode: poll the streams, print NEW timeline entries as they
    land (keyed by content — the streams are append-only JSONL), exit
    rc 0 once a discovered fleet status doc reports the fleet drained
    with nothing new to print, or after ``max_s``. Reads are
    crash-safe mid-drill: records flush per line (a torn tail is
    skipped by read_metrics) and the status doc only ever replaces
    atomically."""
    import time as _time
    printed: set = set()
    t_start = _time.monotonic()
    t0_ref = None
    sizes: dict = {}
    cache: dict = {}
    last_alerts: str | None = None
    while True:
        new = []
        for d in metrics_dirs:
            # re-parse a stream only when its JSONL actually grew —
            # idle ticks must not re-validate the whole history just
            # to find nothing (streams are append-only)
            path = d
            if os.path.isdir(path):
                path = os.path.join(path, METRICS_FILENAME)
            try:
                size = os.path.getsize(path)
            except OSError:
                size = -1
            if sizes.get(d) != size:
                sizes[d] = size
                s = _Stream(d, None)
                cache[d] = ([(t, s.label, src, what)
                             for t, src, what in s.timeline_entries()]
                            if s.dir_exists else [])
            for key in cache.get(d, ()):
                if key in printed:
                    continue
                printed.add(key)
                new.append(key)
        new.sort(key=lambda x: (x[0], x[1]))
        for t, lab, src, what in new:
            if t0_ref is None:
                t0_ref = t
            print(f"  {_fmt_t(t, t0_ref)}  [{src:7s}] [{lab}] {what}",
                  flush=True)
        status = None
        for d in metrics_dirs:
            p = os.path.join(d, STATUS_FILENAME)
            if os.path.exists(p):
                try:
                    with open(p) as f:
                        status = json.load(f)
                except ValueError:
                    pass    # racing the atomic replace; next tick
        # live watchtower surface (v15): render the status doc's
        # active-alert block whenever it CHANGES — the tail shows
        # what is firing right now, not just the fired/resolved
        # timeline entries as they land
        if status is not None:
            active = (status.get("alerts") or {}).get("active") or []
            fp = json.dumps(active, sort_keys=True)
            if fp != last_alerts and (active or last_alerts
                                      is not None):
                if active:
                    print("  ACTIVE ALERTS: " + ", ".join(
                        f"{a.get('detector')} [{a.get('severity')}] "
                        f"since round {a.get('since_round')}"
                        for a in active), flush=True)
                elif last_alerts is not None:
                    print("  active alerts: none (all resolved)",
                          flush=True)
                last_alerts = fp
        if status is not None and status.get("drained") and not new:
            print(f"report: fleet drained @ round "
                  f"{status.get('round')} — follow complete")
            return 0
        if _time.monotonic() - t_start > max_s:
            print("report: --follow_max_s elapsed without a drained "
                  "status doc — stopping the tail")
            return 0
        _time.sleep(interval)


def _fleet_health(streams) -> dict | None:
    """Fold the per-round ``fleet`` records (decode/fleet.py) into a
    balance summary + a sampled utilization timeline."""
    recs = sorted((r for s in streams for r in s.fleets),
                  key=lambda r: r.get("step", 0))
    if not recs:
        return None
    imbs = [r.get("load_imbalance") or 0.0 for r in recs]
    agg: dict = {}
    for r in recs:
        for eid, st in (r.get("engines") or {}).items():
            a = agg.setdefault(eid, {"alive_rounds": 0,
                                     "dead_rounds": 0, "util": [],
                                     "active": [], "waiting": []})
            if not st.get("alive"):
                a["dead_rounds"] += 1
                continue
            a["alive_rounds"] += 1
            a["role"] = st.get("role")
            a["util"].append(st.get("utilization") or 0.0)
            a["active"].append(st.get("active") or 0)
            a["waiting"].append(st.get("waiting") or 0)
    n = len(recs)
    idx = (range(n) if n <= 16 else
           sorted({round(i * (n - 1) / 15) for i in range(16)}))
    timeline = [{
        "round": recs[i].get("step"),
        "load_imbalance": recs[i].get("load_imbalance"),
        "utilization": {
            eid: (st.get("utilization") if st.get("alive") else None)
            for eid, st in (recs[i].get("engines") or {}).items()},
    } for i in idx]
    return {
        "records": n,
        "rounds": recs[-1].get("step"),
        "load_imbalance_mean": round(float(np.mean(imbs)), 4),
        "load_imbalance_max": round(float(np.max(imbs)), 4),
        "engines": {eid: {
            "role": a.get("role"),
            "alive_rounds": a["alive_rounds"],
            "dead_rounds": a["dead_rounds"],
            "utilization_mean": (round(float(np.mean(a["util"])), 4)
                                 if a["util"] else None),
            "utilization_max": (round(float(np.max(a["util"])), 4)
                                if a["util"] else None),
            "active_mean": (round(float(np.mean(a["active"])), 2)
                            if a["active"] else None),
            "waiting_max": (int(max(a["waiting"]))
                            if a["waiting"] else None),
        } for eid, a in sorted(agg.items())},
        "timeline": timeline,
    }


def _render_fleet_health(out: list, fh: dict) -> None:
    out.append("")
    out.append(f"fleet health: {fh['records']} round record(s) "
               f"(through round {fh['rounds']}), load imbalance "
               f"mean {fh['load_imbalance_mean']} / "
               f"max {fh['load_imbalance_max']}")
    for eid, a in fh["engines"].items():
        if a["alive_rounds"] == 0:
            out.append(f"  {eid:8s} dead for all "
                       f"{a['dead_rounds']} recorded round(s)")
            continue
        dead = (f", dead {a['dead_rounds']} round(s)"
                if a["dead_rounds"] else "")
        out.append(f"  {eid:8s} [{a.get('role')}]  util mean "
                   f"{a['utilization_mean']} max {a['utilization_max']}"
                   f"  active mean {a['active_mean']}  waiting max "
                   f"{a['waiting_max']}{dead}")
    out.append("  utilization timeline (sampled):")
    for row in fh["timeline"]:
        cells = "  ".join(
            f"{eid} {'dead' if u is None else format(u, '.2f')}"
            for eid, u in sorted(row["utilization"].items()))
        out.append(f"    round {row['round']:>4}  "
                   f"imb {row['load_imbalance']:.2f}  {cells}")


def _tenant_of(rec) -> str:
    """The per-tenant bucket key (schema v13): null tenants fold under
    the driver's single-tenant bucket — ONE definition
    (runtime/workload.py ``tenant_key``), so record-side and
    driver-side counts reconcile key for key by construction."""
    from .runtime.workload import tenant_key
    return tenant_key(rec.get("tenant"))


def _workload_fold(streams) -> dict | None:
    """Fold the schema-v13 workload plane: trace identity + the
    offered-vs-served interval curve from the driver's ``workload``
    records, and per-tenant latency/TTFT/ITL percentiles +
    shed/quarantine counts from the per-request records — with the
    cross-check that the driver's cumulative per-tenant counts
    RECONCILE with the request records (sum of per-tenant completions
    == fleet-wide completions; a mismatch renders, never hides)."""
    wl_recs = sorted((r for s in streams for r in s.workloads),
                     key=lambda r: (r.get("t", 0.0), r.get("step", 0)))
    comp = _merged_completions(streams)
    has_tenants = any(r.get("tenant") is not None
                      for s in streams for r in s.requests)
    if not wl_recs and not has_tenants:
        return None
    out: dict = {}
    if wl_recs:
        out["trace"] = wl_recs[0].get("trace")
        n = len(wl_recs)
        idx = (range(n) if n <= 16 else
               sorted({round(i * (n - 1) / 15) for i in range(16)}))
        out["intervals"] = [{
            "step": wl_recs[i].get("step"),
            "offered": wl_recs[i].get("offered"),
            "admitted": wl_recs[i].get("admitted"),
        } for i in idx]
        out["offered_total"] = sum(int(r.get("offered") or 0)
                                   for r in wl_recs)
        out["admitted_total"] = sum(int(r.get("admitted") or 0)
                                    for r in wl_recs)
        # the driver's cumulative per-tenant book: the LAST record is
        # the totals (monotonic by contract)
        out["driver_tenants"] = wl_recs[-1].get("tenants") or {}
    # per-tenant slices off the per-request records (merged + deduped
    # like every fleet-level read)
    tenants: dict = {}

    def bucket(t):
        return tenants.setdefault(t, {
            "completed": 0, "quarantined": 0, "shed": 0,
            "latencies": [], "ttfts": []})

    for r in comp.values():
        b = bucket(_tenant_of(r))
        b["completed"] += 1
        if r.get("latency_s") is not None:
            b["latencies"].append(r["latency_s"])
        if r.get("ttft_s") is not None:
            b["ttfts"].append(r["ttft_s"])
    seen_q = set()
    seen_exp = set()
    for s in streams:
        for r in s.requests:
            key = (r.get("uid"), r.get("event"), r.get("step"))
            if r["event"] == "quarantined":
                if key in seen_q:
                    continue
                seen_q.add(key)
                bucket(_tenant_of(r))["quarantined"] += 1
            elif r["event"] == "expired":
                # by UID, not (uid, step): a request that expired on a
                # dead engine after its last snapshot re-expires on the
                # survivor it was replayed to — two records, ONE
                # caller-visible loss (the fleet summary's
                # expired_uids stance)
                if r.get("uid") in seen_exp:
                    continue
                seen_exp.add(r.get("uid"))
                bucket(_tenant_of(r))["shed"] += 1
    # driver-counted admission sheds (the request records never saw a
    # shed request's tenant — the anonymous uid -1)
    for t, c in (out.get("driver_tenants") or {}).items():
        if c.get("shed"):
            bucket(t)["shed"] += int(c["shed"])
    # per-tenant ITL off the decode-segment spans (spans pin tenant)
    itl: dict = {}
    for ss in _merged_spans(streams).values():
        for sp in ss:
            if sp["span"] == "decode" and sp.get("tokens") \
                    and sp.get("duration_s") is not None:
                itl.setdefault(_tenant_of(sp), []).append(
                    sp["duration_s"] / sp["tokens"])
    folded = {}
    for t in sorted(tenants):
        b = tenants[t]
        e = {"completed": b["completed"],
             "quarantined": b["quarantined"], "shed": b["shed"]}
        if b["latencies"]:
            (e["latency_p50_s"], e["latency_p90_s"],
             e["latency_p99_s"]) = _pct3(b["latencies"])
        if b["ttfts"]:
            (e["ttft_p50_s"], e["ttft_p90_s"],
             e["ttft_p99_s"]) = _pct3(b["ttfts"])
        if itl.get(t):
            (e["itl_p50_s"], e["itl_p90_s"],
             e["itl_p99_s"]) = _pct3(itl[t], 6)
        folded[t] = e
    out["tenants"] = folded
    # the reconciliation: per-tenant sums vs fleet totals, and the
    # driver's book vs the records' — numbers that disagree are a
    # measurement bug, so the report SAYS so instead of averaging it
    total_completed = sum(e["completed"] for e in folded.values())
    out["completed_total"] = len(comp)
    out["reconciled"] = total_completed == len(comp)
    if wl_recs:
        drv = out["driver_tenants"]
        rec_ok = all(
            folded.get(t, {}).get("completed") == c.get("completed")
            for t, c in drv.items())
        out["reconciled"] = out["reconciled"] and rec_ok
    return out


def _render_workload(out: list, wl: dict) -> None:
    out.append("")
    tr = wl.get("trace") or {}
    head = "workload"
    if tr:
        head += (f" [trace {tr.get('id')} v{tr.get('version')}]")
    offered = wl.get("offered_total")
    if offered is not None:
        head += (f": {offered} offered, {wl.get('admitted_total')} "
                 f"admitted, {wl.get('completed_total')} completed")
    out.append(head + ("" if wl["reconciled"] else
                       "  [NOT RECONCILED — per-tenant sums disagree "
                       "with fleet totals]"))
    for t, e in wl["tenants"].items():
        line = (f"  tenant {t:10s} {e['completed']} completed, "
                f"{e['shed']} shed, {e['quarantined']} quarantined")
        if "latency_p50_s" in e:
            line += (f"  latency p50 {e['latency_p50_s']}s "
                     f"p99 {e['latency_p99_s']}s")
        if "ttft_p50_s" in e:
            line += (f"  TTFT p50 {e['ttft_p50_s']}s "
                     f"p99 {e['ttft_p99_s']}s")
        if "itl_p50_s" in e:
            line += (f"  ITL p50 {e['itl_p50_s']}s "
                     f"p99 {e['itl_p99_s']}s")
        out.append(line)
    if wl.get("intervals"):
        out.append("  offered vs admitted per interval (sampled):")
        for row in wl["intervals"]:
            out.append(f"    round {row['step']:>4}  offered "
                       f"{row['offered']:>3}  admitted "
                       f"{row['admitted']:>3}")


def _render_slo(out: list, slo: dict) -> None:
    out.append("")
    pct = ("n/a" if slo["attainment"] is None
           else f"{slo['attainment'] * 100:.1f}%")
    out.append(f"SLO attainment (TTFT <= {slo['slo_ttft_s']}s, "
               f"ITL <= {slo['slo_itl_s']}s): {pct} — "
               f"{slo['attained']}/{slo['completed']} attained, "
               f"{slo['violated']} violated, "
               f"{slo['unreconciled']} unreconciled")
    if slo["violations_by_span"]:
        out.append("  violations by attributed span: " + ", ".join(
            f"{k} {v}" for k, v in sorted(
                slo["violations_by_span"].items(),
                key=lambda kv: -kv[1])))
    bt = slo.get("by_tenant") or {}
    if bt and set(bt) != {"default"}:
        # the per-tenant goodput slice (v13): print only on a real
        # multi-tenant run — a single-tenant report already said it
        for t, b in sorted(bt.items()):
            pct = ("n/a" if b["attainment"] is None
                   else f"{b['attainment'] * 100:.1f}%")
            out.append(f"  tenant {t:10s} goodput {pct} — "
                       f"{b['attained']}/{b['completed']} attained, "
                       f"{b['violated']} violated, "
                       f"{b['unreconciled']} unreconciled")
    bp = slo.get("by_policy") or {}
    if bp:
        # the per-policy goodput slice (v14): only labelled runs
        # (``generate --policy``) land here — the policy-search readout
        for p, b in sorted(bp.items()):
            pct = ("n/a" if b["attainment"] is None
                   else f"{b['attainment'] * 100:.1f}%")
            out.append(f"  policy {p:10s} goodput {pct} — "
                       f"{b['attained']}/{b['completed']} attained, "
                       f"{b['violated']} violated, "
                       f"{b['unreconciled']} unreconciled")
    for e in slo["requests"]:
        if e["status"] == "attained":
            continue
        if e["status"] == "unreconciled":
            out.append(f"  uid {e['uid']} UNRECONCILED — {e.get('why')}")
            continue
        viol = "+".join(e.get("violates", []))
        bd = ", ".join(f"{k} {v}s" for k, v in
                       list(e.get("breakdown", {}).items())[:4])
        out.append(f"  uid {e['uid']} VIOLATED ({viol}: ttft "
                   f"{e['ttft_s']}s, itl {e['itl_s']}s) -> attributed "
                   f"{e.get('attributed')}"
                   + (" [migrated]" if e["migrated"] else "")
                   + (f"  ({bd})" if bd else ""))


def _render_engine_sections(out: list, doc: dict) -> None:
    """Text render of one stream's folded sections (appended to
    ``out``) — shared between the single- and multi-stream layouts."""
    if doc.get("run"):
        out.append("run config:")
        for k, v in doc["run"].items():
            out.append(f"  {k}: {v}")
    for strat, st in doc.get("steps", {}).items():
        out.append("")
        out.append(f"steps [{strat}]: {st['logged_steps']} logged "
                   f"record(s), steps {st['first_step']}.."
                   f"{st['last_step']}")
        if "step_time_p50_ms" in st:
            out.append(f"  step time   p50 {st['step_time_p50_ms']} ms  "
                       f"p90 {st['step_time_p90_ms']} ms  "
                       f"p99 {st['step_time_p99_ms']} ms "
                       "(steady-state: first logged chunk excluded)")
        if "tokens_per_sec_mean" in st:
            out.append(f"  throughput  mean {st['tokens_per_sec_mean']} "
                       f"tok/s  best {st['tokens_per_sec_best']} tok/s")
        if "mfu_mean" in st:
            out.append(f"  MFU         mean {st['mfu_mean']}  "
                       f"best {st['mfu_best']}")
        if "first_loss" in st:
            out.append(f"  loss        {st['first_loss']} -> "
                       f"{st['last_loss']}")
        if "hbm_high_water_bytes" in st:
            out.append("  HBM high-water  "
                       + _fmt_bytes(st["hbm_high_water_bytes"]))
    if doc.get("serving"):
        sv = doc["serving"]
        out.append("")
        out.append(f"serving [{sv.get('kv_dtype')}]: "
                   f"{sv['records']} decode record(s), "
                   f"{sv.get('engine_steps')} engine step(s), "
                   f"{sv.get('tokens_generated')} token(s), "
                   f"{sv.get('compiled_programs')} compiled program(s)")
        if "tokens_per_sec_mean" in sv:
            out.append(f"  throughput  mean {sv['tokens_per_sec_mean']} "
                       f"tok/s  best {sv['tokens_per_sec_best']} tok/s")
        if "batch_occupancy_mean" in sv:
            out.append(f"  occupancy   mean {sv['batch_occupancy_mean']}")
        if "accept_rate" in sv:
            out.append(f"  speculation accept rate {sv['accept_rate']}  "
                       f"({sv.get('accepted_tokens')}/"
                       f"{sv.get('drafted_tokens')} drafted; "
                       f"{sv.get('tokens_per_step')} tokens/step)")
        if "prefix_hit_blocks" in sv:
            rate = sv.get("prefix_hit_rate")
            out.append(f"  prefix cache hit {sv['prefix_hit_blocks']} "
                       f"block(s)"
                       + (f" (rate {rate})" if rate is not None else "")
                       + f", saved {sv.get('prefill_tokens_saved')} "
                       f"prefill token(s), peak "
                       f"{sv.get('shared_blocks_max')} shared block(s), "
                       f"{sv.get('cow_copies')} CoW cop(ies)")
        if "spilled_blocks" in sv:
            out.append(f"  KV spill    {sv['spilled_blocks']} "
                       f"demotion(s) ({_fmt_bytes(sv.get('spill_bytes'))}"
                       f"), {sv.get('restores')} restore(s) saving "
                       f"{sv.get('restore_tokens_saved')} prefill "
                       f"token(s) in {sv.get('restore_stall_s')}s, "
                       f"peak host tier "
                       f"{sv.get('host_tier_utilization_max')}")
        if "partial_hits" in sv:
            out.append(f"  KV spill    {sv['partial_hits']} sub-block "
                       "partial hit(s)")
        if "kv_pool_utilization_max" in sv:
            out.append("  KV pool     max utilization "
                       f"{sv['kv_pool_utilization_max']}")
        if "free_blocks_low_water" in sv:
            out.append(f"  KV pool     free-block low water "
                       f"{sv['free_blocks_low_water']}, churn "
                       f"{sv.get('block_allocs')} alloc(s) / "
                       f"{sv.get('block_frees')} free(s) / "
                       f"{sv.get('block_scrubs')} scrub(s)")
        if "kv_fragmentation_max" in sv:
            out.append(f"  KV pool     max fragmentation "
                       f"{sv['kv_fragmentation_max']}  stored "
                       + _fmt_bytes(sv.get("kv_bytes_stored_max")))
    if doc.get("serving_reliability"):
        rl = doc["serving_reliability"]
        out.append("")
        out.append(f"serving reliability: {rl['admitted']} admission(s), "
                   f"{rl['completed']} completed, "
                   f"{rl['quarantined']} quarantine(s), "
                   f"{rl['retried']} retry(ies), "
                   f"{rl['preempted']} preemption(s), "
                   f"{rl['shed']} shed "
                   f"({rl['rejected']} rejected / {rl['expired']} "
                   "expired)")
        if rl.get("failed_uids"):
            out.append(f"  FAILED uids: {rl['failed_uids']}")
        if "latency_p50_s" in rl:
            out.append(f"  request latency  p50 {rl['latency_p50_s']}s  "
                       f"p90 {rl['latency_p90_s']}s  "
                       f"p99 {rl['latency_p99_s']}s")
        if "ttft_p50_s" in rl:
            out.append(f"  TTFT             p50 {rl['ttft_p50_s']}s  "
                       f"p90 {rl['ttft_p90_s']}s  "
                       f"p99 {rl['ttft_p99_s']}s")
        if "itl_p50_s" in rl:
            out.append(f"  ITL (per decode segment)  "
                       f"p50 {rl['itl_p50_s']}s  "
                       f"p90 {rl['itl_p90_s']}s  "
                       f"p99 {rl['itl_p99_s']}s")
        if len(rl.get("completed_by_version") or {}) > 1:
            out.append("  completions by weights version: " + ", ".join(
                f"{k} x{v}" for k, v in sorted(
                    rl["completed_by_version"].items())))
    if doc.get("step_phases"):
        sp = doc["step_phases"]
        out.append("")
        out.append(f"step phases: {sp['steps']} engine step(s), mean "
                   f"{sp['step_mean_ms']} ms")
        out.append(f"  {'phase':18s} {'steps':>6s} {'mean ms':>10s} "
                   f"{'p99 ms':>10s} {'share':>7s}")
        for name, ph in sorted(sp["phases"].items(),
                               key=lambda kv: -(kv[1]["share"] or 0.0)):
            out.append(f"  {name:18s} {ph['steps']:6d} "
                       f"{ph['mean_ms']:10.4f} {ph['p99_ms']:10.4f} "
                       f"{100 * (ph['share'] or 0.0):6.2f}%")
        out.append(f"  {'program (bucket)':18s} {'runs':>6s} "
                   f"{'mean ms':>10s} {'p99 ms':>10s}   launch to "
                   "the end of the read")
        for d in sp["dispatches"]:
            label = f"{d['kind']} ({d['bucket']})"
            out.append(f"  {label:18s} {d['count']:6d} "
                       f"{d['mean_ms']:10.4f} {d['p99_ms']:10.4f}")
        cr = sp.get("cache_reads")
        if cr and cr["blocks"]:
            kb = cr["blocks"]
            read, held = (kb["kv_blocks_read_mean"],
                          kb["kv_blocks_capacity_mean"])
            out.append(
                f"  cache reads: {read} blocks a step fetched by the "
                f"decode-side reads, of {held} in their rows' tables "
                f"({100 * read / held:.1f}%; {kb['steps']} step(s))"
                + _row_bytes(kb["kv_row_bytes"]))
            if kb["ring_blocks_capacity_mean"]:
                out.append(
                    f"  cache reads: {kb['ring_blocks_read_mean']} blocks "
                    "a step fetched of the window layers' rings, of "
                    f"{kb['ring_blocks_capacity_mean']} entries"
                    + _row_bytes(kb["window_row_bytes"]))
        if cr and "steps" in cr:
            out.append(
                f"  cache reads: {cr['window_rows_mean']} positions a "
                f"step in a window layer, {cr['full_rows_mean']} in a "
                f"full one ({cr['steps']} step(s)); window blocks: "
                f"{cr['window_blocks_live_max']} held at most, "
                f"{cr['window_blocks_released']} released")
            if cr["summary_rows_mean"] or cr["summaries_written"]:
                out.append(
                    f"  cache reads: {cr['summary_rows_mean']} chunk "
                    "summaries a step beside the window's positions, "
                    f"{cr['summaries_written']} written")
        row = sp.get("state_row")
        if row:
            state, tail = (row[k] for k in STEP_SPAN_STATE_ROW)
            out.append(
                f"  state row: a slot keeps {state + tail} bytes a "
                f"recurrent layer: {state} of state, {tail} of tail")
    rec = doc.get("recovery", {})
    if (rec.get("attempts_failed") or rec.get("nonfinite_skips")
            or rec.get("attempt_log")
            or rec.get("in_graph_skips") or rec.get("rollbacks")):
        out.append("")
        out.append(f"recovery: {rec['in_graph_skips']} in-graph "
                   f"skip(s), {rec['rollbacks']} rollback(s), "
                   f"{rec['loss_spikes']} loss spike(s), "
                   f"{rec['attempts_failed']} failed "
                   f"attempt(s), {rec['nonfinite_skips']} non-finite "
                   f"skip(s), {rec['publishes']} checkpoint "
                   f"publish(es), run "
                   + ("COMPLETED" if rec["completed"] else
                      "did not record completion"))


def _render_waterfalls(out: list, label: str | None, wf: dict) -> None:
    if not wf:
        return
    out.append("")
    tag = f" [{label}]" if label else ""
    out.append(f"per-request waterfalls{tag}:")
    shown = 0
    for uid, w in wf.items():
        if shown >= 16:
            out.append(f"  ... {len(wf) - shown} more request(s) "
                       "(see --json for all)")
            break
        shown += 1
        verdict = ("reconciled" if w["reconciled"] else
                   ("no completion record" if w["latency_s"] is None
                    else "NOT RECONCILED — unaccounted wall time"))
        lat = ("" if w["latency_s"] is None
               else f", latency {w['latency_s']}s")
        ttft = ("" if w.get("ttft_s") is None
                else f", ttft {w['ttft_s']}s")
        out.append(f"  uid {uid} — {len(w['spans'])} span(s), "
                   f"span sum {w['span_sum_s']}s{lat}{ttft} "
                   f"({verdict})")
        for s in w["spans"]:
            dur = s.get("duration_s")
            out.append(f"    {s['span']:12s} "
                       f"{dur if dur is not None else '?':>9}s  "
                       f"steps {s.get('start_step')}.."
                       f"{s.get('end_step')}")


def _alerts_active_at(alerts: list, t: float) -> list:
    """The watchtower alerts active (fired, unresolved) at wall time
    ``t`` — ``alerts`` pre-sorted by envelope time. Drift alerts key
    per metric (one detector name, two lifecycles)."""
    active: dict = {}
    for a in alerts:
        if a.get("t", 0.0) > t:
            break
        key = (a.get("detector"), a.get("metric"))
        if a.get("event") == "fired":
            active[key] = a
        else:
            active.pop(key, None)
    return [{"detector": a.get("detector"),
             "severity": a.get("severity"),
             "since_round": a.get("step")}
            for _, a in sorted(active.items(),
                               key=lambda kv: str(kv[0]))]


def _render_postmortem(out: list, label: str | None,
                       fr: dict | None) -> None:
    tag = f" [{label}]" if label else ""
    out.append("")
    if fr is None:
        out.append(f"postmortem{tag}: no flight-recorder dump (the "
                   "engine dumps on quarantine / watchdog / kill only)")
        return
    if fr.get("error"):
        out.append(f"postmortem{tag}: {fr['error']}")
        return
    out.append(f"postmortem{tag}: {fr.get('reason')!r} @ engine step "
               f"{fr.get('step')} — {len(fr.get('digests', []))} "
               f"step digest(s) ({fr.get('path')})")
    if fr.get("alerts_at_dump"):
        out.append("  active alert(s) at declaration: " + ", ".join(
            f"{a['detector']} [{a['severity']}] since round "
            f"{a['since_round']}" for a in fr["alerts_at_dump"]))
    for d in fr.get("digests", []):
        bits = [f"step {d.get('step'):>4}",
                f"occ {d.get('occupancy'):.2f}",
                f"free {d.get('free_blocks')}",
                f"waiting {d.get('waiting')}"]
        if d.get("prefill_uid") is not None:
            bits.append(f"prefill uid {d['prefill_uid']}")
        if d.get("decode_uids"):
            bits.append(f"decode uids {d['decode_uids']}")
        if d.get("finite") is not None and not all(d["finite"]):
            bits.append(f"FINITE {d['finite']}")
        line = "  " + "  ".join(bits)
        if d.get("events"):
            line += "  | " + "; ".join(d["events"])
        out.append(line)


# ---- golden-stream diffing (v15, DESIGN.md section 27) --------------
# Two replays of one committed trace must agree on every pinned value;
# where they legitimately differ is WALL TIME — the unpinned envelope
# plus any measured duration/throughput. The differ strips the
# envelope, localizes the first divergent record, and classifies what
# kind of drift it is so "the replays differ" is never the end of the
# diagnosis. scripts/stream_diff.py is the standalone CLI over the
# same functions.

# a differing key is TIMING (not a determinism break) when it measures
# wall-clock — matched by suffix so new measured fields inherit the
# classification without a registry edit
_TIMING_SUFFIXES = ("_s", "_ms", "_us", "_per_sec")
_TIMING_KEYS = {"t", "t_start", "t_end", "dt", "tokens_per_sec"}


def _is_timing_key(key: str) -> bool:
    return key in _TIMING_KEYS or key.endswith(_TIMING_SUFFIXES)


# inside a record's nested ``transport`` attribution these keys name
# HOW the bytes moved, not WHAT moved — two honest replays of one run
# under different transports (inproc vs process vs tcp) legitimately
# disagree on them while every pinned value (tokens, bytes, blocks,
# positions) must still match
_TRANSPORT_EQUIV_KEYS = {"mode"}


def _transport_equiv(va, vb) -> bool:
    """True when two ``transport`` values differ only by carrier: the
    meta record's transport label (a string), or a migration record's
    attribution dict differing only in ``mode`` and wall-clock
    measurements (``crc_verify_s`` — the in-process mode honestly
    reports None where a wire mode reports a verify wall). Any pinned
    content key (``bytes``, ``retries``) must agree."""
    if isinstance(va, str) and isinstance(vb, str):
        return True
    if not (isinstance(va, dict) and isinstance(vb, dict)):
        return False
    if va.keys() != vb.keys():
        return False
    return all(va[k] == vb[k] or k in _TRANSPORT_EQUIV_KEYS
               or _is_timing_key(k) for k in va)


def _is_benign_diff(key: str, ra: dict, rb: dict) -> bool:
    """A differing key that does NOT break determinism: a wall-clock
    measurement, or a transport attribution differing only by
    carrier (the transport-mode-only class — two transports replaying
    one trace token-identically)."""
    if _is_timing_key(key):
        return True
    return key == "transport" and _transport_equiv(ra.get(key),
                                                   rb.get(key))


def load_diff_stream(metrics_dir: str,
                     kinds: tuple | None = None) -> list[dict]:
    """One side of a golden-stream diff: the dir's ``metrics.jsonl``
    in append order, schema-valid records only, the unpinned wall
    envelope (``t``) stripped. ``kinds`` filters to those record
    kinds (e.g. ``("alert",)`` for the replay-identity check)."""
    path = metrics_dir
    if os.path.isdir(path):
        path = os.path.join(path, METRICS_FILENAME)
    records, _problems = read_metrics(path)
    out = []
    for r in records:
        if kinds is not None and r.get("kind") not in kinds:
            continue
        r = dict(r)
        r.pop("t", None)
        out.append(r)
    return out


def diff_streams(a: list[dict], b: list[dict]) -> dict:
    """Localize + classify the first divergence between two record
    streams (each from ``load_diff_stream``). Returns a dict with
    ``verdict`` one of:

    - ``identical`` — byte-equivalent after envelope stripping;
    - ``timing-only`` — records align and every differing key is a
      wall-clock measurement or a transport-mode-only attribution
      (two honest replays of one run — possibly on two transports);
    - ``token-divergence`` — a pinned content key differs, or one
      stream holds records the other lacks (THE determinism break);
    - ``schema-drift`` — aligned records disagree on kind/key-set/
      schema version (different writers, not different runs).

    Verdict severity is schema-drift > token-divergence > timing-only;
    ``index``/``a``/``b``/``keys`` localize the first record of the
    verdict's class."""
    first: dict[str, tuple] = {}
    for i in range(min(len(a), len(b))):
        ra, rb = a[i], b[i]
        if ra == rb:
            continue
        if (ra.get("kind") != rb.get("kind")
                or ra.get("schema") != rb.get("schema")
                or ra.keys() != rb.keys()):
            first.setdefault("schema-drift", (i, ra, rb, sorted(
                ra.keys() ^ rb.keys())))
            continue
        keys = sorted(k for k in ra if ra[k] != rb[k])
        if all(_is_benign_diff(k, ra, rb) for k in keys):
            first.setdefault("timing-only", (i, ra, rb, keys))
        else:
            first.setdefault("token-divergence",
                             (i, ra, rb,
                              [k for k in keys
                               if not _is_benign_diff(k, ra, rb)]))
    if len(a) != len(b):
        i = min(len(a), len(b))
        first.setdefault("token-divergence",
                         (i, a[i] if i < len(a) else None,
                          b[i] if i < len(b) else None, ["<length>"]))
    for verdict in ("schema-drift", "token-divergence", "timing-only"):
        if verdict in first:
            i, ra, rb, keys = first[verdict]
            return {"verdict": verdict, "index": i, "keys": keys,
                    "a": ra, "b": rb,
                    "n_a": len(a), "n_b": len(b)}
    return {"verdict": "identical", "n_a": len(a), "n_b": len(b)}


# ---- telemetry invariant audit (v15, DESIGN.md section 27) ----------
# The one-shot auditor behind `report --audit`: every invariant the
# writers are SUPPOSED to hold, checked over a finished run's metrics
# dirs. The catalog is ordered — rc 2 names the FIRST violated
# invariant and the record that broke it, so a red audit is a
# diagnosis, not a boolean.

def _audit_violation(inv: str, stream, what: str) -> str:
    return (f"audit: VIOLATION [{inv}] in {stream.path}: {what}")


def _audit_schema(streams) -> str | None:
    for s in streams:
        if s.problems:
            return _audit_violation("schema", s, s.problems[0])
    return None


def _audit_span_reconciliation(streams) -> str | None:
    """Span telescoping + request latency arithmetic: every span ends
    at-or-after it starts (both clocks), and a completed request's
    TTFT never exceeds its latency (``ttft_s + post-first-token time
    == latency_s`` is the waterfall fold's reconciliation; the hard
    invariant auditable per record is the ordering)."""
    for s in streams:
        for sp in s.spans:
            if (sp.get("start_step") is not None
                    and sp["start_step"] > sp["step"]):
                return _audit_violation(
                    "span_reconciliation", s,
                    f"span {sp.get('span')!r} uid {sp.get('uid')} "
                    f"starts at step {sp['start_step']} AFTER its end "
                    f"step {sp['step']}")
            if (sp.get("t_start") is not None
                    and sp["t_start"] > sp["t"] + 1e-9):
                return _audit_violation(
                    "span_reconciliation", s,
                    f"span {sp.get('span')!r} uid {sp.get('uid')} "
                    f"t_start {sp['t_start']} after its end t "
                    f"{sp['t']}")
        for r in s.requests:
            if r.get("event") != "completed":
                continue
            ttft, lat = r.get("ttft_s"), r.get("latency_s")
            if (ttft is not None and lat is not None
                    and ttft > lat + RECONCILE_TOL_S):
                return _audit_violation(
                    "span_reconciliation", s,
                    f"completed uid {r.get('uid')} has ttft_s {ttft} "
                    f"> latency_s {lat}")
            if r.get("n_new") is not None and r["n_new"] < 1:
                return _audit_violation(
                    "span_reconciliation", s,
                    f"completed uid {r.get('uid')} claims n_new "
                    f"{r['n_new']} (< 1 token)")
    return None


def _audit_counter_monotonicity(streams) -> str | None:
    """Per-stream clocks and cumulative books never run backwards —
    across resume too (replayed records re-emit at their original,
    stable steps)."""
    for s in streams:
        last_fleet = None
        for f in s.fleets:
            if last_fleet is not None and f["step"] <= last_fleet:
                return _audit_violation(
                    "counter_monotonicity", s,
                    f"fleet round {f['step']} after round "
                    f"{last_fleet} (round clock ran backwards)")
            last_fleet = f["step"]
        last = None
        for d in s.decodes:
            if last is not None and d["step"] < last:
                return _audit_violation(
                    "counter_monotonicity", s,
                    f"decode record at step {d['step']} after step "
                    f"{last}")
            last = d["step"]
        # the workload driver's cumulative per-tenant book
        prev: dict = {}
        for w in s.workloads:
            for tn, c in (w.get("tenants") or {}).items():
                for key in ("offered", "completed", "shed"):
                    cur = int(c.get(key) or 0)
                    if cur < prev.get((tn, key), 0):
                        return _audit_violation(
                            "counter_monotonicity", s,
                            f"workload record @ round {w['step']}: "
                            f"tenant {tn} cumulative {key} fell "
                            f"{prev[(tn, key)]} -> {cur}")
                    prev[(tn, key)] = cur
    return None


def _audit_tenant_reconciliation(streams) -> str | None:
    """The final workload record's per-tenant book must balance:
    completed + shed never exceeds offered, and the interval counters
    sum to no more than the cumulative offered."""
    for s in streams:
        if not s.workloads:
            continue
        final = s.workloads[-1]
        for tn, c in (final.get("tenants") or {}).items():
            off = int(c.get("offered") or 0)
            done = int(c.get("completed") or 0)
            shed = int(c.get("shed") or 0)
            if done + shed > off:
                return _audit_violation(
                    "tenant_reconciliation", s,
                    f"tenant {tn}: completed {done} + shed {shed} > "
                    f"offered {off} in the final workload record")
        total_off = sum(int(w.get("offered") or 0)
                        for w in s.workloads)
        cum_off = sum(int(c.get("offered") or 0)
                      for c in (final.get("tenants") or {}).values())
        if total_off != cum_off:
            return _audit_violation(
                "tenant_reconciliation", s,
                f"interval offered counts sum to {total_off} but the "
                f"final cumulative book holds {cum_off}")
    return None


def _audit_trace_consistency(streams) -> str | None:
    """One uid, one trace_id — across every stream in the set (the
    spine of cross-process stitching; a uid with two trace ids can't
    be traced)."""
    seen: dict = {}
    for s in streams:
        for r in (*s.requests, *s.spans, *s.routers):
            uid, tid = r.get("uid"), r.get("trace_id")
            if uid is None or uid == -1 or tid is None:
                continue
            if uid in seen and seen[uid][0] != tid:
                return _audit_violation(
                    "trace_consistency", s,
                    f"uid {uid} carries trace_id {tid!r} but "
                    f"{seen[uid][1]} recorded {seen[uid][0]!r}")
            seen.setdefault(uid, (tid, s.path))
    return None


def _audit_router_xref(streams) -> str | None:
    """Router decisions cross-reference request outcomes: a uid the
    router shed never completes, and a uid the router moved
    (handoff/migration) was routed first."""
    shed, routed, moved = set(), set(), {}
    for s in streams:
        for r in s.routers:
            uid = r.get("uid")
            if uid is None or uid == -1:
                continue
            if r["event"] == "shed":
                shed.add(uid)
            elif r["event"] == "routed":
                routed.add(uid)
            elif r["event"] in ("handoff", "migrated"):
                moved.setdefault(uid, r)
    if not (shed or routed or moved):
        return None     # no router stream in the set — nothing to xref
    for s in streams:
        for r in s.requests:
            if r.get("event") == "completed" and r.get("uid") in shed:
                return _audit_violation(
                    "router_xref", s,
                    f"uid {r['uid']} completed but the router shed it")
    for uid, r in sorted(moved.items()):
        if uid not in routed:
            for s in streams:
                if r in s.routers:
                    return _audit_violation(
                        "router_xref", s,
                        f"uid {uid} was {r['event']} @ round "
                        f"{r.get('step')} without a routed record")
    return None


def _audit_dedup(streams) -> str | None:
    """Replayed records must be REPLAYS: duplicate (uid, event, step)
    request records within one stream agree on their deterministic
    payload (token count), or a resume double-counted work."""
    for s in streams:
        by: dict = {}
        for r in s.records:
            if r["kind"] == "request":
                by.setdefault((r.get("uid"), r.get("event"),
                               r.get("step")), []).append(r)
        for (uid, ev, step), recs in by.items():
            if len(recs) < 2 or ev == "rejected":
                continue
            n_new = {r.get("n_new") for r in recs}
            if len(n_new) > 1:
                return _audit_violation(
                    "dedup", s,
                    f"uid {uid} {ev} @ step {step} recorded "
                    f"{len(recs)}x with differing n_new "
                    f"{sorted(n_new, key=str)}")
    return None


# ordered: rc 2 names the FIRST violated invariant in THIS order
_AUDIT_CATALOG = (
    ("schema", _audit_schema),
    ("span_reconciliation", _audit_span_reconciliation),
    ("counter_monotonicity", _audit_counter_monotonicity),
    ("tenant_reconciliation", _audit_tenant_reconciliation),
    ("trace_consistency", _audit_trace_consistency),
    ("router_xref", _audit_router_xref),
    ("dedup", _audit_dedup),
)


def audit_streams(streams) -> str | None:
    """Run the ordered invariant catalog over the stream set; None
    when every invariant holds, else the first violation line."""
    for _name, check in _AUDIT_CATALOG:
        msg = check(streams)
        if msg is not None:
            return msg
    return None


def report_main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="report",
        description="Fold one or more --metrics_dir runs (+ supervise "
                    "attempt logs + optional profile dir) into one run "
                    "report; multiple dirs merge onto one timeline "
                    "with per-engine stats")
    p.add_argument("metrics_dirs", nargs="+",
                   help="the run's --metrics_dir (holds metrics.jsonl); "
                        "pass several to merge engines onto one "
                        "timeline")
    p.add_argument("--attempt_log", default=None,
                   help="supervise's per-attempt JSONL (default: "
                        "discovered from each run's meta records)")
    p.add_argument("--profile_dir", default=None,
                   help="a trace directory captured with --profile_dir; "
                        "adds comm/compute overlap + per-named-scope "
                        "totals")
    p.add_argument("--postmortem", action="store_true",
                   help="render each stream's flight-recorder dump "
                        "(per-step scheduler digests persisted on "
                        "quarantine / watchdog / kill)")
    p.add_argument("--slo", default=None, metavar="TTFT_S:ITL_S",
                   help="serving-SLO goodput accounting over the "
                        "merged streams: attainment of TTFT <= TTFT_S "
                        "and observed inter-token latency <= ITL_S "
                        "over completed requests, each violation "
                        "attributed to its dominant span (queued / "
                        "prefill / replay / decode / preempt_gap / "
                        "quarantine / migration); e.g. --slo 0.5:0.05")
    p.add_argument("--trace", default=None, metavar="UID",
                   help="render ONE request's cross-engine causal "
                        "waterfall, stitched by its trace_id (schema "
                        "v12): spans, router moves, and lifecycle "
                        "events across every given stream in causal "
                        "order, with unexplained wall-clock gaps "
                        "flagged UNRECONCILED; rc 2 on a non-integer "
                        "or unknown uid")
    p.add_argument("--follow", action="store_true",
                   help="tail mode: poll the streams, print NEW "
                        "timeline entries as they land, exit rc 0 "
                        "when the router's fleet status doc reports "
                        "the fleet drained (or after --follow_max_s)")
    p.add_argument("--follow_interval", type=float, default=0.5,
                   help="poll cadence of --follow in seconds")
    p.add_argument("--follow_max_s", type=float, default=60.0,
                   help="--follow gives up (rc 0, with a note) after "
                        "this many seconds without a drained status")
    p.add_argument("--audit", action="store_true",
                   help="one-shot telemetry invariant audit over the "
                        "given metrics dir(s): schema validity, span "
                        "telescoping + latency arithmetic, counter "
                        "monotonicity across resume, per-tenant "
                        "reconciliation, trace_id consistency, "
                        "router/request cross-references, replay "
                        "dedup; rc 0 clean, rc 2 naming the FIRST "
                        "violated invariant and the record")
    p.add_argument("--diff", action="store_true",
                   help="golden-stream diff of EXACTLY TWO metrics "
                        "dirs: strips the wall envelope, localizes "
                        "the first divergent record, classifies it "
                        "timing-only / token-divergence / "
                        "schema-drift; rc 0 when identical or "
                        "timing-only, rc 2 otherwise")
    p.add_argument("--kinds", default=None, metavar="K1,K2",
                   help="--diff filter: compare only these record "
                        "kinds (e.g. --kinds alert for the alert-"
                        "history replay-identity check)")
    p.add_argument("--json", action="store_true",
                   help="emit the folded report as one JSON object "
                        "instead of text")
    args = p.parse_args(argv)

    # the train-CLI parse discipline: a malformed --trace uid rejects
    # rc 2 BEFORE any stream is read
    trace_uid = None
    if args.trace is not None:
        try:
            trace_uid = int(args.trace)
        except ValueError:
            print(f"report: unparseable --trace {args.trace!r} (want "
                  "a request uid, e.g. --trace 2)", file=sys.stderr)
            return 2
    if args.follow and args.json:
        print("report: --follow is a live text tail; drop --json",
              file=sys.stderr)
        return 2
    if args.audit and args.diff:
        print("report: --audit checks one run's invariants, --diff "
              "compares two runs — pick one", file=sys.stderr)
        return 2
    if args.diff and len(args.metrics_dirs) != 2:
        print(f"report: --diff compares exactly TWO metrics dirs, got "
              f"{len(args.metrics_dirs)}", file=sys.stderr)
        return 2
    if args.kinds is not None and not args.diff:
        print("report: --kinds filters a --diff; pass --diff A B",
              file=sys.stderr)
        return 2
    diff_kinds = None
    if args.kinds is not None:
        diff_kinds = tuple(k.strip() for k in args.kinds.split(",")
                           if k.strip())
        bad = [k for k in diff_kinds if k not in RECORD_KINDS]
        if not diff_kinds or bad:
            print(f"report: unparseable --kinds {args.kinds!r} (want "
                  f"a comma list of record kinds from "
                  f"{'/'.join(RECORD_KINDS)})", file=sys.stderr)
            return 2
    if args.follow_interval <= 0 or args.follow_max_s <= 0:
        print("report: --follow_interval/--follow_max_s must be > 0",
              file=sys.stderr)
        return 2

    # the train-CLI parse discipline: a malformed spec rejects rc 2
    # BEFORE any stream is read
    slo = None
    if args.slo is not None:
        parts = args.slo.split(":")
        try:
            if len(parts) != 2:
                raise ValueError
            slo = (float(parts[0]), float(parts[1]))
            if slo[0] < 0 or slo[1] < 0:
                raise ValueError
        except ValueError:
            print(f"report: unparseable --slo {args.slo!r} (want "
                  "TTFT_S:ITL_S with both >= 0, e.g. 0.5:0.05)",
                  file=sys.stderr)
            return 2

    # an explicit --attempt_log names ONE supervisor log: attach it to
    # the first stream only — giving it to every stream would replay
    # the same recovery events once per engine on the merged timeline
    # (the other streams still auto-discover their own from meta)
    streams = [_Stream(d, args.attempt_log if i == 0 else None)
               for i, d in enumerate(args.metrics_dirs)]
    # engine labels key the merge: disambiguate collisions (two dirs
    # both named "metrics" with no engine_id stamped) instead of
    # silently overwriting one stream's entire report
    seen_labels: dict = {}
    for s in streams:
        n = seen_labels.get(s.label, 0)
        seen_labels[s.label] = n + 1
        if n:
            s.label = f"{s.label}#{n + 1}"
    missing = [s for s in streams if not s.dir_exists]
    if missing:
        for s in missing:
            print(f"report: no metrics stream at {s.path}",
                  file=sys.stderr)
        return 2
    if args.diff:
        res = diff_streams(
            load_diff_stream(args.metrics_dirs[0], diff_kinds),
            load_diff_stream(args.metrics_dirs[1], diff_kinds))
        if args.json:
            print(json.dumps(res, indent=1))
        else:
            what = (f" over kinds {','.join(diff_kinds)}"
                    if diff_kinds else "")
            if res["verdict"] == "identical":
                print(f"diff: identical{what} — {res['n_a']} "
                      "record(s) each, byte-equivalent after "
                      "envelope stripping")
            else:
                print(f"diff: {res['verdict']}{what} @ record "
                      f"{res['index']} (streams hold {res['n_a']} / "
                      f"{res['n_b']} record(s))")
                print(f"  differing key(s): {res['keys']}")
                print(f"  a: {json.dumps(res['a'], sort_keys=True)}")
                print(f"  b: {json.dumps(res['b'], sort_keys=True)}")
        return 0 if res["verdict"] in ("identical",
                                       "timing-only") else 2
    if args.audit:
        msg = audit_streams(streams)
        if msg is not None:
            print(msg, file=sys.stderr)
            return 2
        n = sum(len(s.records) for s in streams)
        print(f"audit: clean — {len(_AUDIT_CATALOG)} invariant(s) "
              f"hold over {n} record(s) across {len(streams)} "
              "stream(s)")
        return 0
    if args.follow:
        # the live tail replaces the one-shot fold (a run may still be
        # record-free while its engines boot — the tail waits for it)
        return _follow(args.metrics_dirs, args.follow_interval,
                       args.follow_max_s)
    multi = len(streams) > 1

    if not any(s.records for s in streams):
        if trace_uid is not None:
            # asking to trace a uid through streams that hold nothing
            # is an unknown-uid error, not a record-free answer
            print(f"report: no record for uid {trace_uid} — the given "
                  "stream(s) hold no schema-valid records",
                  file=sys.stderr)
            return 2
        # a record-free stream is an ANSWER (the run emitted nothing),
        # not a tooling failure: rc 0 with an explicit summary naming
        # whatever failed to validate
        out = []
        for s in streams:
            out.append(f"report: no records — {s.path} holds no "
                       f"schema-valid records "
                       f"({len(s.problems)} problem(s))")
            for prob in s.problems:
                out.append(f"  {prob}")
        if args.json:
            print(json.dumps({
                "no_records": True,
                "streams": [{"metrics_path": s.path,
                             "problems": s.problems}
                            for s in streams]}, indent=1))
        else:
            print("\n".join(out))
        return 0

    # ---- fold every stream ------------------------------------------
    doc: dict = {}
    per_engine: dict = {}
    timeline = []
    waterfalls: dict = {}
    for si, s in enumerate(streams):
        sub = {"metrics_path": s.path, "n_records": len(s.records),
               "problems": s.problems, "run": s.header,
               "steps": s.step_stats(), "recovery": s.recovery()}
        serving = s.serving()
        if serving:
            sub["serving"] = serving
        rel = s.reliability()
        if rel:
            sub["serving_reliability"] = rel
        phases = s.step_phases()
        if phases:
            sub["step_phases"] = phases
        per_engine[s.label] = sub
        wf = s.waterfalls()
        if wf:
            waterfalls[s.label] = wf
        for order, (t, src, what) in enumerate(s.timeline_entries()):
            timeline.append((t, si, order, src, what, s.label))
    # deterministic merge: equal timestamps break ties by (stream,
    # per-stream entry order), so repeated merges of the same dirs
    # render byte-identical timelines (pinned by test)
    timeline.sort(key=lambda x: (x[0], x[1], x[2]))
    timeline = [(t, src, what, lab)
                for t, _si, _order, src, what, lab in timeline]

    # ---- fleet summary (schema-v8 router records, decode/fleet.py) --
    # the fleet-LEVEL read of the merged streams: routing decisions
    # from any router stream + request outcomes from EVERY stream, so
    # the latency percentiles describe what a caller of the fleet saw,
    # not any one engine
    router_recs = [r for s in streams for r in s.routers]
    if router_recs:
        by_ev: dict[str, int] = {}
        for r in router_recs:
            by_ev[r["event"]] = by_ev.get(r["event"], 0) + 1
        mig_reasons: dict[str, int] = {}
        for r in router_recs:
            if r["event"] == "migrated":
                key = r.get("reason") or "?"
                mig_reasons[key] = mig_reasons.get(key, 0) + 1
        # completions dedupe by uid across streams (a request completed
        # on an engine after its last snapshot re-completes on a
        # survivor when that engine dies — same tokens, two records;
        # the caller saw the FIRST one), and the headline shed counts
        # only CALLER-visible losses: the router's fleet-wide "shed"
        # records plus deadline expiries — never per-engine "rejected"
        # events, which a spillover leaves behind even when the request
        # lands (and completes) on the next engine
        completed = list(_merged_completions(streams).values())
        expired_uids = {r["uid"] for s in streams for r in s.requests
                        if r["event"] == "expired"}
        # routed-policy attribution (v9) + live-move stall stats
        policies: dict[str, int] = {}
        for r in router_recs:
            if r["event"] == "routed" and r.get("policy"):
                policies[r["policy"]] = policies.get(r["policy"], 0) + 1
        moves = [r for r in router_recs
                 if r["event"] in ("handoff", "migrated")
                 and r.get("duration_s") is not None]
        fleet = {
            "engines": len([s for s in streams if s.decodes]),
            "routed": by_ev.get("routed", 0),
            "routed_by_policy": policies,
            "handoffs": by_ev.get("handoff", 0),
            "migrations": by_ev.get("migrated", 0),
            "migrated_by_reason": mig_reasons,
            "shed": by_ev.get("shed", 0) + len(expired_uids),
            "shed_at_router": by_ev.get("shed", 0),
            # v10: CRC/torn/version-rejected wire handoffs (each was
            # replay-rerouted; the records carry the one-line reason)
            "wire_rejected": by_ev.get("wire_rejected", 0),
            "completed": len(completed),
        }
        # v11 live-deploy surface: per-version completion counts dedup
        # BY UID across streams first (a migrated-then-completed
        # request may appear in two engines' files — one uid, one
        # version, one count) and the deploy lifecycle tallies
        vers: dict[str, int] = {}
        for r in completed:
            if r.get("weights_version") is not None:
                key = f"v{r['weights_version']}"
                vers[key] = vers.get(key, 0) + 1
        if vers:
            fleet["completed_by_version"] = vers
        deploy_recs = [d for s in streams for d in s.deploys]
        if deploy_recs:
            fleet["deploys"] = sum(1 for d in deploy_recs
                                   if d["event"] == "completed")
            fleet["deploy_rollbacks"] = sum(1 for d in deploy_recs
                                            if d["event"]
                                            == "rolled_back")
        if moves:
            fleet["handoff_blocks"] = sum(int(r.get("blocks") or 0)
                                          for r in moves)
            fleet["handoff_bytes"] = sum(int(r.get("bytes") or 0)
                                         for r in moves)
            fleet["handoff_stall_p90_ms"] = round(float(np.percentile(
                np.asarray([r["duration_s"] for r in moves],
                           np.float64), 90)) * 1e3, 3)
            # v10 transport attribution: how each move actually
            # crossed (inproc doc / wire file / replay re-queue)
            modes: dict[str, int] = {}
            for r in moves:
                mode = (r.get("transport") or {}).get("mode") or "?"
                modes[mode] = modes.get(mode, 0) + 1
            fleet["moves_by_transport"] = modes
        lat = [r["latency_s"] for r in completed
               if r.get("latency_s") is not None]
        if lat:
            q = np.percentile(np.asarray(lat, np.float64), [50, 90, 99])
            fleet["latency_p50_s"] = round(float(q[0]), 4)
            fleet["latency_p90_s"] = round(float(q[1]), 4)
            fleet["latency_p99_s"] = round(float(q[2]), 4)
        # fleet-wide TTFT/ITL (v9): completions deduped by uid, decode
        # segments pooled across every stream
        ttfts = [r["ttft_s"] for r in completed
                 if r.get("ttft_s") is not None]
        if ttfts:
            (fleet["ttft_p50_s"], fleet["ttft_p90_s"],
             fleet["ttft_p99_s"]) = _pct3(ttfts)
        gaps = _merged_decode_gaps(streams)
        if gaps:
            (fleet["itl_p50_s"], fleet["itl_p90_s"],
             fleet["itl_p99_s"]) = _pct3(gaps, 6)
        doc["fleet"] = fleet

    fh = _fleet_health(streams)
    if fh:
        doc["fleet_health"] = fh
    wl = _workload_fold(streams)
    if wl:
        doc["workload"] = wl
    tp = _transport_fold(streams)
    if tp:
        doc["transport"] = tp
    if slo is not None:
        doc["slo"] = _slo_accounting(streams, *slo)
    if trace_uid is not None:
        tr = _trace_doc(streams, trace_uid)
        if tr is None:
            print(f"report: no record for uid {trace_uid} in the "
                  "given stream(s) — nothing to trace (pass every "
                  "engine's metrics dir plus the router's)",
                  file=sys.stderr)
            return 2
        doc["trace"] = tr

    if multi:
        doc["engines"] = per_engine
        doc["problems"] = [f"[{s.label}] {p}" for s in streams
                           for p in s.problems]
    else:
        doc.update(per_engine[streams[0].label])
    doc["timeline"] = [{"t": t, "source": src, "what": what,
                        **({"engine": lab} if multi else {})}
                       for t, src, what, lab in timeline]
    if waterfalls:
        doc["waterfalls"] = (waterfalls if multi
                             else waterfalls[streams[0].label])

    flights = {}
    rposts: dict = {}
    if args.postmortem:
        flights = {s.label: s.flight_recorder() for s in streams}
        # active-alerts-at-declaration (v15): the worker's flight
        # recorder can't see the router's alert plane, so the merge
        # folds it here — every alert fired but not yet resolved at
        # the dump's wall time was ACTIVE while the engine died
        all_alerts = sorted((a for s in streams for a in s.alerts),
                            key=lambda a: (a.get("t", 0.0),
                                           a.get("step", 0)))
        for fr in flights.values():
            if fr and not fr.get("error") and fr.get("t") is not None:
                fr["alerts_at_dump"] = _alerts_active_at(
                    all_alerts, fr["t"])
        doc["postmortem"] = (flights if multi
                             else flights[streams[0].label])
        rposts = {s.label: v for s in streams
                  if (v := s.router_postmortems())}
        if rposts:
            doc["router_postmortem"] = rposts

    # ---- profile folding (first stream's strategy names the scopes) --
    if args.profile_dir:
        from .utils.trace_analysis import (load_spans, overlap_payload,
                                           scope_totals,
                                           strategy_scope_key)
        # one gunzip+parse feeds both analyses (hardware traces run to
        # hundreds of MB — never load twice)
        trace_file, spans = load_spans(args.profile_dir)
        prof = overlap_payload(spans, trace_file)
        # fold per-region totals under the RUN's strategy when the meta
        # records name one; unknown strategies fall back to the
        # prefixed-regions union (scope_totals documents why)
        scope_key = strategy_scope_key(
            streams[0].header.get("strategy"))
        prof["scope_totals_us"] = {
            k: round(v, 1)
            for k, v in scope_totals(spans, scope_key).items() if v}
        doc["profile"] = prof

    if not multi and streams[0].benches:
        doc["bench_rows"] = len(streams[0].benches)

    if args.json:
        print(json.dumps(doc, indent=1))
        return 0

    # ---- render ------------------------------------------------------
    out = []
    out.append("=" * 72)
    if multi:
        out.append(f"RUN REPORT — {len(streams)} merged stream(s): "
                   + ", ".join(s.label for s in streams))
    else:
        out.append(f"RUN REPORT — {streams[0].path}")
    out.append("=" * 72)
    if doc.get("fleet"):
        # ABOVE the per-engine blocks: the caller-facing fleet view
        fl = doc["fleet"]
        out.append("")
        out.append(f"fleet: {fl['routed']} routed, "
                   f"{fl['handoffs']} prefill handoff(s), "
                   f"{fl['migrations']} migration(s)"
                   + (f" {fl['migrated_by_reason']}"
                      if fl["migrated_by_reason"] else "")
                   + f", {fl['shed']} shed, "
                   f"{fl['completed']} completed")
        if "latency_p50_s" in fl:
            out.append(f"  fleet latency  p50 {fl['latency_p50_s']}s  "
                       f"p90 {fl['latency_p90_s']}s  "
                       f"p99 {fl['latency_p99_s']}s")
        if fl.get("routed_by_policy"):
            out.append("  routed by policy: " + ", ".join(
                f"{k} {v}" for k, v in sorted(
                    fl["routed_by_policy"].items(),
                    key=lambda kv: -kv[1])))
        if "ttft_p50_s" in fl:
            out.append(f"  fleet TTFT     p50 {fl['ttft_p50_s']}s  "
                       f"p90 {fl['ttft_p90_s']}s  "
                       f"p99 {fl['ttft_p99_s']}s")
        if "itl_p50_s" in fl:
            out.append(f"  fleet ITL      p50 {fl['itl_p50_s']}s  "
                       f"p90 {fl['itl_p90_s']}s  "
                       f"p99 {fl['itl_p99_s']}s  (per decode segment)")
        if "handoff_stall_p90_ms" in fl:
            via = ""
            if fl.get("moves_by_transport"):
                via = " via " + ", ".join(
                    f"{k} x{v}" for k, v in sorted(
                        fl["moves_by_transport"].items()))
            out.append(f"  KV moves       {fl['handoff_blocks']} "
                       f"block(s) / {_fmt_bytes(fl['handoff_bytes'])} "
                       f"shipped, stall p90 "
                       f"{fl['handoff_stall_p90_ms']} ms{via}")
        if fl.get("wire_rejected"):
            out.append(f"  wire integrity {fl['wire_rejected']} "
                       "handoff doc(s) REJECTED (CRC/torn/version — "
                       "replay-rerouted; reasons on the timeline)")
        if "deploys" in fl or "deploy_rollbacks" in fl:
            out.append(f"  deploys        {fl.get('deploys', 0)} "
                       f"completed, {fl.get('deploy_rollbacks', 0)} "
                       "rolled back (events on the timeline)")
        if fl.get("completed_by_version"):
            out.append("  completions by weights version: " + ", ".join(
                f"{k} x{v}" for k, v in sorted(
                    fl["completed_by_version"].items())))
    if doc.get("fleet_health"):
        _render_fleet_health(out, doc["fleet_health"])
    if doc.get("workload"):
        _render_workload(out, doc["workload"])
    if doc.get("transport"):
        _render_transport(out, doc["transport"])
    if doc.get("slo"):
        _render_slo(out, doc["slo"])
    if doc.get("trace"):
        _render_trace(out, doc["trace"])
    if multi:
        for s in streams:
            sub = per_engine[s.label]
            out.append("")
            out.append(f"--- engine [{s.label}] — {s.path} ---")
            _render_engine_sections(out, sub)
    else:
        _render_engine_sections(out, doc)
    for lab, wf in waterfalls.items():
        _render_waterfalls(out, lab if multi else None, wf)
    if timeline:
        t0 = timeline[0][0]
        out.append("")
        out.append("timeline:")
        for t, src, what, lab in timeline:
            tag = f"[{lab}] " if multi else ""
            out.append(f"  {_fmt_t(t, t0)}  [{src:7s}] {tag}{what}")
    if args.postmortem:
        for s in streams:
            _render_postmortem(out, s.label if multi else None,
                               flights.get(s.label))
        for s in streams:
            if rposts.get(s.label):
                _render_router_postmortem(out,
                                          s.label if multi else None,
                                          rposts[s.label])
    if "profile" in doc:
        pr = doc["profile"]
        out.append("")
        out.append(f"profile: {pr['trace_file']}")
        out.append(f"  {pr['comm_spans']} comm / {pr['compute_spans']} "
                   f"compute span(s), overlap {pr['overlap_us']} us")
        if pr.get("scope_totals_us"):
            out.append("  per-region span totals (us):")
            for k, v in sorted(pr["scope_totals_us"].items(),
                               key=lambda kv: -kv[1]):
                out.append(f"    {k:16s} {v}")
    problems = (doc.get("problems") if multi
                else streams[0].problems) or []
    if problems:
        out.append("")
        out.append(f"schema problems ({len(problems)}):")
        for prob in problems:
            out.append(f"  {prob}")
    print("\n".join(out))
    return 0


if __name__ == "__main__":
    sys.exit(report_main())
