"""Serving supervisor: snapshot-resume, chaos injection, hung-step
watchdog — the decode engine's crash-recovery loop.

The training side earned its recovery discipline in rounds 6 and 8
(``runtime/failure.py``: restart ladder, jittered backoff, per-attempt
JSONL). This module is the serving twin, built around one observation:
the engine's whole device state is RECOMPUTABLE from host metadata.
A sequence's continuation is a pure function of ``(params, engine
seed, uid, prompt, emitted tokens)`` — the sampling keys fold
``(seed, uid, position)`` and never the slot — so the **snapshot** is
a small JSON document (waiting queue, per-slot uid/position/block-table
state, finished/failed maps, counters), not a KV-pool dump. Recovery
re-prefills each in-flight prompt and teacher-forces its recorded
tokens through the decode path (``_Seq.emitted``), which replays the
exact KV **write history** — so the rebuilt cache is bit-identical at
every kv_dtype, int8 quantization history included, and the resumed
run's remaining tokens match an uninterrupted run token for token.

The supervisor wraps ``DecodeEngine.run`` with two hooks:

- ``before_step``: fire due decode chaos faults (``runtime/chaos.py``
  decode grammar) — ``hang_step`` sleeps, ``nan_logits`` arms the
  in-graph poison operand, ``corrupt_block`` poisons a pool block;
- ``after_step``: watchdog latch check + kick (a step that overran
  ``watchdog_ms`` leaves ``hung_step`` evidence in the attempt log and
  the telemetry stream), atomic snapshot persist, then ``kill`` faults
  (SIGKILL right AFTER the step's snapshot — the deterministic
  crash-between-steps fault; a resumed run starts past that step and
  never re-fires it).

In-process failures (anything ``engine.run`` raises) take the restart
rung: reload the last snapshot into a fresh engine, with the SAME
jittered-backoff schedule and attempt-log record shapes as the
training supervisor (``runtime.failure.backoff_delay``). SIGKILL-class
deaths are recovered by the next invocation of the same command — the
generate CLI resumes automatically when its ``--snapshot_dir`` holds a
snapshot.
"""

from __future__ import annotations

import dataclasses
import json
import os
import random
import signal
import time

from ..runtime.failure import _head, backoff_delay
from .engine import AdmissionError, DecodeEngine, POISON_ALL

SNAPSHOT_FILENAME = "engine_snapshot.json"
# v2 (round 11): counters grow the KV-pool churn trio (block_allocs /
# block_frees / block_scrubs) so the schema-v5 decode records stay
# monotonic across crash-resume
# v3 (round 12): counters grow the speculation pair (drafted_tokens /
# accepted_tokens) — same monotonic-across-resume contract; the
# drafter itself needs NO snapshot state (drafts are a pure function
# of prompt + out, decode/draft.py)
# v4 (round 13): counters grow the shared-prefix set (prefix_hit_blocks
# / prefill_tokens_saved / cow_copies / prefix_lookup_blocks /
# prefill_dispatches) and the snapshot persists ``prefix_tree`` — the
# radix share graph (``PrefixCache.snapshot()``: per-node token edge,
# physical block, refcount, LRU clock, poison flag). Block CONTENT
# dies with the process, so restore deliberately does NOT rebuild the
# tree: replay re-prefills each live request and re-INSERTS its full
# prompt blocks, so the share graph reassembles organically (the first
# replayed sharer prefills, later ones hit — the ~1-prefill property
# survives the crash) and the persisted tree is the certificate tests
# pin the rebuild against.
# v5 (round 15): request entries carry ``t_first`` — the first-token
# timestamp (``SpanTracer.mark_first_token``) — so a crash-resumed
# request's completed record keeps its TRUE ``ttft_s`` (schema v9).
# The crash gap itself stays visibly unaccounted in the span stream;
# only the first-token FACT survives, never invented wall time.
# v6 (round 17): the live-weight hot-swap state (DESIGN.md section 23).
# Request entries carry ``weights_version`` — the pin a resumed
# request replays and finishes on (None = never admitted, pins at
# admission) — and the snapshot pins ``serving_version`` plus
# ``weights_versions`` (version id -> model fingerprint for every
# resident version, the ledger-sourced identity restore validates: a
# mixed-version engine's snapshot can only restore onto an engine
# that HOLDS those versions). ``model`` remains the serving version's
# fingerprint (the pre-v6 readers' key).
# v7 (round 18): request entries carry ``trace_id`` — the causal
# identity minted once at admission (schema v12) — so a crash-resumed
# request's records keep stitching into the SAME cross-process trace
# waterfall (the crash gap itself stays visibly unaccounted, exactly
# the ``t_first`` stance).
# v8 (round 19): request entries carry ``tenant`` — the tenant tag
# (schema v13, None single-tenant) — so a crash-resumed or
# kill-migrated request keeps its per-tenant attribution (the
# workload plane's noisy-tenant numbers survive the death).
# v9 (round 23): counters grow the KV-spill set (spilled_blocks /
# spill_bytes / restores / restore_tokens_saved / restore_stall_s /
# partial_hits — schema v17) and the persisted ``prefix_tree`` nodes
# carry ``spilled``. The host tier's BYTES are deliberately NOT
# persisted: the spill tier is process memory (decode/spill.py), so
# resume restores an engine whose tier is EMPTY and replay re-prefills
# — exactly the v4 stance on device block content. The tree's
# ``spilled`` flags are certificate, not restore input.
SNAPSHOT_VERSION = 9


# ---------------------------------------------------------------- snapshot

def _model_meta(engine: DecodeEngine) -> dict:
    """Model identity the snapshot pins — shared with the KV handoff
    (round 14): ``DecodeEngine.model_meta()`` is the one fingerprint
    both resume-replay and cross-engine sequence import check, so the
    two can never drift apart on what "the same model" means."""
    return engine.model_meta()


def snapshot_state(engine: DecodeEngine) -> dict:
    """The host-side engine state as one JSON-serializable document.
    ``requests`` lists in-flight sequences first (admission order, each
    with its slot / position / block-table view — the observable the
    snapshot certifies, even though resume recomputes the pool) and
    then the waiting queue in queue order, so a restore re-queues them
    in scheduling priority order."""
    engine._refuse_kept_beside("an engine snapshot (--snapshot_dir)")
    engine.collect()    # a snapshot holds landed tokens and positions
    requests = []
    running = sorted(
        ((seq.admit_index, slot, seq)
         for slot, seq in enumerate(engine.slots) if seq is not None))
    for _, slot, seq in running:
        requests.append({
            "uid": seq.uid, "prompt": seq.prompt, "out": seq.out,
            "max_new": seq.max_new, "retries": seq.retries,
            "t_submit": seq.t_submit, "submit_step": seq.submit_step,
            "t_first": engine.tracer.first_token_t(seq.uid),
            "weights_version": seq.weights_version,
            "trace_id": seq.trace_id,
            "tenant": seq.tenant,
            "state": "RUNNING", "slot": slot,
            "position": int(engine.lengths[slot]),
            "prefilled": seq.prefilled,
            "block_table": engine.tables[slot].tolist(),
            "blocks": list(seq.blocks),
        })
    for seq in engine.waiting:
        requests.append({
            "uid": seq.uid, "prompt": seq.prompt, "out": seq.out,
            "max_new": seq.max_new, "retries": seq.retries,
            "t_submit": seq.t_submit, "submit_step": seq.submit_step,
            "t_first": engine.tracer.first_token_t(seq.uid),
            "weights_version": seq.weights_version,
            "trace_id": seq.trace_id,
            "tenant": seq.tenant,
            "state": "WAITING",
        })
    snap = {
        "version": SNAPSHOT_VERSION,
        "step": engine.global_step,
        "t": time.time(),
        "config": dataclasses.asdict(engine.cfg),
        "policy": dataclasses.asdict(engine.policy),
        "model": _model_meta(engine),
        "serving_version": engine.serving_version,
        "weights_versions": {str(v): engine.model_meta(v)
                             for v in sorted(engine.weights)},
        "requests": requests,
        "finished": {str(u): t for u, t in engine.finished.items()},
        "failed": {str(u): dict(info)
                   for u, info in engine.failed.items()},
        "prompt_lens": {str(u): n
                        for u, n in engine.prompt_lens.items()},
        "counters": {
            "tokens_generated": engine.tokens_generated,
            "quarantined": engine.quarantined,
            "retried": engine.retried,
            "preempted": engine.preempted,
            "rejected": engine.rejected,
            "expired": engine.expired,
            "block_allocs": engine.block_allocs,
            "block_frees": engine.block_frees,
            "block_scrubs": engine.block_scrubs,
            "drafted_tokens": engine.drafted_tokens,
            "accepted_tokens": engine.accepted_tokens,
            "prefix_hit_blocks": engine.prefix_hit_blocks,
            "prefill_tokens_saved": engine.prefill_tokens_saved,
            "cow_copies": engine.cow_copies,
            "prefix_lookup_blocks": engine.prefix_lookup_blocks,
            "prefill_dispatches": engine.prefill_dispatches,
            "spilled_blocks": engine.spilled_blocks,
            "spill_bytes": engine.spill_bytes,
            "restores": engine.restores,
            "restore_tokens_saved": engine.restore_tokens_saved,
            "restore_stall_s": engine.restore_stall_s,
            "partial_hits": engine.partial_hits,
        },
        "prefix_tree": (None if engine.prefix is None
                        else engine.prefix.snapshot()),
    }
    if engine.pool.k_scale is not None:
        # int8 scales metadata: shape/dtype of the per-block scale
        # arrays the replay rebuilds (values are write-history-derived,
        # so recording the layout is the honest full description)
        snap["int8_scales"] = {
            "shape": list(engine.pool.k_scale.shape),
            "dtype": str(engine.pool.k_scale.dtype),
            "note": "values recomputed bit-identically by replay "
                    "(quantization history == write history)",
        }
    return snap


def snapshot_path(snapshot_dir: str) -> str:
    return os.path.join(snapshot_dir, SNAPSHOT_FILENAME)


def write_snapshot(engine: DecodeEngine, snapshot_dir: str) -> str:
    """Atomic publish through ``runtime/wire.py`` (the one home of the
    tmp + fsync + rename + dir-fsync discipline this module used to
    hand-roll): a SIGKILL between any two instructions leaves either
    the old or the new snapshot, never a torn one. The same call is the
    engine-WORKER snapshot publisher (``decode/worker.py``)."""
    from ..runtime.wire import publish_json
    os.makedirs(snapshot_dir, exist_ok=True)
    return publish_json(snapshot_path(snapshot_dir),
                        snapshot_state(engine))


def load_snapshot(snapshot_dir: str) -> dict | None:
    """The latest engine snapshot, or None when none was ever
    published. A snapshot is only ever replaced atomically, so a
    parse failure is real corruption and raises."""
    path = snapshot_path(snapshot_dir)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        snap = json.load(f)
    if snap.get("version") != SNAPSHOT_VERSION:
        raise ValueError(f"engine snapshot version "
                         f"{snap.get('version')!r} != {SNAPSHOT_VERSION}")
    return snap


def restore_engine_state(engine: DecodeEngine, snap: dict) -> None:
    """Load a snapshot into a FRESH engine: finished/failed maps and
    counters restored, every live request re-queued for replay-resume
    (``DecodeEngine.resume_request``), ``step_base`` set so the global
    step keeps counting from the crash point (chaos schedules and
    request records stay monotonic across the death). The engine must
    have been built with the snapshot's exact config/policy — resuming
    onto a different compiled surface would silently change numerics,
    so a mismatch raises."""
    engine._refuse_kept_beside("resuming from an engine snapshot")
    cfg = dataclasses.asdict(engine.cfg)
    if cfg != snap["config"]:
        diff = {k: (snap["config"].get(k), cfg.get(k))
                for k in set(cfg) | set(snap["config"])
                if snap["config"].get(k) != cfg.get(k)}
        raise ValueError(f"engine config != snapshot config: {diff} "
                         "(snapshot resume requires the identical "
                         "EngineConfig)")
    pol = dataclasses.asdict(engine.policy)
    if pol != snap["policy"]:
        raise ValueError(f"serve policy != snapshot policy: "
                         f"{snap['policy']} vs {pol}")
    # per-version identity (snapshot v6): the engine must HOLD every
    # version the snapshot pins, with the identical fingerprint —
    # resume replays each request through its pinned version's
    # weights, so any missing/mismatched version silently breaks the
    # token-identical contract. A v0-only snapshot degenerates to the
    # old single-model check.
    for ver_s, want in snap["weights_versions"].items():
        ver = int(ver_s)
        if ver not in engine.weights:
            raise ValueError(
                f"engine does not hold weights version {ver} pinned "
                f"by the snapshot (held: {sorted(engine.weights)}) — "
                "load_weights the version before restoring")
        model = engine.model_meta(ver)
        if model != want:
            diff = {k: (want.get(k), model.get(k))
                    for k in set(model) | set(want)
                    if want.get(k) != model.get(k)}
            raise ValueError(
                f"model != snapshot model for weights version {ver}: "
                f"{diff} — resume replays recorded tokens through the "
                "pinned weights, so the identical model (same shape "
                "AND same init) is required for the token-identical "
                "contract")
    engine.set_serving_version(int(snap["serving_version"]))
    engine.step_base = int(snap["step"])
    engine.finished = {int(u): list(t)
                       for u, t in snap["finished"].items()}
    engine.failed = {int(u): dict(info)
                     for u, info in snap["failed"].items()}
    engine.prompt_lens = {int(u): int(n)
                          for u, n in snap["prompt_lens"].items()}
    c = snap["counters"]
    engine.tokens_generated = int(c["tokens_generated"])
    engine.quarantined = int(c["quarantined"])
    engine.retried = int(c["retried"])
    engine.preempted = int(c["preempted"])
    engine.rejected = int(c["rejected"])
    engine.expired = int(c["expired"])
    engine.block_allocs = int(c["block_allocs"])
    engine.block_frees = int(c["block_frees"])
    engine.block_scrubs = int(c["block_scrubs"])
    engine.drafted_tokens = int(c["drafted_tokens"])
    engine.accepted_tokens = int(c["accepted_tokens"])
    engine.prefix_hit_blocks = int(c["prefix_hit_blocks"])
    engine.prefill_tokens_saved = int(c["prefill_tokens_saved"])
    engine.cow_copies = int(c["cow_copies"])
    engine.prefix_lookup_blocks = int(c["prefix_lookup_blocks"])
    engine.prefill_dispatches = int(c["prefill_dispatches"])
    engine.spilled_blocks = int(c["spilled_blocks"])
    engine.spill_bytes = int(c["spill_bytes"])
    engine.restores = int(c["restores"])
    engine.restore_tokens_saved = int(c["restore_tokens_saved"])
    engine.restore_stall_s = float(c["restore_stall_s"])
    engine.partial_hits = int(c["partial_hits"])
    # snap["prefix_tree"] is deliberately NOT loaded: the pool content
    # it indexed died with the process, so a fresh engine's tree starts
    # empty and replay re-inserts as it re-prefills — the persisted
    # tree is the share-graph certificate, not restore input
    for req in snap["requests"]:
        engine.resume_request(req["uid"], req["prompt"], req["max_new"],
                              out=req["out"], retries=req["retries"],
                              t_submit=req.get("t_submit"),
                              submit_step=req.get("submit_step"),
                              t_first=req.get("t_first"),
                              weights_version=req.get("weights_version"),
                              trace=req.get("trace_id"),
                              tenant=req.get("tenant"))
    # auto-uid assignment must clear EVERY restored uid, not just the
    # live ones resume_request walked — a fresh submit colliding with a
    # finished uid would sample in lockstep with its twin and overwrite
    # the finished entry
    for uid in list(engine.finished) + list(engine.failed):
        engine._next_uid = max(engine._next_uid, int(uid) + 1)


# --------------------------------------------------------------- supervisor

def supervise_decode(make_engine, requests=(), *, snapshot_dir: str,
                     chaos=None, watchdog_ms: int = 0, metrics=None,
                     log_every: int = 0, snapshot_every: int = 1,
                     max_restarts: int = 3, backoff_base_s: float = 0.5,
                     backoff_max_s: float = 30.0,
                     backoff_jitter: float = 0.5, backoff_seed: int = 0,
                     log_path: str | None = None) -> DecodeEngine:
    """Drain a decode engine under failure supervision.

    ``make_engine`` is a zero-arg factory for a fresh ``DecodeEngine``
    (a restart needs a clean pool — and a resumed process needs any
    engine at all); ``requests`` is the ``(prompt, max_new)`` list
    submitted on a FRESH start (a resumed run's requests come from the
    snapshot; shed submissions — ``AdmissionError`` — are recorded by
    the engine's own ``rejected`` event and skipped). Returns the
    drained engine: ``engine.finished`` / ``engine.failed`` carry the
    outcome per uid.

    The attempt log (default ``{snapshot_dir}/serve_supervise.jsonl``)
    uses the training supervisor's record shapes — ``attempt_failed``
    rows carry the exception head, backoff and restarts left;
    ``hung_step`` rows the watchdog latch; ``completed`` the final
    verdict — so ``report`` folds both supervisors the same way.
    """
    os.makedirs(snapshot_dir, exist_ok=True)
    if log_path is None:
        log_path = os.path.join(snapshot_dir, "serve_supervise.jsonl")
    rng = random.Random(backoff_seed)
    history: list[BaseException] = []

    def log(record: dict) -> None:
        record.setdefault("t", time.time())
        try:
            with open(log_path, "a") as f:
                f.write(json.dumps(record) + "\n")
        except OSError:
            pass  # logging must never take down the supervised run

    attempt = 0
    while True:
        engine = make_engine()
        if metrics is not None:
            engine.metrics = metrics
        snap = load_snapshot(snapshot_dir)
        if snap is not None:
            restore_engine_state(engine, snap)
            if chaos is not None:
                chaos.mark_decode_fired_through(engine.step_base)
            log({"event": "resumed", "attempt": attempt,
                 "step": engine.step_base,
                 "live_requests": len(engine.waiting),
                 "finished": len(engine.finished),
                 "failed": len(engine.failed)})
        else:
            if chaos is not None:
                # a restart with no snapshot replays from step 1: every
                # decode fault must fire again (same alignment as the
                # snapshot path)
                chaos.mark_decode_fired_through(0)
            for req in requests:
                try:
                    engine.submit(*req)
                except AdmissionError:
                    pass        # engine recorded the rejected event
            # publish the step-0 snapshot NOW: a crash before the first
            # per-step snapshot then restores this one instead of
            # resubmitting from scratch (which would re-emit the
            # admission/rejection records and re-shed at the door)
            write_snapshot(engine, snapshot_dir)
            log({"event": "started", "attempt": attempt,
                 "submitted": len(engine.waiting),
                 "shed": engine.rejected})

        dog = None
        hung = 0
        if watchdog_ms > 0:
            from ..runtime import native
            dog = native.Watchdog(watchdog_ms)

        def before_step(local_step: int, _eng=engine) -> None:
            if chaos is None:
                return
            g = _eng.step_base + local_step
            for f in chaos.decode_due(g):
                if f.kind == "hang_step":
                    secs = 0.25 if f.arg is None else float(f.arg)
                    chaos._note(f, sleep_s=secs)
                    time.sleep(secs)
                elif f.kind == "nan_logits":
                    uid = (POISON_ALL if f.arg is None else int(f.arg))
                    chaos._note(f, uid=None if f.arg is None
                                else int(f.arg))
                    _eng.arm_poison(uid)
                elif f.kind == "corrupt_block":
                    chaos._note(f, block=int(f.arg))
                    _eng.corrupt_block(int(f.arg))
                elif f.kind == "corrupt_spill":
                    chaos._note(f, spill_id=int(f.arg),
                                hit=_eng.corrupt_spill(int(f.arg)))
                # kill fires in after_step, behind the snapshot

        def after_step(local_step: int, _eng=engine, _dog=dog) -> None:
            nonlocal hung
            g = _eng.step_base + local_step
            if _dog is not None:
                # latch check BEFORE the kick (the kick clears it)
                if _dog.expired:
                    hung += 1
                    rec = {"event": "hung_step", "step": g,
                           "watchdog_expired": True,
                           "watchdog_ms": watchdog_ms}
                    log(rec)
                    if metrics is not None:
                        metrics.event(rec)
                    # what was the engine doing before it stalled —
                    # the flight recorder is the watchdog's evidence
                    _eng.dump_flight_recorder(f"watchdog step {g}")
                _dog.kick()
            due_kill = (chaos is not None and any(
                f.kind == "kill" for f in chaos.decode_due(g)))
            if due_kill or snapshot_every <= 1 \
                    or g % snapshot_every == 0 \
                    or not (_eng.waiting or _eng.active):
                write_snapshot(_eng, snapshot_dir)
            if due_kill:
                for f in chaos.decode_due(g):
                    if f.kind == "kill":
                        chaos._note(f, snapshot_step=g)
                        log({"event": "chaos_kill", "step": g})
                        # the post-mortem the dead process can't write
                        # later: dump BEFORE the SIGKILL
                        _eng.dump_flight_recorder(f"chaos_kill step {g}")
                        os.kill(os.getpid(), signal.SIGKILL)

        t0 = time.monotonic()
        try:
            engine.run(metrics=metrics, log_every=log_every,
                       before_step=before_step, after_step=after_step)
            log({"event": "completed", "attempt": attempt,
                 "elapsed_s": round(time.monotonic() - t0, 3),
                 "hung_steps": hung,
                 "watchdog_expired": bool(hung),
                 "finished": len(engine.finished),
                 "failed": len(engine.failed)})
            return engine
        except Exception as e:  # noqa: BLE001 — supervisor catches all
            history.append(e)
            record = {"event": "attempt_failed", "rung": "restart",
                      "attempt": attempt, "error": _head(e),
                      "elapsed_s": round(time.monotonic() - t0, 3),
                      "watchdog_expired": bool(hung),
                      "restarts_left": max_restarts - attempt,
                      "backoff_s": None}
            if attempt == max_restarts:
                log(record)
                break
            backoff = backoff_delay(attempt, backoff_base_s,
                                    backoff_max_s, backoff_jitter, rng)
            record["backoff_s"] = round(backoff, 3)
            log(record)
            if backoff > 0:
                time.sleep(backoff)
            attempt += 1
        finally:
            if dog is not None:
                dog.close()
    heads = "; ".join(f"attempt {i}: {_head(e)}"
                      for i, e in enumerate(history))
    raise RuntimeError(
        f"serving failed after {max_restarts} restarts; "
        f"attempt history: [{heads}]") from history[-1]
