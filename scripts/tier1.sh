#!/bin/bash
# Tier-1 verify — the ROADMAP.md command, verbatim, preceded by the
# telemetry smoke. This is the gate every PR must keep no worse than the
# seed; run it before pushing.
#
# Scope notes:
# - `-m 'not slow'` keeps it CPU-fast; the chaos/probe/recovery tests
#   (tests/test_chaos.py, plus the corruption/exhaustion additions in
#   tests/test_checkpoint.py and tests/test_failure.py) are deliberately
#   NOT slow-marked, so fault injection is exercised on every tier-1 run.
# - DOTS_PASSED counts progress dots so a collection-error run can't
#   masquerade as a pass.
# - The telemetry smoke drives a tiny CPU run with --metrics_dir,
#   asserts the stream holds >= 1 schema-valid record, and requires the
#   `report` subcommand to exit 0 on it — the observability surface is
#   gated like any other subsystem (runtime/telemetry.py).
# - Each phase prints PHASE_SECONDS so budget regressions against the
#   870s pytest ceiling (and smoke creep) are visible in the log.
cd "$(dirname "$0")/.."

_phase_t0=$(date +%s)
phase_done() {  # phase_done NAME — print the elapsed wall clock
  echo "PHASE_SECONDS $1=$(( $(date +%s) - _phase_t0 ))"
  _phase_t0=$(date +%s)
}

echo "=== telemetry smoke ==="
SMOKE_DIR=$(mktemp -d /tmp/tier1_telemetry.XXXXXX)
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli -m 2 -s 4 -bs 2 -n 8 -d 8 -l 2 \
    --fake_devices 4 --metrics_dir "$SMOKE_DIR/metrics" --log_every 4 \
    > /dev/null; then
  echo "TELEMETRY_SMOKE=FAIL (run)"; rm -rf "$SMOKE_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python - "$SMOKE_DIR/metrics" <<'EOF'
import sys
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, read_metrics)
import os
records, problems = read_metrics(
    os.path.join(sys.argv[1], METRICS_FILENAME))
steps = [r for r in records if r["kind"] == "step"]
assert steps, "no schema-valid step record in the smoke stream"
assert not problems, problems
EOF
then
  echo "TELEMETRY_SMOKE=FAIL (schema)"; rm -rf "$SMOKE_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report "$SMOKE_DIR/metrics" \
    > /dev/null; then
  echo "TELEMETRY_SMOKE=FAIL (report)"; rm -rf "$SMOKE_DIR"; exit 1
fi
rm -rf "$SMOKE_DIR"
echo "TELEMETRY_SMOKE=OK"
phase_done telemetry_smoke

echo "=== self-healing smoke ==="
# A CPU chaos run injecting nan_grad@2 under --guardrails must finish
# with ZERO process restarts (--max_restarts 0 makes any restart fatal:
# the in-graph skip is the only acceptable remedy) and leave >= 1
# schema-valid `anomaly` record in the metrics stream (schema v2,
# runtime/guardrails.py + runtime/telemetry.py).
HEAL_DIR=$(mktemp -d /tmp/tier1_selfheal.XXXXXX)
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli -m 1 -s 8 -bs 2 -n 8 -d 16 \
    -l 2 -r 3 --lr 0.1 --checkpoint_dir "$HEAL_DIR/ck" \
    --checkpoint_every 2 --chaos nan_grad@2 --guardrails \
    --max_restarts 0 --metrics_dir "$HEAL_DIR/metrics" \
    > /dev/null; then
  echo "SELFHEAL_SMOKE=FAIL (run survived zero-restart budget?)"
  rm -rf "$HEAL_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python - "$HEAL_DIR" <<'EOF'
import json, os, sys
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, read_metrics, validate_record)
base = sys.argv[1]
records, problems = read_metrics(
    os.path.join(base, "metrics", METRICS_FILENAME))
assert not problems, problems
anomalies = [r for r in records if r["kind"] == "anomaly"]
assert anomalies, "no schema-valid anomaly record in the smoke stream"
assert all(validate_record(a)[0] for a in anomalies)
with open(os.path.join(base, "ck", "train_single",
                       "supervise.jsonl")) as f:
    log = [json.loads(ln) for ln in f if ln.strip()]
restarts = [r for r in log if r.get("event") == "attempt_failed"]
assert not restarts, f"self-healing run restarted: {restarts}"
assert any(r.get("event") == "completed" for r in log)
EOF
then
  echo "SELFHEAL_SMOKE=FAIL (schema/restart check)"
  rm -rf "$HEAL_DIR"; exit 1
fi
rm -rf "$HEAL_DIR"
echo "SELFHEAL_SMOKE=OK"
phase_done selfheal_smoke

echo "=== decode smoke ==="
# A tiny CPU `generate` run: two staggered prompts through the
# continuous-batching engine must exit 0 and leave >= 1 schema-valid
# `decode` record (decode/engine.py + runtime/telemetry.py) AND >= 1
# schema-valid `span` record (schema v5, runtime/tracing.py — the
# request-phase tracing layer is gated like the records it rides with).
DEC_DIR=$(mktemp -d /tmp/tier1_decode.XXXXXX)
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate \
    --prompt_lens 3,7 --max_new 5 -d 32 -l 2 --heads 4 --vocab 64 \
    --max_seq_len 64 --block_size 8 --prefill_chunk 4 \
    --metrics_dir "$DEC_DIR/metrics" --log_every 2 > /dev/null; then
  echo "DECODE_SMOKE=FAIL (run)"; rm -rf "$DEC_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python - "$DEC_DIR/metrics" <<'EOF'
import os, sys
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, read_metrics, validate_record)
records, problems = read_metrics(
    os.path.join(sys.argv[1], METRICS_FILENAME))
assert not problems, problems
decs = [r for r in records if r["kind"] == "decode"]
assert decs, "no schema-valid decode record in the smoke stream"
assert all(validate_record(d)[0] for d in decs)
assert decs[-1]["tokens_generated"] == 2 * 5, decs[-1]
spans = [r for r in records if r["kind"] == "span"]
assert spans, "no schema-valid span record in the smoke stream"
assert all(validate_record(s)[0] for s in spans)
EOF
then
  echo "DECODE_SMOKE=FAIL (schema)"; rm -rf "$DEC_DIR"; exit 1
fi
# the invariant auditor must hold over every stream tier-1 produces
# (report --audit, DESIGN.md section 27): rc 2 fails the phase
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report "$DEC_DIR/metrics" \
    --audit > /dev/null; then
  echo "DECODE_SMOKE=FAIL (audit)"; rm -rf "$DEC_DIR"; exit 1
fi
rm -rf "$DEC_DIR"
echo "DECODE_SMOKE=OK"
phase_done decode_smoke

echo "=== speculative-decode smoke ==="
# `generate --speculate 4` vs a `--speculate 0` run of the SAME
# prompts: tokens must be BYTE-IDENTICAL (greedy verification is the
# identity contract, decode/engine.py section 18), and the metrics
# stream must hold >= 1 schema-v6 decode record whose cumulative
# accepted_tokens exceeds its engine step count — multi-token steps as
# recorded data, not inference.
SPEC_DIR=$(mktemp -d /tmp/tier1_spec.XXXXXX)
SPEC_ARGS="--prompt_lens 3,7 --max_new 24 -d 32 -l 2 --heads 4 --vocab 64
  --max_seq_len 64 --block_size 8 --prefill_chunk 4 --log_every 4"
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $SPEC_ARGS \
    > "$SPEC_DIR/base.json"; then
  echo "SPEC_SMOKE=FAIL (baseline run)"; rm -rf "$SPEC_DIR"; exit 1
fi
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $SPEC_ARGS \
    --speculate 4 --metrics_dir "$SPEC_DIR/metrics" \
    > "$SPEC_DIR/spec.json"; then
  echo "SPEC_SMOKE=FAIL (speculative run)"; rm -rf "$SPEC_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python - "$SPEC_DIR" <<'EOF'
import json, os, sys
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, read_metrics, validate_record)
base = sys.argv[1]
plain = json.load(open(os.path.join(base, "base.json")))
spec = json.load(open(os.path.join(base, "spec.json")))
a = {s["uid"]: s["tokens"] for s in plain["sequences"]}
b = {s["uid"]: s["tokens"] for s in spec["sequences"]}
assert a == b, "speculative tokens != non-speculative run"
assert spec["engine_steps"] < plain["engine_steps"], (
    spec["engine_steps"], plain["engine_steps"])
records, problems = read_metrics(
    os.path.join(base, "metrics", METRICS_FILENAME))
assert not problems, problems
decs = [r for r in records if r["kind"] == "decode"]
assert decs, "no schema-valid decode record in the smoke stream"
assert all(validate_record(d)[0] for d in decs)
assert any(d["accepted_tokens"] > d["step"] for d in decs), (
    [(d["accepted_tokens"], d["step"]) for d in decs])
EOF
then
  echo "SPEC_SMOKE=FAIL (identity/schema check)"; rm -rf "$SPEC_DIR"
  exit 1
fi
rm -rf "$SPEC_DIR"
echo "SPEC_SMOKE=OK"
phase_done spec_smoke

echo "=== prefix-cache smoke ==="
# 3 requests sharing a 16-token system prompt (block 8 -> 2 shared full
# blocks), serialized through ONE slot so later admissions walk a warm
# radix cache: `--prefix_cache` (the default) must emit BYTE-IDENTICAL
# tokens to `--no-prefix_cache` while paying FEWER prefill dispatches,
# and the metrics stream must hold >= 1 schema-v7 decode record with
# prefix_hit_blocks > 0 (decode/prefix.py, DESIGN.md section 19).
PFX_DIR=$(mktemp -d /tmp/tier1_prefix.XXXXXX)
PFX="1,2,3,4,5,6,7,8,9,10,11,12,13,14,15,16"
PFX_ARGS="--prompts $PFX,20,21;$PFX,30,31;$PFX,40,41 --max_new 5
  -d 32 -l 2 --heads 4 --vocab 64 --max_seq_len 64 --block_size 8
  --prefill_chunk 4 --max_slots 1 --log_every 2"
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $PFX_ARGS \
    --metrics_dir "$PFX_DIR/metrics" > "$PFX_DIR/cached.json"; then
  echo "PREFIX_SMOKE=FAIL (cached run)"; rm -rf "$PFX_DIR"; exit 1
fi
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $PFX_ARGS \
    --no-prefix_cache > "$PFX_DIR/plain.json"; then
  echo "PREFIX_SMOKE=FAIL (unshared run)"; rm -rf "$PFX_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python - "$PFX_DIR" <<'EOF'
import json, os, sys
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, read_metrics, validate_record)
base = sys.argv[1]
cached = json.load(open(os.path.join(base, "cached.json")))
plain = json.load(open(os.path.join(base, "plain.json")))
a = {s["uid"]: s["tokens"] for s in cached["sequences"]}
b = {s["uid"]: s["tokens"] for s in plain["sequences"]}
assert a == b, "prefix-cached tokens != unshared run"
assert cached["prefill_dispatches"] < plain["prefill_dispatches"], (
    cached["prefill_dispatches"], plain["prefill_dispatches"])
assert cached["prefix_hit_blocks"] > 0, cached["prefix_hit_blocks"]
assert cached["cow_copies"] == 0, cached["cow_copies"]
records, problems = read_metrics(
    os.path.join(base, "metrics", METRICS_FILENAME))
assert not problems, problems
decs = [r for r in records if r["kind"] == "decode"]
assert decs, "no schema-valid decode record in the smoke stream"
assert all(validate_record(d)[0] for d in decs)
assert any(d["prefix_hit_blocks"] > 0 for d in decs), (
    [d["prefix_hit_blocks"] for d in decs])
EOF
then
  echo "PREFIX_SMOKE=FAIL (identity/schema check)"; rm -rf "$PFX_DIR"
  exit 1
fi
rm -rf "$PFX_DIR"
echo "PREFIX_SMOKE=OK"
phase_done prefix_smoke

echo "=== kv-spill smoke ==="
# The ISSUE 19 session-churn drill: 4 DISTINCT 9-token sessions
# returning 3 times through an 11-block device pool (block 4 — the
# running pair only; retention of all four prefixes cannot stay
# device-resident) with a 32-block host-RAM spill tier. Returning
# prefixes must RESTORE through the donated implant program instead of
# re-prefilling: tokens BYTE-IDENTICAL to a big-pool no-spill oracle,
# the output summary must report restores > 0, the metrics stream must
# hold >= 1 schema-v17 decode record with restores > 0, and `report
# --audit` must hold over the stream (decode/spill.py, DESIGN.md
# section 29).
SPL_DIR=$(mktemp -d /tmp/tier1_spill.XXXXXX)
SPL_P1="1,2,3,4,5,6,7,8,9"
SPL_P2="9,8,7,6,5,4,3,2,1"
SPL_P3="11,12,13,14,15,16,17,18,19"
SPL_P4="21,22,23,24,25,26,27,28,29"
SPL_RET="$SPL_P1;$SPL_P2;$SPL_P3;$SPL_P4"
SPL_ARGS="--prompts $SPL_RET;$SPL_RET;$SPL_RET --max_new 6 -d 32 -l 2
  --heads 4 --vocab 64 --max_seq_len 64 --block_size 4
  --prefill_chunk 4 --max_slots 2 --max_blocks_per_seq 8 --log_every 2"
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $SPL_ARGS \
    --n_blocks 64 > "$SPL_DIR/oracle.json"; then
  echo "SPILL_SMOKE=FAIL (big-pool oracle)"; rm -rf "$SPL_DIR"; exit 1
fi
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $SPL_ARGS \
    --n_blocks 11 --spill_blocks 32 --metrics_dir "$SPL_DIR/metrics" \
    > "$SPL_DIR/spill.json"; then
  echo "SPILL_SMOKE=FAIL (tiered run)"; rm -rf "$SPL_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python - "$SPL_DIR" <<'EOF'
import json, os, sys
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, read_metrics, validate_record)
base = sys.argv[1]
oracle = json.load(open(os.path.join(base, "oracle.json")))
spill = json.load(open(os.path.join(base, "spill.json")))
a = {s["uid"]: s["tokens"] for s in oracle["sequences"]}
b = {s["uid"]: s["tokens"] for s in spill["sequences"]}
assert a == b, "tiered-KV tokens != big-pool no-spill oracle"
assert spill["restores"] > 0, spill["restores"]
assert spill["spilled_blocks"] >= spill["restores"], (
    spill["spilled_blocks"], spill["restores"])
assert spill["restore_tokens_saved"] > 0, spill["restore_tokens_saved"]
records, problems = read_metrics(
    os.path.join(base, "metrics", METRICS_FILENAME))
assert not problems, problems
decs = [r for r in records if r["kind"] == "decode"]
assert decs, "no schema-valid decode record in the smoke stream"
assert all(validate_record(d)[0] for d in decs)
assert all(d["schema"] == 17 for d in decs)
assert any(d["restores"] > 0 for d in decs), (
    [d["restores"] for d in decs])
EOF
then
  echo "SPILL_SMOKE=FAIL (identity/schema check)"; rm -rf "$SPL_DIR"
  exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report "$SPL_DIR/metrics" \
    --audit > /dev/null; then
  echo "SPILL_SMOKE=FAIL (audit)"; rm -rf "$SPL_DIR"; exit 1
fi
rm -rf "$SPL_DIR"
echo "SPILL_SMOKE=OK"
phase_done spill_smoke

echo "=== serving-chaos smoke ==="
# kill@4 mid-decode under the engine supervisor: run 1 SIGKILLs itself
# right after the step-4 snapshot (rc 137); run 2 (same command) resumes
# from the snapshot, completes rc 0, and its tokens are TOKEN-IDENTICAL
# to an uninterrupted run — plus >= 1 schema-valid `request` record in
# the metrics stream (schema v4, decode/supervise.py + runtime/telemetry).
SRV_DIR=$(mktemp -d /tmp/tier1_servechaos.XXXXXX)
GEN_ARGS="--prompt_lens 3,7 --max_new 5 -d 32 -l 2 --heads 4 --vocab 64
  --max_seq_len 64 --block_size 8 --prefill_chunk 4 --log_every 2"
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $GEN_ARGS \
    > "$SRV_DIR/oracle.json"; then
  echo "SERVING_CHAOS_SMOKE=FAIL (oracle)"; rm -rf "$SRV_DIR"; exit 1
fi
timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $GEN_ARGS \
    --snapshot_dir "$SRV_DIR/snap" --metrics_dir "$SRV_DIR/metrics" \
    --chaos kill@4 > /dev/null 2>&1
rc=$?
if [ "$rc" -ne 137 ]; then
  echo "SERVING_CHAOS_SMOKE=FAIL (kill@4 rc=$rc, wanted 137)"
  rm -rf "$SRV_DIR"; exit 1
fi
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $GEN_ARGS \
    --snapshot_dir "$SRV_DIR/snap" --metrics_dir "$SRV_DIR/metrics" \
    --chaos kill@4 > "$SRV_DIR/resumed.json" 2>/dev/null; then
  echo "SERVING_CHAOS_SMOKE=FAIL (resume)"; rm -rf "$SRV_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python - "$SRV_DIR" <<'EOF'
import json, os, sys
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, read_metrics, validate_record)
base = sys.argv[1]
oracle = json.load(open(os.path.join(base, "oracle.json")))
resumed = json.load(open(os.path.join(base, "resumed.json")))
a = {s["uid"]: s["tokens"] for s in oracle["sequences"]}
b = {s["uid"]: s["tokens"] for s in resumed["sequences"]}
assert a == b, "resumed tokens != uninterrupted run"
assert resumed["resumed_from_step"] == 4, resumed.get("resumed_from_step")
assert not resumed["failed"], resumed["failed"]
records, problems = read_metrics(
    os.path.join(base, "metrics", METRICS_FILENAME))
assert not problems, problems
reqs = [r for r in records if r["kind"] == "request"]
assert reqs, "no schema-valid request record in the smoke stream"
assert all(validate_record(r)[0] for r in reqs)
assert any(r["event"] == "completed" for r in reqs)
EOF
then
  echo "SERVING_CHAOS_SMOKE=FAIL (token-identity/schema check)"
  rm -rf "$SRV_DIR"; exit 1
fi
rm -rf "$SRV_DIR"
echo "SERVING_CHAOS_SMOKE=OK"
phase_done serving_chaos_smoke

echo "=== serving-observability smoke ==="
# The ISSUE 7 acceptance drill end to end on CPU: engine A runs under
# `--chaos nan_logits@3 --max_retries 1` (every active sequence is
# quarantined at step 3, retried, replay-resumed, completed); engine B
# is a clean run. `report A B` must yield (a) a per-request waterfall
# for EVERY completed uid whose summed span durations reconcile with
# its recorded latency_s, (b) a flight-recorder dump covering the steps
# up to the quarantine, rendered by `report --postmortem`, and (c) one
# merged two-engine timeline with per-engine latency percentiles.
OBS_DIR=$(mktemp -d /tmp/tier1_obs.XXXXXX)
OBS_ARGS="--max_new 5 -d 32 -l 2 --heads 4 --vocab 64
  --max_seq_len 64 --block_size 8 --prefill_chunk 4 --log_every 2"
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $OBS_ARGS \
    --prompt_lens 3,7 --chaos nan_logits@3 --max_retries 1 \
    --snapshot_dir "$OBS_DIR/snapA" --metrics_dir "$OBS_DIR/A" \
    --engine_id A > /dev/null; then
  echo "OBSERVABILITY_SMOKE=FAIL (engine A)"; rm -rf "$OBS_DIR"; exit 1
fi
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $OBS_ARGS \
    --prompt_lens 4,6 --metrics_dir "$OBS_DIR/B" --engine_id B \
    > /dev/null; then
  echo "OBSERVABILITY_SMOKE=FAIL (engine B)"; rm -rf "$OBS_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report "$OBS_DIR/A" \
    "$OBS_DIR/B" --json > "$OBS_DIR/report.json"; then
  echo "OBSERVABILITY_SMOKE=FAIL (merged report)"; rm -rf "$OBS_DIR"
  exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report "$OBS_DIR/A" \
    --postmortem > "$OBS_DIR/postmortem.txt"; then
  echo "OBSERVABILITY_SMOKE=FAIL (postmortem rc)"; rm -rf "$OBS_DIR"
  exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python - "$OBS_DIR" <<'EOF'
import json, os, sys
base = sys.argv[1]
doc = json.load(open(os.path.join(base, "report.json")))
assert set(doc["engines"]) == {"A", "B"}, doc.get("engines")
for eng in ("A", "B"):
    rel = doc["engines"][eng]["serving_reliability"]
    assert rel["completed"] == 2, (eng, rel)
    assert "latency_p50_s" in rel and "latency_p99_s" in rel, (eng, rel)
    wf = doc["waterfalls"][eng]
    assert len(wf) == 2, (eng, sorted(wf))
    for uid, w in wf.items():
        assert w["reconciled"], (eng, uid, w)
assert doc["engines"]["A"]["serving_reliability"]["quarantined"] == 2
assert {r["engine"] for r in doc["timeline"]} == {"A", "B"}
ts = [r["t"] for r in doc["timeline"]]
assert ts == sorted(ts), "merged timeline not in wall-clock order"
post = open(os.path.join(base, "postmortem.txt")).read()
assert "postmortem" in post and "quarantine" in post, post[-500:]
assert "FINITE" in post, "postmortem lacks the non-finite evidence row"
fr = json.load(open(os.path.join(base, "A", "flight_recorder.json")))
steps = [d["step"] for d in fr["digests"]]
assert steps and steps[-1] == fr["step"], (steps, fr["step"])
EOF
then
  echo "OBSERVABILITY_SMOKE=FAIL (drill check)"; rm -rf "$OBS_DIR"
  exit 1
fi
rm -rf "$OBS_DIR"
echo "OBSERVABILITY_SMOKE=OK"
phase_done observability_smoke

echo "=== fleet smoke ==="
# The chaos drill a single engine cannot pass (DESIGN.md section 20):
# 3 router-fronted engines, kill e1 at fleet round 4 mid-stream — every
# in-flight request must complete TOKEN-IDENTICALLY to the unkilled
# single-engine oracle (migration resumes them on the survivors), and
# the merged `report router e0 e1 e2` must show the kill and the
# migrations on one timeline with a fleet summary + schema-v8 router
# records.
FLEET_DIR=$(mktemp -d /tmp/tier1_fleet.XXXXXX)
FLEET_ARGS="--prompt_lens 3,7,5 --max_new 8 -d 32 -l 2 --heads 4
  --vocab 64 --max_seq_len 64 --block_size 8 --prefill_chunk 4
  --log_every 2"
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $FLEET_ARGS \
    > "$FLEET_DIR/oracle.json"; then
  echo "FLEET_SMOKE=FAIL (oracle)"; rm -rf "$FLEET_DIR"; exit 1
fi
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $FLEET_ARGS \
    --fleet 3 --fleet_kill e1@4 --metrics_dir "$FLEET_DIR/m" \
    > "$FLEET_DIR/fleet.json"; then
  echo "FLEET_SMOKE=FAIL (fleet run)"; rm -rf "$FLEET_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report "$FLEET_DIR/m/router" \
    "$FLEET_DIR/m/e0" "$FLEET_DIR/m/e1" "$FLEET_DIR/m/e2" \
    > "$FLEET_DIR/report.txt"; then
  echo "FLEET_SMOKE=FAIL (merged report rc)"; rm -rf "$FLEET_DIR"
  exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python - "$FLEET_DIR" <<'EOF'
import json, os, sys
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, read_metrics, validate_record)
base = sys.argv[1]
oracle = json.load(open(os.path.join(base, "oracle.json")))
fleet = json.load(open(os.path.join(base, "fleet.json")))
a = {s["uid"]: s["tokens"] for s in oracle["sequences"]}
b = {s["uid"]: s["tokens"] for s in fleet["sequences"]}
assert a == b, "fleet tokens != unkilled single-engine oracle"
assert not fleet["failed"], fleet["failed"]
st = fleet["fleet"]
assert st["kills"] == 1 and st["migrations"] >= 1, st
assert st["engines"]["e1"]["alive"] is False, st["engines"]["e1"]
records, problems = read_metrics(
    os.path.join(base, "m", "router", METRICS_FILENAME))
assert not problems, problems
routers = [r for r in records if r["kind"] == "router"]
assert routers and all(validate_record(r)[0] for r in routers)
assert any(r["event"] == "migrated" and r["source"] == "e1"
           for r in routers), routers
rep = open(os.path.join(base, "report.txt")).read()
assert "fleet:" in rep and "migration" in rep, rep[:800]
assert "engine_killed" in rep and "MIGRATED" in rep, rep[-2000:]
EOF
then
  echo "FLEET_SMOKE=FAIL (token-identity/schema/report check)"
  rm -rf "$FLEET_DIR"; exit 1
fi
# the merged four-stream kill drill must audit clean — the writers'
# invariants survive a mid-stream casualty
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report "$FLEET_DIR/m/router" \
    "$FLEET_DIR/m/e0" "$FLEET_DIR/m/e1" "$FLEET_DIR/m/e2" \
    --audit > /dev/null; then
  echo "FLEET_SMOKE=FAIL (audit)"; rm -rf "$FLEET_DIR"; exit 1
fi
rm -rf "$FLEET_DIR"
echo "FLEET_SMOKE=OK"
phase_done fleet_smoke

echo "=== workload smoke ==="
# The round-19 trace plane (DESIGN.md section 25): generate a tiny
# 2-tenant bursty trace (--trace_gen, persisted via --trace_out),
# replay it TWICE through a 2-engine fleet — byte-identical tokens and
# identical schema-v13 workload records (replay IS the determinism
# proof) — then `report` must show per-tenant percentiles, and a
# malformed trace file / bad --trace_gen spec must exit rc 2.
WL_DIR=$(mktemp -d /tmp/tier1_workload.XXXXXX)
WL_SPEC="n=10,arrival=bursty:40:0.2:0.3,plen=zipf:1.7:3:12,max_new=4,tenants=a:3;b:1,seed=5"
WL_ARGS="-d 32 -l 2 --heads 4 --vocab 64 --max_seq_len 64 --block_size 8
  --prefill_chunk 4 --log_every 2 --fleet 2"
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $WL_ARGS \
    --trace_gen "$WL_SPEC" --trace_out "$WL_DIR/trace.jsonl" \
    --metrics_dir "$WL_DIR/m1" > "$WL_DIR/run1.json"; then
  echo "WORKLOAD_SMOKE=FAIL (generate+replay run)"; rm -rf "$WL_DIR"
  exit 1
fi
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $WL_ARGS \
    --trace "$WL_DIR/trace.jsonl" \
    --metrics_dir "$WL_DIR/m2" > "$WL_DIR/run2.json"; then
  echo "WORKLOAD_SMOKE=FAIL (file replay run)"; rm -rf "$WL_DIR"
  exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report "$WL_DIR/m2/router" \
    "$WL_DIR/m2/e0" "$WL_DIR/m2/e1" > "$WL_DIR/report.txt"; then
  echo "WORKLOAD_SMOKE=FAIL (report rc)"; rm -rf "$WL_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python - "$WL_DIR" <<'EOF_WL'
import json, os, sys
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, read_metrics, validate_record)
base = sys.argv[1]
r1 = json.load(open(os.path.join(base, "run1.json")))
r2 = json.load(open(os.path.join(base, "run2.json")))
a = {s["uid"]: s["tokens"] for s in r1["sequences"]}
b = {s["uid"]: s["tokens"] for s in r2["sequences"]}
assert a == b, "trace replayed twice produced different tokens"
assert not r1["failed"] and not r2["failed"]
assert r1["workload"] == r2["workload"], (r1["workload"],
                                          r2["workload"])
assert set(r1["workload"]["tenants"]) == {"a", "b"}
def wl_records(m):
    recs, problems = read_metrics(
        os.path.join(base, m, "router", METRICS_FILENAME))
    assert not problems, problems
    wl = [r for r in recs if r["kind"] == "workload"]
    assert wl and all(validate_record(r)[0] for r in wl)
    return [{k: v for k, v in r.items() if k != "t"} for r in wl]
assert wl_records("m1") == wl_records("m2"), \
    "workload records differ across replays"
rep = open(os.path.join(base, "report.txt")).read()
assert "workload [trace" in rep, rep[:800]
assert "tenant a" in rep and "tenant b" in rep, rep[:1200]
assert "TTFT" in rep
EOF_WL
then
  echo "WORKLOAD_SMOKE=FAIL (determinism/per-tenant check)"
  rm -rf "$WL_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report "$WL_DIR/m2/router" \
    "$WL_DIR/m2/e0" "$WL_DIR/m2/e1" --audit > /dev/null; then
  echo "WORKLOAD_SMOKE=FAIL (audit)"; rm -rf "$WL_DIR"; exit 1
fi
echo '{"torn' >> "$WL_DIR/trace.jsonl"
if timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $WL_ARGS \
    --trace "$WL_DIR/trace.jsonl" > /dev/null 2>&1; then
  echo "WORKLOAD_SMOKE=FAIL (torn trace file accepted)"
  rm -rf "$WL_DIR"; exit 1
fi
if timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $WL_ARGS \
    --trace_gen "n=0" > /dev/null 2>&1; then
  echo "WORKLOAD_SMOKE=FAIL (bad --trace_gen spec accepted)"
  rm -rf "$WL_DIR"; exit 1
fi
rm -rf "$WL_DIR"
echo "WORKLOAD_SMOKE=OK"
phase_done workload_smoke

echo "=== process-transport smoke ==="
# The round-16 drill the in-process fleet cannot run (DESIGN.md
# section 22): 3 engine WORKER PROCESSES behind the router
# (--transport process; decode/worker.py — socket protocol, KV
# handoffs as CRC-verified wire files), kill e1 mid-stream — a real
# SIGKILL of a real process — and every request must complete
# TOKEN-IDENTICALLY to the in-process fleet oracle. The merged report
# must show the dead worker + the MIGRATED rows, and the router stream
# must hold schema-v10 records (migrated records pinning the transport
# attribution).
PROC_DIR=$(mktemp -d /tmp/tier1_proc.XXXXXX)
PROC_ARGS="--prompt_lens 3,7,5 --max_new 8 -d 32 -l 2 --heads 4
  --vocab 64 --max_seq_len 64 --block_size 8 --prefill_chunk 4
  --log_every 2"
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $PROC_ARGS \
    --fleet 3 > "$PROC_DIR/oracle.json"; then
  echo "PROCESS_SMOKE=FAIL (in-process fleet oracle)"
  rm -rf "$PROC_DIR"; exit 1
fi
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $PROC_ARGS \
    --fleet 3 --transport process --fleet_kill e1@4 \
    --metrics_dir "$PROC_DIR/m" > "$PROC_DIR/proc.json"; then
  echo "PROCESS_SMOKE=FAIL (process fleet run)"; rm -rf "$PROC_DIR"
  exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report "$PROC_DIR/m/router" \
    "$PROC_DIR/m/e0" "$PROC_DIR/m/e1" "$PROC_DIR/m/e2" \
    > "$PROC_DIR/report.txt"; then
  echo "PROCESS_SMOKE=FAIL (merged report rc)"; rm -rf "$PROC_DIR"
  exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python - "$PROC_DIR" <<'EOF'
import json, os, sys
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, read_metrics, validate_record)
base = sys.argv[1]
oracle = json.load(open(os.path.join(base, "oracle.json")))
proc = json.load(open(os.path.join(base, "proc.json")))
a = {s["uid"]: s["tokens"] for s in oracle["sequences"]}
b = {s["uid"]: s["tokens"] for s in proc["sequences"]}
assert a == b, "process-fleet tokens != in-process fleet oracle"
assert not proc["failed"], proc["failed"]
assert proc["transport"] == "process", proc.get("transport")
st = proc["fleet"]
assert st["kills"] == 1 and st["migrations"] >= 1, st
assert st["engines"]["e1"]["alive"] is False, st["engines"]["e1"]
records, problems = read_metrics(
    os.path.join(base, "m", "router", METRICS_FILENAME))
assert not problems, problems
routers = [r for r in records if r["kind"] == "router"]
assert routers and all(validate_record(r)[0] for r in routers)
migs = [r for r in routers if r["event"] == "migrated"
        and r["source"] == "e1"]
assert migs, routers
assert all(r["transport"]["mode"] == "replay" for r in migs), migs
rep = open(os.path.join(base, "report.txt")).read()
assert "engine_killed" in rep and "MIGRATED" in rep, rep[-2000:]
# the SIGKILLed worker's own stream survived (flushed per record)
e1_recs, _ = read_metrics(os.path.join(base, "m", "e1",
                                       METRICS_FILENAME))
assert e1_recs, "dead worker left no telemetry"
EOF
then
  echo "PROCESS_SMOKE=FAIL (token-identity/schema/report check)"
  rm -rf "$PROC_DIR"; exit 1
fi
echo "PROCESS_SMOKE=OK"
phase_done process_smoke

echo "=== tcp-transport smoke ==="
# The round-22 network-boundary drill (DESIGN.md section 28): the
# SAME 3-worker fleet over TCP loopback (--transport tcp — reconnect
# ladder + sequence-numbered replay, handoffs streamed over the
# framed side channel) with the link to one worker PARTITIONED
# mid-stream (partition_worker@4:2 — drops both ways, heals) and
# another worker SIGKILLed under async live migration
# (kill_worker@8:1 --async_migration). Tokens must be byte-identical
# to the AF_UNIX oracle, the partition must cost a reconnect and
# ZERO dead-host declarations (kills == the 1 scheduled SIGKILL, no
# worker_dead events), the router stream must hold >=1 schema-v17
# reconnected record, and `report --audit` over the streams must be
# rc 0. Malformed --transport/chaos combinations must reject rc 2
# with one stderr line.
TCP_DIR=$(mktemp -d /tmp/tier1_tcp.XXXXXX)
TCP_ARGS="--prompt_lens 3,7,5 --max_new 8 -d 32 -l 2 --heads 4
  --vocab 64 --max_seq_len 64 --block_size 8 --prefill_chunk 4
  --log_every 2"
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $TCP_ARGS \
    --fleet 3 --transport process > "$TCP_DIR/oracle.json"; then
  echo "TCP_SMOKE=FAIL (AF_UNIX fleet oracle)"
  rm -rf "$TCP_DIR"; exit 1
fi
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $TCP_ARGS \
    --fleet 3 --transport tcp --async_migration \
    --fleet_chaos partition_worker@4:2,kill_worker@8:1 \
    --metrics_dir "$TCP_DIR/m" > "$TCP_DIR/tcp.json"; then
  echo "TCP_SMOKE=FAIL (tcp chaos drill run)"; rm -rf "$TCP_DIR"
  exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report --audit \
    "$TCP_DIR/m/router" "$TCP_DIR/m/e0" "$TCP_DIR/m/e1" \
    "$TCP_DIR/m/e2" > "$TCP_DIR/audit.txt"; then
  echo "TCP_SMOKE=FAIL (report --audit rc)"; rm -rf "$TCP_DIR"
  exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python - "$TCP_DIR" <<'EOF'
import json, os, sys
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, read_metrics, validate_record)
base = sys.argv[1]
oracle = json.load(open(os.path.join(base, "oracle.json")))
tcp = json.load(open(os.path.join(base, "tcp.json")))
a = {s["uid"]: s["tokens"] for s in oracle["sequences"]}
b = {s["uid"]: s["tokens"] for s in tcp["sequences"]}
assert a == b, "tcp-fleet tokens != AF_UNIX fleet oracle"
assert not tcp["failed"], tcp["failed"]
assert tcp["transport"] == "tcp", tcp.get("transport")
st = tcp["fleet"]
# the partition healed: ONE kill (the scheduled SIGKILL), >=1
# reconnect, zero dead-host declarations
assert st["kills"] == 1 and st["reconnects"] >= 1, st
assert st["engines"]["e1"]["alive"] is False, st["engines"]["e1"]
records, problems = read_metrics(
    os.path.join(base, "m", "router", METRICS_FILENAME))
assert not problems, problems
assert not [r for r in records
            if r.get("event") == "worker_dead"], "false death"
routers = [r for r in records if r["kind"] == "router"]
assert routers and all(validate_record(r)[0] for r in routers)
recon = [r for r in routers if r["event"] == "reconnected"]
assert recon and all(r["schema"] == 17 for r in recon), routers
migs = [r for r in routers if r["event"] == "migrated"]
assert migs and all("ship_s" in r and "catchup_tokens" in r
                    for r in migs), migs
EOF
then
  echo "TCP_SMOKE=FAIL (token-identity/reconnect/schema check)"
  rm -rf "$TCP_DIR"; exit 1
fi
# malformed --transport/chaos combinations: rc 2, one stderr line
for BAD in \
    "--fleet 3 --transport process --fleet_chaos partition_worker@4" \
    "--fleet 3 --fleet_chaos drop_conn@3" \
    "--fleet 3 --transport tcp --fleet_chaos slow_link@3:-5" \
    "--transport tcp"; do
  if timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
      distributed_llm_code_samples_tpu.cli generate $TCP_ARGS $BAD \
      > /dev/null 2> "$TCP_DIR/err.txt"; then
    echo "TCP_SMOKE=FAIL (accepted: $BAD)"; rm -rf "$TCP_DIR"; exit 1
  fi
  if [ "$(wc -l < "$TCP_DIR/err.txt")" -ne 1 ]; then
    echo "TCP_SMOKE=FAIL (not one stderr line: $BAD)"
    cat "$TCP_DIR/err.txt"; rm -rf "$TCP_DIR"; exit 1
  fi
done
rm -rf "$TCP_DIR"
echo "TCP_SMOKE=OK"
phase_done tcp_smoke

echo "=== autoscale smoke ==="
# The ISSUE 16 closed loop (DESIGN.md section 26): a bursty 2-tenant
# trace through a 2-engine PROCESS fleet with kill_worker mid-burst —
# the controller must scale up (spawned worker warmed before traffic),
# tokens must be byte-identical across two replays of the committed
# trace (controller decisions fold only the virtual round clock), the
# router stream must hold >=1 schema-v14 autoscale record, `report
# --slo` must print per-tenant AND per-policy attainment, and a
# malformed --autoscale spec must exit rc 2 with a one-line error.
AS_DIR=$(mktemp -d /tmp/tier1_autoscale.XXXXXX)
AS_SPEC="n=10,arrival=bursty:40:0.2:0.3,plen=zipf:1.7:3:12,max_new=4,tenants=a:3;b:1,seed=5"
AS_ARGS="-d 32 -l 2 --heads 4 --vocab 64 --max_seq_len 64
  --block_size 8 --prefill_chunk 4 --log_every 2 --fleet 2
  --max_slots 2 --transport process --fleet_chaos kill_worker@6"
AS_POLICY="min=2,max=3,up=3,down=1,hysteresis=2,cooldown=6"
AS_QOS="discipline=wfq,weights=a:2;b:1"
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $AS_ARGS \
    --autoscale "$AS_POLICY" --qos "$AS_QOS" --policy wfq \
    --trace_gen "$AS_SPEC" --trace_out "$AS_DIR/trace.jsonl" \
    --metrics_dir "$AS_DIR/m1" > "$AS_DIR/run1.json"; then
  echo "AUTOSCALE_SMOKE=FAIL (chaos run 1)"; rm -rf "$AS_DIR"; exit 1
fi
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $AS_ARGS \
    --autoscale "$AS_POLICY" --qos "$AS_QOS" --policy wfq \
    --trace "$AS_DIR/trace.jsonl" \
    --metrics_dir "$AS_DIR/m2" > "$AS_DIR/run2.json"; then
  echo "AUTOSCALE_SMOKE=FAIL (committed-trace replay)"
  rm -rf "$AS_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report "$AS_DIR/m1/router" \
    "$AS_DIR/m1/e0" "$AS_DIR/m1/e1" "$AS_DIR/m1/e2" --slo 100:0.5 \
    > "$AS_DIR/report.txt"; then
  echo "AUTOSCALE_SMOKE=FAIL (report --slo rc)"; rm -rf "$AS_DIR"
  exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python - "$AS_DIR" <<'EOF_AS'
import json, os, sys
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, read_metrics, validate_record)
base = sys.argv[1]
r1 = json.load(open(os.path.join(base, "run1.json")))
r2 = json.load(open(os.path.join(base, "run2.json")))
a = {s["uid"]: s["tokens"] for s in r1["sequences"]}
b = {s["uid"]: s["tokens"] for s in r2["sequences"]}
assert a == b, "autoscaled replay produced different tokens"
assert not r1["failed"] and not r2["failed"], (r1["failed"],
                                              r2["failed"])
assert r1["shed"] == 0 and r2["shed"] == 0, (r1["shed"], r2["shed"])
assert r1["policy"] == "wfq", r1.get("policy")
# the controller reacted — and identically on both replays
asc = r1["autoscale"]
assert asc["scale_ups"] >= 1, asc
assert any(h["event"] == "scale_up" for h in asc["history"]), asc
assert asc == r2["autoscale"], (asc, r2["autoscale"])
assert r1["fleet"]["kills"] == 1, r1["fleet"]
# router stream holds schema-valid autoscale records
recs, problems = read_metrics(
    os.path.join(base, "m1", "router", METRICS_FILENAME))
assert not problems, problems
auto = [r for r in recs if r["kind"] == "autoscale"]
assert auto and all(validate_record(r)[0] for r in auto), auto
assert any(r["event"] == "scale_up" for r in auto), auto
rep = open(os.path.join(base, "report.txt")).read()
assert "tenant a" in rep and "tenant b" in rep, rep[-2000:]
assert "policy wfq" in rep and "goodput" in rep, rep[-2000:]
EOF_AS
then
  echo "AUTOSCALE_SMOKE=FAIL (determinism/schema/slo check)"
  rm -rf "$AS_DIR"; exit 1
fi
if timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $AS_ARGS \
    --autoscale "min=2,max=1" --trace_gen "$AS_SPEC" \
    > /dev/null 2> "$AS_DIR/bad.err"; then
  echo "AUTOSCALE_SMOKE=FAIL (malformed --autoscale spec accepted)"
  rm -rf "$AS_DIR"; exit 1
fi
if [ "$(wc -l < "$AS_DIR/bad.err")" -ne 1 ]; then
  echo "AUTOSCALE_SMOKE=FAIL (spec rejection not a one-line error)"
  rm -rf "$AS_DIR"; exit 1
fi
rm -rf "$AS_DIR"
echo "AUTOSCALE_SMOKE=OK"
phase_done autoscale_smoke

echo "=== watchtower smoke ==="
# The ISSUE 17 acceptance drill (DESIGN.md section 27): a bursty
# 2-tenant trace through a 2-engine fleet, e1 killed at round 4 under
# the opening burst with `--watch deadline=8,fast=4,slow=12,
# incidents=1` — the burn-rate page must FIRE within the deadline
# window of the kill and RESOLVE after migration while the replay
# still runs; a second replay of the committed trace must agree
# byte-for-byte on the alert history (`report --diff --kinds alert`
# says identical, rc 0); the run's streams must audit clean; and a
# malformed --watch spec must exit rc 2 with a one-line error.
WT_DIR=$(mktemp -d /tmp/tier1_watch.XXXXXX)
WT_SPEC="n=8,arrival=bursty:30:0.15:2.5,plen=zipf:1.7:3:12,max_new=4,tenants=a:3;b:1,seed=7"
WT_ARGS="-d 32 -l 2 --heads 4 --vocab 64 --max_seq_len 64
  --block_size 8 --prefill_chunk 4 --log_every 4 --fleet 2
  --max_slots 2 --fleet_kill e1@4"
WT_WATCH="deadline=8,fast=4,slow=12,incidents=1"
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $WT_ARGS \
    --watch "$WT_WATCH" --trace_gen "$WT_SPEC" \
    --trace_out "$WT_DIR/trace.jsonl" --metrics_dir "$WT_DIR/m1" \
    > "$WT_DIR/run1.json"; then
  echo "WATCHTOWER_SMOKE=FAIL (kill drill run 1)"; rm -rf "$WT_DIR"
  exit 1
fi
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $WT_ARGS \
    --watch "$WT_WATCH" --trace "$WT_DIR/trace.jsonl" \
    --metrics_dir "$WT_DIR/m2" > "$WT_DIR/run2.json"; then
  echo "WATCHTOWER_SMOKE=FAIL (committed-trace replay)"
  rm -rf "$WT_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python - "$WT_DIR" <<'EOF_WT'
import json, os, sys
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, read_metrics, validate_record)
base = sys.argv[1]
r1 = json.load(open(os.path.join(base, "run1.json")))
r2 = json.load(open(os.path.join(base, "run2.json")))
a = {s["uid"]: s["tokens"] for s in r1["sequences"]}
b = {s["uid"]: s["tokens"] for s in r2["sequences"]}
assert a == b, "watched replay produced different tokens"
assert not r1["failed"] and not r2["failed"]
w = r1["watch"]
# the lifecycle, not just the page: fired AND resolved, both detectors
assert w["fired"] == 2 and w["resolved"] == 2, w
hist = [(h["round"], h["event"], h["detector"]) for h in w["history"]]
fired = next(r for r, e, d in hist
             if d == "burn_rate" and e == "fired")
resolved = next(r for r, e, d in hist
                if d == "burn_rate" and e == "resolved")
assert fired - 4 <= 8, (fired, "page later than a deadline window "
                        "after the kill")
assert resolved > fired, hist
assert r1["fleet"]["kills"] == 1, r1["fleet"]
# the alert history is replay-deterministic in the payload too
assert r2["watch"] == w, (w, r2["watch"])
recs, problems = read_metrics(
    os.path.join(base, "m1", "router", METRICS_FILENAME))
assert not problems, problems
alerts = [r for r in recs if r["kind"] == "alert"]
assert [(x["step"], x["event"], x["detector"]) for x in alerts] \
    == hist, (alerts, hist)
assert all(validate_record(x)[0] for x in alerts)
EOF_WT
then
  echo "WATCHTOWER_SMOKE=FAIL (reaction/lifecycle check)"
  rm -rf "$WT_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report "$WT_DIR/m1/router" \
    "$WT_DIR/m2/router" --diff --kinds alert > "$WT_DIR/diff.txt"
then
  echo "WATCHTOWER_SMOKE=FAIL (alert history diverged across replays)"
  cat "$WT_DIR/diff.txt"; rm -rf "$WT_DIR"; exit 1
fi
if ! grep -q "identical" "$WT_DIR/diff.txt"; then
  echo "WATCHTOWER_SMOKE=FAIL (diff verdict not identical)"
  cat "$WT_DIR/diff.txt"; rm -rf "$WT_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report "$WT_DIR/m1/router" \
    "$WT_DIR/m1/e0" "$WT_DIR/m1/e1" --audit > /dev/null; then
  echo "WATCHTOWER_SMOKE=FAIL (audit)"; rm -rf "$WT_DIR"; exit 1
fi
if timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $WT_ARGS \
    --watch "deadline=8,fast=4,slow=4" --trace_gen "$WT_SPEC" \
    > /dev/null 2> "$WT_DIR/bad.err"; then
  echo "WATCHTOWER_SMOKE=FAIL (malformed --watch spec accepted)"
  rm -rf "$WT_DIR"; exit 1
fi
if [ "$(wc -l < "$WT_DIR/bad.err")" -ne 1 ]; then
  echo "WATCHTOWER_SMOKE=FAIL (spec rejection not a one-line error)"
  rm -rf "$WT_DIR"; exit 1
fi
rm -rf "$WT_DIR"
echo "WATCHTOWER_SMOKE=OK"
phase_done watchtower_smoke

echo "=== trace smoke ==="
# The ISSUE 14 spine on the PROCESS drill's own artifacts (no second
# fleet boot): `report --trace` on the uid the SIGKILL migrated must
# exit 0 with ONE stitched cross-process waterfall (spans from the
# dead worker's surviving stream AND the survivor's, the kill's dead
# time classified a migration stall, span sum + gaps reconciling with
# the recorded latency — never UNRECONCILED); a malformed --trace arg
# rejects rc 2; `fleetstat` reads the finished run's atomic status
# doc rc 0 (and rc 2 with no doc).
TRACE_UID=$(timeout -k 10 60 env JAX_PLATFORMS=cpu python - "$PROC_DIR" <<'EOF'
import os, sys
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, read_metrics)
records, _ = read_metrics(os.path.join(sys.argv[1], "m", "router",
                                       METRICS_FILENAME))
migs = [r for r in records if r["kind"] == "router"
        and r["event"] == "migrated"]
assert migs, "process drill migrated nothing"
print(migs[0]["uid"])
EOF
)
if [ -z "$TRACE_UID" ]; then
  echo "TRACE_SMOKE=FAIL (no migrated uid)"; rm -rf "$PROC_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report "$PROC_DIR/m/router" \
    "$PROC_DIR/m/e0" "$PROC_DIR/m/e1" "$PROC_DIR/m/e2" \
    --trace "$TRACE_UID" > "$PROC_DIR/trace.txt"; then
  echo "TRACE_SMOKE=FAIL (report --trace rc)"; rm -rf "$PROC_DIR"
  exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python - "$PROC_DIR" <<'EOF'
import sys
text = open(sys.argv[1] + "/trace.txt").read()
assert "trace " in text and "MIGRATED" in text, text[-800:]
assert "reconciled" in text, text[-800:]
assert "UNRECONCILED" not in text, text[-800:]
EOF
then
  echo "TRACE_SMOKE=FAIL (waterfall content)"; rm -rf "$PROC_DIR"
  exit 1
fi
if timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report "$PROC_DIR/m/router" \
    --trace banana > /dev/null 2>&1; then
  echo "TRACE_SMOKE=FAIL (malformed --trace accepted)"
  rm -rf "$PROC_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli fleetstat \
    "$PROC_DIR/m/router" > "$PROC_DIR/status.txt"; then
  echo "TRACE_SMOKE=FAIL (fleetstat rc)"; rm -rf "$PROC_DIR"; exit 1
fi
if ! grep -q "DRAINED" "$PROC_DIR/status.txt" \
    || ! grep -q "DEAD" "$PROC_DIR/status.txt"; then
  echo "TRACE_SMOKE=FAIL (status content)"
  cat "$PROC_DIR/status.txt"; rm -rf "$PROC_DIR"; exit 1
fi
if timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli fleetstat \
    "$PROC_DIR/m/e0" > /dev/null 2>&1; then
  echo "TRACE_SMOKE=FAIL (fleetstat rc 0 with no status doc)"
  rm -rf "$PROC_DIR"; exit 1
fi
# the process drill's surviving streams — including the SIGKILLed
# worker's — must audit clean across the process boundary
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report "$PROC_DIR/m/router" \
    "$PROC_DIR/m/e0" "$PROC_DIR/m/e1" "$PROC_DIR/m/e2" \
    --audit > /dev/null; then
  echo "TRACE_SMOKE=FAIL (audit)"; rm -rf "$PROC_DIR"; exit 1
fi
rm -rf "$PROC_DIR"
echo "TRACE_SMOKE=OK"
phase_done trace_smoke

echo "=== fleet SLO smoke ==="
# The ISSUE 11 acceptance drill (DESIGN.md section 21): a 3-engine
# fleet with one migration forced (kill e1 late, so the dead engine's
# decode stretch becomes the migration gap), then `report --slo` over
# the merged four-stream run must exit 0 with attainment printed, the
# router stream must hold >= 1 schema-valid `fleet` health record, and
# the migrated uid's violation must be attributed to `migration` — not
# to an innocent decode span. A malformed --slo spec rejects rc 2 (the
# train-CLI parse discipline).
SLO_DIR=$(mktemp -d /tmp/tier1_slo.XXXXXX)
SLO_ARGS="--prompt_lens 3,7,5 --max_new 12 -d 32 -l 2 --heads 4
  --vocab 64 --max_seq_len 64 --block_size 8 --prefill_chunk 4
  --log_every 2"
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $SLO_ARGS \
    --fleet 3 --fleet_kill e1@8 --metrics_dir "$SLO_DIR/m" \
    > "$SLO_DIR/fleet.json"; then
  echo "SLO_SMOKE=FAIL (fleet run)"; rm -rf "$SLO_DIR"; exit 1
fi
if timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report "$SLO_DIR/m/router" \
    --slo banana > /dev/null 2>&1; then
  echo "SLO_SMOKE=FAIL (malformed --slo accepted)"; rm -rf "$SLO_DIR"
  exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report "$SLO_DIR/m/router" \
    "$SLO_DIR/m/e0" "$SLO_DIR/m/e1" "$SLO_DIR/m/e2" \
    --slo 100:0.000001 > "$SLO_DIR/slo.txt"; then
  echo "SLO_SMOKE=FAIL (report --slo rc)"; rm -rf "$SLO_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli report "$SLO_DIR/m/router" \
    "$SLO_DIR/m/e0" "$SLO_DIR/m/e1" "$SLO_DIR/m/e2" \
    --slo 100:0.000001 --json > "$SLO_DIR/slo.json"; then
  echo "SLO_SMOKE=FAIL (report --slo --json rc)"; rm -rf "$SLO_DIR"
  exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python - "$SLO_DIR" <<'EOF'
import json, os, sys
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, read_metrics, validate_record)
base = sys.argv[1]
text = open(os.path.join(base, "slo.txt")).read()
assert "SLO attainment" in text and "attributed" in text, text[-800:]
records, problems = read_metrics(
    os.path.join(base, "m", "router", METRICS_FILENAME))
assert not problems, problems
fleet_recs = [r for r in records if r["kind"] == "fleet"]
assert fleet_recs, "no schema-valid fleet record in the router stream"
assert all(validate_record(r)[0] for r in fleet_recs)
mig_uids = {r["uid"] for r in records if r["kind"] == "router"
            and r["event"] == "migrated"}
assert mig_uids, "drill forced no migration"
doc = json.load(open(os.path.join(base, "slo.json")))
slo = doc["slo"]
assert slo["unreconciled"] == 0, slo
by_uid = {e["uid"]: e for e in slo["requests"]}
for uid in mig_uids:
    e = by_uid[uid]
    assert e["status"] == "violated", e
    assert e["attributed"] == "migration", (
        "migration-stalled uid attributed to an innocent span", e)
# every completed uid's decomposition reconciled (ttft + post-first
# spans + the migration gap account for the full latency)
assert slo["completed"] == len(slo["requests"]) == 3, slo
EOF
then
  echo "SLO_SMOKE=FAIL (attainment/attribution check)"
  rm -rf "$SLO_DIR"; exit 1
fi
rm -rf "$SLO_DIR"
echo "SLO_SMOKE=OK"
phase_done slo_smoke

echo "=== rolling-deploy smoke ==="
# Live weight hot-swap (DESIGN.md section 23): the TRAINER publishes
# checkpoints via the existing atomic fsync+CRC publish (-m 11, the LM
# family at the serving shape), then a 3-engine fleet rolls the newest
# step engine-by-engine at round 4 mid-serve (drain over the KV
# handoff, swap, re-admit — zero shed). Every completed uid must be
# BYTE-IDENTICAL to one of the two pinned-version single-engine
# oracles (--random_seed 0 = the boot weights; --weights_from = the
# deployed checkpoint) with BOTH versions represented, and the router
# stream must hold schema-v11 deploy records. The corrupt_deploy
# variant tears the target step: the CRC ladder must reject it with
# the one-line rollback (stderr + rolled_back record), every request
# completing on v0 with no engine left mixed.
DEP_DIR=$(mktemp -d /tmp/tier1_deploy.XXXXXX)
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli -m 11 -s 4 -bs 2 -n 64 -d 32 \
    -l 2 --heads 4 --vocab 64 --fake_devices 4 \
    --checkpoint_dir "$DEP_DIR/ck" --checkpoint_every 2 > /dev/null
then
  echo "DEPLOY_SMOKE=FAIL (trainer publish)"; rm -rf "$DEP_DIR"; exit 1
fi
DEP_CK="$DEP_DIR/ck/train_lm_tp"
DEP_ARGS="--prompt_lens 3,7,5,6,4,9 --max_new 8 -d 32 -l 2 --heads 4
  --vocab 64 --max_seq_len 64 --block_size 8 --prefill_chunk 4
  --max_slots 1 --log_every 2"
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $DEP_ARGS \
    > "$DEP_DIR/v0.json"; then
  echo "DEPLOY_SMOKE=FAIL (v0 oracle)"; rm -rf "$DEP_DIR"; exit 1
fi
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $DEP_ARGS \
    --weights_from "$DEP_CK" > "$DEP_DIR/vnew.json"; then
  echo "DEPLOY_SMOKE=FAIL (deployed-version oracle)"
  rm -rf "$DEP_DIR"; exit 1
fi
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $DEP_ARGS \
    --fleet 3 --deploy_dir "$DEP_CK" --deploy_round 4 \
    --metrics_dir "$DEP_DIR/m" > "$DEP_DIR/fleet.json"; then
  echo "DEPLOY_SMOKE=FAIL (rolling deploy run)"; rm -rf "$DEP_DIR"
  exit 1
fi
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu python -m \
    distributed_llm_code_samples_tpu.cli generate $DEP_ARGS \
    --fleet 3 --deploy_dir "$DEP_CK" --deploy_round 4 \
    --fleet_chaos corrupt_deploy@4 --metrics_dir "$DEP_DIR/mc" \
    > "$DEP_DIR/corrupt.json" 2> "$DEP_DIR/corrupt.err"; then
  echo "DEPLOY_SMOKE=FAIL (corrupt_deploy run)"; rm -rf "$DEP_DIR"
  exit 1
fi
if ! grep -q "rolled back" "$DEP_DIR/corrupt.err"; then
  echo "DEPLOY_SMOKE=FAIL (no one-line rollback on stderr)"
  tail -3 "$DEP_DIR/corrupt.err"; rm -rf "$DEP_DIR"; exit 1
fi
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python - "$DEP_DIR" <<'EOF'
import json, os, sys
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, read_metrics, validate_record)
base = sys.argv[1]
v0 = {s["uid"]: s["tokens"] for s in
      json.load(open(os.path.join(base, "v0.json")))["sequences"]}
vn = {s["uid"]: s["tokens"] for s in
      json.load(open(os.path.join(base, "vnew.json")))["sequences"]}
fl = json.load(open(os.path.join(base, "fleet.json")))
toks = {s["uid"]: s["tokens"] for s in fl["sequences"]}
assert not fl["failed"] and fl["shed"] == 0, (fl["failed"], fl["shed"])
st = fl["fleet"]
assert st["deploys"] == 1 and st["deploy_rollbacks"] == 0, st
assert st["sheds"] == 0, st
target = {v["serving_version"] for v in st["engines"].values()}
assert target == {4}, target            # every engine on the new step
# token identity per pinned version: each uid matches an oracle, both
# versions represented (old pins finished on v0, post-deploy
# admissions decoded on the deployed weights)
assert set(toks) == set(v0) == set(vn)
on_old = {u for u in toks if toks[u] == v0[u]}
on_new = {u for u in toks if toks[u] == vn[u]}
assert on_old | on_new == set(toks), set(toks) - (on_old | on_new)
assert on_old and on_new, (sorted(on_old), sorted(on_new))
records, problems = read_metrics(
    os.path.join(base, "m", "router", METRICS_FILENAME))
assert not problems, problems
deps = [r for r in records if r["kind"] == "deploy"]
assert deps and all(validate_record(d)[0] for d in deps)
assert [d["event"] for d in deps] == (
    ["started"] + ["engine_swapped"] * 3 + ["completed"]), deps
assert all(d["from_version"] == 0 and d["to_version"] == 4
           for d in deps)
# the corrupt_deploy variant: rollback record with the one-line named
# reason, fleet stays on v0, every request completes on the v0 oracle
co = json.load(open(os.path.join(base, "corrupt.json")))
ctoks = {s["uid"]: s["tokens"] for s in co["sequences"]}
assert ctoks == v0, "corrupt-deploy run diverged from the v0 oracle"
cst = co["fleet"]
assert cst["deploys"] == 0 and cst["deploy_rollbacks"] == 1, cst
assert {v["serving_version"] for v in cst["engines"].values()} == {0}
crecs, cproblems = read_metrics(
    os.path.join(base, "mc", "router", METRICS_FILENAME))
assert not cproblems, cproblems
[rb] = [r for r in crecs if r["kind"] == "deploy"]
assert rb["event"] == "rolled_back" and validate_record(rb)[0]
assert "\n" not in rb["reason"] and "rejected" in rb["reason"], rb
EOF
then
  echo "DEPLOY_SMOKE=FAIL (pinned-identity/schema check)"
  rm -rf "$DEP_DIR"; exit 1
fi
rm -rf "$DEP_DIR"
echo "DEPLOY_SMOKE=OK"
phase_done deploy_smoke

echo "=== bench-trend smoke ==="
# The committed SCALING_*/DECODE_* round artifacts must keep their row
# contracts (scripts/bench_trend.py exits 2 on drift or a missing
# headline key) — the bench-trajectory story stays parseable.
if ! timeout -k 10 60 python scripts/bench_trend.py > /dev/null; then
  echo "BENCH_TREND_SMOKE=FAIL"; exit 1
fi
echo "BENCH_TREND_SMOKE=OK"
phase_done bench_trend_smoke

echo "=== tier-1 pytest ==="
# budget raised 870 -> 1500 at r20: measured 982s green (808 passed /
# 0 failed, warm XLA cache) on a 1-core image — the old number was
# calibrated on 2 cores; the suite itself is unchanged in cost (~25s
# of r20 additions), the box is serial-bound.
set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 1500 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c); phase_done pytest; exit $rc
