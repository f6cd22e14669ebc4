"""Tiered KV memory hierarchy (ISSUE 19): the host-RAM spill tier
(``decode/spill.py``), sub-block prefix sharing, and their engine
composition (``decode/engine.py``, DESIGN.md section 29).

The acceptance spine:

- **Session-churn capacity**: K distinct sessions returning M times
  through a device pool sized below the working set pay ~K prefill
  passes, not K*M — returning prefixes RESTORE from the host tier via
  the donated implant program instead of re-prefilling
  (dispatch-count-provable, like the round-13 prefix reuse).
- **Bit-identity everywhere**: spill/restore output == the big-pool
  never-evicting engine token for token at f32/bf16/int8 — restored
  bytes are the evicted bytes (wire CRC + the differential oracle).
- **Reliability composition**: poisoned blocks never spill; a
  CRC-corrupt tier entry quarantines exactly the restoring request
  (survivors bit-identical); kill→resume restores an engine whose
  host tier is EMPTY and replay rebuilds the share graph.
- **Sub-block sharing**: a partial-block radix hit CoW-copies the
  shared rows; f32/bf16 output is byte-identical to the whole-block
  engine (row purity), int8 is deterministic under the donor's frozen
  scale.
"""

import os

import jax
import numpy as np
import pytest

from distributed_llm_code_samples_tpu.decode import (DecodeEngine,
                                                     EngineConfig,
                                                     ServePolicy,
                                                     load_snapshot,
                                                     supervise_decode,
                                                     write_snapshot)
from distributed_llm_code_samples_tpu.decode.spill import SpillTier
from distributed_llm_code_samples_tpu.models import init_lm
from distributed_llm_code_samples_tpu.runtime import wire
from distributed_llm_code_samples_tpu.runtime.chaos import FaultPlan

V, D, L, H = 64, 32, 2, 4
BLOCK = 4
# device pool sized for the two running reservations only (scratch +
# 2 slots * 8 blocks/seq ceiling would be huge; the churn prompts below
# use 4 blocks each, so 11 blocks = scratch + running pair + 2 slack)
SMALL = dict(block_size=BLOCK, n_blocks=11, max_slots=2,
             max_blocks_per_seq=8, prefill_chunk=BLOCK,
             temperature=0.0, seed=0, prefix_cache=True)
BIG = dict(SMALL, n_blocks=64)


@pytest.fixture(scope="module")
def lm_params():
    return init_lm(jax.random.PRNGKey(0), V, D, L, max_seq_len=64)


@pytest.fixture(scope="module")
def sessions():
    """Four DISTINCT 9-token session prompts (2 full blocks + 1 tail
    token each): retention of all four outgrows the small pool, so
    churn must demote through the spill tier."""
    rng = np.random.default_rng(3)
    return [rng.integers(0, V, size=9).tolist() for _ in range(4)]


def _churn(params, cfg_kw, prompts, returns=3, max_new=6, policy=None):
    """K sessions x M returns, submitted in rounds (each return lands
    after the previous round drained — the returning-session shape)."""
    eng = DecodeEngine(params, H, EngineConfig(**cfg_kw), policy=policy)
    for _ in range(returns):
        for p in prompts:
            eng.submit(p, max_new)
        eng.run()
    return eng


# ---------------------------------------------------------------------------
# tier units (pure host code)


def test_spill_tier_put_take_roundtrip():
    tier = SpillTier(4)
    doc = {"k": np.arange(12, dtype=np.float32).reshape(2, 6),
           "v": np.ones((2, 6), np.float32), "k_scale": None,
           "v_scale": None}
    sid, dropped = tier.put(object(), doc)
    assert dropped == [] and len(tier) == 1
    back = tier.take(sid)
    assert len(tier) == 0 and tier.restores == 1
    np.testing.assert_array_equal(back["k"], doc["k"])
    np.testing.assert_array_equal(back["v"], doc["v"])
    assert back["k_scale"] is None
    with pytest.raises(KeyError):
        tier.take(sid)                          # promotion consumed it


def test_spill_tier_overflow_drops_oldest():
    tier = SpillTier(2)
    nodes = [object() for _ in range(3)]
    doc = {"k": np.zeros(3, np.float32), "v": np.zeros(3, np.float32),
           "k_scale": None, "v_scale": None}
    s0, d0 = tier.put(nodes[0], doc)
    s1, d1 = tier.put(nodes[1], doc)
    s2, d2 = tier.put(nodes[2], doc)
    assert d0 == [] and d1 == []
    assert d2 == [nodes[0]]                     # FIFO = LRU-by-spill
    assert len(tier) == 2 and tier.drops == 1
    with pytest.raises(KeyError):
        tier.take(s0)                           # dropped, unrestorable
    assert tier.take(s2)["k"].shape == (3,)


def test_spill_tier_corrupt_detected_at_take():
    tier = SpillTier(2)
    doc = {"k": np.arange(8, dtype=np.float32),
           "v": np.arange(8, dtype=np.float32), "k_scale": None,
           "v_scale": None}
    sid, _ = tier.put(object(), doc)
    assert tier.corrupt(sid)
    with pytest.raises(wire.WireError):
        tier.take(sid)
    assert len(tier) == 0                       # evidence consumed
    assert tier.restores == 0 and tier.drops == 1
    assert not tier.corrupt(sid)                # already gone: a miss


def test_spill_tier_rejects_zero_capacity():
    with pytest.raises(ValueError, match=">= 1 block"):
        SpillTier(0)


def test_engine_config_validation(lm_params):
    with pytest.raises(ValueError, match="prefix_cache"):
        DecodeEngine(lm_params, H, EngineConfig(
            **dict(SMALL, prefix_cache=False, spill_blocks=8)))
    with pytest.raises(ValueError, match="prefix_cache"):
        DecodeEngine(lm_params, H, EngineConfig(
            **dict(SMALL, prefix_cache=False, prefix_partial=True)))
    with pytest.raises(ValueError, match="spill_restore_per_step"):
        DecodeEngine(lm_params, H, EngineConfig(
            **dict(SMALL, spill_blocks=8, spill_restore_per_step=0)))


# ---------------------------------------------------------------------------
# the session-churn drill: capacity below the working set, ~K prefills,
# byte-identical output


@pytest.mark.parametrize("kv_dtype", ["f32", "bf16", "int8"])
def test_session_churn_byte_identity(lm_params, sessions, kv_dtype):
    oracle = _churn(lm_params, dict(BIG, kv_dtype=kv_dtype), sessions,
                    returns=1)
    eng = _churn(lm_params, dict(SMALL, kv_dtype=kv_dtype,
                                 spill_blocks=32), sessions, returns=3)
    assert len(eng.finished) == 3 * len(sessions)
    for uid, toks in eng.finished.items():
        assert toks == oracle.finished[uid % len(sessions)], uid
    # churn actually exercised the tier, and restores saved re-prefill
    assert eng.spilled_blocks > 0 and eng.restores > 0
    assert eng.restore_tokens_saved == eng.restores * BLOCK
    # ~K prefill passes, not K*M: the no-spill engine on the same tiny
    # pool re-prefills every evicted return
    base = _churn(lm_params, dict(SMALL, kv_dtype=kv_dtype), sessions,
                  returns=3)
    assert eng.prefill_dispatches < base.prefill_dispatches
    assert base.finished == eng.finished        # same tokens either way


def test_restore_stall_bounded_per_step(lm_params, sessions):
    """The restore budget: spill_restore_per_step=1 means a returning
    session whose prefix spilled N blocks is admitted over >= N steps
    (budget-deferred), each step restoring at most one block — and the
    engine keeps making progress (no stall-guard trip)."""
    eng = _churn(lm_params, dict(SMALL, kv_dtype="f32", spill_blocks=32,
                                 spill_restore_per_step=1), sessions,
                 returns=1)
    for p in sessions:
        eng.submit(p, 6)
    restores_by_step = []
    last = eng.restores
    while eng.waiting or eng.active:
        eng.step()
        restores_by_step.append(eng.restores - last)
        last = eng.restores
    assert eng.restores > 0
    assert max(restores_by_step) <= 1           # the per-step budget
    assert len(eng.finished) == 2 * len(sessions)
    # cumulative stall stays a sum of per-block implant costs — the
    # drill's "p90 bounded" reading: no step restored more than budget
    assert eng.restore_stall_s >= 0.0


def test_schema_v17_record_with_restores(lm_params, sessions, tmp_path):
    from distributed_llm_code_samples_tpu.runtime.telemetry import (
        DECODE_REQUIRED, METRICS_FILENAME, SCHEMA_VERSION,
        TelemetryWriter, read_metrics, validate_record)
    mdir = str(tmp_path / "metrics")
    with TelemetryWriter(mdir, meta={"subcommand": "generate"}) as w:
        eng = DecodeEngine(lm_params, H, EngineConfig(
            **dict(SMALL, kv_dtype="f32", spill_blocks=32)))
        eng.metrics = w
        for _ in range(2):
            for p in sessions:
                eng.submit(p, 6)
            eng.run(metrics=w, log_every=2)
    records, problems = read_metrics(os.path.join(mdir,
                                                  METRICS_FILENAME))
    assert problems == []
    decs = [r for r in records if r["kind"] == "decode"]
    assert decs
    for r in decs:
        assert r["schema"] == SCHEMA_VERSION
        ok, reason = validate_record(r)
        assert ok, reason
        for key in ("spilled_blocks", "spill_bytes", "restores",
                    "restore_tokens_saved", "restore_stall_s",
                    "partial_hits", "host_tier_utilization"):
            assert key in r, key
        assert 0.0 <= r["host_tier_utilization"] <= 1.0
    assert decs[-1]["restores"] > 0             # the smoke's pin
    assert decs[-1]["spill_bytes"] > 0
    # a decode record missing a v17 key is rejected by the contract
    bad = {k: v for k, v in decs[-1].items() if k != "restores"}
    ok, reason = validate_record(bad)
    assert not ok and "restores" in reason
    assert DECODE_REQUIRED[-7:] == (
        "spilled_blocks", "spill_bytes", "restores",
        "restore_tokens_saved", "restore_stall_s", "partial_hits",
        "host_tier_utilization")


# ---------------------------------------------------------------------------
# reliability composition


def test_poisoned_block_never_spills(lm_params, sessions):
    """A chaos-corrupted refs-0 cached block reached by the demotion
    sweep is detached and scrubbed — the tier only ever stores bytes
    the purity argument certifies."""
    eng = _churn(lm_params, dict(SMALL, kv_dtype="f32",
                                 spill_blocks=32), sessions, returns=1)
    # corrupt one resident cached block, then force a demotion sweep
    # big enough to reach every evictable node
    cached = [b for b in eng.prefix._by_block if b != 0]
    assert cached
    victim = cached[0]
    eng.corrupt_block(victim)
    assert victim in eng._corrupted
    spilled_before = eng.spilled_blocks
    eng._reclaim_cached(len(cached))
    # the corrupt block was freed (scrubbed), never admitted to host
    assert victim in eng.free_blocks
    assert victim not in eng._corrupted
    docs = [eng.spill._nodes[s] for s in eng.spill._store]
    assert all(n.block == -1 for n in docs)
    assert eng.spilled_blocks > spilled_before  # clean peers DID spill
    # tier holds only clean entries: every restore must CRC-verify
    for sid in list(eng.spill._store):
        eng.spill.take(sid)                     # no WireError


def test_corrupt_spill_quarantines_restoring_request(lm_params,
                                                     sessions):
    """One flipped host-RAM byte -> exactly the restoring request is
    quarantined (retried clean under budget), survivors bit-identical;
    the damaged edge leaves the tree so the retry re-prefills it."""
    oracle = _churn(lm_params, dict(BIG, kv_dtype="f32"), sessions,
                    returns=1)
    eng = _churn(lm_params, dict(SMALL, kv_dtype="f32",
                                 spill_blocks=32), sessions, returns=1,
                 policy=ServePolicy(max_retries=1))
    sids = sorted(eng.spill._store)
    assert sids, "round 1 left nothing spilled — the drill is vacuous"
    assert eng.corrupt_spill(sids[0])
    for p in sessions:
        eng.submit(p, 6)
    eng.run()
    assert eng.quarantined == 1 and eng.retried == 1
    assert not eng.failed                       # retry succeeded
    assert len(eng.finished) == 2 * len(sessions)
    for uid, toks in eng.finished.items():
        assert toks == oracle.finished[uid % len(sessions)], uid
    # without retry budget the same damage is a clean failure naming
    # the reason (the quarantine-before-slot path)
    eng2 = _churn(lm_params, dict(SMALL, kv_dtype="f32",
                                  spill_blocks=32), sessions, returns=1)
    sids2 = sorted(eng2.spill._store)
    assert eng2.corrupt_spill(sids2[0])
    for p in sessions:
        eng2.submit(p, 6)
    eng2.run()
    assert eng2.quarantined == 1
    assert len(eng2.failed) == 1
    assert next(iter(eng2.failed.values()))["reason"] == "corrupt_spill"
    for uid, toks in eng2.finished.items():
        assert toks == oracle.finished[uid % len(sessions)], uid


def test_corrupt_spill_chaos_kind_via_supervisor(lm_params, sessions,
                                                 tmp_path):
    """The ``corrupt_spill@STEP:ID`` chaos kind end to end: the
    supervisor flips the byte before the step, the restore CRC-fails,
    the request quarantines-and-retries, and the drained outcome is
    byte-identical to the no-chaos run."""
    cfg_kw = dict(SMALL, kv_dtype="f32", spill_blocks=32)
    reqs = [(p, 6) for p in sessions] * 2
    clean = supervise_decode(
        lambda: DecodeEngine(lm_params, H, EngineConfig(**cfg_kw),
                             policy=ServePolicy(max_retries=1)),
        reqs, snapshot_dir=str(tmp_path / "clean"))
    plan = FaultPlan.parse("corrupt_spill@2:0")
    eng = supervise_decode(
        lambda: DecodeEngine(lm_params, H, EngineConfig(**cfg_kw),
                             policy=ServePolicy(max_retries=1)),
        reqs, snapshot_dir=str(tmp_path / "chaos"), chaos=plan)
    assert not eng.failed
    assert eng.finished == clean.finished
    # the fault either found its entry (quarantine observed) or fired
    # before anything spilled (hit: false noted) — both are recorded
    assert plan.faults[0].fired


def test_kill_resume_rebuilds_share_graph_with_empty_tier(
        lm_params, sessions, tmp_path):
    """SIGKILL mid-churn: the snapshot (v9) records spill counters and
    the tree's spilled flags, the host tier's BYTES die with the
    process, and the resumed replay rebuilds the share graph from
    re-prefills — byte-identical outcome, empty tier at restore."""
    cfg_kw = dict(SMALL, kv_dtype="f32", spill_blocks=32)
    reqs = [(p, 6) for p in sessions] * 2
    clean = supervise_decode(
        lambda: DecodeEngine(lm_params, H, EngineConfig(**cfg_kw)),
        reqs, snapshot_dir=str(tmp_path / "clean"))

    # in-process twin of the SIGKILL: drive churn until blocks spilled,
    # snapshot, then restore into a FRESH engine (the dead process's
    # tier is unreachable by construction)
    eng = DecodeEngine(lm_params, H, EngineConfig(**cfg_kw))
    for p, n in reqs:
        eng.submit(p, n)
    while not eng.spilled_blocks and (eng.waiting or eng.active):
        eng.step()
    assert eng.spilled_blocks > 0
    write_snapshot(eng, str(tmp_path / "kill"))
    snap = load_snapshot(str(tmp_path / "kill"))
    assert snap["version"] == 9
    assert snap["counters"]["spilled_blocks"] == eng.spilled_blocks
    assert "restore_stall_s" in snap["counters"]
    # the persisted tree records WHICH nodes were spilled (shape only)
    spilled_nodes = [n for n in snap["prefix_tree"] if n["spilled"]]
    assert len(spilled_nodes) == len(eng.spill)

    resumed = supervise_decode(
        lambda: DecodeEngine(lm_params, H, EngineConfig(**cfg_kw)),
        [], snapshot_dir=str(tmp_path / "kill"))
    assert resumed.finished == clean.finished
    # counters survived monotonically; the tier started empty
    assert resumed.spilled_blocks >= eng.spilled_blocks


# ---------------------------------------------------------------------------
# sub-block prefix sharing


@pytest.fixture(scope="module")
def short_shared():
    """Three prompts sharing a 6-token head (1 full 4-block + 2 rows
    into the next) and diverging after it — whole-block matching alone
    shares only the first block."""
    rng = np.random.default_rng(11)
    head = rng.integers(0, V, size=6).tolist()
    return [head + [t, t + 1, t + 2] for t in (1, 5, 9)]


def _staggered(params, cfg_kw, prompts, max_new=6):
    eng = DecodeEngine(params, H, EngineConfig(**cfg_kw))
    for p in prompts:
        eng.submit(p, max_new)
        for _ in range(4):
            eng.step()
    eng.run()
    return eng


@pytest.mark.parametrize("kv_dtype", ["f32", "bf16"])
def test_partial_hit_exact_f32_bf16(lm_params, short_shared, kv_dtype):
    base = _staggered(lm_params, dict(BIG, kv_dtype=kv_dtype),
                      short_shared)
    eng = _staggered(lm_params, dict(BIG, kv_dtype=kv_dtype,
                                     prefix_partial=True), short_shared)
    assert eng.partial_hits >= 1
    assert eng.prefill_tokens_saved > base.prefill_tokens_saved
    assert eng.finished == base.finished        # row purity: bit-equal


def test_partial_hit_int8_deterministic(lm_params, short_shared):
    """int8 partial shares reuse the donor's FROZEN per-block scale —
    deterministic (same engine config twice -> same tokens), though
    not pinned bit-equal to the unshared engine (DESIGN.md section 29
    documents the trade)."""
    a = _staggered(lm_params, dict(BIG, kv_dtype="int8",
                                   prefix_partial=True), short_shared)
    b = _staggered(lm_params, dict(BIG, kv_dtype="int8",
                                   prefix_partial=True), short_shared)
    assert a.partial_hits >= 1
    assert a.finished == b.finished


def test_partial_hit_prefill_clock_starts_past_copied_rows(
        lm_params, short_shared):
    """The copied rows never re-prefill: saved tokens grow by exactly
    the partial rows the CoW copy covered."""
    base = _staggered(lm_params, dict(BIG, kv_dtype="f32"),
                      short_shared)
    eng = _staggered(lm_params, dict(BIG, kv_dtype="f32",
                                     prefix_partial=True), short_shared)
    extra = eng.prefill_tokens_saved - base.prefill_tokens_saved
    # 2 later sharers x 2 shared rows past the full block
    assert extra == eng.partial_hits * 2


def test_partial_off_by_default(lm_params, short_shared):
    eng = _staggered(lm_params, dict(BIG, kv_dtype="f32"),
                     short_shared)
    assert eng.partial_hits == 0


# ---------------------------------------------------------------------------
# CLI surface


def test_generate_cli_spill_restores_match_big_pool(tmp_path, capsys):
    """The session-churn drill through `generate`: four 9-token
    sessions returning three times through an 11-block pool with
    `--spill_blocks 32` emit the tokens of a 64-block pool that never
    evicts, report restores, and leave a stream that `report --audit`
    holds."""
    import json

    import distributed_llm_code_samples_tpu.cli as cli
    from distributed_llm_code_samples_tpu.runtime.telemetry import (
        METRICS_FILENAME, read_metrics)
    ret = ("1,2,3,4,5,6,7,8,9;9,8,7,6,5,4,3,2,1;"
           "11,12,13,14,15,16,17,18,19;21,22,23,24,25,26,27,28,29")
    args = ["generate", "--prompts", ";".join([ret] * 3), "--max_new",
            "6", "-d", "32", "-l", "2", "--heads", "4", "--vocab", "64",
            "--max_seq_len", "64", "--block_size", "4",
            "--prefill_chunk", "4", "--max_slots", "2",
            "--max_blocks_per_seq", "8", "--log_every", "2"]
    mdir = str(tmp_path / "metrics")
    assert cli.main(args + ["--n_blocks", "64"]) == 0
    oracle = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert cli.main(args + ["--n_blocks", "11", "--spill_blocks", "32",
                            "--metrics_dir", mdir]) == 0
    spill = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert ({s["uid"]: s["tokens"] for s in spill["sequences"]}
            == {s["uid"]: s["tokens"] for s in oracle["sequences"]})
    assert spill["spilled_blocks"] >= spill["restores"] > 0
    assert spill["restore_tokens_saved"] > 0
    records, problems = read_metrics(os.path.join(mdir,
                                                  METRICS_FILENAME))
    assert problems == []
    assert any(r["kind"] == "decode" and r["restores"] > 0
               for r in records)
    assert cli.main(["report", mdir, "--audit"]) == 0
    capsys.readouterr()
