"""Speculative decoding (ISSUE 8): n-gram drafting, greedy verify,
token identity at every KV dtype, composition with the reliability
machinery (quarantine rollback, snapshot-resume re-drafting), the
steady-state compile surface, and the schema-v6 speculation telemetry.

The identity bar: a ``speculate=k`` engine's output is BIT-IDENTICAL
to the non-speculative engine's for staggered continuous-batch prompts
at f32, bf16, AND int8 — the verify program's acceptance-masked KV
writes land exactly the rows the plain engine would have written, so
even int8's cross-row requant history matches (decode/engine.py
``_verify_fn``). Drafts are a pure function of ``prompt + out``
(decode/draft.py), so every replay path re-drafts identically.

Model shapes deliberately match tests/test_decode_engine.py (same
params seed, same BASE config) so the compiled programs land in the
same XLA cache entries.
"""

import os

import jax
import numpy as np
import pytest

from distributed_llm_code_samples_tpu.decode import (
    DecodeEngine, EngineConfig, ServePolicy, draft_tokens,
    load_snapshot, restore_engine_state, supervise_decode,
    write_snapshot)
from distributed_llm_code_samples_tpu.models import generate, init_lm
from distributed_llm_code_samples_tpu.runtime.chaos import FaultPlan

V, D, L, H = 64, 32, 2, 4
BASE = dict(block_size=8, n_blocks=33, max_slots=3, max_blocks_per_seq=6,
            prefill_chunk=8)
KV_DTYPES = ("f32", "bf16", "int8")


@pytest.fixture(scope="module")
def lm_params():
    return init_lm(jax.random.PRNGKey(0), V, D, L, max_seq_len=64)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(1)
    return [rng.integers(0, V, size=n).tolist() for n in (5, 9, 13)]


def _staggered(params, cfg, prompts, max_new=12, mesh=None):
    """The staggered continuous-batching pattern the identity proofs
    use: two prompts up front, three steps, then a late admission."""
    eng = DecodeEngine(params, H, cfg, mesh=mesh)
    eng.submit(prompts[0], max_new, uid=0)
    eng.submit(prompts[1], max_new, uid=1)
    for _ in range(3):
        eng.step()
    eng.submit(prompts[2], max_new, uid=2)
    return eng, eng.run()


# ---------------------------------------------------------------------------
# drafter units (pure-function contract)


def test_draft_tokens_ngram_lookup():
    # trigram suffix [3,1,2] never recurs; bigram [1,2] does — copy
    # what followed its most recent earlier occurrence
    assert draft_tokens([1, 2, 3, 1, 2], 3) == [3, 1, 2]
    # constant attractor (what greedy decode on random weights does):
    # the longest, most recent match ends one short of the history, so
    # the copy is one token — the next step re-drafts, so loops still
    # verify at full width over time
    assert draft_tokens([7, 9, 9, 9], 4) == [9]
    # a longer copy when the match sits further back
    assert draft_tokens([1, 2, 3, 4, 1, 2], 3) == [3, 4, 1]
    # recency wins over earlier occurrences
    assert draft_tokens([1, 2, 5, 1, 2, 6, 1, 2], 2) == [6, 1]
    # no history repeat -> no draft; degenerate inputs -> no draft
    assert draft_tokens([1, 2, 3, 4], 3) == []
    assert draft_tokens([5], 3) == []
    assert draft_tokens([1, 2, 1], 0) == []
    # pure function: same history, same drafts
    h = [3, 1, 4, 1, 5, 1, 4]
    assert draft_tokens(h, 3) == draft_tokens(list(h), 3)


def test_speculate_validation(lm_params):
    with pytest.raises(ValueError, match="greedily"):
        DecodeEngine(lm_params, H,
                     EngineConfig(**BASE, temperature=0.9, speculate=2))
    with pytest.raises(ValueError, match="speculate"):
        DecodeEngine(lm_params, H, EngineConfig(**BASE, speculate=-1))


# ---------------------------------------------------------------------------
# token identity (the acceptance bar)


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_spec_matches_nonspec_engine(lm_params, prompts, kv_dtype):
    """Acceptance: speculative greedy output == non-speculative engine
    output for staggered continuous-batch prompts, per KV dtype —
    int8 included, because acceptance-masked writes reproduce the
    exact per-row requant history."""
    _, base = _staggered(lm_params, EngineConfig(**BASE,
                                                 kv_dtype=kv_dtype),
                         prompts)
    eng, spec = _staggered(lm_params,
                           EngineConfig(**BASE, kv_dtype=kv_dtype,
                                        speculate=3), prompts)
    assert spec == base
    # the drafter actually worked: multi-token steps happened
    assert eng.drafted_tokens > 0 and eng.accepted_tokens > 0
    assert eng.tokens_generated > eng.steps


def test_spec_matches_lockstep_reference(lm_params, prompts):
    """Transitivity check straight to the repo's oldest oracle: the
    speculative engine equals ``models.lm.generate`` per sequence."""
    eng = DecodeEngine(lm_params, H, EngineConfig(**BASE, speculate=4))
    outs = eng.generate(prompts, 8)
    for p, out in zip(prompts, outs):
        ref = np.asarray(generate(lm_params, jax.numpy.asarray([p]), 8,
                                  H))[0].tolist()
        assert out == ref


def test_spec_exact_fit_and_short_requests(lm_params):
    """The draft budget cap: a request whose remaining budget is
    smaller than ``speculate`` must not overrun ``max_new`` or its
    block reservation — including the exact-capacity-fit request and
    a one-token request (budget 0: the verify step degenerates to a
    plain decode step inside the same program)."""
    base_cfg = EngineConfig(**BASE)
    spec_cfg = EngineConfig(**BASE, speculate=4)
    for prompt, max_new in ([1] * 40, 9), ([2, 3], 1), ([4] * 6, 3):
        want = DecodeEngine(lm_params, H, base_cfg).generate([prompt],
                                                             max_new)
        got = DecodeEngine(lm_params, H, spec_cfg).generate([prompt],
                                                            max_new)
        assert got == want
        assert len(got[0]) == len(prompt) + max_new


# ---------------------------------------------------------------------------
# compile surface


def test_spec_zero_new_compiles_steady_state(lm_params):
    """Speculation on: the program set is still bounded by the bucket
    count (verify replaces decode one-for-one) and stops growing after
    the first wave — steady state stays dispatch-only."""
    eng = DecodeEngine(lm_params, H, EngineConfig(**BASE, speculate=3))
    rng = np.random.default_rng(5)
    first = [rng.integers(0, V, size=n).tolist()
             for n in (1, 2, 3, 5, 8, 13)]
    eng.generate(first, 5)
    warm = eng.compile_count
    dispatches = eng.dispatch_count
    more = [rng.integers(0, V, size=n).tolist() for n in (4, 7, 11, 2)]
    eng.generate(more, 7)
    assert eng.compile_count == warm            # zero new compiles
    assert eng.dispatch_count > dispatches


# ---------------------------------------------------------------------------
# composition with the reliability machinery


def test_spec_quarantine_rolls_back_drafted_tail(tmp_path, lm_params,
                                                 prompts):
    """nan_logits under speculation: the poisoned uid's whole verify
    step — drafted tail included — is rolled back (nothing emitted,
    nothing kept in the pool), survivors are bit-identical to a clean
    run, and the retry recovers the clean tokens (the reliability
    suite's contract, now with multi-token steps)."""
    clean = {}
    for i, p in enumerate(prompts):
        e = DecodeEngine(lm_params, H, EngineConfig(**BASE, speculate=3))
        e.submit(p, 8, uid=i)
        clean.update(e.run())
    plan = FaultPlan.parse("nan_logits@4:1")
    eng = supervise_decode(
        lambda: DecodeEngine(lm_params, H,
                             EngineConfig(**BASE, speculate=3),
                             policy=ServePolicy(max_retries=1)),
        [(p, 8) for p in prompts], snapshot_dir=str(tmp_path / "s"),
        chaos=plan)
    assert eng.failed == {}
    assert dict(eng.finished) == clean
    assert eng.quarantined == 1 and eng.retried == 1
    events = [(e["event"], e["uid"]) for e in eng.request_events]
    assert ("quarantined", 1) in events and ("retried", 1) in events


def test_spec_quarantine_without_retry_fails_only_poisoned(
        lm_params, prompts):
    """No retry budget: exactly the poisoned uid fails, its ``out`` is
    rolled back whole (no token from the poisoned verify step leaks),
    and survivors still match a run that never admitted it."""
    cfg = EngineConfig(**BASE, speculate=3)
    oracle = {}
    for i in (0, 2):
        e = DecodeEngine(lm_params, H, cfg)
        e.submit(prompts[i], 8, uid=i)
        oracle.update(e.run())
    eng = DecodeEngine(lm_params, H, cfg)
    for i, p in enumerate(prompts):
        eng.submit(p, 8, uid=i)
    for step in range(1, 5):
        if step == 4:
            eng.arm_poison(1)
        assert eng.step()
    assert set(eng.failed) == {1}
    assert eng.failed[1]["reason"] == "nonfinite_logits"
    done = eng.run()
    assert done[0] == oracle[0] and done[2] == oracle[2]


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_spec_snapshot_resume_re_drafts_identically(tmp_path, lm_params,
                                                    prompts, kv_dtype):
    """Kill -> resume under speculation, per KV dtype: a fresh engine
    restored mid-flight replays the recorded tokens (teacher-forced as
    drafts, all accepted) and then RE-DRAFTS the live continuation
    identically — drafter state derives only from emitted tokens, so
    the resumed run's output is bit-identical to the uninterrupted
    one's."""
    cfg = EngineConfig(**BASE, kv_dtype=kv_dtype, speculate=3)
    oracle = DecodeEngine(lm_params, H, cfg)
    for i, p in enumerate(prompts):
        oracle.submit(p, 10, uid=i)
    want = oracle.run()
    eng = DecodeEngine(lm_params, H, cfg)
    for i, p in enumerate(prompts):
        eng.submit(p, 10, uid=i)
    snap_dir = str(tmp_path / "snap")
    for _ in range(5):                    # die mid-flight
        assert eng.step()
        write_snapshot(eng, snap_dir)
    eng2 = DecodeEngine(lm_params, H, cfg)
    restore_engine_state(eng2, load_snapshot(snap_dir))
    assert eng2.step_base == 5
    done = eng2.run()
    merged = {**eng.finished, **done}     # pre-crash completions count
    assert merged == want
    # counters restored monotonic (the snapshot-v3 pair)
    assert eng2.drafted_tokens >= eng.drafted_tokens


def test_spec_preemption_token_identity(lm_params, prompts):
    """Pool pressure + speculation: eviction/replay churn cannot move
    a token (replay re-drafts from the recorded continuation)."""
    full = DecodeEngine(lm_params, H, EngineConfig(**BASE, speculate=3))
    want = full.generate(prompts, 8)
    tight = DecodeEngine(
        lm_params, H,
        EngineConfig(**{**BASE, "n_blocks": 9}, speculate=3),
        policy=ServePolicy(preempt_after_steps=2))
    got = tight.generate(prompts, 8)
    assert got == want


def test_spec_tp_matches_single(lm_params, prompts, mesh_model4):
    """Speculation under Megatron TP: the verify program shard_maps
    like the decode program (drafts/dlens replicated), picks gather
    identically on every shard."""
    outs = DecodeEngine(lm_params, H, EngineConfig(**BASE, speculate=3),
                        mesh=mesh_model4).generate(prompts, 6)
    ref = DecodeEngine(lm_params, H,
                       EngineConfig(**BASE,
                                    speculate=3)).generate(prompts, 6)
    assert outs == ref


# ---------------------------------------------------------------------------
# telemetry (schema v6)


def test_spec_decode_records_schema_v6(lm_params, prompts, tmp_path):
    from distributed_llm_code_samples_tpu.runtime.telemetry import (
        METRICS_FILENAME, TelemetryWriter, read_metrics,
        validate_record)
    mdir = str(tmp_path / "metrics")
    with TelemetryWriter(mdir, meta={"subcommand": "generate"}) as w:
        eng = DecodeEngine(lm_params, H,
                           EngineConfig(**BASE, speculate=3))
        eng.generate(prompts, 12, metrics=w, log_every=2)
    records, problems = read_metrics(os.path.join(mdir,
                                                  METRICS_FILENAME))
    assert problems == []
    decs = [r for r in records if r["kind"] == "decode"]
    assert decs and all(validate_record(r)[0] for r in decs)
    last = decs[-1]
    assert last["drafted_tokens"] == eng.drafted_tokens > 0
    assert last["accepted_tokens"] == eng.accepted_tokens > 0
    assert 0.0 <= last["accept_rate"] <= 1.0
    # the raw-latency claim as recorded data: tokens-per-step > 1
    assert last["tokens_generated"] > last["step"]
    # decode-segment spans carry their token counts (multi-token steps)
    spans = [r for r in records if r["kind"] == "span"
             and r["span"] == "decode"]
    assert spans and any(s.get("tokens", 0) > 1 for s in spans)
    # speculation off -> the contract keys still present, rate null
    eng0 = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    rec = eng0.telemetry_record()
    assert rec["drafted_tokens"] == 0 and rec["accept_rate"] is None


# ---------------------------------------------------------------------------
# CLI surface


def test_generate_cli_speculate_matches_plain(tmp_path, capsys):
    """`generate --speculate 4` against the flag-free run of the same
    prompts: byte-identical tokens in fewer engine steps, and a metrics
    stream whose decode records count more tokens than steps."""
    import json

    import distributed_llm_code_samples_tpu.cli as cli
    from distributed_llm_code_samples_tpu.runtime.telemetry import (
        METRICS_FILENAME, read_metrics)
    args = ["generate", "--prompt_lens", "3,7", "--max_new", "24", "-d",
            "32", "-l", "2", "--heads", "4", "--vocab", "64",
            "--max_seq_len", "64", "--block_size", "8",
            "--prefill_chunk", "4", "--log_every", "4"]
    mdir = str(tmp_path / "metrics")
    assert cli.main(args) == 0
    plain = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert cli.main(args + ["--speculate", "4",
                            "--metrics_dir", mdir]) == 0
    spec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert ({s["uid"]: s["tokens"] for s in spec["sequences"]}
            == {s["uid"]: s["tokens"] for s in plain["sequences"]})
    assert spec["engine_steps"] < plain["engine_steps"]
    records, problems = read_metrics(os.path.join(mdir,
                                                  METRICS_FILENAME))
    assert problems == []
    decs = [r for r in records if r["kind"] == "decode"]
    assert decs[-1]["accepted_tokens"] > 0
    assert decs[-1]["tokens_generated"] == 2 * 24 > decs[-1]["step"]
