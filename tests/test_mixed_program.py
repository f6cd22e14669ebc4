"""A prompt's chunk rides with the decode batch (ISSUE 36): the fourth
step program, ``mixed`` (``decode/programs.py::_mixed_fn``), and the
step's choice of it (``decode/engine.py::_mixed_batch``).

The three older bodies are the oracle. Every comparison below serves
the same traffic twice: once as the engine runs it, once with the
step's choice patched out HERE (``_mixed_batch`` returning no riders:
the program has no switch), which is the parent's two-program step. Toy
engines of the four served families, float32: the GPT-2 block, the
hybrid (Mamba + attention), latent attention + experts, gated
convolutions + GQA + experts.
"""

import jax
import numpy as np
import pytest

from distributed_llm_code_samples_tpu.decode import (DecodeEngine,
                                                     EngineConfig)
from distributed_llm_code_samples_tpu.decode.model_config import (
    engine_from_config)
from distributed_llm_code_samples_tpu.models import init_lm

from test_lfm2_moe_lm import TOY as TOY_CONV_MOE

FAMILIES = ["gpt2", "hybrid", "latent", "conv_moe"]
V, CHUNK = 96, 16
BASE = dict(block_size=16, n_blocks=1 + 4 * 8, max_slots=4,
            max_blocks_per_seq=8, prefill_chunk=CHUNK)


class Collector:
    """A writer that keeps ``engine_step`` records in memory."""

    def __init__(self):
        self.steps, self.path = [], None

    def span(self, rec):
        if rec["span"] == "engine_step":
            self.steps.append(rec)

    def __getattr__(self, _name):
        return lambda *a, **k: None


@pytest.fixture
def engine(toy_hybrid_config, toy_latent_config, monkeypatch):
    """``(family, ride=True, **cfg) -> engine``; ``ride=False`` patches
    the step's choice out, in the test."""
    configs = {"hybrid": toy_hybrid_config, "latent": toy_latent_config,
               "conv_moe": TOY_CONV_MOE}

    def build(family, ride=True, metrics=None, **over):
        cfg = EngineConfig(**dict(BASE, **over))
        if family == "gpt2":
            eng = DecodeEngine(
                init_lm(jax.random.PRNGKey(0), V, 32, 2, 128, n_heads=4),
                4, cfg, metrics=metrics)
        else:
            eng = engine_from_config(dict(configs[family]), seed=1,
                                     engine_config=cfg)
            eng.metrics = metrics
        if not ride:
            monkeypatch.setattr(eng, "_mixed_batch", lambda pre, only: [])
        return eng

    return build


def backlog(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, V, n).tolist() for n in lens]


def kinds_built(eng):
    return {kind for kind, _ in eng._programs}


# full chunks behind ready slots, tails, a prompt shorter than a chunk,
# one of exactly a chunk, more requests than slots
LENS = (37, 5, 53, 16, 32, 70, 21, 48)


@pytest.mark.parametrize("family", FAMILIES)
def test_same_tokens_with_the_ride_and_without(engine, family):
    """Every request's tokens are what the two-program step serves: the
    picks are keyed on ``(uid, position)``, and a row's arithmetic does
    not know which rows it shares a program with."""
    prompts = backlog(LENS)
    rode, split = engine(family), engine(family, ride=False)
    assert rode.generate(prompts, 12) == split.generate(prompts, 12)
    assert rode.mixed_dispatches > 0 and "mixed" in kinds_built(rode)
    assert split.mixed_dispatches == 0 and "mixed" not in kinds_built(split)
    # one chunk a step either way, and each ride is a dispatch saved (a
    # prompt whose LAST chunk rode decodes a step later: at most one
    # more batch dispatch each, at the backlog's end)
    assert rode.prefill_dispatches == split.prefill_dispatches
    assert (split.dispatch_count - rode.dispatch_count
            >= rode.mixed_dispatches - len(prompts))


def _mid_flight(eng, ready_lens=(5, 9, 3), chunk_len=2 * CHUNK + 5):
    """An engine with three ready slots a few tokens into decoding and a
    fourth whose next chunk is a full one; returns ``(slot, ready)``."""
    for p in backlog(ready_lens, seed=3):
        eng.submit(p, 30)
    for _ in range(8):
        eng.step()
    eng.submit(backlog((chunk_len,), seed=4)[0], 8)
    eng._admit()
    slot = next(i for i, s in enumerate(eng.slots)
                if s is not None and not s.prompt_done)
    ready = eng._mixed_batch(slot, False)
    assert len(ready) == 3
    return slot, ready


@pytest.mark.parametrize("family", FAMILIES)
def test_mixed_program_is_the_two_programs_on_the_same_rows(engine, family):
    """One program against two on ONE cache state and the same rows:
    the batch's picks and the chunk's last row's are the same, the
    cache holds the same values afterwards, and an expert model's
    counters are the SUM of the two programs' (a row is routed the same
    whichever program carries it)."""
    eng = engine(family)
    slot, ready = _mid_flight(eng)
    seq, progs = eng.slots[slot], eng.programs
    b, *batch = eng._marshal(ready, eng.slot_buckets[-1])
    batch_f = eng._batch_fields(ready, b, *batch)
    cache, params = eng._carry(), eng.params

    def run(kind, bucket, cache, **fields):     # not donated: cache kept
        out, result = jax.jit(progs.body(kind, bucket))(
            params, cache, progs.pack(kind, bucket, **fields))
        return out, progs.split(kind, np.asarray(result))

    both, (picks, rows) = run(
        "mixed", b, cache, **batch_f,
        **eng._chunk_fields(slot, seq, CHUNK, "chunk"))
    after, (pre_pick, pre_rows) = run(
        "prefill", CHUNK, cache, poison=batch_f["poison"],
        **eng._chunk_fields(slot, seq, CHUNK, "tokens"))
    after, (dec_picks, dec_rows) = run("decode", b, after, **batch_f)
    assert picks.shape == (b + 1,)
    assert picks[:b].tolist() == dec_picks.tolist()
    assert picks[-1] == pre_pick[0]
    for got, want in zip(jax.tree_util.tree_leaves(both),
                         jax.tree_util.tree_leaves(after)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=1e-5, atol=1e-5)
    if eng.spec.expert_layers:
        assert rows.shape == (eng.spec.expert_layers, eng.spec.n_experts)
        assert (rows == pre_rows + dec_rows).all()
        assert rows.sum() == (b + CHUNK) * 4 * eng.spec.expert_layers
    else:
        assert rows is None


@pytest.mark.parametrize("family", FAMILIES)
def test_a_long_prompt_rides_its_full_chunks_and_walks_its_tails(engine,
                                                                  family):
    """A prompt of ``16 k + 5`` tokens behind a ready slot: ``k`` mixed
    dispatches (the ONE mixed program: the batch of one padded to the
    largest slot bucket), then its tails (4, 1) through the prefill
    program and the batch's decode as before. No 16-token prefill
    program is ever built here: the only full chunks rode."""
    k = 3
    eng = engine(family)
    first, long = backlog((5, CHUNK * k + 5), seed=5)
    eng.submit(first, 40)
    for _ in range(3):
        eng.step()
    before = (eng.mixed_dispatches, eng.prefill_dispatches)
    uid = eng.submit(long, 4)
    eng.run()
    assert eng.mixed_dispatches - before[0] == k
    assert eng.prefill_dispatches - before[1] == k + 2
    assert set(eng._programs) >= {("mixed", 4), ("prefill", 4),
                                  ("prefill", 1)}
    assert ("prefill", CHUNK) not in eng._programs
    assert [k for k in eng._programs if k[0] == "mixed"] == [("mixed", 4)]
    assert len(eng.finished[uid]) == len(long) + 4


def _speculating(engine):
    eng = engine("gpt2", speculate=2)
    return eng, lambda: eng.generate(backlog(LENS), 10)


def _prefill_tier(engine):
    eng = engine("gpt2")

    def serve():
        for p in backlog((5, 40, 33)):
            eng.submit(p, 6)
        while eng.step(prefill_only=True):
            pass
        assert all(s is None or s.prompt_done for s in eng.slots)
    return eng, serve


def _alone(engine):
    eng = engine("hybrid")
    # one request at a time: its chunks never find a ready slot
    return eng, lambda: [eng.generate([p], 6) for p in backlog((40, 33))]


def _two_versions(engine):
    eng = engine("gpt2")

    def serve():
        eng.submit(backlog((5,))[0], 60)
        for _ in range(4):
            eng.step()
        eng.load_weights(1, init_lm(jax.random.PRNGKey(9), V, 32, 2, 128,
                                    n_heads=4))
        eng.set_serving_version(1)
        # the ready slot is pinned to version 0, the chunks to version 1
        uid = eng.submit(backlog((3 * CHUNK,), seed=6)[0], 4)
        while uid not in eng.finished:
            assert eng.step()
        assert eng.slots[0] is not None     # ... and was ready throughout
    return eng, serve


@pytest.mark.parametrize("case", [_speculating, _prefill_tier, _alone,
                                  _two_versions])
def test_what_cannot_ride_never_builds_the_program(engine, case):
    """Speculation, the fleet's prefill tier, a chunk with no ready slot
    and a chunk on another weights version than a ready slot all run as
    before: full chunks through ``prefill``, and ``mixed`` is neither
    dispatched nor built."""
    eng, serve = case(engine)
    serve()
    assert ("prefill", CHUNK) in eng._programs
    assert "mixed" not in kinds_built(eng) and eng.mixed_dispatches == 0


MIXED_LAUNCH = ["prefill.cow", "decode.cow", "decode.marshal",
                "mixed.upload", "mixed.dispatch"]
MIXED_LAND = ["mixed.readback", "prefill.book", "decode.emit"]


@pytest.mark.parametrize("family", FAMILIES)
def test_a_mixed_step_is_one_dispatch_that_carried_a_chunk(engine, family):
    """The accounting of a step that rides: ONE dispatch
    (``dispatch_count``), which carried a chunk (``prefill_dispatches``)
    in the mixed program (``mixed_dispatches``), so a reader that takes
    ``dispatches - prefill dispatches`` for the decode count reads 0;
    the record's phases are both dispatches' host phases round one
    ``mixed.*`` launch, and the NEXT step's hold its wait and what
    follows it (the read is a step late: ``tests/test_late_read.py``);
    the state bytes are the batch's rows', the experts' counters the
    whole program's, in the record of the step that read them; and the
    counter is in the telemetry record and the flight digest."""
    sink = Collector()
    eng = engine(family, metrics=sink)
    _mid_flight(eng)
    eng.collect()
    before = (eng.dispatch_count, eng.prefill_dispatches,
              eng.mixed_dispatches, eng.compile_count)
    assert eng.step()
    assert (eng.dispatch_count - before[0], eng.prefill_dispatches
            - before[1], eng.mixed_dispatches - before[2]) == (1, 1, 1)
    assert eng.compile_count - before[3] == 1       # mixed(4): the one
    rec = sink.steps[-1]
    assert [p[0] for p in rec["phases"]] == (
        ["expire", "admit", "decode.marshal"] + MIXED_LAUNCH
        + ["decode.marshal", "digest"])
    assert rec["dispatches"] == [["mixed", 4]] and rec["readbacks"] == []
    digest = eng.flight[-1]
    assert digest["mixed_dispatches"] == eng.mixed_dispatches
    assert digest["prefill_uid"] is not None
    assert len(digest["decode_uids"]) == 3 and digest["finite"] is None
    assert {"mixed.upload", "mixed.dispatch"} <= set(digest["phase_ms"])
    want_state = 3 * eng.state.bytes_per_slot if eng.state is not None else 0
    assert rec["state_bytes"] == digest["state_bytes"] == want_state
    assert rec["expert_rows"] == 0                  # nothing read yet
    # the next step is the same prompt's second full chunk: no build;
    # it reads the first one's result once its own program is launched
    assert eng.step() and eng.compile_count - before[3] == 1
    assert eng.mixed_dispatches - before[2] == 2
    nxt = sink.steps[-1]
    assert [p[0] for p in nxt["phases"]] == (
        ["expire", "admit", "decode.marshal"] + MIXED_LAUNCH + MIXED_LAND
        + ["decode.marshal", "digest"])
    assert nxt["readbacks"] == [rec["launches"] - 1]
    assert len(digest["finite"]) == 4 and all(digest["finite"])
    # a bucket's padded row routes too: 4 rows + the chunk's 16
    assert nxt["expert_rows"] == (4 + CHUNK) * 4 * eng.spec.expert_layers
    assert eng.telemetry_record()["mixed_dispatches"] == eng.mixed_dispatches


@pytest.mark.parametrize("family", FAMILIES)
def test_the_benchmarks_warm_up_reaches_every_program(engine, family):
    """After ``benchmark/serve.py::warm``'s traffic (a 31-token prompt a
    slot, admitted while the ready count climbs), a backlog of other
    lengths builds no program and adds no entry to any program's jit
    cache: the one ``mixed`` program was reached on the second
    request's first chunk, ``prefill(c)`` and ``decode(b)`` on the
    tails. One program more than the two-program engine's set."""
    from benchmark import serve
    eng = engine(family, max_slots=8, n_blocks=1 + 8 * 8)
    serve.warm(eng)
    built = eng.compile_count
    assert {k for k in eng._programs if k[0] in ("decode", "mixed")} == {
        ("decode", 1), ("decode", 2), ("decode", 4), ("decode", 8),
        ("mixed", 8)}
    assert {c for kind, c in eng._programs if kind == "prefill"} == {
        1, 2, 4, 8, 16}

    def entries():
        return {k: fn._cache_size() for k, fn in eng._programs.items()}

    before = entries()
    assert all(n == 1 for n in before.values())
    out = eng.generate(backlog(LENS * 2, seed=8), 9)
    assert len(out) == 2 * len(LENS) and eng.mixed_dispatches > 8
    assert eng.compile_count == built and entries() == before
    # engine.warm() names the same set (and the implant program)
    assert eng.warm() == built + 1
