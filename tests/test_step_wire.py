"""The wire format of a step program (ISSUE 30): ``run(params, cache,
operand) -> (cache, result)``, ONE packed ``int32`` vector in and ONE
packed ``int32`` array out (``decode/programs.py``: ``Wire``, ``pack``,
the in-graph ``unpack``, ``_fold``).

What is proved: ``pack`` then ``unpack`` returns every field for every
``(kind, bucket)`` of a toy engine of both families; the operands the
engine really dispatches hold its pad rows at the scratch block with
zero length, token and uid; a row whose logits were not finite reads
negative in ``result`` and is quarantined as before; arming the poison
compiles nothing; and a step costs one host-to-device transfer and one
executed device program a dispatch, and nothing else. The static side
(one argument, one result, the cache aliased whole) is pinned in
``tests/test_chip_compile.py``.
"""

import collections
import glob
import os

import jax
import numpy as np
import pytest

from distributed_llm_code_samples_tpu.decode import (DecodeEngine,
                                                     EngineConfig)
from distributed_llm_code_samples_tpu.decode.engine import POISON_ALL
from distributed_llm_code_samples_tpu.decode.model_config import (
    engine_from_config)
from distributed_llm_code_samples_tpu.decode.paged import SCRATCH_BLOCK
from distributed_llm_code_samples_tpu.models import init_lm

V, D, L, H = 96, 32, 2, 4
BASE = dict(block_size=8, n_blocks=33, max_slots=4, max_blocks_per_seq=6,
            prefill_chunk=8)

@pytest.fixture(scope="module")
def lm_params():
    return init_lm(jax.random.PRNGKey(0), V, D, L, max_seq_len=64)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(1)
    return [rng.integers(0, V, size=n).tolist() for n in (5, 9, 13)]


@pytest.fixture
def engine(lm_params, toy_hybrid_config):
    """``(family, **EngineConfig fields) -> engine`` of either toy."""

    def make(family, **fields):
        cfg = EngineConfig(**BASE, **fields)
        if family == "hybrid":
            return engine_from_config(toy_hybrid_config, seed=1,
                                      engine_config=cfg)
        return DecodeEngine(lm_params, H, cfg)

    return make


def _spy(eng):
    """Record ``[kind, bucket, fields of the operand, result]`` of every
    dispatch the engine makes, in launch order, the operand unpacked by
    the program's own layout; the result (the picks) is None until the
    engine has read it (``eng.collect()``)."""
    seen = []
    real = eng._launch

    def launch(phase, asked, fn, params, operand, land):
        (kind, bucket), = [k for k, v in eng._programs.items() if v is fn]
        assert asked == bucket      # what the step's ``dispatches`` notes
        fields = {k: np.asarray(v) for k, v in
                  eng.programs.wire(kind, bucket).unpack(operand).items()}
        entry = [kind, bucket, fields, None]
        seen.append(entry)

        def landed(picks):
            entry[3] = picks
            return land(picks)

        real(phase, asked, fn, params, operand, landed)

    eng._launch = launch
    return seen


@pytest.mark.parametrize("family,kind", [
    ("gpt2", "decode"), ("gpt2", "prefill"), ("gpt2", "verify"),
    ("hybrid", "decode"), ("hybrid", "prefill")])
def test_pack_then_unpack_returns_every_field(engine, family, kind):
    """Every ``(kind, bucket)`` of the engine's program set: what the
    host packed by name is what the program's body sees by name, shape
    for shape, as ``int32``, and the vector holds nothing else."""
    eng = engine(family,
                  **({"speculate": 2} if kind == "verify" else {}))
    buckets = (eng.chunk_buckets if kind == "prefill" else eng.slot_buckets)
    rng = np.random.default_rng(3)
    assert len(buckets) > 1
    for bucket in buckets:
        wire = eng.programs.wire(kind, bucket)
        want = {name: rng.integers(-2, 2 ** 31 - 1, size=shape,
                                   dtype=np.int64).astype(np.int32)
                for name, (_, _, shape) in wire.fields.items()}
        names = set(want)
        # the row's slot: its entry of the token store (and its state
        # row); a verify's tokens are always the host's
        assert ("rows" in names or "row" in names) == (kind != "verify")
        assert ("drafts" in names) == (kind == "verify")
        operand = eng.programs.pack(kind, bucket, **want)
        assert operand.dtype == np.int32 and operand.ndim == 1
        assert operand.size == sum(v.size for v in want.values())
        got = jax.jit(wire.unpack)(operand)
        assert set(got) == names
        for name, value in want.items():
            assert got[name].dtype == np.int32
            assert got[name].shape == value.shape
            np.testing.assert_array_equal(np.asarray(got[name]), value)
        # a field missing or unknown is refused, not guessed
        with pytest.raises(TypeError, match="operand fields"):
            wire.pack(**dict(want, extra=0))
        with pytest.raises(TypeError, match="operand fields"):
            wire.pack(**{k: v for k, v in want.items() if k != "poison"})
        with pytest.raises(ValueError):
            wire.pack(**dict(want, poison=[1, 2]))


@pytest.mark.parametrize("family", ["gpt2", "hybrid"])
def test_dispatched_operands_hold_pad_rows_at_scratch(engine, prompts,
                                                      family):
    """Three ready slots ride the bucket of four: the fourth row of the
    operand the engine really dispatched points at the scratch block
    with zero length, token and uid (and, for a recurrent model, the
    scratch state row), the live rows are the scheduler's own tables,
    and a prefill operand names its one slot."""
    eng = engine(family)
    seen = _spy(eng)
    for uid, p in enumerate(prompts):
        eng.submit(p, 16, uid=uid + 1)
    while eng.step():
        pass
    assert sorted(eng.finished) == [1, 2, 3]
    padded = [(f, r) for kind, b, f, r in seen
              if kind == "decode" and b == 4 and (f["uids"] > 0).sum() == 3]
    assert padded
    for f, result in padded:
        assert (f["tables"][3] == SCRATCH_BLOCK).all()
        assert f["lengths"][3] == f["tokens"][3] == f["uids"][3] == 0
        assert (f["lengths"][:3] > 0).all() and f["poison"] == -1
        assert result.shape == (4,) and (result[:3] >= 0).all()
        assert f["rows"][3] == eng.cfg.max_slots    # the scratch row
        assert sorted(f["rows"][:3]) == [0, 1, 2]
        if family == "hybrid":
            assert eng.state.scratch_row == eng.cfg.max_slots
    pre = [(b, f, r) for kind, b, f, r in seen if kind == "prefill"]
    assert pre
    for bucket, f, result in pre:
        assert f["tokens"].shape == (bucket,) and f["uid"].shape == (1,)
        assert f["uid"][0] in (1, 2, 3) and f["pos0"] % bucket == 0
        assert result.shape == (1,)
        assert f["row"] in (0, 1, 2)


@pytest.mark.parametrize("target", ["one-uid", "all"])
@pytest.mark.parametrize("speculate", [0, 2])
def test_nonfinite_row_reads_negative_and_is_quarantined(engine, prompts,
                                                         target, speculate):
    """``nan_logits`` on one uid and on ``POISON_ALL``: the poisoned
    rows of the step's results read negative — every one of a verify
    row's sub-steps — the others do not, and exactly the poisoned uids
    are quarantined when that step's result is read."""
    eng = engine("gpt2", speculate=speculate)
    seen = _spy(eng)
    for uid, p in enumerate(prompts):
        eng.submit(p, 24, uid=uid + 1)
    while sum(s is not None and s.prompt_done for s in eng.slots) < 3:
        assert eng.step()
    eng.collect()
    healthy = len(seen)
    assert all((r >= 0).all() for *_, r in seen)
    eng.arm_poison(POISON_ALL if target == "all" else 2)
    assert eng.step()
    eng.collect()       # the flags land when the result is read
    (kind, _, f, result), = seen[healthy:]
    assert kind == ("verify" if speculate else "decode")
    assert f["poison"] == (POISON_ALL if target == "all" else 2)
    picks = result[:, :speculate + 1] if speculate else result[:, None]
    live = f["uids"] > 0      # POISON_ALL names the pad row too: unread
    bad = live if target == "all" else (f["uids"] == 2)
    assert (picks[bad] < 0).all() and (picks[live & ~bad] >= 0).all()
    assert sorted(eng.failed) == ([1, 2, 3] if target == "all" else [2])
    digest = eng.flight[-1]       # the flags the digest keeps are the rows'
    assert [u for u, ok in zip(digest["decode_uids"], digest["finite"])
            if not ok] == sorted(eng.failed)
    eng.run()
    assert sorted(eng.finished) == ([] if target == "all" else [1, 3])
    assert all((r >= 0).all() for *_, r in seen[healthy + 1:])


def test_arming_the_poison_compiles_nothing(engine, prompts):
    """The poison is a field of the runtime operand: a poisoned step
    adds no entry to any program's jit cache and builds no program."""
    eng = engine("gpt2")
    for uid, p in enumerate(prompts):
        eng.submit(p, 12, uid=uid + 1)
    while sum(s is not None and s.prompt_done for s in eng.slots) < 3:
        assert eng.step()
    assert eng.step()       # all three decode in one batch

    def entries():
        return {k: fn._cache_size() for k, fn in eng._programs.items()}

    before, built = entries(), eng.compile_count
    assert before and all(n == 1 for n in before.values())
    eng.arm_poison(3)
    assert eng.step()
    eng.collect()
    assert 3 in eng.failed
    eng.arm_poison(POISON_ALL)
    assert eng.step()
    eng.collect()
    assert entries() == before and eng.compile_count == built


def _trace_counts(trace_dir):
    """How often each host event of a CPU ``jax.profiler`` trace
    occurred, by name."""
    from jax.profiler import ProfileData
    [path] = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                    "*.xplane.pb"))
    counts = collections.Counter()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                counts.update(ev.name for ev in line.events)
    return counts


@pytest.mark.parametrize("ride", [True, False])
@pytest.mark.parametrize("family", ["gpt2", "hybrid"])
def test_a_step_is_one_transfer_and_one_program_a_dispatch(
        engine, tmp_path, monkeypatch, family, ride):
    """Over steps that each carry a full prefill chunk and a decode
    batch — ONE mixed dispatch where the chunk rides with the batch,
    a prefill and a decode dispatch where the step's choice is patched
    out here (``ride`` false: the program has no switch) — the
    runtime executes as many programs as the engine dispatched — no
    ``convert_element_type`` or ``broadcast`` one-off from a scalar
    constructor — and moves one array to the device a dispatch. Counted
    in a CPU profiler trace by the runtime's own host events: an
    executable's ``Execute`` a program run, a ``DevicePut*`` a
    host-to-device transfer (in the jitted call's argument handling or
    under ``jax.device_put``), ``PjitFunction(<name>)`` a call by name.
    (The parent of PR 30 ran 5-7 transfers and 2-3 one-off programs a
    dispatch.)"""
    eng = engine(family)
    if not ride:
        monkeypatch.setattr(eng, "_mixed_batch", lambda pre, only: [])
    rng = np.random.default_rng(5)
    # long prompts: a chunk in every step traced below
    batch = [rng.integers(0, V, size=32).tolist() for _ in range(8)]
    for uid, p in enumerate(batch):
        eng.submit(p, 12, uid=uid + 1)
    eng.run()                # every program this traffic uses is compiled
    for uid, p in enumerate(batch):
        eng.submit(p, 12, uid=uid + 101)
    for _ in range(6):       # the first prompt is in: decode runs too
        assert eng.step()
    before = (eng.dispatch_count, eng.prefill_dispatches, eng.compile_count)
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        for _ in range(4):
            assert eng.step()
    finally:
        jax.profiler.stop_trace()
    dispatched = eng.dispatch_count - before[0]
    assert eng.prefill_dispatches - before[1] == 4
    assert dispatched == (4 if ride else 8)
    assert eng.mixed_dispatches >= 4 * ride and (
        ride or not eng.mixed_dispatches)
    assert eng.compile_count == before[2]
    counts = _trace_counts(trace_dir)
    executed = sum(n for name, n in counts.items()
                   if name.endswith("Executable::Execute"))
    transfers = sum(n for name, n in counts.items()
                    if name.startswith("DevicePut"))
    calls = {name: n for name, n in counts.items()
             if name.startswith("PjitFunction(")}
    assert executed == dispatched, counts
    assert transfers == dispatched, counts
    assert set(calls) == {"PjitFunction(run)"}, calls
