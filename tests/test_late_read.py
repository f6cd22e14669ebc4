"""A step's results are read one step late (ISSUE 38): ``engine.step``
launches its program and only then reads the program launched before it
(``decode/engine.py::_launch`` / ``_collect``), and a slot's next token
goes from one program to the next on the device
(``decode/programs.py``: the token store in every step program's
carry).

The oracle is THE SAME ENGINE read after every step (``eng.collect()``:
the program has no switch), whose rows then always take the host's
tokens. Toy widths, float32: a KV toy (the GPT-2 block) and a recurrent
toy (the hybrid: Mamba + attention).
"""

import numpy as np
import pytest

from distributed_llm_code_samples_tpu.decode import ServePolicy
from distributed_llm_code_samples_tpu.decode.programs import (
    FROM_SLOT, POISON_ALL, POISON_NONE)

# the toy engines of the four served families, their writer and traffic
from test_mixed_program import LENS, Collector, backlog
from test_mixed_program import engine as family_engine  # noqa: F401

FAMILIES = ["gpt2", "hybrid"]


@pytest.fixture
def engine(family_engine):      # noqa: F811
    """``(family, metrics=None, policy=None, **cfg) -> engine``: the
    policy is host-side only, so it is set on the built engine."""
    def build(family, metrics=None, policy=None, **over):
        eng = family_engine(family, metrics=metrics, **over)
        if policy is not None:
            eng.policy = policy
        return eng

    return build


def drain(eng, each_step=lambda: None):
    """The loop every driver of the engine runs."""
    while eng.active or eng.waiting:
        eng.step()
        each_step()


def late_reads(steps):
    """Of the records' dispatches, those read in a later record than
    the one that launched them."""
    late = 0
    for rec in steps:
        first = rec["launches"] - len(rec["dispatches"])
        late += sum(o < first for o in rec["readbacks"])
    return late


# ``LENS``: full chunks behind ready slots, tails, a prompt shorter than
# a chunk, one of exactly a chunk (its completing chunk rides), more
# requests than slots; two of them arrive mid-run
NEW = (3, 12, 7, 9, 1, 15, 4, 6)


def serve(eng, read_every_step):
    prompts = backlog(LENS)
    for p, n in zip(prompts[:6], NEW):
        eng.submit(p, n)
    for _ in range(7):
        eng.step()
        if read_every_step:
            eng.collect()
    for p, n in zip(prompts[6:], NEW[6:]):
        eng.submit(p, n)
    drain(eng, eng.collect if read_every_step else lambda: None)
    assert not eng.failed and eng._inflight is None
    return eng.finished


@pytest.mark.parametrize("family", FAMILIES)
def test_same_tokens_read_late_and_read_every_step(engine, family):
    """Token for token what the same engine serves when every step's
    result is read before the next is launched: over a backlog with
    tails, completing chunks that ride, finishes (one after a single
    token) and admissions mid-run."""
    sink = Collector()
    late, each = engine(family, metrics=sink), engine(family)
    assert len(LENS) == len(NEW)
    got, want = serve(late, False), serve(each, True)
    assert got == want and len(got) == len(LENS)
    assert late.tokens_generated == each.tokens_generated == sum(NEW)
    # the reads really waited: most dispatches were read a step late,
    # and a row took its token from the slot on the device
    n = sum(len(r["dispatches"]) for r in sink.steps)
    assert late_reads(sink.steps) > n // 2
    # no token was computed that the oracle does not compute: the same
    # rows were launched (a slot frees a step later, so steps differ)
    assert late.prefill_dispatches == each.prefill_dispatches


@pytest.mark.parametrize("family", FAMILIES)
def test_a_row_in_flight_takes_its_token_on_the_device(engine, family,
                                                       monkeypatch):
    """What the operand says: a row whose last token is unread carries
    ``FROM_SLOT`` and its slot; a replay's row and a row of an engine
    read every step carry the token itself."""
    eng = engine(family)
    seen = []
    real = eng.programs.pack

    def pack(kind, bucket, **fields):
        if kind != "prefill":
            seen.append((kind, [int(t) for t in fields["tokens"]],
                         list(fields["rows"])))
        return real(kind, bucket, **fields)

    monkeypatch.setattr(eng.programs, "pack", pack)
    for p in backlog((5, 9)):
        eng.submit(p, 12)
    for _ in range(6):      # 4 + 1 and 8 + 1 prompt tokens, then batches
        eng.step()
    kind, tokens, rows = seen[-1]
    assert kind == "decode" and tokens[:2] == [FROM_SLOT] * 2
    assert rows[:2] == [0, 1] and set(rows[2:]) <= {eng.cfg.max_slots}
    eng.collect()
    eng.step()
    kind, tokens, _ = seen[-1]
    assert tokens[:2] == [eng.slots[0].out[-1], eng.slots[1].out[-1]]
    # the store holds what the host then read
    eng.collect()
    store = np.asarray(eng.token_store)
    assert [store[0], store[1]] == [eng.slots[0].out[-1],
                                    eng.slots[1].out[-1]]


@pytest.mark.parametrize("family", FAMILIES)
def test_next_dispatch_opens_before_the_last_readback_closes(engine,
                                                             family):
    """Steady state, record by record: the step's ``*.dispatch`` phase
    comes BEFORE its ``*.readback`` phase, which read the previous
    launch (``readbacks`` names it; ``dispatches`` stays with the step
    that launched)."""
    sink = Collector()
    eng = engine(family, metrics=sink)
    for p in backlog((5, 9, 40)):
        eng.submit(p, 24)
    drain(eng)
    steady = [r for r in sink.steps
              if len(r["dispatches"]) == 1 and len(r["readbacks"]) == 1
              and r["readbacks"][0] == r["launches"] - 2]
    assert len(steady) >= 20
    for rec in steady:
        names = [p[0] for p in rec["phases"]]
        d = next(i for i, n in enumerate(names) if n.endswith(".dispatch"))
        r = next(i for i, n in enumerate(names) if n.endswith(".readback"))
        assert d < r and rec["phases"][d][1] < rec["phases"][r][2]
        # the read's own host work follows it, inside the same step
        assert names[r + 1] in ("prefill.book", "decode.emit")
    # every launch is read exactly once, in launch order
    read = [o for r in sink.steps for o in r["readbacks"]]
    assert read == list(range(eng.launches))
    # and the digests agree with the records
    assert [d["readbacks"] for d in eng.flight] == [
        r["readbacks"] for r in sink.steps][-len(eng.flight):]


@pytest.mark.parametrize("family", FAMILIES)
def test_active_while_unread_and_the_closing_step_returns_true(engine,
                                                               family):
    """A sequence keeps its slot until its last token lands, so
    ``active`` is true while a result is unread and a driver's loop
    makes the step that reads it; a step that only reads returns True;
    and the step that launches the LAST row of a draining engine reads
    it at once (nothing would be queued behind it)."""
    eng = engine(family)
    for p in backlog((5, 9)):
        eng.submit(p, 8)
    for _ in range(6):
        eng.step()
    assert eng._inflight is not None and eng.active == 2
    steps, launches = eng.steps, eng.launches
    # nothing to launch (the prefill tier's step, no prompt waiting):
    # the step reads what is in flight, and that is work
    assert eng.step(prefill_only=True) is True
    assert eng._inflight is None and eng.launches == launches
    assert eng.flight[-1]["dispatches"] == []
    assert eng.flight[-1]["readbacks"] == [launches - 1]
    assert eng.steps == steps + 1
    assert eng.step(prefill_only=True) is False     # and now there is none
    # every row of a step non-finite, seen a step late: both requests
    # fail in the step after, which reads its own (dropped) rows too
    eng.arm_poison(POISON_ALL)
    assert eng.step() and not eng.failed and eng.active == 2
    assert eng.step() and sorted(eng.failed) == [0, 1]
    assert eng.slots == [None] * 4 and eng._inflight is None
    assert eng.active == 0 and eng.step() is False


@pytest.mark.parametrize("family", FAMILIES)
def test_a_poisoned_row_is_quarantined_one_step_late(engine, family):
    """A non-finite row of step N is seen after N+1 was launched: the
    slot is quarantined then, its row in N+1 dropped when it lands, the
    neighbours' tokens are those of a run that never admitted it, and
    the poison names exactly one step's dispatches."""
    prompts = backlog((5, 9, 7))
    eng, clean = engine(family), engine(family)
    for uid, p in enumerate(prompts):
        eng.submit(p, 20, uid=uid)
        if uid != 1:
            clean.submit(p, 20, uid=uid)
    for _ in range(9):      # past the prompts' tails: batches only
        eng.step()
    eng.collect()
    n_out = len(eng.slots[1].out)
    eng.arm_poison(1)
    eng.step()                                          # step N
    assert eng._poison_uid == POISON_NONE               # one step's window
    assert 1 not in eng.failed and eng.slots[1] is not None
    digest_n = eng.flight[-1]
    assert digest_n["finite"] is None                   # still unread
    eng.step()                                          # N + 1
    assert eng.failed[1]["reason"] == "nonfinite_logits"
    assert eng.failed[1]["n_out"] == n_out      # the bad pick never landed
    assert eng.slots[1] is None and eng.quarantined == 1
    # step N's OWN flags, written when they landed
    assert digest_n["decode_uids"] == [0, 1, 2]
    assert digest_n["finite"] == [True, False, True]
    # its row in N + 1 was launched (nobody knew) and dropped on arrival
    assert eng.flight[-1]["decode_uids"] == [0, 1, 2]
    # (the quarantine's dump of the flight recorder read N + 1 too)
    assert eng.flight[-1]["finite"] == [True, True, True]   # one step's
    eng.step()
    assert eng.flight[-1]["decode_uids"] == [0, 2]
    drain(eng)
    assert eng.quarantined == 1
    clean.run()
    assert {u: eng.finished[u] for u in (0, 2)} == clean.finished


def test_a_retried_row_in_flight_does_not_land_on_its_next_life(engine):
    """A quarantined request with a retry left may be back in its old
    slot when the row launched for its last life lands: that row is
    told by the admission, not by the uid."""
    eng = engine("gpt2", policy=ServePolicy(max_retries=1), max_slots=1)
    want = engine("gpt2", max_slots=1)
    (p,) = backlog((5,))
    eng.submit(p, 12)
    want.submit(p, 12)
    for _ in range(4):
        eng.step()
    eng.arm_poison(0)
    drain(eng)
    assert eng.retried == 1 and not eng.failed
    assert eng.finished == want.run()


def test_values_are_read_before_whatever_needs_them(engine):
    """``export_sequence`` / ``release_request`` / a snapshot's
    ``telemetry_record`` read the unread result first: the document
    holds the token that was in flight."""
    eng = engine("gpt2")
    for p in backlog((5, 9, 7)):
        eng.submit(p, 20)
    for _ in range(6):
        eng.step()
    assert eng._inflight is not None
    launched = eng.slots[0].launched
    assert len(eng.slots[0].out) == launched - 1
    doc = eng.export_sequence(0)
    assert eng._inflight is None
    assert len(doc["out"]) == launched == doc["emitted"]
    assert doc["next_token"] == doc["out"][-1]
    assert doc["position"] == len(doc["prompt"]) + launched - 1
    eng.step()
    assert eng._inflight is not None
    launched = eng.slots[1].launched
    entry = eng.release_request(1)
    assert eng._inflight is None and len(entry["out"]) == launched
    eng.step()
    assert eng._inflight is not None
    rec = eng.telemetry_record()
    assert eng._inflight is None
    assert rec["tokens_generated"] == eng.tokens_generated == (
        len(doc["out"]) + len(entry["out"]) + len(eng.slots[2].out))
    # the exported sequence goes on elsewhere from the host's token
    other = engine("gpt2")
    other.import_sequence(doc)
    whole = engine("gpt2")
    whole.submit(doc["prompt"], 20, uid=0)
    assert other.run()[0] == whole.run()[0]


def test_preemption_replays_from_landed_tokens(engine):
    """Pool-pressure preemption under the late read: the victim's
    replay entry is ``prompt + out`` as landed, and every request's
    tokens are the unpreempted engine's."""
    prompts = backlog((20, 20, 40), seed=2)     # 3 + 3 blocks, then 4
    tight = engine("gpt2", policy=ServePolicy(preempt_after_steps=2),
                   n_blocks=1 + 7, max_blocks_per_seq=4)
    roomy = engine("gpt2", max_blocks_per_seq=4)
    for p in prompts:
        tight.submit(p, 20)
        roomy.submit(p, 20)
    assert tight.run() == roomy.run()
    assert tight.preempted >= 1 and not tight.failed


@pytest.mark.parametrize("family", FAMILIES)
def test_warm_builds_the_parents_programs(engine, family):
    """No new step program, no new bucket axis, no second shape of any
    program: a decode program a slot bucket (1, 2, 4), ONE mixed, a
    prefill program a chunk bucket (1 .. 16) and the implant; serving a
    backlog afterwards builds nothing."""
    eng = engine(family)
    assert eng.warm() == 3 + 1 + 5 + 1
    eng.generate(backlog(LENS), 6)
    assert eng.compile_count == 10


def test_an_expiry_counts_landed_tokens(engine):
    """A deadline that falls with a result unread: the result is read
    first, so a request whose last token was in flight has FINISHED and
    an expired one reports every token it produced."""
    eng = engine("gpt2", policy=ServePolicy(deadline_steps=6))
    each = engine("gpt2", policy=ServePolicy(deadline_steps=6))
    for p, n in zip(backlog((5, 9)), (4, 30)):
        eng.submit(p, n)
        each.submit(p, n)
    drain(eng)
    drain(each, each.collect)
    assert eng.finished == each.finished and sorted(eng.finished) == [0]
    assert eng.failed == each.failed
    assert eng.failed[1]["reason"] == "deadline"
