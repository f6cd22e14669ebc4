"""``late_read_share.offline`` on hand-made ``engine_step`` records:
steps that read the launch of the step before (the read a step late),
steps that read their own launch, a tail's step (two launches, the
chunk's read in the step), a step that only read, and a program whose
records hold no ``readbacks`` (the parent's)."""

import pytest

from benchmark import harness
from benchmark.serve import Step

NAME = "late_read_share.offline"


def make_ctx(steps, before=2, v20=True):
    """One record a step, ``before`` untraced ones ahead of the traced
    steps. A step is ``(kinds launched, how many of ITS launches it
    read)``: each launch is followed by the read of whatever was
    unread, as the engine does it."""
    recs, traced, launches, unread = [], [], 0, []
    for k, (kinds, own) in enumerate([(["decode"], 1)] * before
                                     + list(steps)):
        t, names, reads = 1_000_000 * k, ["admit"], []
        first = launches
        for kind in kinds:
            names.append(kind + ".dispatch")
            unread.append((launches, kind))
            launches += 1
            while len(unread) > 1:
                o, was = unread.pop(0)
                names.append(was + ".readback")
                reads.append(o)
        while unread and (not kinds or sum(o >= first for o in reads)
                          < own):
            o, was = unread.pop(0)
            names.append(was + ".readback")
            reads.append(o)
        rec = {"span": "engine_step", "uid": None, "step": k,
               "tokens_generated": 50 + 7 * k,
               "start_ns": t, "end_ns": t + 900_000,
               "phases": [[n, t + 10 * i, t + 10 * i + 9]
                          for i, n in enumerate(names)],
               "dispatches": [[kind, 4] for kind in kinds]}
        if v20:
            rec.update(readbacks=reads, launches=launches)
        recs.append(rec)
        if k >= before:
            traced.append(Step(0.0, 0.0, 3, 50 + 7 * k, 0, 0, 1, True))
    return {"values": {"traced_steps": traced}, "spans": recs}


LATE, OWN, TAIL = (["mixed"], 0), (["decode"], 1), (["prefill", "decode"], 0)


@pytest.mark.parametrize("steps,want", [
    ([LATE] * 9 + [OWN], 90.0),             # steady, then a drain's end
    ([OWN, OWN, OWN], 0.0),                 # every result read at once
    ([LATE, TAIL, LATE, LATE], 80.0),       # the tail's chunk is read in
                                            # its step, its batch is not
    ([LATE, ([], 0), OWN], 50.0),           # a step that only read
    ([([], 0)], None)])                     # nothing launched
def test_share_of_launches_read_a_step_late(steps, want):
    got = harness.read_layer_metric(NAME, make_ctx(steps))
    assert got == (want if want is None else pytest.approx(want))


def test_a_program_without_readbacks_reads_zero():
    """The parent's records: every ``*.readback`` follows its own
    ``*.dispatch`` in the same record, and says so by having no
    ``readbacks``."""
    ctx = make_ctx([OWN, (["prefill", "decode"], 2), OWN], v20=False)
    assert harness.read_layer_metric(NAME, ctx) == 0.0


def test_nothing_to_read_is_none_not_an_error():
    ctx = make_ctx([LATE, OWN])
    assert harness.read_layer_metric(NAME, dict(ctx, spans=[])) is None
    # a traced step without its record: the join gives nothing
    ctx["spans"].pop()
    assert harness.read_layer_metric(NAME, ctx) is None


def test_the_metric_is_listed_for_the_serving_cells():
    import json
    import os
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry["unit"] == "%" and entry["better"] == "higher"
    assert entry["moves"] == "out_tokens_per_s"
    assert entry["source"] == "program_counter"
    spec = harness.read_json("layer_metrics", NAME + ".json")
    assert spec["layer"] == entry["layer"] == "serving scheduler"
    serving = [m for m in bench["end_to_end"]
               if m["name"] == "out_tokens_per_s"][0]["workloads"]
    assert entry["workloads"] == serving == spec["cells"]
