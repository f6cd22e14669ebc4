"""``chunk_ride_share.offline`` on hand-made ``engine_step`` records:
steps whose chunk rode with the batch (``mixed.dispatch``), steps that
ran the chunk in a program of its own (``prefill.dispatch``), steps with
no chunk, and a program that has no ``mixed`` phase at all."""

import pytest

from benchmark import harness
from benchmark.serve import Step

NAME = "chunk_ride_share.offline"
MIXED = ["prefill.cow", "decode.cow", "decode.marshal", "mixed.upload",
         "mixed.dispatch", "mixed.readback", "prefill.book", "decode.emit"]
SPLIT = ["prefill.cow", "prefill.upload", "prefill.dispatch",
         "prefill.readback", "prefill.book", "decode.marshal", "decode.cow",
         "decode.marshal", "decode.upload", "decode.dispatch",
         "decode.readback", "decode.emit"]
DECODE = SPLIT[5:]


def make_ctx(kinds, before=2):
    """One record a step, ``before`` untraced ones ahead of the traced
    steps whose phases ``kinds`` lists."""
    recs, steps = [], []
    for k, names in enumerate([DECODE] * before + list(kinds)):
        t = 1_000_000 * k
        recs.append({"span": "engine_step", "uid": None, "step": k,
                     "tokens_generated": 50 + 7 * k,
                     "start_ns": t, "end_ns": t + 900_000,
                     "phases": [[n, t + 10 * i, t + 10 * i + 9]
                                for i, n in enumerate(["admit"] + names)]})
        if k >= before:
            steps.append(Step(0.0, 0.0, 3, 50 + 7 * k, 0, 0, 1, True))
    return {"values": {"traced_steps": steps}, "spans": recs}


@pytest.mark.parametrize("kinds,want", [
    ([MIXED, MIXED, SPLIT, DECODE, MIXED, DECODE], 75.0),   # with
    ([DECODE, DECODE, DECODE], None),                       # without
    ([SPLIT, DECODE, SPLIT], 0.0),          # old-path chunks only: the
                                            # parent's program reads 0
    ([MIXED], 100.0)])
def test_share_of_chunk_steps_that_rode(kinds, want):
    got = harness.read_layer_metric(NAME, make_ctx(kinds))
    assert got == (want if want is None else pytest.approx(want))


def test_nothing_to_read_is_none_not_an_error():
    ctx = make_ctx([MIXED, SPLIT])
    assert harness.read_layer_metric(NAME, dict(ctx, spans=[])) is None
    # a traced step without its record: the join gives nothing
    ctx["spans"].pop()
    assert harness.read_layer_metric(NAME, ctx) is None


def test_the_metric_is_listed_for_the_serving_cells():
    import json
    import os
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry["name"] == NAME and entry["unit"] == "%"
    assert entry["moves"] == "out_tokens_per_s"
    spec = harness.read_json("layer_metrics", NAME + ".json")
    assert spec["layer"] == entry["layer"] == "serving scheduler"
    serving = [m for m in bench["end_to_end"]
               if m["name"] == "out_tokens_per_s"][0]["workloads"]
    assert entry["workloads"] == serving == spec["cells"]
