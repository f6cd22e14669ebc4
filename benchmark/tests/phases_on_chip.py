#!/usr/bin/env python3
"""The clock check of the engine's step phases, for the chip.

    python3 benchmark/tests/phases_on_chip.py

``engine_phases.py`` lays the ``engine_step`` records' ``time.time_ns()``
stamps against the profiler's events, so the two have to be one clock.
This traces a few steps of an engine of GPT-2-small's widths, loads the
trace with the program's own ``engine:`` host events kept, and prints,
over the traced steps: the record's ``start_ns`` less its
``engine:step`` event's start, less the trace's start (the profiler
stores event times relative to ``profile_start_time``, wall-clock ns):
the two clocks' disagreement; and the two durations' difference.
"""

import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


class Collector:
    """A writer that keeps span records in memory."""

    def __init__(self):
        self.spans = []

    def span(self, rec):
        self.spans.append(rec)

    def __getattr__(self, _name):
        return lambda *a, **k: None


def quantiles(xs: list) -> dict:
    return {"n": len(xs), "min": min(xs), "median": statistics.median(xs),
            "max": max(xs)}


def clock(steps=24, vocab=50257, d_model=768, layers=12, heads=12) -> dict:
    import jax
    from jax.profiler import ProfileData

    from benchmark import xplane
    from distributed_llm_code_samples_tpu.decode import (DecodeEngine,
                                                         EngineConfig)
    from distributed_llm_code_samples_tpu.models import init_lm
    params = init_lm(jax.random.PRNGKey(0), vocab, d_model, layers,
                     max_seq_len=256)
    sink = Collector()
    eng = DecodeEngine(params, heads, EngineConfig(
        block_size=16, n_blocks=65, max_slots=4, max_blocks_per_seq=16,
        prefill_chunk=16), metrics=sink)
    prompts = [[(7 * i + j) % vocab for j in range(40 + 8 * i)]
               for i in range(4)]
    eng.generate(prompts, 8)            # compile outside the trace
    sink.spans.clear()
    for p in prompts:
        eng.submit(p, steps)
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        try:
            for _ in range(steps):
                eng.step()
        finally:
            jax.profiler.stop_trace()
        path = xplane.find_xplane(trace_dir)
        events = xplane.host_events(
            xplane.load(path, host_names=("bench:", "engine:")),
            "engine:step")
        env = next(p for p in ProfileData.from_file(path).planes
                   if p.name == "Task Environment")
        start = dict(env.stats)["profile_start_time"]
    recs = [s for s in sink.spans if s["span"] == "engine_step"]
    assert len(recs) == len(events) == steps, (len(recs), len(events))
    return {
        "steps": steps, "profile_start_time": start,
        "record_minus_event_minus_trace_start_ns": quantiles(
            [r["start_ns"] - e[1] - start for r, e in zip(recs, events)]),
        "duration_record_minus_event_ns": quantiles(
            [r["end_ns"] - r["start_ns"] - e[2]
             for r, e in zip(recs, events)])}


if __name__ == "__main__":
    print(json.dumps(clock()), flush=True)
