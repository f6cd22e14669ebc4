#!/usr/bin/env python3
"""What the cell's own window cannot reach, on the chip at the cell's
own size: every slot of ``evabyte.bytereason-offline``'s engine decoded
past THREE window boundaries (the cell's 30 s window follows a pre-roll
of two completions, so what finishes inside it holds ~2.1-2.6 k
positions and has crossed one boundary: PERF.md section 7), and the
plain reference teacher-forced beside the engine on sequences of up to
``serving.max_positions``.

``--rows`` requests (a slot each) with prompts of 64 to 248 bytes and
totals spread evenly from ``3 x window + 17`` to ``max_positions`` are
submitted at once and run to their ends; ``benchmark/serve.py::check``
then reads the cell's two numbers over the longest and ``sample - 1``
others, against the cell's limits. Printed beside them: the checked
lengths, each row's summaries at its end, the step's p50 by the depth of
the shallowest live row (one figure a window), and the device's peak
bytes with the engine alone and with the reference beside it. No metric
is defined here.

    python3 benchmark/tests/deep_window_on_chip.py --seed 4100000701
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "evabyte.bytereason-offline"


class _Requests:
    """What ``serve.check`` reads of a ``serve.Driver``."""

    def __init__(self, engine):
        self.engine = engine
        self.requests: list[dict] = []
        self.uid_of: dict[int, int] = {}

    def submit(self, prompt: list[int], max_new: int) -> None:
        self.uid_of[self.engine.submit(prompt, max_new)] = len(self.requests)
        self.requests.append({"prompt": prompt})


def main(argv=None, shrink=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--modes", default="")
    args = ap.parse_args(argv)
    import numpy as np
    from benchmark import harness, serve
    cell = harness.load_cell(CELL)
    if shrink is not None:
        shrink(cell)
    import jax
    from distributed_llm_code_samples_tpu.runtime.init import (
        enable_compile_cache)
    enable_compile_cache()
    config = cell["config"]
    window = int(config["window_size"])
    top = int(config["serving"]["max_positions"])
    rows = args.rows or int(config["serving"]["max_slots"])
    sut = harness.driver_module(config)
    w = sut.make_weights(config, args.seed)
    engine = sut.build_engine(config, w)
    serve.warm(engine)
    drv = _Requests(engine)
    rng = np.random.default_rng([args.seed & 0xFFFFFFFF, 0xDEE9])
    lo = 3 * window + int(config["chunk_size"]) + 1
    totals = np.linspace(lo, top, rows).astype(int)
    for j, total in enumerate(totals):
        plen = min(64 + 8 * j, window // 2)
        drv.submit(rng.integers(0, config["vocab_size"], plen).tolist(),
                   int(total) - plen)
    by_window: dict[int, list[float]] = {}
    n0 = engine.steps
    t0 = time.perf_counter()
    while engine.active or engine.waiting:
        live = [int(engine.lengths[s]) for s, q in enumerate(engine.slots)
                if q is not None]
        a = time.perf_counter()
        engine.step()
        if len(live) == rows:
            by_window.setdefault(min(live) // window, []).append(
                (time.perf_counter() - a) * 1e3)
    engine.collect()
    took = time.perf_counter() - t0
    peak_engine = harness.peak_bytes()
    done = {u: len(engine.finished[u]) for u in drv.uid_of}
    res = serve.check(cell, harness.reference_module(config), w, drv,
                      list(drv.uid_of), args.seed,
                      control=tuple(m for m in args.modes.split(",") if m))
    print(json.dumps({
        "seed": args.seed, "rows": rows, "steps": engine.steps - n0,
        "seconds": took,
        "lengths": sorted(done.values()),
        "windows_crossed": sorted(n // window for n in done.values()),
        "summaries_at_end": sorted((n // window) * (window // int(
            config["chunk_size"])) for n in done.values()),
        "step_ms_p50_by_shallowest_rows_window": {
            str(k): float(np.median(v)) for k, v in sorted(by_window.items())},
        "steps_by_window": {str(k): len(v)
                            for k, v in sorted(by_window.items())},
        "peak_bytes_engine": peak_engine,
        "peak_bytes_with_reference": harness.peak_bytes(),
        "failed": len(engine.failed),
        "correct": res}), flush=True)
    return 0 if res["ok"] and not engine.failed else 1


if __name__ == "__main__":
    sys.exit(main())
