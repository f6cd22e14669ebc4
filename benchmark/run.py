#!/usr/bin/env python3
"""One run of one cell, as a new process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's files by its name (``workloads/<cell>.json``, the
configuration and per-layer readers they name), refuses to run off a
TPU, makes weights and traffic from ``--seed``, warms the cell's shapes
through the persistent compile cache (set-up), measures for
``--seconds``, checks what the window produced against the plain
reference, and prints the contract's one JSON object as the last line
of stdout. ``--trace 0`` reports the end-to-end metrics; ``--trace 1``
traces a short part of the window and reports the per-layer ones.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def run_cell(name: str, seed: int, seconds: float, trace_on: bool,
             check_device: bool = True, alter=None, shrink=None,
             control: tuple = ()) -> dict:
    """Everything but the argument parsing. ``check_device=False``,
    ``alter`` (a fault planted in the timed path), ``shrink`` (the cell
    cut to a size the CPU holds) and ``control`` (lower-precision modes
    of the reference read beside the program) are for the tests and the
    control script under ``benchmark/tests``; no command-line option
    reaches them."""
    from benchmark import harness
    cell = harness.load_cell(name)
    if shrink is not None:
        shrink(cell)
    import jax
    if check_device:
        device = harness.require_chips(cell["chips"])
    else:
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": jax.device_count()}
    # the program's own switch: <checkout>/.jax_cache unless
    # JAX_COMPILATION_CACHE_DIR is set — a fixed path either way
    from distributed_llm_code_samples_tpu.runtime.init import (
        enable_compile_cache)
    cache_dir = enable_compile_cache()
    compiles = harness.Compiles()
    # the kind's first word names its module: "serve-offline" and
    # "serve-open" are benchmark/serve.py, "train" is benchmark/train.py
    kind = cell["work"]["kind"]
    try:
        driver = importlib.import_module("benchmark." + kind.split("-")[0])
    except ModuleNotFoundError:
        raise SystemExit(f"unknown workload kind {kind!r}") from None
    harness.say(phase="start", workload=name, seed=seed, seconds=seconds,
                trace=int(trace_on), device=device, compile_cache=cache_dir)
    outcome, ctx = driver.run(cell, seed, seconds, trace_on, device,
                              compiles, T_START, alter=alter,
                              control=control)
    harness.say(phase="setup", setup_s=outcome["e2e"]["setup_s"],
                compile_s=compiles.seconds, compiled=compiles.compiles,
                cache_hits=compiles.hits, cache_misses=compiles.misses)
    return harness.result_line(cell, device, trace_on, outcome, ctx)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
