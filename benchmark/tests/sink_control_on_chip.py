#!/usr/bin/env python3
"""The control of the sink, on the chip at the cell's own size: the
``mimo-v2-flash.longreason-offline`` cell run with the PROGRAM's window
layers computing their softmax WITHOUT the sink (the model's
``window_sink`` answers None, as a family without one does), every
number ``correct`` compares printed beside its limit. The sinks are
drawn so that this control FAILS (``models/mimo_v2_flash_lm.py::
SINK_MEAN``): a run that reads ``correct`` true here says the cell's
limits cannot see the mechanism. ``--with_sink 1`` runs the program as
it is, for the peak of the process's device memory AFTER the check (the
reference beside the engine), which the result line does not carry.
For a builder who changes the sinks' draw or the cell's limits: no
metric is defined here.

    python3 benchmark/tests/sink_control_on_chip.py --seed 4900000501
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "mimo-v2-flash.longreason-offline"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--with_sink", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from benchmark import harness, run
    from distributed_llm_code_samples_tpu.models import mimo_v2_flash_lm
    if not args.with_sink:
        mimo_v2_flash_lm.MimoV2FlashLMParams.window_sink = (
            lambda self, i: None)
    line = run.run_cell(CELL, args.seed, args.seconds, False)
    print(json.dumps({"seed": args.seed, "with_sink": args.with_sink,
                      "correct": line["correct"],
                      "compared": line["compared"],
                      "memory_peak_bytes_after_check": harness.peak_bytes()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
