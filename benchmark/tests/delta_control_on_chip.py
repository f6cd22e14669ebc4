#!/usr/bin/env python3
"""The controls of the gated delta rule, on the chip at the cell's own
size: the ``qwen3-next.longreason-offline`` cell run with the PROGRAM's
recurrence changed in one mechanism, every number ``correct`` compares
printed beside its limit.

- ``--control no_state``: the carried state zeroed before every token
  (``exp(g)`` 0 in all three program forms: ``S <- 0 S`` first, so a
  token sees its own write and nothing older): a program that drops the
  matrix between steps, or a chunk that does not hand it on.
- ``--control no_decay``: ``exp(g)`` fixed at 1 (``g`` 0): the state
  never forgets.
- ``--control none``: the program as it is, for the peak of the
  process's device memory AFTER the check (the reference beside the
  engine), which the result line does not carry.

The decay is drawn so that both controls FAIL (``models/
qwen3_next_lm.py::A_MAX``, ``DT_MIN``, ``DT_MAX``): a run that reads
``correct`` true under a control says the cell's limits cannot see the
mechanism. For a builder who changes the draw or the cell's limits: no
metric is defined here.

    python3 benchmark/tests/delta_control_on_chip.py --seed 5200000501 \
        --control no_state
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "qwen3-next.longreason-offline"
FORMS = ("delta_chunk", "delta_step_in_place", "delta_mixed")


def arm(control: str, put=setattr) -> None:
    """Every program form of the recurrence with ``g`` replaced
    (``put``: how a test plants, and takes back, the change)."""
    import jax.numpy as jnp
    from distributed_llm_code_samples_tpu.ops import delta_rule
    fill = {"no_state": -jnp.inf, "no_decay": 0.0}[control]

    def changed(fn):
        def run(q, k, v, g, beta, *rest, **kw):
            return fn(q, k, v, jnp.full_like(g, fill), beta, *rest, **kw)
        return run

    for name in FORMS:
        put(delta_rule, name, changed(getattr(delta_rule, name)))


def main(argv=None, shrink=None, put=setattr) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", choices=("no_state", "no_decay", "none"),
                    default="no_state")
    args = ap.parse_args(argv)
    from benchmark import harness, run
    if args.control != "none":
        arm(args.control, put)
    line = run.run_cell(CELL, args.seed, args.seconds, False,
                        check_device=shrink is None, shrink=shrink)
    print(json.dumps({"seed": args.seed, "control": args.control,
                      "correct": line["correct"],
                      "compared": line["compared"],
                      "metrics": line["metrics"],
                      "memory_peak_bytes": line["device"][
                          "memory_peak_bytes"],
                      "memory_peak_bytes_after_check": harness.peak_bytes()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
