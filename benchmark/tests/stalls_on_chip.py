#!/usr/bin/env python3
"""Which step stalls, and where in it: one untraced run of a serving
cell with every ``engine.step()`` over ``--over_ms`` written down beside
the step's own flight digest (``phase_ms``: where the host's time went;
``dispatches`` / ``readbacks``: which program was launched and which
read; ``events``) and every garbage collection over 20 ms. The engine's
digest ring keeps 256 steps and no run keeps it (PERF.md section 7): this
does, for the slow ones. A tool for a builder; no metric is defined
here.

    python3 benchmark/tests/stalls_on_chip.py --workload <cell> \
        --seed 3900000501 --seconds 120
"""

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=120.0)
    ap.add_argument("--over_ms", type=float, default=100.0)
    args = ap.parse_args()
    from benchmark import run
    from distributed_llm_code_samples_tpu.decode.engine import DecodeEngine
    slow, collections, t_gc = [], [], [0.0]

    def on_gc(phase, info):
        if phase == "start":
            t_gc[0] = time.perf_counter()
        elif (time.perf_counter() - t_gc[0]) * 1e3 > 20:
            collections.append({"t": time.time(), "gen": info["generation"],
                                "ms": (time.perf_counter() - t_gc[0]) * 1e3})

    gc.callbacks.append(on_gc)
    real = DecodeEngine.step

    def step(self, *a, **k):
        t0 = time.perf_counter()
        out = real(self, *a, **k)
        ms = (time.perf_counter() - t0) * 1e3
        if ms > args.over_ms and self.flight:
            d = self.flight[-1]
            slow.append({"t": time.time(), "ms": ms, "step": d["step"],
                         "phase_ms": d["phase_ms"],
                         "dispatches": d["dispatches"],
                         "readbacks": d["readbacks"], "events": d["events"],
                         "free_blocks": d["free_blocks"],
                         "waiting": d["waiting"]})
        return out

    DecodeEngine.step = step
    line = run.run_cell(args.workload, args.seed, args.seconds, False)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out",
                        f"stalls_{args.workload}_{args.seed}.json")
    with open(path, "w") as f:
        json.dump({"slow_steps": slow, "collections": collections}, f,
                  indent=1)
    for s in slow:
        print(json.dumps({"phase": "slow_step", **s}), flush=True)
    print(json.dumps({"phase": "collections", "n": len(collections),
                      "worst_ms": max((c["ms"] for c in collections),
                                      default=0.0)}), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
