#!/usr/bin/env python3
"""What ``benchmark/window_trace.py`` reads, shown op by op, on the chip:
one traced run of the cell, and beside its result line a file
``chiprun_out/window_ops_<seed>.json`` with every op of the FIRST
decode-side program event of the traced window (the HLO text the
profiler names it by, cut to 400 characters, its device microseconds and
the mechanism the reader books it under) and each mechanism's total.
For a builder who changes the program's shapes or the reader's
patterns: no metric is defined here.

    python3 benchmark/tests/window_ops_on_chip.py --seed 3900000101
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "laguna-s-2.1.longreason-offline"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args()
    from benchmark import harness, run, window_trace, xplane
    kept = {}
    real = harness.result_line

    def keep(cell, device, trace_on, outcome, ctx):
        kept["ctx"] = ctx
        return real(cell, device, trace_on, outcome, ctx)

    harness.result_line = keep
    line = run.run_cell(CELL, args.seed, args.seconds, True)
    ctx = kept["ctx"]
    spans = window_trace.decode_events(ctx) or []
    out = {"decode_events": len(spans), "counters": window_trace.counters(ctx)}
    if spans:
        z = window_trace.sizes(ctx)
        tests = {"full": window_trace.attn_op(z, "full"),
                 "window": window_trace.attn_op(z, "window"),
                 "held": window_trace.held_op(z)}
        trace = ctx["trace"]["trace"]
        plane = xplane.device_planes(trace)[0]
        a, b = spans[0]
        ops, totals = [], dict.fromkeys(list(tests) + ["other"], 0.0)
        for name, start, dur, _ in sorted(
                trace["planes"][plane].get(xplane.OPS_LINE, []),
                key=lambda e: e[1]):
            if a <= start < b:
                kind = next((k for k, t in tests.items() if t(name)),
                            "other")
                totals[kind] += dur / 1e3
                ops.append([kind, round(dur / 1e3, 2), name[:400]])
        out.update(event_us=(b - a) / 1e3, totals_us=totals, ops=ops)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"window_ops_{args.seed}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
