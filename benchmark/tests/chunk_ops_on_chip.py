#!/usr/bin/env python3
"""What ``benchmark/chunk_trace.py`` reads, shown op by op, on the chip:
one traced run of the cell, and beside its result line a file
``chiprun_out/chunk_ops_<seed>.json`` with every op of the FIRST
decode-side program of the traced window as the reader pairs it (the
HLO text the profiler names it by, cut to 400 characters, its device
microseconds and the store the reader books it under), each store's
total, how many programs and dispatches the trace and the records hold,
and the counters. For a builder who changes the program's shapes or the
reader's patterns: no metric is defined here.

    python3 benchmark/tests/chunk_ops_on_chip.py --seed 4100000101
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = "evabyte.bytereason-offline"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args()
    from benchmark import (chunk_trace, engine_phases, engine_trace, harness,
                           run, xplane)
    kept = {}
    real = harness.result_line

    def keep(cell, device, trace_on, outcome, ctx):
        kept["ctx"] = ctx
        return real(cell, device, trace_on, outcome, ctx)

    harness.result_line = keep
    line = run.run_cell(CELL, args.seed, args.seconds, True)
    ctx = kept["ctx"]
    trace = ctx["trace"]["trace"]
    plane = xplane.device_planes(trace)[0]
    recs = engine_phases.traced_records(ctx) or []
    out = {"programs_in_trace": sum(
               e[0].startswith(engine_trace.PROGRAM)
               for e in trace["planes"][plane].get(xplane.MODULES_LINE, [])),
           "dispatches_in_records": [d for r in recs
                                     for d in r.get("dispatches", [])],
           "counters": chunk_trace.counters(ctx)}
    got = chunk_trace.pairs(ctx) or []
    out["decode_side_programs"] = len(got)
    if got:
        test = chunk_trace.classify(chunk_trace.sizes(ctx))
        p = got[0]
        ops, totals = [], {"ring": 0.0, "summary": 0.0, "other": 0.0}
        for name, start, dur, _ in sorted(
                trace["planes"][plane].get(xplane.OPS_LINE, []),
                key=lambda e: e[1]):
            if p.start <= start < p.end:
                kind = test(name) or "other"
                totals[kind] += dur / 1e3
                ops.append([kind, round(dur / 1e3, 2), name[:400]])
        out.update(kind=p.kind, event_us=(p.end - p.start) / 1e3,
                   totals_us=totals, ops=ops)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"chunk_ops_{args.seed}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
