#!/usr/bin/env python3
"""The control of "how ``correct`` is decided", on the chip at the
cell's own size: a short run of the cell per seed, and beside the
program's numbers the same numbers with the plain reference computed in
a lower precision put in the program's place. Prints every number; a
limit belongs above the program's largest and below the control's
smallest (PERF.md records the readings each limit was set from).

    python3 benchmark/tests/control_on_chip.py --workload <cell> \
        --seeds 11,12,13 --seconds 5 --modes int8,bf16
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--modes", default="int8,bf16")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    if len(seeds) != 1:
        # one process per seed: a process holds the chip, and every
        # seed has weights and an engine of its own
        import subprocess
        for s in seeds:
            rc = subprocess.run([sys.executable, __file__, "--workload",
                                 args.workload, "--seeds", str(s),
                                 "--seconds", str(args.seconds),
                                 "--modes", args.modes]).returncode
            if rc:
                return rc
        return 0
    from benchmark import run
    line = run.run_cell(args.workload, seeds[0], args.seconds, False,
                        control=tuple(args.modes.split(",")))
    print(json.dumps({"seed": seeds[0], "correct": line["correct"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
