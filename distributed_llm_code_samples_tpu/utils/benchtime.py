"""On-chip timing discipline: fence, then read the clock.

JAX returns before the device finishes, so a timing without a fence
measures the enqueue. A caller (a) runs its whole schedule as ONE
compiled program (``lax.scan`` over steps) and (b) fences the timed
window with ``jax.block_until_ready`` on the program's outputs. One
user is left, ``train_real_text.py``; the benchmark (``benchmark/``)
times its windows itself and imports nothing from here (ROADMAP C2).
"""

from __future__ import annotations

import time

import jax


def sync(tree) -> None:
    """Wait until every array in ``tree`` has been computed."""
    jax.block_until_ready(tree)


def steps_per_sec(run_fn, p0, warm, timed, reps: int, steps: int) -> float:
    """Best-of-``reps`` steps/sec of ``run_fn(params, seeds)``: one warm
    call (compile) on the ``warm`` schedule, then ``reps`` timed calls on
    ``timed`` (same length — the jitted run caches on the scan trip
    count), each fenced by ``sync``."""
    out = run_fn(p0, warm)
    sync(out)
    best = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        out = run_fn(out, timed)
        sync(out)
        best = max(best, steps / (time.perf_counter() - t0))
    return best
