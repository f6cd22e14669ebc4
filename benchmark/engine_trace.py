"""Which device program was the decode step and which the prefill chunk.

The engine's compiled programs all reach the trace as ``jit_run(<id>)``
on the ``XLA Modules`` line, and the op events carry no source scope
(the ``jax.named_scope`` trail does not survive into this profiler's
op events), so the programs are told apart by what the harness knows:
every traced ``engine.step()`` sits in a host span of its own, the
driver counted that step's prefill and decode dispatches, and inside a
step the engine dispatches the prefill chunk before the decode batch.
"""

from __future__ import annotations

from . import harness, xplane

PROGRAM = "jit_run("


def program_seconds(ctx: dict) -> dict | None:
    """``{"prefill": [seconds, dispatches], "decode": [...]}`` over the
    traced steps whose program events were all found, or None."""
    red, steps = ctx.get("trace"), ctx["values"].get("traced_steps")
    if red is None or not steps:
        return None
    trace = red["trace"]
    planes = xplane.device_planes(trace)
    if not planes:
        return None
    spans = [e for e in xplane.host_events(
        trace, harness.ANNOTATION + "engine.step")
        if e[1] >= red["lo"] and e[1] + e[2] <= red["hi"]]
    mods = sorted((e for e in trace["planes"][planes[0]].get(
        xplane.MODULES_LINE, []) if e[0].startswith(PROGRAM)),
        key=lambda e: e[1])
    out = {"prefill": [0.0, 0], "decode": [0.0, 0]}
    j = 0
    # the i-th traced step is the i-th span (both in time order)
    for step, (_, s0, dur, _) in zip(steps, spans):
        while j < len(mods) and mods[j][1] < s0:
            j += 1
        mine = []
        while j < len(mods) and mods[j][1] < s0 + dur:
            mine.append(mods[j])
            j += 1
        n_pre, n_dec = step.n_prefill, step.n_decode
        if len(mine) != n_pre + n_dec:
            continue        # a program of another kind ran: skip the step
        for k, ev in enumerate(mine):
            kind = "prefill" if k < n_pre else "decode"
            out[kind][0] += ev[2] / 1e9
            out[kind][1] += 1
    return out


def program_ms(ctx: dict, kind: str):
    got = program_seconds(ctx)
    if not got or not got[kind][1]:
        return None
    return 1e3 * got[kind][0] / got[kind][1]
