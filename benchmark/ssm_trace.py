"""The recurrent-state update inside the decode program, from the trace.

A hybrid model's decode dispatch reads one state row of every recurrent
layer for each ready slot and writes it back (``decode/paged.py::
RecurrentState``; ``ops/ssm.py``). Two things are read here:

- what the PROGRAM says a step's decode dispatches read of the state:
  ``state_bytes`` in its ``engine_step`` span records (telemetry; a
  program that writes none — an older commit, a model with no recurrent
  layer — gives every reader here nothing to read, and they return
  None);
- the device time of the ops that touch the state, inside the decode
  program's own events. The profiler's op events carry no source scope
  (``engine_trace.py``), so the ops are told by their result shapes in
  the HLO instruction text the profiler names them by (``xplane.op_kind``
  labels them for the table): a float32 array whose minor axes are
  the scan state's ``[d_state, inner]`` or the convolution tail's
  ``[(d_conv - 1) * inner]`` (``[d_conv - 1, inner]`` once the program
  has split it), with at least the batch in front — the
  gathered rows, the recurrence over them, the rows scattered back into
  the store. Sizes from the configuration's published keys.
"""

from __future__ import annotations

import re

from . import engine_phases, engine_trace, harness, xplane


def state_bytes(ctx: dict):
    """Mean over the traced steps that dispatched a decode batch of the
    state bytes the program says those dispatches READ, or None."""
    recs = engine_phases.traced_records(ctx)
    if recs is None:
        return None
    got = [r["state_bytes"] for r in recs if r.get("state_bytes")]
    if not got:
        return None
    return sum(got) / len(got)


def decode_intervals(ctx: dict) -> list | None:
    """``[(start_ns, end_ns)]`` of the decode program's events on the
    first device, over the traced steps whose program events were all
    found (the pairing of ``engine_trace.program_seconds``)."""
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
        xplane.MODULES_LINE, [])
        if e[0].startswith(engine_trace.PROGRAM)), key=lambda e: e[1])
    out, j = [], 0
    for step, (_, s0, dur, _) in zip(steps, spans):
        while j < len(mods) and mods[j][1] < s0:
            j += 1
        mine = []
        while j < len(mods) and mods[j][1] < s0 + dur:
            mine.append(mods[j])
            j += 1
        if len(mine) != step.n_prefill + step.n_decode:
            continue
        out += [(e[1], e[1] + e[2]) for e in mine[step.n_prefill:]]
    return out


RESULT = re.compile(r"^%?\S+ = (?P<res>.*?)\s[a-z][a-z0-9\-]*\(")


def state_op(config: dict):
    """``instruction text -> bool``: is one of the op's RESULTS shaped
    like the recurrent state of a batch. The profiler names an op by its
    whole HLO instruction; ``xplane.op_kind`` keeps the first result's
    shape only, and the recurrence itself is a fusion with two (``(f32[b,
    inner], f32[b, d_state, inner])``: the mixer's output and the new
    state), so the whole result type is searched."""
    inner = int(config["mamba_expand"]) * int(config["hidden_size"])
    n = int(config["mamba_d_state"])
    tail = (int(config["mamba_d_conv"]) - 1) * inner
    k1 = int(config["mamba_d_conv"]) - 1
    pat = re.compile(r"f32\[(\d+,)+(%d,%d|%d,%d|%d)\]"
                     % (n, inner, k1, inner, tail))

    def is_state(name: str) -> bool:
        m = RESULT.match(name)
        return bool(m and pat.search(m.group("res")))

    return is_state


def update_seconds(ctx: dict):
    """``(device seconds of the state's ops inside the decode events,
    decode dispatches)`` or None."""
    spans = decode_intervals(ctx)
    ops = update_ops(ctx, spans)
    if not ops:
        return None
    return sum(v[0] for v in ops.values()), len(spans)


def update_ops(ctx: dict, spans: list | None = None) -> dict | None:
    """``{label: [seconds, count]}`` of the state's ops inside the
    decode program's events (``spans``: ``decode_intervals(ctx)``)."""
    if spans is None:
        spans = decode_intervals(ctx)
    if not spans:
        return None
    is_state = state_op(ctx["cell"]["config"])
    red = ctx["trace"]
    plane = xplane.device_planes(red["trace"])[0]
    evs = sorted(red["trace"]["planes"][plane].get(xplane.OPS_LINE, []),
                 key=lambda e: e[1])
    out: dict = {}
    j = 0
    for a, b in spans:
        while j < len(evs) and evs[j][1] + evs[j][2] <= a:
            j += 1
        k = j
        while k < len(evs) and evs[k][1] < b:
            name, start, dur, scope = evs[k]
            if is_state(name):
                acc = out.setdefault(xplane.op_kind(name, scope),
                                     [0.0, 0])
                acc[0] += (min(b, start + dur) - max(a, start)) / 1e9
                acc[1] += 1
            k += 1
    return out
