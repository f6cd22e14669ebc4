"""Which device program a dispatch ran, joined by ORDER.

Since telemetry v19 a traced step's ``engine_step`` record says which
step programs it launched (``dispatches``: ``[kind, bucket]`` a launch,
the i-th entry belonging to the i-th ``*.dispatch`` phase of ``phases``
and the ``*.readback`` after it; ``runtime/tracing.py`` has the
contract). The engine launches them in order on one device and ends
each in a blocking read, and ``harness.Tracer.poll`` starts and stops
the profiler between steps, so the trace holds the traced steps'
programs and no others: **the k-th ``jit_run`` program of the first
device plane is the k-th dispatch of the traced records.** No
device-plane stamp is compared with a host stamp to say so, which is
what ``engine_trace.program_seconds`` has to do and what a device plane
that leads its host plane by a millisecond defeats.

**The join.** The traced records (``engine_phases.traced_records``)
give the window's dispatches in step order, each with the start of its
``*.dispatch`` phase and the end of its ``*.readback``. ALL the
``jit_run(`` events of the first device plane's ``XLA Modules`` line,
in time order, are the window's programs — not cut to ``[lo, hi]``,
a host-plane interval that a leading device plane's first event can
fall outside. Another count on either side, a record without
``dispatches`` (an older program) or no trace: nothing to read, and
every reader returns None.

**The checks that replace trust.**

- Within one ``(kind, bucket)`` the paired device times of a window
  agree closely (one program on one chip): a group whose p90 is over
  ``GROUP_SPREAD`` x its p10 means the pairing is off, and gives None.
- With the records put on the trace's host clock
  (``engine_phases.profiler_shift``), a lead ``L`` of the device plane
  (positive: its stamps are early) is FEASIBLE if no program starts
  before its dispatch phase opened or ends after its readback closed:
  ``L in [max_k(dispatch_start_k - ev_start_k),
  min_k(readback_end_k - ev_end_k)]``. An empty interval means the
  pairing or the clocks are wrong: None. Where the records cannot be
  put on that clock at all, the clock-free readers go on and the two
  that need the lead return None.

One stderr note a run says what was found or why nothing was.
"""

from __future__ import annotations

import sys
from typing import NamedTuple

import numpy as np

from . import engine_phases, engine_trace, xplane

PROGRAM = engine_trace.PROGRAM      # a step program's event: ``jit_run(``
GROUP_SPREAD = 1.25
_KEY = "_dispatch_join"       # the join, kept in ``ctx`` for six readers


class Pair(NamedTuple):
    """One dispatch and the device program it ran."""
    kind: str
    bucket: int
    dispatch_ns: int          # the ``*.dispatch`` phase opened (host)
    readback_ns: int          # the ``*.readback`` phase closed (host)
    start_ns: float           # the program's event (device plane)
    dur_ns: float


class Join(NamedTuple):
    pairs: list
    lead: tuple | None        # feasible [lo, hi] ns on the trace's clock
    shift: int | None         # ns off a record's stamp: the trace's clock

    @property
    def lead_mid(self) -> float:
        return (self.lead[0] + self.lead[1]) / 2


def _note(msg: str) -> None:
    print(f"[dispatch_join] {msg}", file=sys.stderr, flush=True)


def dispatches(recs: list[dict]) -> list | None:
    """``(kind, bucket, dispatch start, readback end)`` of every step
    program the records' steps launched, in launch order; None for a
    record that does not say (a program older than v19: silent) or
    says another number than its phases (noted)."""
    out = []
    for rec in recs:
        said = rec.get("dispatches")
        if said is None:
            return None
        opened = [s for name, s, _ in rec["phases"]
                  if name.endswith(".dispatch")]
        closed = [e for name, _, e in rec["phases"]
                  if name.endswith(".readback")]
        if not len(said) == len(opened) == len(closed):
            _note(f"a record names {len(said)} dispatches beside "
                  f"{len(opened)} *.dispatch and {len(closed)} "
                  "*.readback phases: no join")
            return None
        out.extend((kind, bucket, s, e)
                   for (kind, bucket), s, e in zip(said, opened, closed))
    return out


def programs(trace: dict) -> list | None:
    """Every step-program event of the first device plane, in time
    order; None for a trace with no device plane."""
    planes = xplane.device_planes(trace)
    if not planes:
        return None
    return sorted((e for e in trace["planes"][planes[0]].get(
        xplane.MODULES_LINE, []) if e[0].startswith(PROGRAM)),
        key=lambda e: e[1])


def feasible_lead(pairs: list, shift: int) -> tuple:
    """``(lo, hi)`` ns: the leads of the device plane under which every
    program lies inside its dispatch's launch-to-read interval."""
    lo = max(p.dispatch_ns - shift - p.start_ns for p in pairs)
    hi = min(p.readback_ns - shift - (p.start_ns + p.dur_ns)
             for p in pairs)
    return lo, hi


def _join(ctx: dict) -> Join | None:
    red = ctx.get("trace")
    recs = engine_phases.traced_records(ctx)
    if red is None or recs is None:
        return None
    said = dispatches(recs)
    if said is None:
        return None
    events = programs(red["trace"])
    if events is None:
        return None
    if len(events) != len(said) or not said:
        _note(f"{len(said)} dispatches in {len(recs)} traced records, "
              f"{len(events)} {PROGRAM}..) programs in the trace: "
              "no join")
        return None
    pairs = [Pair(kind, bucket, s, e, ev[1], ev[2])
             for (kind, bucket, s, e), ev in zip(said, events)]
    groups: dict = {}
    for p in pairs:
        groups.setdefault((p.kind, p.bucket), []).append(p.dur_ns)
    for key, ns in sorted(groups.items()):
        p10, p90 = np.quantile(ns, [0.1, 0.9])
        if p90 > GROUP_SPREAD * p10:
            _note(f"{key[0]}({key[1]}): {len(ns)} paired programs, p10 "
                  f"{p10 / 1e6:.3f} ms, p90 {p90 / 1e6:.3f} ms: the "
                  "pairing is off, no join")
            return None
    shift = engine_phases.profiler_shift(ctx, recs)
    lead = None
    if shift is None:
        _note(f"{len(pairs)} programs paired; the records cannot be put "
              "on the trace's clock: no lead")
    else:
        lead = feasible_lead(pairs, shift)
        if lead[0] > lead[1]:
            _note(f"{len(said)} dispatches, {len(events)} programs: no "
                  f"lead of the device plane fits them all (at least "
                  f"{lead[0] / 1e6:.4f} ms, at most {lead[1] / 1e6:.4f} "
                  "ms): the pairing or the clocks are wrong, no join")
            return None
        _note(f"{len(pairs)} programs paired by order; device plane "
              f"leads by {lead[0] / 1e6:.4f} to {lead[1] / 1e6:.4f} ms "
              f"(width {(lead[1] - lead[0]) / 1e6:.4f} ms)")
    return Join(pairs, lead, shift)


def joined(ctx: dict) -> Join | None:
    """The traced window's dispatches, each with its program, or None
    where there is nothing to read or a check fails."""
    if _KEY not in ctx:
        ctx[_KEY] = _join(ctx)
    return ctx[_KEY]


def program_ms(ctx: dict, kind: str):
    """Mean device milliseconds of the programs the ``kind`` dispatches
    ran (every bucket in one mean)."""
    got = joined(ctx)
    if got is None:
        return None
    ns = [p.dur_ns for p in got.pairs if p.kind == kind]
    return float(np.mean(ns)) / 1e6 if ns else None


def overhead_ms(ctx: dict):
    """Mean over dispatches of the launch-to-read host interval less
    the program's device time: what a launch and a blocking read cost
    beyond the program. A host duration less a device duration: no
    clock is compared with another."""
    got = joined(ctx)
    if got is None:
        return None
    return float(np.mean([p.readback_ns - p.dispatch_ns - p.dur_ns
                          for p in got.pairs])) / 1e6


def lead_ms(ctx: dict):
    """The midpoint of the feasible leads of the device plane."""
    got = joined(ctx)
    if got is None or got.lead is None:
        return None
    return got.lead_mid / 1e6


def launch_latency_ms(ctx: dict):
    """Mean over dispatches of the program's start, moved by the lead's
    midpoint, after its dispatch phase opened: known to half the
    feasible interval's width (the join's note prints it). The return
    side is ``overhead_ms`` less this."""
    got = joined(ctx)
    if got is None or got.lead is None:
        return None
    return float(np.mean([
        p.start_ns + got.lead_mid - (p.dispatch_ns - got.shift)
        for p in got.pairs])) / 1e6
