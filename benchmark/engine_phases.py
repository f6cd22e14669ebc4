"""The engine's own step spans, read for the traced window.

Since telemetry v18 the engine emits one ``engine_step`` span record a
step to an attached writer (``serve.py::_Collector`` in traced runs):
the parent span and its phases as ``[name, start_ns, end_ns]`` on
``time.time_ns()``. A program that emits none (an older commit) gives
every reader here nothing to read: they return None and the line leaves
the metric out.

**Which records.** The traced steps are joined to their records on
``tokens_generated`` (the driver's ``Step.tokens`` and the record both
hold the engine's counter after the step): the one run of consecutive
records whose counters equal the traced steps'. No clock is needed, and
no run or more than one means no number.

**Which clock.** The profiler stores event times relative to the
trace's start, and the harness keeps neither that start nor the
program's own ``engine:`` events, so a record is put on the profiler's
clock by the ``bench:engine.step`` event that wraps each traced step:
one shift has to place EVERY record inside its own event (the span
opens a few microseconds after the driver's and closes before it, so
the shifts that fit lie within some tens of microseconds of each other;
the latest is taken, which leaves the engine's span as near the
driver's opening as the closest step had it). If no single shift fits,
the clocks moved against each other and the device-idle readers return
None. The span-only readers never need it.

**Phase classes** (``runtime/tracing.py`` has the vocabulary): ``launch``
hands the device operands and a program (``*.upload``, ``*.dispatch``),
``wait`` blocks on its results (``*.readback``), everything else is
``host`` work that neither feeds nor waits for the device.
"""

from __future__ import annotations

from . import harness, xplane

STEP_SPAN = "engine_step"
STEP_EVENT = harness.ANNOTATION + "engine.step"
HOST, LAUNCH, WAIT = "host", "launch", "wait"


def phase_class(name: str) -> str:
    if name.endswith((".upload", ".dispatch")):
        return LAUNCH
    if name.endswith(".readback"):
        return WAIT
    return HOST


def traced_records(ctx: dict) -> list[dict] | None:
    """The traced steps' ``engine_step`` records, in step order, or
    None unless every traced step found its record."""
    steps = ctx["values"].get("traced_steps")
    recs = [s for s in ctx.get("spans", []) if s.get("span") == STEP_SPAN]
    if not steps or not recs:
        return None
    want = [st.tokens for st in steps]
    have = [r["tokens_generated"] for r in recs]
    n = len(want)
    at = [i for i in range(len(have) - n + 1) if have[i:i + n] == want]
    if len(at) != 1:
        return None
    return recs[at[0]:at[0] + n]


def mean_ms(ctx: dict, what: str):
    """Mean milliseconds per traced step of one class of its phases,
    or of the whole span (``what="span"``: what the classes are held
    against; no metric of its own, ``engine_step_ms.offline`` times the
    same call from outside)."""
    recs = traced_records(ctx)
    if recs is None:
        return None
    if what == "span":
        ns = sum(r["end_ns"] - r["start_ns"] for r in recs)
    else:
        ns = sum(e - s for r in recs for name, s, e in r["phases"]
                 if phase_class(name) == what)
    return ns / len(recs) / 1e6


def profiler_shift(ctx: dict, recs: list[dict]):
    """Nanoseconds to take off a record's stamps to put them on the
    trace's clock, or None unless one shift puts every record inside
    the ``bench:engine.step`` event of its step."""
    red = ctx.get("trace")
    if red is None:
        return None
    events = [e for e in xplane.host_events(red["trace"], STEP_EVENT)
              if e[1] >= red["lo"] and e[1] + e[2] <= red["hi"]]
    if len(events) != len(recs):
        return None
    latest = min(r["start_ns"] - e[1] for r, e in zip(recs, events))
    earliest = max(r["end_ns"] - (e[1] + e[2])
                   for r, e in zip(recs, events))
    if earliest > latest:
        return None
    return latest


def device_gaps(trace: dict, lo: float, hi: float) -> list | None:
    """The intervals of [lo, hi] in which the first device ran no op,
    in time order; None for a trace with no device plane."""
    planes = xplane.device_planes(trace)
    if not planes:
        return None
    gaps, t = [], lo
    for a, b in xplane.busy(trace, lo, hi)[planes[0]]["intervals"]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def idle_ns(ctx: dict) -> dict | None:
    """``{phase class: ns}``: the first device's idle time inside
    the traced window, each gap between its busy intervals split over
    the phases that overlap it (what no phase covers — the driver's own
    part of the loop — goes nowhere)."""
    recs = traced_records(ctx)
    if recs is None:
        return None
    shift = profiler_shift(ctx, recs)
    if shift is None:
        return None
    red = ctx["trace"]
    gaps = device_gaps(red["trace"], red["lo"], red["hi"])
    if gaps is None:
        return None
    # phases of consecutive steps are in time order and disjoint, the
    # gaps too: one pass over both
    phases = [(s - shift, e - shift, phase_class(name))
              for r in recs for name, s, e in r["phases"]]
    out: dict = {}
    j = 0
    for a, b in gaps:
        while j < len(phases) and phases[j][1] <= a:
            j += 1
        k = j
        while k < len(phases) and phases[k][0] < b:
            s, e, label = phases[k]
            out[label] = out.get(label, 0.0) + max(
                0.0, min(b, e) - max(a, s))
            k += 1
    return out


def idle_pct(ctx: dict, classes: tuple):
    """The share of the traced window, in percent, in which the device
    ran no op while the program was in a phase of ``classes``."""
    got = idle_ns(ctx)
    if got is None:
        return None
    red = ctx["trace"]
    return (100.0 * sum(got.get(c, 0.0) for c in classes)
            / (red["hi"] - red["lo"]))
