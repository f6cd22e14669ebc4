"""From a profiler trace (``.xplane.pb``) to numbers.

``load`` flattens the trace into plain lists; every reduction below
works on that structure, so the recorded fixture under ``tests/`` (a
real chip trace cut to a few steps and saved as JSON) exercises the
same code a run does. Only JAX's own ``ProfileData`` reader is used.

Structure: ``{"planes": {plane: {line: [[name, start_ns, dur_ns,
scope], ...]}}}``. ``scope`` is the op's source scope path where a
trace has one; this profiler's op events carry none (their name is the
whole HLO instruction text), so ``load`` leaves it "".
Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO op, ``XLA Modules`` one per executed program.
Host planes hold ``TraceAnnotation`` spans on thread lines.
"""

from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, host_names: tuple[str, ...] = ()) -> dict:
    """Flatten a trace. Host lines keep only events whose name starts
    with one of ``host_names`` (the harness's own annotations) — a host
    plane carries every Python frame otherwise."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes: dict = {}
    for plane in data.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        if not is_dev and not plane.name.startswith("/host:CPU"):
            continue
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            out = []
            for ev in line.events:
                name = ev.name
                if not is_dev:
                    if not name.startswith(host_names):
                        continue
                    out.append([name, ev.start_ns, ev.duration_ns, ""])
                    continue
                out.append([name, ev.start_ns, ev.duration_ns, ""])
            if out:
                lines.setdefault(line.name, []).extend(out)
    return {"planes": planes}


def summarize(path: str, per_line: int = 6) -> dict:
    """What a trace looks like — planes, lines, counts, a few events
    with all their stats. For looking at one trace by hand."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = {
                "n": len(evs),
                "first": [{"name": e.name, "start_ns": e.start_ns,
                           "dur_ns": e.duration_ns,
                           "stats": {k: (v if isinstance(v, (int, float))
                                         else str(v)[:160])
                                     for k, v in e.stats}}
                          for e in evs[:per_line]]}
        out[plane.name] = lines
    return out


def device_planes(trace: dict) -> list[str]:
    return sorted((p for p in trace["planes"] if DEVICE_PLANE.match(p)),
                  key=lambda p: int(DEVICE_PLANE.match(p).group(1)))


def host_events(trace: dict, prefix: str = "") -> list[list]:
    out = []
    for plane, lines in trace["planes"].items():
        if DEVICE_PLANE.match(plane):
            continue
        for evs in lines.values():
            out.extend(e for e in evs if e[0].startswith(prefix))
    return sorted(out, key=lambda e: e[1])


def window(trace: dict, name: str) -> tuple[float, float]:
    """[start, end] ns of the harness's window annotation."""
    evs = host_events(trace, name)
    if not evs:
        raise ValueError(f"no host annotation {name!r} in the trace")
    return float(evs[0][1]), float(evs[0][1] + evs[0][2])


def _clip(evs: list[list], lo: float, hi: float) -> list[tuple]:
    out = []
    for name, start, dur, scope in evs:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((a, b, name, scope))
    return out


def _union(intervals: list[tuple]) -> list[list[float]]:
    merged: list[list[float]] = []
    for a, b, *_ in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy(trace: dict, lo: float, hi: float) -> dict:
    """Per device: seconds in [lo, hi] in which some op ran (the union
    of the ``XLA Ops`` intervals), and the merged busy intervals."""
    out = {}
    for plane in device_planes(trace):
        evs = _clip(trace["planes"][plane].get(OPS_LINE, []), lo, hi)
        merged = _union(evs)
        out[plane] = {"busy_s": sum(b - a for a, b in merged) / 1e9,
                      "intervals": merged}
    return out


HLO_TEXT = re.compile(
    r"^%?(?P<name>[^ ]+) = (?P<shape>\(?[a-z0-9]+\[[0-9,]*\])?.*?"
    r"\s(?P<op>[a-z][a-z0-9\-]*)\(")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def op_kind(name: str, scope: str = "") -> str:
    """A short, stable label for an op event. This profiler names an op
    by its whole HLO instruction text (``%fusion.12 = f32[8,128]{...}
    fusion(...)``): the label is the opcode, the instruction's name
    without its numbers, and the result shape — so the 72 whole-pool
    copies of one program fall under one label."""
    m = HLO_TEXT.match(name)
    if not m:
        return re.sub(r"[.\d]+$", "", name)[:80]
    base = re.sub(r"\.\d+", "", m.group("name"))
    return f"{m.group('op')} {base} {m.group('shape') or ''}".strip()


def is_collective(name: str, scope: str = "") -> bool:
    m = HLO_TEXT.match(name)
    op = m.group("op") if m else name
    return op.startswith(COLLECTIVES)


def op_seconds(trace: dict, lo: float, hi: float, key=None,
               line: str = OPS_LINE) -> dict[str, list]:
    """``{key: [seconds, count]}`` summed over devices, for events of
    ``line`` clipped to [lo, hi]. ``key(name, scope) -> str | None``
    (None drops the event); default ``op_kind``."""
    key = key or op_kind
    out: dict[str, list] = {}
    for plane in device_planes(trace):
        for a, b, name, scope in _clip(
                trace["planes"][plane].get(line, []), lo, hi):
            k = key(name, scope)
            if k is None:
                continue
            acc = out.setdefault(k, [0.0, 0])
            acc[0] += (b - a) / 1e9
            acc[1] += 1
    return out


def exposed_seconds(trace: dict, lo: float, hi: float,
                    is_comm) -> tuple[float, float]:
    """(seconds of collective ops, seconds of them during which no
    other op ran on the same device), averaged over devices."""
    planes = device_planes(trace)
    tot = exp = 0.0
    for plane in planes:
        evs = _clip(trace["planes"][plane].get(OPS_LINE, []), lo, hi)
        comm = _union([e for e in evs if is_comm(e[2], e[3])])
        comp = _union([e for e in evs if not is_comm(e[2], e[3])])
        tot += sum(b - a for a, b in comm)
        j = 0
        for a, b in comm:
            covered = 0.0
            while j < len(comp) and comp[j][1] <= a:
                j += 1
            k = j
            while k < len(comp) and comp[k][0] < b:
                covered += min(b, comp[k][1]) - max(a, comp[k][0])
                k += 1
            exp += (b - a) - covered
    n = max(len(planes), 1)
    return tot / 1e9 / n, exp / 1e9 / n


def idle_gaps(trace: dict, lo: float, hi: float, host_prefix: str,
              top: int = 10) -> list[list]:
    """The device's idle time in [lo, hi] by what the host was doing:
    each gap between busy intervals (first device) is split over the
    harness annotations that overlap it, innermost first; what no
    annotation covers is ``(unannotated)``. ``[[name, seconds], ...]``,
    largest first."""
    planes = device_planes(trace)
    if not planes:
        return []
    merged = busy(trace, lo, hi)[planes[0]]["intervals"]
    gaps, t = [], lo
    for a, b in merged:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    # innermost annotation wins: sort by duration so short spans are
    # tried first
    spans = sorted(((s, s + d, n) for n, s, d, _ in
                    host_events(trace, host_prefix)),
                   key=lambda x: x[1] - x[0])
    starts = sorted(spans)           # by start, for the scan below
    out: dict[str, float] = {}
    for ga, gb in gaps:
        mid = (ga + gb) / 2
        name = "(unannotated)"
        best = None
        for s, e, n in starts:
            if s > mid:
                break
            if e >= mid and (best is None or e - s < best):
                best, name = e - s, n
        out[name] = out.get(name, 0.0) + (gb - ga) / 1e9
    return [[k, v] for k, v in sorted(out.items(),
                                      key=lambda kv: -kv[1])[:top]]


def cut(trace: dict, lo: float, hi: float) -> dict:
    """The part of a trace inside [lo, hi] — for recording a fixture."""
    planes = {}
    for plane, lines in trace["planes"].items():
        planes[plane] = {
            line: [e for e in evs if e[1] + e[2] >= lo and e[1] <= hi]
            for line, evs in lines.items()}
    return {"planes": planes}


def save(trace: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace, f, separators=(",", ":"))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


if __name__ == "__main__":
    # look at one trace by hand: python3 -m benchmark.xplane <trace_dir>
    import sys
    print(json.dumps(summarize(find_xplane(sys.argv[1])), indent=1))
