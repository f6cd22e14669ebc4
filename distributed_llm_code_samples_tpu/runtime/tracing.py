"""Span tracing: where one serving request's latency goes
(``SpanTracer``), and where one engine step's or one trainer call's
host time goes (``PhaseTimer``, at the end of this file).

PR 5's ``request`` records say WHAT happened to a request (admitted /
quarantined / completed); nothing says where its wall-clock went —
queue time vs prefill vs decode vs preemption churn. This module is the
missing phase accounting: a ``SpanTracer`` tracks one OPEN span per
uid and emits a schema-v5 ``span`` record every time the request
changes phase, through the same ``TelemetryWriter`` every other record
kind rides.

The span vocabulary (``telemetry.SPAN_NAMES``):

- ``queued`` — submit (or snapshot re-queue) -> admission,
- ``prefill`` — one span PER PREFILL CHUNK (each starts where the
  previous chunk's span ended, so a long prompt's chunk spans tile the
  whole prefill phase, engine steps spent on other slots included),
- ``replay`` — the teacher-forcing window after a re-admission
  (recorded tokens re-fed to rebuild the KV write history),
- ``decode`` — live token generation, one span per contiguous segment
  (a preemption or quarantine ends the segment); segment-ending
  records carry a ``tokens`` extra — under speculative decoding
  (round 12) a segment's step count and its token count diverge, and
  the span is where the per-segment yield lives,
- ``quarantine`` — quarantine -> re-admission (zero-length when the
  retry budget is exhausted and the request fails terminally),
- ``preempt_gap`` — pool-pressure eviction -> re-admission.

**The telescoping-clock contract.** Every transition closes the open
span and opens its successor at the SAME timestamp; the first span
opens at the request's ``t_submit`` and the last closes at the
completion timestamp the ``latency_s`` request record uses. Span
durations therefore sum — exactly, up to rounding — to the request's
recorded latency, which is what lets ``report``'s waterfall view
RECONCILE the phase breakdown against the latency percentiles instead
of presenting two unrelated numbers (the observability analogue of the
repo's differential-testing stance).

**First-token marks (round 15).** The tracer also keeps one
first-token timestamp per uid (``mark_first_token``), set by the
engine at the instant the prefill-completing chunk emits its pick —
the same timestamp that closes the prefill span and opens the first
decode span. Completed ``request`` records carry it as ``ttft_s``
(schema v9), and because the mark sits exactly on a span boundary,
``ttft_s == sum(pre-first-token spans)`` and ``ttft_s +
sum(post-first-token spans) == latency_s`` hold by the same
telescoping argument as the full reconciliation. The mark travels
with the sequence (snapshot v5, handoff v2); when the first token
predates a crash-resume with no persisted mark, ``ttft_s`` is null —
unreconstructable, never invented.

**Crash behavior.** Open spans are process state and die with it;
emitted spans are already on disk. An in-process supervisor restart
replays steps whose spans were already emitted — the replayed records
are byte-identical in ``(uid, span, start_step, step)`` and ``report``
dedups them exactly like replayed ``request`` records. A crash-resume
opens a fresh ``queued`` span at resume time, so the crash gap itself
is visibly unaccounted (the waterfall flags the request unreconciled
rather than inventing a phase for dead time).
"""

from __future__ import annotations

import time
from typing import Callable

import jax


class SpanTracer:
    """Per-uid lifecycle span tracking (one open span per uid).

    ``metrics_fn`` returns the live ``TelemetryWriter`` (or None) at
    emit time — the engine re-binds its writer mid-life
    (``DecodeEngine.run(metrics=...)``), so the tracer must not capture
    it at construction. ``trace_fn(uid)`` returns the uid's causal
    ``trace_id`` (schema v12: every span record pins it — the stitch
    key of the cross-process trace waterfall; None with no trace
    plumbed, e.g. standalone tracer tests). ``tenant_fn(uid)`` returns
    the uid's tenant tag (schema v13: every span record pins it — the
    per-tenant ITL slice reads decode-segment spans by tenant; None
    single-tenant). All methods are host-side and O(1); with no writer
    attached the tracer still tracks phases (close/transition stay
    cheap no-ops on the emit half).
    """

    def __init__(self, metrics_fn: Callable,
                 trace_fn: Callable | None = None,
                 tenant_fn: Callable | None = None):
        self._metrics_fn = metrics_fn
        self._trace_fn = trace_fn
        self._tenant_fn = tenant_fn
        self._open: dict[int, dict] = {}   # uid -> open-span state
        # uid -> wall clock of the FIRST live token (round 15, the
        # TTFT decomposition): marked once at the prefill-completing
        # chunk's emission instant — the SAME timestamp that closes the
        # prefill span and opens the first decode span, so
        # ``ttft = t_first - t_submit`` equals the pre-first-token span
        # sum EXACTLY and ``ttft + post-first-token spans == latency``
        # telescopes by construction. Keyed by uid (not admission), so
        # preemption/retry churn keeps the original first-token time.
        self._first: dict[int, float] = {}

    def open(self, uid: int, span: str, step: int,
             t: float | None = None) -> None:
        """Start ``uid``'s FIRST span (``queued``) at ``t`` (defaults
        to now; pass the request's ``t_submit`` so queue time counts
        from submission, not from bookkeeping)."""
        self._open[int(uid)] = {"span": span, "start_step": int(step),
                                "start_t": time.time() if t is None
                                else float(t)}

    def transition(self, uid: int, span: str, step: int,
                   t: float | None = None, **extra) -> None:
        """Close ``uid``'s open span at ``t`` (emitting its record,
        ``extra`` attached) and open ``span`` at the same instant —
        the telescoping handoff that makes span sums reconcile."""
        uid = int(uid)
        now = time.time() if t is None else float(t)
        cur = self._open.get(uid)
        if cur is not None:
            self._emit(uid, cur, int(step), now, extra)
        self._open[uid] = {"span": span, "start_step": int(step),
                           "start_t": now}

    def mark_first_token(self, uid: int, t: float) -> None:
        """Record ``uid``'s first-token timestamp (idempotent: the
        first mark wins, so a replay re-reaching the prefill boundary
        — or a restore re-installing a persisted mark — never moves
        it)."""
        self._first.setdefault(int(uid), float(t))

    def first_token_t(self, uid: int) -> float | None:
        """The marked first-token wall clock, or None when the first
        token predates this tracer's life (crash-resume without a
        persisted mark — the decomposition is then honestly
        unreconstructable)."""
        return self._first.get(int(uid))

    def pop_first_token(self, uid: int) -> float | None:
        """``first_token_t`` + forget — the terminal-transition form
        (completion / terminal failure / handoff export)."""
        return self._first.pop(int(uid), None)

    def close(self, uid: int, step: int, t: float | None = None,
              **extra) -> None:
        """Close ``uid``'s open span with no successor (completion,
        terminal failure, deadline expiry)."""
        uid = int(uid)
        cur = self._open.pop(uid, None)
        if cur is None:
            return
        now = time.time() if t is None else float(t)
        self._emit(uid, cur, int(step), now, extra)

    def _emit(self, uid: int, cur: dict, end_step: int, end_t: float,
              extra: dict) -> None:
        metrics = self._metrics_fn()
        if metrics is None:
            return
        metrics.span({
            "uid": uid,
            "trace_id": (self._trace_fn(uid) if self._trace_fn
                         is not None else None),
            "tenant": (self._tenant_fn(uid) if self._tenant_fn
                       is not None else None),
            "span": cur["span"],
            "start_step": cur["start_step"],
            "step": end_step,
            "start_t": cur["start_t"],
            "t": end_t,
            "duration_s": round(end_t - cur["start_t"], 6),
            **extra,
        })


# -- step phases -------------------------------------------------------
#
# ``SpanTracer`` follows a REQUEST across steps; nothing above says what
# the host did INSIDE one step. ``PhaseTimer`` is that record, and the
# package's only use of ``jax.profiler.TraceAnnotation``: every phase is
# a host event ``<site>:<name>`` in a profiler trace (``--profile_dir``,
# the benchmark's traced window), on the same clock as the device's
# ``XLA Ops`` / ``XLA Modules`` lines, so an idle gap of the device can
# be put down to the phase the host was in. The profiler stores event
# times relative to the trace's start (``profile_start_time`` in the
# trace's ``Task Environment`` plane, wall-clock ns); a stamp below is
# wall-clock ns, so ``stamp - profile_start_time`` is the event's time.
#
# The engine's vocabulary (``decode/engine.py::step``; ``engine:step``
# is the parent of the rest, ``prefill.*`` / ``decode.*`` repeat per
# dispatch, the speculative verify path takes the ``decode.*`` names; a
# step whose chunk rides with its decode batch runs both dispatches'
# host phases under their own names round ONE ``mixed.*`` launch and
# wait; since PR 38 a launch's ``*.readback`` and the ``prefill.book``
# / ``decode.emit`` that fold what it read come AFTER the next launch's
# ``*.dispatch``, in the next step's stamps where the read waited a
# step):
#
#   host    expire  admit  prefill.cow  prefill.book  decode.marshal
#           decode.cow  decode.emit  digest
#   launch  prefill.upload  prefill.dispatch  decode.upload
#           decode.dispatch  mixed.upload  mixed.dispatch
#   wait    prefill.readback  decode.readback  mixed.readback
#
# ``host`` neither feeds nor waits for the device, ``launch`` hands it
# operands and a program, ``wait`` blocks on its results (the classes
# are told by the suffix: ``benchmark/engine_phases.py::phase_class``).
#
# Which program a ``*.dispatch`` launched is in the step's
# ``dispatches`` (``engine._launch``; the ``engine_step`` record and
# the flight digest carry it): ``[kind, bucket]`` a launch, in launch
# order, ``kind`` one of ``decode`` / ``prefill`` / ``mixed`` /
# ``verify`` and ``bucket`` the key the program was built under (the
# batch bucket; the chunk bucket for ``prefill``). The i-th entry
# belongs to the i-th ``*.dispatch`` phase of the step's ``phases``;
# ``dispatches`` stays with the step that LAUNCHED the program. One
# device runs the launches in that order, so the k-th entry of a run
# of steps is also the k-th step program of a device trace of those
# steps: a reader joins the two by ORDER and needs no clock.
#
# What the step's launched rows read of the cache is in its
# ``window_rows`` / ``full_rows`` (``engine._count_rows``; telemetry
# v21: the cached positions they attend over in a window layer, at most
# the window a row, and in a full one, a chunk's one view counted once),
# beside ``window_blocks_released`` / ``window_blocks_live``, the
# window pool's turnover; all 0 for a model with no window layer. They
# are counts the host has at launch: no phase of their own. So are
# ``kv_blocks_read`` / ``kv_blocks_capacity`` (``engine._count_blocks``;
# telemetry v22): the pool's blocks the decode-side reads of the
# launched rows fetched, and what a gather of their whole tables reads
# (``ring_blocks_read`` / ``ring_blocks_capacity``, telemetry v24: the
# same two of a window layer's rings).
# And ``summary_rows`` / ``summaries_written`` (telemetry v23): for a
# model whose layers summarise finished chunks, the summaries the
# launched rows attend over and the launched writes that finished one;
# ``window_rows`` then counts a row's own ALIGNED window.
#
# Which launch a ``*.readback`` read is in the step's ``readbacks``
# (``engine._read``; telemetry v20): the launch's ORDINAL among the
# engine's launches (from 0), the i-th entry the i-th ``*.readback``
# phase's. A read may lie in a LATER step's record than its launch:
# the engine launches a step's program and only then reads the one
# launched before it (``engine.step``'s docstring says which reads
# wait), so in steady state a record holds ONE ``*.dispatch`` and,
# after it, the ``*.readback`` of the previous record's launch. The
# record's ``launches`` counts the engine's launches up to and with
# the step's own: its ``dispatches`` are the ordinals ``launches -
# len(dispatches) ..< launches``, and a readback below them was
# launched by an earlier step. Every launch is read exactly once, in
# launch order; a read made between two steps (``engine.collect``, an
# export) has an annotation and no stamp, and its ordinal is in no
# record. A reader that pairs the i-th ``*.dispatch`` of a record with
# the i-th ``*.readback`` of the SAME record
# (``benchmark/dispatch_join.py``) pairs a launch with the read of the
# one before it wherever the read waited: pair by ``readbacks``.
# ``cow`` / ``cow_rows`` / ``implant``
# are not step programs: no phase pair, no entry. The trainer's
# sites are annotations only: ``train:clone`` / ``train:run``
# (``parallel/single.py``), ``launch:build`` / ``launch:run``
# (``parallel/launcher.py``).


class _Phase:
    """One open phase (``PhaseTimer.phase``)."""

    __slots__ = ("_timer", "_name", "_annot", "_t0")

    def __init__(self, timer: "PhaseTimer", name: str):
        self._timer, self._name = timer, name

    def __enter__(self):
        timer = self._timer
        label = timer.label(self._name)
        # outside a running trace a TraceAnnotation is a flag check
        self._annot = (jax.profiler.TraceAnnotation(label)
                       if timer.step is None else
                       jax.profiler.TraceAnnotation(label, step=timer.step))
        self._annot.__enter__()
        self._t0 = time.time_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.time_ns()
        self._annot.__exit__(*exc)
        stamps = self._timer.stamps
        if stamps is not None:
            stamps.append([self._name, self._t0, t1])
        return False


class PhaseTimer:
    """Host phases of one site (``engine``, ``train``, ``launch``).

    ``phase(name)`` is the one context manager: a
    ``TraceAnnotation("<site>:<name>")`` round the block, and — once
    ``begin(step)`` has opened a step — ``[name, start_ns, end_ns]``
    (``time.time_ns()``, the ``SpanTracer`` clock family) appended to
    that step's ``stamps`` as the phase CLOSES, so a parent follows its
    children and a name may repeat. A site that never calls ``begin``
    (the trainer's) gets the annotations and keeps nothing. No switch:
    what differs between tracing off and on is whether somebody reads
    the stamps or runs the profiler."""

    def __init__(self, site: str):
        self.site = site
        self.step: int | None = None
        self.stamps: list[list] | None = None
        self._labels: dict[str, str] = {}

    def begin(self, step: int) -> None:
        """Open ``step``: its annotations carry ``step=<n>`` (the
        profiler's ``#step=n#`` stat) and its stamps start empty."""
        self.step = int(step)
        self.stamps = []

    def end(self) -> None:
        """Close the step: its stamps belong to whoever took them. A
        phase run before the next ``begin`` (the engine reading a
        result between two steps) keeps its annotation and no stamp."""
        self.step = None
        self.stamps = None

    def label(self, name: str) -> str:
        """``<site>:<name>``, formatted once per name."""
        label = self._labels.get(name)
        if label is None:
            label = self._labels[name] = f"{self.site}:{name}"
        return label

    def phase(self, name: str) -> _Phase:
        return _Phase(self, name)

    def phase_ms(self) -> dict[str, float]:
        """Milliseconds per phase name over the open step's stamps
        (a repeated name is summed)."""
        out: dict[str, float] = {}
        for name, t0, t1 in self.stamps or ():
            out[name] = out.get(name, 0.0) + (t1 - t0) / 1e6
        return {k: round(v, 4) for k, v in out.items()}
