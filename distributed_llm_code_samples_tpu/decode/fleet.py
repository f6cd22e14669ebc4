"""Fleet-scale serving: a host-side router over N decode-engine
replicas, with disaggregated prefill/decode and KV-handoff migration —
round 16: across a REAL process boundary.

One ``DecodeEngine`` is not "heavy traffic from millions of users":
aggregate tokens/s scales only with what a single engine holds, and a
long prefill still steals a step from every running decode on the same
engine. This module is data parallelism one level up — the dp axis of
the training meshes (SNIPPETS.md [3]'s dp x mp factorization) applied
at the REQUEST level — plus the DistServe/Splitwise disaggregation
argument: prefill is compute-bound and bursty, decode is memory-bound
and steady, so co-locating them trades throughput for interference.

The router drives every replica through ONE handle API, with three
transports behind it:

- **In-process** (``EngineHandle``): the engine lives in the router's
  process; the PR 10 fleet, unchanged in behavior, now expressed
  through the same driver surface the process transport uses.
- **In-process + wire docs** (``wire_dir=``): every live KV move
  serializes through the versioned npz wire format
  (``runtime/wire.py`` — per-array CRC-32, atomic publish) and imports
  from the file. Same process, real serialization boundary: the bench
  floor for the transport, and the cheap test surface for wire
  rejection.
- **Process workers** (``decode/worker.py``): each engine runs in its
  own OS process behind a socket protocol
  (``ProcessEngineHandle``); KV crosses as wire files, an engine kill
  is a real SIGKILL, and a silent worker is a real hung peer. The
  router's liveness ladder (per-call deadlines -> bounded
  ``failure.backoff_delay`` retries -> declare dead -> SIGKILL ->
  migrate-from-last-snapshot) is what turns "a process stopped
  answering" into "every request still completes token-identically".

The three routing/migration moves, each riding machinery earlier
rounds already built:

- **Routing** (``FleetRouter.submit``): least-loaded admission over the
  per-engine digests the handles report (queue depth, occupancy, pool
  utilization), session affinity, and **prefix affinity** — the router
  probes every engine's radix tree (``warm_blocks``) and sends a
  sharer where the prefix is warm, so PR 9's ~1-prefill property holds
  FLEET-wide. A full target spills to the next-best engine; all-full
  sheds at the door (the serving 503).

- **Disaggregated prefill/decode** (``prefill_engines=M``): M dedicated
  prefill engines run the chunked prefill; the moment a prompt
  completes, the sequence ships to a decode engine via the
  **single-sequence KV handoff** (``DecodeEngine.export_sequence`` /
  ``import_sequence``, handoff doc v3 over the wire format). Decode
  engines execute ZERO prefill dispatches.

- **Migration as the same primitive**: pool exhaustion moves the
  youngest running sequence to a peer with capacity (live, no replay);
  a dead engine — dropped object or SIGKILLed process — migrates its
  in-flight requests to survivors from the router's last snapshot of
  it, where replay fills the gap since that snapshot and continues
  token-identically. The sampling keys fold ``(seed, uid, position)``
  — never the slot OR the engine — so a migrated sequence's remaining
  tokens match the un-migrated oracle bit for bit at every kv_dtype.

**Chaos at the boundary** (``fleet_chaos=``, the ``--fleet_chaos``
grammar, ``runtime/chaos.py`` FLEET_KINDS): ``kill_worker@R[:IDX]``
SIGKILLs a decode worker at the start of round R; ``hang_worker@R[:S]``
makes one go silent (the liveness ladder must declare it dead);
``corrupt_wire@R`` bit-flips the next wire handoff in transit (the CRC
layer must reject it with a named reason and the request must be
replay-rerouted with no partial import). The tier-1 drill kills one of
three worker PROCESSES mid-stream and pins byte-identical output
against the unkilled oracle.

**Live weight hot-swap** (round 17, ``rolling_deploy`` /
``schedule_deploy``, DESIGN.md section 23): publish a checkpoint (the
trainer's existing atomic fsync+CRC publish) and roll it through the
serving fleet with ZERO shed — drain one engine at a time over the
same KV handoff (waiting/mid-prefill requests move by
``release_request`` replay), swap its double-buffered weights to the
ledger-verified step, re-admit. In-flight requests finish on their
pinned ``weights_version`` wherever they land; new admissions take
the deployed one. A CRC-rejected target step — or any mid-roll
failure, a dying worker included — rolls every swapped engine back
with one named-reason ``rolled_back`` deploy record: no engine left
mixed. Chaos ``corrupt_deploy@R`` drills the torn-checkpoint path.

Every router decision emits one schema-v11 ``router`` record; live
moves carry ``blocks``/``bytes``/``duration_s`` plus the pinned
``transport`` attribution ({mode, bytes, crc_verify_s, retries} —
``bytes`` is the SERIALIZED size, what actually crosses the boundary);
a CRC rejection emits a ``wire_rejected`` record naming the reason.
Each round additionally emits one ``fleet`` health record and each
deploy its lifecycle ``deploy`` records.
``report router eng0 ...`` folds them onto the merged timeline
(DESIGN.md sections 20-23).
"""

from __future__ import annotations

import collections
import os
import time

from ..runtime import wire
from ..runtime.telemetry import (ROUTER_POSTMORTEM_PREFIX,
                                 STATUS_FILENAME)
from ..runtime.wire import WireError
from .engine import AdmissionError, DecodeEngine
from .supervise import snapshot_state

# engine-id prefixes: prefill tier "p", decode tier "e" (unified
# engines are decode-tier — they can prefill too)
DECODE_PREFIX = "e"
PREFILL_PREFIX = "p"

# hang_worker's default silence floor (seconds) when the spec has no
# :SECS. The ACTUAL default is derived from the target handle's
# per-call deadline at fire time (2.5x covers the deadline, its
# bounded-backoff retry, and scheduling slack) so the liveness ladder
# is GUARANTEED to declare the worker dead before it wakes — a fixed
# constant shorter than the transport's deadline would just stall the
# run and never fire the ladder it exists to drill
HANG_WORKER_DEFAULT_S = 30.0


class TransportError(RuntimeError):
    """A worker transport call failed (the process boundary's failure
    surface). The router's liveness ladder converts these into a
    dead-host declaration + migrate-from-last-snapshot."""


class TransportTimeout(TransportError):
    """A call (or its bounded-backoff retries) overran its deadline —
    the silent-worker signature."""


class TransportDead(TransportError):
    """The peer is gone (EOF / reset / process exited)."""


class HandoffRef:
    """One exported sequence in transit: either the in-process document
    itself (``doc``) or a published wire file (``path``), plus the
    scalar facts the router records either way."""

    __slots__ = ("uid", "position", "blocks_written", "doc", "path")

    def __init__(self, uid: int, position: int, blocks_written: int,
                 doc: dict | None = None, path: str | None = None):
        self.uid = uid
        self.position = position
        self.blocks_written = blocks_written
        self.doc = doc
        self.path = path


class EngineHandle:
    """One IN-PROCESS fleet member: the engine, its role, its liveness,
    and the driver API the router speaks (``decode/worker.py``'s
    ``ProcessEngineHandle`` implements the same surface over a socket).
    A killed handle drops its engine object outright — the in-process
    simulation of a dead host — keeping only the last snapshot the
    router migrates from."""

    transport = "inproc"

    def __init__(self, eid: str, engine: DecodeEngine, role: str,
                 wire_dir: str | None = None):
        self.id = eid
        self.engine = engine
        self.role = role                    # "prefill" | "decode"
        self.alive = True
        self.retired = False                # drained out, not dead
        self.snapshot: dict | None = None   # last snapshot_state doc
        self.killed_at_round: int | None = None
        self.last_tokens = 0                # decode-record cadence state
        self.last_t = time.perf_counter()
        # wall time of THIS engine's slice of the last fleet round —
        # the per-engine number the interference bench reads (the
        # round-robin loop serializes engines in-process, so timing a
        # whole round would charge every engine for its neighbors)
        self.last_step_s = 0.0
        # wire_dir set => every export serializes through the versioned
        # wire format and every import reads + CRC-verifies the file
        # (the in-process floor for the process transport)
        self.wire_dir = wire_dir
        self._did = False
        self._seq = 0
        # staged handoffs awaiting commit_import (async migration):
        # uid -> (verified doc, wire stats, spool path or None, mode)
        self._staged: dict[int, tuple] = {}

    # -- identity / validation ----------------------------------------

    def model_meta(self) -> dict:
        return self.engine.model_meta()

    def validate_member(self) -> None:
        if self.engine.mesh is not None:
            raise ValueError("fleet replicas are single-device "
                             "(KV handoff has no TP path)")

    # -- weight lifecycle (round 17, DESIGN.md section 23) -------------

    @property
    def serving_version(self) -> int:
        return self.engine.serving_version

    def load_weights(self, version: int, ckpt_dir: str, step: int,
                     params=None) -> dict:
        """Install checkpoint step ``step`` as weights version
        ``version``. In-process the ROUTER loads the checkpoint once
        per deploy and passes the params object here (read-only across
        replicas — engine programs donate only the pool); the process
        transport sends the recipe and each worker restores from the
        shared checkpoint dir itself (weights never ride the
        socket)."""
        if params is None:
            from ..runtime.weights import VersionLedger
            params = VersionLedger(ckpt_dir).load(step,
                                                  self.engine.params)
        return self.engine.load_weights(version, params)

    def set_serving_version(self, version: int) -> None:
        self.engine.set_serving_version(version)

    # -- reads ---------------------------------------------------------

    @property
    def has_work(self) -> bool:
        return self.alive and bool(self.engine.waiting
                                   or self.engine.active)

    def digest(self, light: bool = False) -> dict:
        """The scheduler-state view every routing decision reads —
        computed live in-process; the process transport returns the
        digest riding each worker response (same keys, zero extra
        round-trips, the flag ignored there — cached is cached).
        ``light=True`` skips the per-slot list for the hot-path scalar
        reads (load keys, capacity probes, fleet records) — the O(1)
        admission-path discipline. ``tokens_generated`` rides every
        digest (one int) so the live status doc's last-interval
        throughput costs zero extra round-trips."""
        e = self.engine
        d = {
            "waiting": len(e.waiting),
            "active": e.active,
            "serving_version": e.serving_version,
            "tokens_generated": e.tokens_generated,
            "free_slots": sum(1 for s in e.slots if s is None),
            "free_blocks": len(e.free_blocks),
            "evictable": (e.prefix.evictable_blocks()
                          if e.prefix is not None else 0),
            "utilization": e.kv_pool_utilization(),
            # KV spill tier (round 23, schema v17): host-tier occupancy
            # + cumulative clean restores — zeros when the tier is off
            "spill_tier_blocks": (0 if e.spill is None
                                  else len(e.spill)),
            "spill_restores": e.restores,
            "head": ({"prompt_len": len(e.waiting[0].prompt),
                      "max_new": e.waiting[0].max_new}
                     if e.waiting else None),
            # per-tenant live counts (schema v13; empty single-tenant)
            # — the in-flight half of the status doc's tenants block,
            # riding the digest so it costs zero extra round-trips
            "tenants": e.tenant_load(),
        }
        if not light:
            d["slots"] = [{"uid": s.uid, "prompt_done": s.prompt_done,
                           "admit_index": s.admit_index,
                           "prompt_len": len(s.prompt),
                           "max_new": s.max_new}
                          for s in e.slots if s is not None]
        return d

    def blocks_needed(self, prompt_len: int, max_new: int) -> int:
        return self.engine._blocks_needed(prompt_len, max_new)

    def max_blocks_per_seq(self) -> int:
        return self.engine.cfg.max_blocks_per_seq

    def warm_blocks(self, prompt) -> int | None:
        """Radix-tree warm-path depth for ``prompt`` (None when the
        prefix cache is off) — the prefix-affinity probe, under the
        SERVING version's root: a fresh admission pins the serving
        version, so retired versions' cached blocks must not count as
        warm (they can never be its hits) and the new version's must.
        Host-side read only; probing never steps an engine."""
        if self.engine.prefix is None:
            return None
        return self.engine.prefix.warm_blocks(
            prompt, self.engine.serving_version)

    # -- scheduling ----------------------------------------------------

    def submit(self, prompt, max_new: int, uid: int,
               trace: str | None = None,
               tenant: str | None = None) -> dict:
        """Submit; returns the WAITING snapshot entry for the router's
        O(1) snapshot-append discipline (raises ``AdmissionError`` on a
        full queue — the caller's spillover path). ``trace`` is the
        router-minted trace id the engine records verbatim; ``tenant``
        the request's tenant tag (schema v13)."""
        self.engine.submit(prompt, max_new, uid=uid, trace=trace,
                           tenant=tenant)
        seq = next(s for s in reversed(self.engine.waiting)
                   if s.uid == uid)
        return {"uid": seq.uid, "prompt": seq.prompt, "out": seq.out,
                "max_new": seq.max_new, "retries": seq.retries,
                "t_submit": seq.t_submit,
                "submit_step": seq.submit_step,
                "t_first": None,       # no first token yet
                "weights_version": None,   # pins at admission
                "trace_id": seq.trace_id,
                "tenant": seq.tenant,
                "state": "WAITING"}

    def resume_request(self, uid: int, prompt, max_new: int, *, out=(),
                       retries: int = 0, t_submit=None,
                       t_first=None, weights_version=None,
                       trace=None, tenant=None) -> None:
        self.engine.resume_request(uid, prompt, max_new, out=out,
                                   retries=retries, t_submit=t_submit,
                                   t_first=t_first,
                                   weights_version=weights_version,
                                   trace=trace, tenant=tenant)

    def release_request(self, uid: int) -> dict:
        """The drain primitive's replay half (rolling deploy): pop one
        live request off the engine, returning its replay entry."""
        return self.engine.release_request(uid)

    def step_begin(self, prefill_only: bool = False) -> None:
        """First half of one fleet-round step. In-process the step runs
        here (synchronously); the process transport SENDS the step to
        the worker so all workers step concurrently and ``step_end``
        collects."""
        t0 = time.perf_counter()
        self._did = self.engine.step(prefill_only=prefill_only)
        self.last_step_s = time.perf_counter() - t0

    def step_end(self) -> bool:
        return self._did

    def fetch_snapshot(self) -> dict:
        return snapshot_state(self.engine)

    # -- the KV handoff ------------------------------------------------

    def export(self, uid: int, keep: bool = False) -> HandoffRef:
        """Export one resident fully-prefilled sequence. With a
        ``wire_dir`` the document is serialized + atomically published
        as a wire file (per-array CRC-32); otherwise the doc rides
        in-process. ``keep=True`` is the async-migration ship-half:
        the sequence STAYS resident and decoding while its snapshot
        crosses (``finish_export`` settles up at commit time)."""
        doc = self.engine.export_sequence(uid, keep=keep)
        ref = HandoffRef(uid, int(doc["position"]),
                         int(doc["blocks_written"]))
        if self.wire_dir is None:
            ref.doc = doc
        else:
            import os
            os.makedirs(self.wire_dir, exist_ok=True)
            self._seq += 1
            ref.path = os.path.join(
                self.wire_dir, f"handoff_{self.id}_{uid}_{self._seq}.npz")
            wire.write_doc(ref.path, doc)
        return ref

    def import_doc(self, ref: HandoffRef) -> dict:
        """Import a handoff; returns the transport attribution
        ({mode, crc_verify_s, and — off the wire — bytes}). A doc-
        passing move reports no bytes here: the caller computes the
        serialized size OUTSIDE its timed window (``_move``), so the
        in-process stall numbers stay an honest floor for the wire
        lane instead of quietly including a serialization of their
        own. Raises ``WireError`` (one-line named reason) on a
        torn/corrupted wire file, BEFORE any engine state is
        touched."""
        if ref.doc is not None:
            self.engine.import_sequence(ref.doc)
            return {"mode": "inproc", "crc_verify_s": None}
        stats: dict = {}
        doc = wire.read_doc(ref.path, stats)    # raises WireError
        self.engine.import_sequence(doc)
        import os
        try:
            # consumed; a REJECTED file is kept for post-mortem by the
            # router's bounded retention instead (renamed *.rejected,
            # oldest pruned past keep_rejected — FleetRouter._move)
            os.unlink(ref.path)
        except OSError:
            pass
        return {"mode": "wire", "bytes": stats["bytes"],
                "crc_verify_s": stats["crc_verify_s"]}

    # -- async migration (round 22, DESIGN.md section 28) --------------

    def export_keep(self, uid: int) -> HandoffRef:
        """Ship-half of an async migration: export WITHOUT evicting
        (the worker handle names this op the same way — the router
        calls one method on either transport)."""
        return self.export(uid, keep=True)

    def finish_export(self, uid: int) -> dict:
        """Commit-half of an async migration on the SOURCE: evict now
        and return the final token list (status ``"resident"``), or
        the abort status when the request finished/failed/was
        preempted during the ship window."""
        return self.engine.finish_export(uid)

    def stage_ref(self, ref: HandoffRef) -> dict:
        """Stage a shipped handoff on the TARGET for a later
        ``commit_import``: integrity-verify NOW (the wire CRC ladder
        for a file; a doc-mode ref is already in-memory) and park the
        verified document keyed by uid — a corrupt ship must be
        rejected at stage time, never after the source evicted."""
        if ref.doc is not None:
            uid = int(ref.doc["uid"])
            self._staged[uid] = (ref.doc, {}, None, "inproc")
            return {"uid": uid, "mode": "inproc", "bytes": 0,
                    "crc_verify_s": None}
        stats: dict = {}
        doc = wire.read_doc(ref.path, stats)    # raises WireError
        uid = int(doc["uid"])
        self._staged[uid] = (doc, stats, ref.path, "wire")
        return {"uid": uid, "mode": "wire", "bytes": stats["bytes"],
                "crc_verify_s": stats["crc_verify_s"]}

    def stage_bytes(self, data: bytes) -> dict:
        """Stage a handoff shipped as raw wire bytes (the TCP side
        channel) — the identical CRC discipline, off the stream."""
        stats: dict = {}
        doc = wire.deserialize_doc(data, stats)  # raises WireError
        uid = int(doc["uid"])
        self._staged[uid] = (doc, stats, None, "tcp")
        return {"uid": uid, "mode": "tcp", "bytes": stats["bytes"],
                "crc_verify_s": stats["crc_verify_s"]}

    def commit_import(self, uid: int, out=None) -> dict:
        """Import the staged doc. ``out`` (when given) patches the
        token list to the source's FINAL one first — ``emitted`` stays
        at the ship point, so the engine's replay contract teacher-
        forces the delta and rebuilds the window bit-identically (the
        catch-up)."""
        entry = self._staged.pop(int(uid), None)
        if entry is None:
            raise ValueError(f"no staged handoff for uid {uid}")
        doc, stats, path, mode = entry
        if out is not None:
            doc = {**doc, "out": [int(t) for t in out]}
        self.engine.import_sequence(doc)
        if path is not None:
            try:
                os.unlink(path)     # consumed
            except OSError:
                pass
        return {"mode": mode, "bytes": stats.get("bytes", 0),
                "crc_verify_s": stats.get("crc_verify_s"),
                "catchup_tokens": (len(doc["out"])
                                   - int(doc["emitted"]))}

    def discard_stage(self, uid: int) -> bool:
        """Drop a staged handoff (the abort path: the request finished
        or was preempted on the source mid-ship). Idempotent."""
        entry = self._staged.pop(int(uid), None)
        if entry is not None and entry[2] is not None:
            try:
                os.unlink(entry[2])
            except OSError:
                pass
        return entry is not None

    # -- drain/telemetry surfaces --------------------------------------

    def results(self) -> dict[int, list[int]]:
        return dict(self.engine.finished)

    def failed_map(self) -> dict[int, dict]:
        return {u: dict(i) for u, i in self.engine.failed.items()}

    def stats(self) -> dict:
        e = self.engine
        return {
            "engine_steps": e.global_step,
            "tokens_generated": e.tokens_generated,
            "prefill_dispatches": e.prefill_dispatches,
            "compiled_programs": e.compile_count,
            "dispatches": e.dispatch_count,
            "finished": len(e.finished),
            "prefix_hit_blocks": e.prefix_hit_blocks,
            "prefill_tokens_saved": e.prefill_tokens_saved,
        }

    def emit_decode(self) -> None:
        if self.engine.metrics is None:
            return
        now = time.perf_counter()
        delta = self.engine.tokens_generated - self.last_tokens
        dt = max(now - self.last_t, 1e-9)
        tps = round(delta / dt, 2) if delta > 0 else None
        self.engine.metrics.decode(self.engine.telemetry_record(tps))
        self.last_tokens = self.engine.tokens_generated
        self.last_t = now

    # -- transport attribution (round 18, DESIGN.md section 24) --------

    def rpc_stats(self) -> dict | None:
        """Per-op RPC cost attribution — None in-process: a method
        call has no socket, no marshal, no deadline, so reporting
        zeros would masquerade as a measured transport."""
        return None

    def evidence(self) -> dict:
        """The router-side view of this member for a dead-host
        postmortem: what the router knew when it declared death. The
        in-process handle has no call/backoff history (calls are
        plain method calls) — the last snapshot summary is the
        evidence."""
        snap = self.snapshot
        return {
            "transport": self.transport,
            "alive": self.alive,
            "last_snapshot_step": (None if snap is None
                                   else snap.get("step")),
            "last_snapshot_requests": (None if snap is None
                                       else len(snap.get("requests",
                                                         ()))),
        }

    # -- liveness ------------------------------------------------------

    def ping(self) -> None:
        """Heartbeat no-op in-process (the process transport's ping is
        a real round-trip with a short deadline)."""

    def warm(self, deadline_s: float = 600.0) -> int:
        """Pre-build the engine's full program set BEFORE it takes
        traffic (``DecodeEngine.warm``) — the autoscaler's
        spawn-then-warm discipline: a joining member must never pay
        its compiles under live load. Returns the engine's compile
        count; ``deadline_s`` is ignored in-process (the process
        transport bounds the RPC with it)."""
        return self.engine.warm()

    def hang(self, secs: float) -> None:
        raise ValueError(
            "hang_worker requires the process transport (an in-process "
            "engine cannot go silent without hanging the router) — run "
            "the fleet with --transport process")

    def kill(self) -> None:
        """Drop the engine object — the in-process dead host. Its pool,
        like a dead host's HBM, is unreachable afterwards."""
        self.alive = False
        self.engine = None

    def close(self) -> None:
        """Release transport resources (no-op in-process)."""


class FleetRouter:
    """N decode-engine replicas behind one admission point.

    ``make_engine(engine_id)`` is a factory returning a FRESH
    single-device engine per fleet member (attach a per-engine
    ``TelemetryWriter`` inside it; the router never shares one), OR
    pass pre-built ``handles=`` (the process transport:
    ``decode/worker.py`` spawns the workers and hands their
    ``ProcessEngineHandle``s over). All engines must share the
    numerics-relevant ``EngineConfig`` keys and the model — the
    handoff's own fingerprint check enforces it at migration time, and
    the router cross-checks fingerprints up front so a mismatched fleet
    fails at construction, not mid-drill.

    ``prefill_engines=M`` dedicates the first M members to prefill
    (disaggregation); ``0`` runs every engine unified. ``n_engines``
    may be 1 (the router degenerates to a pass-through — the honest
    N=1 baseline for the bench scaling rows); the CLI requires >= 2.

    ``snapshot_every`` is the router-held snapshot cadence in fleet
    rounds (the PR 5 discipline: a kill migrates from the LAST
    snapshot and replay fills the gap since it). ``wire_dir`` routes
    every in-process live move through the wire format (serialize +
    CRC-verify + import from the published file). ``fleet_chaos`` is a
    validated ``FaultPlan`` of FLEET_KINDS faults, fired on the
    router's round clock.
    """

    def __init__(self, make_engine, n_engines: int,
                 prefill_engines: int = 0, *, metrics=None,
                 snapshot_every: int = 1, session_affinity: bool = True,
                 prefix_affinity: bool = True, wire_dir: str | None = None,
                 handles: list | None = None, fleet_chaos=None,
                 keep_rejected: int = 8, status_dir: str | None = None,
                 status_every_s: float = 1.0,
                 async_migration: bool = False):
        if n_engines < 1:
            raise ValueError(f"n_engines must be >= 1, got {n_engines}")
        if not 0 <= prefill_engines < n_engines:
            raise ValueError(
                f"prefill_engines must leave >= 1 decode engine: got "
                f"{prefill_engines} of {n_engines}")
        if snapshot_every < 1:
            raise ValueError(f"snapshot_every must be >= 1, got "
                             f"{snapshot_every}")
        if handles is not None:
            if len(handles) != n_engines:
                raise ValueError(f"{len(handles)} handle(s) for "
                                 f"n_engines={n_engines}")
            self.handles = list(handles)
        else:
            self.handles = []
            for i in range(prefill_engines):
                eid = f"{PREFILL_PREFIX}{i}"
                self.handles.append(EngineHandle(
                    eid, make_engine(eid), "prefill", wire_dir=wire_dir))
            for i in range(n_engines - prefill_engines):
                eid = f"{DECODE_PREFIX}{i}"
                self.handles.append(EngineHandle(
                    eid, make_engine(eid), "decode", wire_dir=wire_dir))
        metas = [h.model_meta() for h in self.handles]
        if any(m != metas[0] for m in metas[1:]):
            raise ValueError("fleet engines disagree on model identity "
                             f"({metas}) — every replica must serve the "
                             "same weights")
        for h in self.handles:
            h.validate_member()
        self.by_id = {h.id: h for h in self.handles}
        self.metrics = metrics              # the ROUTER's own writer
        self.snapshot_every = snapshot_every
        self.session_affinity = session_affinity
        self.prefix_affinity = prefix_affinity
        self.fleet_chaos = fleet_chaos
        if fleet_chaos is not None:
            # every fault the plan can fire must be honorable by THIS
            # fleet — reject at construction, not rounds later at fire
            # time (the CLI's parse-rejection discipline, enforced once
            # here so library callers get it too)
            kinds = {f.kind for f in fleet_chaos.faults}
            wired = wire_dir is not None or any(
                h.transport == "process" for h in self.handles)
            if "corrupt_wire" in kinds and not wired:
                raise ValueError(
                    "corrupt_wire needs a wire boundary to corrupt: "
                    "run the fleet with --transport process (or an "
                    "in-process wire_dir)")
            decode_handles = [h for h in self.handles
                              if h.role == "decode"]
            if "hang_worker" in kinds and any(
                    h.transport != "process" for h in decode_handles):
                raise ValueError(
                    "hang_worker requires the process transport (an "
                    "in-process engine cannot go silent without "
                    "hanging the router) — run the fleet with "
                    "--transport process")
            for f in fleet_chaos.faults:
                if f.kind != "kill_worker":
                    continue
                idx = 0 if f.arg is None else int(f.arg)
                if idx >= len(decode_handles):
                    raise ValueError(
                        f"kill_worker index {idx} names e{idx}, but "
                        f"this fleet has {len(decode_handles)} decode "
                        "engine(s)")
                if len(decode_handles) == 1:
                    raise ValueError(
                        "kill_worker would kill the only decode "
                        "engine in this fleet (the survivors have "
                        "nowhere to migrate its requests)")
            # the round-22 network kinds drill the reconnect ladder,
            # which only the TCP family carries (AF_UNIX keeps the
            # round-16 EOF-is-dead semantics); slow_link only needs a
            # socket to be slow on
            if {"partition_worker", "drop_conn"} & kinds and any(
                    getattr(h, "family", None) != "tcp"
                    for h in decode_handles):
                raise ValueError(
                    "partition_worker/drop_conn drill the reconnect "
                    "ladder, which only the TCP transport carries — "
                    "run the fleet with --transport tcp")
            if "slow_link" in kinds and any(
                    h.transport != "process" for h in decode_handles):
                raise ValueError(
                    "slow_link injects socket latency and needs a "
                    "socket to inject it on — run the fleet with "
                    "--transport process (or tcp)")
        self.rounds = 0                     # fleet scheduling rounds
        self._next_uid = 0
        self._sessions: dict = {}           # session -> engine id
        # request book: what the router needs to place (and re-place)
        # a request — NOT a mirror of engine progress (the snapshot is)
        self.requests: dict[int, dict] = {}
        self._kills: dict[int, list[str]] = collections.defaultdict(list)
        # results carried off dead engines (their snapshot's finished/
        # failed maps; survivors re-complete anything newer)
        self._dead_finished: dict[int, list[int]] = {}
        self._dead_failed: dict[int, dict] = {}
        # decision counters (the payload/bench surface)
        self.routed = 0
        self.handoffs = 0
        self.migrations = 0
        self.sheds = 0
        self.kills = 0
        self.routed_by = {"least_loaded": 0, "session": 0, "prefix": 0}
        self.prefix_routed_hit_blocks = 0
        # migration-stall instrumentation (ROADMAP item 1's bench
        # criterion): every LIVE move (export -> import — prefill
        # handoff or pool-pressure migration) accumulates the blocks
        # and SERIALIZED bytes shipped and its wall-clock duration;
        # replay-migrations off a dead engine's snapshot ship no KV and
        # stay out of these (their records carry duration_s with
        # blocks/bytes 0 and transport mode "replay")
        self.handoff_blocks = 0
        self.handoff_bytes = 0
        self.handoff_durations: list[float] = []
        # wire-integrity accounting (round 16): rejected handoff files
        # (CRC/torn/version — each also emitted a ``wire_rejected``
        # router record with the one-line reason) and per-uid rejection
        # counts (the ``retries`` field of the next successful move)
        self.wire_rejects = 0
        self._uid_wire_rejects: dict[int, int] = {}
        self._corrupt_next_wire = False
        # -- async live migration (round 22, DESIGN.md section 28) --
        # opt-in: pool-pressure moves run the three-phase pipeline
        # (export_keep -> ship-during-step -> finish_export/commit)
        # instead of the synchronous export->import, so the source
        # engine never stalls for the ship; uid -> the pending move
        self.async_migration = async_migration
        self._pending_moves: dict[int, dict] = {}
        # reconnect accounting (schema v16 "reconnected" records):
        # every handle that can heal a dropped connection reports here
        self.reconnects_total = 0
        for h in self.handles:
            if hasattr(h, "on_reconnect"):
                h.on_reconnect = self._note_reconnect
        # bounded post-mortem retention for REJECTED wire docs (round
        # 17 satellite, mirroring checkpoint.keep_last): a rejected
        # handoff file is renamed *.rejected and the oldest are pruned
        # past this cap — a chaos loop of rejections must not grow a
        # worker's spool without bound. 0 keeps none.
        if keep_rejected < 0:
            raise ValueError(f"keep_rejected must be >= 0, got "
                             f"{keep_rejected}")
        self.keep_rejected = keep_rejected
        # -- live weight hot-swap (round 17, DESIGN.md section 23) --
        self._deploys: dict[int, tuple] = {}    # round -> (dir, step)
        self.deploys = 0
        self.deploy_rollbacks = 0
        # deploy-on-publish watcher (round 19, ROADMAP item 3
        # follow-on): poll the ledger's latest_verified on a wall-clock
        # cadence and roll forward when it advances past the fleet's
        # serving version — the trainer's atomic publish becomes the
        # deploy trigger, no operator in the loop (None = off)
        self._watch: tuple | None = None    # (ckpt_dir, poll_every_s)
        self._watch_t_last = 0.0
        # per-tenant admission accounting (round 19, schema v13): the
        # offered/shed half of the status doc's tenants block (the
        # in-flight half rides the digests); None tenants excluded
        self.tenant_offered: dict[str, int] = {}
        self.tenant_shed: dict[str, int] = {}
        # armed by corrupt_deploy chaos: the truncation fraction to
        # apply to the NEXT deploy's target checkpoint (None = off)
        self._corrupt_next_deploy: float | None = None
        # -- fleet trace spine + live ops plane (round 18, DESIGN.md
        # section 24) --
        # the router mints every request's fleet-unique trace id at
        # admission (host metadata only — no compiled program, no
        # extra round-trip); the nonce disambiguates routers across
        # processes/runs, the uid suffix within a run
        self._trace_nonce = os.urandom(4).hex()
        # live status doc: one atomic JSON per round via
        # wire.publish_json, throttled like the PR 12 spool snapshot
        # (the drain-end publish is forced so a finished run's doc is
        # always final). status_dir None (and no metrics writer) =
        # publishing off.
        if status_dir is None and metrics is not None:
            status_dir = os.path.dirname(metrics.path)
        self.status_dir = status_dir
        if status_every_s <= 0:
            raise ValueError(f"status_every_s must be > 0, got "
                             f"{status_every_s}")
        self.status_every_s = status_every_s
        self._status_t_last = 0.0       # monotonic: last publish
        self._status_tokens_last = 0    # fleet tokens at last publish
        self._status_wall_last: float | None = None
        # round wall clock (the denominator of the RPC overhead share)
        self.round_wall_s = 0.0
        # -- closed-loop autoscaling (round 20, DESIGN.md section 26) --
        # the controller (decode/autoscale.py) mirrors its live state
        # here after every tick for the status doc; the router itself
        # never decides to scale — it only provides the membership
        # primitives (add_engine/retire_engine) and the digests the
        # controller reads
        self.autoscale_state: dict | None = None
        # -- watchtower (round 21, DESIGN.md section 27) --
        # the live alert block (runtime/watch.py mirrors it here after
        # every tick, exactly like autoscale_state): the status doc's
        # ``alerts`` block and the router postmortem's
        # active-alerts-at-declaration evidence — null when no
        # watchtower drives this fleet
        self.watch_state: dict | None = None
        # spawned decode members continue the e-numbering — engine ids
        # are never reused (a retired/killed handle keeps its slot in
        # ``handles`` for the post-mortem book)
        self._decode_serial = sum(1 for h in self.handles
                                  if h.role == "decode")
        # per-tenant shed baseline consumed by _publish_status only
        # (the tps-interval pattern): the published doc's shed_delta
        # covers publish-to-publish exactly; an out-of-band
        # status_doc() read must not shorten it
        self._status_tenant_shed_last: dict[str, int] = {}

    # -- introspection -------------------------------------------------

    def alive_handles(self, role: str | None = None):
        return [h for h in self.handles if h.alive
                and (role is None or h.role == role)]

    def engine(self, eid: str) -> DecodeEngine:
        return self.by_id[eid].engine

    def close(self) -> None:
        """Release every handle's transport resources (shuts down
        worker processes under the process transport). Idempotent."""
        for h in self.handles:
            h.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- telemetry -----------------------------------------------------

    def _record(self, event: str, uid: int, source=None, target=None,
                reason=None, policy=None, trace_id=None,
                **extra) -> None:
        if self.metrics is None:
            return
        if trace_id is None:
            # every router record pins the request's trace id (v12);
            # callers on the shed path pass it explicitly — the
            # request book never learned a shed uid
            trace_id = self.requests.get(int(uid), {}).get("trace")
        if event == "migrated":
            # schema v16: every migrated record pins the async-
            # migration attribution, with honest defaults on the sync
            # and replay paths — ship_s null (nothing shipped while
            # decoding) and catchup_tokens = the replay length (the
            # full catch-up a replay-migration teacher-forces)
            extra.setdefault("ship_s", None)
            extra.setdefault("catchup_tokens",
                             int(extra.get("replay", 0)))
        self.metrics.router({"step": self.rounds, "uid": int(uid),
                             "event": event, "source": source,
                             "target": target, "reason": reason,
                             "policy": policy, "trace_id": trace_id,
                             **extra})

    def _note_reconnect(self, h, info: dict) -> None:
        """A handle healed a dropped connection (reconnect + sync +
        sequence-numbered replay): one schema-v16 ``reconnected``
        router record — uid -1, this is link-level, not per-request —
        so the drill can pin that a partition cost reconnects, never
        deaths."""
        self.reconnects_total += 1
        self._record("reconnected", -1, source=h.id,
                     reason=info.get("cause"),
                     attempts=info.get("attempts"),
                     gap_s=info.get("gap_s"),
                     replayed_ops=len(info.get("replayed", ())))

    def _event(self, record: dict) -> None:
        if self.metrics is not None:
            self.metrics.event(record)

    def _candidates(self, handles, prompt=None) -> list[dict]:
        """The per-engine scores a placement decision saw (schema-v9
        ``routed`` attribution): warm-block depth (null when the
        prefix probe didn't run — prefill-tier admission, affinity
        off, or no prompt), queue depth, active slots, pool
        utilization. Host-side reads only — probing never steps an
        engine."""
        out = []
        for h in handles:
            d = h.digest(light=True)
            warm = None
            if prompt is not None and self.prefix_affinity:
                warm = h.warm_blocks(prompt)
            out.append({
                "engine": h.id,
                "warm_blocks": warm,
                "queue_depth": d["waiting"],
                "active": d["active"],
                "pool_utilization": round(d["utilization"], 4),
            })
        return out

    def _fleet_record(self) -> dict:
        """One per-round fleet health record (schema-v9 ``fleet``
        kind): per-engine waiting/active/free-blocks/utilization and
        the load-imbalance scalar over alive decode engines
        (``(max - min) / max`` of ``active + waiting``; 0.0 balanced
        or idle, toward 1.0 when one engine holds everything)."""
        engines = {}
        loads = []
        for h in self.handles:
            if not h.alive:
                engines[h.id] = {"alive": False}
                continue
            d = h.digest(light=True)
            engines[h.id] = {
                "alive": True, "role": h.role,
                "waiting": d["waiting"], "active": d["active"],
                "free_blocks": d["free_blocks"],
                "utilization": round(d["utilization"], 4),
                "spill_tier_blocks": d.get("spill_tier_blocks", 0),
                "spill_restores": d.get("spill_restores", 0),
            }
            if h.role == "decode":
                loads.append(d["active"] + d["waiting"])
        imb = 0.0
        if len(loads) > 1 and max(loads) > 0:
            imb = round((max(loads) - min(loads)) / max(loads), 4)
        return {"step": self.rounds, "engines": engines,
                "load_imbalance": imb}

    # -- live ops plane (round 18, DESIGN.md section 24) ---------------

    def status_doc(self) -> dict:
        """The live fleet status document: one atomic, self-contained
        JSON snapshot of what an operator needs mid-run — per-engine
        liveness/role/serving-version/queue-depth/pool watermarks,
        deploy state, decision counters, and the throughput since the
        last publish. Built from the light digests (cached under the
        process transport — reading status never adds a round-trip)."""
        engines = {}
        tokens = 0
        in_flight: dict[str, int] = {}
        for h in self.handles:
            if not h.alive:
                # a RETIRED member drained out gracefully (scale-down)
                # — distinct from a death, which names the kill round
                engines[h.id] = ({"alive": False, "retired": True}
                                 if getattr(h, "retired", False)
                                 else {"alive": False,
                                       "killed_at_round":
                                           h.killed_at_round})
                continue
            d = h.digest(light=True)
            tokens += int(d.get("tokens_generated") or 0)
            for t, n in (d.get("tenants") or {}).items():
                in_flight[t] = in_flight.get(t, 0) + int(n)
            engines[h.id] = {
                "alive": True, "role": h.role,
                "serving_version": int(d["serving_version"]),
                "waiting": d["waiting"], "active": d["active"],
                "free_slots": d["free_slots"],
                "free_blocks": d["free_blocks"],
                "evictable_blocks": d["evictable"],
                "utilization": round(d["utilization"], 4),
                "spill_tier_blocks": d.get("spill_tier_blocks", 0),
                "spill_restores": d.get("spill_restores", 0),
                "last_step_s": round(h.last_step_s, 6),
            }
            fam = getattr(h, "family", None)
            if fam is not None:
                # the operator's "which boundary is this member
                # behind" tag (round 22): unix/tcp, with the member's
                # survived-reconnect count alongside under tcp
                engines[h.id]["family"] = fam
                engines[h.id]["reconnects"] = getattr(
                    h, "reconnects", 0)
        # the interval baseline is CONSUMED by _publish_status only —
        # an out-of-band status_doc() read (tests, an in-process
        # consumer) must not shorten the next published interval
        now = time.perf_counter()
        tps = None
        if self._status_wall_last is not None:
            dt = now - self._status_wall_last
            delta = tokens - self._status_tokens_last
            if dt > 0 and delta > 0:
                tps = round(delta / dt, 2)
        drained = all(not e.get("waiting") and not e.get("active")
                      for e in engines.values() if e.get("alive"))
        return {
            "version": 1,
            "t": time.time(),
            "round": self.rounds,
            "drained": drained,
            "engines": engines,
            "tokens_generated": tokens,
            "tokens_per_sec_last_interval": tps,
            "deploy": {
                "scheduled_rounds": sorted(self._deploys),
                "deploys": self.deploys,
                "rollbacks": self.deploy_rollbacks,
            },
            "counters": {
                "routed": self.routed, "handoffs": self.handoffs,
                "migrations": self.migrations, "sheds": self.sheds,
                "kills": self.kills,
                "wire_rejects": self.wire_rejects,
                "reconnects": self.reconnects_total,
            },
            # per-tenant ops counters (round 19, schema v13): in-flight
            # summed off the digests (zero extra round-trips), offered/
            # shed from the router's own admission book — empty dict on
            # a single-tenant fleet (the pre-v13 doc, plus this key)
            "tenants": {
                t: {"in_flight": in_flight.get(t, 0),
                    "offered": self.tenant_offered.get(t, 0),
                    "shed": self.tenant_shed.get(t, 0),
                    # sheds since the LAST PUBLISH (round 20): the
                    # operator's "is it shedding NOW" signal — the
                    # baseline is consumed by _publish_status exactly
                    # like the tps interval's
                    "shed_delta": (self.tenant_shed.get(t, 0)
                                   - self._status_tenant_shed_last
                                   .get(t, 0))}
                for t in sorted(set(in_flight)
                                | set(self.tenant_offered)
                                | set(self.tenant_shed))
            },
            # live autoscale state (round 20): mirrored by the
            # controller after every tick — null when no controller
            # drives this fleet
            "autoscale": self.autoscale_state,
            # live watchtower alerts (round 21): mirrored by the
            # watchtower after every tick — null when none watches
            "alerts": self.watch_state,
        }

    def _publish_status(self, force: bool = False) -> str | None:
        """Publish the status doc atomically (``wire.publish_json`` —
        a reader mid-drill sees the old doc or the new one, never a
        torn one), throttled to ``status_every_s`` like the PR 12
        spool snapshot: the ops plane must not put per-round fsyncs on
        the hot path. ``force`` (the drain-end publish) skips the
        throttle so a finished run's doc is final."""
        if self.status_dir is None:
            return None
        now = time.monotonic()
        if not force and now - self._status_t_last < self.status_every_s:
            return None
        self._status_t_last = now
        doc = self.status_doc()
        # consume the throughput-interval baseline HERE (the one
        # production caller): the next doc's tokens_per_sec covers
        # publish-to-publish exactly
        self._status_wall_last = time.perf_counter()
        self._status_tokens_last = doc["tokens_generated"]
        self._status_tenant_shed_last = dict(self.tenant_shed)
        os.makedirs(self.status_dir, exist_ok=True)
        return wire.publish_json(
            os.path.join(self.status_dir, STATUS_FILENAME), doc)

    def transport_stats(self) -> dict:
        """Per-worker RPC cost attribution (the process transport's
        measured overhead; in-process members report None — a method
        call has no transport to price): per-op call/handle duration
        percentiles, per-op overhead (router-side call minus
        worker-side handle = socket + JSON marshal), heartbeat RTTs,
        and the round wall clock the overhead share is computed
        against (``report``'s transport block)."""
        return {
            "round_wall_s": round(self.round_wall_s, 6),
            "rounds": self.rounds,
            "engines": {h.id: h.rpc_stats() for h in self.handles},
        }

    def emit_transport_stats(self) -> None:
        """One ``transport_stats`` event record on the router's stream
        (rides the schema-free event kind; ``report`` folds it into
        the transport block). Called at drain end by ``run()``; manual
        step() drivers call it themselves."""
        stats = self.transport_stats()
        if any(v for v in stats["engines"].values()):
            self._event({"event": "transport_stats", **stats})

    def _dump_router_postmortem(self, h, reason: str) -> str | None:
        """Atomically dump the router's own evidence on a dead-host
        declaration: the dying worker's flight recorder dies with the
        process, but the router still holds the last digests, the
        pending call ids, the per-op/backoff/ping history, and the
        declaration reason — published per engine
        (``router_postmortem_<id>.json`` next to the status doc /
        router stream) and rendered by ``report --postmortem``."""
        if self.status_dir is None:
            return None
        doc = {
            "version": 1,
            "engine": h.id,
            "round": self.rounds,
            "t": time.time(),
            "reason": reason,
            "evidence": h.evidence(),
            # active-alerts-at-declaration (round 21): what the
            # watchtower was ALREADY paging about when the router
            # declared this engine dead — null when none watches
            "alerts": self.watch_state,
        }
        os.makedirs(self.status_dir, exist_ok=True)
        return wire.publish_json(
            os.path.join(self.status_dir,
                         f"{ROUTER_POSTMORTEM_PREFIX}{h.id}.json"),
            doc)

    # -- routing -------------------------------------------------------

    def _load_key(self, h: EngineHandle):
        """Least-loaded ordering: queue depth first (waiting work is
        the latency the next request inherits), then slot occupancy,
        then pool pressure — engine id breaks ties deterministically."""
        d = h.digest(light=True)
        return (d["waiting"], d["active"],
                round(d["utilization"], 4), h.id)

    def _has_capacity(self, h: EngineHandle, prompt_len: int,
                      max_new: int) -> bool:
        """Can ``h`` take a handoff IMPORT right now (free slot + full
        block reservation)? Queue-based admission never needs this —
        submit/resume queue and the engine admits when space frees."""
        d = h.digest(light=True)
        if d["free_slots"] < 1:
            return False
        need = h.blocks_needed(prompt_len, max_new)
        if need > h.max_blocks_per_seq():
            return False
        return need <= d["free_blocks"] + d["evictable"]

    def _route(self, prompt, session, warm_by_id=None):
        """Pick the decode-tier engine for a fresh request. Precedence:
        session affinity (stickiness beats balance — the session's KV
        locality is on that engine), then prefix affinity (the engine
        with the deepest warm radix path wins, load breaking ties),
        then least-loaded. ``warm_by_id`` reuses warm-block counts a
        caller already probed (the candidates capture) so a
        telemetry-enabled submit walks each radix tree once, not
        twice."""
        handles = self.alive_handles("decode")
        if not handles:
            raise RuntimeError("no alive decode engine in the fleet")
        if self.session_affinity and session is not None:
            eid = self._sessions.get(session)
            if eid is not None and self.by_id[eid].alive:
                return self.by_id[eid], "session", 0
        if self.prefix_affinity:
            if warm_by_id is not None:
                warm = [(warm_by_id[h.id], h) for h in handles
                        if warm_by_id.get(h.id) is not None]
            else:
                warm = [(w, h) for h in handles
                        if (w := h.warm_blocks(prompt)) is not None]
            best = max((w for w, _ in warm), default=0)
            if best > 0:
                tied = [h for w, h in warm if w == best]
                return min(tied, key=self._load_key), "prefix", best
        return min(handles, key=self._load_key), "least_loaded", 0

    def submit(self, prompt, max_new: int, session=None,
               tenant: str | None = None) -> int:
        """Route one request into the fleet; returns its fleet-global
        uid. Disaggregated fleets admit through the least-loaded
        PREFILL engine (the decode target is chosen at handoff time,
        when the KV exists); unified fleets route by
        session/prefix/load. A full target spills over to the next
        engine by load; when every engine sheds, the request is shed
        fleet-wide (``AdmissionError``, one ``shed`` router record)."""
        # the uid is CONSUMED whether the request lands or sheds — a
        # shed record must never carry a number a later accepted
        # request reuses (the engine-side audit-trail discipline:
        # aliasing two requests per uid breaks the per-uid timeline)
        uid = self._next_uid
        self._next_uid += 1
        prompt = [int(t) for t in prompt]
        if tenant is not None:
            self.tenant_offered[tenant] = \
                self.tenant_offered.get(tenant, 0) + 1
        # the trace spine's mint point (schema v12): ONE fleet-unique
        # causal identity per admission, consumed like the uid whether
        # the request lands or sheds — it rides the engine submit, all
        # downstream request/span records, every router record, the
        # handoff doc (v5), and the snapshots (v7)
        trace = f"{self._trace_nonce}-{uid}"
        reason, hit_blocks = None, 0
        prefills = self.alive_handles("prefill")
        # decision attribution (schema v9): the per-engine scores this
        # placement saw, captured BEFORE any engine takes the request
        # (only when a router stream exists — the probe is host-cheap
        # but pointless without a record to ride); the routing decision
        # below REUSES the captured warm-block counts, so each radix
        # tree is walked once per submit either way
        candidates = None
        if prefills:
            order = sorted(prefills, key=self._load_key)
            reason = "least_loaded"
            if self.metrics is not None:
                candidates = self._candidates(order, prompt)
        else:
            warm_by_id = None
            if self.metrics is not None:
                candidates = self._candidates(
                    self.alive_handles("decode"), prompt)
                warm_by_id = {c["engine"]: c["warm_blocks"]
                              for c in candidates}
            target, reason, hit_blocks = self._route(prompt, session,
                                                     warm_by_id)
            others = sorted(
                (h for h in self.alive_handles("decode")
                 if h is not target), key=self._load_key)
            order = [target] + others
        shed_reasons = []
        shed_causes = []
        spilled = False
        for h in order:
            try:
                entry = h.submit(prompt, max_new, uid=uid, trace=trace,
                                 tenant=tenant)
            except AdmissionError as e:
                # the engine names WHY it shed (queue_full /
                # predicted_deadline_miss) — propagate it instead of
                # guessing, so the fleet-wide shed record and the
                # driver's per-tenant book attribute the real cause
                shed_causes.append(getattr(e, "reason", "queue_full"))
                shed_reasons.append(f"{h.id}: {shed_causes[-1]}")
                # spillover loses affinity — including the warm-block
                # count probed for the ORIGINAL target (the next engine
                # tried is cold; recording the stale count would credit
                # it with blocks it doesn't hold)
                reason, hit_blocks = "least_loaded", 0
                spilled = True
                continue
            self.requests[uid] = {"prompt": prompt, "max_new": max_new,
                                  "engine": h.id, "session": session,
                                  "trace": trace, "tenant": tenant,
                                  # admission round (round 21): the
                                  # watchtower's round-denominated
                                  # latency baseline for this uid
                                  "round": self.rounds}
            if session is not None and h.role == "decode":
                self._sessions[session] = h.id
            self.routed += 1
            self.routed_by[reason] = self.routed_by.get(reason, 0) + 1
            if reason == "prefix":
                self.prefix_routed_hit_blocks += hit_blocks
            # policy: what ACTUALLY placed the request — "spill" when
            # the probed target shed and the request landed on a later
            # engine by load (the affinity-era reason would credit a
            # policy that didn't place it)
            self._record("routed", uid, target=h.id, reason=reason,
                         policy=("spill" if spilled else reason),
                         prefix_hit_blocks=hit_blocks,
                         candidates=candidates)
            # the step-0 snapshot discipline: a kill before the first
            # cadence snapshot must still know this request exists.
            # O(1) per submit: append the one new WAITING entry
            # (returned by the handle's submit) to the existing
            # snapshot instead of re-serializing the whole engine — a
            # burst of n submissions must not pay O(n^2) host work on
            # the admission path; the cadence snapshot already lags by
            # design, and kill-migration only needs the request LISTED
            # (resume replays from `out`)
            if h.snapshot is None:
                h.snapshot = h.fetch_snapshot()
            else:
                h.snapshot["requests"].append(entry)
            return uid
        self.sheds += 1
        if tenant is not None:
            self.tenant_shed[tenant] = \
                self.tenant_shed.get(tenant, 0) + 1
        # the fleet-wide record names the PRIMARY target's cause (the
        # engine the router actually wanted — spillover engines only
        # corroborate), and the raised error carries it for the
        # driver's own per-reason book
        cause = shed_causes[0] if shed_causes else "queue_full"
        self._record("shed", uid, reason=cause, trace_id=trace)
        raise AdmissionError(
            f"every fleet engine shed request uid {uid}: "
            f"[{'; '.join(shed_reasons)}]", reason=cause)

    # -- the fleet round -----------------------------------------------

    def _fire_fleet_chaos(self) -> bool:
        """Fire fleet-transport faults due at the START of this round
        (``runtime/chaos.py`` FLEET_KINDS). Returns whether any
        fired."""
        if self.fleet_chaos is None:
            return False
        fired = False
        for f in self.fleet_chaos.fleet_due(self.rounds):
            fired = True
            if f.kind == "kill_worker":
                idx = 0 if f.arg is None else int(f.arg)
                eid = f"{DECODE_PREFIX}{idx}"
                if eid not in self.by_id:
                    raise ValueError(f"kill_worker index {idx} names "
                                     f"unknown engine {eid!r}")
                self.fleet_chaos._note(f, engine=eid)
                self.kill_engine(eid)
            elif f.kind == "hang_worker":
                cands = self.alive_handles("decode")
                if not cands:
                    continue
                if f.arg is None:
                    # derived default: strictly past the target's
                    # deadline + retry window, whatever it is tuned to
                    deadline = getattr(cands[0], "call_deadline_s", 0.0)
                    secs = max(HANG_WORKER_DEFAULT_S, 2.5 * deadline)
                else:
                    secs = float(f.arg)
                self.fleet_chaos._note(f, engine=cands[0].id,
                                       sleep_s=secs)
                cands[0].hang(secs)
            elif f.kind == "corrupt_wire":
                self.fleet_chaos._note(f)
                self._corrupt_next_wire = True
            elif f.kind == "corrupt_deploy":
                frac = 0.5 if f.arg is None else float(f.arg)
                self.fleet_chaos._note(f, frac=frac)
                self._corrupt_next_deploy = frac
            elif f.kind == "partition_worker":
                # drop the first alive decode worker's link BOTH ways;
                # the reconnect ladder must wait the partition out and
                # replay — zero deaths, one "reconnected" record
                cands = [h for h in self.alive_handles("decode")
                         if getattr(h, "family", None) == "tcp"]
                if not cands:
                    continue
                secs = 2.0 if f.arg is None else float(f.arg)
                self.fleet_chaos._note(f, engine=cands[0].id,
                                       secs=secs)
                cands[0].partition(secs)
            elif f.kind == "slow_link":
                # permanent injected latency from this round on — a
                # SLOW link, not a dead one: per-call deadlines must
                # absorb it without paging the liveness ladder
                cands = [h for h in self.alive_handles("decode")
                         if h.transport == "process"]
                if not cands:
                    continue
                ms = 50.0 if f.arg is None else float(f.arg)
                self.fleet_chaos._note(f, engine=cands[0].id, ms=ms)
                cands[0].slow_link(ms)
            elif f.kind == "drop_conn":
                # mid-message RST on the next send: the response is
                # lost in flight; reconnect + dedup-cache replay must
                # recover it with no duplicate side effects
                cands = [h for h in self.alive_handles("decode")
                         if getattr(h, "family", None) == "tcp"]
                if not cands:
                    continue
                self.fleet_chaos._note(f, engine=cands[0].id)
                cands[0].drop_conn()
        return fired

    def step(self) -> bool:
        """One fleet scheduling round: fire due chaos + kills (the
        round clock), step every alive engine once — CONCURRENTLY
        under the process transport (step_begin fans out, step_end
        collects; a worker that misses its deadline or drops its
        connection is declared dead mid-round and its requests migrate
        before the round continues) — heartbeat-ping the idle members,
        ship completed prefills to the decode tier, relieve pool
        pressure by migration, then refresh the router-held snapshots
        on cadence. Returns whether any engine ran work this round.

        The round's wall clock accumulates in ``round_wall_s`` (the
        denominator of the RPC overhead share) and the live status doc
        publishes at round end, throttled (DESIGN.md section 24)."""
        t0 = time.perf_counter()
        try:
            return self._step_round()
        finally:
            self.round_wall_s += time.perf_counter() - t0
            self._publish_status()

    def _step_round(self) -> bool:
        did = self._fire_fleet_chaos()
        killed = bool(self._kills.get(self.rounds))
        for eid in self._kills.pop(self.rounds, ()):
            self.kill_engine(eid)
        did = did or killed
        # rolling deploys fire on the same round clock as kills, AFTER
        # them (a deploy never drains onto an engine the same round is
        # about to kill) and BEFORE any engine steps, so the deploy's
        # drain sees the round's pre-step truth
        dep = self._deploys.pop(self.rounds, None)
        if dep is not None:
            self.rolling_deploy(dep[0], step=dep[1])
            did = True
        if self._poll_deploy_watch():
            did = True
        stepping, idle = [], []
        for h in self.handles:
            (stepping if h.has_work else idle).append(h)
        for h in stepping:
            if not h.alive:
                continue
            try:
                h.step_begin(prefill_only=(h.role == "prefill"))
            except TransportError as e:
                self._transport_death(h, e)
                did = True
        # async live migration phase 2 (round 22): ship pending
        # documents NOW, between the step fan-out and the collect —
        # the stage RPCs queue behind each worker's in-flight step, so
        # the whole fleet decodes while the KV crosses the wire
        if self._pending_moves:
            self._ship_pending_moves()
        for h in stepping:
            if not h.alive:
                continue
            try:
                did = h.step_end() or did
            except TransportError as e:
                self._transport_death(h, e)
                did = True
        # heartbeat liveness: members with no work this round still
        # answer a cheap ping (short deadline) — a dead IDLE worker is
        # declared now, not discovered when the router finally needs it
        # (it may hold finished results only its snapshot remembers)
        for h in idle:
            if not h.alive:
                continue
            try:
                h.ping()
            except TransportError as e:
                self._transport_death(h, e)
        before = self.handoffs + self.migrations
        self._handoff_completed_prefills()
        self._migrate_pool_pressure()
        # async live migration phase 3: settle every shipped move
        # (finish_export evicts on the source; the staged doc commits
        # with its ship-window delta patched in — one teacher-forced
        # catch-up on the target, zero source stall)
        if self._pending_moves:
            self._commit_pending_moves()
        did = did or (self.handoffs + self.migrations > before)
        self.rounds += 1
        if self.rounds % self.snapshot_every == 0:
            for h in self.handles:
                if h.alive:
                    h.snapshot = h.fetch_snapshot()
        # one fleet health record per round (schema v9): the
        # per-engine balance view the SLO/autoscaling layer reads.
        # ``step`` is the post-round clock — record N describes the
        # fleet after N rounds.
        if self.metrics is not None:
            self.metrics.fleet(self._fleet_record())
        return did

    def _placement_target(self, prompt_len: int, max_new: int,
                          exclude=()) -> EngineHandle | None:
        cands = [h for h in self.alive_handles("decode")
                 if h.id not in exclude
                 and self._has_capacity(h, prompt_len, max_new)]
        return min(cands, key=self._load_key) if cands else None

    def _move(self, source: EngineHandle, target: EngineHandle,
              uid: int):
        """One LIVE sequence move (export -> serialize/ship -> verify
        -> import), instrumented: returns ``(ref, blocks, bytes,
        duration_s, transport)`` and feeds the migration-stall
        accumulators. ``transport`` is the schema-v10 attribution
        ({mode, bytes, crc_verify_s, retries}); a CRC/torn/version
        rejection raises ``WireError`` with the target engine
        untouched (import validates before it allocates)."""
        t0 = time.perf_counter()
        ref = source.export(uid)
        if self._corrupt_next_wire and ref.path is not None:
            _corrupt_wire_file(ref.path)
            self._corrupt_next_wire = False
        try:
            if (getattr(source, "family", None) == "tcp"
                    or getattr(target, "family", None) == "tcp"):
                # the spool is (notionally) not shared across hosts:
                # stream the doc over the framed side channel instead
                # of handing the target a path it could not open
                data = source.fetch_wire(ref.path)
                st = target.stage_bytes(data)
                target.commit_import(uid)
                info = {"mode": "tcp", "bytes": st["bytes"],
                        "crc_verify_s": st["crc_verify_s"]}
            else:
                info = target.import_doc(ref)  # WireError on damage
        except WireError:
            # keep the damaged file for post-mortem — renamed so it can
            # never be re-consumed, pruned past keep_rejected so a
            # rejection loop can't grow the spool unboundedly (the
            # checkpoint keep_last stance, applied to the wire spool)
            if ref.path is not None:
                _retain_rejected(ref.path, self.keep_rejected)
            raise
        dur = time.perf_counter() - t0
        blocks = ref.blocks_written
        # an in-process doc move reports the SERIALIZED size too (the
        # satellite: bytes = what would cross a boundary, never the
        # nbytes sum) — computed HERE, outside the timed window, so the
        # floor's stall numbers don't include a serialization the
        # in-process transport never performs
        nbytes = (int(info["bytes"]) if "bytes" in info
                  else wire.doc_wire_bytes(ref.doc))
        self.handoff_blocks += blocks
        self.handoff_bytes += nbytes
        self.handoff_durations.append(dur)
        transport = {"mode": info["mode"], "bytes": nbytes,
                     "crc_verify_s": info.get("crc_verify_s"),
                     "retries": self._uid_wire_rejects.get(uid, 0)}
        return ref, blocks, nbytes, dur, transport

    def _replay_transport(self, uid: int) -> dict:
        """The transport attribution for a replay-migration: no KV
        ships (the source pool is unreachable or its export was
        rejected), so bytes are honestly 0 and the replay length on
        the record names the catch-up cost instead."""
        return {"mode": "replay", "bytes": 0, "crc_verify_s": None,
                "retries": self._uid_wire_rejects.get(uid, 0)}

    # -- async live migration (round 22, DESIGN.md section 28) ---------

    def _start_move(self, source, target, uid: int,
                    reason: str) -> None:
        """Phase 1 (end of round N): snapshot the sequence to the
        wire WITHOUT evicting (``export_keep``) — the source keeps
        decoding it through the whole ship window. Phases 2/3 run
        inside round N+1 (``_ship_pending_moves`` between the step
        fan-out and collect; ``_commit_pending_moves`` after)."""
        ref = source.export_keep(uid)
        if self._corrupt_next_wire and ref.path is not None:
            _corrupt_wire_file(ref.path)
            self._corrupt_next_wire = False
        self._pending_moves[uid] = {
            "uid": uid, "source": source, "target": target,
            "ref": ref, "reason": reason, "stage": None,
            "t0": time.perf_counter(), "state": "exported"}

    def _ship_pending_moves(self) -> None:
        """Phase 2: stage each exported document on its target while
        every worker decodes its in-flight step. Failures here abort
        with the SOURCE UNDISTURBED — nothing was evicted yet, so a
        corrupt ship costs one ``wire_rejected`` record and the
        request never stops decoding (no replay, no reroute)."""
        for uid, mv in list(self._pending_moves.items()):
            if mv["state"] != "exported":
                continue
            source, target, ref = mv["source"], mv["target"], mv["ref"]
            if not source.alive or not target.alive:
                self._abort_move(mv, "member died before ship")
                continue
            try:
                if (getattr(source, "family", None) == "tcp"
                        or getattr(target, "family", None) == "tcp"):
                    # the spool is (notionally) not shared across
                    # hosts: stream source spool -> router -> target
                    # over the sockets' framed side channel
                    data = source.fetch_wire(ref.path)
                    mv["stage"] = target.stage_bytes(data)
                else:
                    mv["stage"] = target.stage_ref(ref)
            except WireError as e:
                self.wire_rejects += 1
                self._uid_wire_rejects[uid] = \
                    self._uid_wire_rejects.get(uid, 0) + 1
                self._record("wire_rejected", uid, source=source.id,
                             target=target.id, reason=str(e))
                self._event({"event": "wire_rejected",
                             "uid": int(uid), "source": source.id,
                             "target": target.id,
                             "context": "async_ship",
                             "reason": str(e)})
                if ref.path is not None:
                    _retain_rejected(ref.path, self.keep_rejected)
                del self._pending_moves[uid]
                continue
            except TransportError as e:
                # the failing member's own step collect declares the
                # death; the move dissolves (the source still owns
                # the request and its snapshot still lists it)
                self._abort_move(mv, f"{type(e).__name__}: {e}")
                continue
            mv["state"] = "staged"

    def _abort_move(self, mv: dict, why: str) -> None:
        """Dissolve one pending move with the source outcome standing
        (it never evicted); drop any staged doc on the target."""
        uid = mv["uid"]
        if mv.get("stage") is not None and mv["target"].alive:
            try:
                mv["target"].discard_stage(uid)
            except (TransportError, ValueError):
                pass
        self._event({"event": "move_aborted", "uid": int(uid),
                     "source": mv["source"].id,
                     "target": mv["target"].id, "reason": why})
        self._pending_moves.pop(uid, None)

    def _drop_pending_moves(self, h) -> None:
        """A dying member dissolves every pending move it touches: as
        the SOURCE the sequence stayed resident through the ship
        window so the snapshot replay recovers it; as the TARGET the
        source still owns it — either way nothing is lost."""
        for uid, mv in list(self._pending_moves.items()):
            if mv["source"] is h or mv["target"] is h:
                self._abort_move(mv, f"member {h.id} died mid-move")

    def _commit_pending_moves(self) -> None:
        """Phase 3 (after the round's collect): settle every shipped
        move. ``finish_export`` evicts on the source and returns the
        FINAL token list; the staged doc commits with that list
        patched in — ``emitted`` stays at the ship point, so the
        target's engine teacher-forces exactly the ship-window delta
        (the one replay the moving request pays). An abort status
        (finished/failed/preempted mid-ship) just discards the stage.
        The recorded ``duration_s`` is the commit stall alone — the
        ship wall is ``ship_s``, overlapped with decoding by
        construction."""
        for uid, mv in list(self._pending_moves.items()):
            if mv["state"] != "staged":
                continue
            source, target = mv["source"], mv["target"]
            del self._pending_moves[uid]
            if not source.alive or not target.alive:
                self._abort_move({**mv}, "member died before commit")
                continue
            t_commit = time.perf_counter()
            try:
                delta = source.finish_export(uid)
            except TransportError:
                continue    # the source's death is being declared
            if delta.get("status") != "resident":
                try:
                    target.discard_stage(uid)
                except (TransportError, ValueError):
                    pass
                self._event({"event": "move_aborted", "uid": int(uid),
                             "source": source.id, "target": target.id,
                             "reason": (f"request "
                                        f"{delta.get('status')} "
                                        "during ship window")})
                continue
            try:
                info = target.commit_import(uid, out=delta["out"])
            except TransportError as e:
                self._transport_death(target, e)
                self._resume_from_delta(source, uid, delta,
                                        mv["reason"])
                continue
            except (WireError, ValueError, RuntimeError):
                self._resume_from_delta(source, uid, delta,
                                        mv["reason"])
                continue
            dur = time.perf_counter() - t_commit
            ship_s = time.perf_counter() - mv["t0"]
            ref, st = mv["ref"], mv["stage"]
            blocks = ref.blocks_written
            nbytes = int(st["bytes"]) or (
                wire.doc_wire_bytes(ref.doc)
                if ref.doc is not None else 0)
            self.handoff_blocks += blocks
            self.handoff_bytes += nbytes
            self.handoff_durations.append(dur)
            self.migrations += 1
            req = self.requests[uid]
            req["engine"] = target.id
            if req.get("session") is not None:
                self._sessions[req["session"]] = target.id
            self._record(
                "migrated", uid, source=source.id, target=target.id,
                reason=mv["reason"], position=int(delta["position"]),
                blocks=blocks, bytes=nbytes,
                duration_s=round(dur, 6), ship_s=round(ship_s, 6),
                catchup_tokens=int(info["catchup_tokens"]),
                transport={"mode": st["mode"], "bytes": nbytes,
                           "crc_verify_s": st.get("crc_verify_s"),
                           "retries": self._uid_wire_rejects.get(
                               uid, 0)})
            # the handoff snapshot-refresh discipline: neither side's
            # stale snapshot may lose or resurrect the moved request
            source.snapshot = source.fetch_snapshot()
            target.snapshot = target.fetch_snapshot()

    def _resume_from_delta(self, source, uid: int, delta: dict,
                           reason: str) -> None:
        """Commit fallback: the source already evicted, so the only
        correct continuation is a replay-resume from the FINAL token
        list ``finish_export`` returned — the full-catch-up
        degenerate case of the same teacher-forcing contract."""
        req = self.requests[uid]
        entry = None
        if source.snapshot is not None:
            entry = next((r for r in source.snapshot["requests"]
                          if int(r["uid"]) == uid), None)
        cands = [h for h in self.alive_handles("decode")
                 if h.id != source.id] or self.alive_handles("decode")
        dest = min(cands, key=self._load_key)
        t0 = time.perf_counter()
        dest.resume_request(
            uid, req["prompt"], req["max_new"], out=delta["out"],
            retries=(entry or {}).get("retries", 0),
            t_submit=(entry or {}).get("t_submit"),
            t_first=(entry or {}).get("t_first"),
            weights_version=(entry or {}).get("weights_version"),
            trace=req.get("trace"), tenant=req.get("tenant"))
        dur = time.perf_counter() - t0
        self.migrations += 1
        req["engine"] = dest.id
        if req.get("session") is not None:
            self._sessions[req["session"]] = dest.id
        self._record("migrated", uid, source=source.id,
                     target=dest.id, reason=f"{reason}_commit_failed",
                     replay=len(delta["out"]), blocks=0, bytes=0,
                     duration_s=round(dur, 6),
                     transport=self._replay_transport(uid))
        source.snapshot = source.fetch_snapshot()
        dest.snapshot = dest.fetch_snapshot()

    def _wire_rejected(self, source: EngineHandle, target: EngineHandle,
                       uid: int, err: WireError, context: str,
                       exclude=()) -> None:
        """A wire handoff failed integrity checks: record the named
        reason, then re-route the request by REPLAY from the source's
        last router-held snapshot (export already evicted it there —
        the stale snapshot still lists the request with its emitted
        tokens, and replay from ANY out-prefix regenerates the same
        continuation, so token identity survives the rejected file).
        The target engine was never touched (import validates before
        it allocates) and remains a legitimate replay destination."""
        self.wire_rejects += 1
        self._uid_wire_rejects[uid] = \
            self._uid_wire_rejects.get(uid, 0) + 1
        self._record("wire_rejected", uid, source=source.id,
                     target=target.id, reason=str(err))
        self._event({"event": "wire_rejected", "uid": int(uid),
                     "source": source.id, "target": target.id,
                     "context": context, "reason": str(err)})
        entry = None
        if source.snapshot is not None:
            entry = next((r for r in source.snapshot["requests"]
                          if int(r["uid"]) == uid), None)
        req = self.requests[uid]
        cands = [h for h in self.alive_handles("decode")
                 if h.id not in exclude]
        dest = min(cands or self.alive_handles("decode"),
                   key=self._load_key)
        t0 = time.perf_counter()
        if entry is not None:
            dest.resume_request(uid, entry["prompt"], entry["max_new"],
                                out=entry["out"],
                                retries=entry["retries"],
                                t_submit=entry.get("t_submit"),
                                t_first=entry.get("t_first"),
                                weights_version=entry.get(
                                    "weights_version"),
                                trace=entry.get("trace_id",
                                                req.get("trace")),
                                tenant=entry.get("tenant",
                                                 req.get("tenant")))
            replay = len(entry["out"])
        else:
            # no snapshot entry (a submit-then-immediate-move corner):
            # replay from the request book — more catch-up, same tokens
            dest.resume_request(uid, req["prompt"], req["max_new"],
                                trace=req.get("trace"),
                                tenant=req.get("tenant"))
            replay = 0
        dur = time.perf_counter() - t0
        req["engine"] = dest.id
        if req.get("session") is not None:
            # the reroute moved the session's KV locality with it — a
            # stale affinity entry would split the session across two
            # live engines (the success-path handoff updates it too)
            self._sessions[req["session"]] = dest.id
        self.migrations += 1
        self._record("migrated", uid, source=source.id, target=dest.id,
                     reason="wire_rejected", replay=replay, blocks=0,
                     bytes=0, duration_s=round(dur, 6),
                     transport=self._replay_transport(uid))
        # the uid is gone from the source engine (export evicted it):
        # refresh its snapshot so a later death can't resurrect it, and
        # the destination's so a later death can't lose it
        source.snapshot = source.fetch_snapshot()
        dest.snapshot = dest.fetch_snapshot()

    def _handoff_completed_prefills(self) -> None:
        """Ship every fully-prefilled sequence off the prefill tier.
        No decode capacity right now -> the sequence PARKS (the
        prefill tier steps with ``prefill_only=True``, so a parked
        sequence makes no decode progress there) and the handoff is
        retried next round; a burst larger than the decode tier's
        total capacity surfaces as ``run()``'s fleet-stalled error
        rather than silently decoding on the wrong tier — tier purity
        is what the dispatch-count proof pins."""
        for ph in self.alive_handles("prefill"):
            if ph.digest(light=True)["active"] < 1:
                continue        # nothing resident, nothing to ship
            ready = [s["uid"] for s in ph.digest()["slots"]
                     if s["prompt_done"]]
            for uid in ready:
                req = self.requests[uid]
                target = self._placement_target(len(req["prompt"]),
                                                req["max_new"])
                if target is None:
                    continue
                try:
                    ref, blocks, nbytes, dur, transport = \
                        self._move(ph, target, uid)
                except WireError as e:
                    self._wire_rejected(ph, target, uid, e,
                                        context="handoff")
                    continue
                self.handoffs += 1
                req["engine"] = target.id
                if req["session"] is not None:
                    self._sessions[req["session"]] = target.id
                self._record("handoff", uid, source=ph.id,
                             target=target.id, reason="prefill_done",
                             position=ref.position, blocks=blocks,
                             bytes=nbytes, duration_s=round(dur, 6),
                             transport=transport)
                # refresh BOTH snapshots now: a kill before the next
                # cadence snapshot must neither lose the moved request
                # (target's snapshot predates it) nor resurrect it on
                # the source (whose stale snapshot still lists it)
                ph.snapshot = ph.fetch_snapshot()
                target.snapshot = target.fetch_snapshot()

    def _migrate_pool_pressure(self) -> None:
        """A starved engine (head-of-line waiter has a free slot but
        not its block reservation) moves its YOUNGEST fully-prefilled
        running sequence to a peer with capacity — a LIVE handoff, no
        replay. The same victim policy as the engine's own preemption
        (the oldest resident keeps making progress), but the victim
        keeps running instead of losing its KV."""
        for h in self.alive_handles("decode"):
            # light digest for the steady-state early exits; the
            # per-slot list is only materialized in the rare
            # pool-starved case that actually picks a victim
            d = h.digest(light=True)
            if not d["waiting"] or d["free_slots"] < 1:
                continue                    # idle, or slot-starved
            head = d["head"]
            need = h.blocks_needed(head["prompt_len"], head["max_new"])
            if need <= d["free_blocks"] + d["evictable"]:
                continue                    # admission will take it
            victims = [(s["admit_index"], s["uid"], s["prompt_len"],
                        s["max_new"])
                       for s in h.digest()["slots"]
                       if s["prompt_done"]
                       and s["uid"] not in self._pending_moves]
            if not victims:
                continue
            _, uid, plen, mnew = max(victims)
            target = self._placement_target(plen, mnew,
                                            exclude=(h.id,))
            if target is None:
                continue
            if self.async_migration:
                # async live migration: snapshot now, ship during the
                # next round's decode step, commit after its collect —
                # the source never stalls on the wire
                self._start_move(h, target, uid,
                                 reason="pool_pressure")
                continue
            try:
                ref, blocks, nbytes, dur, transport = \
                    self._move(h, target, uid)
            except WireError as e:
                self._wire_rejected(h, target, uid, e,
                                    context="pool_pressure")
                continue
            self.migrations += 1
            self.requests[uid]["engine"] = target.id
            self._record("migrated", uid, source=h.id,
                         target=target.id, reason="pool_pressure",
                         position=ref.position, blocks=blocks,
                         bytes=nbytes, duration_s=round(dur, 6),
                         transport=transport)
            # the handoff snapshot-refresh discipline (see above)
            h.snapshot = h.fetch_snapshot()
            target.snapshot = target.fetch_snapshot()

    # -- failure (the chaos drill's surface) ---------------------------

    def schedule_kill(self, engine_id: str, at_round: int) -> None:
        """Arm a deterministic engine kill at the START of fleet round
        ``at_round`` (the round's snapshot cadence has NOT yet run —
        the last snapshot honestly lags by up to ``snapshot_every``
        rounds, and replay fills exactly that gap). Under the process
        transport this is a REAL SIGKILL of the worker process."""
        if engine_id not in self.by_id:
            raise ValueError(f"unknown engine id {engine_id!r} "
                             f"(fleet: {sorted(self.by_id)})")
        if at_round < 0:
            raise ValueError(f"kill round must be >= 0, got {at_round}")
        self._kills[at_round].append(engine_id)

    def _transport_death(self, h: EngineHandle, err: Exception) -> None:
        """The liveness ladder's verdict: a worker stopped answering
        (deadline + bounded-backoff retries exhausted, or its
        connection dropped). Declare it dead — SIGKILL the process so a
        zombie can't answer a stale request later — and migrate its
        requests from the last snapshot, exactly the kill path."""
        self._event({"event": "worker_dead", "engine": h.id,
                     "round": self.rounds,
                     "reason": f"{type(err).__name__}: {err}"})
        # the router's OWN evidence, dumped BEFORE the SIGKILL closes
        # the book: the dead worker's flight recorder died with it —
        # this is the half of the post-mortem only the router holds
        self._dump_router_postmortem(
            h, f"{type(err).__name__}: {err}")
        h.kill()
        h.killed_at_round = self.rounds
        self.kills += 1
        self._event({"event": "engine_killed", "engine": h.id,
                     "round": self.rounds})
        self._drop_pending_moves(h)
        self._recover_dead(h)

    def kill_engine(self, engine_id: str) -> int:
        """Kill one engine NOW and migrate its in-flight requests to
        the survivors from its last snapshot: finished/failed results
        ride over verbatim, every live request re-enters a survivor's
        queue for replay-resume (``resume_request`` — prompt
        re-prefilled, recorded tokens teacher-forced, so the rebuilt KV
        write history and the remaining tokens are bit-identical to the
        uninterrupted run's). Returns the number of migrated requests.
        In-process the engine object is dropped; under the process
        transport the worker is SIGKILLed — a real dead host either
        way, its pool unreachable."""
        h = self.by_id.get(engine_id)
        if h is None:
            raise ValueError(f"unknown engine id {engine_id!r}")
        if not h.alive:
            return 0
        # same evidence discipline as the liveness-ladder death: the
        # worker's own flight recorder is about to become unreachable
        self._dump_router_postmortem(h, "engine killed (scheduled "
                                        "kill / chaos)")
        h.kill()
        h.killed_at_round = self.rounds
        self.kills += 1
        self._event({"event": "engine_killed", "engine": h.id,
                     "round": self.rounds})
        self._drop_pending_moves(h)
        return self._recover_dead(h)

    def _recover_dead(self, h: EngineHandle) -> int:
        """Migrate a dead member's requests off its last router-held
        snapshot (replay-resume on survivors)."""
        snap = h.snapshot
        if snap is None:
            return 0
        self._dead_finished.update(
            {int(u): list(t) for u, t in snap["finished"].items()})
        self._dead_failed.update(
            {int(u): dict(i) for u, i in snap["failed"].items()})
        # a dead prefill engine's queue re-enters the prefill tier
        # while one exists (tier purity survives the kill); decode
        # requests always land on decode survivors
        survivors = (self.alive_handles("prefill")
                     if h.role == "prefill" else [])
        survivors = survivors or self.alive_handles("decode")
        if not survivors:
            raise RuntimeError("last decode engine killed: the fleet "
                               "has nowhere to migrate its requests")
        moved = 0
        for req in snap["requests"]:
            target = min(survivors, key=self._load_key)
            t0 = time.perf_counter()
            target.resume_request(
                req["uid"], req["prompt"], req["max_new"],
                out=req["out"], retries=req["retries"],
                t_submit=req.get("t_submit"),
                t_first=req.get("t_first"),
                weights_version=req.get("weights_version"),
                trace=req.get("trace_id", self.requests.get(
                    int(req["uid"]), {}).get("trace")),
                tenant=req.get("tenant", self.requests.get(
                    int(req["uid"]), {}).get("tenant")))
            dur = time.perf_counter() - t0
            self.requests[int(req["uid"])]["engine"] = target.id
            # a replay-migration ships no KV (the dead pool is
            # unreachable): blocks/bytes are honestly 0 and the replay
            # length names the catch-up cost instead; duration_s here
            # is the re-queue cost only — the replay itself shows up
            # in the request's own span stream
            self._record("migrated", req["uid"], source=h.id,
                         target=target.id, reason="engine_killed",
                         replay=len(req["out"]), blocks=0, bytes=0,
                         duration_s=round(dur, 6),
                         transport=self._replay_transport(
                             int(req["uid"])))
            # a survivor dying right after must re-migrate this too
            target.snapshot = target.fetch_snapshot()
            moved += 1
        self.migrations += moved
        return moved

    # -- elastic membership (round 20, DESIGN.md section 26) -----------

    def next_decode_eid(self) -> str:
        """Mint the next decode engine id. Spawned members continue
        the e-numbering and ids are NEVER reused — a retired e1 keeps
        its slot in the book and its replacement is e2, so every
        record ever written still names a unique member."""
        eid = f"{DECODE_PREFIX}{self._decode_serial}"
        self._decode_serial += 1
        return eid

    def add_engine(self, handle) -> None:
        """Admit one WARMED decode member into the live fleet (the
        autoscaler's scale-up half). The construction-time gates apply
        unchanged — model identity against the incumbents, the
        single-device membership check, and serving-version agreement
        — so an elastic join can never relax what ``__init__``
        enforces. The joining engine must already be warm
        (``EngineHandle.warm``): admission is instant and the next
        round routes to it."""
        if handle.id in self.by_id:
            raise ValueError(f"engine id {handle.id!r} already in the "
                             "fleet (ids are never reused)")
        if handle.role != "decode":
            raise ValueError("elastic members are decode-tier only "
                             f"(got role {handle.role!r})")
        incumbent = next((h for h in self.handles if h.alive), None)
        if incumbent is not None:
            if handle.model_meta() != incumbent.model_meta():
                raise ValueError(
                    "joining engine disagrees on model identity — "
                    "every replica must serve the same weights")
            fleet_v = self._fleet_serving_version()
            join_v = int(handle.digest(light=True)["serving_version"])
            if join_v != fleet_v:
                raise ValueError(
                    f"joining engine serves weights version {join_v} "
                    f"but the fleet serves {fleet_v} — load the "
                    "current checkpoint before add_engine")
        handle.validate_member()
        if hasattr(handle, "on_reconnect"):
            handle.on_reconnect = self._note_reconnect
        self.handles.append(handle)
        self.by_id[handle.id] = handle
        # the step-0 snapshot discipline: a kill before the first
        # cadence snapshot must still know this member's requests
        handle.snapshot = handle.fetch_snapshot()

    def retire_engine(self, engine_id: str) -> int:
        """Remove one decode member from the live fleet with ZERO shed
        (the autoscaler's scale-down half): drain it through the
        rolling-deploy primitive — live residents ship their KV to
        peers, everything else replay-resumes, nothing touches a queue
        limit — then close its transport gracefully. The handle stays
        in ``handles`` marked ``retired`` (distinct from dead: no kill
        round, nothing to post-mortem). Returns the number of drained
        requests. Refuses to retire the last alive decode engine —
        the min-floor is the controller's invariant, this is the
        router's own."""
        h = self.by_id.get(engine_id)
        if h is None:
            raise ValueError(f"unknown engine id {engine_id!r}")
        if not h.alive:
            raise ValueError(f"engine {engine_id!r} is not alive")
        if h.role != "decode":
            raise ValueError("only decode members retire (the "
                             "prefill tier is static)")
        if len(self.alive_handles("decode")) <= 1:
            raise ValueError("refusing to retire the only alive "
                             "decode engine (scale-to-zero is "
                             "structurally impossible)")
        drained = self._drain_engine(h)
        h.close()
        h.alive = False
        h.retired = True
        # a drained book must never resurrect requests the peers now
        # hold — retirement is not a death, there is nothing to
        # migrate from
        h.snapshot = None
        if h.transport == "inproc":
            h.engine = None     # release the pool, like a dead host's
        return drained

    # -- live weight hot-swap (round 17, DESIGN.md section 23) ---------

    def schedule_deploy(self, ckpt_dir: str, at_round: int,
                        step: int | None = None) -> None:
        """Arm a rolling deploy at the START of fleet round
        ``at_round``: the newest published step under ``ckpt_dir``
        (or the explicit ``step``) is verified by the CRC ladder and
        rolled through the fleet engine by engine — drain by
        migration, swap, re-admit. Fires after that round's kills (a
        deploy never drains onto an engine the round kills) and
        before any engine steps."""
        if at_round < 0:
            raise ValueError(f"deploy round must be >= 0, got "
                             f"{at_round}")
        if at_round in self._deploys:
            raise ValueError(f"a deploy is already scheduled for "
                             f"round {at_round}")
        self._deploys[at_round] = (ckpt_dir, step)

    def deploy_watch(self, ckpt_dir: str, poll_every_s: float) -> None:
        """Arm the deploy-on-publish watcher: poll ``ckpt_dir``'s
        ``latest_verified`` every ``poll_every_s`` seconds of wall
        clock (between rounds — the poll is a directory listing plus a
        CRC ladder, never on the per-step hot path) and roll the fleet
        forward whenever it advances past the current serving version.
        The trainer's existing atomic publish IS the trigger: publish a
        checkpoint mid-serve and the fleet takes it with zero shed (the
        ``rolling_deploy`` contract, CRC rollback included)."""
        if poll_every_s <= 0:
            raise ValueError(f"deploy_watch poll cadence must be > 0, "
                             f"got {poll_every_s}")
        self._watch = (ckpt_dir, float(poll_every_s))
        self._watch_t_last = 0.0

    def _poll_deploy_watch(self) -> bool:
        """The watcher's per-round check (throttled): a verified step
        newer than the fleet's serving version triggers a rolling
        deploy NOW. Runs after scheduled deploys so an explicit
        ``schedule_deploy`` always wins its round."""
        if self._watch is None:
            return False
        ckpt_dir, every = self._watch
        now = time.monotonic()
        if now - self._watch_t_last < every:
            return False
        self._watch_t_last = now
        from ..runtime.weights import VersionLedger
        newest = VersionLedger(ckpt_dir).latest_verified()
        if newest is None or newest <= self._fleet_serving_version():
            return False
        self.rolling_deploy(ckpt_dir, step=newest)
        return True

    def _deploy_record(self, event: str, from_v, to_v, **extra) -> None:
        """One schema-v11 ``deploy`` record (started / engine_swapped
        / completed / rolled_back) on the router's own stream."""
        if self.metrics is not None:
            self.metrics.deploy({"step": self.rounds, "event": event,
                                 "from_version": from_v,
                                 "to_version": to_v, **extra})

    def _rollback_swapped(self, swapped, from_v: int) -> None:
        """Flip already-swapped engines back to ``from_v`` — guarded:
        a SECOND worker dying during the rollback must not let the
        exception escape with no rolled_back record and the fleet
        mixed (a dead engine isn't mixed; it takes the ordinary
        dead-host path — declare, SIGKILL, migrate-from-snapshot)."""
        for s in swapped:
            if not s.alive:
                continue
            try:
                s.set_serving_version(from_v)
            except TransportError as e:
                self._transport_death(s, e)

    def _find_dead(self, suspect) -> "EngineHandle":
        """Which alive handle actually stopped answering? Ping sweep,
        the suspect first (cheap short-deadline heartbeat, the idle-
        member liveness probe); falls back to the suspect when every
        ping answers (a transient that already cleared — declaring
        the suspect dead is then the conservative verdict)."""
        order = [suspect] + [x for x in self.handles
                             if x.alive and x is not suspect]
        for cand in order:
            if not cand.alive:
                continue
            try:
                cand.ping()
            except TransportError:
                return cand
        return suspect

    def _fleet_serving_version(self) -> int:
        vers = sorted({int(h.digest(light=True)["serving_version"])
                       for h in self.handles if h.alive})
        if len(vers) != 1:
            raise RuntimeError(
                f"fleet engines disagree on serving version ({vers}) "
                "— an aborted deploy left a mixed fleet behind")
        return vers[0]

    def rolling_deploy(self, ckpt_dir: str,
                       step: int | None = None) -> dict:
        """Publish new weights into the serving fleet with ZERO shed
        and zero restarts: for each engine in turn, DRAIN it (every
        fully-prefilled resident ships to a peer over the existing KV
        handoff — the PR 10 primitive IS the drain; waiting and
        mid-prefill requests move by replay-resume), swap its weights
        to the ledger-verified target version, and re-admit it. The
        fleet serves BOTH versions mid-deploy: drained requests keep
        their ``weights_version`` pin and finish on the old weights
        wherever they land (every engine double-buffers the old
        version), while new admissions pin the new one.

        Failure is first-class: a target step the CRC ladder rejects —
        or any load failure mid-roll, including a worker dying — rolls
        EVERY already-swapped engine back to the old serving version
        (its weights never left) and emits one ``rolled_back`` deploy
        record whose reason is the one-line named cause plus the
        ``latest_verified_step`` fallback: deploy aborted, no engine
        left mixed, nothing shed."""
        from ..checkpoint import CorruptCheckpointError
        from ..runtime.weights import VersionLedger
        t0 = time.perf_counter()
        ledger = VersionLedger(ckpt_dir)
        from_v = self._fleet_serving_version()
        if self._corrupt_next_deploy is not None:
            # chaos corrupt_deploy: tear the target checkpoint BEFORE
            # the ledger reads it — the CRC ladder must reject it
            frac = self._corrupt_next_deploy
            self._corrupt_next_deploy = None
            tgt = step if step is not None else ledger.latest_step()
            if tgt is not None:
                from ..runtime.chaos import truncate_checkpoint
                truncate_checkpoint(ledger.step_path(tgt), frac=frac)
        target = step if step is not None else ledger.latest_step()

        def rolled_back(reason: str) -> dict:
            import sys
            self.deploy_rollbacks += 1
            fb = ledger.latest_verified()
            line = (f"deploy of step_{target} rolled back: {reason} — "
                    f"fleet stays on version {from_v} (latest "
                    f"verified step: {fb})")
            # the operator-visible one-liner (the checkpoint layer's
            # stderr-notice precedent); the durable copy is the
            # ``rolled_back`` deploy record below
            print(f"fleet: {line}", file=sys.stderr)
            self._deploy_record(
                "rolled_back", from_v, target, reason=line,
                latest_verified=fb,
                duration_s=round(time.perf_counter() - t0, 6))
            self._event({"event": "deploy_rolled_back",
                         "round": self.rounds, "from_version": from_v,
                         "to_version": target, "reason": line})
            return {"status": "rolled_back", "reason": line,
                    "from_version": from_v, "to_version": target,
                    "latest_verified": fb}

        if target is None:
            return rolled_back(
                f"no checkpoint published under {ckpt_dir}")
        ok, why = ledger.verify(target)
        if not ok:
            return rolled_back(f"checkpoint step_{target} rejected "
                               f"({why})")
        if target == from_v:
            return {"status": "noop", "from_version": from_v,
                    "to_version": target}
        self._deploy_record("started", from_v, target,
                            ckpt_dir=ckpt_dir)
        params = None
        swapped: list = []
        drained_total = 0
        h = None
        try:
            for h in [x for x in self.handles if x.alive]:
                if h.transport != "process" and params is None:
                    # in-process: the router loads the checkpoint ONCE
                    # and shares the (read-only, never-donated) params
                    # across replicas; process workers restore from
                    # the shared dir themselves — weights never ride
                    # the socket
                    params = ledger.load(target, h.engine.params)
                drained_total += self._drain_engine(h)
                t1 = time.perf_counter()
                h.load_weights(target, ckpt_dir, target, params=params)
                h.set_serving_version(target)
                swapped.append(h)
                self._deploy_record(
                    "engine_swapped", from_v, target, engine=h.id,
                    duration_s=round(time.perf_counter() - t1, 6))
                h.snapshot = h.fetch_snapshot()
        except TransportError as e:
            # the drain touches PEERS too (imports, resumes) — blame
            # the handle that actually stopped answering, not the one
            # being drained: a misattributed death would SIGKILL a
            # healthy worker and leave the real corpse marked alive
            dead = self._find_dead(h)
            self._transport_death(dead, e)
            self._rollback_swapped(swapped, from_v)
            return rolled_back(
                f"worker {dead.id} died mid-deploy "
                f"({type(e).__name__}: {e}); {len(swapped)} swapped "
                "engine(s) rolled back")
        except (CorruptCheckpointError, ValueError, RuntimeError,
                OSError) as e:
            # the mid-roll failure path: engines already swapped flip
            # their serving version back (the old weights never left —
            # that IS the double buffer), so no engine admits on a
            # version the fleet just refused
            self._rollback_swapped(swapped, from_v)
            return rolled_back(
                f"{type(e).__name__}: {e}; {len(swapped)} swapped "
                "engine(s) rolled back")
        self.deploys += 1
        dur = round(time.perf_counter() - t0, 6)
        self._deploy_record("completed", from_v, target,
                            duration_s=dur, engines=len(swapped),
                            drained=drained_total)
        return {"status": "completed", "from_version": from_v,
                "to_version": target, "engines": len(swapped),
                "drained": drained_total, "duration_s": dur}

    def _drain_engine(self, h) -> int:
        """Empty one engine for its swap: fully-prefilled residents
        move LIVE (export -> import, KV ships, zero replay) to a
        decode peer with capacity; everything else — waiting,
        mid-prefill, or no peer capacity — moves by replay-resume
        (``release_request`` + a peer's ``resume_request``, pin
        attached). Nothing is shed: replay-resume bypasses queue
        limits exactly as kill-migration does. With no alive peer the
        engine swaps IN PLACE — the double-buffered pins keep its
        in-flight requests on their own version regardless."""
        peers = [p for p in self.handles if p.alive and p is not h]
        if not peers:
            return 0
        snap = h.fetch_snapshot()
        h.snapshot = snap
        moved = 0
        for req in snap["requests"]:
            uid = int(req["uid"])
            live = (req.get("state") == "RUNNING"
                    and req.get("prefilled", 0) >= len(req["prompt"]))
            if live:
                target = self._placement_target(
                    len(req["prompt"]), req["max_new"],
                    exclude=(h.id,))
                if target is not None:
                    try:
                        ref, blocks, nbytes, dur, transport = \
                            self._move(h, target, uid)
                    except WireError as e:
                        self._wire_rejected(h, target, uid, e,
                                            context="deploy_drain",
                                            exclude=(h.id,))
                        moved += 1
                        continue
                    self.migrations += 1
                    book = self.requests[uid]
                    book["engine"] = target.id
                    if book.get("session") is not None:
                        self._sessions[book["session"]] = target.id
                    self._record("migrated", uid, source=h.id,
                                 target=target.id,
                                 reason="deploy_drain",
                                 position=ref.position, blocks=blocks,
                                 bytes=nbytes,
                                 duration_s=round(dur, 6),
                                 transport=transport)
                    # refresh BOTH sides per move (the handoff
                    # discipline): a death later in this drain must
                    # neither lose the moved request nor resurrect it
                    # from the source's drain-start snapshot
                    h.snapshot = h.fetch_snapshot()
                    target.snapshot = target.fetch_snapshot()
                    moved += 1
                    continue
            # replay drain (tier-preserving: prefill work re-enters
            # the prefill tier while one exists)
            entry = h.release_request(uid)
            survivors = ([p for p in peers if p.role == h.role]
                         or [p for p in peers if p.role == "decode"]
                         or peers)
            dest = min(survivors, key=self._load_key)
            t1 = time.perf_counter()
            dest.resume_request(
                uid, entry["prompt"], entry["max_new"],
                out=entry["out"], retries=entry["retries"],
                t_submit=entry.get("t_submit"),
                t_first=entry.get("t_first"),
                weights_version=entry.get("weights_version"),
                trace=entry.get("trace_id"),
                tenant=entry.get("tenant"))
            dur = time.perf_counter() - t1
            self.migrations += 1
            book = self.requests[uid]
            book["engine"] = dest.id
            if book.get("session") is not None:
                self._sessions[book["session"]] = dest.id
            self._record("migrated", uid, source=h.id, target=dest.id,
                         reason="deploy_drain",
                         replay=len(entry["out"]), blocks=0, bytes=0,
                         duration_s=round(dur, 6),
                         transport=self._replay_transport(uid))
            h.snapshot = h.fetch_snapshot()
            dest.snapshot = dest.fetch_snapshot()
            moved += 1
        return moved

    # -- drain ---------------------------------------------------------

    @property
    def has_work(self) -> bool:
        return any(h.has_work for h in self.handles)

    def _pending_kills(self) -> bool:
        scheduled = any(self.by_id[eid].alive
                        for ids in self._kills.values() for eid in ids)
        chaos = self.fleet_chaos is not None and any(
            not f.fired for f in self.fleet_chaos.faults
            if f.kind in ("kill_worker", "hang_worker"))
        return scheduled or chaos

    def run(self, log_every: int = 0) -> dict[int, list[int]]:
        """Drain the fleet: round until every request finished or
        failed (scheduled kills past the drain point are dropped — a
        dead-on-arrival fault has nothing to kill). ``log_every``
        emits one ``decode`` cadence record per engine through ITS OWN
        writer every that-many rounds (the engines are stepped
        manually, so the router owns the cadence ``DecodeEngine.run``
        normally would)."""
        while self.has_work:
            did = self.step()
            if log_every > 0 and self.rounds % log_every == 0:
                self._emit_decode_records()
            if not did and self.has_work and not self._pending_kills():
                raise RuntimeError(
                    "fleet stalled: waiting requests but no engine ran "
                    "work and no kill is pending")
        self._emit_decode_records()
        # drain-end ops-plane flush: the transport block lands on the
        # router stream and the status doc publishes FINAL (forced
        # past the throttle — a finished run's doc must say drained)
        self.emit_transport_stats()
        self._publish_status(force=True)
        return self.results()

    def _emit_decode_records(self) -> None:
        for h in self.handles:
            if not h.alive:
                continue
            try:
                h.emit_decode()
            except TransportError as e:
                self._transport_death(h, e)

    def results(self) -> dict[int, list[int]]:
        """Merged per-uid outcomes across the whole fleet, dead
        engines' pre-kill completions included. A request completed on
        a dead engine AFTER its last snapshot re-completes on a
        survivor (replay is deterministic), so the merge can never see
        two different answers for one uid."""
        out = dict(self._dead_finished)
        for h in self.handles:
            if h.alive:
                out.update(h.results())
        return out

    def failed(self) -> dict[int, dict]:
        out = dict(self._dead_failed)
        for h in self.handles:
            if h.alive:
                out.update(h.failed_map())
        return out

    # -- the payload/bench surface -------------------------------------

    def fleet_stats(self) -> dict:
        """Fleet-level counters + per-engine summaries — the generate
        CLI payload block and the bench rows' raw material."""
        per_engine = {}
        for h in self.handles:
            if not h.alive:
                per_engine[h.id] = {"alive": False,
                                    "retired": getattr(h, "retired",
                                                       False),
                                    "killed_at_round": h.killed_at_round}
                continue
            per_engine[h.id] = {"alive": True, "role": h.role,
                                "serving_version": int(
                                    h.digest(light=True)
                                    ["serving_version"]),
                                **h.stats()}
        stats = {
            "engines": per_engine,
            "rounds": self.rounds,
            "routed": self.routed,
            "routed_by": dict(self.routed_by),
            "handoffs": self.handoffs,
            "migrations": self.migrations,
            "sheds": self.sheds,
            "kills": self.kills,
            "prefix_routed_hit_blocks": self.prefix_routed_hit_blocks,
            # the migration-stall surface (live moves only): blocks +
            # SERIALIZED wire bytes shipped and the per-move wall-clock
            # list's summary
            "handoff_blocks": self.handoff_blocks,
            "handoff_bytes": self.handoff_bytes,
            "wire_rejects": self.wire_rejects,
            # network-boundary robustness (round 22): links that
            # dropped and were healed by reconnect-and-replay instead
            # of being declared dead
            "reconnects": self.reconnects_total,
            # live weight hot-swap (round 17): completed rolling
            # deploys and CRC/mid-roll rollbacks
            "deploys": self.deploys,
            "deploy_rollbacks": self.deploy_rollbacks,
            # transport cost attribution (round 18): per-worker RPC
            # op percentiles + the round wall clock (None per engine
            # in-process — nothing to price)
            "transport": self.transport_stats(),
        }
        if self.handoff_durations:
            import numpy as np
            stats["handoff_stall_p90_ms"] = round(float(np.percentile(
                np.asarray(self.handoff_durations), 90)) * 1e3, 3)
        return stats


def _retain_rejected(path: str, keep: int) -> None:
    """Bounded post-mortem retention for a REJECTED wire doc: rename
    it ``*.rejected`` (so no retry can re-consume the damaged bytes)
    and prune the spool's oldest rejected files past ``keep`` — the
    ``checkpoint.keep_last`` discipline applied to the wire spool. A
    chaos loop of rejections must never grow a worker's spool without
    bound."""
    import os
    try:
        os.replace(path, path + ".rejected")
    except OSError:
        return
    spool = os.path.dirname(path) or "."
    try:
        rejected = [os.path.join(spool, name)
                    for name in os.listdir(spool)
                    if name.endswith(".rejected")]
    except OSError:
        return

    def age(p):
        try:
            return (os.path.getmtime(p), p)
        except OSError:
            return (0.0, p)

    rejected.sort(key=age)
    for old in (rejected if keep <= 0 else rejected[:-keep]):
        try:
            os.unlink(old)
        except OSError:
            pass


def _corrupt_wire_file(path: str) -> None:
    """The ``corrupt_wire`` chaos mechanics: flip a run of bytes just
    past the middle of a published wire file — inside the array payload
    region for any realistic KV doc — simulating in-transit damage that
    slipped past rename atomicity. The per-array CRC (or, for damage
    landing on container structure, the npz parse itself) must reject
    the import. The flipped run is 128 bytes: a zip member's local
    header + extra-field padding (bytes NO checksum covers) can span
    ~70 bytes, and an 8-byte flip that happened to land entirely
    inside that dead zone once sailed through every integrity check —
    the run must be wider than any possible gap so it always reaches
    CRC-covered payload."""
    import os
    size = os.path.getsize(path)
    off = max(1, int(size * 0.55))
    with open(path, "r+b") as f:
        f.seek(off)
        chunk = f.read(128)
        f.seek(off)
        f.write(bytes(b ^ 0xFF for b in chunk))
