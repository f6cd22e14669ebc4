"""High-throughput decode engine: paged KV + continuous batching.

The lockstep decoder (``models.lm.generate``) is a fixed-batch program:
every sequence enters together, decodes in step, and the batch ends
when the longest member does — between requests the chip idles and
short sequences pad out long ones. This engine is the Orca-style
answer, hand-rolled in the repo's idiom (explicit state, no framework
wrappers):

- **Paged KV** (``decode/paged.py``): one static-shape block pool for
  every sequence; a finished sequence frees its blocks with a host-side
  table edit — no recompile, no pool reshape.
- **Continuous batching**: a host scheduler admits queued prompts into
  freed slots *between* compiled steps. The compiled surface is a small
  static set — one decode program per power-of-two slot bucket, one
  prefill program per power-of-two chunk bucket, one mixed program —
  so steady-state steps are dispatch-only and the compile count is
  bounded by the bucket count (the ``--log_every`` chunk discipline,
  recompile-guard-tested).
- **Chunked prefill**: long prompts enter in bounded chunks, so a new
  long prompt costs one chunk per engine step instead of stalling every
  running decode behind a full-prompt pass. A chunk of the full size
  RIDES with the step's decode batch in one ``mixed`` program
  (``_mixed_batch`` says when), so the weights are read once for both
  kinds of row; a prompt's tail chunks run in a program of their own
  before the batch's.
- **Fused sampling** (``decode/sampling.py``): temperature / top-k /
  top-p picked inside the compiled step, keyed on
  ``(engine seed, sequence uid, position)`` — continuous-batching
  output is token-identical to decoding each sequence alone.

This module is the SCHEDULER. What a step program is made of is
``decode/programs.py``'s, built from the model's face (``models/face.py``:
a family is one file) and the cache (``decode/paged.py``); the engine
calls ``_program(kind, bucket)(params, carry, operand) -> (carry,
result)`` — ONE packed ``int32`` vector in (``programs.pack``: one
host-to-device transfer a dispatch), ONE packed ``int32`` array out
(one blocking read; a row whose logits were not finite reads negative)
— and reads sizes only from the model: ``vocab``,
``max_seq_len``, the kinds of its ``layers``, its ``cache_spec``. The
blocking read of a step's program comes AFTER the launch of the next
step's (``_launch`` / ``_collect``; ``step``'s docstring says which
reads wait and what a caller may read when): the scheduler works from
counts it has at launch, and a slot's next token is handed from one
program to the next on the device (the carry's token store). A
recurrent layer's state lives beside the pool, by slot, and a window
layer's blocks in a second pool with a short table and a free list of
their own (``wtables``, ``free_wblocks``: a slot's window blocks are a
ring, taken at admission and handed back with the slot; ``paged.py``).
A chunked layer (chunk-summarised attention) has an index in BOTH
pools: its ring is the window kind's, and a ROW of the full kind's pool
stands for one finished chunk of ``block_size`` positions, so a table
of ``max_blocks_per_seq`` blocks holds ``block_size`` times the
positions (``capacity``, ``_blocks_needed``); what moves a sequence by
its ONE block table alone — prefix hits,
speculation, a mesh, the KV handoff, snapshots, the spill tier —
refuses in one line for such a model
(``_refuse_kept_beside``), by what the model is and under no flag. A
latent-cache layer's rows live IN the pool (one row a token, no heads:
``paged.py``), so all of those carry them unchanged and only what needs
KV heads refuses: int8 scales (``paged.init_pool``) and a mesh (here).
An expert model's step programs return their layers' counters after the
picks; the step's digest folds them (``EXPERT_COUNTERS``).
``mesh=None`` runs single-device; a model-axis mesh the Megatron decode
layout (``parallel.lm``; the collectives are in ``decode/programs.py``).

Determinism contract: a sequence's output depends only on
``(params, engine seed, uid, prompt, sampling config)`` — never on slot
assignment, admission order, chunk interleaving, or pool layout
(tests/test_decode_engine.py pins paged==contiguous bit-for-bit at f32
and continuous==sequential token-for-token).

Reliability layer (round 10, DESIGN.md section 16 — the serving
counterpart of the self-healing training ladder):

- **In-graph logits guardrail**: every compiled step returns a per-row
  all-finite flag over the full-vocab logits
  (``runtime.guardrails.rows_finite``) next to the picks; a non-finite
  sequence is **quarantined** at that step — slot and blocks freed
  (blocks scrubbed: NaN stale bytes are the one thing the masks can't
  neutralize), uid reported FAILED with a reason, every other sequence
  untouched. Because the sampling keys and the per-slot gathers never
  reference the slot, survivors are bit-identical to a run that never
  admitted the poisoned request.
- **Per-request retry**: a quarantined request with budget left
  (``ServePolicy.max_retries``) re-enters the queue and is replayed —
  prompt re-prefilled, already-emitted tokens teacher-forced through
  the decode path so the KV write history (and hence the int8
  quantization history) is bit-identical to the uninterrupted run's.
  The same replay mechanism serves **preemption** (pool-pressure
  eviction of the youngest sequence back to WAITING) and the
  supervisor's **snapshot-resume** (``decode/supervise.py``).
- **Admission control**: bounded waiting queue (``queue_limit``,
  reject-on-full with ``AdmissionError``), per-request TTL
  (``deadline_steps``), and lifecycle telemetry — one schema-v4
  ``request`` record per transition (admitted / preempted / retried /
  quarantined / completed / rejected / expired).

Raw-latency layer (round 12, DESIGN.md section 18):

- **Speculative decoding** (``EngineConfig(speculate=k)``): an n-gram
  prompt-copy drafter (``decode/draft.py`` — no second model, state a
  pure function of ``prompt + out``) proposes up to ``k`` tokens per
  slot; ONE compiled verify dispatch (``programs.py::_verify_fn``)
  accepts the matched greedy prefix, so a step emits ``1 + accepted``
  tokens per sequence at one dispatch's host/scheduler cost, and the
  pool's write history holds exactly the rows the non-speculative
  engine would have written: token identity BIT-FOR-BIT at every
  kv_dtype, and nothing to roll back. Replay teacher-forces recorded tokens as drafts (all
  accepted on a healthy replay), so quarantine/preempt/crash-resume
  re-draft identically; teacher-forced tokens stay OUT of the
  ``drafted_tokens``/``accepted_tokens`` telemetry pair, which scores
  the live n-gram drafter only.

Shared-prefix layer (round 13, DESIGN.md section 19 — the capacity
multiplier: most requests share a long system prompt, so N admissions
should pay ~1 prefill and ~1 copy of the shared KV, not N):

- **Radix prefix cache** (``decode/prefix.py``,
  ``EngineConfig(prefix_cache=True)``, the default): every fully
  prefilled FULL block of a prompt is content-keyed into a host-side
  radix tree (the edge is the block's token tuple); admission walks the
  tree and maps every hit block straight into the new slot's table —
  refcounted, zero recompiles (tables are data). A hit block's bytes
  are bit-identical to what the sequence's own prefill would have
  written (full-block content is a pure function of the token prefix
  and the engine config — chunk boundaries inside full blocks are
  position-determined, so even the int8 requant history matches), and
  the walk always leaves >= 1 prompt token to prefill so the first
  pick comes from the same prefill program the unshared engine ran:
  prefix-cached output == unshared output token for token at every
  kv_dtype.
- **Copy-on-write**: a shared block is read-only. Structurally no
  scheduler write ever aims at one (hits cover only fully-prefilled
  prompt blocks; every write — decode, chunked prefill, spec-decode
  verify, whose rejected rows redirect to scratch — lands at or past
  the prefill frontier), and ``_cow_private`` ENFORCES it: any write
  window that would touch a shared block first takes a bit-identical
  private copy (``paged.copy_block``), leaving every sharer's bytes
  untouched. ``cow_copies`` counts triggers (0 in steady state — the
  invariant, pinned by tests).
- **Reliability composition**: quarantine and preemption DECREF shared
  blocks instead of scrubbing while sharers remain (a poisoned sharer
  must not zero an innocent survivor's prefix); the last distrusted
  release scrubs-and-detaches. Chaos-corrupted blocks are poisoned in
  the tree immediately (no new sharer inherits the NaN). refs-0 cached
  blocks are reclaimed LRU under pool pressure, so retention never
  shrinks usable capacity. Snapshot v4 persists the tree + refcounts;
  resume rebuilds the share graph through replay (the first replayed
  sharer re-prefills and re-inserts, later ones hit).

Live weight hot-swap layer (round 17, DESIGN.md section 23 — the
fleet's rolling deploy rides it):

- **Double-buffered weights**: ``weights: {version -> params}`` with
  ``serving_version`` naming what new admissions take
  (``load_weights`` / ``set_serving_version``). Weights are traced
  OPERANDS of every compiled program, so a swap costs one device_put
  and zero recompiles; old versions stay resident while any live
  sequence pins them (unpinned non-serving versions retire).
- **Per-request version pin** (``_Seq.weights_version``): set ONCE at
  first admission, carried through replay/preemption/quarantine,
  snapshot v6, and handoff doc v4 — an in-flight sequence finishes on
  the version it STARTED on, wherever it lands. Dispatches group
  ready slots by pin (one dispatch per resident version); the
  sampling keys and per-slot gathers never reference batch
  composition, so the mixed-version batch is token-identical to each
  pin's single-version oracle. The radix prefix cache is
  version-partitioned (one root per version): block bytes are a
  function of the weights, so a v0 block is never a v1 hit.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import time
from collections.abc import Callable
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..models.face import ATTN, CHUNKED, LATENT, WINDOW, ServedModel
from ..parallel import launcher
from ..parallel.mesh import MODEL_AXIS
from ..runtime.policy import QosPolicy
from ..runtime.telemetry import FLIGHT_FILENAME, STEP_SPAN
from ..runtime.tracing import PhaseTimer, SpanTracer
from ..runtime.workload import tenant_key
from ..runtime.weights import (BOOT_VERSION, architecture_diff,
                               model_fingerprint, same_architecture)
from ..runtime import wire
from .draft import draft_tokens
from .paged import (SCRATCH_BLOCK, corrupt_block as _pool_corrupt_block,
                    extract_blocks, kv_bytes_per_token, pool_bytes,
                    ring_start, scrub_blocks, walks)
from .programs import FROM_SLOT, POISON_ALL, POISON_NONE, StepPrograms
from .prefix import PrefixCache
from .spill import SpillTier
from .sampling import check_sampling, check_speculation

# the request-record event vocabulary (telemetry schema v4 ``request``
# kind; runtime/telemetry.py REQUEST_REQUIRED pins the KEY set, this
# names the transitions; "handoff" is the round-14 addition — a
# sequence leaving this engine via the single-sequence KV handoff,
# decode/fleet.py)
REQUEST_EVENTS = ("admitted", "preempted", "retried", "quarantined",
                  "completed", "rejected", "expired", "handoff")

# the single-sequence KV handoff wire format (export_sequence /
# import_sequence): one uid's written blocks + int8 scales + position +
# scheduler state, restored into a FOREIGN pool under that pool's block
# numbering. v1 (round 14, DESIGN.md section 20). v2 (round 15): the
# document carries ``t_first`` — the sequence's first-token timestamp —
# so a migrated request's completed record still reports its true
# ``ttft_s`` (schema v9, DESIGN.md section 21). v3 (round 16): the
# document is a WIRE contract, not just an in-process dict — every
# non-array value is JSON-safe (plain ints/floats/strings/lists/dicts/
# None), every array a numpy array AT THE STORAGE DTYPE — so it
# round-trips the versioned npz wire format (``runtime/wire.py``:
# per-array CRC-32, atomic publish) bit-identically across a process
# boundary; a mismatched version is rejected BEFORE any engine state is
# touched, like every other import_sequence check (DESIGN.md
# section 22). v4 (round 17): the document carries the sequence's
# ``weights_version`` pin and the fingerprint OF THAT VERSION — a
# migrated request decodes on its pinned weights even on a target
# already serving newer ones, so the importing engine must HOLD the
# pinned version (the rolling deploy's double-buffer guarantees it)
# and its fingerprint must match (DESIGN.md section 23). v5 (round
# 18): the document carries the sequence's ``trace_id`` — the causal
# identity minted once at admission (schema v12) — so a migrated
# request's records on the TARGET engine stitch into the same
# cross-process trace waterfall (DESIGN.md section 24). v6 (round
# 19): the document carries the sequence's ``tenant`` tag (schema
# v13) — a migrated request's per-tenant attribution survives the
# move, so the workload plane's noisy-tenant numbers stay honest
# through kills and deploys (DESIGN.md section 25).
# v7 (round 22): the config schema grew the spill-tier capacity keys
# (``spill_blocks`` / ``spill_restore_per_step`` / ``spill_low_water``)
# — engine-local capacity knobs, pool-size class, so two engines may
# disagree on them and still exchange sequences.
HANDOFF_VERSION = 7

# EngineConfig keys two engines may legitimately disagree on and still
# exchange sequences: pool SIZE is an engine-local capacity choice —
# device pool shape AND the host spill tier behind it (a spilled block
# restores bit-identically, so tier sizing never touches numerics).
# Every other key participates in the token-identity proof (sampling
# keys, chunk grouping — hence int8 requant history — and
# speculation paths) and must match exactly; ``prefix_partial`` is
# deliberately NOT here — at int8 a sub-block share carries the
# donor's frozen scale, so the flag is a numerics key.
_HANDOFF_POOL_KEYS = ("n_blocks", "max_slots", "max_blocks_per_seq",
                      "spill_blocks", "spill_restore_per_step",
                      "spill_low_water")

# flight recorder: bounded ring of per-step scheduler digests, dumped
# atomically on quarantine / watchdog latch / chaos kill — the "what
# was the engine doing in the steps before the fault" record a
# post-mortem needs when the process (or the pool) is already gone.
# 256 steps of digests is a few hundred KB at worst; the ring bounds a
# long-lived engine by construction. The dump filename lives in
# runtime/telemetry.py (FLIGHT_FILENAME, re-exported here) so
# report --postmortem can discover the file without importing this
# (jax-heavy) module.
FLIGHT_RECORDER_STEPS = 256

# the expert layers' counters a step's ``engine_step`` record and flight
# digest carry (``DecodeEngine._fold_expert_rows``)
EXPERT_COUNTERS = ("expert_rows", "experts_touched", "expert_rows_max")

# ... and the cache reads' (``DecodeEngine._count_rows``): the cached
# positions the step's launched rows attend over in a window layer and
# in a full one, the window blocks that left a sequence in the step
# (overwritten in its ring, behind its window, or handed back with its
# slot) and those sequences hold at its end. All 0 for a model with no
# window layer
WINDOW_COUNTERS = ("window_rows", "full_rows", "window_blocks_released",
                   "window_blocks_live")

# ... and a chunked layer's (``_count_rows`` too): the chunk summaries
# the step's launched rows attend over (those of every window before a
# row's own; a chunk's view counted once) and the rows among them that
# finished a chunk and so wrote its summary. For such a model
# ``window_rows`` counts the positions of a row's own ALIGNED window.
# Both 0 for a model with no chunked layer
CHUNK_COUNTERS = ("summary_rows", "summaries_written")


# ... and the blocks the decode-side reads of the step's launched rows
# fetched (``DecodeEngine._count_blocks``): over the rows of its
# ``decode`` / ``mixed`` / ``verify`` programs, a bucket's padded ones
# with them, times the pool's layers — the rows' LIVE blocks where the
# read walks each row's table (``paged.walks``), every table's whole
# capacity where it gathers — beside that capacity; the full kind's pool
# (``kv_*``) and the window layers' ring (``ring_*``: the blocks from
# the one that holds a row's ``paged.ring_start`` to the one it writes;
# both 0 for a model with no window layer)
KV_COUNTERS = ("kv_blocks_read", "kv_blocks_capacity",
               "ring_blocks_read", "ring_blocks_capacity")

# ... and what turns those counts into bytes: the bytes ONE cached
# position takes in ONE layer of each store, its K row and its V row as
# the arrays hold them (``window_row_bytes`` 0 for a model with no
# window layer). Constants of the engine, carried by every
# ``engine_step`` record and by the ``decode`` record so that a reader
# takes a store's width from the program and not from a family's keys
ROW_BYTES = ("kv_row_bytes", "window_row_bytes")
# ... and what ONE sequence holds in ONE recurrent layer, the state and
# the convolution's tail (``models/face.py::StateRow``; 0 and 0 for a
# model with no recurrent layer): ``state_bytes`` counts a launched
# row's two, over the recurrent layers, ONCE (read; it is written back
# the same size)
STATE_ROW_BYTES = ("state_row_bytes", "tail_row_bytes")


class AdmissionError(RuntimeError):
    """A request was shed at submit time (bounded queue full, or a
    predicted deadline miss under a QoS policy) — the serving 503,
    distinct from the ValueError family (malformed requests) so
    callers can tell load shedding from bad input. ``reason`` names
    the shed cause (``queue_full`` / ``predicted_deadline_miss``) so
    the fleet router's shed records attribute it instead of guessing."""

    def __init__(self, msg: str, reason: str = "queue_full"):
        super().__init__(msg)
        self.reason = reason


def blocks_needed(prompt_len: int, max_new: int, block_size: int) -> int:
    """Full block reservation for one request: the final generated
    token is returned, never cached, so ``prompt_len + max_new - 1``
    positions round up to blocks. THE one definition — the engine's
    admission math and the fleet transports' remote capacity probes
    (``decode/worker.py``) must never disagree on this count."""
    return -(-(prompt_len + max_new - 1) // block_size)


def _buckets(limit: int) -> tuple[int, ...]:
    """Power-of-two sizes up to ``limit`` (``limit`` itself appended
    when it isn't one) — the static shape set for slots and chunks."""
    out = []
    b = 1
    while b < limit:
        out.append(b)
        b *= 2
    out.append(limit)
    return tuple(out)


def _bucket_for(n: int, buckets: tuple[int, ...]) -> int:
    for b in buckets:
        if b >= n:
            return b
    return buckets[-1]


@dataclass(frozen=True)
class EngineConfig:
    """Static decode-engine configuration (one compiled program set per
    config). ``block_size`` must be a power of two so power-of-two
    prefill chunks never straddle a block boundary (``paged.write_chunk``).
    ``n_blocks`` includes the reserved scratch block. ``temperature=0``
    is greedy; ``top_k=0`` / ``top_p=0`` disable those truncations.

    ``speculate`` is the per-step draft budget (0 = off): each decode
    dispatch becomes a ``speculate+1``-token verify program emitting
    the accepted greedy prefix (requires ``temperature == 0``;
    ``decode/draft.py``). ``prefix_cache`` enables the
    shared-prefix radix cache (``decode/prefix.py``) — host-side only,
    so the flag never changes a compiled program; it lives in the
    config because snapshot-resume must restore onto the same sharing
    policy.

    The KV memory hierarchy (round 22, DESIGN.md section 29):
    ``spill_blocks`` sizes the host-RAM spill tier in blocks
    (``decode/spill.py``; 0 = off, requires the prefix cache) —
    pool-pressure demotion moves refs-0 cached blocks there instead of
    discarding them, and a radix hit on a spilled edge restores the
    bytes through the implant program instead of re-prefilling.
    ``spill_restore_per_step`` budgets restores per engine step (the
    chunked-prefill stance: promotion must never stall running
    decodes — an over-budget admission keeps its partial restores and
    finishes next step). ``spill_low_water`` demotes proactively
    whenever the free list dips below it (0 = demand-only).
    ``prefix_partial`` enables SUB-BLOCK sharing: a partial-block
    radix hit row-copies the shared prefix rows into a private block
    (``paged.copy_block_rows``) and prefills past them. Exact at
    f32/bf16 (rows are per-row pure); at int8 the borrowed rows carry
    the donor's FROZEN per-block scale — deterministic, but not
    bit-equal to an unshared run — which is why the flag is off by
    default and a numerics key for handoff."""
    block_size: int = 16
    n_blocks: int = 65
    max_slots: int = 4
    max_blocks_per_seq: int = 8
    prefill_chunk: int = 16
    kv_dtype: str = "f32"
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0
    seed: int = 0
    use_rope: bool = False
    speculate: int = 0
    prefix_cache: bool = True
    spill_blocks: int = 0
    spill_restore_per_step: int = 2
    spill_low_water: int = 0
    prefix_partial: bool = False

    @property
    def capacity(self) -> int:
        """Max cached positions per sequence."""
        return self.max_blocks_per_seq * self.block_size


@dataclass(frozen=True)
class ServePolicy:
    """Host-side scheduling/reliability knobs — unlike ``EngineConfig``
    these never touch a compiled program, so any policy mix shares the
    same program set. All zeros (the default) reproduce the round-9
    engine exactly.

    - ``queue_limit``: bounded waiting queue; a submit past it raises
      ``AdmissionError`` (reject-on-full, the serving 503). 0 = off.
    - ``deadline_steps``: per-request TTL in engine steps from submit;
      an unfinished request past it is failed with reason
      ``deadline`` (waiting OR running — queue time counts). 0 = off.
    - ``max_retries``: per-request budget for re-queuing a QUARANTINED
      request (replayed from its prompt + already-emitted tokens);
      budget exhausted -> reported FAILED. 0 = fail on first fault.
    - ``preempt_after_steps``: pool-pressure preemption — when the
      head-of-line request has a free slot but not its block
      reservation for this many consecutive steps, the YOUNGEST running
      sequence is evicted back to WAITING (resumed later, token-
      identically, via replay). Two guards bound the churn: the wait
      threshold is hysteresis (each eviction is preceded by that many
      steps of decode), and the LAST running sequence is never evicted
      — so the oldest resident always makes live progress and every
      request eventually completes. 0 = off (strict reserve-on-admit
      FCFS)."""

    queue_limit: int = 0
    deadline_steps: int = 0
    max_retries: int = 0
    preempt_after_steps: int = 0

    def __post_init__(self):
        for name in ("queue_limit", "deadline_steps", "max_retries",
                     "preempt_after_steps"):
            if getattr(self, name) < 0:
                raise ValueError(f"{name} must be >= 0, got "
                                 f"{getattr(self, name)}")


@dataclasses.dataclass
class _Seq:
    """Host-side per-sequence record (the scheduler's unit of state).

    ``emitted`` counts the ``out`` tokens already fed through the decode
    path since the last (re)admission. ``emitted < len(out)`` is the
    REPLAY state (after a retry / preemption / snapshot-resume): the
    prompt re-prefills, then each recorded token is teacher-forced
    through the decode step — the picks are discarded but the KV write
    history is bit-identical to the uninterrupted run's, which is what
    makes resume token-identical at every kv_dtype (int8 included: the
    quantization history is the write history)."""
    uid: int
    prompt: list[int]
    max_new: int
    out: list[int] = field(default_factory=list)
    prefilled: int = 0
    blocks: list[int] = field(default_factory=list)
    # the window layers' blocks (a model with none holds none): the
    # slot's ring, block ``j`` of the sequence in entry ``j mod len``
    wblocks: list[int] = field(default_factory=list)
    # nodes[i] is the PrefixNode backing blocks[i] when that leading
    # block is shared through the radix cache (a prefix-hit at
    # admission, or this sequence's own full prompt block transferred
    # into the tree at prefill completion); None = private. The shared
    # region is always a leading run of fully-prefilled prompt blocks,
    # which is why no write ever aims at it (writes land at or past
    # the prefill frontier).
    nodes: list = field(default_factory=list)
    emitted: int = 0
    # the ``out`` tokens whose producing row has been LAUNCHED since the
    # last (re)admission: ``emitted``, or one more while that row's
    # result is still unread (``DecodeEngine._collect``). What a launch
    # needs of a sequence is a function of this count; the token values
    # follow when they land
    launched: int = 0
    retries: int = 0
    submit_step: int = 0
    admit_index: int = -1
    t_submit: float = field(default_factory=time.time)
    # the weights-version pin (round 17): None until the sequence
    # STARTS (first admission pins the engine's serving version); a
    # pinned sequence finishes on that version through every replay,
    # preemption, migration, and crash-resume — the hot-swap identity
    # contract (DESIGN.md section 23)
    weights_version: int | None = None
    # the causal identity (round 18, schema v12): minted ONCE at
    # submit (by the fleet router, or by the engine itself when no
    # router fronts it) and carried verbatim through replay,
    # preemption, quarantine, migration (handoff doc v5), crash-resume
    # (snapshot v7), and version pins — the stitch key every
    # request/span/router record for this sequence pins
    trace_id: str | None = None
    # the tenant tag (round 19, schema v13): set at submit (None
    # single-tenant) and carried exactly like trace_id — through
    # replay, preemption, migration (handoff doc v6), and crash-resume
    # (snapshot v8) — the per-tenant accounting key the workload
    # plane's report slices pin
    tenant: str | None = None

    @property
    def prompt_done(self) -> bool:
        return self.prefilled >= len(self.prompt)

    @property
    def replaying(self) -> bool:
        return self.emitted < len(self.out)

    @property
    def finished(self) -> bool:
        return len(self.out) >= self.max_new and not self.replaying

    @property
    def all_launched(self) -> bool:
        """The row that produces the last token has been launched: no
        further row is (``finished`` follows when it lands; ``out``
        never outgrows ``max_new``, so a replay ends here too)."""
        return self.launched >= self.max_new

    @property
    def next_token(self) -> int:
        """The input of the sequence's next decode row: the recorded or
        landed token, or ``FROM_SLOT`` while the row that produces it is
        still in flight (its pick is in the slot's entry of the device's
        token store then)."""
        n = self.launched
        return self.out[n - 1] if n <= len(self.out) else FROM_SLOT


@dataclasses.dataclass
class _Launch:
    """One launched step program whose result the host has not read
    yet (``DecodeEngine._launch`` / ``_collect``)."""
    ordinal: int            # which of the engine's launches it is
    phase: str              # the phase prefix: prefill / decode / mixed
    kind: str               # the program's kind (``dispatches`` has it)
    result: jax.Array       # the packed result, still on the device
    land: Callable          # picks -> the rows' finite flags
    # the flight digest of the step that launched it, once that step
    # has closed: where its rows' finite flags go when they land later
    digest: dict | None = None


class DecodeEngine:
    """The serving loop. ``submit()`` queues prompts; ``step()`` runs one
    scheduler iteration (admit -> at most one prefill chunk -> one decode
    dispatch over every ready slot, then the read of the LAST step's
    result); ``collect()`` reads what a step left unread; ``run()``
    drains everything and returns ``{uid: full token list}``. See the
    module docstring for the design; DESIGN.md section 15 for the state
    machine."""

    def __init__(self, params: ServedModel, n_heads: int,
                 config: EngineConfig | None = None, mesh=None,
                 policy: ServePolicy | None = None, metrics=None,
                 qos: QosPolicy | None = None):
        cfg = config or EngineConfig()
        if cfg.block_size & (cfg.block_size - 1):
            raise ValueError(f"block_size must be a power of two, got "
                             f"{cfg.block_size}")
        if cfg.max_slots < 1 or cfg.max_blocks_per_seq < 1:
            raise ValueError("max_slots and max_blocks_per_seq must be "
                             ">= 1")
        if cfg.prefill_chunk < 1 or (cfg.prefill_chunk
                                     & (cfg.prefill_chunk - 1)):
            raise ValueError(
                f"prefill_chunk must be a power of two >= 1, got "
                f"{cfg.prefill_chunk} (power-of-two chunks are what "
                "keeps a chunk inside one block — paged.write_chunk)")
        if cfg.spill_blocks < 0:
            raise ValueError(f"spill_blocks must be >= 0, got "
                             f"{cfg.spill_blocks}")
        if cfg.spill_restore_per_step < 1:
            raise ValueError(
                f"spill_restore_per_step must be >= 1, got "
                f"{cfg.spill_restore_per_step} (a zero budget would "
                "starve every admission whose prefix spilled)")
        if cfg.spill_low_water < 0:
            raise ValueError(f"spill_low_water must be >= 0, got "
                             f"{cfg.spill_low_water}")
        if (cfg.spill_blocks > 0 or cfg.prefix_partial) \
                and not cfg.prefix_cache:
            raise ValueError(
                "spill_blocks / prefix_partial extend the radix prefix "
                "cache; they require prefix_cache=True")
        check_sampling(cfg.temperature, cfg.top_k, cfg.top_p, params.vocab)
        check_speculation(cfg.speculate, cfg.temperature)
        # the kinds of layer that carry a recurrent state beside the KV
        # blocks (none for a model whose layers are all attention). What
        # cannot carry that state yet refuses, here and at the entry of
        # every later call, by what the model is: no flag turns it off
        kinds = {kind for kind, _ in params.layers}
        self.recurrent = sorted(kinds - {ATTN, LATENT, WINDOW, CHUNKED})
        # chunked layers keep their exact keys in the window kind's
        # ring: whatever a window layer's second table refuses, they do
        self.chunked = CHUNKED in kinds
        self.windowed = WINDOW in kinds or self.chunked
        # ... as a refusal names them
        self._ringed = "/".join(sorted(kinds & {WINDOW, CHUNKED}))
        if self.windowed and cfg.kv_dtype == "int8":
            raise ValueError(
                "kv_dtype int8 is not served for a model with "
                f"{self._ringed} layers: a block's scale is its "
                "write history's, and a window block is overwritten as "
                "a ring")
        if self.chunked:
            self._check_chunked(params.cache_spec(n_heads), cfg, kinds)
        if mesh is not None:
            self._refuse_kept_beside("a model-axis mesh (--tp)")
            if LATENT in kinds:
                raise ValueError(
                    "a model-axis mesh (--tp) is not served for a model "
                    "with latent-cache layers: the pool is sharded by "
                    "KV heads, and a latent row has none")
        if cfg.speculate:
            self._refuse_kept_beside("speculate > 0 (a rejected draft "
                                     "would have to be undone there)")
        if cfg.spill_blocks or cfg.prefix_partial:
            self._refuse_kept_beside("spill_blocks / prefix_partial (they "
                                     "extend the prefix cache, which is "
                                     "off)")
        self.params = params
        self.n_heads = n_heads
        self.cfg = cfg
        self.mesh = mesh
        # what the model keeps per sequence, as sizes (no weight is read)
        self.spec = params.cache_spec(n_heads)
        if mesh is not None:
            from ..parallel.lm import tp_shard_params
            self.params = tp_shard_params(params, mesh, n_heads)
        # the builder of step programs; ``_program`` keeps the built ones
        self.programs = StepPrograms(cfg, self.spec, params.vocab, mesh)
        # -- live weight hot-swap (round 17, DESIGN.md section 23) --
        # double-buffered weights: version id -> params. The BOOT
        # weights are version 0; a deploy loads a checkpoint step as a
        # new version (``load_weights``) while the old one stays
        # resident, so in-flight sequences finish on the version they
        # started on (their ``_Seq.weights_version`` pin) while new
        # admissions take ``serving_version``. Every compiled program
        # takes params as a traced operand, so a swap never recompiles.
        self.weights: dict[int, ServedModel] = {BOOT_VERSION: self.params}
        self.serving_version = BOOT_VERSION
        # the architecture anchor for load_weights: held VERSIONS come
        # and go (retirement), but the engine's shape never does — a
        # check against weights[BOOT_VERSION] would break the third
        # deploy, once retirement has dropped the boot buffers
        self._arch_fingerprint = model_fingerprint(self.params,
                                                   n_heads)
        # uid -> pin (None until first admission) — the request-record
        # attribution (telemetry v11: every request record carries
        # ``weights_version``); kept like prompt_lens, per uid
        self._pins: dict[int, int | None] = {}
        # -- fleet trace spine (round 18, DESIGN.md section 24) --
        # uid -> trace_id: the causal identity every request/span
        # record for the uid pins (schema v12). The engine mints one
        # at submit when the caller (a fleet router) didn't — the
        # nonce makes ids unique across engines/processes, the uid
        # suffix makes them unique within a run. Host metadata only:
        # no compiled program ever sees a trace id (the zero-new-
        # compiles overhead contract).
        self._trace_nonce = os.urandom(4).hex()
        self._traces: dict[int, str] = {}
        # uid -> tenant tag (round 19, schema v13): the per-tenant
        # attribution key every request/span record for the uid pins
        # (None single-tenant) — host metadata only, like _traces
        self._tenants: dict[int, str | None] = {}
        # the pool, and the recurrent layers' state by slot beside it
        # (None for a model that has none): donated into the step
        # programs together and updated in place
        self.pool, self.state = self.programs.init_cache()
        # whether the decode-side read walks each row's live blocks or
        # gathers every table whole: the pool's own dtype and shape say
        # (a shard's, under a mesh); what ``_count_blocks`` counts by
        self._walks = walks(
            self.pool, shards=1 if mesh is None else mesh.shape[MODEL_AXIS])
        # ... and the window layers' pool (None for a model with none):
        # a second block pool, its own scratch block, table and free
        # list; its read walks or gathers by the same rule
        self.wpool = self.programs.init_window()
        self._ring_walks = self.wpool is not None and walks(self.wpool)
        self.row_bytes = dict(zip(ROW_BYTES, (
            0 if store is None else
            (store.k.shape[3] + store.v.shape[3]) * store.k.dtype.itemsize
            for store in (self.pool, self.wpool))))
        row = self.spec.state_row
        self.state_row_bytes = dict(zip(STATE_ROW_BYTES, (
            (0, 0) if row is None else (row.state_bytes, row.tail_bytes))))
        # each slot's next token, on the device beside them (and one
        # scratch row): a row's pick is handed to the slot's next row
        # there, so a step can be launched before the last one is read
        self.token_store = self.programs.init_tokens()
        # the ONE launched step program whose result is still unread
        # (``_launch`` / ``_collect``), and how many were launched
        self._inflight: _Launch | None = None
        self.launches = 0
        s, mb = cfg.max_slots, cfg.max_blocks_per_seq
        # cached positions one sequence may hold: a table's rows, each
        # of which a chunked layer's summaries make a whole chunk
        self.capacity = cfg.capacity * (cfg.block_size if self.chunked
                                        else 1)
        self.tables = np.full((s, mb), SCRATCH_BLOCK, np.int32)
        # cached positions by slot, counted as rows are LAUNCHED
        self.lengths = np.zeros((s,), np.int32)
        self.uids = np.zeros((s,), np.int32)
        self.slots: list[_Seq | None] = [None] * s
        self.waiting: collections.deque[_Seq] = collections.deque()
        self.finished: dict[int, list[int]] = {}
        self.failed: dict[int, dict] = {}     # uid -> {reason, retries}
        self.prompt_lens: dict[int, int] = {}  # uid -> len(prompt)
        self.free_blocks = list(range(1, cfg.n_blocks))
        # the window kind's: a slot's short table, used as a ring
        wt = self.programs.window_blocks
        self.wtables = np.full((s, wt), SCRATCH_BLOCK, np.int32)
        self.free_wblocks = list(range(1, 1 + s * wt))
        self.slot_buckets = _buckets(cfg.max_slots)
        self.chunk_buckets = _buckets(cfg.prefill_chunk)
        self._programs: dict = {}
        self.compile_count = 0       # program builds (recompile guard)
        self.dispatch_count = 0
        self.steps = 0
        self.step_base = 0        # snapshot-resume offset (global step)
        self.tokens_generated = 0
        self._occ_sum = 0.0
        self._next_uid = 0
        self.policy = policy or ServePolicy()
        # -- tenant QoS (round 20, DESIGN.md section 26) --
        # None = the historical strict-FCFS engine exactly. All QoS
        # state is host-side scheduling metadata (like _head_blocked):
        # it never enters a compiled program or a sampling key, so a
        # policy change reorders ADMISSIONS, never a request's tokens.
        self.qos = qos
        # tenant_key -> tokens served (live + replayed emissions): the
        # WFQ virtual clock's numerator. Deterministic by construction
        # (token counts, never wall time), so the admission order a
        # policy produces replays identically with the tokens.
        self._tenant_served: dict[str, int] = {}
        # uids whose budget deferral was already recorded (one qos
        # record per uid per wait, not one per scheduler iteration)
        self._budget_deferred: set[int] = set()
        self.metrics = metrics           # TelemetryWriter (or None)
        # host-side audit ring (the durable trail is the telemetry
        # stream; this is for in-process inspection, bounded so a
        # long-lived engine can't grow it without limit)
        self.request_events: collections.deque[dict] = \
            collections.deque(maxlen=4096)
        self._corrupted: set[int] = set()   # chaos-poisoned block ids
        self.quarantined = 0
        self.retried = 0
        self.preempted = 0
        self.rejected = 0
        self.expired = 0
        self._admit_counter = 0     # admission order (preempt youngest)
        self._head_blocked = 0      # head-of-line pool-starved streak
        self._head_blocked_uid: int | None = None  # whose streak it is
        self._poison_uid = POISON_NONE   # armed for the NEXT step only
        # -- serving observability (round 11, DESIGN.md section 17) --
        # per-request lifecycle spans; the writer is looked up lazily
        # because run(metrics=...) re-binds it after construction
        # (trace_fn: every span record pins the uid's trace_id)
        self.tracer = SpanTracer(lambda: self.metrics,
                                 trace_fn=self._traces.get,
                                 tenant_fn=self._tenants.get)
        # KV-pool churn (cumulative; snapshot-persisted so they stay
        # monotonic across crash-resume) + free-block watermark window
        # (min/max since the last decode record)
        self.block_allocs = 0
        self.block_frees = 0
        self.block_scrubs = 0
        # speculative-decoding counters (cumulative; snapshot-persisted
        # like the churn trio): drafted = tokens proposed to verify
        # steps, accepted = drafted tokens the greedy verify kept (the
        # per-step bonus token is counted in tokens_generated, not here
        # — accept_rate = accepted / drafted is the drafter's score)
        self.drafted_tokens = 0
        self.accepted_tokens = 0
        # -- shared-prefix KV reuse (round 13, DESIGN.md section 19) --
        # the radix tree over full prompt blocks; None = sharing off
        # (every block private, the round-9..12 engine exactly)
        # -- KV memory hierarchy (round 22, DESIGN.md section 29) --
        # the host-RAM spill tier behind the device pool; None = the
        # round-13 single-tier cache exactly (demotion discards)
        self.spill = (SpillTier(cfg.spill_blocks)
                      if cfg.prefix_cache and cfg.spill_blocks > 0
                      else None)
        # a KV block hit is worth nothing without the recurrent state at
        # that boundary (ROADMAP M4), nor without the window layers'
        # blocks up to it (a hit is valid by layer kind: M3), so such a
        # model takes no hits and inserts no blocks: no cache object
        self.prefix = (PrefixCache(cfg.block_size, spill=self.spill)
                       if cfg.prefix_cache and not self.recurrent
                       and not self.windowed else None)
        # cumulative, snapshot-persisted (monotonic across crash-resume
        # like the churn trio): hit blocks mapped at admission, prompt
        # tokens those hits skipped, copy-on-write triggers (0 in
        # steady state — the write-barrier invariant), and candidate
        # full blocks walked (the hit-rate denominator)
        self.prefix_hit_blocks = 0
        self.prefill_tokens_saved = 0
        self.cow_copies = 0
        self.prefix_lookup_blocks = 0
        # spill-tier counters (schema v17, cumulative and snapshot-
        # persisted like the churn trio — the TIER dies with the
        # process, these survive it): blocks demoted to host RAM,
        # wire bytes they serialized to, blocks promoted back through
        # the implant program, prompt tokens those promotions kept off
        # the prefill path, host wall-clock the promotions cost (the
        # stall budget's measured term), and sub-block partial hits
        self.spilled_blocks = 0
        self.spill_bytes = 0
        self.restores = 0
        self.restore_tokens_saved = 0
        self.restore_stall_s = 0.0
        self.partial_hits = 0
        # per-step promotion budget state (reset in step())
        self._restores_left = cfg.spill_restore_per_step
        self._step_restores = 0
        # prefill program dispatches (the shared-prefix win is provable
        # as a dispatch count: N sharers run ~1 prefill pass over the
        # shared prefix, not N); snapshot-persisted
        self.prefill_dispatches = 0
        # ... of which the chunk rode with the step's decode batch in
        # ONE ``mixed`` program (``_mixed_dispatch``)
        self.mixed_dispatches = 0
        # the blocks the decode-side reads fetched of the pool and of
        # the rings, and the capacity a gather of every row's table
        # reads (``KV_COUNTERS``, cumulative; the step's own are
        # ``_step_kv``)
        self.kv_blocks_read = self.kv_blocks_capacity = 0
        self.ring_blocks_read = self.ring_blocks_capacity = 0
        # tokens emitted inside the CURRENT span per uid (decode/replay
        # segments emit many tokens per step under speculation; the
        # span record carries the count so a waterfall shows work, not
        # just wall clock)
        self._span_tokens: dict[int, int] = {}
        free0 = len(self.free_blocks)
        self._free_lo = self._free_hi = free0
        # flight recorder: per-step digests + the current step's
        # request events / dispatch evidence feeding the next digest
        self.flight: collections.deque[dict] = collections.deque(
            maxlen=FLIGHT_RECORDER_STEPS)
        self.flight_dir: str | None = None  # default: the metrics dir
        self._step_events: list[str] = []
        self._step_finite: list[bool] | None = None
        self._step_prefill_uid: int | None = None
        self._step_decode_uids: list[int] = []
        # recurrent-state bytes this step's decode dispatches read (one
        # row a ready slot; written back the same size): the engine_step
        # record's and the digest's ``state_bytes``
        self._step_state_bytes = 0
        # the cache reads of the rows this step launched and the window
        # blocks' turnover (``WINDOW_COUNTERS``, ``_count_rows``)
        self._step_window = dict.fromkeys(
            WINDOW_COUNTERS + CHUNK_COUNTERS, 0)
        self._step_kv = dict.fromkeys(KV_COUNTERS, 0)
        # the step programs this step launched, ``[kind, bucket]`` in
        # launch order (``_launch``): the engine_step record's and the
        # digest's ``dispatches``
        self._step_dispatches: list[list] = []
        # the launches whose results this step read, by ordinal, the
        # i-th the i-th ``*.readback`` phase's: the record's and the
        # digest's ``readbacks``
        self._step_readbacks: list[int] = []
        # the expert layers' counters of this step's dispatches, as the
        # step programs returned them (``[expert_layers, n_experts]``
        # each; none for a model with no expert layer), folded in the
        # step's digest phase into ``_step_experts``
        self._step_expert_rows: list[np.ndarray] = []
        self._step_experts = dict.fromkeys(EXPERT_COUNTERS, 0)
        self._dump_reason: str | None = None
        # host phases of the current step (runtime/tracing.py): always
        # stamped, summed into the digest's ``phase_ms``; with a writer
        # attached one ``engine_step`` span record a step carries them
        # whole
        self.phases = PhaseTimer("engine")

    # -- pool ----------------------------------------------------------

    @staticmethod
    def _check_chunked(spec, cfg, kinds) -> None:
        """What a chunked layer's two stores hold the engine to, each
        refused by name: a pool block IS a chunk, a window is whole
        blocks, a prefill chunk lies inside one block (it finishes at
        most one chunk), and every layer is of the kind (a layer owns
        the same index in both pools)."""
        blk = cfg.block_size
        if kinds != {CHUNKED}:
            raise ValueError(
                "a model with chunked layers is served with every layer "
                f"chunked only, got {sorted(kinds)}: a chunked layer "
                "owns the same index in both pools")
        if spec.chunk != blk or spec.window % blk:
            raise ValueError(
                f"chunked layers summarise chunks of {spec.chunk} "
                f"positions in windows of {spec.window}: served with "
                f"block_size == the chunk only, got {blk} (a pool block "
                "is a chunk, its summary one row)")
        if blk % cfg.prefill_chunk:
            raise ValueError(
                f"prefill_chunk {cfg.prefill_chunk} does not divide the "
                f"chunked layers' chunk of {blk} positions: a prefill "
                "chunk would straddle a chunk's boundary and finish "
                "more than one summary")

    def _refuse_kept_beside(self, what: str) -> None:
        """The one line every path that moves a sequence by its ONE
        block table refuses with, for a model that keeps more of a
        sequence beside it: a recurrent state, or window (or chunked)
        layers' blocks in a table of their own."""
        if self.recurrent:
            raise ValueError(
                f"{what} is not served for a model with "
                f"{'/'.join(self.recurrent)} layers: it cannot carry "
                "their recurrent state yet")
        if self.windowed:
            raise ValueError(
                f"{what} is not served for a model with {self._ringed} "
                "layers: "
                "it moves a sequence by one block table, and theirs is "
                "a second one")

    def _cache(self):
        """What a model's forward reads and writes of a sequence: the
        pool, and for a model that keeps more the tuple of it all
        (``StepPrograms.whole``: the window layers' pool, the recurrent
        state)."""
        return self.programs.whole(self.pool, self.wpool, self.state)

    def _carry(self):
        """The donated operand of every step program: ``_cache()`` and
        the slots' token store."""
        return self._cache(), self.token_store

    def _keep(self, carry) -> None:
        """Take back what a step program returned in ``_carry()``'s
        place."""
        cache, self.token_store = carry
        self.pool, self.wpool, self.state = self.programs.parts(cache)

    # -- compiled programs (one per (kind, bucket); bounded) -----------

    def _program(self, kind: str, bucket: int):
        """The engine's cache of built programs. ``kind``: decode /
        prefill / verify / mixed (bucketed), cow / cow_rows / implant (one
        each, built on first use: steady state never builds them, so
        the recompile-guard tests hold with the write barrier armed)."""
        key = (kind, bucket)
        fn = self._programs.get(key)
        if fn is None:
            self.compile_count += 1
            fn = self._programs[key] = self.programs.build(kind, bucket)
        self.dispatch_count += 1
        return fn

    def warm(self) -> int:
        """Prebuild the engine's full program set — every decode (and
        verify, when speculating) slot bucket, the one mixed program
        (when not), every prefill chunk bucket, and the implant program
        — so a freshly
        spawned engine pays its compiles BEFORE it takes traffic (the
        autoscaler's warm-before-traffic contract; also the worker
        protocol's ``warm`` op). Idempotent; returns
        ``compile_count``."""
        for b in self.slot_buckets:
            self._program("decode", b)
            if self.cfg.speculate:
                self._program("verify", b)
        if not self.cfg.speculate:
            self._program("mixed", self.slot_buckets[-1])
        for c in self.chunk_buckets:
            self._program("prefill", c)
        self._program("implant", 0)
        return self.compile_count

    # -- model identity (snapshots + KV handoff pin it) ----------------

    def model_meta(self, version: int | None = None) -> dict:
        """Model identity the snapshot AND the KV handoff pin: resume
        replays recorded tokens through the pinned version's weights,
        and an imported sequence's KV was written by the SOURCE's
        weights for that version — either under different weights
        silently breaks the token-identical contract. THE fingerprint
        definition lives in ``runtime/weights.py``
        (``model_fingerprint`` — shapes + the coarse embedding-row
        sum); this is a re-binding per held version. Default: the
        current serving version."""
        ver = self.serving_version if version is None else int(version)
        return model_fingerprint(self._params_for(ver), self.n_heads)

    # -- live weight hot-swap (round 17, DESIGN.md section 23) ---------

    def _params_for(self, version: int) -> ServedModel:
        try:
            return self.weights[int(version)]
        except KeyError:
            raise RuntimeError(
                f"engine does not hold weights version {version} "
                f"(held: {sorted(self.weights)}) — a pinned sequence "
                "can only run where its version is resident") from None

    def pinned_versions(self) -> set[int]:
        """Versions some live (resident or waiting) sequence is pinned
        to — what ``load_weights``'s double-buffer retirement must
        keep."""
        pins = {s.weights_version for s in self.slots
                if s is not None and s.weights_version is not None}
        pins |= {s.weights_version for s in self.waiting
                 if s.weights_version is not None}
        return pins

    def load_weights(self, version: int, params: ServedModel) -> dict:
        """Install ``params`` as weights version ``version`` —
        double-buffered: the previous versions stay resident while any
        live sequence pins them (an in-flight request must finish on
        its version), and unpinned non-serving versions retire to keep
        the buffer at ~2. The params arrive as device arrays (the
        ledger's restore already performed the one fresh-ownership
        device_put) and every compiled program takes them as a traced
        operand, so this call costs zero recompiles. Architecture must
        match the boot weights exactly — the pool layout and program
        set are shape functions. Idempotent for an already-held
        version with the same fingerprint."""
        if self.mesh is not None:
            raise ValueError(
                "load_weights is single-device (the fleet's rolling "
                "deploy runs single-device replicas; TP engines "
                "redeploy by restart)")
        version = int(version)
        new_fp = model_fingerprint(params, self.n_heads)
        if version in self.weights:
            held = self.model_meta(version)
            if held != new_fp:
                raise ValueError(
                    f"weights version {version} already held with a "
                    f"different fingerprint ({held} != {new_fp}) — "
                    "version ids are immutable once loaded")
            return new_fp
        if not same_architecture(self._arch_fingerprint, new_fp):
            raise ValueError(
                "weights architecture != engine architecture: "
                f"{architecture_diff(self._arch_fingerprint, new_fp)} "
                "— hot-swap requires the identical model shape (the "
                "KV pool and compiled programs are shape functions)")
        # double-buffer retirement: non-serving versions no live
        # sequence pins free their buffers now (their refs-0 cached
        # prefix blocks decay through the ordinary LRU)
        keep = self.pinned_versions() | {self.serving_version, version}
        for old in [v for v in self.weights if v not in keep]:
            if self.weights[old] is self.params:
                # the construction-time alias (static shape/vocab
                # reads, the ledger-restore template, the static cost
                # report) would otherwise pin the retired buffers for
                # the process lifetime — rebind it to the incoming
                # version; every such read is architecture-only, so
                # any held version serves it identically
                self.params = params
            del self.weights[old]
        self.weights[version] = params
        return new_fp

    def set_serving_version(self, version: int) -> None:
        """New admissions pin ``version`` from now on; sequences
        already pinned elsewhere are untouched (they keep decoding on
        their own resident version — the mixed-version engine the
        version-grouped dispatch below serves)."""
        version = int(version)
        if version not in self.weights:
            raise ValueError(
                f"cannot serve weights version {version}: not loaded "
                f"(held: {sorted(self.weights)}) — load_weights first")
        self.serving_version = version

    # -- single-sequence KV handoff (DESIGN.md section 20) -------------

    def export_sequence(self, uid: int, keep: bool = False) -> dict:
        """Export one RESIDENT fully-prefilled sequence as a handoff
        document: scheduler state (prompt, emitted tokens, position,
        pending next token) plus the WRITTEN blocks' bytes and int8
        scales at the storage dtype — everything a foreign engine needs
        to continue the sequence token-identically without replay. The
        sequence leaves this engine on the way out: shared prefix
        blocks DECREF (an innocent sharer's prefix is untouched — the
        quarantine stance, without the distrust), private blocks return
        to the free list clean. Generalizes the PR 5 snapshot from
        whole-engine metadata to one sequence WITH its KV content.

        ``keep=True`` is the SHIP half of an async live migration
        (round 22): the document is built at the current position but
        the sequence STAYS resident and keeps decoding while the
        snapshot ships — ``finish_export`` later evicts it and returns
        the delta tokens emitted during the ship window, which the
        target teacher-forces after importing the shipped document
        (the replay contract: forced tokens rebuild KV bit-identically,
        so the splice of shipped blocks + caught-up delta is the same
        KV the sync path would have shipped). No handoff event is
        emitted and no span closes until the commit — the sequence has
        not left yet."""
        self._refuse_kept_beside("export_sequence (the KV handoff)")
        if self.mesh is not None:
            raise ValueError(
                "KV handoff is single-device (the fleet runs "
                "single-device replicas; TP engines keep the "
                "whole-engine snapshot path)")
        self._collect()     # the document holds token VALUES
        slot = next((i for i, s in enumerate(self.slots)
                     if s is not None and s.uid == uid), None)
        if slot is None:
            raise ValueError(f"uid {uid} is not resident on this engine "
                             "(waiting/finished requests migrate by "
                             "replay, not handoff)")
        seq = self.slots[slot]
        if not seq.prompt_done:
            raise ValueError(
                f"uid {uid} is mid-prefill ({seq.prefilled}/"
                f"{len(seq.prompt)} tokens): handoff exports fully-"
                "prefilled sequences; an unprefilled request migrates "
                "by replay")
        pos = int(self.lengths[slot])
        nb_written = -(-pos // self.cfg.block_size)
        phys = [int(b) for b in seq.blocks[:nb_written]]
        bad = [b for b in phys if b in self._corrupted]
        if bad:
            raise ValueError(
                f"uid {uid} holds chaos-corrupted block(s) {bad}: a "
                "poisoned sequence must quarantine, not migrate the "
                "poison to an innocent engine")
        doc = {
            "handoff_version": HANDOFF_VERSION,
            # the pin travels (v4): the sequence's KV was written by
            # THIS version's weights, and the target must finish it
            # there — the fingerprint is the pinned version's
            "weights_version": int(seq.weights_version),
            "model": self.model_meta(seq.weights_version),
            "config": dataclasses.asdict(self.cfg),
            "uid": int(seq.uid),
            # the causal identity travels (v5): the target's records
            # stitch into the same trace waterfall
            "trace_id": seq.trace_id,
            # the tenant tag travels (v6): per-tenant attribution
            # survives the move
            "tenant": seq.tenant,
            "prompt": list(seq.prompt),
            "out": list(seq.out),
            "max_new": int(seq.max_new),
            "emitted": int(seq.emitted),
            "retries": int(seq.retries),
            "t_submit": float(seq.t_submit),
            "position": pos,
            "next_token": int(seq.next_token),
            # the first-token mark travels with the sequence (handoff
            # v2) so the importing engine's completed record reports
            # the TRUE ttft_s, not a restarted clock
            "t_first": self.tracer.pop_first_token(seq.uid),
            "blocks_written": nb_written,
            "source_blocks": phys,     # the renumbering certificate
            **extract_blocks(self.pool, phys),
        }
        if keep:
            # the ship half: the doc captured t_first by POPPING the
            # mark — restore it, the sequence is still live here and
            # may yet complete locally (an aborted migration must
            # still report the true ttft_s)
            if doc["t_first"] is not None:
                self.tracer.mark_first_token(seq.uid, doc["t_first"])
            return doc
        self._event("handoff", seq.uid, reason="exported",
                    n_out=len(seq.out), position=pos)
        self.tracer.close(seq.uid, self.global_step, reason="handoff",
                          tokens=self._span_tokens.pop(seq.uid, 0))
        self._evict(slot)
        return doc

    def finish_export(self, uid: int) -> dict:
        """Commit half of an async live migration: the snapshot from
        ``export_sequence(uid, keep=True)`` has shipped, so take the
        sequence OFF this engine now and return the delta —
        ``{"status": "resident", "out": [...], "position": P}`` with
        the FULL token list as of the commit (the shipped document's
        ``out`` is a strict prefix; the difference is what the target
        teacher-forces to catch up). If the request finished, failed,
        or was preempted back to WAITING during the ship window, the
        migration aborts instead: the terminal/requeued state is
        reported (``finished`` / ``failed`` / ``waiting`` / ``gone``)
        and NOTHING is evicted — the request never left this engine,
        and the target discards its staged copy."""
        self._collect()     # the delta is token VALUES
        slot = next((i for i, s in enumerate(self.slots)
                     if s is not None and s.uid == uid), None)
        if slot is None:
            if uid in self.finished:
                return {"status": "finished"}
            if uid in self.failed:
                return {"status": "failed"}
            if any(s.uid == uid for s in self.waiting):
                return {"status": "waiting"}
            return {"status": "gone"}
        seq = self.slots[slot]
        out = [int(t) for t in seq.out]
        pos = int(self.lengths[slot])
        self._event("handoff", seq.uid, reason="exported",
                    n_out=len(out), position=pos)
        self.tracer.close(seq.uid, self.global_step, reason="handoff",
                          tokens=self._span_tokens.pop(seq.uid, 0))
        self.tracer.pop_first_token(seq.uid)   # travels with the doc
        self._evict(slot)
        return {"status": "resident", "out": out, "position": pos}

    def import_sequence(self, doc: dict) -> int:
        """Restore an ``export_sequence`` document into THIS engine's
        pool under THIS pool's block numbering: allocate the full block
        reservation, implant the written blocks' bytes (+ int8 scales,
        bit-exactly — the content is copied at the storage dtype, never
        round-tripped through f32), install the sequence into a free
        slot at its exported position, and transfer its full prompt
        blocks into the local radix tree so the NEXT local sharer hits
        them (cross-engine prefix reuse). Decode continues on the very
        next step — no replay, no prefill dispatch. Model fingerprint
        and the numerics-relevant config keys must match the source's
        (pool-size keys may differ; that is the point of renumbering)."""
        self._refuse_kept_beside("import_sequence (the KV handoff)")
        if self.mesh is not None:
            raise ValueError(
                "KV handoff is single-device (the fleet runs "
                "single-device replicas; TP engines keep the "
                "whole-engine snapshot path)")
        if doc.get("handoff_version") != HANDOFF_VERSION:
            raise ValueError(f"handoff version "
                             f"{doc.get('handoff_version')!r} != "
                             f"{HANDOFF_VERSION}")
        ver = int(doc["weights_version"])
        if ver not in self.weights:
            raise ValueError(
                f"engine does not hold weights version {ver} (held: "
                f"{sorted(self.weights)}) — the imported sequence is "
                "pinned there and would decode on the wrong weights")
        model = self.model_meta(ver)
        if doc["model"] != model:
            diff = {k: (doc["model"].get(k), model.get(k))
                    for k in set(model) | set(doc["model"])
                    if doc["model"].get(k) != model.get(k)}
            raise ValueError(
                f"model != handoff model: {diff} — the imported KV was "
                "written by the source's weights, so the identical "
                "model (same shape AND same init) is required for the "
                "token-identical contract")
        cfg = dataclasses.asdict(self.cfg)
        diff = {k: (doc["config"].get(k), cfg[k]) for k in cfg
                if k not in _HANDOFF_POOL_KEYS
                and doc["config"].get(k) != cfg[k]}
        if diff:
            raise ValueError(
                f"engine config != handoff config: {diff} (pool-size "
                f"keys {_HANDOFF_POOL_KEYS} may differ; every numerics "
                "key must match for token identity)")
        uid = int(doc["uid"])
        prompt = [int(t) for t in doc["prompt"]]
        max_new = int(doc["max_new"])
        if uid in self.finished or uid in self.failed \
                or any(s is not None and s.uid == uid for s in self.slots) \
                or any(s.uid == uid for s in self.waiting):
            raise ValueError(f"uid {uid} already in use")
        need = self._blocks_needed(len(prompt), max_new)
        if need > self.cfg.max_blocks_per_seq:
            raise ValueError(
                f"handoff needs {need} blocks, exceeding this engine's "
                f"max_blocks_per_seq {self.cfg.max_blocks_per_seq}")
        if len(prompt) + max_new - 1 > self.params.max_seq_len:
            raise ValueError("handoff exceeds max_seq_len")
        slot = next((i for i, s in enumerate(self.slots) if s is None),
                    None)
        if slot is None:
            raise RuntimeError("no free slot for handoff import (the "
                               "router checks capacity before "
                               "dispatching a handoff)")
        if need > len(self.free_blocks) and self.prefix is not None:
            self._reclaim_cached(need - len(self.free_blocks))
        if need > len(self.free_blocks):
            raise RuntimeError(
                f"handoff needs {need} blocks, {len(self.free_blocks)} "
                "free (the router checks capacity before dispatching)")
        blocks = [self.free_blocks.pop(0) for _ in range(need)]
        nb = int(doc["blocks_written"])
        fn_args = []
        for i in range(nb):
            args = [jnp.asarray(doc["k"][:, i]),
                    jnp.asarray(doc["v"][:, i])]
            if doc["k_scale"] is not None:
                args += [jnp.asarray(doc["k_scale"][:, i]),
                         jnp.asarray(doc["v_scale"][:, i])]
            fn_args.append(args)
        for i, args in enumerate(fn_args):
            fn = self._program("implant", 0)
            self.pool = fn(self.pool, jnp.int32(blocks[i]), *args)
        seq = _Seq(uid=uid, prompt=prompt, max_new=max_new,
                   out=[int(t) for t in doc["out"]],
                   retries=int(doc["retries"]),
                   submit_step=self.global_step,
                   weights_version=ver,
                   trace_id=(doc.get("trace_id")
                             or f"{self._trace_nonce}-{uid}"),
                   tenant=doc.get("tenant"))
        self._pins[uid] = ver
        self._traces[uid] = seq.trace_id
        self._tenants[uid] = seq.tenant
        # its next row takes ``out[emitted - 1]`` from the host (the
        # document's ``next_token``): the token store never held it
        seq.emitted = seq.launched = int(doc["emitted"])
        seq.t_submit = float(doc["t_submit"])
        seq.prefilled = len(prompt)
        seq.blocks = blocks
        self.prompt_lens[uid] = len(prompt)
        row = np.full((self.cfg.max_blocks_per_seq,), SCRATCH_BLOCK,
                      np.int32)
        row[:need] = blocks
        self.tables[slot] = row
        self.lengths[slot] = int(doc["position"])
        self.uids[slot] = uid
        self.slots[slot] = seq
        seq.admit_index = self._admit_counter
        self._admit_counter += 1
        self.block_allocs += need
        self._next_uid = max(self._next_uid, uid) + 1
        self._event("admitted", uid, reason="handoff",
                    position=int(doc["position"]), replay=0)
        # the span clock restarts at import (the resume stance: the
        # in-transit gap is visibly unaccounted rather than invented —
        # report --slo attributes it to `migration` via the router's
        # handoff record), but the first-token mark RIDES the document:
        # the first token really happened then, on the source
        if doc.get("t_first") is not None:
            self.tracer.mark_first_token(uid, float(doc["t_first"]))
        self.tracer.open(uid, "replay" if seq.replaying else "decode",
                         self.global_step)
        # cross-engine prefix reuse: the imported full prompt blocks
        # enter THIS engine's radix tree (late dedup applies — a local
        # twin already cached wins and the duplicate frees)
        self._cache_full_blocks(slot, len(prompt))
        return uid

    def release_request(self, uid: int) -> dict:
        """Take one live request OFF this engine (waiting or resident,
        prefilled or not) and return its replay entry — the rolling
        deploy's drain primitive for everything the KV handoff can't
        carry (mid-prefill or still-queued requests migrate by replay;
        fully-prefilled residents go through ``export_sequence``
        instead, which ships the KV). The entry is exactly what a
        peer's ``resume_request`` takes: replay re-prefills and
        teacher-forces on the PINNED version, so the moved request's
        remaining tokens stay bit-identical to its unmoved oracle."""
        uid = int(uid)
        self._collect()     # the entry holds ``out``
        seq = None
        for i, s in enumerate(self.waiting):
            if s.uid == uid:
                seq = s
                del self.waiting[i]
                break
        if seq is None:
            slot = next((i for i, s in enumerate(self.slots)
                         if s is not None and s.uid == uid), None)
            if slot is None:
                raise ValueError(f"uid {uid} is not live on this "
                                 "engine (finished/failed requests "
                                 "have nothing to drain)")
            seq = self._evict(slot)
        self._event("handoff", uid, reason="drained",
                    n_out=len(seq.out))
        self.tracer.close(uid, self.global_step, reason="drained",
                          tokens=self._span_tokens.pop(uid, 0))
        return {"uid": uid, "prompt": list(seq.prompt),
                "out": list(seq.out), "max_new": int(seq.max_new),
                "retries": int(seq.retries),
                "t_submit": float(seq.t_submit),
                "t_first": self.tracer.pop_first_token(uid),
                "weights_version": seq.weights_version,
                "trace_id": seq.trace_id,
                "tenant": seq.tenant}

    # -- scheduler -----------------------------------------------------

    def submit(self, prompt, max_new: int, uid: int | None = None,
               trace: str | None = None,
               tenant: str | None = None) -> int:
        """Queue one request. ``prompt`` is a list of token ids; the
        capacity checks run here so an impossible request fails at
        submit time, never mid-serve. ``trace`` is the caller-minted
        trace id (the fleet router mints at fleet admission); None
        mints one here — either way the id sticks to the uid for the
        request's whole cross-engine life (schema v12). ``tenant`` is
        the request's tenant tag (schema v13; None single-tenant),
        carried exactly like the trace id."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        if any(not 0 <= t < self.params.vocab for t in prompt):
            raise ValueError("prompt token out of vocab range")
        # the final generated token is returned, never cached or embedded
        # (_blocks_needed counts the same way), so a request may exactly
        # fill its block reservation
        cached = len(prompt) + max_new - 1
        if cached > self.capacity:
            raise ValueError(
                f"prompt {len(prompt)} + max_new {max_new} needs "
                f"{cached} cached positions, exceeding the per-sequence "
                f"cache capacity {self.capacity} "
                "(max_blocks_per_seq * block_size)")
        if cached > self.params.max_seq_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new {max_new} needs "
                f"{cached} cached positions, exceeding max_seq_len "
                f"{self.params.max_seq_len}")
        if self._blocks_needed(len(prompt), max_new) > self.cfg.n_blocks - 1:
            raise ValueError("request needs more blocks than the pool "
                             f"holds ({self.cfg.n_blocks - 1} usable)")
        auto_uid = uid is None
        if auto_uid:
            uid = self._next_uid
        elif uid < 0:
            # negative uids collide with the poison operand sentinels
            # (POISON_NONE/POISON_ALL): uid -1 would match the idle
            # poison comparison and be NaN'd every step
            raise ValueError(f"uid must be >= 0, got {uid}")
        elif (uid in self.finished or uid in self.failed
              or any(s is not None and s.uid == uid for s in self.slots)
              or any(s.uid == uid for s in self.waiting)):
            # a duplicate uid would sample in lockstep with its twin
            # (the key folds the uid) and overwrite its finished entry
            raise ValueError(f"uid {uid} already in use")
        if (self.policy.queue_limit
                and len(self.waiting) >= self.policy.queue_limit):
            # reject-on-full: shed load at the door instead of growing
            # an unbounded queue every waiter times out in. An
            # auto-assigned uid is NOT consumed (_next_uid only
            # advances on acceptance) — so its rejected record carries
            # uid -1, not a number a LATER accepted request will reuse
            # (aliasing two requests in the per-uid audit trail)
            self.rejected += 1
            self._event("rejected", -1 if auto_uid else uid,
                        reason="queue_full",
                        queue_len=len(self.waiting))
            raise AdmissionError(
                f"waiting queue full ({len(self.waiting)} >= "
                f"queue_limit {self.policy.queue_limit}); request "
                f"uid {uid} shed")
        if (self.qos is not None and self.qos.predictive_shed
                and self.policy.deadline_steps > 0):
            # admission throttling by predicted deadline miss: when
            # even an OPTIMISTIC queue-position ETA (every engine step
            # serves max_slots requests' tokens in parallel, nobody
            # else's prefill costs anything) already blows the
            # deadline, admitting the request would only burn pool
            # blocks on work _expire_deadlines is certain to fail —
            # shed it at the door with the ETA on the record instead
            eta = self._eta_steps(len(prompt), max_new)
            if eta >= self.policy.deadline_steps:
                self.rejected += 1
                self._event("rejected", -1 if auto_uid else uid,
                            reason="predicted_deadline_miss",
                            eta_steps=eta,
                            deadline_steps=self.policy.deadline_steps,
                            queue_len=len(self.waiting))
                self._qos_event("predicted_miss_shed", tenant,
                                uid=-1 if auto_uid else uid,
                                eta_steps=eta,
                                deadline_steps=self.policy
                                .deadline_steps)
                raise AdmissionError(
                    f"predicted deadline miss (eta {eta} >= "
                    f"deadline_steps {self.policy.deadline_steps} "
                    f"steps); request uid {uid} shed",
                    reason="predicted_deadline_miss")
        self._next_uid = max(self._next_uid, uid) + 1
        self.prompt_lens[uid] = len(prompt)
        self._pins.setdefault(uid, None)    # pinned at first admission
        seq = _Seq(uid=uid, prompt=prompt, max_new=max_new,
                   submit_step=self.global_step,
                   trace_id=(trace if trace is not None
                             else f"{self._trace_nonce}-{uid}"),
                   tenant=tenant)
        self._traces[uid] = seq.trace_id
        self._tenants[uid] = tenant
        self.waiting.append(seq)
        # the queued span opens at t_submit — the same clock latency_s
        # measures from, so the waterfall's span sum reconciles with it
        self.tracer.open(uid, "queued", self.global_step, t=seq.t_submit)
        return uid

    def resume_request(self, uid: int, prompt, max_new: int, out=(),
                       retries: int = 0, t_submit=None,
                       submit_step=None, t_first=None,
                       weights_version=None,
                       trace: str | None = None,
                       tenant: str | None = None) -> int:
        """Re-enter a request from an engine snapshot
        (``decode/supervise.py``): queued for replay-resume — prompt
        re-prefilled, recorded ``out`` tokens teacher-forced, then live
        generation continues token-identically (the sampling keys fold
        ``(seed, uid, position)``, never the slot or the crash).
        Bypasses ``queue_limit`` (the request was admitted once — a
        crash must not shed it). ``weights_version`` carries the pin
        across the resume: a pinned request replays and finishes on
        the version it started on (the engine must hold it by
        admission time); None re-pins at admission — the request never
        started."""
        prompt = [int(t) for t in prompt]
        out = [int(t) for t in out]
        if uid < 0:
            raise ValueError(f"uid must be >= 0, got {uid}")
        if uid in self.finished or uid in self.failed \
                or any(s is not None and s.uid == uid for s in self.slots) \
                or any(s.uid == uid for s in self.waiting):
            raise ValueError(f"uid {uid} already in use")
        seq = _Seq(uid=int(uid), prompt=prompt, max_new=int(max_new),
                   out=out, retries=int(retries),
                   submit_step=(self.global_step if submit_step is None
                                else int(submit_step)),
                   weights_version=(None if weights_version is None
                                    else int(weights_version)),
                   # trace carries the causal identity across the
                   # resume (snapshot v7 / the caller's book persisted
                   # it); None mints fresh — a pre-v12 entry had none
                   trace_id=(trace if trace is not None
                             else f"{self._trace_nonce}-{int(uid)}"),
                   # the tenant rides the resume exactly like the
                   # trace id (snapshot v8 / handoff v6 persisted it)
                   tenant=tenant)
        self._pins[int(uid)] = seq.weights_version
        self._traces[int(uid)] = seq.trace_id
        self._tenants[int(uid)] = tenant
        if t_submit is not None:
            seq.t_submit = float(t_submit)
        if t_first is not None:
            # the snapshot persisted the first-token mark (v5): the
            # first token really happened then, so the resumed
            # request's completed record keeps its true ttft_s (the
            # crash GAP still shows as unaccounted span time)
            self.tracer.mark_first_token(seq.uid, float(t_first))
        self._next_uid = max(self._next_uid, int(uid)) + 1
        self.prompt_lens[seq.uid] = len(prompt)
        self.waiting.append(seq)
        # a resumed request's span clock restarts NOW: the crash gap is
        # deliberately unaccounted (the waterfall flags the request
        # unreconciled instead of inventing a phase for dead time)
        self.tracer.open(seq.uid, "queued", self.global_step)
        return seq.uid

    def _blocks_needed(self, t0: int, max_new: int) -> int:
        """The request's blocks of the full kind's pool: its positions'
        or, for chunked layers, its chunks' summary rows' (one row a
        block of positions)."""
        need = blocks_needed(t0, max_new, self.cfg.block_size)
        return -(-need // self.cfg.block_size) if self.chunked else need

    def _wblocks_needed(self, t0: int, max_new: int) -> int:
        """... and of the window kind's a constant: a ring as long as
        the window table, or the whole request where it is shorter."""
        return min(blocks_needed(t0, max_new, self.cfg.block_size),
                   self.programs.window_blocks)

    # -- request lifecycle (telemetry schema v4 `request` records) -----

    @property
    def global_step(self) -> int:
        """Engine steps across crash-resumes: ``step_base`` (the
        snapshot step a resumed engine continues from) + in-process
        steps — the index chaos schedules and request records use."""
        return self.step_base + self.steps

    def _event(self, event: str, uid: int, reason: str | None = None,
               **extra) -> None:
        # telemetry v11: every request record carries the uid's
        # weights-version pin (None before first admission / for the
        # anonymous rejected uid -1) — the per-version attribution the
        # mixed-version report reads; v12: and its trace_id (None only
        # for requests that never entered — the anonymous rejected -1)
        rec = {"step": self.global_step, "uid": int(uid),
               "event": event, "reason": reason,
               "weights_version": self._pins.get(int(uid)),
               "trace_id": self._traces.get(int(uid)),
               "tenant": self._tenants.get(int(uid)), **extra}
        self.request_events.append(rec)
        # the flight recorder's per-step decision line (compact: the
        # digest ring is bounded memory, the durable trail is the
        # telemetry stream)
        self._step_events.append(
            f"{event} uid {uid}" + (f" ({reason})" if reason else ""))
        if self.metrics is not None:
            self.metrics.request(rec)

    def _qos_event(self, event: str, tenant, **extra) -> None:
        """One tenant-QoS scheduling decision record (telemetry v14
        ``qos`` kind): the step clock + the numbers that justified the
        decision — all deterministic, so the decision stream replays
        identically with the tokens."""
        if self.metrics is not None:
            self.metrics.qos({"step": self.global_step, "event": event,
                              "tenant": tenant, **extra})
        self._step_events.append(f"qos {event}"
                                 + (f" tenant {tenant}" if tenant
                                    else ""))

    def arm_poison(self, uid: int) -> None:
        """Arm the chaos nan_logits operand for the NEXT engine step:
        ``uid``'s logits row (every row for ``POISON_ALL``) comes out
        NaN, in-graph, zero recompiles. Consumed by that step."""
        self._poison_uid = int(uid)

    def corrupt_block(self, block: int) -> None:
        """Chaos ``corrupt_block``: poison one physical pool block
        (``paged.corrupt_block`` — NaN values, or NaN scales under
        int8) host-side between steps. The id is tracked so ANY
        release of the block (not just quarantine — a preemption or
        deadline expiry can evict the owner before its next dispatch
        flags the NaN) scrubs it instead of handing the poison to an
        innocent successor. A block the radix cache holds is POISONED
        in the tree immediately: no new sharer may match it (the fault
        must not propagate into future admissions), while its bytes are
        left alone until the last live sharer releases it (the
        decref-not-scrub contract — current sharers' own dispatches
        flag the NaN through the logits guardrail)."""
        self.pool = _pool_corrupt_block(self.pool, block)
        self._corrupted.add(int(block))
        if self.prefix is not None:
            node = self.prefix.node_for_block(int(block))
            if node is not None:
                node.poisoned = True

    def corrupt_spill(self, spill_id: int) -> bool:
        """Chaos ``corrupt_spill``: flip one byte of a HOST-TIER entry
        (``SpillTier.corrupt``) — the host-RAM bit rot the wire CRC
        ladder exists to catch. The damage is latent until a radix hit
        tries to restore the entry: ``take``'s CRC check raises, the
        edge detaches, and the restoring request quarantines
        (``corrupt_spill`` reason) while every survivor — including
        sharers of the RESIDENT prefix above the damaged edge — is
        untouched. Returns False when the entry no longer exists
        (already restored or dropped: the fault found nothing, exactly
        like poisoning an already-freed block)."""
        if self.spill is None:
            return False
        return self.spill.corrupt(int(spill_id))

    # -- scheduler (continued) -----------------------------------------

    def _eta_steps(self, prompt_len: int, max_new: int) -> int:
        """OPTIMISTIC engine steps from now until a newly submitted
        request would finish: all queued + resident remaining tokens
        plus its own, served max_slots per step (the engine's best
        case), plus its own prefill chunks. Deliberately a lower
        bound — predictive shedding must only fire on CERTAIN misses
        (an optimistic ETA past the deadline is a proof, a pessimistic
        one a guess). Deterministic: token counts and the step clock
        only."""
        work = max_new
        for s in self.slots:
            if s is not None:
                work += max(s.max_new - max(len(s.out), s.launched), 0)
        for s in self.waiting:
            work += s.max_new
        chunks = -(-prompt_len // self.cfg.prefill_chunk)
        return -(-work // self.cfg.max_slots) + chunks

    def _resident_tokens(self) -> dict[str, int]:
        """Per-tenant RESIDENT reserved tokens: the sum of admitted-
        but-unfinished ``max_new`` across slots — the token-budget
        gate's measure (reservations, not emissions: a budget caps how
        much of the pool's future work one tenant may hold)."""
        out: dict[str, int] = {}
        for s in self.slots:
            if s is not None:
                tk = tenant_key(s.tenant)
                out[tk] = out.get(tk, 0) + s.max_new
        return out

    def _next_waiting_index(self) -> tuple[int, float | None]:
        """The admission order's ONE decision point: ``(index into
        waiting of the next request to admit, its virtual time)``.
        FCFS (no qos policy, or discipline fcfs) returns ``(0, None)``
        — the historical strict head-of-line engine. WFQ picks among
        each tenant's FIFO head the tenant with the smallest virtual
        time (served_tokens / weight), ties broken by (submit_step,
        uid) — deterministic by construction. A tenant whose resident
        reservation would exceed ``token_budget`` is skipped (recorded
        once per uid) unless EVERY candidate is over budget — the
        budget shapes order, it never deadlocks the pool."""
        if (self.qos is None or self.qos.discipline == "fcfs"
                or len(self.waiting) <= 1):
            return 0, None
        heads: dict[str, tuple[int, _Seq]] = {}
        for i, s in enumerate(self.waiting):
            heads.setdefault(tenant_key(s.tenant), (i, s))
        budget = self.qos.token_budget
        if budget > 0 and len(heads) > 1:
            resident = self._resident_tokens()
            under = {}
            for tk, (i, s) in heads.items():
                if resident.get(tk, 0) + s.max_new <= budget:
                    under[tk] = (i, s)
                elif s.uid not in self._budget_deferred:
                    self._budget_deferred.add(s.uid)
                    self._qos_event("budget_deferred", s.tenant,
                                    uid=s.uid,
                                    resident_tokens=resident.get(tk, 0),
                                    token_budget=budget)
            if under:
                heads = under

        def vtime(tk: str) -> float:
            return (self._tenant_served.get(tk, 0)
                    / self.qos.weight_of(tk))

        tk = min(heads, key=lambda k: (vtime(k), heads[k][1].submit_step,
                                       heads[k][1].uid))
        i, _ = heads[tk]
        return i, round(vtime(tk), 6)

    def _admit(self) -> int:
        """FCFS admission: move waiting requests into free slots while
        both a slot and the request's full block reservation are
        available (reserve-on-admit keeps steady-state serving
        preemption-free). A head-of-line request that doesn't fit
        blocks the queue — strict FCFS keeps admission deterministic.
        With ``policy.preempt_after_steps > 0``, a head-of-line request
        that has been pool-starved (free slot, not enough free blocks)
        for that many consecutive steps evicts the YOUNGEST running
        sequence back to WAITING (replay resumes it token-identically
        later); the wait threshold is the anti-thrash hysteresis.

        With the prefix cache on, admission first walks the radix tree:
        every hit block is mapped into the table (locked, skipping its
        prefill) and only the MISSED blocks draw on the free list —
        refs-0 cached blocks are reclaimed LRU on demand, so retention
        never starves admission (the "effective sequences" capacity
        multiplier: N sharers of a k-block prefix reserve k + N * tail
        blocks, not N * (k + tail))."""
        admitted = 0
        bumped = False
        while self.waiting:
            # the ONE head-selection point: FCFS index 0, or the WFQ
            # virtual-time pick (DESIGN.md section 26) — either way
            # the chosen request is "the head" for everything below
            # (streaks, preemption, the blocked-queue break)
            head_i, head_vt = self._next_waiting_index()
            seq = self.waiting[head_i]
            need = self._blocks_needed(len(seq.prompt), seq.max_new)
            need_w = self._wblocks_needed(len(seq.prompt), seq.max_new)
            free_slots = [i for i, s in enumerate(self.slots) if s is None]
            if not free_slots:
                break
            # the version this admission would run under: an existing
            # pin (replay/migration — the sequence already started on
            # that version) or the current serving version (a fresh
            # start pins HERE, not at submit: "in-flight finishes on
            # the version it STARTED on, new admissions take the
            # latest" — a queued request that never prefilled takes
            # the post-deploy weights)
            ver = (seq.weights_version if seq.weights_version is not None
                   else self.serving_version)
            hits = ([] if self.prefix is None
                    else self.prefix.match(seq.prompt, ver))
            # split the matched path at its spilled suffix (the device-
            # leaf demotion rule guarantees the suffix shape): resident
            # hits map straight into the table, spilled hits must
            # RESTORE into fresh device blocks first — they draw on the
            # free list exactly like misses; what the hit saves is the
            # prefill, not the block
            n_res = 0
            while n_res < len(hits) and not hits[n_res].spilled:
                n_res += 1
            resident, spilled_sfx = hits[:n_res], hits[n_res:]
            avail = len(self.free_blocks)
            if self.prefix is not None:
                # refs-0 cached blocks are reclaimable — minus the hit
                # nodes themselves (about to be locked, not evicted)
                avail += (self.prefix.evictable_blocks()
                          - sum(1 for n in resident if n.refs == 0))
            if need - n_res > avail or need_w > len(self.free_wblocks):
                pa = self.policy.preempt_after_steps
                if pa > 0 and self._inflight is not None:
                    # a starved head is judged, and a victim replayed
                    # from ``prompt + out``, on what has LANDED: the
                    # unread result may finish a sequence and free what
                    # the head needs
                    self._collect()
                    continue
                if pa > 0:
                    if self._head_blocked_uid != seq.uid:
                        # the streak belongs to ONE head: a new head
                        # (old one admitted/expired/shed) must earn its
                        # own hysteresis, not inherit the old streak
                        self._head_blocked = 0
                        self._head_blocked_uid = seq.uid
                    if not bumped:      # one streak tick per step
                        self._head_blocked += 1
                        bumped = True
                    if (self._head_blocked >= pa
                            and self._preempt_youngest()):
                        continue        # blocks freed: re-check the head
                break
            self._head_blocked = 0
            self._head_blocked_uid = None
            if spilled_sfx:
                step = self.global_step
                todo = spilled_sfx[:max(0, self._restores_left)]
                # pin the resident prefix (and each node as it comes
                # back) so restore-pressure demotion can't reclaim the
                # matched path out from under its own admission
                self.prefix.lock(resident, step)
                locked = list(resident)
                corrupt = None
                try:
                    for node in todo:
                        self._restore_node(node)
                        self.prefix.lock([node], step)
                        locked.append(node)
                except wire.WireError:
                    corrupt = todo[len(locked) - n_res]
                finally:
                    for n in locked:
                        self.prefix.release(n, step)
                if corrupt is not None:
                    # CRC caught a damaged host-tier entry: the edge
                    # (with its now-unreachable spilled descendants)
                    # leaves the tree, and the request that would have
                    # trusted those bytes quarantines from the queue —
                    # survivors never read them
                    self.free_blocks.extend(
                        self.prefix.detach_subtree(corrupt))
                    self._quarantine_waiting(head_i, "corrupt_spill")
                    continue
                if len(todo) < len(spilled_sfx):
                    # promotion budget exhausted: keep what restored
                    # (resident, refs-0, warm — next step's budget
                    # continues from there) and defer the admission —
                    # a restore burst must never stall running decodes
                    break
            del self.waiting[head_i]
            self._budget_deferred.discard(seq.uid)
            if head_i != 0:
                # a non-head-of-line admit is the WFQ decision made
                # visible: record the virtual time that won it
                self._qos_event("wfq_pick", seq.tenant, uid=seq.uid,
                                virtual_time=head_vt)
            if seq.weights_version is None:
                seq.weights_version = ver   # the pin: set ONCE, here
            self._pins[seq.uid] = seq.weights_version
            slot = free_slots[0]
            need_priv = need - len(hits)
            if hits:
                # lock BEFORE any eviction so the matched path can't be
                # reclaimed out from under its own admission
                self.prefix.lock(hits, self.global_step)
                self.prefix_hit_blocks += len(hits)
                self.prefill_tokens_saved += (len(hits)
                                              * self.cfg.block_size)
            if self.prefix is not None:
                self.prefix_lookup_blocks += self.prefix.match_cap(
                    len(seq.prompt))
                if need_priv > len(self.free_blocks):
                    self._reclaim_cached(need_priv
                                         - len(self.free_blocks))
            seq.nodes = list(hits)
            seq.blocks = [n.block for n in hits] + [
                self.free_blocks.pop(0) for _ in range(need_priv)]
            # the hit region is already prefilled CONTENT — the prefill
            # clock starts past it (>= 1 token always remains, so the
            # first pick still comes from the prefill program)
            seq.prefilled = len(hits) * self.cfg.block_size
            if self.cfg.prefix_partial:
                # sub-block sharing: the longest resident edge sharing
                # a PARTIAL leading run of the remaining tokens donates
                # its first m rows into this sequence's first private
                # block (one compiled row-masked copy — scales freeze
                # at share time), and the prefill clock starts past
                # them. need_priv >= 1 always (the final-token block
                # is never a hit), so the destination exists.
                part = self.prefix.partial_match(seq.prompt, hits, ver)
                if part is not None:
                    donor, m = part
                    fn = self._program("cow_rows", 0)
                    self.pool = fn(self.pool, jnp.int32(donor.block),
                                   jnp.int32(seq.blocks[len(hits)]),
                                   jnp.int32(m))
                    donor.last_use = self.global_step  # LRU touch
                    seq.prefilled += m
                    self.partial_hits += 1
                    self.prefill_tokens_saved += m
            self.block_allocs += need
            row = np.full((self.cfg.max_blocks_per_seq,), SCRATCH_BLOCK,
                          np.int32)
            row[:need] = seq.blocks
            self.tables[slot] = row
            seq.wblocks = [self.free_wblocks.pop(0) for _ in range(need_w)]
            self.wtables[slot, :need_w] = seq.wblocks
            self.lengths[slot] = 0
            self.uids[slot] = seq.uid
            self.slots[slot] = seq
            seq.admit_index = self._admit_counter
            self._admit_counter += 1
            self._event("admitted", seq.uid,
                        wait_steps=self.global_step - seq.submit_step,
                        replay=len(seq.out),
                        prefix_hit_blocks=len(hits))
            # admission closes whatever gap span the request sat in
            # (queued / preempt_gap / quarantine) and starts prefill
            self.tracer.transition(seq.uid, "prefill", self.global_step)
            admitted += 1
        return admitted

    def _reclaim_cached(self, n: int) -> None:
        """Convert up to ``n`` refs-0 cached blocks back into free-list
        blocks (LRU, ``prefix.evict_lru``) — the pool-pressure valve
        that makes retention free: cached capacity is always
        reclaimable capacity. A reclaimed block the chaos layer
        corrupted is scrubbed on the way out (the ANY-release scrub
        contract: a poisoned refs-0 cached block has no owner whose
        eviction would otherwise scrub it). With the spill tier armed,
        reclamation DEMOTES instead of discarding — same LRU order,
        same freed device blocks, but the bytes move to host RAM and
        the edges stay matchable."""
        if self.spill is not None:
            self._demote(n)
            return
        got = self.prefix.evict_lru(n, self.global_step)
        bad = [b for b in got if b in self._corrupted]
        if bad:
            self.pool = scrub_blocks(self.pool, bad)
            self._corrupted.difference_update(bad)
            self.block_scrubs += len(bad)
        self.free_blocks.extend(got)

    def _demote(self, n: int) -> None:
        """Spill up to ``n`` refs-0 cached device-leaves to the host
        tier (``prefix.spill_victims`` — LRU, non-detaching): each
        victim's bytes leave the device through ``extract_blocks`` as
        ONE wire document (storage dtype + int8 scales, per-array
        CRC-32 — ``decode/spill.py``), the node flips to spilled, and
        the device block joins the free list. Poisoned / chaos-
        corrupted victims NEVER spill: the tier stores only bytes the
        purity argument certifies — those detach-and-scrub exactly as
        the single-tier engine did. A tier-capacity overflow drops the
        oldest-spilled entries; their now-unrestorable edges detach
        from the tree (FIFO by spill id IS LRU by spill time — a
        spilled node's clock cannot advance until restore)."""
        for node in self.prefix.spill_victims(n, self.global_step):
            b = node.block
            if node.poisoned or b in self._corrupted:
                sub = self.prefix.detach_subtree(node)
                bad = [x for x in sub if x in self._corrupted]
                if bad:
                    self.pool = scrub_blocks(self.pool, bad)
                    self._corrupted.difference_update(bad)
                    self.block_scrubs += len(bad)
                self.free_blocks.extend(sub)
                continue
            got = extract_blocks(self.pool, [b])
            doc = {"k": got["k"][:, 0], "v": got["v"][:, 0],
                   "k_scale": (None if got["k_scale"] is None
                               else got["k_scale"][:, 0]),
                   "v_scale": (None if got["v_scale"] is None
                               else got["v_scale"][:, 0])}
            before = self.spill.bytes_spilled
            sid, dropped = self.spill.put(node, doc)
            self.prefix.mark_spilled(node, sid)
            self.spilled_blocks += 1
            self.spill_bytes += self.spill.bytes_spilled - before
            self.free_blocks.append(b)
            for victim in dropped:
                if victim.parent is not None:    # still in the tree
                    self.free_blocks.extend(
                        self.prefix.detach_subtree(victim))

    def _restore_node(self, node) -> None:
        """Promote ONE spilled node back into a fresh device block: CRC-
        verify the tier entry (``SpillTier.take`` — raises
        ``wire.WireError`` on damage, the caller's quarantine path),
        implant the bytes through the same donated compiled program the
        KV handoff uses, and re-enter the node into every block-indexed
        view with a fresh LRU clock. The host wall-clock this costs is
        the ``restore_stall_s`` term the per-step budget bounds; each
        restored block is ``block_size`` prompt tokens that did NOT
        re-prefill."""
        t0 = time.perf_counter()
        # secure the destination BEFORE consuming the tier entry: a
        # corrupt entry (WireError below) must leave the free list
        # untouched for the survivors
        if not self.free_blocks:
            self._reclaim_cached(1)
        if not self.free_blocks:
            raise RuntimeError(
                "spill restore needs a free block and the pool has "
                "none (admission checked availability — this is a "
                "bookkeeping bug)")
        doc = self.spill.take(node.spill_id)
        dst = self.free_blocks.pop(0)
        args = [jnp.asarray(doc["k"]), jnp.asarray(doc["v"])]
        if doc["k_scale"] is not None:
            args += [jnp.asarray(doc["k_scale"]),
                     jnp.asarray(doc["v_scale"])]
        fn = self._program("implant", 0)
        self.pool = fn(self.pool, jnp.int32(dst), *args)
        self.prefix.mark_restored(node, dst, self.global_step)
        self.restores += 1
        self.restore_tokens_saved += self.cfg.block_size
        self.restore_stall_s += time.perf_counter() - t0
        self._step_restores += 1
        self._restores_left -= 1

    def _cache_full_blocks(self, slot: int, upto: int) -> None:
        """Transfer a slot's newly fully-prefilled FULL prompt blocks
        into the radix tree (the insert side of the prefix cache; runs
        as every prefill chunk lands, ``upto`` the prompt tokens
        prefilled up to and with that chunk: a chunk launched after it
        and still unread inserts nothing yet). Only blocks whose every
        row came from prompt tokens are cacheable — a partial block's remaining
        rows will be decode writes, making its content a function of
        the sampled continuation, not the prompt. The inserting
        sequence keeps using the block and holds one ref (its table
        entry). When ANOTHER sequence already cached this exact token
        path (two sharers prefilled concurrently — neither admission
        could see the other's blocks), the slot remaps onto the cached
        block and frees its freshly-written duplicate: the bytes are
        identical by the purity argument, so the remap is invisible to
        the sequence and the pool just got one block richer."""
        if self.prefix is None:
            return
        seq = self.slots[slot]
        bs = self.cfg.block_size
        full = min(upto, len(seq.prompt)) // bs
        step = self.global_step
        while len(seq.nodes) < full:
            i = len(seq.nodes)
            # inserts land under the sequence's PINNED version root:
            # block bytes are a function of the weights, so a block
            # prefilled under v is only ever a hit for v-admissions
            node = self.prefix.insert(seq.prompt, i, seq.blocks[i],
                                      step, version=seq.weights_version)
            if node is None:
                # parent path evicted/poisoned mid-prefill: the block
                # simply stays private (correct, just unshared)
                seq.nodes.append(None)
                continue
            if node.block != seq.blocks[i]:
                # late dedup: remap onto the cached twin, free ours
                self.free_blocks.append(seq.blocks[i])
                self.block_frees += 1
                self.block_allocs += 1      # the new shared mapping
                seq.blocks[i] = node.block
                self.tables[slot][i] = node.block
            self.prefix.lock([node], step)
            seq.nodes.append(node)

    def _cow_private(self, slot: int, lo: int, hi: int) -> None:
        """The copy-on-write barrier: before a dispatch whose KV write
        window covers table indices ``lo..hi`` of ``slot``, any block
        in that window still backed by a radix-tree node is privatized
        — a bit-identical device copy (``paged.copy_block``) into a
        fresh block, table remapped, node ref released — so no write
        can ever land in a block another sequence (or the cache) still
        reads. Structurally the scheduler never aims a write at a
        shared block (hits and inserts cover only fully-prefilled
        prompt blocks; every write lands at or past the prefill
        frontier), so this is an ENFORCED invariant, not a hot path:
        ``cow_copies`` stays 0 in steady state and the tests pin both
        the zero and the barrier's correctness when triggered by
        hand."""
        seq = self.slots[slot]
        if self.prefix is None or not seq.nodes:
            return
        for li in range(lo, min(hi + 1, len(seq.nodes))):
            node = seq.nodes[li]
            if node is None:
                continue
            if not self.free_blocks:
                self._reclaim_cached(1)
            if not self.free_blocks:
                raise RuntimeError(
                    "copy-on-write of a shared block needs a free "
                    "block and the pool has none (refs-0 cache empty)")
            dst = self.free_blocks.pop(0)
            fn = self._program("cow", 0)
            self.pool = fn(self.pool, jnp.int32(node.block),
                           jnp.int32(dst))
            self.prefix.release(node, self.global_step)
            seq.nodes[li] = None
            seq.blocks[li] = dst
            self.tables[slot][li] = dst
            self.block_allocs += 1          # the private replacement
            self.block_frees += 1           # the released shared map
            self.cow_copies += 1

    def _evict(self, slot: int, drop_shared: bool = False) -> _Seq:
        """Take a sequence off its slot and return its blocks (shared
        tail of release/quarantine/preempt/expire).

        Private blocks go back to the free list — scrubbed when the
        chaos layer marked them corrupted (an eviction that precedes
        the owner's next dispatch would otherwise hand the NaN to
        whoever reserves the block next), or wholesale under
        ``drop_shared`` (the quarantine stance: a poisoned run's
        PRIVATE bytes are not trusted).

        Shared blocks DECREF instead of free: while sharers remain,
        the bytes — an innocent survivor's prefix — are untouched (the
        decref-not-scrub contract). A clean last release leaves the
        block CACHED (refs-0, LRU-evictable: the cross-request reuse).
        A distrusted last release (``drop_shared`` or chaos-corrupted)
        scrubs it and detaches it — with its now-unreachable cached
        descendants — back to the free list. Released deepest-first so
        refcounts stay monotone root-to-leaf throughout."""
        seq = self.slots[slot]
        step = self.global_step
        to_free: list[int] = []
        to_scrub: set[int] = set()
        for li in reversed(range(len(seq.blocks))):
            b = seq.blocks[li]
            node = seq.nodes[li] if li < len(seq.nodes) else None
            if node is not None:
                self.prefix.release(node, step)
                if node.refs == 0 and (drop_shared
                                       or b in self._corrupted):
                    sub = self.prefix.detach_subtree(node)
                    to_scrub.update(x for x in sub
                                    if x == b or x in self._corrupted)
                    to_free.extend(sub)
            else:
                if drop_shared or b in self._corrupted:
                    to_scrub.add(b)
                to_free.append(b)
        if to_scrub:
            self.pool = scrub_blocks(self.pool, sorted(to_scrub))
            self._corrupted.difference_update(to_scrub)
            self.block_scrubs += len(to_scrub)
        if seq.wblocks:
            # the window kind's go back whole (none is ever shared),
            # scrubbed where the run's bytes are not trusted
            if drop_shared:
                self.wpool = scrub_blocks(self.wpool, seq.wblocks)
            self._step_window["window_blocks_released"] += len(seq.wblocks)
            self.free_wblocks.extend(seq.wblocks)
            seq.wblocks = []
            self.wtables[slot] = SCRATCH_BLOCK
        self.block_frees += len(seq.blocks)
        self.free_blocks.extend(to_free)
        seq.blocks = []
        seq.nodes = []
        self.tables[slot] = SCRATCH_BLOCK
        self.lengths[slot] = 0
        self.uids[slot] = 0
        self.slots[slot] = None
        return seq

    def _release(self, slot: int) -> None:
        seq = self.slots[slot]
        self.finished[seq.uid] = seq.prompt + seq.out
        # ONE completion timestamp feeds both the latency record and
        # the final span close — that identity is the reconciliation
        # the report waterfall asserts. ttft_s decomposes the latency
        # at the first-token mark (schema v9); null when the first
        # token predates a crash-resume that lost the mark.
        now = time.time()
        t_first = self.tracer.pop_first_token(seq.uid)
        self._event("completed", seq.uid,
                    latency_s=round(now - seq.t_submit, 4),
                    ttft_s=(None if t_first is None
                            else round(t_first - seq.t_submit, 4)),
                    n_new=len(seq.out), retries=seq.retries)
        self.tracer.close(seq.uid, self.global_step, t=now,
                          n_new=len(seq.out),
                          tokens=self._span_tokens.pop(seq.uid, 0))
        self._evict(slot)

    def _requeue(self, seq: _Seq) -> None:
        """Send a live sequence back to WAITING for replay-resume:
        prefill restarts from zero, recorded ``out`` tokens will be
        teacher-forced (``_Seq.emitted``). ``submit_step`` is
        deliberately NOT reset: the deadline TTL measures from the
        ORIGINAL submission, so preemption/retry churn cannot extend a
        request's life past its deadline."""
        seq.prefilled = 0
        seq.emitted = seq.launched = 0
        self.waiting.append(seq)

    def _preempt_youngest(self) -> bool:
        """Evict the most recently admitted running sequence back to
        WAITING (pool-pressure preemption). Never evicts the LAST
        running sequence: with >= 2 residents the oldest is never the
        victim and always makes live progress (termination guarantee);
        evicting a lone resident would hand out replay-only windows in
        which a long sequence never advances — the one true livelock
        shape, excluded by construction. Returns False when no eviction
        is allowed (the head then waits for a completion)."""
        victims = [(s.admit_index, i) for i, s in enumerate(self.slots)
                   if s is not None]
        if len(victims) < 2:
            return False
        _, slot = max(victims)
        seq = self._evict(slot)
        self.preempted += 1
        self._event("preempted", seq.uid, reason="pool_pressure",
                    n_out=len(seq.out))
        self.tracer.transition(seq.uid, "preempt_gap", self.global_step,
                               reason="pool_pressure",
                               tokens=self._span_tokens.pop(seq.uid, 0))
        self._requeue(seq)
        self._head_blocked = 0
        return True

    def _quarantine(self, slot: int, reason: str) -> None:
        """The guardrail's remedy: free exactly this sequence's slot and
        blocks — SCRUBBED, because a poisoned cache may hold NaN/Inf
        the masks cannot neutralize — and either retry (budget left:
        re-queue for replay-resume; the fault's garbage pick was never
        appended, so the retried request re-generates that token
        cleanly) or report the uid FAILED with the reason. Every other
        running sequence is untouched: per-slot gathers and
        (seed, uid, position) sampling keys make survivors bit-identical
        to a run that never admitted this request."""
        seq = self.slots[slot]
        # drop_shared: the poisoned run's PRIVATE blocks are scrubbed
        # wholesale (its bytes are not trusted), but blocks shared
        # through the radix cache only DECREF while sharers remain —
        # the bytes are an innocent survivor's prefix, pure functions
        # of the shared tokens, and zeroing them would corrupt the
        # survivor (the scrub-vs-decref contract; the last distrusted
        # release detaches and scrubs inside _evict)
        self._evict(slot, drop_shared=True)
        # scrub the shared scratch block too: every table pads with
        # SCRATCH_BLOCK, so a corrupted scratch poisons every gather
        # (0*nan==nan) — scrubbing it here is what turns "scratch
        # corrupted" into one quarantine wave + clean retries instead
        # of a permanent all-requests failure. Scratch is semantically
        # all-zeros (only pad writes land there, always masked), so
        # the scrub is always safe.
        self.pool = scrub_blocks(self.pool, [SCRATCH_BLOCK])
        self._corrupted.discard(SCRATCH_BLOCK)
        self.block_scrubs += 1
        self.quarantined += 1
        # dump the flight recorder at the END of this engine step (so
        # the digest covering the quarantine itself is in the ring)
        self._dump_reason = f"quarantine uid {seq.uid} ({reason})"
        self.tracer.transition(seq.uid, "quarantine", self.global_step,
                               reason=reason,
                               tokens=self._span_tokens.pop(seq.uid, 0))
        if seq.retries < self.policy.max_retries:
            seq.retries += 1
            self.retried += 1
            self._event("quarantined", seq.uid, reason=reason,
                        retrying=True)
            self._event("retried", seq.uid, reason=reason,
                        attempt=seq.retries,
                        max_retries=self.policy.max_retries)
            self._requeue(seq)
            return
        self._event("quarantined", seq.uid, reason=reason,
                    retrying=False, retries=seq.retries)
        self.tracer.close(seq.uid, self.global_step, reason=reason)
        self.tracer.pop_first_token(seq.uid)    # terminal: forget
        self.failed[seq.uid] = {"reason": reason, "retries": seq.retries,
                                "n_out": len(seq.out)}

    def _quarantine_waiting(self, head_i: int, reason: str) -> None:
        """Quarantine a request that faulted BEFORE taking a slot — the
        spill-restore failure mode: its radix hit named a host-tier
        entry whose CRC check failed (``corrupt_spill``), so the
        request that would have trusted those bytes is the one
        quarantined, at its waiting-queue position. No slot, no blocks,
        no pool bytes were touched; the corrupt edge is already
        detached, so a retry re-matches WITHOUT it and re-prefills the
        lost span cleanly. Same retry-or-fail ladder as the running
        quarantine, same record vocabulary — a report reader sees one
        quarantine story with two entry points."""
        seq = self.waiting[head_i]
        del self.waiting[head_i]
        self._budget_deferred.discard(seq.uid)
        self.quarantined += 1
        self._dump_reason = f"quarantine uid {seq.uid} ({reason})"
        self.tracer.transition(seq.uid, "quarantine", self.global_step,
                               reason=reason,
                               tokens=self._span_tokens.pop(seq.uid, 0))
        if seq.retries < self.policy.max_retries:
            seq.retries += 1
            self.retried += 1
            self._event("quarantined", seq.uid, reason=reason,
                        retrying=True)
            self._event("retried", seq.uid, reason=reason,
                        attempt=seq.retries,
                        max_retries=self.policy.max_retries)
            self._requeue(seq)
            return
        self._event("quarantined", seq.uid, reason=reason,
                    retrying=False, retries=seq.retries)
        self.tracer.close(seq.uid, self.global_step, reason=reason)
        self.tracer.pop_first_token(seq.uid)    # terminal: forget
        self.failed[seq.uid] = {"reason": reason, "retries": seq.retries,
                                "n_out": len(seq.out)}

    def _expire_deadlines(self) -> None:
        """Per-request TTL: fail any request (waiting or running) still
        unfinished ``deadline_steps`` engine steps after submission —
        graceful degradation under overload beats unbounded tail
        latency. Runs before admission so an expired waiter never
        takes a slot."""
        dl = self.policy.deadline_steps
        if dl <= 0:
            return

        def expire(seq: _Seq) -> None:
            # the one place the deadline record/entry shape is built —
            # waiting and running expiries cannot fork
            self.expired += 1
            self._event("expired", seq.uid, reason="deadline",
                        n_out=len(seq.out))
            self.tracer.close(seq.uid, self.global_step,
                              reason="deadline",
                              tokens=self._span_tokens.pop(seq.uid, 0))
            self.tracer.pop_first_token(seq.uid)    # terminal: forget
            self.failed[seq.uid] = {"reason": "deadline",
                                    "retries": seq.retries,
                                    "n_out": len(seq.out)}
            self._budget_deferred.discard(seq.uid)

        def overdue(seq: _Seq) -> bool:
            return self.global_step - seq.submit_step >= dl

        if any(seq is not None and overdue(seq) for seq in self.slots):
            # a request whose last token is in flight has finished, not
            # expired, and an expiry's ``n_out`` counts landed tokens
            self._collect()
        for slot, seq in enumerate(self.slots):
            if seq is not None and overdue(seq):
                self._evict(slot)
                expire(seq)
        if any(overdue(seq) for seq in self.waiting):
            keep = collections.deque()
            for seq in self.waiting:
                if overdue(seq):
                    expire(seq)
                else:
                    keep.append(seq)
            self.waiting = keep

    def _emit(self, slot: int, pick: int) -> None:
        """Fold one picked token into a slot: the live path appends the
        pick; the REPLAY path discards it and teacher-forces the
        recorded token instead (the picks match bit-for-bit on a
        healthy replay — forcing just removes the need to assume it)."""
        seq = self.slots[slot]
        was_replaying = seq.replaying
        if not seq.replaying:
            seq.out.append(pick)
            self.tokens_generated += 1
        seq.emitted += 1
        # a row that was launched and read in one go (a verify's
        # accepted drafts) counts as launched here
        seq.launched = max(seq.launched, seq.emitted)
        # the WFQ virtual clock: every emission (live or teacher-
        # forced replay — a migrated request's service on THIS engine
        # counts as this engine's service) advances its tenant's
        # served-token count
        tk = tenant_key(seq.tenant)
        self._tenant_served[tk] = self._tenant_served.get(tk, 0) + 1
        # the emission belongs to the CURRENT span (replay or decode
        # segment) — speculation makes steps multi-token, so span
        # records carry the count, not just the wall clock
        self._span_tokens[seq.uid] = self._span_tokens.get(seq.uid,
                                                           0) + 1
        if seq.finished:
            self._release(slot)
        elif was_replaying and not seq.replaying:
            # caught up: the teacher-forcing window ends, live decode
            # begins (a new decode SEGMENT span)
            self.tracer.transition(seq.uid, "decode", self.global_step,
                                   replayed=len(seq.out),
                                   tokens=self._span_tokens.pop(
                                       seq.uid, 0))

    @staticmethod
    def _maybe_capture(fn, *args) -> None:
        """The PR 2 capture hook, shared with the training launcher:
        when ``parallel.launcher.CAPTURE_COMPILED`` is armed, append
        this dispatch's optimized HLO so the named-scope attribution
        contract is asserted against the REAL compiled serving program
        (tests), not a reconstruction. None (the default) costs one
        attribute read per dispatch.

        The capture compile bypasses the persistent XLA cache: a
        deserialized executable's ``as_text()`` drops op_name metadata
        — exactly the scope names being asserted — and unlike the
        shard_map'd training programs (which the cache can't serialize)
        the single-device engine programs DO round-trip through it, so
        a warm tier-1 cache would void the contract test."""
        if launcher.CAPTURE_COMPILED is None:
            return
        old = jax.config.jax_compilation_cache_dir
        try:
            jax.config.update("jax_compilation_cache_dir", None)
            launcher.CAPTURE_COMPILED.append(
                fn.lower(*args).compile().as_text())
        finally:
            jax.config.update("jax_compilation_cache_dir", old)

    def _launch(self, phase: str, bucket: int, fn, params: ServedModel,
                operand: np.ndarray, land) -> None:
        """Launch one step program on its packed operand
        (``decode/programs.py`` has the format): one host-to-device
        transfer, the vector handed to the jitted call as it is, in the
        phase ``<phase>.dispatch``; the call returns at once and its
        result stays on the device. ``bucket`` is the key ``fn`` was
        asked of ``_program`` under; the launch is noted in the step's
        ``dispatches`` as ``[kind, bucket]``. ``land(picks)`` is what
        folds the result's picks into the scheduler, whenever they are
        read (``_collect``). The launch BEFORE this one, if it is still
        unread, is read now that the device has its next program
        queued: at most one result is ever in flight."""
        # with speculation on every decode dispatch is a verify dispatch
        kind = ("verify" if phase == "decode" and self.cfg.speculate
                else phase)
        self._step_dispatches.append([kind, bucket])
        args = (params, self._carry(), operand)
        self._maybe_capture(fn, *args)
        with self.phases.phase(phase + ".dispatch"):
            carry, result = fn(*args)
            self._keep(carry)
        before, self._inflight = self._inflight, _Launch(
            self.launches, phase, kind, result, land)
        self.launches += 1
        self._read(before)

    def _collect(self) -> None:
        """Read the result that is still in flight, if one is, and fold
        it into the scheduler: after it ``seq.out``, ``finished``,
        ``tokens_generated``, the tenant clocks and the request spans
        say what every launched row produced. Whatever needs token
        VALUES to go on calls this first."""
        launch, self._inflight = self._inflight, None
        self._read(launch)

    def _read(self, launch: _Launch | None) -> None:
        """One blocking read of ``launch``'s packed result, in the phase
        ``<its phase>.readback`` (noted in the step's ``readbacks``),
        then its ``land``. An expert model's counters, which came on
        the same read, are kept for the step's digest; the rows' finite
        flags go to the digest of the step that launched them."""
        if launch is None:
            return
        with self.phases.phase(launch.phase + ".readback"):
            result = np.asarray(launch.result)
        self._step_readbacks.append(launch.ordinal)
        picks, rows = self.programs.split(launch.kind, result)
        if rows is not None:
            self._step_expert_rows.append(rows)
        flags = launch.land(picks)
        if launch.digest is not None:
            launch.digest["finite"] = (launch.digest["finite"] or []) + flags
        else:
            self._step_finite = (self._step_finite or []) + flags

    def collect(self) -> None:
        """Bring the host's view up to the device's: read the result of
        the last launched step, if ``step()`` left it unread (its
        docstring says when it does). For a caller that steps the
        engine itself and looks at ``slots[i].out``, ``finished`` or
        ``tokens_generated`` in between."""
        self._collect()

    def _row(self, slot: int) -> tuple:
        """A launched row's owner: the slot, its sequence and WHICH
        admission of it (a retried sequence may be back in its old slot
        when the row lands)."""
        seq = self.slots[slot]
        return slot, seq, seq.admit_index

    def _holds(self, row: tuple) -> bool:
        """Whether the sequence a row was launched for still holds its
        slot under that admission; the row is dropped otherwise."""
        slot, seq, admitted = row
        return self.slots[slot] is seq and seq.admit_index == admitted

    def _land(self, chunk: tuple | None, rows: list[tuple], picks) -> list:
        """Fold a read result into the scheduler: the chunk's last
        pick, then the batch's. Returns the finite flags in that
        order."""
        flags = []
        if chunk is not None:
            with self.phases.phase("prefill.book"):
                flags.append(self._prefill_book(*chunk, int(picks[-1])))
        if rows:
            with self.phases.phase("decode.emit"):
                flags += self._emit_batch(rows, picks)
        return flags

    def _prefill_step(self, slot: int) -> None:
        seq = self.slots[slot]
        phase = self.phases.phase
        with phase("prefill.cow"):
            c = self._prefill_chunk(seq)
            self._cow_chunk(slot, seq, c)
        with phase("prefill.upload"):
            self.prefill_dispatches += 1
            fn = self._program("prefill", c)
            operand = self.programs.pack(
                "prefill", c, poison=self._poison_uid,
                **self._chunk_fields(slot, seq, c, "tokens"))
            chunk = self._count_chunk(slot, seq, c)
        self._launch(
            "prefill", c, fn, self._params_for(seq.weights_version),
            operand, lambda picks: self._land(chunk, [], picks))

    def _cow_chunk(self, slot: int, seq: _Seq, c: int) -> None:
        """The CoW write barrier over the blocks ``seq``'s next chunk
        of ``c`` tokens writes."""
        bs = self.cfg.block_size
        self._cow_private(slot, seq.prefilled // bs,
                          (seq.prefilled + c - 1) // bs)

    def _chunk_fields(self, slot: int, seq: _Seq, c: int,
                      tokens: str) -> dict:
        """One slot's chunk in a step program's operand: its table,
        start, the next ``c`` prompt tokens (under the name the program
        gives them), uid and the slot itself (its entry of the token
        store and its state row)."""
        fields = {"table": self.tables[slot], "pos0": seq.prefilled,
                  tokens: seq.prompt[seq.prefilled:seq.prefilled + c],
                  "uid": seq.uid, "row": slot}
        if self.windowed:
            fields["wtable"] = self.wtables[slot]
        return fields

    def _prefill_chunk(self, seq: _Seq) -> int:
        """The next chunk's size for ``seq``."""
        remaining = len(seq.prompt) - seq.prefilled
        # largest power-of-two bucket that fits the remaining prompt:
        # chunk starts stay multiples of the chunk size, so no chunk
        # ever straddles a block boundary (paged.write_chunk's contract)
        c = max(b for b in self.chunk_buckets if b <= remaining)
        bs = self.cfg.block_size
        if seq.prefilled % bs:
            # a sub-block partial hit started the clock mid-block: cap
            # the chunk at the largest power of two that stays inside
            # the current block (write_chunk's single-block contract);
            # once the clock reaches the boundary, normal chunking
            # resumes — same greedy power-of-two discipline, just
            # anchored to the block edge instead of offset zero
            gap = bs - seq.prefilled % bs
            c = max(b for b in self.chunk_buckets if b <= min(c, gap))
        return c

    def _count_chunk(self, slot: int, seq: _Seq, c: int) -> tuple:
        """Advance the COUNTS of a chunk that is about to be launched:
        the prompt tokens prefilled and, where it completes the prompt,
        the slot's length and the first launched token, so the slot's
        first decode row can follow in the next program. Returns what
        ``_prefill_book`` takes when the chunk's pick lands."""
        self._step_prefill_uid = seq.uid
        row = self._row(slot)
        if self.windowed:
            # one view for the chunk's rows: the positions up to its end
            self._count_rows(np.asarray([seq.prefilled + c - 1]),
                             np.asarray([seq.prefilled]))
        seq.prefilled += c
        if seq.prompt_done:
            self.lengths[slot] = len(seq.prompt)
            seq.launched = 1
        return row, c, seq.prefilled

    def _count_rows(self, last: np.ndarray, first: np.ndarray) -> None:
        """Count the cache reads of rows about to be launched
        (``WINDOW_COUNTERS``; a model with window layers only): each
        view ends at position ``last`` and was opened by writing from
        ``first`` on (a decode row writes one position, a chunk its
        own), so a full layer reads ``last + 1`` positions, a window
        layer at most the window, and every block the write OPENS
        beyond the ring's length overwrites one that is behind it. A
        chunked layer's counts (``CHUNK_COUNTERS``) are taken here
        too."""
        blk, entries = self.cfg.block_size, self.programs.window_blocks
        w = self._step_window
        window = self.spec.window
        w["full_rows"] += int((last + 1).sum())
        if self.chunked:
            # the row's own aligned window up to itself, and a summary
            # for every chunk of the windows before it; a write that
            # ends on a chunk's last position finishes its summary
            w["window_rows"] += int((last % window + 1).sum())
            w["summary_rows"] += int((last // window).sum()) * (window
                                                                 // blk)
            w["summaries_written"] += int((last % blk == blk - 1).sum())
        else:
            w["window_rows"] += int(np.minimum(last + 1, window).sum())
        opened = last // blk - (first - 1) // blk   # block starts in range
        fresh = np.minimum(opened, last // blk + 1 - entries)
        w["window_blocks_released"] += int(np.maximum(fresh, 0).sum())

    def _count_blocks(self, ready: list[int], b: int,
                      reads: int = 1) -> None:
        """Count the blocks the decode-side reads of a batch about to
        be launched fetch (``KV_COUNTERS``): ``ready`` the slots of its
        rows BEFORE their lengths advance, ``b`` its bucket, ``reads``
        the reads a row makes a layer (a verify program's sub-steps,
        each one position further). Of the pool, a row that attends
        over ``n`` positions walks ``ceil(n / block)`` blocks; of a
        window layer's ring, the blocks from its window's first
        position to its last (``paged.ring_start``: the rule the kernel
        is handed); a padded row the scratch block of either; a gather
        reads every row's whole table whatever it holds. A verify
        program's dead sub-steps past a row's last position count the
        table's capacity, no more (only ``reads > 1`` can pass it)."""
        blk = self.cfg.block_size
        n = np.minimum(self.lengths[ready][:, None] + 1 + np.arange(reads),
                       self.capacity)
        padded = reads * (b - len(ready))
        ring = ring_held = 0
        if self.wpool is not None:
            window = self.spec.window
            layers = self.wpool.k.shape[0]
            ring_held = reads * b * self.programs.window_blocks * layers
            first = ring_start(n - 1, window, self.chunked) // blk
            ring = (((int(((n - 1) // blk - first + 1).sum()) + padded)
                     * layers) if self._ring_walks else ring_held)
            if self.chunked:
                # the pool's rows are summaries: those of the windows
                # before the row's own (a row in its first window
                # walks one block, masked whole)
                n = np.maximum((n - 1) // window * (window // blk), 1)
        layers = self.pool.k.shape[0]
        held = reads * b * self.cfg.max_blocks_per_seq * layers
        read = ((int((-(-n // blk)).sum()) + padded) * layers
                if self._walks else held)
        for key, blocks in zip(KV_COUNTERS, (read, held, ring, ring_held)):
            self._step_kv[key] += blocks
            setattr(self, key, getattr(self, key) + blocks)

    def _prefill_book(self, row: tuple, c: int, end: int,
                      nxt: int) -> bool:
        """Fold a launched chunk's result (its last row's folded pick)
        into the slot: ``c`` tokens that brought the prompt to ``end``.
        Returns its finite flag."""
        slot, seq, _ = row
        fine = nxt >= 0
        if not self._holds(row):
            return fine         # quarantined or expired meanwhile
        if not fine:
            self._quarantine(slot, "nonfinite_logits")
            return fine
        self._cache_full_blocks(slot, end)
        if end == len(seq.prompt):
            # the chunk that completes the prompt hands the span clock
            # to the next phase BEFORE the emit below may release the
            # sequence outright (max_new == 1). ONE timestamp serves
            # the span boundary AND the first-token mark: the emit
            # below appends the first live token at exactly this
            # instant, which is what makes ttft_s reconcile with the
            # pre-first-token span sum (runtime/tracing.py). A
            # replaying sequence already emitted its first token in a
            # previous life — the mark is idempotent and replay never
            # re-marks here (its recorded first token is forced, not
            # picked).
            now = time.time()
            if not seq.replaying:
                self.tracer.mark_first_token(seq.uid, now)
            self.tracer.transition(
                seq.uid, "replay" if seq.replaying else "decode",
                self.global_step, t=now, tokens=c)
            # the pick is used only where the chunk completes the prompt
            self._emit(slot, nxt)
        else:
            # one span per prefill chunk, telescoping across the engine
            # steps spent on other slots in between
            self.tracer.transition(seq.uid, "prefill", self.global_step,
                                   tokens=c)
        return fine

    def _marshal(self, ready: list[int], b: int | None = None):
        """Bucket-pad the dispatch operands for ``ready`` (to ``b``
        rows where given, else to the smallest slot bucket that holds
        them): pad rows point at the scratch block with zeroed
        length/token/uid, so their writes land in the pad row's
        designated dump and their idle uid never matches a poison
        operand. A row's token is the host's where the host has it
        (``_Seq.next_token``)."""
        if b is None:
            b = _bucket_for(len(ready), self.slot_buckets)
        idx = ready + [0] * (b - len(ready))        # pad rows
        tables = self.tables[idx].copy()
        lengths = self.lengths[idx].copy()
        tokens = ([self.slots[slot].next_token for slot in ready]
                  + [0] * (b - len(ready)))
        uids = self.uids[idx].copy()
        for j in range(len(ready), b):              # pads -> scratch
            tables[j] = SCRATCH_BLOCK
            lengths[j] = 0
            uids[j] = 0
        return b, tables, lengths, tokens, uids

    def _version_groups(self, ready: list[int]) -> list[list[int]]:
        """Split the ready slots by weights-version pin — ONE dispatch
        per resident version (a compiled program runs one params
        operand). Slot order is preserved within each group and the
        common single-version case degenerates to the old whole-batch
        dispatch; token identity is untouched either way because the
        sampling keys and per-slot gathers never reference the batch
        composition (the migration identity argument, applied to the
        mixed-version engine a rolling deploy creates)."""
        groups: dict[int, list[int]] = {}
        for slot in ready:
            groups.setdefault(self.slots[slot].weights_version,
                              []).append(slot)
        return [groups[v] for v in sorted(groups)]

    def _cow_batch(self, ready: list[int]) -> None:
        """The CoW write barrier over the block each ready slot's next
        token lands in."""
        bs = self.cfg.block_size
        for slot in ready:
            self._cow_private(slot, int(self.lengths[slot]) // bs,
                              int(self.lengths[slot]) // bs)

    def _batch_fields(self, ready: list[int], b: int, tables, lengths,
                      tokens, uids) -> dict:
        """A marshalled decode batch in a step program's operand, with
        the poison; counts the state bytes its rows read."""
        # each batch row's slot (its entry of the token store and its
        # state row), and the scratch row for the bucket's padded rows
        rows = ready + [self.cfg.max_slots] * (b - len(ready))
        if self.state is not None:
            self._step_state_bytes += (len(ready)
                                       * self.state.bytes_per_slot)
        fields = dict(tables=tables, lengths=lengths, tokens=tokens,
                      uids=uids, poison=self._poison_uid, rows=rows)
        if self.windowed:
            wtables = np.full((b, self.wtables.shape[1]), SCRATCH_BLOCK,
                              np.int32)
            wtables[:len(ready)] = self.wtables[ready]
            fields["wtables"] = wtables
        return fields

    def _count_batch(self, ready: list[int], b: int) -> list[tuple]:
        """Advance the COUNTS of a decode batch that is about to be
        launched in a bucket of ``b`` rows: each slot's length and its
        sequence's launched tokens. Returns the rows ``_emit_batch``
        takes when the picks land."""
        rows = [self._row(slot) for slot in ready]
        if self.windowed:
            self._count_rows(self.lengths[ready], self.lengths[ready])
        self._count_blocks(ready, b)
        self.lengths[ready] += 1
        for _, seq, _ in rows:
            seq.launched += 1
        self._step_decode_uids += [seq.uid for _, seq, _ in rows]
        return rows

    def _emit_batch(self, rows: list[tuple], picks) -> list[bool]:
        """Fold a launched decode batch's folded picks into its slots;
        returns the rows' finite flags."""
        flags = (picks[:len(rows)] >= 0).tolist()
        for j, row in enumerate(rows):  # pad rows are never in `rows`
            if not self._holds(row):
                continue        # quarantined or expired meanwhile
            if not flags[j]:
                self._quarantine(row[0], "nonfinite_logits")
                continue
            self._emit(row[0], int(picks[j]))
        return flags

    def _decode_dispatch(self, ready: list[int]) -> None:
        phase = self.phases.phase
        with phase("decode.cow"):
            self._cow_batch(ready)
        with phase("decode.marshal"):
            params = self._params_for(
                self.slots[ready[0]].weights_version)
            b, *batch = self._marshal(ready)
            fn = self._program("decode", b)
        with phase("decode.upload"):
            operand = self.programs.pack(
                "decode", b, **self._batch_fields(ready, b, *batch))
            rows = self._count_batch(ready, b)
        self._launch("decode", b, fn, params, operand,
                     lambda picks: self._land(None, rows, picks))

    # -- the chunk rides with the batch ---------------------------------

    def _ready(self) -> list[int]:
        """The slots whose next decode row can be launched: the prompt
        prefilled (its last chunk launched, at least) and a token still
        to produce that no launched row is producing already."""
        return [i for i, s in enumerate(self.slots)
                if s is not None and s.prompt_done and not s.all_launched]

    def _mixed_batch(self, pre: int | None,
                     prefill_only: bool) -> list[int]:
        """The ready slots this step's prefill chunk rides with in ONE
        ``mixed`` program, or none where the step runs as today (a
        ``prefill`` program, then ``decode``): decided by what the step
        can see. It rides when the chunk is of the FULL size (a
        prompt's tails and a chunk cut by a mid-block prefix hit take
        the old path), there is a ready slot, every ready slot and the
        chunk's are on one weights version (a program runs one params
        operand), and the engine neither speculates nor serves the
        prefill tier. ONE program serves them all — the batch padded to
        the LARGEST slot bucket, not a program a bucket and not a
        bucket x chunk grid: a step program costs seconds of every
        start-up (its layers are traced, fetched and loaded one by
        one), and padded rows cost a ride little (they read no weights;
        their cache reads are what the full batch's are)."""
        if pre is None or prefill_only or self.cfg.speculate:
            return []
        seq = self.slots[pre]
        if self._prefill_chunk(seq) != self.cfg.prefill_chunk:
            return []
        ready = self._ready()
        if any(self.slots[i].weights_version != seq.weights_version
               for i in ready):
            return []
        return ready

    def _mixed_dispatch(self, slot: int, ready: list[int]) -> None:
        """One dispatch for the step's chunk (``slot``) and its decode
        batch (``ready``): the host halves of ``_prefill_step`` and
        ``_decode_dispatch`` under their own phase names round ONE
        ``mixed.upload`` / ``.dispatch`` and, whenever the result is
        read, ``mixed.readback`` / ``prefill.book`` / ``decode.emit``.
        Counted as one dispatch that carried a chunk. A prompt the
        chunk completes joins the batch in the next step; its first
        token is emitted when the result lands."""
        seq = self.slots[slot]
        phase = self.phases.phase
        c = self.cfg.prefill_chunk
        with phase("prefill.cow"):
            self._cow_chunk(slot, seq, c)
        with phase("decode.cow"):
            self._cow_batch(ready)
        with phase("decode.marshal"):
            b, *batch = self._marshal(ready, self.slot_buckets[-1])
            fn = self._program("mixed", b)
        with phase("mixed.upload"):
            self.prefill_dispatches += 1
            self.mixed_dispatches += 1
            operand = self.programs.pack(
                "mixed", b, **self._batch_fields(ready, b, *batch),
                **self._chunk_fields(slot, seq, c, "chunk"))
            chunk = self._count_chunk(slot, seq, c)
            rows = self._count_batch(ready, b)
        self._launch(
            "mixed", b, fn, self._params_for(seq.weights_version),
            operand, lambda picks: self._land(chunk, rows, picks))

    # -- speculative decoding (DESIGN.md section 18) -------------------

    def _draft_for(self, seq: _Seq, budget: int) -> tuple[list[int], int]:
        """Up to ``budget`` draft tokens for one slot, plus how many of
        them are teacher-forced REPLAY tokens. During replay the
        recorded continuation IS the draft (teacher-forcing through
        the verify path — all accepted on a healthy replay, so resume
        re-speculates at full width); past the recorded window (and for
        live sequences) the n-gram prompt-copy drafter proposes from
        the full known history. Both sources are pure functions of
        ``prompt + out`` — the re-draft-identically contract. The
        replay count lets ``_verify_dispatch`` keep teacher-forced tokens
        out of ``drafted_tokens``/``accepted_tokens``: they are
        accepted by construction, not by drafter skill, and a
        crash-resume already restored them into the counters once."""
        if budget <= 0:
            return [], 0
        rec = seq.out[seq.emitted:seq.emitted + budget]
        if len(rec) < budget:
            guess = draft_tokens(seq.prompt + seq.out,
                                 budget - len(rec))
            return rec + guess[:budget - len(rec)], len(rec)
        return rec[:budget], budget

    def _verify_dispatch(self, ready: list[int]) -> None:
        """The speculative decode dispatch: draft per slot (capped so
        accepted emissions can never outrun ``max_new`` or the block
        reservation — a verify step writes one KV row per emitted
        token, the non-speculative 1:1), run the verify program once,
        then emit each slot's ``1 + accepted`` greedy tokens. A
        non-finite flag anywhere in a slot's USED window (sub-steps
        ``0..accepted``) quarantines the whole step for that uid —
        nothing is emitted, the drafted tail is rolled back whole
        (its masked rows only ever landed in the uid's own blocks,
        which quarantine frees and scrubs)."""
        phase = self.phases.phase
        k = self.cfg.speculate
        bs = self.cfg.block_size
        with phase("decode.cow"):
            for slot in ready:
                # the verify window writes positions lengths..lengths+k
                # (rejected rows land on scratch, but the barrier guards
                # the whole window — a masked write must never even AIM
                # at a shared block)
                self._cow_private(slot, int(self.lengths[slot]) // bs,
                                  (int(self.lengths[slot]) + k) // bs)
        with phase("decode.marshal"):
            b, tables, lengths, tokens, uids = self._marshal(ready)
            drafts = np.zeros((b, k), np.int32)
            dlens = np.zeros((b,), np.int32)
            replayed = np.zeros((b,), np.int32)
            for j, slot in enumerate(ready):
                seq = self.slots[slot]
                # emissions this step <= max_new - emitted (the final
                # token of a sequence is returned, never cached, so the
                # row budget works out to exactly the capacity check
                # submit() performed)
                d, n_rec = self._draft_for(
                    seq, min(k, seq.max_new - seq.emitted - 1))
                dlens[j] = len(d)
                drafts[j, :len(d)] = d
                replayed[j] = n_rec
                self.drafted_tokens += len(d) - n_rec
            fn = self._program("verify", b)
            params = self._params_for(
                self.slots[ready[0]].weights_version)
        with phase("decode.upload"):
            operand = self.programs.pack(
                "verify", b, tables=tables, lengths=lengths, tokens=tokens,
                uids=uids, poison=self._poison_uid, drafts=drafts,
                dlens=dlens)
        self._step_decode_uids += [self.slots[s].uid for s in ready]
        self._count_blocks(ready, b, reads=k + 1)

        def land(result) -> list[bool]:
            with phase("decode.emit"):
                picks, acc = result[:, :k + 1], result[:, k + 1]
                ok = picks >= 0
                flags = []
                for j, slot in enumerate(ready):
                    m = int(acc[j])
                    fine = bool(ok[j, :m + 1].all())
                    flags.append(fine)
                    if not fine:
                        self._quarantine(slot, "nonfinite_logits")
                        continue
                    self.accepted_tokens += max(0, m - int(replayed[j]))
                    self.lengths[slot] += m + 1
                    for t in range(m + 1):
                        if self.slots[slot] is None:
                            break   # released at its final emission
                        self._emit(slot, int(picks[j, t]))
            return flags

        # the accepted count sets the lengths: no verify's read waits
        # (``_may_defer``), so ``land`` runs before the step ends
        self._launch("decode", b, fn, params, operand, land)

    def step(self, prefill_only: bool = False) -> bool:
        """One scheduler iteration: expire deadlines, admit (with
        pool-pressure preemption when armed), at most ONE prefill chunk
        (so a long prompt never stalls running decodes for more than a
        chunk) and one decode dispatch over every ready slot — as ONE
        mixed dispatch where ``_mixed_batch`` finds the chunk can ride
        with the batch, else the chunk's and then the batch's. Returns
        whether any work ran. An armed chaos poison operand applies to
        exactly this step's dispatches.

        **Launch, then collect.** A step launches its program and only
        then reads the result of the program launched BEFORE it, so the
        device always has the next program queued behind the running
        one. What a launch needs of a sequence are counts the host has
        at launch (``lengths``, the prompt tokens prefilled, the tokens
        launched, the ready set, all advanced as a row is launched); a
        row's pick reaches the slot's next row on the device (the token
        store). The VALUES land one step late, together: ``seq.out``,
        ``tokens_generated``, the tenant clock, ``finished[uid]``, the
        request records and spans, a non-finite row's quarantine (its
        row in the program launched meanwhile is dropped when it
        lands), and the launching step's ``finite`` flags in its flight
        digest. So **after ``step()`` returns a caller may read**
        ``waiting``, ``active``, ``lengths``, ``steps``, the counters of
        launches (``dispatch_count``, ``prefill_dispatches``) and of
        everything that had landed; ``slots[i].out``, ``finished``,
        ``failed`` and ``tokens_generated`` may lack the LAST launched
        program's tokens until the next ``step()`` or ``collect()``.
        ``active`` stays non-zero while a result is unread, so ``while
        eng.active or eng.waiting: eng.step()`` ends with everything
        landed; a step that only reads returns True.

        Which read waits is decided by what the step can see, by no
        field or flag (``_may_defer``): that of a ``decode`` or
        ``mixed`` program on one weights version, while some resident
        sequence still has a row to launch. Every other result is read
        in the step that launched it, as before: a speculative verify
        (the accepted count sets the lengths), the prefill tier's
        chunks (``prefill_only``: the first pick is the router's), a
        chunk with no batch to follow, several weights versions in one
        step, the last program of a draining engine. And whatever needs
        values reads first (``_collect``): ``export_sequence`` /
        ``finish_export`` / ``release_request``, an overdue deadline,
        pool-pressure preemption (a replay needs ``prompt + out``),
        ``telemetry_record``, ``dump_flight_recorder``, ``run()``'s
        return.

        ``prefill_only`` skips the decode dispatch — the fleet's
        prefill tier (``decode/fleet.py``): a prompt that completes
        emits its first pick from the prefill program and then PARKS
        until the router ships it to a decode engine, so a
        prefill-tier engine never compiles or dispatches a decode
        program at all (the disaggregation dispatch proof, both
        directions)."""
        phases = self.phases
        phases.begin(self.global_step + 1)
        with phases.phase("step"):
            did = self._step(prefill_only)
        _, start_ns, end_ns = phases.stamps.pop()   # the parent closed last
        if did and self.metrics is not None:
            self.metrics.span(self._step_record(start_ns, end_ns))
        phases.end()
        return did

    def _may_defer(self, groups: int) -> bool:
        """Whether the read of the program this step launched last may
        wait for the next step's launch: a ``decode`` or ``mixed``
        program (a verify's accepted counts and the prefill tier's
        picks are values the host goes on with), one weights version
        in the step, and a resident sequence with a row still to
        launch (with none, nothing would be queued behind it)."""
        return (self._inflight.kind in ("decode", "mixed") and groups <= 1
                and any(s is not None and not s.all_launched
                        for s in self.slots))

    def _step(self, prefill_only: bool) -> bool:
        """``step``'s body, every part of it inside one phase of
        ``self.phases`` (``runtime/tracing.py`` has the vocabulary)."""
        phase = self.phases.phase
        # _step_events is NOT reset here: shed/rejected events from
        # between-step submissions (and a prior dispatch-free step)
        # belong to the next digest taken — resetting would drop them
        # from the flight recorder entirely
        self._step_finite = None
        self._step_prefill_uid = None
        self._step_decode_uids = []
        self._step_state_bytes = 0
        self._step_window = dict.fromkeys(
            WINDOW_COUNTERS + CHUNK_COUNTERS, 0)
        self._step_kv = dict.fromkeys(KV_COUNTERS, 0)
        self._step_dispatches = []
        self._step_readbacks = []
        self._step_expert_rows = []
        with phase("expire"):
            # spill-tier housekeeping: a fresh promotion budget each
            # step (the restore analogue of one-prefill-chunk-per-step),
            # and the proactive low-watermark demotion — keep a cushion
            # of free blocks so admission bursts don't pay the demotion
            # walk inline
            self._restores_left = self.cfg.spill_restore_per_step
            self._step_restores = 0
            if (self.spill is not None and self.cfg.spill_low_water > 0
                    and len(self.free_blocks) < self.cfg.spill_low_water):
                self._demote(self.cfg.spill_low_water
                             - len(self.free_blocks))
            self._expire_deadlines()
        with phase("admit"):
            self._admit()
            pre = next((i for i, s in enumerate(self.slots)
                        if s is not None and not s.prompt_done), None)
        with phase("decode.marshal"):
            riders = self._mixed_batch(pre, prefill_only)
        if riders:
            self._mixed_dispatch(pre, riders)
        elif pre is not None:
            self._prefill_step(pre)
        if self.cfg.speculate:
            # a draft is a function of ``prompt + out``: the step's
            # chunk has to have landed before its slot is drafted for
            self._collect()
        with phase("decode.marshal"):
            ready = [] if prefill_only or riders else self._ready()
            groups = self._version_groups(ready)
        # speculation on -> every decode dispatch is a verify dispatch
        # (one program kind per bucket; a zero-draft step degenerates to
        # plain decode inside the same program, so the steady-state
        # compile surface stays bounded)
        dispatch = (self._verify_dispatch if self.cfg.speculate
                    else self._decode_dispatch)
        for group in groups:
            dispatch(group)
        # the result in flight is this step's last launch (a launch
        # reads the one before it), or an earlier step's where this one
        # launched nothing: read it now unless it may wait
        if self._inflight is not None and not (
                self._step_dispatches and self._may_defer(len(groups))):
            self._collect()
        did = bool(self._step_dispatches or self._step_readbacks)
        with phase("digest"):
            self._step_experts = self._fold_expert_rows()
            self._step_window["window_blocks_live"] = (
                self.wtables.size - len(self.free_wblocks))
            if self._step_restores:
                # budget-deferred admission: restores ran compiled
                # implant work this step even if no prefill/decode
                # dispatched — that IS progress (run()'s stall guard
                # must see it; the deferred head admits once the budget
                # catches up)
                did = True
            if did:
                self.steps += 1
                self._poison_uid = POISON_NONE  # one-step fault window
                active = sum(s is not None for s in self.slots)
                self._occ_sum += active / self.cfg.max_slots
                free = len(self.free_blocks)
                self._free_lo = min(self._free_lo, free)
                self._free_hi = max(self._free_hi, free)
            if did or self._step_events:
                # a dispatch-free step that only expired/shed requests
                # is still a scheduler decision the post-mortem needs
                digest = self._flight_digest()
                self.flight.append(digest)
                if self._inflight is not None:
                    # its rows' finite flags join this digest later
                    self._inflight.digest = digest
                self._step_events = []
            if self._dump_reason is not None:
                # a quarantine happened this step: dump now that the
                # step's own digest is in the ring ("the steps UP TO
                # the fault")
                self.dump_flight_recorder(self._dump_reason)
                self._dump_reason = None
        return did

    def _step_record(self, start_ns: int, end_ns: int) -> dict:
        """The executed step as ONE ``engine_step`` span record
        (telemetry v24): the parent span and its phases in the order
        they closed (each a child by being in this list), the step
        programs it launched (``dispatches``: the i-th entry belongs to
        the i-th ``*.dispatch`` phase) and the launches whose results
        it read (``readbacks``: the i-th entry the ordinal of the
        launch the i-th ``*.readback`` phase read, which may lie in an
        earlier step's record; ``launches`` counts the engine's
        launches up to and with this step's, so the record's own are
        the last ``len(dispatches)`` ordinals below it). The expert
        counters are those of the results READ; the cache reads'
        (``WINDOW_COUNTERS``, ``KV_COUNTERS``) of the rows LAUNCHED,
        with each store's bytes a position beside them (``ROW_BYTES``)
        and a recurrent layer's bytes a sequence (``STATE_ROW_BYTES``).
        ``tokens_generated`` is what a reader joins a step on."""
        return {
            "uid": None,
            "span": STEP_SPAN,
            "start_step": self.global_step,
            "step": self.global_step,
            "start_ns": start_ns,
            "end_ns": end_ns,
            "t": end_ns / 1e9,
            "duration_s": round((end_ns - start_ns) / 1e9, 6),
            "phases": self.phases.stamps,
            "tokens_generated": self.tokens_generated,
            "state_bytes": self._step_state_bytes,
            **self._step_experts,
            **self._step_window,
            **self._step_kv,
            **self.row_bytes,
            **self.state_row_bytes,
            "dispatches": self._step_dispatches,
            "readbacks": list(self._step_readbacks),
            "launches": self.launches,
        }

    def _fold_expert_rows(self) -> dict:
        """This step's expert counters (``EXPERT_COUNTERS``), over all
        the results it read (``readbacks``: they come back with the
        picks): ``expert_rows`` the (row, choice) pairs the held
        experts received (a bucket's padded rows route too: they are
        rows the device multiplied), ``experts_touched`` the experts
        that received at least one, summed over layers and dispatches
        (each is one expert's weights read), ``expert_rows_max`` the
        fullest expert's rows in any one layer of one dispatch. All 0
        for a model with no expert layer."""
        got = self._step_expert_rows
        if not got:
            return dict.fromkeys(EXPERT_COUNTERS, 0)
        rows = np.stack(got)
        return {"expert_rows": int(rows.sum()),
                "experts_touched": int(np.count_nonzero(rows)),
                "expert_rows_max": int(rows.max())}

    @property
    def active(self) -> int:
        """Slots taken. Never 0 while a launched result is unread: a
        sequence keeps its slot until its last token lands, and a read
        waits only while some resident sequence has a row to launch
        (``_may_defer``), so a loop on ``active or waiting`` makes the
        step that reads it."""
        return sum(s is not None for s in self.slots)

    def tenant_load(self) -> dict[str, int]:
        """Per-tenant LIVE request counts (waiting + resident; None
        tenants excluded) — the in-flight half of the per-tenant ops
        surface (schema v13): rides the handle digest so the fleet
        status doc's tenants block costs zero extra round-trips.
        O(slots + waiting) host work, empty dict single-tenant."""
        load: dict[str, int] = {}
        for seq in list(self.waiting) + [s for s in self.slots
                                         if s is not None]:
            if seq.tenant is not None:
                load[seq.tenant] = load.get(seq.tenant, 0) + 1
        return load

    def mean_occupancy(self) -> float:
        return self._occ_sum / self.steps if self.steps else 0.0

    def kv_pool_utilization(self) -> float:
        """Non-reclaimable fraction of the usable pool. refs-0 CACHED
        blocks count as free: the radix cache retains them off the
        free list, but admission reclaims them LRU on demand, so they
        are admissible capacity — without the correction a long-lived
        prefix-cached engine serving diverse prompts reads as
        permanently exhausted once the pool has cycled through the
        cache. The raw ``free_blocks`` keys keep their literal
        free-list meaning (the watermark window and churn math depend
        on it); ``prefix_evictable_blocks`` rides the record so the
        two readings reconcile."""
        usable = self.cfg.n_blocks - 1
        free = len(self.free_blocks)
        if self.prefix is not None:
            free += self.prefix.evictable_blocks()
        return (usable - free) / usable

    def window_pool_utilization(self) -> float:
        """Taken fraction of the window layers' pool (0.0 for a model
        with none); ``kv_pool_utilization`` is of the full kind's."""
        usable = self.wtables.size
        return (usable - len(self.free_wblocks)) / usable if usable else 0.0

    def live_tokens(self) -> int:
        """Cached positions currently holding real KV, summed over
        active slots. ``lengths[slot]`` only starts counting at prompt
        completion (the decode path's position clock), so a
        mid-prefill slot's written positions are its ``prefilled``
        count — take the max of the two clocks."""
        return sum(max(int(self.lengths[i]), s.prefilled)
                   for i, s in enumerate(self.slots) if s is not None)

    def kv_fragmentation(self) -> float:
        """Unused fraction of RESERVED block capacity: reserve-on-admit
        hands each request its whole block budget at admission, so a
        freshly-admitted long request 'holds' capacity it hasn't
        written yet. ``1 - live_tokens / (live_blocks * block_size)``;
        0.0 with nothing resident."""
        live_blocks = sum(len(s.blocks) for s in self.slots
                          if s is not None)
        if not live_blocks:
            return 0.0
        return 1.0 - self.live_tokens() / (
            live_blocks * self.capacity // self.cfg.max_blocks_per_seq)

    def kv_bytes_stored(self) -> int:
        """Live-token KV bytes at the engine's storage dtype — the
        measured form of the roofline's ``B * kv_bytes`` term."""
        return int(self.live_tokens() * self._kv_bytes_per_token())

    def _kv_bytes_per_token(self) -> float:
        spec, row = self.spec, self.spec.row
        return kv_bytes_per_token(self.cfg.kv_dtype, spec.kv_layers,
                                  row.heads, row.k_dim,
                                  latent=bool(spec.latent_rank),
                                  v_head_dim=row.v_dim)

    def telemetry_record(self, tokens_per_sec=None) -> dict:
        """One schema-v5 ``decode`` record (``runtime/telemetry.py``
        ``DECODE_REQUIRED`` contract; the reliability counters ride as
        extra keys). Reading a record CONSUMES the free-block watermark
        window: low/high water describe the span since the previous
        record (the cadence envelope), then reset to the instantaneous
        value."""
        self._collect()     # the counters are of landed tokens
        free = len(self.free_blocks)
        lo, hi = self._free_lo, self._free_hi
        self._free_lo = self._free_hi = free
        return {
            "step": self.global_step,
            "tokens_per_sec": tokens_per_sec,
            "batch_occupancy": round(self.active / self.cfg.max_slots, 4),
            "kv_pool_utilization": round(self.kv_pool_utilization(), 4),
            # extra: the window layers' pool (0.0 for a model with none)
            "window_pool_utilization": round(
                self.window_pool_utilization(), 4),
            "free_blocks": free,
            "free_blocks_low_water": lo,
            "free_blocks_high_water": hi,
            "block_allocs": self.block_allocs,
            "block_frees": self.block_frees,
            "block_scrubs": self.block_scrubs,
            "kv_fragmentation": round(self.kv_fragmentation(), 4),
            "kv_bytes_stored": self.kv_bytes_stored(),
            "active": self.active,
            "waiting": len(self.waiting),
            "tokens_generated": self.tokens_generated,
            "kv_dtype": self.cfg.kv_dtype,
            # extra (v11): which weights version new admissions take —
            # a deploy shows up as this stepping between records
            "serving_version": self.serving_version,
            "compiled_programs": self.compile_count,
            "drafted_tokens": self.drafted_tokens,
            "accepted_tokens": self.accepted_tokens,
            "accept_rate": (round(self.accepted_tokens
                                  / self.drafted_tokens, 4)
                            if self.drafted_tokens else None),
            # v7 shared-prefix keys: cumulative admission hits / prompt
            # tokens skipped / CoW triggers (0 = the write-barrier
            # invariant held), plus the INSTANTANEOUS count of blocks
            # named by >= 2 live tables right now
            "prefix_hit_blocks": self.prefix_hit_blocks,
            "prefill_tokens_saved": self.prefill_tokens_saved,
            "shared_blocks": (0 if self.prefix is None
                              else self.prefix.shared_blocks()),
            "cow_copies": self.cow_copies,
            # extras (not required keys): the hit-rate pair's
            # denominator, the cached-block inventory, and the prefill
            # dispatch count the ~1-prefill property is proved on
            "prefix_lookup_blocks": self.prefix_lookup_blocks,
            "prefix_hit_rate": (round(self.prefix_hit_blocks
                                      / self.prefix_lookup_blocks, 4)
                                if self.prefix_lookup_blocks else None),
            "prefix_cached_blocks": (0 if self.prefix is None
                                     else len(self.prefix)),
            # reclaimable retention right now — what reconciles the
            # literal free_blocks keys with kv_pool_utilization's
            # cached-blocks-are-free reading
            "prefix_evictable_blocks": (0 if self.prefix is None
                                        else
                                        self.prefix.evictable_blocks()),
            "prefill_dispatches": self.prefill_dispatches,
            # extra: ... of which the chunk rode with the decode batch
            "mixed_dispatches": self.mixed_dispatches,
            # extra (v22; v24 the rings'): the blocks the decode-side
            # reads fetched, beside the capacity a gather of every
            # launched row's table reads (``KV_COUNTERS``, cumulative)
            "kv_blocks_read": self.kv_blocks_read,
            "kv_blocks_capacity": self.kv_blocks_capacity,
            "ring_blocks_read": self.ring_blocks_read,
            "ring_blocks_capacity": self.ring_blocks_capacity,
            # extra (v24, v25, additive): a cached position's bytes in
            # one layer of each store (``ROW_BYTES``) and a sequence's
            # in one recurrent layer (``STATE_ROW_BYTES``)
            **self.row_bytes,
            **self.state_row_bytes,
            # v17 KV-memory-hierarchy keys (pinned): demotion volume
            # (cumulative blocks + wire bytes), promotion wins
            # (restores, the prompt tokens they kept off the prefill
            # path, the host wall-clock they cost — the budgeted
            # stall term), sub-block partial hits, and the host tier's
            # instantaneous occupancy fraction (0.0 with the tier off)
            "spilled_blocks": self.spilled_blocks,
            "spill_bytes": self.spill_bytes,
            "restores": self.restores,
            "restore_tokens_saved": self.restore_tokens_saved,
            "restore_stall_s": round(self.restore_stall_s, 6),
            "partial_hits": self.partial_hits,
            "host_tier_utilization": (
                round(self.spill.utilization(), 4)
                if self.spill is not None else 0.0),
            # extra: the tier's instantaneous entry count (occupancy's
            # numerator — what fleetstat renders beside the pool line)
            "spill_tier_blocks": (0 if self.spill is None
                                  else len(self.spill)),
            "quarantined": self.quarantined,
            "retried": self.retried,
            "preempted": self.preempted,
            "rejected": self.rejected,
            "expired": self.expired,
        }

    # -- flight recorder (DESIGN.md section 17) ------------------------

    def _flight_digest(self) -> dict:
        """One per-executed-step scheduler digest for the bounded ring:
        what the scheduler decided (this step's request events), what
        it dispatched (prefill uid / decode uids), what came back (the
        per-row finite flags), and the pool pressure at step end."""
        return {
            "step": self.global_step,
            "t": round(time.time(), 4),
            "events": list(self._step_events),
            "prefill_uid": self._step_prefill_uid,
            "decode_uids": list(self._step_decode_uids),
            # cumulative: steps whose chunk rode with the decode batch
            "mixed_dispatches": self.mixed_dispatches,
            # the finite flags of the rows this step LAUNCHED, in launch
            # order; those of a result still unread join when it lands
            "finite": self._step_finite,
            "slots": [None if s is None else
                      {"uid": s.uid, "pos": int(self.lengths[i]),
                       "blocks": len(s.blocks)}
                      for i, s in enumerate(self.slots)],
            "occupancy": round(self.active / self.cfg.max_slots, 4),
            "free_blocks": len(self.free_blocks),
            "waiting": len(self.waiting),
            # the recurrent state beside the blocks: rows that hold a
            # resident sequence's state at step end, and the bytes this
            # step's decode dispatches read of it (0 / 0 for a model
            # with no recurrent layer)
            "state_slots": self.active if self.state is not None else 0,
            "state_bytes": self._step_state_bytes,
            # the expert layers' counters of this step's dispatches
            # (0 for a model with no expert layer)
            **self._step_experts,
            # the cache reads of the rows launched and the window
            # blocks' turnover (0 for a model with no window layer)
            **self._step_window,
            # the pool's blocks the launched rows' reads fetched, and
            # the capacity a gather of their tables reads
            **self._step_kv,
            # where the step's host time went up to this digest
            # (runtime/tracing.py PhaseTimer): what an UNTRACED run's
            # ring says about a slow step
            "phase_ms": self.phases.phase_ms(),
            # the step programs those ``*.dispatch`` phases launched,
            # ``[kind, bucket]`` in order, and the launches (by ordinal)
            # those ``*.readback`` phases read
            "dispatches": self._step_dispatches,
            "readbacks": list(self._step_readbacks),
        }

    def dump_flight_recorder(self, reason: str) -> str | None:
        """Atomically persist the digest ring as ``flight_recorder.json``
        next to the metrics stream (or ``self.flight_dir``) via
        ``runtime/wire.py``'s publish discipline (tmp + fsync + rename
        + dir fsync — one implementation for checkpoints, snapshots,
        wire docs, and this dump). Called on quarantine (engine),
        watchdog latch and chaos kill (supervisor). Returns the path,
        or None when the engine has nowhere to put it (no metrics dir,
        no explicit flight_dir)."""
        self._collect()     # the last digest's own finite flags
        out_dir = self.flight_dir
        if out_dir is None and self.metrics is not None:
            out_dir = os.path.dirname(self.metrics.path)
        if out_dir is None:
            return None
        from ..runtime.wire import publish_json
        os.makedirs(out_dir, exist_ok=True)
        doc = {"version": 1, "reason": reason,
               "step": self.global_step, "t": time.time(),
               "kv_dtype": self.cfg.kv_dtype,
               "max_slots": self.cfg.max_slots,
               "n_blocks": self.cfg.n_blocks,
               "digests": list(self.flight)}
        return publish_json(os.path.join(out_dir, FLIGHT_FILENAME),
                            doc)

    # -- static cost attribution (DESIGN.md section 17) ----------------

    def decode_static_report(self, bucket: int | None = None) -> dict:
        """Compile-time attribution of one decode-step program (the
        largest slot bucket by default): a ``runtime.telemetry
        StepReport`` (XLA cost_analysis + lowered collective counts +
        compiled memory) over the REAL program body, cross-checked
        against the hand-side KV accounting — ``kv_pool_bytes`` (the
        device truth, ``paged.pool_bytes``) must equal
        ``kv_bytes_per_token * n_blocks * block_size`` (the DECODE
        roofline's per-dtype prediction) exactly, or the roofline
        prices a layout the engine doesn't run. Lowering is AOT and
        donation-free; the serving program set is untouched."""
        from ..runtime.telemetry import StepReport
        b = self.slot_buckets[-1] if bucket is None else bucket
        if b not in self.slot_buckets:
            raise ValueError(f"bucket {b} not in the engine's slot "
                             f"buckets {self.slot_buckets}")
        z = np.zeros((b,), np.int32)
        operand = self.programs.pack(
            "decode", b, tables=np.full((b, self.cfg.max_blocks_per_seq),
                                        SCRATCH_BLOCK),
            lengths=z, tokens=z, uids=z, poison=POISON_NONE, rows=z,
            **({"wtables": np.full((b, self.wtables.shape[1]),
                                   SCRATCH_BLOCK)}
               if self.windowed else {}))
        rep = StepReport.of(self.programs.body("decode", b), self.params,
                            self._carry(), operand)
        per_tok = self._kv_bytes_per_token()
        kv_bytes, scale_bytes = pool_bytes(self.pool)
        return {
            "slot_bucket": b,
            "kv_dtype": self.cfg.kv_dtype,
            "step_report": rep.as_dict(),
            "kv_bytes_per_token": int(per_tok),
            "kv_pool_bytes": kv_bytes,
            "kv_pool_bytes_predicted": int(
                per_tok * self.cfg.n_blocks * self.cfg.block_size),
            "kv_scale_bytes": scale_bytes,
        }

    def run(self, metrics=None, log_every: int = 0, before_step=None,
            after_step=None) -> dict[int, list[int]]:
        """Drain the queue: step until every submitted sequence finished
        (or failed). ``metrics`` is a ``TelemetryWriter`` (defaults to
        the constructor's — request lifecycle records flow there either
        way); one ``decode`` record lands every ``log_every`` engine
        steps (0 = final only), with throughput measured between records
        (host wall clock, device-synced by the per-step readback of the
        picks). ``before_step(next_local_step)`` /
        ``after_step(local_step)`` are the supervisor's hooks
        (``decode/supervise.py``): chaos injection before, watchdog +
        snapshot + kill after — hook exceptions propagate (the
        supervisor's restart ladder owns them)."""
        if metrics is not None:
            self.metrics = metrics
        metrics = self.metrics
        last_t = time.perf_counter()
        last_tokens = self.tokens_generated
        last_step = self.steps
        while self.waiting or self.active:
            if before_step is not None:
                before_step(self.steps + 1)
            if not self.step():
                # a step may legitimately run no compiled work when it
                # only expired/failed requests — re-check the loop
                # condition before calling it a stall. The after_step
                # hook still fires so the supervisor's final snapshot
                # reflects the expiries (a stale snapshot would resume
                # the dead uids and double-count their records).
                if self.waiting or self.active:
                    raise RuntimeError("decode engine stalled: waiting "
                                       "requests but no admissible work")
                if after_step is not None:
                    after_step(self.steps)
                break
            if after_step is not None:
                after_step(self.steps)
            if (metrics is not None and log_every > 0
                    and self.steps - last_step >= log_every):
                now = time.perf_counter()
                dt = max(now - last_t, 1e-9)
                tps = (self.tokens_generated - last_tokens) / dt
                metrics.decode(self.telemetry_record(round(tps, 2)))
                last_t, last_tokens = now, self.tokens_generated
                last_step = self.steps
        self._collect()     # a hook may have emptied the loop's condition
        if metrics is not None:
            now = time.perf_counter()
            dt = max(now - last_t, 1e-9)
            tps = ((self.tokens_generated - last_tokens) / dt
                   if self.tokens_generated > last_tokens else None)
            metrics.decode(self.telemetry_record(
                round(tps, 2) if tps is not None else None))
        return dict(self.finished)

    def generate(self, prompts, max_new: int, metrics=None,
                 log_every: int = 0) -> list[list[int] | None]:
        """Convenience batch API: submit every prompt, drain, return
        full token lists in submission order. A request that FAILED
        terminally (quarantine budget exhausted, deadline expiry)
        yields ``None`` in its position — the reason is in
        ``self.failed[uid]`` — and so does one SHED at the door by
        ``queue_limit`` (the ``rejected`` counter/event records it);
        malformed prompts still raise ``ValueError``."""
        uids = []
        for p in prompts:
            try:
                uids.append(self.submit(p, max_new))
            except AdmissionError:
                uids.append(None)
        done = self.run(metrics=metrics, log_every=log_every)
        return [None if u is None else done.get(u) for u in uids]
