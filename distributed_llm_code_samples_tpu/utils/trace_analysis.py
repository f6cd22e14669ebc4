"""Chrome-trace span analysis, keyed on the framework's named scopes.

A span parser (comm-vs-compute interval intersection over a captured
Perfetto/chrome trace) as an importable library, with the **named-scope
region map**: every parallel strategy annotates its step with
``jax.named_scope`` regions (see ``SCOPES`` below), those names flow
into XLA op metadata and — on hardware traces — into the span names the
profiler records, so a trace can be folded per region (how long did
``fsdp``'s ``comm`` spend vs its ``fwd``?) with plain substring
matching instead of op-name archaeology.

Naming map (the contract tests/test_telemetry.py pins against compiled
HLO): each strategy wraps its step in a scope named after the strategy,
with nested ``fwd`` / ``bwd`` / ``comm`` / ``optim`` regions. Autodiff
strategies (the LM/MoE families) trace forward and derive the backward,
so their ``fwd`` scope also tags the transposed backward ops — their
region list omits ``bwd`` rather than pretend a boundary exists.
"""

from __future__ import annotations

import glob
import gzip
import json
import os

# strategy -> the named-scope region paths its compiled step carries
# (each appears verbatim in compiled-HLO op metadata; presence is
# contract-tested per strategy against the REAL launched program in
# tests/test_telemetry.py). Nested paths record structure: DDP's grad
# psum fires inside the backward walk (ddp/bwd/comm), FSDP gathers in
# both directions (fsdp/{fwd,bwd}/comm). The pipeline's stage compute
# runs inside lax.cond branches whose sub-computations don't inherit
# the outer pp scope, so its fwd/bwd regions are unprefixed; the ring
# transfers and update are top-level (pp/comm, pp/optim). The LM family
# differentiates with jax.grad (one trace for forward + transpose), so
# its fwd region covers both and no bwd region exists.
SCOPES = {
    "single": ("single/fwd", "single/bwd", "single/optim"),
    "ddp": ("ddp/fwd", "ddp/bwd", "ddp/bwd/comm", "ddp/optim"),
    "fsdp": ("fsdp/fwd", "fsdp/bwd", "fsdp/fwd/comm", "fsdp/bwd/comm",
             "fsdp/optim"),
    "tp": ("tp/fwd", "tp/bwd", "tp/fwd/comm", "tp/bwd/comm", "tp/optim"),
    "hybrid": ("hybrid/fwd", "hybrid/bwd", "hybrid/fwd/comm",
               "hybrid/optim"),
    "zero1": ("zero1/fwd", "zero1/bwd", "zero1/comm", "zero1/optim"),
    "pp": ("pp/", "fwd", "bwd", "pp/comm", "pp/optim"),
    "seq": ("seq/fwd", "seq/bwd", "seq/comm", "seq/optim"),
    "ep": ("ep/fwd", "ep/bwd", "ep/comm", "ep/optim"),
    "tf": ("tf/fwd", "tf/bwd", "tf/optim"),
    "lm": ("lm/fwd", "lm/comm", "lm/optim"),
    "moe_lm": ("moe_lm/fwd", "moe_lm/comm", "moe_lm/optim"),
    "moe_tf": ("moe_tf/fwd", "moe_tf/bwd", "moe_tf/comm",
               "moe_tf/optim"),
    # serving cost attribution (round 11, decode/engine.py): the decode
    # engine's two compiled program kinds, split by the DECODE
    # roofline's own terms — "gather" the paged-KV block read (+int8
    # dequant), "requant" the KV write (the int8 read-modify-requantize
    # proper; at f32/bf16 it tags the plain scatter, so the region
    # reads near zero there), "attn" the score+AV math, "head" the
    # final LN + tied head (+ the TP logits all_gather), "sample" the
    # fused in-graph pick. Serving steps have no optimizer, so these
    # entries carry no "optim" region (the training-side four-role
    # structure does not apply).
    "decode": ("decode/gather", "decode/attn", "decode/head",
               "decode/sample", "decode/requant"),
    "prefill": ("prefill/gather", "prefill/attn", "prefill/head",
                "prefill/sample", "prefill/requant"),
}

# the SCOPES keys that name SERVING programs (no optimizer region; the
# per-strategy four-role contract below applies to the training keys)
SERVING_SCOPES = ("decode", "prefill")

# span-name keywords (lowercased substring match)
COMM_KEYWORDS = ("all-gather", "all_gather", "reduce-scatter",
                 "reduce_scatter", "all-reduce", "all_reduce",
                 "copy-start", "collective-permute", "dma")
COMPUTE_KEYWORDS = ("fusion", "dot", "convolution", "matmul")


def load_spans(trace_dir: str):
    """``(trace_file, spans)``: all complete ("X"-phase, named) events
    from the NEWEST chrome trace under ``trace_dir`` (recursive;
    ``jax.profiler.trace`` nests runs in timestamped subdirs).
    ``(None, [])`` when no trace exists."""
    files = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.trace.json.gz"), recursive=True),
        key=os.path.getmtime)
    if not files:
        return None, []
    with gzip.open(files[-1], "rt") as f:
        events = json.load(f).get("traceEvents", [])
    return files[-1], [e for e in events
                       if e.get("ph") == "X" and e.get("name")]


def classify_span(name: str) -> str | None:
    """"comm" / "compute" / None for one span name."""
    low = name.lower()
    if any(k in low for k in COMM_KEYWORDS):
        return "comm"
    if any(k in low for k in COMPUTE_KEYWORDS):
        return "compute"
    return None


def comm_compute_overlap(spans) -> tuple[int, int, float]:
    """``(n_comm, n_compute, overlap_us)``: per-lane comm-vs-compute
    interval intersection — observed overlap is the measured form of
    the async-pair proof (``utils/hlo.count_async_pairs``).

    ``overlap_us`` sums the intersection of every (comm, compute) pair
    in the same lane — pair multiplicity included, like the original
    bench_trace fold. Computed by an event sweep (the integral of
    ``active_comm(t) * active_compute(t)`` equals the pairwise sum), so
    real hardware traces with 1e4-1e5 spans fold in O(n log n) instead
    of the lifted loop's O(n_comm * n_compute)."""
    from collections import defaultdict

    events: dict = defaultdict(list)  # pid -> (t, which, +-1)
    n_comm = n_compute = 0
    for e in spans:
        cls = classify_span(e["name"])
        if cls is None:
            continue
        t0, t1 = e["ts"], e["ts"] + e.get("dur", 0)
        which = 0 if cls == "comm" else 1
        n_comm += which == 0
        n_compute += which == 1
        events[e.get("pid")].append((t0, which, 1))
        events[e.get("pid")].append((t1, which, -1))
    overlap_us = 0.0
    for evs in events.values():
        evs.sort()
        active = [0, 0]
        prev_t = None
        for t, which, d in evs:
            if prev_t is not None and t > prev_t:
                overlap_us += (t - prev_t) * active[0] * active[1]
            active[which] += d
            prev_t = t
    return n_comm, n_compute, overlap_us


def strategy_scope_key(trainer_name: str | None) -> str | None:
    """Map a trainer function name (the ``strategy`` field run meta
    records carry, e.g. ``train_lm_tp``) to its ``SCOPES`` key, or None
    when unknown."""
    if not trainer_name:
        return None
    name = trainer_name.removeprefix("train_")
    if name in SCOPES:
        return name
    # longest/most-specific prefixes first: *_seq trainers scope "seq"
    # (transformer_seq) or "lm" (lm_seq — the LM wraps its own step),
    # *_pp trainers all scope "pp"
    for prefix, key in (("moe_lm", "moe_lm"), ("moe_transformer", "moe_tf"),
                        ("moe", "ep"), ("lm_pp", "pp"),
                        ("transformer_pp", "pp"), ("pp", "pp"),
                        ("transformer_seq", "seq"),
                        ("lm", "lm"), ("transformer", "tf"),
                        ("ddp_zero1", "zero1"), ("tp", "tp")):
        if name.startswith(prefix):
            return key
    return None


def scope_totals(spans, strategy: str | None = None) -> dict[str, float]:
    """Total span time (us) per named-scope region.

    With ``strategy`` given, buckets are that strategy's ``SCOPES``
    entries; otherwise every strategy's PREFIXED regions are scanned —
    the pipeline's unprefixed ``fwd``/``bwd`` (a lax.cond scoping
    artifact, see SCOPES) are excluded there because they substring-
    match every strategy's scoped spans and would double-count. A span
    counts toward a region when the region name appears in the span
    name (XLA op metadata carries the full scope path; profilers that
    surface ``tf_op``/op_name annotations put it in the span name)."""
    regions = (SCOPES.get(strategy, ()) if strategy is not None
               else tuple({r for rs in SCOPES.values() for r in rs
                           if "/" in r}))
    totals = {r: 0.0 for r in regions}
    for e in spans:
        name = e["name"]
        args = e.get("args") or {}
        # profilers stash the op path under args too (tf_op / long_name)
        haystack = " ".join([name, str(args.get("tf_op", "")),
                             str(args.get("long_name", ""))])
        for r in regions:
            if r in haystack:
                totals[r] += e.get("dur", 0)
    return totals


def overlap_payload(spans, trace_file: str | None = None) -> dict:
    """The shared span-inventory + overlap fold (bench_trace's artifact
    core and the report tool's profile section). Takes already-loaded
    ``spans`` so callers that also need ``scope_totals`` parse the
    (potentially hundreds-of-MB) trace exactly once."""
    n_comm, n_compute, overlap_us = comm_compute_overlap(spans)
    return {
        "trace_file": trace_file,
        "n_spans": len(spans),
        "comm_spans": n_comm,
        "compute_spans": n_compute,
        "overlap_us": round(overlap_us, 1),
    }
