"""The expert layers and the latent-cache read inside the decode
program, from the trace; and the expert layers' counters.

Three things are read here, for a latent-attention, sparse-expert model
(``configs/glm47-flash-serve.json``: ``models/mla_moe_lm.py``):

- the PROGRAM's counters: ``expert_rows``, ``experts_touched`` and
  ``expert_rows_max`` in its ``engine_step`` span records (what the step
  programs returned after their picks, folded over a step's dispatches).
  A program that writes none — an older commit, a model with no expert
  layer — gives every reader here nothing to read, and they return None.
  A step's counters cover its prefill chunk AND its decode batch, so
  what is said of a decode dispatch is read from the traced steps that
  dispatched a decode batch and no chunk.
- which ``jit_run`` program events are DECODE dispatches. Not by the
  step's span (``engine_trace.py`` counts a step's events inside its
  host span and calls the first ``n_prefill`` the prefill; the device
  plane leads the host plane by a varying 0.2-1.2 ms, and the two
  programs then swap names: PERF.md section 7), but by what the event
  itself shows: the ops that ran inside it. Every layer's cache read
  starts with a gather of the rows as stored, ``[blocks, block, m]``: a
  decode batch gathers EVERY row's table (``blocks * block`` is ``b``
  times the slots' positions, ``b`` the batch's rows), a prefill chunk
  its ONE slot's (exactly the positions). So an event is a decode
  dispatch where its largest such gather covers two slots' positions
  or more. (The scores' shape does not tell them apart: the compiler
  writes a chunk's as ``f32[c, heads, T]``, rows in front, like a
  batch's.) A decode batch of ONE row gathers what a chunk gathers and
  is not counted: no traced step of a full cell dispatches one.
- the device time, inside the decode events, of the ops of the two
  mechanisms, told by their result shapes in the HLO instruction text
  the profiler names them by (op events carry no scope trail;
  ``ssm_trace.py`` does the same for the recurrent state). Sizes from
  the configuration's published keys and the engine's capacity:

  * expert layers: results ``[b, E, F]`` (every held expert's gate and
    up products over every row, and the gated activation), ``f32[b, d,
    1]`` (the down product over all experts), ``[b, F_shared]`` (the
    shared expert's gate and up), ``[b, E]`` and ``[b, top_k]`` (the
    router, its sort and its weights). The shared expert's down product
    has the shape of attention's output projection (``f32[b, d]``) and
    is left out: 1/190 of the layer's bytes;
  * latent read: results ``[b * blocks, block, m]`` (the gather of every
    slot's rows as stored, ``m`` the stored row's lanes), ``[b, heads,
    positions]`` (scores, softmax) and ``[b, heads, m]`` (the weighted
    sum over the rows; the query's cast to the rows' type has the same
    shape and is counted with it).
"""

from __future__ import annotations

import re

from . import engine_phases, engine_trace, xplane

RESULT = re.compile(r"^%?\S+ = (?P<res>.*?)\s[a-z][a-z0-9\-]*\(")
COUNTERS = ("expert_rows", "experts_touched", "expert_rows_max")


def sizes(ctx: dict) -> dict:
    """The shapes' numbers, from the configuration and the run."""
    config = ctx["cell"]["config"]
    serving = config["serving"]
    item = {"bf16": 2, "f32": 4}[serving["kv_dtype"]]
    layers = int(config["num_hidden_layers"])
    return {
        "heads": int(config["num_attention_heads"]),
        "positions": int(serving["max_positions"]),
        "row": int(ctx["values"]["kv_bytes_per_token"]) // (layers * item),
        "d": int(config["hidden_size"]),
        "experts": int(config["n_routed_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "ffn": int(config["moe_intermediate_size"]),
        "shared_ffn": (int(config["n_shared_experts"])
                       * int(config["moe_intermediate_size"])),
        "expert_layers": layers - int(config["first_k_dense_replace"]),
    }


def _result(name: str) -> str:
    m = RESULT.match(name)
    return m.group("res") if m else ""


def moe_op(z: dict):
    pat = re.compile(
        r"\[\d+,%(experts)d,%(ffn)d\]|f32\[\d+,%(d)d,1\]|\[\d+,%(shared_ffn)d\]"
        r"|\[\d+,%(experts)d\]|\[\d+,%(top_k)d(,1)?\]" % z)
    return lambda name: bool(pat.search(_result(name)))


def latent_op(z: dict):
    pat = re.compile(r"\[\d+,\d+,%(row)d\]|\[\d+,%(heads)d,%(positions)d\]"
                     % z)
    return lambda name: bool(pat.search(_result(name)))


def _ops_in(trace: dict, plane: str, spans: list, keep) -> dict:
    """``{label: [seconds, count]}`` of the op events inside ``spans``
    (sorted ``[(start, end)]``) that ``keep(name)`` holds."""
    evs = sorted(trace["planes"][plane].get(xplane.OPS_LINE, []),
                 key=lambda e: e[1])
    out: dict = {}
    j = 0
    for a, b in spans:
        while j < len(evs) and evs[j][1] + evs[j][2] <= a:
            j += 1
        k = j
        while k < len(evs) and evs[k][1] < b:
            name, start, dur, scope = evs[k]
            if keep(name):
                acc = out.setdefault(xplane.op_kind(name, scope), [0.0, 0])
                acc[0] += (min(b, start + dur) - max(a, start)) / 1e9
                acc[1] += 1
            k += 1
    return out


def decode_events(ctx: dict) -> list | None:
    """``[(start_ns, end_ns)]`` of the program events of the traced
    window, on the first device, that are decode dispatches by their own
    ops (the module docstring says how)."""
    red = ctx.get("trace")
    if red is None:
        return None
    trace = red["trace"]
    planes = xplane.device_planes(trace)
    if not planes:
        return None
    z = sizes(ctx)
    gather = re.compile(r"\[(\d+),(\d+),%(row)d\]" % z)
    mods = sorted((e for e in trace["planes"][planes[0]].get(
        xplane.MODULES_LINE, [])
        if e[0].startswith(engine_trace.PROGRAM)
        and e[1] >= red["lo"] and e[1] + e[2] <= red["hi"]),
        key=lambda e: e[1])
    ops = sorted(trace["planes"][planes[0]].get(xplane.OPS_LINE, []),
                 key=lambda e: e[1])
    out, j = [], 0
    for _, start, dur, _ in mods:
        while j < len(ops) and ops[j][1] < start:
            j += 1
        k, rows = j, 0              # the largest gather's blocks * block
        while k < len(ops) and ops[k][1] < start + dur:
            for blocks, block in gather.findall(_result(ops[k][0])):
                rows = max(rows, int(blocks) * int(block))
            k += 1
        if rows >= 2 * z["positions"] and rows % z["positions"] == 0:
            out.append((start, start + dur))
    return out or None


def decode_ms(ctx: dict):
    """Mean device milliseconds of a decode dispatch (``decode_events``)."""
    spans = decode_events(ctx)
    if not spans:
        return None
    return sum(b - a for a, b in spans) / len(spans) / 1e6


def part_ops(ctx: dict, which: str) -> dict | None:
    """``{label: [seconds, count]}`` of one mechanism's ops (``"moe"`` or
    ``"latent"``) inside the decode events."""
    spans = decode_events(ctx)
    if not spans:
        return None
    z = sizes(ctx)
    keep = {"moe": moe_op, "latent": latent_op}[which](z)
    red = ctx["trace"]
    return _ops_in(red["trace"], xplane.device_planes(red["trace"])[0],
                   spans, keep)


def part_ms(ctx: dict, which: str):
    """Device milliseconds a decode dispatch spends in one mechanism's
    ops, or None (also where the program wrote no expert counter: the
    parent of PR 31, a model with no expert layer)."""
    if decode_counters(ctx) is None:
        return None
    ops = part_ops(ctx, which)
    if not ops:
        return None
    return 1e3 * sum(v[0] for v in ops.values()) / len(decode_events(ctx))


def decode_counters(ctx: dict) -> dict | None:
    """Means of the program's expert counters over the traced steps that
    dispatched ONE decode batch and no prefill chunk, or None."""
    recs = engine_phases.traced_records(ctx)
    if recs is None:
        return None
    steps = ctx["values"]["traced_steps"]
    got = [r for r, st in zip(recs, steps)
           if st.n_decode == 1 and not st.n_prefill
           and r.get("expert_rows")]
    if not got:
        return None
    return {k: sum(r[k] for r in got) / len(got) for k in COUNTERS}


# -- the bytes a decode dispatch needs (kept with the benchmark) ----------


def expert_bytes(z: dict, itemsize: int = 2) -> int:
    """One routed expert's three matrices."""
    return 3 * z["d"] * z["ffn"] * itemsize


def moe_ffn_bytes(z: dict, experts_touched: float) -> float:
    """What the TIMED expert-layer ops have to read in one decode
    dispatch: the experts the counters say received a row, and per
    expert layer the float32 router and the shared expert's gate and up
    (its down product is not among the timed ops)."""
    per_layer = (4 * z["experts"] * (z["d"] + 1)
                 + 2 * z["d"] * z["shared_ffn"] * 2)
    return (experts_touched * expert_bytes(z)
            + z["expert_layers"] * per_layer)


def moe_decode_step_bytes(z: dict, weight_bytes: int, experts_touched: float,
                          kv_bytes_per_token: float,
                          live_tokens: float) -> float:
    """One decode dispatch: the touched experts, every other leaf of
    ``decode_weight_bytes`` once, the live latent rows once."""
    routed_all = z["expert_layers"] * z["experts"] * expert_bytes(z)
    return (weight_bytes - routed_all + experts_touched * expert_bytes(z)
            + kv_bytes_per_token * live_tokens)
