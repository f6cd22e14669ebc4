"""The routed experts and the gated short convolutions inside the decode
program, from the trace; and the expert layers' counters. The reader of
``configs/lfm2-24b-a2b-serve.json`` (``models/lfm2_moe_lm.py``):
``moe_trace.sizes`` reads another family's keys (``n_routed_experts``,
``first_k_dense_replace``, ``n_shared_experts``, the latent row),
``ssm_trace`` reads ``mamba_*``, and neither is edited; what they share
with this reader (``_ops_in``, ``decode_counters``) is imported.

- the PROGRAM's counters (``expert_rows``, ``experts_touched``,
  ``expert_rows_max``, ``state_bytes`` in its ``engine_step`` records),
  over the traced steps that dispatched ONE decode batch and no prefill
  chunk (a step's record sums its chunk's and its batch's). A program
  that writes none — a commit before this family, another family —
  gives every reader here nothing to read: they return None.
- which ``jit_run`` events are DECODE dispatches, by what the event
  itself shows and never by the host span that holds it (the device
  plane leads the host plane by a varying 0.1-1.2 ms and the join by
  span swaps the two programs: PERF.md section 7). Either of two marks
  of the decode program's own ops: a kernel call whose result holds a
  batch's rows ``[b, 1, d]`` (``ops/ssm.py::conv_step_in_place``, one a
  convolution layer; the prefill chunk's convolution is plain ops), or
  a K/V gather over every row's table, ``[b * blocks, block, H_kv *
  dh]`` covering two slots' positions or more (a chunk gathers its ONE
  slot's). A decode batch of one row gathers what a chunk gathers but
  still calls the kernel.
- the device time, inside the decode events, of the ops of the two
  mechanisms, told by the shapes in the HLO instruction text the
  profiler names an op by — its RESULT and its OPERANDS (the down
  product over the experts is fused with the residual add: its result is
  ``f32[b, d]`` like every layer's, its operands are the activations
  ``[b, E, F]`` and the stack ``[L_e, E, d, F]``). Sizes from the
  configuration's published keys:

  * routed experts: a shape ``[.., E, F]`` (the gate and up products of
    every held expert over every row, the gated activation, the down
    product's operand), ``[.., E, F, d]`` / ``[.., E, d, F]`` (the
    stacks), ``[L_e, E, d]`` (the router's matrix), ``[b, E]`` (scores,
    the sort, the gates), ``[b, top_k]`` / ``[b, top_k, 1]`` (the
    choice and its weights);
  * gated convolution: ``[b, 3d]`` (the ``W_in`` product; as an operand
    the ``B * X`` product and the ``C * v`` / ``W_out`` fusion), ``[b,
    1, d]`` (the kernel's rows in and out) and ``[L_c, slots+1, 1,
    (K-1) d]`` (the store of tails: the kernel call, and the
    compiler's staging of it through its fast memory).
"""

from __future__ import annotations

import re

from . import engine_trace, xplane
from .moe_trace import _ops_in, _result, decode_counters

# where an instruction's result and operand list end and its attributes
# begin
ATTRS = re.compile(r"\), [a-z_]+=")


def sizes(ctx: dict) -> dict:
    """The shapes' numbers, from the configuration's published keys and
    the engine's capacity."""
    config = ctx["cell"]["config"]
    serving = config["serving"]
    heads = int(config["num_attention_heads"])
    d = int(config["hidden_size"])
    dh = int(config.get("head_dim") or d // heads)
    types = config["layer_types"]
    return {
        "d": d, "d3": 3 * d,
        "experts": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "ffn": int(config["moe_intermediate_size"]),
        "expert_layers": (int(config["num_hidden_layers"])
                          - int(config["num_dense_layers"])),
        "conv_layers": sum(t == "conv" for t in types),
        "taps": int(config["conv_L_cache"]),
        "tail": (int(config["conv_L_cache"]) - 1) * d,
        "kv_row": int(config["num_key_value_heads"]) * dh,
        "positions": int(serving["max_positions"]),
    }


def _head(name: str) -> str:
    """An instruction's result and operands, without its attributes."""
    m = ATTRS.search(name)
    return name[:m.start() + 1] if m else name


def routed_op(z: dict):
    pat = re.compile(
        r"\[(\d+,)+%(experts)d,%(ffn)d(,%(d)d)?\]|\[\d+,%(experts)d,%(d)d(,%(ffn)d)?\]"
        r"|\[\d+,%(experts)d\]|\[\d+,%(top_k)d(,1)?\]" % z)
    return lambda name: bool(pat.search(_head(name)))


def conv_op(z: dict):
    pat = re.compile(r"\[\d+,%(d3)d\]|\[\d+,1,%(d)d\]|\[\d+,\d+,1,%(tail)d\]"
                     % z)
    return lambda name: bool(pat.search(_head(name)))


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
    gather = re.compile(r"\[(\d+),(\d+),%(kv_row)d\]" % z)
    rows_out = re.compile(r"\[\d+,1,%(d)d\]" % z)
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
        k, rows, kernel = j, 0, False
        while k < len(ops) and ops[k][1] < start + dur:
            res = _result(ops[k][0])
            if " custom-call(" in ops[k][0] and rows_out.search(res):
                kernel = True
            for blocks, block in gather.findall(res):
                rows = max(rows, int(blocks) * int(block))
            k += 1
        if kernel or (rows >= 2 * z["positions"]
                      and rows % z["positions"] == 0):
            out.append((start, start + dur))
    return out or None


def decode_ms(ctx: dict):
    """Mean device milliseconds of a decode dispatch (``decode_events``)."""
    spans = decode_events(ctx)
    if not spans:
        return None
    return sum(b - a for a, b in spans) / len(spans) / 1e6


def part_ops(ctx: dict, which: str, spans: list | None = None):
    """``{label: [seconds, count]}`` of one mechanism's ops (``"routed"``
    or ``"conv"``) inside the decode events (``spans``:
    ``decode_events(ctx)``), or None."""
    spans = spans or decode_events(ctx)
    if not spans:
        return None
    keep = {"routed": routed_op, "conv": conv_op}[which](sizes(ctx))
    red = ctx["trace"]
    return _ops_in(red["trace"], xplane.device_planes(red["trace"])[0],
                   spans, keep)


def part_ms(ctx: dict, which: str):
    """Device milliseconds a decode dispatch spends in one mechanism's
    ops, or None (also where the program wrote no expert counter: a
    commit before this family)."""
    if counters(ctx) is None:
        return None
    spans = decode_events(ctx)
    ops = part_ops(ctx, which, spans)
    if not ops:
        return None
    return 1e3 * sum(v[0] for v in ops.values()) / len(spans)


def counters(ctx: dict) -> dict | None:
    """Means of the program's counters over the traced steps that
    dispatched ONE decode batch and no prefill chunk: the experts'
    (``moe_trace.decode_counters``) and ``state_bytes``, what that batch
    read of the convolutions' tails. None where there is none."""
    got = decode_counters(ctx)
    if got is None:
        return None
    from . import engine_phases
    recs = engine_phases.traced_records(ctx)
    steps = ctx["values"]["traced_steps"]
    state = [r.get("state_bytes", 0) for r, st in zip(recs, steps)
             if st.n_decode == 1 and not st.n_prefill
             and r.get("expert_rows")]
    return dict(got, state_bytes=sum(state) / len(state))


# -- the bytes a decode dispatch needs (kept with the benchmark) ----------


def expert_bytes(z: dict, itemsize: int = 2) -> int:
    """One routed expert's three matrices."""
    return 3 * z["d"] * z["ffn"] * itemsize


def routed_ffn_bytes(z: dict, experts_touched: float) -> float:
    """What the TIMED expert-layer ops have to read in one decode
    dispatch: the experts the counters say received a row, and per
    expert layer the float32 router and its choice bias."""
    return (experts_touched * expert_bytes(z)
            + z["expert_layers"] * 4 * z["experts"] * (z["d"] + 1))


def gated_conv_bytes(z: dict, state_bytes: float, itemsize: int = 2) -> float:
    """What the TIMED convolution ops have to move in one decode
    dispatch: every ready row's tails read AND written (``state_bytes``
    is what the program says it read), and the convolution mixers'
    weights once: ``W_in [3d, d]``, the taps ``[K, d]``, ``W_out [d,
    d]``."""
    d = z["d"]
    per_layer = (3 * d * d + z["taps"] * d + d * d) * itemsize
    return 2 * state_bytes + z["conv_layers"] * per_layer


def decode_step_bytes(z: dict, weight_bytes: int, experts_touched: float,
                      kv_bytes_per_token: float, live_tokens: float,
                      state_bytes: float) -> float:
    """One decode dispatch: the touched experts, every other leaf of
    ``decode_weight_bytes`` once, the live K/V rows once, the ready
    rows' tails read and written."""
    routed_all = z["expert_layers"] * z["experts"] * expert_bytes(z)
    return (weight_bytes - routed_all + experts_touched * expert_bytes(z)
            + kv_bytes_per_token * live_tokens + 2 * state_bytes)
