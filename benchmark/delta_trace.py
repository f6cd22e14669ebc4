"""The gated delta rule's state update and the chip's share of the
fine-grained experts inside the decode-side programs, from the trace:
the reader of ``configs/qwen3-next-80b-a3b-serve.json``
(``models/qwen3_next_lm.py``). ``ssm_trace.state_op`` reads another
family's keys and ONE width for the convolution and the state, and is
not edited; what this reader shares with the older ones (``_ops_in``,
``decode_counters``, ``_head``, ``share_op``, ``share_of_peak``,
``dispatch_join.decode_side_events``) is imported.

- the PROGRAM's counters in its ``engine_step`` records:
  ``state_bytes`` (the state rows the decode-side dispatches a step
  LAUNCHED read: each ready row's tail and matrix in every gated-delta
  layer, counted ONCE; a row is written back the same size, so the
  traffic is twice that), ``kv_blocks_read`` with ``kv_row_bytes`` (the
  full layers' live blocks, over all of them, and a position's bytes in
  one) and the experts' three (of the results a step READ). A program
  that writes no ``state_bytes`` with its row bytes beside them — the
  parent commit, a family with no recurrent layer — gives every reader
  here nothing to read: they return None.
- which ``jit_run`` events are DECODE-side dispatches (the ``decode``
  and the ``mixed`` program), by ORDINAL (``dispatch_join.decode_side``)
  and by nothing a program's ops show.
- the device time, inside those events, of the ops of each mechanism,
  told by the shapes in the HLO instruction text the profiler names an
  op by, its RESULT and its OPERANDS (sizes from the cell's own
  configuration file and the engine's capacity, never a family's name):

  * the delta rule: the two kernels a gated-delta layer, ``custom-call``
    s handed and handing back the state's stores WHOLE (``f32[L_d, S,
    d_k, D]`` the matrices, ``f32[L_d, S, 1, (K-1) C]`` the tails), and
    what stands between them and touches the state: the kernels' row
    results ``[b, 1, C]`` / ``[b, 1, D]``, the three rows a batch row
    brings to the delta kernel ``[b, 3, D]`` (``v``, ``exp(g)``,
    ``beta``) and its heads' key and query columns ``[b, tiles, d_k, 2
    heads]``;
  * the share of the experts: a shape ``[.., E, F]`` / ``[.., E, F, d]``
    / ``[.., E, d, F]`` over the ``E`` HELD experts, the router over all
    ``R`` published ones (``[L, R, d]``, ``[b, R]``), the choice (``[b,
    top_k]``, ``[b, top_k, 1]``, ``[b, top_k, E]``, ``[b, E]``) and the
    shared expert's stacks ``[L, F_s, d]`` / ``[L, d, F_s]``; NOT an op
    that shows the full layers' store or a delta layer's stack beside
    such a shape (a K row is 512 lanes, the router's width).
"""

from __future__ import annotations

import re

from . import dispatch_join, engine_phases, harness, xplane
from .conv_moe_trace import _head
from .moe_trace import _ops_in, decode_counters
from .sink_window_trace import share_of_peak, share_op  # noqa: F401  (the first: the metrics')


def sizes(ctx: dict) -> dict:
    """The shapes' numbers, from the configuration's published keys and
    the engine's capacity (bytes come from the program: ``counters``)."""
    config = ctx["cell"]["config"]
    serving = config["serving"]
    cfg = harness.driver_module(config).engine_config(config)
    layers = int(config["num_hidden_layers"])
    h_k, h_v = (int(config[f"linear_num_{s}_heads"])
                for s in ("key", "value"))
    d_k, d_v = (int(config[f"linear_{s}_head_dim"])
                for s in ("key", "value"))
    lanes = h_v * d_v
    conv = 2 * h_k * d_k + lanes
    return {
        "block": cfg.block_size,
        "rows": int(serving["max_slots"]) + 1,  # the slots and the scratch
        "delta_layers": layers - layers // int(
            config["full_attention_interval"]),
        "key_dim": d_k, "value_dim": d_v, "lanes": lanes, "conv": conv,
        "tail": (int(config["linear_conv_kernel_dim"]) - 1) * conv,
        "value_heads": h_v,
        # the full layers' store, whose K row (2 x 256 lanes) is as wide
        # as the router (512): what ``fine_op`` has to tell apart
        "kv_layers": layers // int(config["full_attention_interval"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "d": int(config["hidden_size"]),
        "experts": int(config["num_experts"]),
        "routed": int(config.get("router_experts", config["num_experts"])),
        "top_k": int(config["num_experts_per_tok"]),
        "ffn": int(config["moe_intermediate_size"]),
        "shared_ffn": int(config["shared_expert_intermediate_size"]),
        "expert_layers": layers,
    }


def delta_op(z: dict):
    """Whether an op is one of the delta rule's: the module docstring
    has the shapes."""
    pat = re.compile(
        r"\[%(delta_layers)d,%(rows)d,(%(key_dim)d,%(lanes)d|1,%(tail)d)\]"
        r"|\[\d+,(1,%(conv)d|1,%(lanes)d|3,%(lanes)d)\]" % z)
    # the columns ``[b, tiles, d_k, 2 * heads a tile]``, whatever the tile
    cols = re.compile(r"\[\d+,(\d+),%(key_dim)d,(\d+)\]" % z)
    both = 2 * z["lanes"] // z["value_dim"]

    # ... but not a weight product that merely takes a kernel's result
    # (the gated norm fused into ``W_out``'s product)
    weights = re.compile(r"bf16\[%(delta_layers)d," % z)

    def keep(name: str) -> bool:
        head = _head(name)
        return not weights.search(head) and (
            bool(pat.search(head)) or any(
                int(t) * int(h) == both for t, h in cols.findall(head)))

    return keep


def fine_op(z: dict):
    """The share of the experts as ``sink_window_trace.share_op`` tells
    it (the held experts' products, the router, the choice) and the
    shared expert's stacks, less what the other mixers own and the
    generic shapes would take with them: here a K or V row is as wide
    as the router (2 x 256 = 512) and the delta layers' ``[b; alpha]``
    as the held experts (2 x 32 = 64), so an op that shows the full
    layers' pool, their K / V stacks, the walk's query, a KV head split,
    one of the delta layers' stacks or a ``[b, H_v]`` half is not the
    experts'."""
    routed = share_op(z)
    shared = re.compile(
        r"\[%(expert_layers)d,(%(shared_ffn)d,%(d)d|%(d)d,%(shared_ffn)d)\]"
        % z)
    kv_row = z["kv_heads"] * z["head_dim"]
    others = re.compile(
        r"\[%(kv_layers)d,(\d+,%(block)d,)?%(kv_row)d(,%(d)d)?\]"
        r"|\[\d+,%(heads)d,%(kv_row)d\]"
        r"|\[\d+,%(kv_heads)d,(1,)?%(head_dim)d\]"
        r"|\[%(delta_layers)d,|\[\d+,%(value_heads)d\]"
        % dict(z, kv_row=kv_row))

    def keep(name: str) -> bool:
        head = _head(name)
        return not others.search(head) and (
            routed(name) or bool(shared.search(head)))

    return keep


def decode_events(ctx: dict) -> list | None:
    """``[(start_ns, end_ns)]`` of the decode-side program events of the
    traced window, on the first device, by ordinal."""
    return dispatch_join.decode_side_events(ctx)


def counters(ctx: dict) -> dict | None:
    """Means of the program's counters over the traced steps that
    dispatched ONE decode batch and no prefill chunk: the state bytes,
    the full layers' blocks, the experts', and a cached position's
    bytes a layer. None where the program wrote no state bytes or no
    row bytes (the parent commit, another family)."""
    recs = engine_phases.traced_records(ctx)
    if recs is None:
        return None
    steps = ctx["values"]["traced_steps"]
    got = [r for r, st in zip(recs, steps)
           if st.n_decode == 1 and not st.n_prefill and r.get("state_bytes")
           and r.get("kv_row_bytes") and r.get("kv_blocks_read")]
    if not got:
        return None
    out = {k: sum(r[k] for r in got) / len(got)
           for k in ("state_bytes", "kv_blocks_read")}
    out["kv_row_bytes"] = got[0]["kv_row_bytes"]
    out.update(decode_counters(ctx) or {})
    return out


def decode_ms(ctx: dict):
    """Mean device milliseconds of a decode-side dispatch."""
    spans = decode_events(ctx)
    if not spans or counters(ctx) is None:
        return None
    return sum(b - a for a, b in spans) / len(spans) / 1e6


def part_ops(ctx: dict, which: str, spans: list | None = None):
    """``{label: [seconds, count]}`` of one mechanism's ops (``"delta"``
    or ``"fine"``) inside the decode-side events, or None."""
    if spans is None:
        spans = decode_events(ctx)
    if not spans:
        return None
    z = sizes(ctx)
    keep = {"delta": delta_op, "fine": fine_op}[which](z)
    red = ctx["trace"]
    return _ops_in(red["trace"], xplane.device_planes(red["trace"])[0],
                   spans, keep) or None


def part_ms(ctx: dict, which: str):
    """Device milliseconds a decode-side dispatch spends in one
    mechanism's ops, or None."""
    if counters(ctx) is None:
        return None
    spans = decode_events(ctx)
    ops = part_ops(ctx, which, spans)
    if not ops:
        return None
    return 1e3 * sum(v[0] for v in ops.values()) / len(spans)


# -- the bytes a decode dispatch needs (kept with the benchmark) ----------


def delta_rule_bytes(state_bytes: float) -> float:
    """What ANY update of the state has to move in one decode dispatch:
    every launched row's tail and matrix of every gated-delta layer
    READ once and WRITTEN once. ``state_bytes`` is the program's count
    of one of the two (``RecurrentState.bytes_per_slot`` a row)."""
    return 2.0 * state_bytes


def delta_rule_flops(z: dict, state_bytes: float) -> float:
    """The recurrence's arithmetic in one decode dispatch: seven
    operations an element of every launched row's matrix (the decay,
    two contractions of a product and a sum each, the rank-one write's
    product and sum) — under one operation a byte moved, so the update
    is bound by its bytes on a chip with 240 operations a byte of
    bandwidth and the roofline above is the memory one."""
    matrix = 4 * z["key_dim"] * z["lanes"]
    return 7.0 * (state_bytes * matrix / state_row_bytes(z)) / 4


def state_row_bytes(z: dict) -> int:
    """A sequence's bytes in ONE gated-delta layer, from the
    configuration's keys: the float32 matrix a value head and the
    convolution's float32 tail (what the program's ``state_bytes``
    counts a launched row a layer; ``tests/test_benchmark_yardsticks.py``
    holds the two together)."""
    return 4 * (z["key_dim"] * z["lanes"] + z["tail"])


def expert_bytes(z: dict, itemsize: int = 2) -> int:
    """One routed expert's three matrices."""
    return 3 * z["d"] * z["ffn"] * itemsize


def fine_ffn_bytes(z: dict, experts_touched: float,
                   itemsize: int = 2) -> float:
    """What the TIMED expert-layer ops have to read in one decode
    dispatch: the held experts the counters say received a row, once
    each, and per layer the float32 router over all published experts
    and the shared expert's three matrices."""
    per_layer = (4 * z["routed"] * z["d"]
                 + 3 * z["d"] * z["shared_ffn"] * itemsize)
    return (experts_touched * expert_bytes(z, itemsize)
            + z["expert_layers"] * per_layer)


def kv_bytes(z: dict, got: dict) -> float:
    """The full layers' live blocks, K and V, once: the program's blocks
    (over all its full layers) times a block's positions times the
    program's bytes a position a layer."""
    return got["kv_blocks_read"] * z["block"] * got["kv_row_bytes"]


def decode_step_bytes(z: dict, weight_bytes: int, got: dict) -> float:
    """One decode dispatch: the touched held experts, every other leaf
    of ``decode_weight_bytes`` once, the launched rows' state read and
    written, and the full layers' live blocks."""
    held_all = z["expert_layers"] * z["experts"] * expert_bytes(z)
    return (weight_bytes - held_all
            + got["experts_touched"] * expert_bytes(z)
            + delta_rule_bytes(got["state_bytes"]) + kv_bytes(z, got))
