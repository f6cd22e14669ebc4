"""The two kinds of attention read and the held experts inside the
decode-side programs, from the trace; and the counters of the cache
reads. The reader of ``configs/laguna-s-2.1-serve.json``
(``models/laguna_lm.py``): ``moe_trace.sizes`` and
``conv_moe_trace.sizes`` read other families' keys and neither is
edited; what they share with this reader (``_ops_in``,
``decode_counters``, ``_head``) is imported.

- the PROGRAM's counters in its ``engine_step`` records: ``window_rows``
  / ``full_rows`` (the cached positions the rows a step LAUNCHED attend
  over in a window layer and in a full one), ``window_blocks_live``, and
  the experts' three (of the results a step READ), over the traced
  steps that dispatched ONE decode batch and no prefill chunk. A program
  that writes none — a commit before this family, another family —
  gives every reader here nothing to read: they return None.
- which ``jit_run`` events are DECODE-side dispatches (the ``decode``
  and the ``mixed`` program), by what the event itself shows and never
  by the host span that holds it: a K/V gather over EVERY row's table,
  ``[b * blocks, block, H_kv * dh]`` (as this compiler writes it) or
  ``[b, blocks, block, H_kv * dh]``, covering two sequences' capacity
  or more (a prefill chunk gathers its ONE slot's). The pools
  themselves (``[L, n_blocks, block, row]``, the result of every
  in-place write) are no gather: their block count is no table's.
- the device time, inside those events, of the ops of each mechanism,
  told by the shapes in the HLO instruction text the profiler names an
  op by, its RESULT and its OPERANDS. ``T`` is the positions ONE row's
  gathered view holds — the sequence's capacity in a full layer, the
  ring (``entries * block``: the configuration's driver says how the
  engine sizes it) in a window layer — and ``H`` a kind's query heads:

  * an attention read: the gather ``[b * T / block, block, row]`` (or
    ``[b, T / block, block, row]``), the view ``[b, T, row]``, the
    scores and probabilities ``[b, H, T]``, the weighted sum over the
    stored rows ``[b, H, row]`` and the query laid out for them ``[b,
    row, H]``;
  * the held experts: a shape ``[.., E, F]`` / ``[.., E, F, d]`` / ``[..,
    E, d, F]`` over the ``E`` HELD experts, the router over all ``R``
    published ones (``[L_e, R, d]``, ``[b, R]``), the choice (``[b,
    top_k]``, ``[b, top_k, 1]``, ``[b, top_k, E]``, ``[b, E]``) and the
    shared expert's stacks ``[L_e, F_s, d]`` / ``[L_e, d, F_s]``.
"""

from __future__ import annotations

import re

from . import engine_phases, engine_trace, harness, xplane
from .conv_moe_trace import _head
from .moe_trace import _ops_in, _result, decode_counters

SHAPE = re.compile(r"\[(\d+(?:,\d+)*)\]")


def sizes(ctx: dict) -> dict:
    """The shapes' numbers, from the configuration's published keys and
    the engine's capacity."""
    config = ctx["cell"]["config"]
    serving = config["serving"]
    types = config["layer_types"]
    heads = dict(zip(types, config["num_attention_heads_per_layer"]))
    sut = harness.driver_module(config)
    block = sut.engine_config(config).block_size
    return {
        "block": block,
        "ring": (sut.window_pool_blocks(config) // int(serving["max_slots"])
                 * block),
        "d": int(config["hidden_size"]),
        "experts": int(config["num_experts"]),
        "routed": int(config.get("router_experts", config["num_experts"])),
        "top_k": int(config["num_experts_per_tok"]),
        "ffn": int(config["moe_intermediate_size"]),
        "shared_ffn": int(config["shared_expert_intermediate_size"]),
        "expert_layers": sum(m == "sparse"
                             for m in config["mlp_layer_types"]),
        "row": int(config["num_key_value_heads"]) * int(config["head_dim"]),
        "positions": int(serving["max_positions"]),
        "window": int(config["sliding_window"]),
        "full_layers": sum(t == "full_attention" for t in types),
        "window_layers": sum(t == "sliding_attention" for t in types),
        "full_heads": int(heads["full_attention"]),
        "window_heads": int(heads["sliding_attention"]),
        "kv_itemsize": {"bf16": 2, "f32": 4}[serving["kv_dtype"]],
    }


def _shapes(text: str) -> list[tuple]:
    return [tuple(int(n) for n in m.split(","))
            for m in SHAPE.findall(text)]


def attn_op(z: dict, which: str):
    """Whether an op is one of the ``which`` (``"full"`` / ``"window"``)
    kind's read: the module docstring has the shapes."""
    row, blk, heads = z["row"], z["block"], z[which + "_heads"]
    t = z["positions"] if which == "full" else z["ring"]
    # a flattened gather of b full tables is no multiple of a ring
    # unless it is one of a table too; the full kind takes those
    other = z["ring"] if which == "full" else z["positions"]
    # the shared experts' down stack ``[L_e, d, F_s]`` is no view, even
    # where ``d`` is a capacity and ``F_s`` a row's lanes (both are here)
    stack = (z["expert_layers"], z["d"], z["shared_ffn"])

    def keep(name: str) -> bool:
        for s in _shapes(_head(name)):
            if len(s) == 4 and s[2:] == (blk, row) and s[1] * blk == t:
                return True
            if len(s) != 3 or s == stack:
                continue
            if s[1:] == (blk, row):         # b rows' tables, flattened
                n = s[0] * blk      # ... of two rows or more
                if (n >= 2 * t and n % t == 0
                        and (which == "full" or n % other)):
                    return True
            elif ((s[2] == row and s[1] == t)
                    or (s[1] == heads and s[2] in (t, row))
                    or (s[1] == row and s[2] == heads)):
                return True
        return False

    return keep


def held_op(z: dict):
    pat = re.compile(
        r"\[(\d+,)+%(experts)d,%(ffn)d(,%(d)d)?\]"
        r"|\[\d+,%(experts)d,%(d)d(,%(ffn)d)?\]"
        r"|\[%(expert_layers)d,%(routed)d,%(d)d\]|\[\d+,%(routed)d\]"
        r"|\[\d+,%(top_k)d(,1|,%(experts)d)?\]|\[\d+,%(experts)d\]"
        r"|\[%(expert_layers)d,%(shared_ffn)d,%(d)d\]"
        r"|\[%(expert_layers)d,%(d)d,%(shared_ffn)d\]" % z)
    return lambda name: bool(pat.search(_head(name)))


def decode_events(ctx: dict) -> list | None:
    """``[(start_ns, end_ns)]`` of the program events of the traced
    window, on the first device, that hold a K/V gather over every
    row's table (the module docstring says how)."""
    red = ctx.get("trace")
    if red is None:
        return None
    trace = red["trace"]
    planes = xplane.device_planes(trace)
    if not planes:
        return None
    z = sizes(ctx)
    blk, row, cap = z["block"], z["row"], z["positions"]

    def every_rows(s: tuple) -> bool:
        if s[-2:] != (blk, row):
            return False
        if len(s) == 3:
            return s[0] * blk >= 2 * cap and s[0] * blk % cap == 0
        return len(s) == 4 and s[0] >= 2 and s[1] * blk == cap

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
        k, batch = j, False
        while k < len(ops) and ops[k][1] < start + dur and not batch:
            batch = any(map(every_rows, _shapes(_result(ops[k][0]))))
            k += 1
        if batch:
            out.append((start, start + dur))
    return out or None


def decode_ms(ctx: dict):
    """Mean device milliseconds of a decode-side dispatch."""
    spans = decode_events(ctx)
    if not spans or counters(ctx) is None:
        return None
    return sum(b - a for a, b in spans) / len(spans) / 1e6


def part_ms(ctx: dict, which: str):
    """Device milliseconds a decode-side dispatch spends in one
    mechanism's ops (``"full"``, ``"window"`` or ``"held"``), or None
    (also where the program wrote no window counter: a commit before
    this family, another family)."""
    if counters(ctx) is None:
        return None
    spans = decode_events(ctx)
    if not spans:
        return None
    z = sizes(ctx)
    keep = held_op(z) if which == "held" else attn_op(z, which)
    red = ctx["trace"]
    ops = _ops_in(red["trace"], xplane.device_planes(red["trace"])[0],
                  spans, keep)
    if not ops:
        return None
    return 1e3 * sum(v[0] for v in ops.values()) / len(spans)


def counters(ctx: dict) -> dict | None:
    """Means of the program's counters over the traced steps that
    dispatched ONE decode batch and no prefill chunk: the cache reads'
    and the experts'. None where the program wrote no ``full_rows``."""
    recs = engine_phases.traced_records(ctx)
    if recs is None:
        return None
    steps = ctx["values"]["traced_steps"]
    got = [r for r, st in zip(recs, steps)
           if st.n_decode == 1 and not st.n_prefill and r.get("full_rows")]
    if not got:
        return None
    out = {k: sum(r[k] for r in got) / len(got)
           for k in ("window_rows", "full_rows")}
    out.update(decode_counters(ctx) or {})
    return out


def pool_util_pct(ctx: dict):
    """The window layers' pool: the mean, over the traced steps, of the
    blocks sequences hold at a step's end (``window_blocks_live``) over
    the pool's usable blocks as the configuration's driver sizes it."""
    recs = engine_phases.traced_records(ctx)
    if not recs or any("window_blocks_live" not in r for r in recs):
        return None
    config = ctx["cell"]["config"]
    usable = harness.driver_module(config).window_pool_blocks(config)
    return (100.0 * sum(r["window_blocks_live"] for r in recs)
            / len(recs) / usable)


# -- the bytes a decode dispatch needs (kept with the benchmark) ----------


def kv_bytes(z: dict, which: str, rows: float) -> float:
    """What ANY read of one kind has to move in one decode dispatch:
    every attended position's K and V row once in each of the kind's
    layers (``rows``: the positions the dispatch's rows attend over,
    summed over rows; a window layer's at most the window a row)."""
    return rows * 2 * z["row"] * z["kv_itemsize"] * z[which + "_layers"]


def expert_bytes(z: dict, itemsize: int = 2) -> int:
    """One routed expert's three matrices."""
    return 3 * z["d"] * z["ffn"] * itemsize


def held_ffn_bytes(z: dict, experts_touched: float,
                   itemsize: int = 2) -> float:
    """What the TIMED expert-layer ops have to read in one decode
    dispatch: the held experts the counters say received a row, and per
    sparse layer the float32 router over all published experts and the
    shared expert's three matrices."""
    per_layer = (4 * z["routed"] * z["d"]
                 + 3 * z["d"] * z["shared_ffn"] * itemsize)
    return (experts_touched * expert_bytes(z, itemsize)
            + z["expert_layers"] * per_layer)


def decode_step_bytes(z: dict, weight_bytes: int, got: dict) -> float:
    """One decode dispatch: the touched held experts, every other leaf
    of ``decode_weight_bytes`` once, and both kinds' attended rows."""
    held_all = z["expert_layers"] * z["experts"] * expert_bytes(z)
    return (weight_bytes - held_all
            + got["experts_touched"] * expert_bytes(z)
            + kv_bytes(z, "full", got["full_rows"])
            + kv_bytes(z, "window", got["window_rows"]))
