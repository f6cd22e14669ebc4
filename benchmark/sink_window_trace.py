"""The two kinds of attention read and the chip's share of the experts
inside the decode-side programs, from the trace, for a model whose two
stores have rows of their own: the reader of
``configs/mimo-v2-flash-serve.json`` (``models/mimo_v2_flash_lm.py``).
``window_trace.sizes`` reads another family's keys and ONE row width
for both stores and is not edited; what this reader shares with the
older ones (``_ops_in``, ``decode_counters``, ``_head``, ``_shapes``,
``dispatch_join.decode_side``) is imported.

- the PROGRAM's counters in its ``engine_step`` records: ``window_rows``
  / ``full_rows`` (the cached positions the rows a step LAUNCHED attend
  over in a window layer and in a full one), the experts' three (of the
  results a step READ) and, new with this family, each store's BYTES a
  position a layer, ``kv_row_bytes`` / ``window_row_bytes`` (its K row
  and its V row as the arrays hold them: here 4 x (192 + 128) x 2 =
  2,560 and 8 x 320 x 2 = 5,120). The bytes of a read are the
  program's positions times the program's bytes, never a family's
  config keys. A program that writes no row bytes — the parent commit,
  another family — gives every reader here nothing to read: they
  return None.
- which ``jit_run`` events are DECODE-side dispatches (the ``decode``
  and the ``mixed`` program), by ORDINAL (``dispatch_join.decode_side``)
  and by nothing a program's ops show.
- the device time, inside those events, of the ops of each mechanism,
  told by the shapes in the HLO instruction text the profiler names an
  op by, its RESULT and its OPERANDS. Both kinds have the SAME 64 query
  heads here, so a kind is told by its store's rows (``K`` row ``H_kv x
  dk`` lanes, ``V`` row ``H_kv x dv``: 768 / 512 in a full layer, 1,536
  / 1,024 in a window layer) and a kernel by the tables it is handed:

  * an attention read as a WALK (``ops/kv_walk.py``): the kernel call,
    a ``custom-call`` whose result is ``f32[b, H, V row]`` and which is
    handed the kind's tables ``s32[b, T / block]`` (``s32[64,192]`` in a
    full layer, the ring's ``s32[64,10]`` in a window layer, where it is
    also handed the sinks ``f32[64,1]``), and what stands round it: the
    query laid out for the stored K rows ``[b, H, K row]`` / ``[b, K
    row, H]`` and the head pick of the result ``[b, H, V row]``; or, in
    a program that gathers a batch's rows instead, the gather ``[b * T /
    block, block, row]`` and the view ``[b, T, row]`` (a riding chunk's
    ONE slot, ``[T / block, block, row]``, is not the batch's read and
    is booked under neither kind);
  * the share of the experts: a shape ``[.., E, F]`` / ``[.., E, F, d]``
    / ``[.., E, d, F]`` over the ``E`` HELD experts, the router over all
    ``R`` published ones (``[L_e, R, d]``, ``[b, R]``, its choice bias
    ``[L_e, R]``) and the choice (``[b, top_k]``, ``[b, top_k, 1]``,
    ``[b, top_k, E]``, ``[b, E]``). There is no shared expert.
"""

from __future__ import annotations

import re

from . import dispatch_join, engine_phases, harness, xplane
from .conv_moe_trace import _head
from .moe_trace import _ops_in, decode_counters
from .window_trace import _shapes

ROW_BYTES = ("kv_row_bytes", "window_row_bytes")


def sizes(ctx: dict) -> dict:
    """The shapes' numbers, from the configuration's published keys and
    the engine's capacity (bytes come from the program: ``counters``)."""
    config = ctx["cell"]["config"]
    serving = config["serving"]
    from distributed_llm_code_samples_tpu.decode.programs import (
        window_entries)
    sut = harness.driver_module(config)
    cfg = sut.engine_config(config)
    dk, dv = int(config["head_dim"]), int(config["v_head_dim"])
    pattern = config["hybrid_layer_pattern"]
    return {
        "block": cfg.block_size,
        "positions": int(serving["max_positions"]),
        "ring": window_entries(cfg, int(config["sliding_window"]))
        * cfg.block_size,
        "heads": int(config["num_attention_heads"]),
        "full_rows": tuple(int(config["num_key_value_heads"]) * x
                           for x in (dk, dv)),
        "window_rows": tuple(int(config["swa_num_key_value_heads"]) * x
                             for x in (dk, dv)),
        "full_layers": sum(not t for t in pattern),
        "window_layers": sum(bool(t) for t in pattern),
        "d": int(config["hidden_size"]),
        "experts": int(config["n_routed_experts"]),
        "routed": int(config.get("router_experts",
                                 config["n_routed_experts"])),
        "top_k": int(config["num_experts_per_tok"]),
        "ffn": int(config["moe_intermediate_size"]),
        "expert_layers": sum(bool(m) for m in config["moe_layer_freq"]),
    }


def attn_op(z: dict, which: str):
    """Whether an op is one of the ``which`` (``"full"`` / ``"window"``)
    kind's read: the module docstring has the shapes."""
    blk, heads = z["block"], z["heads"]
    rows = z[which + "_rows"]
    t = z["positions"] if which == "full" else z["ring"]
    other = z["ring"] if which == "full" else z["positions"]

    def keep(name: str) -> bool:
        shapes = _shapes(_head(name))
        if " custom-call(" in name:
            # a kernel: by the tables it is handed, ``[b, T / block]``
            tables = [s[1] * blk for s in shapes if len(s) == 2 and s[0] > 1
                      and s[1] * blk in (t, other)]
            if tables:
                return t in tables
        for s in shapes:
            if len(s) == 4 and s[2] == blk and s[3] in rows and (
                    s[1] * blk == t):
                return True             # b rows' tables, gathered
            if len(s) != 3:
                continue
            if s[1] == blk and s[2] in rows:
                n = s[0] * blk      # b rows' tables, flattened: two or
                if (n >= 2 * t and n % t == 0       # more (a chunk's ONE
                        and (which == "full" or n % other)):    # slot is
                    return True                     # not the batch's read)
            elif ((s[2] in rows and s[1] in (t, heads))
                    or (s[1] in rows and s[2] == heads)):
                return True
        return False

    return keep


def share_op(z: dict):
    pat = re.compile(
        r"\[(\d+,)+%(experts)d,%(ffn)d(,%(d)d)?\]"
        r"|\[\d+,%(experts)d,%(d)d(,%(ffn)d)?\]"
        r"|\[%(expert_layers)d,%(routed)d(,%(d)d)?\]|\[\d+,%(routed)d\]"
        r"|\[\d+,%(top_k)d(,1|,%(experts)d)?\]|\[\d+,%(experts)d\]" % z)
    return lambda name: bool(pat.search(_head(name)))


def decode_events(ctx: dict) -> list | None:
    """``[(start_ns, end_ns)]`` of the decode-side program events of the
    traced window, on the first device, by ordinal."""
    return dispatch_join.decode_side_events(ctx)


def counters(ctx: dict) -> dict | None:
    """Means of the program's counters over the traced steps that
    dispatched ONE decode batch and no prefill chunk: the cache reads',
    the experts', and each store's bytes a position a layer. None where
    the program wrote no row bytes (the parent commit, another
    family)."""
    recs = engine_phases.traced_records(ctx)
    if recs is None:
        return None
    steps = ctx["values"]["traced_steps"]
    got = [r for r, st in zip(recs, steps)
           if st.n_decode == 1 and not st.n_prefill and r.get("full_rows")
           and all(r.get(k) for k in ROW_BYTES)]
    if not got:
        return None
    out = {k: sum(r[k] for r in got) / len(got)
           for k in ("window_rows", "full_rows")}
    out.update({k: got[0][k] for k in ROW_BYTES})
    out.update(decode_counters(ctx) or {})
    return out


def decode_ms(ctx: dict):
    """Mean device milliseconds of a decode-side dispatch."""
    spans = decode_events(ctx)
    if not spans or counters(ctx) is None:
        return None
    return sum(b - a for a, b in spans) / len(spans) / 1e6


def part_ms(ctx: dict, which: str):
    """Device milliseconds a decode-side dispatch spends in one
    mechanism's ops (``"full"``, ``"window"`` or ``"share"``), or
    None."""
    if counters(ctx) is None:
        return None
    spans = decode_events(ctx)
    if not spans:
        return None
    z = sizes(ctx)
    keep = share_op(z) if which == "share" else attn_op(z, which)
    red = ctx["trace"]
    ops = _ops_in(red["trace"], xplane.device_planes(red["trace"])[0],
                  spans, keep)
    if not ops:
        return None
    return 1e3 * sum(v[0] for v in ops.values()) / len(spans)


def share_of_peak(ctx: dict, need_bytes: float, ms) -> float | None:
    """``need_bytes`` at the chip's published HBM bandwidth as a share
    (%) of ``ms`` device milliseconds."""
    from . import flops
    if not ms:
        return None
    least_s = need_bytes / flops.peaks(
        ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)


# -- the bytes a decode dispatch needs (kept with the benchmark) ----------


def kv_bytes(z: dict, got: dict, which: str) -> float:
    """What ANY read of one kind has to move in one decode dispatch:
    every attended position's K and V row once in each of the kind's
    layers — the program's positions (``full_rows`` / ``window_rows``:
    summed over the dispatch's rows; a window layer's at most the window
    a row) times the program's bytes a position of that store."""
    key = {"full": "kv_row_bytes", "window": "window_row_bytes"}[which]
    return got[which + "_rows"] * got[key] * z[which + "_layers"]


def expert_bytes(z: dict, itemsize: int = 2) -> int:
    """One routed expert's three matrices."""
    return 3 * z["d"] * z["ffn"] * itemsize


def share_ffn_bytes(z: dict, experts_touched: float,
                    itemsize: int = 2) -> float:
    """What the TIMED expert-layer ops have to read in one decode
    dispatch: the held experts the counters say received a row, once
    each, and per expert layer the float32 router over all published
    experts with its choice bias."""
    per_layer = 4 * z["routed"] * (z["d"] + 1)
    return (experts_touched * expert_bytes(z, itemsize)
            + z["expert_layers"] * per_layer)


def decode_step_bytes(z: dict, weight_bytes: int, got: dict) -> float:
    """One decode dispatch: the touched held experts, every other leaf
    of ``decode_weight_bytes`` once, and both kinds' attended rows."""
    held_all = z["expert_layers"] * z["experts"] * expert_bytes(z)
    return (weight_bytes - held_all
            + got["experts_touched"] * expert_bytes(z)
            + kv_bytes(z, got, "full") + kv_bytes(z, got, "window"))
