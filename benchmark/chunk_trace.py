"""The two stores of a chunk-summarised (EVA) attention layer inside the
decode-side programs, from the trace; and the counters of their reads.
The reader of ``configs/evabyte-6.5b-serve.json``
(``models/evabyte_lm.py``).

- **Which programs.** By ORDINAL and by nothing a program's ops show:
  the traced steps' ``engine_step`` records say which step programs
  they launched (``dispatches``: ``[kind, bucket]``, a record's own
  launches being the ordinals ``launches - len(dispatches) ..<
  launches`` among the engine's), the engine launches them in that
  order on one device, and the profiler is started and stopped between
  steps, so the k-th entry of the traced records is the k-th
  ``jit_run`` event of the first device plane's ``XLA Modules`` line.
  A read is paired with its launch by ``readbacks`` / ``launches``
  (``layer_metrics/late_read_share.offline.py``): a launch that no
  traced record read ran when the window closed and is left out.
  Another count on the two sides, a record without ``dispatches`` or
  ``summary_rows`` (a commit before this family, another family), no
  trace: every reader returns None. The decode-side programs are the
  ``decode`` and ``mixed`` entries. Whether a read is a gather and two
  products or a kernel call changes nothing here.
- **Which ops**, inside those events, by the shapes in the HLO
  instruction text the profiler names an op by (result and operands).
  ``b`` rows, ``H`` heads, ``row = H_kv * dh`` lanes, ``T`` the ring's
  positions (``entries * block``), ``MB`` a slot's blocks of the
  summaries' pool:

  * the RING's read: the gather ``[b * T / block, block, row]`` (or
    ``[b, T / block, block, row]``; a riding chunk's ONE slot: ``[T /
    block, block, row]``), the view ``[b, T, row]``, scores
    and probabilities ``[b, H, T]``, the query laid out for the stored
    rows ``[b, row, H]``, and the weighted sum, an op that takes a view
    or the probabilities and gives ``[b, H, row]``; or ONE kernel call
    (``custom-call``) that takes the ring's pool ``[L, 1 + slots *
    entries, block, row]`` or its tables ``[b, entries]``;
  * the SUMMARIES': the kernel call that takes the summaries' pool
    ``[L, 1 + slots * MB, block, row]`` or tables ``[b, MB]`` (or, for
    a plain read, the gather, view and scores at ``T = MB * block``);
    the summarise-and-write: the finished chunks' blocks ``[b, block,
    row]``, their head-split ``[b, block, H, dh]`` and weights ``[b,
    block, H]``, and the write into the summaries' pool (an op whose
    RESULT is that pool); and the join: what takes a read's ``[b, H,
    row]`` result and is neither read.

- **The counters** (``engine_step`` records, of the rows a step
  LAUNCHED, so of the program paired with the record's own dispatch):
  ``window_rows`` (positions of the rows' own aligned windows),
  ``summary_rows`` (summaries they attend over), ``summaries_written``.
"""

from __future__ import annotations

from typing import NamedTuple

from . import engine_phases, engine_trace, harness, xplane
from .conv_moe_trace import _head
from .moe_trace import _ops_in, _result
from .window_trace import _shapes

DECODE_SIDE = ("decode", "mixed")
_KEY = "_chunk_trace"


def sizes(ctx: dict) -> dict:
    """The shapes' numbers, from the configuration's published keys and
    the engine's capacity (the configuration's driver says how the
    engine sizes the two stores)."""
    config = ctx["cell"]["config"]
    serving = config["serving"]
    sut = harness.driver_module(config)
    cfg = sut.engine_config(config)
    heads = int(config["num_attention_heads"])
    return {
        "block": cfg.block_size,
        "slots": int(serving["max_slots"]),
        "entries": sut.ring_blocks(config),
        "table": cfg.max_blocks_per_seq,
        "layers": int(config["num_hidden_layers"]),
        "heads": heads,
        "dh": int(config["hidden_size"]) // heads,
        "row": (int(config.get("num_key_value_heads", heads))
                * int(config["hidden_size"]) // heads),
        "window": int(config["window_size"]),
        "chunk": int(config["chunk_size"]),
        "kv_itemsize": {"bf16": 2, "f32": 4}[serving["kv_dtype"]],
    }


def classify(z: dict):
    """``name -> "ring" | "summary" | None`` for one op's HLO text (the
    module docstring has the shapes)."""
    blk, row, heads, dh = z["block"], z["row"], z["heads"], z["dh"]
    stores = {"ring": z["entries"], "summary": z["table"]}
    pools = {kind: (z["layers"], 1 + z["slots"] * n, blk, row)
             for kind, n in stores.items()}

    def read_of(shapes, result):
        """The store whose gather, view, scores or laid-out query is
        among ``shapes``."""
        for kind, n in stores.items():
            t = n * blk
            for s in shapes:
                if s in pools.values():
                    continue
                if len(s) == 4 and s[1:] == (n, blk, row):
                    return kind
                if len(s) != 3:
                    continue
                if s[1:] == (blk, row) and s[0] >= n and s[0] % n == 0:
                    return kind     # rows' tables flattened; ONE slot's
                if (s[1:] == (t, row) or s[1:] == (heads, t)
                        or (s[1:] == (row, heads) and s in result)):
                    return kind
        return None

    def keep(name: str):
        head = _head(name)
        shapes, result = _shapes(head), _shapes(_result(name))
        if " custom-call(" in name:
            # a kernel: by the pool or the tables it is handed
            for kind, n in stores.items():
                if pools[kind] in shapes or any(
                        len(s) == 2 and s[1] == n and s[0] > 1
                        for s in shapes):
                    return kind
            return None
        if pools["summary"] in result:
            return "summary"            # the summaries' rows written
        if pools["ring"] in result:
            return None                 # the ring's own K/V write
        kind = read_of(shapes, result)
        if kind:
            return kind
        for s in shapes:
            # the finished chunks' blocks summarised, and the join of
            # the two reads' ``[b, H, row]`` results
            if ((s[1:] in ((blk, row), (blk, heads, dh), (blk, heads))
                 and s[0] <= z["slots"])
                    or (len(s) == 3 and s[1:] == (heads, row))):
                return "summary"
        return None

    return keep


class Pair(NamedTuple):
    """One decode-side dispatch: its record (the counters) and the
    device program it ran."""
    kind: str
    start: float
    end: float
    rec: dict


def _pairs(ctx: dict) -> list | None:
    red = ctx.get("trace")
    recs = engine_phases.traced_records(ctx)
    if red is None or recs is None:
        return None
    if any("dispatches" not in r or "summary_rows" not in r for r in recs):
        return None
    planes = xplane.device_planes(red["trace"])
    if not planes:
        return None
    events = sorted((e for e in red["trace"]["planes"][planes[0]].get(
        xplane.MODULES_LINE, []) if e[0].startswith(engine_trace.PROGRAM)),
        key=lambda e: e[1])
    said = [(kind, rec) for rec in recs for kind, _ in rec["dispatches"]]
    if not said or len(said) != len(events):
        return None
    # a launch is read by the record that names its ordinal; the last
    # traced launch is read after the window and is left out
    read = {o for rec in recs for o in rec.get("readbacks", ())}
    first = recs[0]["launches"] - len(recs[0]["dispatches"])
    out = [Pair(kind, ev[1], ev[1] + ev[2], rec)
           for k, ((kind, rec), ev) in enumerate(zip(said, events))
           if kind in DECODE_SIDE and (first + k in read
                                       or "readbacks" not in rec)]
    return out or None


def pairs(ctx: dict) -> list | None:
    """The traced window's decode-side dispatches, each with its
    program's event, or None where there is nothing to read."""
    if _KEY not in ctx:
        ctx[_KEY] = _pairs(ctx)
    return ctx[_KEY]


def decode_ms(ctx: dict):
    """Mean device milliseconds of a decode-side program."""
    got = pairs(ctx)
    if not got:
        return None
    return sum(p.end - p.start for p in got) / len(got) / 1e6


def part_ms(ctx: dict, which: str):
    """Device milliseconds a decode-side program spends in one store's
    ops (``"ring"`` or ``"summary"``), or None."""
    got = pairs(ctx)
    if not got:
        return None
    red = ctx["trace"]
    store = classify(sizes(ctx))
    ops = _ops_in(red["trace"], xplane.device_planes(red["trace"])[0],
                  [(p.start, p.end) for p in got],
                  lambda name: store(name) == which)
    if not ops:
        return None
    return 1e3 * sum(v[0] for v in ops.values()) / len(got)


def counters(ctx: dict) -> dict | None:
    """Means over the decode-side programs of their records' counters."""
    got = pairs(ctx)
    if not got:
        return None
    return {k: sum(p.rec[k] for p in got) / len(got)
            for k in ("window_rows", "summary_rows", "summaries_written")}


def rows_share_pct(ctx: dict):
    """Of the cached entries the traced steps' launched rows attend
    over, the share in percent that are chunk summaries."""
    recs = engine_phases.traced_records(ctx)
    if not recs or any("summary_rows" not in r for r in recs):
        return None
    summ = sum(r["summary_rows"] for r in recs)
    both = summ + sum(r["window_rows"] for r in recs)
    return 100.0 * summ / both if both else None


# -- the bytes a decode dispatch needs (kept with the benchmark) ----------


def row_bytes(z: dict) -> int:
    """One cached entry of one layer: a K and a V row."""
    return 2 * z["row"] * z["kv_itemsize"]


def ring_bytes(z: dict, got: dict) -> float:
    """What ANY read of the ring has to move in one dispatch: the K and
    V row of every position of the rows' own aligned windows, once in
    each layer."""
    return got["window_rows"] * row_bytes(z) * z["layers"]


def summary_bytes(z: dict, got: dict) -> float:
    """... and of the summaries: every attended summary's pair once in
    each layer, and for each finished chunk its block read and its one
    row written."""
    return ((got["summary_rows"]
             + got["summaries_written"] * (z["chunk"] + 1))
            * row_bytes(z) * z["layers"])


def decode_step_bytes(z: dict, weight_bytes: int, got: dict) -> float:
    """One decode dispatch: every decode leaf once and both stores'
    attended rows."""
    return weight_bytes + ring_bytes(z, got) + summary_bytes(z, got)
