"""The serving engine's compiled step programs: the one builder.

``DecodeEngine`` (``decode/engine.py``) is the scheduler; what a step
program is made of lives here, put together from two sides. The model
(``models/face.py::ServedModel``): embedding, norm, an attention
layer's q/k/v and output projection (a latent-cache layer's query and
row, and its output; a chunked layer's q/k/v, the summary of a finished
chunk, and its output), a recurrent layer's step and chunk, FFN, final
norm and head — asked of the params, never of their class. The cache
(``decode/paged.py``): the pool and the recurrent state, the writes and
the ONE read each kind of row calls (``stored_decode_attn`` for decode
and verify rows — which walks a float pool's live blocks where they
lie, a full layer's table, a latent pool's one-sided rows and a window
layer's ring alike, and gathers the others' tables, as ``paged.walks``
says from the pool alone, never a flag here — and ``gathered_chunk_attn``
for a prefill chunk; a chunked layer calls each over BOTH its stores
with ``stats=True`` and joins the two: ``paged.join_reads``). Between
them, written
once: the walk over the model's layers (``_trunk``, so prefill and
decode numerics cannot drift; in the ``mixed`` body a decode batch and
ONE slot's full chunk are rows of one walk, the weights read once, and
only the cache seams split by row kind), head -> poison -> pick -> finite flags
(``_head_pick``), and the wrapping (``shard_map`` under a model-axis
mesh, ``jit``, the donated cache). The builder gets ``cfg``, the
model's ``CacheSpec``, the vocabulary and the mesh as plain values: it
never sees a sequence, a slot table or a metrics writer.

A step program is ``run(params, carry, operand) -> (carry, result)``.
``carry`` is the donated operand, ``(cache, tokens)``, and comes back in
the same form: ``cache`` the ``PagedKV``, or for a model that keeps more
the tuple of what it keeps, in the order ``(PagedKV, the window layers'
PagedKV, RecurrentState)`` (``StepPrograms.whole`` / ``parts``: a
hybrid's is ``(PagedKV, RecurrentState)``); ``tokens [max_slots + 1]``
each slot's NEXT token (``init_tokens``; the last row is the pad rows'
scratch). A decode row's pick, and the pick of a chunk's last row, is
written to its slot's entry, and a row whose operand token is
``FROM_SLOT`` takes its input from there: a token goes from one step
to the next without visiting the host, so the engine may launch a step
before it has read the last one's result (``decode/engine.py``). A row
whose token the host knows (a replay's recorded token, an imported
sequence, any row of an engine that has read its last result) carries
it in the operand as before. ``operand`` is ONE ``int32`` vector holding
every host operand of the dispatch and ``result`` ONE ``int32`` array
holding all the host reads, so a dispatch costs one host-to-device
transfer and one blocking read. The wire format is written once, here:
``Wire`` lays a kind's fields out (``StepPrograms.wire``), ``pack``
fills the vector in numpy on the host, each body starts with the
in-graph ``unpack`` (static slices) and ends with ``_fold`` (a pick
whose logits were not finite reads negative; vocabulary ids never do).
A model with expert layers gets its counters back on the same array:
the picks flattened, then ``expert_rows [expert_layers, n_experts]``,
the rows each held expert received in this dispatch (``split`` is the
host side; every other model's result is the picks as they were).
The jitted callable keeps the name ``run`` (the profiler's ``jit_run``,
which ``benchmark/engine_trace.py`` reads). Under a mesh (the Megatron
decode layout, ``parallel/lm.py``) the pool is head-sharded, the
embedding vocab-parallel, the operand and the result replicated, and
the local logits are gathered in-graph so the pick (keys fold uid and
position, never the shard) draws the same everywhere.

``jax.named_scope`` names (``decode`` / ``prefill``, ``ssm``, ``mla``,
``moe``, ``head``, ``sample``) are metadata only
(``utils/trace_analysis`` ``SCOPES``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.face import ATTN, CHUNKED, LATENT, WINDOW, CacheSpec, take
from ..parallel.collectives import all_gather, all_reduce
from ..parallel.lm import tp_decode_specs, vp_embed
from ..parallel.mesh import MODEL_AXIS
from ..runtime.guardrails import rows_finite
from .paged import (PagedKV, RecurrentState, SCRATCH_BLOCK, copy_block,
                    copy_block_rows, gathered_chunk_attn, implant_block,
                    init_pool, init_state, join_reads, stored_decode_attn,
                    write_chunk, write_rows, write_summaries)
from .sampling import make_pick

# poison operand values (chaos nan_logits injection rides a runtime
# operand, so arming a fault never recompiles)
POISON_NONE = -1
POISON_ALL = -2

# a batch row's ``tokens`` entry that says: take this row's input token
# from the row's slot in the carried token store (vocabulary ids are
# never negative)
FROM_SLOT = -1

# the pool-only programs: block ids (and row counts) are traced operands,
# so one compiled copy each serves every block; the pool is donated
_POOL_OPS = {"cow": copy_block, "cow_rows": copy_block_rows,
             "implant": implant_block}


class Wire:
    """The layout of one step program's packed operand: named ``int32``
    fields, each a fixed shape, end to end in one vector. ``pack`` is
    the host side (numpy), ``unpack`` the in-graph side (static
    slices); nobody else knows an offset."""

    def __init__(self, fields: dict[str, tuple[int, ...]]):
        self.fields = {}
        self.size = 0
        for name, shape in fields.items():
            end = self.size + math.prod(shape)
            self.fields[name] = (self.size, end, shape)
            self.size = end

    def pack(self, **values) -> np.ndarray:
        """The operand of one dispatch: every field, by name, as the
        host holds it (arrays, lists or plain ints)."""
        if values.keys() != self.fields.keys():
            raise TypeError(f"operand fields {sorted(values)} are not the "
                            f"program's {sorted(self.fields)}")
        out = np.empty((self.size,), np.int32)
        for name, (lo, hi, _) in self.fields.items():
            out[lo:hi] = np.reshape(values[name], hi - lo)
        return out

    def unpack(self, operand) -> dict:
        return {name: operand[lo:hi].reshape(shape)
                for name, (lo, hi, shape) in self.fields.items()}


def _fold(picks, finite):
    """Picks and their all-finite flags as one array: a row whose logits
    were not finite reads -1 (the host never used such a row's pick)."""
    return jnp.where(finite, picks, -1)


def _with_counts(picks, counts):
    """A program's result: the folded picks as they are, or for a model
    with expert layers ONE flat array, the picks and then the counters
    (``StepPrograms.split`` is the host side)."""
    if counts is None:
        return picks
    return jnp.concatenate([picks.reshape(-1), counts.reshape(-1)])


def window_entries(cfg, window: int, chunked: bool = False) -> int:
    """Entries of a slot's window table under ``cfg``: the window's
    blocks, the blocks a prefill chunk writes before it reads, one of
    slack; never more than a sequence can hold at all (``chunked``: a
    row of its full-kind table stands for a whole block of positions,
    ``models/face.py::CHUNKED``)."""
    blk = cfg.block_size
    return min(cfg.max_blocks_per_seq * (blk if chunked else 1),
               -(-window // blk) + max(1, cfg.prefill_chunk // blk) + 1)


class StepPrograms:
    """The programs of one engine configuration over one model's face:
    ``build(kind, bucket)`` is what the engine dispatches, ``body`` the
    same callable before ``jit`` (what the static report lowers),
    ``pack(kind, bucket, **fields)`` the operand it takes."""

    def __init__(self, cfg, spec: CacheSpec, vocab: int, mesh=None):
        self.cfg = cfg
        self.spec = spec
        self.mesh = mesh
        self.pick = make_pick(cfg.temperature, cfg.top_k, cfg.top_p, vocab,
                              cfg.seed)
        self._wires: dict = {}
        # entries of a slot's window table (0 for a model with no window
        # layer)
        self.window_blocks = (
            window_entries(cfg, spec.window, bool(spec.chunk))
            if spec.win_layers else 0)

    # -- the wire format ---------------------------------------------------

    def wire(self, kind: str, bucket: int) -> Wire:
        """The operand's layout for ``(kind, bucket)``. Decode: a
        ``bucket``-row batch's block tables, lengths, tokens
        (``FROM_SLOT`` where the row's slot holds it), uids, the poison
        and each row's slot (``rows``: the entry of the token store and,
        where the model has recurrent layers, the state row; the
        scratch row for a padded one). Verify: a batch's tables,
        lengths, tokens, uids and the poison plus the drafts and their
        lengths (the host reads every verify before it goes on, so its
        tokens are always the host's). Prefill: ONE slot's table, start
        position, ``bucket`` tokens, uid, the poison and its slot
        (``row``). Mixed: decode's for the ``bucket``-row batch plus ONE
        slot's table, start position, full chunk
        (``cfg.prefill_chunk`` tokens), uid and slot. A model with
        window layers (or chunked ones, whose exact keys lie in the same
        ring) has, beside every ``tables`` / ``table``, the rows' short
        window tables (``wtables`` / ``wtable``); how many summaries a
        chunked layer's row attends over is no field: it follows from
        its length."""
        w = self._wires.get((kind, bucket))
        if w is None:
            t, wt = self.cfg.max_blocks_per_seq, self.window_blocks
            if kind == "prefill":
                fields = {"table": (t,), "pos0": (), "tokens": (bucket,),
                          "uid": (1,), "poison": (), "row": ()}
                if wt:
                    fields["wtable"] = (wt,)
            else:
                fields = {"tables": (bucket, t), "lengths": (bucket,),
                          "tokens": (bucket,), "uids": (bucket,),
                          "poison": ()}
                if kind == "verify":
                    fields["drafts"] = (bucket, self.cfg.speculate)
                    fields["dlens"] = (bucket,)
                else:
                    fields["rows"] = (bucket,)
                if kind == "mixed":
                    fields.update(table=(t,), pos0=(),
                                  chunk=(self.cfg.prefill_chunk,), uid=(1,),
                                  row=())
                if wt:
                    fields["wtables"] = (bucket, wt)
                    if kind == "mixed":
                        fields["wtable"] = (wt,)
            w = self._wires[kind, bucket] = Wire(fields)
        return w

    def pack(self, kind: str, bucket: int, **fields) -> np.ndarray:
        return self.wire(kind, bucket).pack(**fields)

    def split(self, kind: str, result: np.ndarray):
        """A dispatch's result on the host: ``(picks, expert_rows)`` —
        the picks in the shape the kind gives them (``[b]``, ``[1]``, a
        verify's ``[b, k+2]``, a mixed step's ``[b + 1]``: the batch's
        and then the chunk's last row's) and the expert layers' counters
        ``[expert_layers, n_experts]``, None for a model with none."""
        n = self.spec.expert_layers * self.spec.n_experts
        if not n:
            return result, None
        picks = result[:-n]
        if kind == "verify":
            picks = picks.reshape(-1, self.cfg.speculate + 2)
        return picks, result[-n:].reshape(self.spec.expert_layers, -1)

    # -- the cache -------------------------------------------------------

    def init_cache(self) -> tuple[PagedKV, RecurrentState | None]:
        """The zero pool (head-sharded under a mesh) and the recurrent
        layers' state by slot beside it (None for a model with none)."""
        cfg, spec = self.cfg, self.spec
        row = spec.row
        pool = init_pool(spec.kv_layers, cfg.n_blocks, row.heads,
                         cfg.block_size, row.k_dim, cfg.kv_dtype,
                         latent_rank=spec.latent_rank, v_head_dim=row.v_dim)
        if self.mesh is not None:
            pool = jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
                pool, self.pool_specs())
        state = None
        if spec.rec_layers:
            state = init_state(spec.rec_layers, cfg.max_slots,
                               spec.state_row)
        return pool, state

    def init_window(self) -> PagedKV | None:
        """The window layers' zero pool (None for a model with none): a
        whole ring for every slot and a scratch block of its own, in
        the window kind's own row (``CacheSpec.window_row``)."""
        cfg, spec = self.cfg, self.spec
        if not spec.win_layers:
            return None
        row = spec.window_row
        return init_pool(spec.win_layers,
                         1 + cfg.max_slots * self.window_blocks,
                         row.heads, cfg.block_size, row.k_dim, cfg.kv_dtype,
                         v_head_dim=row.v_dim)

    def whole(self, pool, wpool=None, state=None):
        """The cache a step program carries: the pool alone, or the
        tuple of what the model keeps, in this order."""
        kept = tuple(x for x in (pool, wpool, state) if x is not None)
        return pool if len(kept) == 1 else kept

    def parts(self, cache) -> tuple:
        """``(pool, window pool, recurrent state)`` of a carried cache,
        None where the model has not the part."""
        spec = self.spec
        if not (spec.win_layers or spec.rec_layers):
            return cache, None, None
        kept = iter(cache)
        return (next(kept), next(kept) if spec.win_layers else None,
                next(kept) if spec.rec_layers else None)

    def init_tokens(self) -> jax.Array:
        """The token store: each slot's next token, and one scratch row
        that a bucket's padded rows write (replicated under a mesh)."""
        tokens = jnp.zeros((self.cfg.max_slots + 1,), jnp.int32)
        if self.mesh is not None:
            tokens = jax.device_put(tokens, NamedSharding(self.mesh, P()))
        return tokens

    def pool_specs(self) -> PagedKV:
        """Heads are contiguous in a stored row's minor axis
        (``H_kv*dh``), so sharding that axis shards the heads (a latent
        row has none: the engine refuses a mesh for such a model)."""
        arr = P(None, None, None, MODEL_AXIS)
        sc = (P(None, None, MODEL_AXIS) if self.cfg.kv_dtype == "int8"
              else None)
        row = self.spec.row
        return PagedKV(arr, arr, sc, sc, row.k_dim, v_head_dim=row.v_dim)

    # -- the model's forward over the cache -------------------------------

    def _embed(self, p, tokens, positions):
        return p.embed(tokens, positions,
                       take if self.mesh is None else vp_embed)

    def _trunk(self, p, cache, x, positions, write_attn, mix=None,
               write_window=None, write_chunked=None):
        """The walk over ``p.layers`` every program runs. Attention:
        norm, q/k/v, the caller's ``write_attn(i, pool, q, k, v) ->
        (pool, y [N, h_loc, dh])`` (where the programs differ: batched
        single-token writes and per-slot reads, or one slot's chunk),
        output projection. Window: the same over the window layers' own
        pool, ``write_window(i, wpool, q, k, v)``, between the model's
        ``window_qkv`` and ``window_out`` (the seam asks the model for
        the layer's ``window_sink``). Chunked: a layer with an index
        in BOTH pools, ``write_chunked(i, pool, wpool, q, k, v) ->
        (pool, wpool, y)`` between ``chunked_qkv`` and ``chunked_out``:
        the exact keys of the row's aligned window in the ring, a
        summary row a finished chunk in the full kind's pool, the two
        reads joined. Latent: the same seam — the
        query for the
        stored row as ``q``, the row as the one "key" of one head and
        no value (the pool's ``v`` is zero lanes wide); the read's ``[N,
        h, latent_rank]`` goes to the model's ``latent_out``. Recurrent:
        norm, the caller's ``mix(i, state, a) -> (state, y [N, d])``.
        Then the FFN; under a mesh both residual adds take the Megatron
        all-reduce. Returns ``(cache, x, counts)``: ``counts
        [expert_layers, n_experts]`` the rows each held expert received,
        None for a model with no expert layer."""
        tp = self.mesh is not None
        pool, wpool, state = self.parts(cache)
        n = x.shape[0]
        counts = []
        for l, (kind, i) in enumerate(p.layers):
            a = p.norm(p.norm_in[l], x)
            if kind == ATTN:
                q, k, v = p.attn_qkv(i, a, positions, self.spec.head_dim,
                                     self.cfg.use_rope)
                pool, y = write_attn(i, pool, q, k, v)
                y = p.attn_out(i, y.reshape(n, -1), a)
            elif kind == WINDOW:
                q, k, v = p.window_qkv(i, a, positions)
                wpool, y = write_window(i, wpool, q, k, v)
                y = p.window_out(i, y.reshape(n, -1), a)
            elif kind == CHUNKED:
                q, k, v = p.chunked_qkv(i, a, positions)
                pool, wpool, y = write_chunked(i, pool, wpool, q, k, v)
                y = p.chunked_out(i, y.reshape(n, -1), a)
            elif kind == LATENT:
                with jax.named_scope("mla"):
                    q, row = p.latent_qrow(i, a, positions)
                    pool, y = write_attn(i, pool, q, row[:, None, :],
                                         row[:, None, :0])
                    y = p.latent_out(i, y)
            else:
                state, y = mix(i, state, a)
            x = x + (all_reduce(y, MODEL_AXIS) if tp else y)
            h = p.norm(p.norm_ff[l], x)
            if self.spec.expert_layers:
                f, rows = p.ffn_counted(l, h)
                if rows is not None:
                    counts.append(rows)
            else:
                f = p.ffn(l, h)
            x = x + (all_reduce(f, MODEL_AXIS) if tp else f)
        return (self.whole(pool, wpool, state), x,
                jnp.stack(counts) if counts else None)

    def logits(self, p, x):
        """Final norm and tied head of ``x [N, d]``; under a mesh each
        shard scores ``V/n`` columns and the gather completes the row."""
        logits = p.head(x)
        if self.mesh is not None:
            logits = all_gather(logits, MODEL_AXIS, dim=1)
        return logits

    def _batch_seams(self, b: int, p, tables, lengths, rows, wtables=None):
        """``(write_attn, mix, write_window, write_chunked)`` of ``b``
        decode rows: each row's token written at its own position and
        attended over its blocks as stored (a window layer: into the
        row's ring, ``wtables [b, entries]``, and over its last
        ``window`` positions, the model's ``window_sink`` in the
        softmax where it has one; a chunked layer: into the ring, its
        chunk's summary into the full kind's pool where the position
        ends the chunk, and over the ring's aligned window joined with
        the summaries of every earlier one, whose count follows from
        ``lengths``); a recurrent layer advances each row's own state
        where it lies (``rows [b]``: the slot's state row, the scratch
        row for a padded one)."""
        cfg = self.cfg
        slot_phys = lengths // cfg.block_size
        off = lengths % cfg.block_size

        def write_attn(l, pool, q, k, v):
            phys = tables[jnp.arange(b), slot_phys]
            pool = write_rows(pool, l, phys, off, k, v, cfg.kv_dtype)
            return pool, stored_decode_attn(pool, l, q, tables,
                                            lengths + 1)

        def mix(i, state, a):
            with jax.named_scope("ssm"):
                y, conv, ssm = p.recurrent_step(i, a, state.conv,
                                                state.ssm, rows)
            return RecurrentState(conv, ssm), y

        def write_window(l, wpool, q, k, v):
            phys = wtables[jnp.arange(b), slot_phys % wtables.shape[1]]
            wpool = write_rows(wpool, l, phys, off, k, v, cfg.kv_dtype)
            return wpool, stored_decode_attn(wpool, l, q, wtables,
                                             lengths + 1, self.spec.window,
                                             sink=p.window_sink(l))

        def write_chunked(l, pool, wpool, q, k, v):
            window = self.spec.window
            phys = wtables[jnp.arange(b), slot_phys % wtables.shape[1]]
            wpool = write_rows(wpool, l, phys, off, k, v, cfg.kv_dtype)
            pool = write_summaries(
                pool, wpool, l, functools.partial(p.chunk_summary, l),
                phys, tables, lengths, cfg.kv_dtype)
            with jax.named_scope("attn.ring"):
                ring = stored_decode_attn(wpool, l, q, wtables, lengths + 1,
                                          window, aligned=True, stats=True)
            with jax.named_scope("attn.summary"):
                summaries = stored_decode_attn(
                    pool, l, q, tables,
                    window // cfg.block_size * (lengths // window),
                    stats=True)
            return pool, wpool, join_reads(ring, summaries)

        return write_attn, mix, write_window, write_chunked

    def _chunk_seams(self, p, table, pos0, row, wtable=None):
        """``(write_attn, mix, write_window, write_chunked)`` of ONE
        slot's chunk of prompt tokens: they enter the cache through its
        block table and attend causally over the gathered view (a
        window layer: through the slot's ring ``wtable``, each row over
        the ``window`` positions up to its own; a chunked layer: the
        ring under the aligned rule joined with the summaries of every
        earlier window, and the chunk's own summary written where it
        ends on a chunk's last position: a chunk lies in ONE block,
        which the engine holds to); a recurrent layer scans the
        chunk through the slot's state (``row``: the convolution's tail
        and, where the model has one, the scan state), which is zero at
        position 0 whatever the row still holds (every prefill, and
        every replay, starts at 0)."""
        cfg = self.cfg

        def write_attn(l, pool, q, k, v):
            pool = write_chunk(pool, l, table, pos0, k, v, cfg.kv_dtype)
            return pool, gathered_chunk_attn(pool, l, q, table, pos0)

        def mix(i, state, a):
            with jax.named_scope("ssm"):
                y, tail, s = p.recurrent_chunk(
                    i, a, *self._slot_state(state, i, row, pos0))
                state = self._keep_slot_state(state, i, row, tail, s)
            return state, y

        def write_window(l, wpool, q, k, v):
            wpool = write_chunk(wpool, l, wtable, pos0, k, v, cfg.kv_dtype,
                                ring=True)
            return wpool, gathered_chunk_attn(wpool, l, q, wtable, pos0,
                                              self.spec.window,
                                              sink=p.window_sink(l))

        def write_chunked(l, pool, wpool, q, k, v):
            window, blk = self.spec.window, cfg.block_size
            wpool = write_chunk(wpool, l, wtable, pos0, k, v, cfg.kv_dtype,
                                ring=True)
            last = pos0 + q.shape[0] - 1
            pool = write_summaries(
                pool, wpool, l, functools.partial(p.chunk_summary, l),
                wtable[last // blk % wtable.shape[0]][None], table[None],
                last[None], cfg.kv_dtype)
            with jax.named_scope("attn.ring"):
                ring = gathered_chunk_attn(wpool, l, q, wtable, pos0, window,
                                           aligned=True, stats=True)
            with jax.named_scope("attn.summary"):
                summaries = gathered_chunk_attn(
                    pool, l, q, table, pos0,
                    rows=window // blk * (pos0 // window), stats=True)
            return pool, wpool, join_reads(ring, summaries)

        return write_attn, mix, write_window, write_chunked

    def _slot_state(self, state, i, row, pos0):
        """``(tail [K-1, C], s [N, D])`` of recurrent layer ``i`` for
        the sequence in state row ``row`` whose chunk starts at
        ``pos0``: zeros at position 0, whatever the row still holds. A
        layer kind with no scan state carries None for it."""
        fresh = pos0 == 0
        tail = jnp.where(fresh, 0.0, state.conv[i, row]).reshape(
            -1, self.spec.state_row.conv_lanes)
        s = (None if state.ssm is None
             else jnp.where(fresh, 0.0, state.ssm[i, row]))
        return tail, s

    @staticmethod
    def _keep_slot_state(state, i, row, tail, s):
        """``state`` with the sequence's row of layer ``i`` written."""
        return state._replace(
            conv=state.conv.at[i, row].set(tail.reshape(1, -1)),
            ssm=None if s is None else state.ssm.at[i, row].set(s))

    def decode_hidden(self, b: int, p, cache, tables, lengths, tokens,
                      rows=None, wtables=None):
        """The decode program up to the head (``_batch_seams``).
        Returns ``(cache, x [b, d], counts)``."""
        x = self._embed(p, tokens, lengths)             # [b, d]
        return self._trunk(p, cache, x, lengths, *self._batch_seams(
            b, p, tables, lengths, rows, wtables))

    def prefill_hidden(self, c: int, p, cache, table, pos0, tokens,
                       row=None, wtable=None):
        """The prefill program up to the head (``_chunk_seams``).
        Returns ``(cache, x [c, d], counts)``."""
        positions = pos0 + jnp.arange(c)
        x = self._embed(p, tokens, positions)           # [c, d]
        return self._trunk(p, cache, x, positions, *self._chunk_seams(
            p, table, pos0, row, wtable))

    def mixed_hidden(self, b: int, p, cache, f: dict):
        """The mixed program up to the head: the batch's ``b`` rows and
        then ONE slot's full chunk are rows of one walk, so whatever
        reads weights (embedding, norms, projections, FFN) runs once
        over all ``b + c`` of them; only the seams split by row kind —
        the cache write and read here, each half what its own program
        runs; the state update inside the model's ``recurrent_mixed``,
        between the mixer's weight products — and the chunk's slot is
        never among the batch's rows, so neither half reads what the
        other writes. ``f``: the unpacked operand. Returns ``(cache, x
        [b + c, d], counts)``."""
        lengths, pos0 = f["lengths"], f["pos0"]
        rows, row = f["rows"], f["row"]
        positions = jnp.concatenate(
            [lengths, pos0 + jnp.arange(self.cfg.prefill_chunk)])
        x = self._embed(p, jnp.concatenate([f["tokens"], f["chunk"]]),
                        positions)
        batch_attn, _, batch_window, batch_chunked = self._batch_seams(
            b, p, f["tables"], lengths, rows, f.get("wtables"))
        chunk_attn, _, chunk_window, chunk_chunked = self._chunk_seams(
            p, f["table"], pos0, row, f.get("wtable"))

        def both(batch, chunk):
            # ``kept``: the pool the seam writes (a chunked layer's: two)
            def write(l, *kept_qkv):
                *kept, q, k, v = kept_qkv
                *kept, yb = batch(l, *kept, q[:b], k[:b], v[:b])
                *kept, yc = chunk(l, *kept, q[b:], k[b:], v[b:])
                return *kept, jnp.concatenate([yb, yc])
            return write

        write_attn = both(batch_attn, chunk_attn)

        def mix(i, state, a):
            # the mixer's own weights are read once too: the model
            # takes both kinds of row in one call
            with jax.named_scope("ssm"):
                y, conv, ssm, tail, s = p.recurrent_mixed(
                    i, a, state.conv, state.ssm, rows,
                    *self._slot_state(state, i, row, pos0))
                state = self._keep_slot_state(
                    RecurrentState(conv, ssm), i, row, tail, s)
            return state, y

        return self._trunk(p, cache, x, positions, write_attn, mix,
                           both(batch_window, chunk_window),
                           both(batch_chunked, chunk_chunked))

    def _head_pick(self, p, x, uids, poison, pos, ahead: int):
        """head -> poison -> pick -> finite flags, for all four
        bodies: the logits of ``x [n, d]``, NaN'd where the chaos
        operand names the row's uid (or is ``POISON_ALL``; a false
        ``where`` leaves a row bit-identical), the in-graph pick keyed
        on ``(uid, pos + ahead)``, and each row's all-finite flag (the
        serving guardrail, on the same readback as the picks)."""
        with jax.named_scope("head"):
            logits = self.logits(p, x)
        bad = jnp.logical_or(uids == poison, poison == POISON_ALL)
        logits = jnp.where(bad[:, None],
                           jnp.asarray(jnp.nan, logits.dtype), logits)
        with jax.named_scope("sample"):
            picks = self.pick(logits, uids, pos + ahead)
        return picks, rows_finite(logits)

    # -- the four bodies ---------------------------------------------------

    @staticmethod
    def _held(tokens, store, rows):
        """A batch's input tokens: the operand's, and for a row marked
        ``FROM_SLOT`` the one its slot holds in the token store."""
        return jnp.where(tokens == FROM_SLOT, store[rows], tokens)

    def _decode_fn(self, b: int):
        """A ``b``-slot bucket's decode step: ``result [b]``; each
        row's pick is also left in its slot of the token store."""
        wire = self.wire("decode", b)

        @jax.named_scope("decode")
        def run(p, carry, operand):
            cache, store = carry
            f = wire.unpack(operand)
            cache, x, counts = self.decode_hidden(
                b, p, cache, f["tables"], f["lengths"],
                self._held(f["tokens"], store, f["rows"]), f["rows"],
                f.get("wtables"))
            picks, finite = self._head_pick(
                p, x, f["uids"], f["poison"], f["lengths"], 1)
            return ((cache, store.at[f["rows"]].set(picks)),
                    _with_counts(_fold(picks, finite), counts))

        return run

    def _verify_fn(self, b: int):
        """The speculative verify body: ``speculate + 1`` decode
        sub-steps UNROLLED and sequential, each reading the cache its
        predecessor wrote (int8's cross-row requant coupling rules out
        a position-parallel verify); the acceptance chain ``alive_i = alive_{i-1} and draft_i ==
        pick_{i-1}`` masks each drafted row's KV WRITE by redirecting a
        dead row's scatter to the scratch block, so a rejected tail
        never lands. ``result [b, k+2]``: the ``k+1`` sub-steps' folded
        picks, then the accepted count."""
        cfg = self.cfg
        k = cfg.speculate
        wire = self.wire("verify", b)

        @jax.named_scope("decode")
        def run(p, carry, operand):
            pool, store = carry     # the store passes through as it is
            f = wire.unpack(operand)
            tables, lengths, tokens = f["tables"], f["lengths"], f["tokens"]
            uids, poison = f["uids"], f["poison"]
            drafts, dlens = f["drafts"], f["dlens"]
            rows = jnp.arange(b)
            alive = jnp.ones((b,), bool)
            acc = jnp.zeros((b,), jnp.int32)
            cur = tokens
            picks_all, finite_all, counts = [], [], None
            for i in range(k + 1):
                pos = lengths + i
                x = self._embed(p, cur, pos)                 # [b, d]
                slot_phys = pos // cfg.block_size
                off = pos % cfg.block_size

                def write_attn(l, pool, q, kk, vv, _off=off,
                               _sp=slot_phys, _keep=alive, _i=i):
                    phys = tables[rows, _sp]
                    phys = jnp.where(_keep, phys, SCRATCH_BLOCK)
                    pool = write_rows(pool, l, phys, _off, kk, vv,
                                      cfg.kv_dtype)
                    return pool, stored_decode_attn(pool, l, q, tables,
                                                    lengths + _i + 1)

                pool, x, cnt = self._trunk(p, pool, x, pos, write_attn)
                if cnt is not None:     # summed over the sub-steps
                    counts = cnt if counts is None else counts + cnt
                pk, finite = self._head_pick(p, x, uids, poison, pos, 1)
                picks_all.append(pk)
                finite_all.append(finite)
                if i < k:
                    d = drafts[:, i]
                    alive = jnp.logical_and(
                        alive, jnp.logical_and(i < dlens, d == pk))
                    acc = acc + alive.astype(jnp.int32)
                    cur = d
            picks = _fold(jnp.stack(picks_all, 1), jnp.stack(finite_all, 1))
            return (pool, store), _with_counts(
                jnp.concatenate([picks, acc[:, None]], 1), counts)

        return run

    def _prefill_fn(self, c: int):
        """One slot's prefill chunk of ``c`` tokens; the host uses the
        final row's pick only when the chunk completes the prompt:
        ``result [1]``, left in the slot's entry of the token store too
        (the slot's first decode row reads it there or from the host; a
        chunk short of the prompt's end leaves a token nobody reads)."""
        wire = self.wire("prefill", c)

        @jax.named_scope("prefill")
        def run(p, carry, operand):
            cache, store = carry
            f = wire.unpack(operand)
            cache, x, counts = self.prefill_hidden(
                c, p, cache, f["table"], f["pos0"], f["tokens"], f["row"],
                f.get("wtable"))
            picks, finite = self._head_pick(
                p, x[-1:], f["uid"], f["poison"], f["pos0"][None], c)
            return ((cache, store.at[f["row"]].set(picks[0])),
                    _with_counts(_fold(picks, finite), counts))

        return run

    def _mixed_fn(self, b: int):
        """A ``b``-slot bucket's decode step with ONE slot's full
        prefill chunk riding in it: ``result [b + 1]``, the batch's
        picks and then the chunk's last row's (which the host uses only
        when the chunk completes the prompt), each left in its slot of
        the token store."""
        wire = self.wire("mixed", b)
        c = self.cfg.prefill_chunk

        @jax.named_scope("decode")
        def run(p, carry, operand):
            cache, store = carry
            f = wire.unpack(operand)
            f["tokens"] = self._held(f["tokens"], store, f["rows"])
            cache, x, counts = self.mixed_hidden(b, p, cache, f)
            picks, finite = self._head_pick(
                p, jnp.concatenate([x[:b], x[-1:]]),
                jnp.concatenate([f["uids"], f["uid"]]), f["poison"],
                jnp.concatenate([f["lengths"], f["pos0"][None] + c - 1]),
                1)
            slots = jnp.concatenate([f["rows"], f["row"][None]])
            return ((cache, store.at[slots].set(picks)),
                    _with_counts(_fold(picks, finite), counts))

        return run

    # -- wrapping -----------------------------------------------------------

    def body(self, kind: str, bucket: int):
        """The callable ``build`` jits; under a mesh shard_mapped, the
        operand, the token store and the result replicated."""
        run = {"decode": self._decode_fn, "prefill": self._prefill_fn,
               "verify": self._verify_fn,
               "mixed": self._mixed_fn}[kind](bucket)
        if self.mesh is None:
            return run
        carry = (self.pool_specs(), P())
        return jax.shard_map(
            run, mesh=self.mesh,
            in_specs=(tp_decode_specs(), carry, P()),
            out_specs=(carry, P()), check_vma=False)

    def build(self, kind: str, bucket: int):
        """The compiled program, the carry donated: XLA updates the
        blocks (and the token store) in place, which also needs the
        buffer to cross the program boundary in the layout the scatters
        and gathers work in — the pool's stored form
        (``decode/paged.py``; ``tests/test_chip_compile.py`` pins the
        compiled module)."""
        if kind in _POOL_OPS:
            return jax.jit(_POOL_OPS[kind], donate_argnums=(0,))
        return jax.jit(self.body(kind, bucket), donate_argnums=(1,))
