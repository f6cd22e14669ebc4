"""The serving engine's compiled step programs: the one builder.

``DecodeEngine`` (``decode/engine.py``) is the scheduler; what a step
program is made of lives here, put together from two sides. The model
(``models/face.py::ServedModel``): embedding, norm, an attention
layer's q/k/v and output projection, a recurrent layer's step and
chunk, FFN, final norm and tied head — asked of the params, never of
their class. The cache (``decode/paged.py``): the pool and the
recurrent state, the writes and the ONE read of each side
(``stored_decode_attn`` for decode and verify rows,
``gathered_chunk_attn`` for a prefill chunk). Between them, written
once: the walk over the model's layers (``_trunk``, so prefill and
decode numerics cannot drift), head -> poison -> pick -> finite flags
(``_head_pick``), and the wrapping (``shard_map`` under a model-axis
mesh, ``jit``, the donated cache). The builder gets ``cfg``, the
model's ``CacheSpec``, the vocabulary and the mesh as plain values: it
never sees a sequence, a slot table or a metrics writer.

A step program is ``run(params, cache, *host operands) -> (cache,
picks, ...flags)``. ``cache`` is the donated operand — the ``PagedKV``,
or for a model with recurrent layers the pair ``(PagedKV,
RecurrentState)`` — and comes back in the same form. The jitted
callable keeps the name ``run`` (the profiler's ``jit_run``, which
``benchmark/engine_trace.py`` reads). Under a mesh (the Megatron decode
layout, ``parallel/lm.py``) the pool is head-sharded, the embedding
vocab-parallel, and the local logits are gathered in-graph so the pick
(keys fold uid and position, never the shard) draws the same
everywhere.

``jax.named_scope`` names (``decode`` / ``prefill``, ``ssm``, ``head``,
``sample``) are metadata only (``utils/trace_analysis`` ``SCOPES``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.face import ATTN, CacheSpec, take
from ..parallel.collectives import all_gather, all_reduce
from ..parallel.lm import tp_decode_specs, vp_embed
from ..parallel.mesh import MODEL_AXIS
from ..runtime.guardrails import rows_finite
from .paged import (PagedKV, RecurrentState, SCRATCH_BLOCK, copy_block,
                    copy_block_rows, gathered_chunk_attn, implant_block,
                    init_pool, init_state, stored_decode_attn, write_chunk,
                    write_rows)
from .sampling import make_pick

# poison operand values (chaos nan_logits injection rides a runtime
# operand, so arming a fault never recompiles)
POISON_NONE = -1
POISON_ALL = -2

# the pool-only programs: block ids (and row counts) are traced operands,
# so one compiled copy each serves every block; the pool is donated
_POOL_OPS = {"cow": copy_block, "cow_rows": copy_block_rows,
             "implant": implant_block}


class StepPrograms:
    """The programs of one engine configuration over one model's face:
    ``build(kind, bucket)`` is what the engine dispatches, ``body`` the
    same callable before ``jit`` (what the static report lowers)."""

    def __init__(self, cfg, spec: CacheSpec, vocab: int, mesh=None):
        self.cfg = cfg
        self.spec = spec
        self.mesh = mesh
        self.pick = make_pick(cfg.temperature, cfg.top_k, cfg.top_p, vocab,
                              cfg.seed)

    # -- the cache -------------------------------------------------------

    def init_cache(self) -> tuple[PagedKV, RecurrentState | None]:
        """The zero pool (head-sharded under a mesh) and the recurrent
        layers' state by slot beside it (None for a model with none)."""
        cfg, spec = self.cfg, self.spec
        pool = init_pool(spec.kv_layers, cfg.n_blocks, spec.kv_heads,
                         cfg.block_size, spec.head_dim, cfg.kv_dtype)
        if self.mesh is not None:
            pool = jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
                pool, self.pool_specs())
        state = None
        if spec.rec_layers:
            state = init_state(spec.rec_layers, cfg.max_slots,
                               d_inner=spec.d_inner, d_state=spec.d_state,
                               d_conv=spec.d_conv)
        return pool, state

    def pool_specs(self) -> PagedKV:
        """Heads are contiguous in a stored row's minor axis
        (``H_kv*dh``), so sharding that axis shards the heads."""
        arr = P(None, None, None, MODEL_AXIS)
        sc = (P(None, None, MODEL_AXIS) if self.cfg.kv_dtype == "int8"
              else None)
        return PagedKV(arr, arr, sc, sc, self.spec.head_dim)

    # -- the model's forward over the cache -------------------------------

    def _embed(self, p, tokens, positions):
        return p.embed(tokens, positions,
                       take if self.mesh is None else vp_embed)

    def _trunk(self, p, cache, x, positions, write_attn, mix=None):
        """The walk over ``p.layers`` every program runs. Attention:
        norm, q/k/v, the caller's ``write_attn(i, pool, q, k, v) ->
        (pool, y [N, h_loc, dh])`` (where the programs differ: batched
        single-token writes and per-slot reads, or one slot's chunk),
        output projection. Recurrent: norm, the caller's ``mix(i,
        state, a) -> (state, y [N, d])``. Then the FFN; under a mesh
        both residual adds take the Megatron all-reduce."""
        tp = self.mesh is not None
        pool, state = cache if self.spec.rec_layers else (cache, None)
        n = x.shape[0]
        for l, (kind, i) in enumerate(p.layers):
            a = p.norm(p.norm_in[l], x)
            if kind == ATTN:
                q, k, v = p.attn_qkv(i, a, positions, self.spec.head_dim,
                                     self.cfg.use_rope)
                pool, y = write_attn(i, pool, q, k, v)
                y = p.attn_out(i, y.reshape(n, -1))
            else:
                state, y = mix(i, state, a)
            x = x + (all_reduce(y, MODEL_AXIS) if tp else y)
            f = p.ffn(l, p.norm(p.norm_ff[l], x))
            x = x + (all_reduce(f, MODEL_AXIS) if tp else f)
        return (pool if state is None else (pool, state)), x

    def logits(self, p, x):
        """Final norm and tied head of ``x [N, d]``; under a mesh each
        shard scores ``V/n`` columns and the gather completes the row."""
        logits = p.head(x)
        if self.mesh is not None:
            logits = all_gather(logits, MODEL_AXIS, dim=1)
        return logits

    def decode_hidden(self, b: int, p, cache, tables, lengths, tokens,
                      rows=None):
        """The decode program up to the head: each of ``b`` rows' token
        written at its own position and attended over its blocks as
        stored; a recurrent layer advances each row's own state
        (``rows [b]``: the slot's state row, the scratch row for a
        padded one). Returns ``(cache, x [b, d])``."""
        cfg = self.cfg
        x = self._embed(p, tokens, lengths)             # [b, d]
        slot_phys = lengths // cfg.block_size
        off = lengths % cfg.block_size

        def write_attn(l, pool, q, k, v):
            phys = tables[jnp.arange(b), slot_phys]
            pool = write_rows(pool, l, phys, off, k, v, cfg.kv_dtype)
            return pool, stored_decode_attn(pool, l, q, tables,
                                            lengths + 1)

        def mix(i, state, a):
            with jax.named_scope("ssm"):
                tail = state.conv[i, rows]
                y, tail, s = p.recurrent_step(
                    i, a, tail.reshape(b, -1, state.ssm.shape[-1]),
                    state.ssm[i, rows])
                state = state._replace(
                    conv=state.conv.at[i, rows].set(tail.reshape(b, -1)),
                    ssm=state.ssm.at[i, rows].set(s))
            return state, y

        return self._trunk(p, cache, x, lengths, write_attn, mix)

    def prefill_hidden(self, c: int, p, cache, table, pos0, tokens,
                       row=None):
        """The prefill program up to the head: ``c`` prompt tokens of
        ONE slot enter the cache through its block table and attend
        causally over the gathered view; a recurrent layer scans the
        chunk through the slot's state (``row``), which is zero at
        position 0 whatever the row still holds (every prefill, and
        every replay, starts at 0). Returns ``(cache, x [c, d])``."""
        cfg = self.cfg
        positions = pos0 + jnp.arange(c)
        x = self._embed(p, tokens, positions)           # [c, d]

        def write_attn(l, pool, q, k, v):
            pool = write_chunk(pool, l, table, pos0, k, v, cfg.kv_dtype)
            return pool, gathered_chunk_attn(pool, l, q, table, pos0)

        def mix(i, state, a):
            with jax.named_scope("ssm"):
                fresh = pos0 == 0
                tail = jnp.where(fresh, 0.0, state.conv[i, row])
                y, tail, s = p.recurrent_chunk(
                    i, a, tail.reshape(-1, state.ssm.shape[-1]),
                    jnp.where(fresh, 0.0, state.ssm[i, row]))
                state = state._replace(
                    conv=state.conv.at[i, row].set(tail.reshape(-1)),
                    ssm=state.ssm.at[i, row].set(s))
            return state, y

        return self._trunk(p, cache, x, positions, write_attn, mix)

    def _head_pick(self, p, x, uids, poison, pos, ahead: int):
        """head -> poison -> pick -> finite flags, for all three
        bodies: the logits of ``x [n, d]``, NaN'd where the chaos
        operand names the row's uid (or is ``POISON_ALL``; a false
        ``where`` leaves a row bit-identical), the in-graph pick keyed
        on ``(uid, pos + ahead)``, and each row's all-finite flag (the
        serving guardrail, on the same readback as the picks). The
        prefill's one row passes scalars and gets scalars."""
        one = jnp.ndim(uids) == 0
        with jax.named_scope("head"):
            logits = self.logits(p, x)
        bad = jnp.logical_or(uids == poison, poison == POISON_ALL)
        logits = jnp.where(bad if one else bad[:, None],
                           jnp.asarray(jnp.nan, logits.dtype), logits)
        with jax.named_scope("sample"):
            picks = (self.pick(logits, uids[None], (pos + ahead)[None])
                     if one else self.pick(logits, uids, pos + ahead))
        if one:
            return picks[0], rows_finite(logits)[0]
        return picks, rows_finite(logits)

    # -- the three bodies --------------------------------------------------

    def _decode_fn(self, b: int):
        """A ``b``-slot bucket's decode step."""

        @jax.named_scope("decode")
        def run(p, cache, tables, lengths, tokens, uids, poison, *rows):
            cache, x = self.decode_hidden(b, p, cache, tables, lengths,
                                          tokens, *rows)
            return (cache,) + self._head_pick(p, x, uids, poison,
                                              lengths, 1)

        return run

    def _verify_fn(self, b: int):
        """The speculative verify body: ``speculate + 1`` decode
        sub-steps UNROLLED and sequential, each reading the cache its
        predecessor wrote (int8's cross-row requant coupling rules out
        a position-parallel verify); the acceptance chain ``alive_i = alive_{i-1} and draft_i ==
        pick_{i-1}`` masks each drafted row's KV WRITE by redirecting a
        dead row's scatter to the scratch block, so a rejected tail
        never lands. Returns ``(pool, picks [b, k+1], accepted [b],
        finite [b, k+1])``."""
        cfg = self.cfg
        k = cfg.speculate

        @jax.named_scope("decode")
        def run(p, pool, tables, lengths, tokens, uids, drafts, dlens,
                poison):
            rows = jnp.arange(b)
            alive = jnp.ones((b,), bool)
            acc = jnp.zeros((b,), jnp.int32)
            cur = tokens
            picks_all, finite_all = [], []
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

                pool, x = self._trunk(p, pool, x, pos, write_attn)
                pk, finite = self._head_pick(p, x, uids, poison, pos, 1)
                picks_all.append(pk)
                finite_all.append(finite)
                if i < k:
                    d = drafts[:, i]
                    alive = jnp.logical_and(
                        alive, jnp.logical_and(i < dlens, d == pk))
                    acc = acc + alive.astype(jnp.int32)
                    cur = d
            return (pool, jnp.stack(picks_all, 1), acc,
                    jnp.stack(finite_all, 1))

        return run

    def _prefill_fn(self, c: int):
        """One slot's prefill chunk of ``c`` tokens; the host uses the
        final row's pick only when the chunk completes the prompt."""

        @jax.named_scope("prefill")
        def run(p, cache, table, pos0, tokens, uid, poison, *row):
            cache, x = self.prefill_hidden(c, p, cache, table, pos0,
                                           tokens, *row)
            return (cache,) + self._head_pick(p, x[-1:], uid, poison,
                                              pos0, c)

        return run

    # -- wrapping -----------------------------------------------------------

    def body(self, kind: str, bucket: int):
        """The callable ``build`` jits; under a mesh shard_mapped, the
        host operands (5; verify adds drafts and their lengths) and the
        picks and flags (2; verify adds the accepted counts)
        replicated."""
        run = {"decode": self._decode_fn, "prefill": self._prefill_fn,
               "verify": self._verify_fn}[kind](bucket)
        if self.mesh is None:
            return run
        n_aux, n_out = (7, 4) if kind == "verify" else (5, 3)
        return jax.shard_map(
            run, mesh=self.mesh,
            in_specs=(tp_decode_specs(), self.pool_specs())
            + (P(),) * n_aux,
            out_specs=(self.pool_specs(),) + (P(),) * (n_out - 1),
            check_vma=False)

    def build(self, kind: str, bucket: int):
        """The compiled program, the cache donated: XLA updates the
        blocks in place, which also needs the buffer to cross the
        program boundary in the layout the scatters and gathers work
        in — the pool's stored form (``decode/paged.py``;
        ``tests/test_chip_compile.py`` pins the compiled module)."""
        if kind in _POOL_OPS:
            return jax.jit(_POOL_OPS[kind], donate_argnums=(0,))
        return jax.jit(self.body(kind, bucket), donate_argnums=(1,))
