"""The decode-side K/V read as a walk over each row's block table.

``decode/paged.py`` keeps the cache as a pool of blocks, ``k [L,
n_blocks, block, H_kv*dh]`` and ``v [L, n_blocks, block, H_kv*dv]``
(two arrays, whose rows need not be equally wide; a LATENT pool's ``v``
has no lanes at all: its one row a position is key and value both), and
a sequence names its blocks through a table. The plain read
(``paged.gathered_decode_attn``) gathers every row's WHOLE table —
capacity, not length — into a copy and runs two products over the copy.
``walk_attn`` is the same two products over the rows where they lie: one
Pallas kernel a layer that, for each batch row, fetches only the row's
live blocks, those that hold its attendable positions ``[start,
length)``, from the pool in HBM into a double-buffered VMEM scratch,
several blocks a copy step, and folds each step's scores into a running
float32 maximum, sum and accumulator (the online softmax; where a head
has a SINK, a term of the denominator with no value row, the maximum
starts at the sink and the sum at 1, and the sink costs no column and
no copy). Nothing of a gathered view's size exists: no gather, no copy,
no ``[b, H, T_cap]`` scores, and the bytes that move are the live rows'.
A pool with ONE side is walked one-sided: each block is fetched once,
into one pair of buffers, and both products run over that buffer.

A table is read as a RING: block ``j`` of the sequence lies in entry ``j
mod MB``. For the full kind ``start`` is 0 and ``j < MB``, so the table
is read in order from its first entry; a window layer's short table
(``decode/paged.py``: the third paged kind) wraps, and its row starts
where its window does, under the sliding or the aligned rule alike: the
kernel is told the range and knows neither rule.

The conventions are the state kernels' (``ops/ssm.py``):
``ssm._interpreted()`` alone decides how the kernel runs, no caller
passes an ``interpret`` of its own, and nothing is chosen by a flag, a
field or the environment. Which pools take the walk at all is
``decode/paged.py::walks``'s to say, from the pool's kind, dtype and
shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ssm

# what the two sides' double-buffered copy steps may hold of a kernel's
# fast memory: a quarter of ``ssm._VMEM_BUDGET`` each for K and V (a
# pool of one side: the half of it for that side)
_STEP_BYTES = ssm._VMEM_BUDGET // 8
_MASKED = -1e30


def blocks_a_step(block: int, row_bytes: int, max_blocks: int,
                  sides: int = 2) -> int:
    """How many blocks one copy step fetches: as many as fit
    ``_STEP_BYTES`` a buffer (twice that where the pool has one side
    and the other's buffers do not exist), a power of two, at least as
    many as make the step's positions whole 128-lane tiles of the
    scores, and no more than a table holds or than 64 (1,024 positions
    of scores a step at blocks of 16). It follows from the row's bytes
    and the fast memory, not from a knob: a 1,280-lane bf16 row gives
    16 blocks of 16 (640 KB a step and side), a 512-lane one 64, a
    latent pool's one 640-lane row 64."""
    fit = max(1, _STEP_BYTES * 2 // sides // (block * row_bytes))
    c = 1 << (fit.bit_length() - 1)
    c = max(c, -(-ssm._LANES // block))
    return min(c, 1 << (max_blocks - 1).bit_length(), 64)


def _walk_kernel(layer_ref, tables_ref, starts_ref, lengths_ref, q_ref,
                 *rest, sides: int, steps: int, scale: float, sunk: bool):
    """One batch row a grid step: the blocks ``starts // block ..
    (lengths - 1) // block`` of its sequence, ``steps`` of them a copy
    step, each from the table's entry of its number modulo the table's
    width. ``sides`` pools stand after the query, ``k_hbm`` and
    ``v_hbm``, or the ONE whose rows are key and value both; ``kbuf [2,
    steps * block, J]`` and ``vbuf [2, steps * block,
    Jv]`` are the two buffers of each side, each as wide as its side's
    row (one side: one pair, which both products read); ``slot_ref``
    says which of them
    the row's FIRST copy step is in (the row before started it, before
    its own last product), ``sems [side, buffer]`` count the copies.
    Where the call asks for the softmax statistics, two more outputs
    stand before the scratch: each head's score maximum and its sum of
    ``exp(s - m)``, a whole tile of lanes wide. ``sunk``: one more
    input stands before the outputs, ``sink_ref [H, 1]``, each head's
    sink: ``exp(sink - m)`` is in the sum from the start."""
    hbms, rest = rest[:sides], rest[sides:]
    if sunk:
        sink_ref, *rest = rest
    *outs, sems, slot_ref, m_ref, l_ref, acc_ref = rest
    (o_ref, *stats), bufs = outs[:-sides], outs[-sides:]
    kbuf, vbuf = bufs[0], bufs[-1]
    r = pl.program_id(0)
    blk = kbuf.shape[1] // steps
    entries = tables_ref.shape[1]
    layer = layer_ref[0]

    def head(row):
        return lax.div(starts_ref[row], blk)

    def live(row):
        # a padded row (length 0 or 1, its table all scratch) walks the
        # scratch block and nothing else
        return jnp.maximum(pl.cdiv(lengths_ref[row], blk) - head(row), 1)

    def fetched(row, c):
        return jnp.minimum(live(row) - c * steps, steps)

    def copies(row, c, buf, act: str):
        """``start`` or ``wait`` for the copies, one a side, of every
        block the row holds of its step ``c``, into buffer ``buf``."""
        # a row holds no more blocks than its table has entries, so the
        # step's entries wrap at most once past its first
        entry0 = lax.rem(head(row) + c * steps, entries)

        def one(i, _):
            entry = entry0 + i
            phys = tables_ref[row, jnp.where(entry < entries, entry,
                                             entry - entries)]
            dst = pl.ds(pl.multiple_of(i * blk, blk), blk)
            for side, (hbm, vmem) in enumerate(zip(hbms, bufs)):
                getattr(pltpu.make_async_copy(
                    hbm.at[layer, phys], vmem.at[buf, dst],
                    sems.at[side, buf]), act)()
            return _
        lax.fori_loop(0, fetched(row, c), one, None)

    @pl.when(r == 0)
    def _():
        slot_ref[0] = 0
        copies(0, 0, 0, "start")

    first = slot_ref[0]
    n_steps = pl.cdiv(live(r), steps)
    start, length = starts_ref[r], lengths_ref[r]
    if sunk:
        m_ref[...] = sink_ref[...]
        l_ref[...] = jnp.ones_like(l_ref)
    else:
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[...]                                      # [H, J]

    def step(c, _):
        buf = lax.rem(first + c, 2)

        # what comes next, into the other buffer, before this step's
        # products: the row's next step, or the NEXT row's first
        last = c + 1 == n_steps

        @pl.when(jnp.logical_or(~last, r + 1 < pl.num_programs(0)))
        def _():
            copies(jnp.where(last, r + 1, r), jnp.where(last, 0, c + 1),
                   1 - buf, "start")

        copies(r, c, buf, "wait")

        # the blocks of the step the row does not hold were not
        # fetched: what the buffer has there is some earlier step's (a
        # NaN of ANOTHER row's among it). Their scores are masked; their
        # values must be zeros, for 0 * NaN is NaN (one side: the rows
        # are the keys too, and a zero key's score is masked all the same)
        def dead(i, _):
            vbuf[buf, pl.ds(pl.multiple_of(i * blk, blk), blk), :] = (
                jnp.zeros((blk, vbuf.shape[2]), vbuf.dtype))
            return _
        lax.fori_loop(fetched(r, c), steps, dead, None)

        k = kbuf[buf]                                   # [T, J]
        v = k if sides == 1 else vbuf[buf]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        pos = ((head(r) + c * steps) * blk
               + lax.broadcasted_iota(jnp.int32, s.shape, 1))
        # rows of the first live block before the start and stale rows
        # of the last one meet an exact 0 (and a NaN there still poisons
        # the row, as in the plain read)
        s = jnp.where((pos >= start) & (pos < length), s, _MASKED)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return _

    lax.fori_loop(0, n_steps, step, None)
    slot_ref[0] = lax.rem(first + n_steps, 2)
    o_ref[...] = acc_ref[...] / l_ref[...]
    for out, ref in zip(stats, (m_ref, l_ref)):
        out[...] = jnp.broadcast_to(ref[...], out.shape)


@functools.partial(jax.jit,
                   static_argnames=("steps", "scale", "interpret", "stats"))
def _walk(layer, tables, starts, lengths, q, k_pool, v_pool, sink=None, *,
          steps: int, scale: float, interpret: bool, stats: bool = False):
    """The kernel's call, jitted on its own: ``layer [1]`` is an
    operand, so the calls of every layer of a step program are ONE
    traced function — lowered (the kernel to Mosaic's module) once a
    program and called a layer, not once a layer. A program of 36
    layers spent 1.4 s of every start-up lowering 36 copies."""
    b, h, j = q.shape
    # a V side of no lanes is no operand: the K side's rows are the
    # values too, and the result is as wide as they are
    pools = [k_pool, v_pool] if v_pool.shape[3] else [k_pool]
    blk, jv = k_pool.shape[2], pools[-1].shape[3]
    row = pl.BlockSpec((None, h, j), lambda r, *_: (r, 0, 0))
    whole = pl.BlockSpec(memory_space=pl.ANY)
    out_specs = pl.BlockSpec((None, h, jv), lambda r, *_: (r, 0, 0))
    out_shape = jax.ShapeDtypeStruct((b, h, jv), jnp.float32)
    in_specs, operands = [row] + [whole] * len(pools), [q, *pools]
    if sink is not None:
        in_specs.append(pl.BlockSpec((h, 1), lambda r, *_: (0, 0)))
        operands.append(sink.astype(jnp.float32).reshape(h, 1))
    if stats:
        stat = pl.BlockSpec((None, h, ssm._LANES), lambda r, *_: (r, 0, 0))
        wide = jax.ShapeDtypeStruct((b, h, ssm._LANES), jnp.float32)
        out_specs, out_shape = ([out_specs, stat, stat],
                                [out_shape, wide, wide])
    return pl.pallas_call(
        functools.partial(_walk_kernel, sides=len(pools), steps=steps,
                          scale=scale, sunk=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b,),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[
                *(pltpu.VMEM((2, steps * blk, side.shape[3]), side.dtype)
                  for side in pools),
                pltpu.SemaphoreType.DMA((len(pools), 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, jv), jnp.float32)]),
        out_shape=out_shape,
        interpret=interpret,
    )(layer, tables, starts, lengths, *operands)


def walk_attn(k_pool: jax.Array, v_pool: jax.Array, layer: int,
              q: jax.Array, tables: jax.Array, starts: jax.Array,
              lengths: jax.Array, scale: float, stats: bool = False,
              sink=None):
    """Single-query attention of ``b`` rows over their own blocks of
    one layer of the pool, where they lie. ``k_pool [L, n_blocks,
    block, J]`` and ``v_pool [L, n_blocks, block, Jv]`` stay in HBM
    whole (``Jv`` 0, a latent pool: the K side's rows are the values
    too, each block is fetched ONCE for both products, and the result
    is ``[b, H, J]``); ``q [b, H, J]`` in the pool's dtype
    is each head's query laid out FOR a stored row (zero outside its KV
    head's lanes: ``decode/paged.py`` builds it); ``tables [b, MB]``,
    block ``j`` of a sequence in entry ``j mod MB``; ``starts [b]`` /
    ``lengths [b]`` the first attendable position and the one after the
    last (the full kind: ``starts`` 0; a ring: no more blocks between
    them than ``MB``). Returns ``[b, H, Jv]`` float32: ``softmax(scale *
    q K^T) V`` over the positions ``starts <= t < lengths``, of which
    head ``h`` keeps its KV head's lanes. ``sink [H]`` float32: head
    ``h``'s softmax has ``exp(sink_h - m)`` more in its denominator
    (``models/attention.py::softmax_stats``) — the kernel's running
    maximum starts at the sink and its sum at 1; without one the call
    is the kernel it was.

    The layer is a scalar operand, so every layer's call is the same
    kernel to compile. Operands in the pool's dtype, sums in float32. A
    row's dead blocks (those that hold no position of its range) are
    never fetched: bytes there, a NaN among them, do not reach the
    result.

    ``stats``: ``(o, m [b, H], l [b, H])``, the result beside each
    head's score maximum and its sum of ``exp(s - m)``, which the
    kernel holds in its scratch anyway (a sink counted in both): what a
    join with another read of the same queries needs
    (``decode/paged.py::join_reads``). A row of length 0 reads its
    first block masked whole: without a sink ``m`` is the mask's value
    and the join gives the read no weight."""
    interpret = ssm._interpreted()
    for j in (q.shape[-1], v_pool.shape[-1]):
        if not interpret and j % ssm._LANES:
            raise ValueError(ssm._UNTILED.format(d=j))
    # a copy step holds the same blocks of both sides: the wider row's
    # bytes say how many
    wide = max(k_pool.shape[-1], v_pool.shape[-1])
    steps = blocks_a_step(k_pool.shape[2], wide * k_pool.dtype.itemsize,
                          tables.shape[1], sides=2 if v_pool.shape[-1] else 1)
    got = _walk(jnp.asarray([layer], jnp.int32), tables, starts, lengths,
                q, k_pool, v_pool, sink, steps=steps, scale=scale,
                interpret=interpret, stats=stats)
    if not stats:
        return got
    o, m, l = got
    return o, m[..., 0], l[..., 0]
