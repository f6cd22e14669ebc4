"""Fused Pallas paged-attention decode kernel: the block-table walk.

The decode engine's hot loop (``decode/engine.py``) reads the KV cache
in two passes: ``gather_paged_kv`` materializes each slot's contiguous
``[H_kv, T_cap, dh]`` f32 view from the block pool (an HBM round-trip
of the whole gathered layout, dequantized — 4x inflated under int8),
then ``models.lm.decode_attn`` reads it again. This kernel fuses the
two: the grid walks each slot's int32 block table directly (scalar
prefetch drives the BlockSpec index maps, so every grid step DMAs
exactly one physical KV block from the pool), streams the blocks
through VMEM with the per-block int8 dequant folded in, and runs the
single-query attention in-register — the gathered layout never exists
in HBM, and the pool bytes cross the bus once, at the STORAGE dtype.
That is the DECODE roofline's ``B * kv_bytes`` term taken at face
value (decode is KV-bandwidth-bound; see bench_decode.py).

Agreement with the oracle (the repo's differential discipline): the
kernel is engine-selectable (``EngineConfig(kernel="fused")``) with the
gather two-pass kept as the oracle. The walk stores each block's raw
score tile and V tile in VMEM scratch and then runs ``decode_attn``'s
ops on the assembled row — divide-by-sqrt, where-mask to -1e30,
softmax, PV — so the two compute the same values from the same pool
bytes. They do not compute the same BITS, and no test holds them to
that: the row is kept tiled ``[blocks, G, block]`` (every store a whole
tile — Mosaic refuses a store at a lane offset it cannot prove is a
multiple of 128, which is what a contiguous ``[G, T_cap]`` row needs
at block sizes under 128), so the softmax and PV sums run over a
different f32 reduction tree than the oracle's; and two separately
compiled XLA programs owe each other no bit identity anyway (on jax
0.9.0 the old contiguous-row kernel already sat 1-4 ULP off the oracle
in the interpreter, depending on how XLA:CPU fused each side). The
contract, per backend:

- *CPU interpreter* (``tests/test_pallas_paged_attention.py``): within
  8 units in the last place of the row's scale at every pool dtype
  (measured: under 2), and greedy token identity through the engine.
- *The chip at float32 matmul precision*
  (``jax.default_matmul_precision("highest")``; the walk's two dots
  carry no ``precision=`` of their own and take the ambient one at
  trace time, as the gather path's XLA dots do): greedy token identity
  through the engine, held by ``chip_smoke.py`` phase 2 on GPT-2 small.
- *The chip at the default precision — how serving runs*: NO identity.
  XLA and Mosaic round f32 operands to bf16 passes differently, and a
  model's near-tied logits turn that into different greedy picks (seen
  on the v5e, seeded GPT-2-small weights: one request of four left the
  gather path's tokens at its first generated position).
  ``chip_smoke.py`` records where the two first differ at the default
  on every run and asserts nothing about it; no ULP bound has been
  measured on the chip.

A streamed rescaling accumulator (the flash-style ``alpha`` fold,
``ops/pallas_attention.py``) is not needed at decode's T_cap (a few K
positions): the assembled row fits VMEM, and ``check_fused_shape``
refuses the shapes where it would not. Blocks entirely past a slot's
length are skipped — their score tiles are pinned to the mask value and
their V tiles to zero, which contribute exactly what the oracle's
masked positions contribute (an exp-underflow zero times a finite
byte).

Layout notes: grid is ``(slots, kv_heads, table_slots)`` with the
block walk innermost (scratch accumulates across it); GQA rides as a
``G = H / H_kv`` query-row dimension per kv head. Compiled for the v5e
at the engine's default block size and GPT-2-small heads, every pool
dtype (``tests/test_chip_compile.py``), and run there by
``chip_smoke.py``; a length-sorted slot order and wider tiles are
tuning, not semantics. Off the chip the kernel runs under
``interpret=True`` (tests/test_pallas_paged_attention.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the oracle's mask value (models.lm.decode_attn) — shared so the
# masked tiles stay bit-identical between the two paths
_NEG = -1e30


# Mosaic gives one kernel 16 MiB of scoped VMEM on a v5e. The two
# scratch rows below (f32, each per-block tile padded to (8, 128)) must
# leave room for the last grid step's temporaries: compiled for the
# v5e ahead of time, 12 MiB of scratch is accepted and 15 MiB refused.
FUSED_SCRATCH_LIMIT = 12 * 2 ** 20


def fused_scratch_bytes(g: int, blk: int, dh: int, mb: int) -> int:
    """VMEM the walk's score and V scratch take at this shape."""
    def pad(n, m):
        return -(-n // m) * m
    return 4 * mb * (pad(g, 8) * pad(blk, 128) + pad(blk, 8) * pad(dh, 128))


def check_fused_shape(g: int, blk: int, dh: int, mb: int) -> None:
    """Raise ``ValueError`` for a shape Mosaic would refuse — callers
    (``DecodeEngine.__init__``) refuse it up front, on every backend,
    instead of dying in the first decode step on the chip."""
    need = fused_scratch_bytes(g, blk, dh, mb)
    if need > FUSED_SCRATCH_LIMIT:
        raise ValueError(
            f"kernel='fused' keeps the whole attention row in VMEM: "
            f"{mb} blocks of {blk} positions at head dim {dh} need "
            f"{need / 2 ** 20:.1f} MiB of scratch, over the "
            f"{FUSED_SCRATCH_LIMIT // 2 ** 20} MiB the v5e's compiler "
            "accepts — use fewer or larger blocks per sequence, or "
            "kernel='gather'")


def _interpret_arg(interpret: bool | None) -> bool:
    # None = auto: interpret off-TPU, Mosaic on chip
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _walk_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, y_ref,
                 s_ref, v_scr, *, blk, mb, g, dh):
    """f32/bf16 variant: no per-block scales. See ``_walk_kernel_q8``
    for the int8 twin; the body is shared via ``_tile``."""
    _tile(table_ref, len_ref, q_ref, k_ref, v_ref, y_ref, s_ref, v_scr,
          None, None, blk=blk, mb=mb, g=g, dh=dh)


def _walk_kernel_q8(table_ref, len_ref, ksc_ref, vsc_ref, q_ref, k_ref,
                    v_ref, y_ref, s_ref, v_scr, *, blk, mb, g, dh):
    _tile(table_ref, len_ref, q_ref, k_ref, v_ref, y_ref, s_ref, v_scr,
          ksc_ref, vsc_ref, blk=blk, mb=mb, g=g, dh=dh)


def _tile(table_ref, len_ref, q_ref, k_ref, v_ref, y_ref, s_ref, v_scr,
          ksc_ref, vsc_ref, *, blk, mb, g, dh):
    i, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    length = len_ref[i]

    @pl.when(j * blk < length)
    def _():
        # one physical block, DMA'd straight off the table walk
        # (the index map already selected pool[table[i, j], h]);
        # dequant folds in here — the pool bytes crossed the bus at
        # the storage dtype
        kb = k_ref[0, 0].astype(jnp.float32)
        vb = v_ref[0, 0].astype(jnp.float32)
        if ksc_ref is not None:
            kb = kb * ksc_ref[i, j, h]
            vb = vb * vsc_ref[i, j, h]
        v_scr[j] = vb
        # raw scores, the oracle's op order: dot then / sqrt(dh)
        s_ref[j] = jax.lax.dot_general(
            q_ref[0, 0], kb, (((1,), (1,)), ((), ()))) / jnp.sqrt(
                jnp.asarray(dh, jnp.float32))

    @pl.when(j * blk >= length)
    def _():
        # a block entirely past the length: every position is masked,
        # so pin the tiles to what the oracle's mask produces (score
        # -> _NEG, V contribution -> exact zero) without reading it
        v_scr[j] = jnp.zeros((blk, dh), jnp.float32)
        s_ref[j] = jnp.full((g, blk), _NEG, jnp.float32)

    @pl.when(j == mb - 1)
    def _():
        # the assembled row, tiled [mb, g, blk]: decode_attn's ops
        # (where-mask to _NEG, softmax, PV) over the (block, position)
        # axes — same values, a different f32 reduction tree
        pos = (jax.lax.broadcasted_iota(jnp.int32, (mb, g, blk), 0) * blk
               + jax.lax.broadcasted_iota(jnp.int32, (mb, g, blk), 2))
        s = jnp.where(pos < length, s_ref[...],
                      jnp.asarray(_NEG, jnp.float32))
        m = jnp.max(jnp.max(s, axis=2, keepdims=True), axis=0,
                    keepdims=True)
        e = jnp.exp(s - m)
        p = e / jnp.sum(jnp.sum(e, axis=2, keepdims=True), axis=0,
                        keepdims=True)
        y_ref[0, 0] = jnp.sum(jax.lax.dot_general(
            p, v_scr[...], (((2,), (1,)), ((0,), (0,)))), axis=0)


def paged_decode_attn(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                      k_scale: jax.Array | None,
                      v_scale: jax.Array | None, tables: jax.Array,
                      lengths: jax.Array, *,
                      interpret: bool | None = None) -> jax.Array:
    """Fused single-query attention against a paged KV pool.

    ``q [B, H, dh]`` f32; ``pool_k/pool_v [n_blocks, H_kv, block, dh]``
    (ONE layer's pool, storage dtype); ``k_scale/v_scale
    [n_blocks, H_kv]`` f32 per-block int8 scales (None for f32/bf16);
    ``tables [B, MB]`` int32 physical block ids; ``lengths [B]`` the
    number of ATTENDABLE positions per slot (callers pass the decode
    convention ``lengths + 1``; must be >= 1 — the engine guarantees
    it, pad rows attend the scratch block's position 0). Returns
    ``y [B, H, dh]`` f32, within the module docstring's ULP bound of
    ``decode_attn(q, *gather_layer(...), lengths)``.

    The per-block scales ride as scalar-prefetch operands, pre-gathered
    to ``[B, MB, H_kv]`` outside the kernel — a few hundred f32s next
    to the block payload the walk is there to keep off the bus."""
    b, hq, dh = q.shape
    nb, hkv, blk, dh2 = pool_k.shape
    if dh2 != dh:
        raise ValueError(f"q head dim {dh} != pool head dim {dh2}")
    if hq % hkv:
        raise ValueError(f"query heads {hq} not divisible by kv heads "
                         f"{hkv}")
    g = hq // hkv
    mb = tables.shape[1]
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale/v_scale must both be set or both None")
    check_fused_shape(g, blk, dh, mb)
    run_interpret = _interpret_arg(interpret)
    qg = q.reshape(b, hkv, g, dh)
    scalar_args = [tables.astype(jnp.int32), lengths.astype(jnp.int32)]
    if k_scale is not None:
        scalar_args += [k_scale[tables], v_scale[tables]]  # [B, MB, Hkv]
        kernel = functools.partial(_walk_kernel_q8, blk=blk, mb=mb, g=g,
                                   dh=dh)
    else:
        kernel = functools.partial(_walk_kernel, blk=blk, mb=mb, g=g,
                                   dh=dh)

    def _pool_spec():
        # the block walk: grid step (i, h, j) pulls pool[table[i,j], h]
        return pl.BlockSpec((1, 1, blk, dh),
                            lambda i, h, j, tr, *_: (tr[i, j], h, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalar_args),
        grid=(b, hkv, mb),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh),
                         lambda i, h, j, *_: (i, h, 0, 0)),     # q
            _pool_spec(),                                       # k
            _pool_spec(),                                       # v
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh),
                               lambda i, h, j, *_: (i, h, 0, 0)),
        # one tile per walked block, indexed on the leading dim: every
        # store is a whole (sublane, lane) tile at any block size
        scratch_shapes=[pltpu.VMEM((mb, g, blk), jnp.float32),  # scores
                        pltpu.VMEM((mb, blk, dh), jnp.float32)],  # V
    )
    y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=run_interpret,
    )(*scalar_args, qg, pool_k, pool_v)
    return y.reshape(b, hq, dh)
