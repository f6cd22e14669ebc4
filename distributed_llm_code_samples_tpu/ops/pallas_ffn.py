"""Fused Pallas TPU kernels for the FFN block — the hot-op path.

The FFN sublayer ``y = relu(x @ w1.T) @ w2.T`` decomposes exactly over the
ffn dimension: ``y = sum_k relu(x @ w1_k.T) @ w2_k.T`` (ReLU is elementwise,
so each ffn slice is independent). These kernels exploit that to fuse the
whole block: the ``[tokens, ffn]`` hidden activation never round-trips to
HBM — it lives tile-by-tile in VMEM between the two MXU contractions. The
plain-XLA path (``ops.ffn``) keeps the same math; these kernels are the
hand-scheduled equivalent (the role CUDA kernels played underneath the
reference's torch ops, here first-party).

Three kernels mirror the hand-written VJP structure (``train_ffns.py:54-70``):

- ``ffn_fwd_pallas``    — fused fwd; grid (token tiles x ffn tiles), ffn as
  the reduction axis, f32 VMEM accumulator.
- ``ffn_bwd_dx_pallas`` — input grad with pre-activation *recompute* (the
  block checkpoints only its input, ``train_ffns.py:63``); reduces over ffn.
- ``ffn_bwd_dw_pallas`` — both weight grads; reduces over token tiles.

``pallas_ffn_block`` wires them into ``jax.custom_vjp`` so the kernels ARE
the differentiation rule, exactly like ``ops.ffn.ffn_block``. All kernels
run under ``interpret=True`` on CPU for the hardware-free test suite.

**Measured verdict (r2, v5e-class chip, bench shape d=768/L=24/8k tok):
XLA stays the default training path.** The XLA path runs at 0.92 MFU —
the fused kernels compile and run (26.4 vs 16.0 steps/s, ratio ~0.60)
but cannot win: the 3-kernel VJP split recomputes ``h`` and ``dy·w2`` in
both backward kernels (18·T·d·f total matmul FLOPs vs the XLA path's
14·T·d·f), and a fused dx+dw kernel is blocked by conflicting reduction
axes (dx reduces over ffn, dw over tokens — an output block revisited
non-consecutively across the grid cannot accumulate in VMEM). With XLA
at 92% of the MXU peak there is no headroom for the extra FLOPs to
hide.

**Round-5: the flash recipe applied** (the exact fix that took the
flash kernels 7→41 TF/s on chip in r4): every MXU operand is cast to
bf16 by default on the compiled path (``mxu_bf16`` — f32 operands make
Mosaic emit multi-pass dots, ~3x the single bf16 pass XLA's default f32
precision lowers to), and the default block sizes are the values an
on-chip sweep of the tile grid settled on (``_BLOCK_T``, ``_BLOCK_F``,
``_DW_BLOCK_F``; a caller that wants another passes ``block_t`` /
``block_f``). The 18-vs-14 FLOP structure is inherent to the 3-kernel
split, so the arithmetic ceiling is 14/18 ≈ 0.78 of an
equally-efficient XLA. No cell of ``BENCHMARK.json`` runs ``--pallas``:
the ratio above is the last measured one, and ROADMAP C4 is the item
that deletes this module.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mxu(x, mxu_bf16: bool):
    """Cast an MXU operand to bf16 when the bf16-MXU policy is on (the
    flash recipe). THE canonical definition: ``pallas_attention`` (and,
    through it, ``pallas_xent``) imports this — imports flow
    attention -> ffn only, never back, so there is no cycle."""
    return x.astype(jnp.bfloat16) if mxu_bf16 else x


def _resolve_mxu_bf16(mxu_bf16, interpret: bool,
                      env_var: str | None = None) -> bool:
    """Default the bf16-MXU policy: on for the compiled TPU path (the
    numerics class of the XLA oracle under JAX's default f32 matmul
    precision), off under the interpreter (the CPU suite then checks
    exact f32 math against the oracle). An explicit ``mxu_bf16`` always
    wins; ``env_var`` names an optional env override between the two
    (the flash kernels pass ``FLASH_MXU_BF16``). Canonical definition —
    the other Pallas modules import it from here."""
    if mxu_bf16 is not None:
        return bool(mxu_bf16)
    if env_var is not None:
        env = os.environ.get(env_var)
        if env is not None:
            return env != "0"
    return not interpret


def _pick_block(size: int, preferred: int, quantum: int) -> int:
    """Largest divisor of ``size`` that is <= preferred and a multiple of
    ``quantum`` (falls back to ``size`` itself for tiny shapes)."""
    best = None
    b = quantum
    while b <= min(size, preferred):
        if size % b == 0:
            best = b
        b += quantum
    return best if best is not None else size

# f32 min sublane tile is 8; lanes are 128 (guide: Tiling Constraints)
_TOKEN_QUANTUM = 8
_FFN_QUANTUM = 128


# default tiles (token x ffn): the swept values on the v5e; the weight-grad
# kernel's ffn tile is smaller for the reason its docstring gives
_BLOCK_T = 256
_BLOCK_F = 512
_DW_BLOCK_F = 256


def _fwd_kernel(x_ref, w1_ref, w2_ref, y_ref, acc_ref, *, mxu_bf16):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    h = jnp.dot(_mxu(x_ref[:], mxu_bf16), _mxu(w1_ref[:], mxu_bf16).T,
                preferred_element_type=jnp.float32)
    a_dtype = jnp.bfloat16 if mxu_bf16 else x_ref.dtype
    a = jnp.maximum(h, 0.0).astype(a_dtype)
    acc_ref[:] += jnp.dot(a, _mxu(w2_ref[:], mxu_bf16).T,
                          preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        y_ref[:] = acc_ref[:].astype(y_ref.dtype)


def ffn_fwd_pallas(w1: jax.Array, w2: jax.Array, x: jax.Array, *,
                   block_t: int | None = None,
                   block_f: int | None = None,
                   interpret: bool = False,
                   mxu_bf16: bool | None = None) -> jax.Array:
    """Fused linear->ReLU->linear forward. ``w1 [ffn, d]``, ``w2 [d, ffn]``,
    ``x [T, d]`` -> ``[T, d]``; hidden tiles stay in VMEM. ``mxu_bf16``
    defaults on for the compiled TPU path (the flash recipe — f32
    accumulation throughout)."""
    T, d = x.shape
    ffn = w1.shape[0]
    bt = _pick_block(T, block_t or _BLOCK_T,
                     _TOKEN_QUANTUM)
    bf = _pick_block(ffn, block_f or _BLOCK_F,
                     _FFN_QUANTUM)
    grid = (T // bt, ffn // bf)
    return pl.pallas_call(
        functools.partial(_fwd_kernel,
                          mxu_bf16=_resolve_mxu_bf16(mxu_bf16, interpret)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, d), lambda i, k: (i, 0)),   # x tile
            pl.BlockSpec((bf, d), lambda i, k: (k, 0)),   # w1 ffn-slice
            pl.BlockSpec((d, bf), lambda i, k: (0, k)),   # w2 ffn-slice
        ],
        out_specs=pl.BlockSpec((bt, d), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * T * d * ffn,
            bytes_accessed=(T * d + 2 * d * ffn + T * d) * x.dtype.itemsize,
            transcendentals=0),
        interpret=interpret,
    )(x, w1, w2)


def _bwd_dx_kernel(x_ref, dy_ref, w1_ref, w2_ref, dx_ref, acc_ref, *,
                   mxu_bf16):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # recompute the pre-activation slice (checkpoint-block-inputs-only)
    h = jnp.dot(_mxu(x_ref[:], mxu_bf16), _mxu(w1_ref[:], mxu_bf16).T,
                preferred_element_type=jnp.float32)
    da = jnp.dot(_mxu(dy_ref[:], mxu_bf16), _mxu(w2_ref[:], mxu_bf16),
                 preferred_element_type=jnp.float32)
    dh_dtype = jnp.bfloat16 if mxu_bf16 else x_ref.dtype
    dh = jnp.where(h <= 0.0, 0.0, da).astype(dh_dtype)
    acc_ref[:] += jnp.dot(dh, _mxu(w1_ref[:], mxu_bf16),
                          preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        dx_ref[:] = acc_ref[:].astype(dx_ref.dtype)


def ffn_bwd_dx_pallas(dy: jax.Array, w1: jax.Array, w2: jax.Array,
                      x: jax.Array, *, block_t: int | None = None,
                      block_f: int | None = None,
                      interpret: bool = False,
                      mxu_bf16: bool | None = None) -> jax.Array:
    """Input gradient ``dx = (relu'(x w1^T) * (dy w2)) w1`` fused."""
    T, d = x.shape
    ffn = w1.shape[0]
    bt = _pick_block(T, block_t or _BLOCK_T,
                     _TOKEN_QUANTUM)
    bf = _pick_block(ffn, block_f or _BLOCK_F,
                     _FFN_QUANTUM)
    grid = (T // bt, ffn // bf)
    return pl.pallas_call(
        functools.partial(_bwd_dx_kernel,
                          mxu_bf16=_resolve_mxu_bf16(mxu_bf16, interpret)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, d), lambda i, k: (i, 0)),   # x tile
            pl.BlockSpec((bt, d), lambda i, k: (i, 0)),   # dy tile
            pl.BlockSpec((bf, d), lambda i, k: (k, 0)),   # w1 slice
            pl.BlockSpec((d, bf), lambda i, k: (0, k)),   # w2 slice
        ],
        out_specs=pl.BlockSpec((bt, d), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dy, w1, w2)


def _bwd_dw_kernel(x_ref, dy_ref, w1_ref, w2_ref, dw1_ref, dw2_ref,
                   acc1_ref, acc2_ref, *, mxu_bf16):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        acc1_ref[:] = jnp.zeros_like(acc1_ref)
        acc2_ref[:] = jnp.zeros_like(acc2_ref)

    x_m = _mxu(x_ref[:], mxu_bf16)
    dy_m = _mxu(dy_ref[:], mxu_bf16)
    h = jnp.dot(x_m, _mxu(w1_ref[:], mxu_bf16).T,
                preferred_element_type=jnp.float32)
    op_dtype = jnp.bfloat16 if mxu_bf16 else x_ref.dtype
    a = jnp.maximum(h, 0.0).astype(op_dtype)
    da = jnp.dot(dy_m, _mxu(w2_ref[:], mxu_bf16),
                 preferred_element_type=jnp.float32)
    dh = jnp.where(h <= 0.0, 0.0, da).astype(op_dtype)
    # dw1 slice [bf, d] = dh^T x ; dw2 slice [d, bf] = dy^T a
    acc1_ref[:] += jnp.dot(dh.T, x_m, preferred_element_type=jnp.float32)
    acc2_ref[:] += jnp.dot(dy_m.T, a, preferred_element_type=jnp.float32)

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        dw1_ref[:] = acc1_ref[:].astype(dw1_ref.dtype)
        dw2_ref[:] = acc2_ref[:].astype(dw2_ref.dtype)


def ffn_bwd_dw_pallas(dy: jax.Array, w1: jax.Array, w2: jax.Array,
                      x: jax.Array, *, block_t: int | None = None,
                      block_f: int | None = None,
                      interpret: bool = False,
                      mxu_bf16: bool | None = None):
    """Both weight gradients, fused, reducing over token tiles:
    ``dw1 = (relu'(h) * (dy w2))^T x``, ``dw2 = dy^T relu(h)``.

    ``block_f`` defaults lower than the other kernels: this one holds TWO
    f32 accumulators plus both weight-grad output blocks in VMEM, and at
    ``block_f=512``/d=768 that footprint (with double buffering) exceeds
    the 16 MB v5e VMEM — the compiler dies at the bench shape (measured;
    256 compiles and runs)."""
    T, d = x.shape
    ffn = w1.shape[0]
    bt = _pick_block(T, block_t or _BLOCK_T,
                     _TOKEN_QUANTUM)
    bf = _pick_block(ffn, block_f or _DW_BLOCK_F,
                     _FFN_QUANTUM)
    grid = (ffn // bf, T // bt)  # token axis is the reduction
    return pl.pallas_call(
        functools.partial(_bwd_dw_kernel,
                          mxu_bf16=_resolve_mxu_bf16(mxu_bf16, interpret)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, d), lambda j, t: (t, 0)),   # x tile
            pl.BlockSpec((bt, d), lambda j, t: (t, 0)),   # dy tile
            pl.BlockSpec((bf, d), lambda j, t: (j, 0)),   # w1 slice
            pl.BlockSpec((d, bf), lambda j, t: (0, j)),   # w2 slice
        ],
        out_specs=[
            pl.BlockSpec((bf, d), lambda j, t: (j, 0)),   # dw1 slice
            pl.BlockSpec((d, bf), lambda j, t: (0, j)),   # dw2 slice
        ],
        out_shape=[jax.ShapeDtypeStruct(w1.shape, w1.dtype),
                   jax.ShapeDtypeStruct(w2.shape, w2.dtype)],
        scratch_shapes=[pltpu.VMEM((bf, d), jnp.float32),
                        pltpu.VMEM((d, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dy, w1, w2)


def ffn_bwd_pallas(dy, w1, w2, x, *, interpret: bool = False):
    """Full-block VJP from the fused kernels — same signature as
    ``ops.ffn.ffn_bwd``: returns ``(dx, (dw1, dw2))``."""
    dx = ffn_bwd_dx_pallas(dy, w1, w2, x, interpret=interpret)
    dw1, dw2 = ffn_bwd_dw_pallas(dy, w1, w2, x, interpret=interpret)
    return dx, (dw1, dw2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def pallas_ffn_block(w1, w2, x, interpret=False):
    """FFN block computed by the fused kernels, differentiated by them too."""
    return ffn_fwd_pallas(w1, w2, x, interpret=interpret)


def _block_fwd(w1, w2, x, interpret):
    return ffn_fwd_pallas(w1, w2, x, interpret=interpret), (w1, w2, x)


def _block_bwd(interpret, res, dy):
    w1, w2, x = res
    dx, (dw1, dw2) = ffn_bwd_pallas(dy, w1, w2, x, interpret=interpret)
    return dw1, dw2, dx


pallas_ffn_block.defvjp(_block_fwd, _block_bwd)
