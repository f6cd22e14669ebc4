"""Fused Pallas TPU flash-attention kernels — the long-context hot op.

The plain attention op (``models.attention``) materializes the full
``[T, T]`` score/probability matrices; fine as a correctness oracle,
quadratic in HBM. These kernels are the hand-scheduled TPU form: the
online-softmax tiling (running row-max ``m``, denominator ``l``, f32 VMEM
accumulator) that ``parallel.sequence.ring_attention`` runs *across chips*,
here applied *within* a chip so no ``[T, T]`` block ever reaches HBM.

Forward saves only ``(y, lse)`` — the flash-attention residual policy,
matching the framework's checkpoint-block-inputs-only stance
(``train_ffns.py:63``): the backward recomputes score tiles from
``q, k, lse`` instead of saving probabilities.

Layout notes (guide: Tiling Constraints): per-row statistics (``lse``,
``D``) are carried lane-broadcast as ``[1, T]`` arrays blocked ``(1, bq)``
so every ref keeps a 128-friendly trailing dim; scratch stats are
``(bq, 128)`` with the value in every lane. Fully-masked causal tiles are
neutralized by zeroing ``p`` *after* the exp (an ``exp(-inf - -inf) = 1``
row would otherwise poison the accumulator). All kernels run under
``interpret=True`` on CPU for the hardware-free suite.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the shared tile/precision helpers live in pallas_ffn (the canonical
# module; imports flow attention -> ffn only, so there is no cycle)
from .pallas_ffn import _mxu, _pick_block
from .pallas_ffn import _resolve_mxu_bf16 as _resolve_mxu_bf16_base

_NEG = -1e30
_LANES = 128
_Q_QUANTUM = 8


# Default tile sizes, as swept on the v5e chip (T=8192, H8, dh64): 128x128
# tiles ran the whole step at ~7 TFLOP/s — the online-softmax VPU work
# (exp, rescale, stats) per tile was unamortized against dh=64 matmuls.
# 1024x1024 forward tiles reach 49.6 TF/s; the backward peaks near
# 512x512 (53.6 TF/s) and larger tiles only add VMEM pressure (2048x1024
# fails to compile). `_pick_block` caps every block at the actual T, so
# small/test shapes are unaffected.
_BLOCK_Q = _BLOCK_K = 1024
_BWD_BLOCK_Q = _BWD_BLOCK_K = 512


def _resolve_mxu_bf16(mxu_bf16, interpret: bool) -> bool:
    """The flash kernels' bf16-MXU policy default: the shared rule
    (``pallas_ffn._resolve_mxu_bf16``) bound to the ``FLASH_MXU_BF16``
    env override. Callers who train flash under a full-f32 precision
    requirement pass ``mxu_bf16=False`` explicitly (or set
    ``FLASH_MXU_BF16=0``) — the policy is a parameter, not a hardwired
    consequence of running on hardware. Casting matmul operands (never
    the f32 accumulators or softmax stats) to bf16 puts the kernels in
    the same numerics class as the XLA oracle's default-precision
    matmuls and was worth ~3x on the r04 chip measurements."""
    return _resolve_mxu_bf16_base(mxu_bf16, interpret,
                                  env_var="FLASH_MXU_BF16")


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying ``like``'s varying-manual-axes type, so
    the kernels can be called from inside ``shard_map`` bodies (Ulysses /
    TP / hybrid trainers) under JAX's ``check_vma`` typing."""
    vma = getattr(jax.typeof(like), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _positions(i, j, bq, bk):
    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return q_pos, k_pos


def _tile_needed(i, j, bq, bk, causal):
    """False only for tiles the causal mask kills entirely (every key
    position past every query position) — those are skipped, the standard
    flash-attention FLOP saving (~2x on the quadratic hot path)."""
    if not causal:
        return True
    return j * bk <= i * bq + bq - 1


def _flash_fwd_kernel(q_ref, k_ref, v_ref, y_ref, lse_ref, m_ref, l_ref,
                      acc_ref, *, scale, causal, bq, bk, mxu_bf16):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(_tile_needed(i, j, bq, bk, causal))
    def _():
        s = jnp.dot(_mxu(q_ref[:], mxu_bf16), _mxu(k_ref[:], mxu_bf16).T,
                    preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if causal:
            q_pos, k_pos = _positions(i, j, bq, bk)
            mask = q_pos >= k_pos
            s = jnp.where(mask, s, _NEG)

        m_prev = m_ref[:, :1]                                    # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(mask, p, 0.0)  # a masked-out row would give p == 1
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        pv_dtype = jnp.bfloat16 if mxu_bf16 else v_ref.dtype
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p.astype(pv_dtype), v_ref[:].astype(pv_dtype),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        l = l_ref[:, :1]
        y_ref[:] = (acc_ref[:] / l).astype(y_ref.dtype)
        lse = (m_ref[:, :1] + jnp.log(l)).T                   # [1, bq]
        lse_ref[:] = lse.astype(lse_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, block_q: int | None = None,
                        block_k: int | None = None,
                        interpret: bool = False,
                        mxu_bf16: bool | None = None):
    """Fused attention forward. ``q, k, v [T, dh]`` -> ``(y [T, dh],
    lse [T])`` with only the log-sum-exp saved for the backward."""
    T, dh = q.shape
    scale = 1.0 / (dh ** 0.5)
    _mxu_bf16 = _resolve_mxu_bf16(mxu_bf16, interpret)
    bq = _pick_block(T, block_q or _BLOCK_Q, _Q_QUANTUM)
    bk = _pick_block(k.shape[0], block_k or _BLOCK_K, _Q_QUANTUM)
    grid = (T // bq, k.shape[0] // bk)
    y, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, mxu_bf16=_mxu_bf16),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, dh), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, dh), lambda i, j: (j, 0)),
            pl.BlockSpec((bk, dh), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, dh), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bq), lambda i, j: (0, i)),
        ],
        out_shape=[_sds((T, dh), q.dtype, q),
                   _sds((1, T), jnp.float32, q)],
        scratch_shapes=[pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return y, lse[0]


def _recompute_p_ds(q_ref, k_ref, v_ref, dy_ref, lse_ref, d_ref, i, j,
                    scale, causal, mxu_bf16):
    """Shared backward tile math: probability tile from the saved lse,
    ``p = exp(q k^T * scale - lse)`` (zeroed where causally masked), and
    the softmax-VJP tile ``ds = p * (dy v^T - D)``."""
    s = jnp.dot(_mxu(q_ref[:], mxu_bf16), _mxu(k_ref[:], mxu_bf16).T,
                preferred_element_type=jnp.float32) * scale
    p = jnp.exp(s - lse_ref[0, :][:, None])
    if causal:
        q_pos, k_pos = _positions(i, j, *s.shape)
        p = jnp.where(q_pos >= k_pos, p, 0.0)
    dp = jnp.dot(_mxu(dy_ref[:], mxu_bf16), _mxu(v_ref[:], mxu_bf16).T,
                 preferred_element_type=jnp.float32)
    ds = p * (dp - d_ref[0, :][:, None])
    return p, ds


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, dy_ref, lse_ref, d_ref,
                         dq_ref, acc_ref, *, scale, causal, bq, bk,
                         mxu_bf16):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(_tile_needed(i, j, bq, bk, causal))
    def _():
        _, ds = _recompute_p_ds(q_ref, k_ref, v_ref, dy_ref, lse_ref, d_ref,
                                i, j, scale, causal, mxu_bf16)
        ds_dtype = jnp.bfloat16 if mxu_bf16 else k_ref.dtype
        acc_ref[:] += jnp.dot(ds.astype(ds_dtype), _mxu(k_ref[:], mxu_bf16),
                              preferred_element_type=jnp.float32) * scale

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        dq_ref[:] = acc_ref[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, dy_ref, lse_ref, d_ref,
                          dk_ref, dv_ref, acck_ref, accv_ref, *, scale,
                          causal, bq, bk, mxu_bf16):
    jblk, t = pl.program_id(0), pl.program_id(1)

    @pl.when(t == 0)
    def _():
        acck_ref[:] = jnp.zeros_like(acck_ref)
        accv_ref[:] = jnp.zeros_like(accv_ref)

    @pl.when(_tile_needed(t, jblk, bq, bk, causal))
    def _():
        p, ds = _recompute_p_ds(q_ref, k_ref, v_ref, dy_ref, lse_ref, d_ref,
                                t, jblk, scale, causal, mxu_bf16)
        lhs_dtype = jnp.bfloat16 if mxu_bf16 else dy_ref.dtype
        accv_ref[:] += jnp.dot(p.T.astype(lhs_dtype),
                               _mxu(dy_ref[:], mxu_bf16),
                               preferred_element_type=jnp.float32)
        acck_ref[:] += jnp.dot(ds.T.astype(lhs_dtype),
                               _mxu(q_ref[:], mxu_bf16),
                               preferred_element_type=jnp.float32) * scale

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        dk_ref[:] = acck_ref[:].astype(dk_ref.dtype)
        dv_ref[:] = accv_ref[:].astype(dv_ref.dtype)


def flash_attention_bwd(dy: jax.Array, q, k, v, y, lse, *,
                        causal: bool = True, block_q: int | None = None,
                        block_k: int | None = None,
                        interpret: bool = False,
                        mxu_bf16: bool | None = None):
    """Flash backward from ``(q, k, v, y, lse)`` — score tiles recomputed,
    never stored. Returns ``(dq, dk, dv)``."""
    T, dh = q.shape
    Tk = k.shape[0]
    scale = 1.0 / (dh ** 0.5)
    _mxu_bf16 = _resolve_mxu_bf16(mxu_bf16, interpret)
    bq = _pick_block(T, block_q or _BWD_BLOCK_Q, _Q_QUANTUM)
    bk = _pick_block(Tk, block_k or _BWD_BLOCK_K, _Q_QUANTUM)
    # D_i = rowsum(dy * y): the only softmax statistic the tiles can't
    # rebuild locally; elementwise, computed once outside the kernels
    d = jnp.sum(dy.astype(jnp.float32) * y.astype(jnp.float32),
                axis=-1)[None, :]                              # [1, T]
    lse2 = lse[None, :]

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, mxu_bf16=_mxu_bf16),
        grid=(T // bq, Tk // bk),
        in_specs=[
            pl.BlockSpec((bq, dh), lambda i, j: (i, 0)),   # q
            pl.BlockSpec((bk, dh), lambda i, j: (j, 0)),   # k
            pl.BlockSpec((bk, dh), lambda i, j: (j, 0)),   # v
            pl.BlockSpec((bq, dh), lambda i, j: (i, 0)),   # dy
            pl.BlockSpec((1, bq), lambda i, j: (0, i)),    # lse
            pl.BlockSpec((1, bq), lambda i, j: (0, i)),    # D
        ],
        out_specs=pl.BlockSpec((bq, dh), lambda i, j: (i, 0)),
        out_shape=_sds((T, dh), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, dy, lse2, d)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, mxu_bf16=_mxu_bf16),
        grid=(Tk // bk, T // bq),
        in_specs=[
            pl.BlockSpec((bq, dh), lambda j, t: (t, 0)),   # q
            pl.BlockSpec((bk, dh), lambda j, t: (j, 0)),   # k
            pl.BlockSpec((bk, dh), lambda j, t: (j, 0)),   # v
            pl.BlockSpec((bq, dh), lambda j, t: (t, 0)),   # dy
            pl.BlockSpec((1, bq), lambda j, t: (0, t)),    # lse
            pl.BlockSpec((1, bq), lambda j, t: (0, t)),    # D
        ],
        out_specs=[
            pl.BlockSpec((bk, dh), lambda j, t: (j, 0)),
            pl.BlockSpec((bk, dh), lambda j, t: (j, 0)),
        ],
        out_shape=[_sds((Tk, dh), k.dtype, k),
                   _sds((Tk, dh), v.dtype, v)],
        scratch_shapes=[pltpu.VMEM((bk, dh), jnp.float32),
                        pltpu.VMEM((bk, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, dy, lse2, d)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal=True, interpret=False, mxu_bf16=None):
    """Attention computed by the fused kernels and differentiated by them
    (flash residuals: ``y`` + ``lse`` only). Single head ``[T, dh]``;
    multi-head/batch via ``jax.vmap``, like ``models.attention.mha``."""
    y, _ = flash_attention_fwd(q, k, v, causal=causal, interpret=interpret,
                               mxu_bf16=mxu_bf16)
    return y


def _flash_fwd_rule(q, k, v, causal, interpret, mxu_bf16):
    y, lse = flash_attention_fwd(q, k, v, causal=causal, interpret=interpret,
                                 mxu_bf16=mxu_bf16)
    return y, (q, k, v, y, lse)


def _flash_bwd_rule(causal, interpret, mxu_bf16, res, dy):
    q, k, v, y, lse = res
    return flash_attention_bwd(dy, q, k, v, y, lse, causal=causal,
                               interpret=interpret, mxu_bf16=mxu_bf16)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_mha(q, k, v, causal: bool = True, interpret: bool = False,
              mxu_bf16: bool | None = None):
    """Multi-head convenience: vmap over a leading heads axis
    (``[H, T, dh] -> [H, T, dh]``). Grouped-query shapes (``k/v
    [H_kv, T, dh]`` with ``H % H_kv == 0``, ``models.attention.gqa``)
    fan each KV head out to its query group — the kernel streams K/V
    blocks per query head either way, so the repeat adds no extra HBM
    traffic inside the kernel (one [H, T, dh] staging copy outside
    it)."""
    hq, hkv = q.shape[0], k.shape[0]
    if hq != hkv:
        if hq % hkv:
            raise ValueError(f"query heads {hq} not divisible by kv "
                             f"heads {hkv}")
        k = jnp.repeat(k, hq // hkv, axis=0)
        v = jnp.repeat(v, hq // hkv, axis=0)
    return jax.vmap(lambda q, k, v: flash_attention(
        q, k, v, causal, interpret, mxu_bf16))(q, k, v)


flash_mha.supports_gqa = True  # repeat-KV fan-out (see docstring)
