"""Transformer FFN sublayer: linear -> ReLU -> linear, hand-differentiated.

Parity target: ``train_ffns.py:54-70``. Two properties of the reference are
preserved deliberately:

- **Only block inputs are checkpointed.** The backward *recomputes* the
  ffn1 pre-activation (``train_ffns.py:63``) instead of saving it — built-in
  activation rematerialization. On TPU this trades one extra ``[tokens, ffn]``
  matmul for not keeping a ``4*d_model``-wide activation in HBM.
- **The backward math is written out by hand** (no autograd). ``ffn_block``
  wraps the pair in ``jax.custom_vjp`` so that even if a caller *does* run
  ``jax.grad`` over the stack, the rule that fires is this manual VJP —
  and the test suite verifies the manual math against JAX autograd, an
  oracle the reference never had.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .linear import linear_fwd, linear_bwd
from .activations import relu_fwd, relu_bwd


def ffn_fwd(w1: jax.Array, w2: jax.Array, x: jax.Array) -> jax.Array:
    """linear -> ReLU -> linear (``train_ffns.py:54-58``).

    Shapes: ``w1 [ffn, d]``, ``w2 [d, ffn]``, ``x [tokens, d]`` -> ``[tokens, d]``.
    """
    h = linear_fwd(w1, x)
    a = relu_fwd(h)
    return linear_fwd(w2, a)


def ffn_bwd(dy: jax.Array, w1: jax.Array, w2: jax.Array, x: jax.Array):
    """Full-block manual VJP with pre-activation recompute (``train_ffns.py:61-70``).

    Args:
      dy: upstream gradient ``[tokens, d]``.
      x: the *block input* saved by the forward (the only checkpointed value).

    Returns ``(dx, (dw1, dw2))``.
    """
    h = linear_fwd(w1, x)  # recompute ffn1 pre-activation instead of saving it
    dw2, da = linear_bwd(dy, w2, relu_fwd(h))
    dh = relu_bwd(da, h)
    dw1, dx = linear_bwd(dh, w1, x)
    return dx, (dw1, dw2)


def ffn_bwd_saved(dy: jax.Array, w1: jax.Array, w2: jax.Array, x: jax.Array,
                  a: jax.Array):
    """Manual block VJP using the **saved** post-ReLU activation ``a``.

    Identical math to ``ffn_bwd`` — ``a = relu(h)`` so the ReLU mask
    ``h > 0`` equals ``a > 0`` — but skips the pre-activation recompute
    (``train_ffns.py:63``), trading one ``[tokens, ffn]`` residual in HBM
    for one fewer matmul per block backward. ``ffn_block`` (remat) stays
    the default for its memory profile; which is faster has not been
    read on the step as it is since PR 47 (``parallel/single.py``).

    Returns ``(dx, (dw1, dw2))``.
    """
    dw2, da = linear_bwd(dy, w2, a)
    dh = relu_bwd(da, a)  # mask a > 0 == h > 0
    dw1, dx = linear_bwd(dh, w1, x)
    return dx, (dw1, dw2)


@jax.custom_vjp
def ffn_block(w1: jax.Array, w2: jax.Array, x: jax.Array) -> jax.Array:
    """FFN block whose differentiation rule is the hand-written VJP above."""
    return ffn_fwd(w1, w2, x)


def _ffn_block_fwd(w1, w2, x):
    # Residuals: params + block input only — matches the reference's
    # checkpoint-block-inputs-only policy (train_ffns.py:77, :63).
    return ffn_fwd(w1, w2, x), (w1, w2, x)


def _ffn_block_bwd(res, dy):
    w1, w2, x = res
    dx, (dw1, dw2) = ffn_bwd(dy, w1, w2, x)
    return dw1, dw2, dx


ffn_block.defvjp(_ffn_block_fwd, _ffn_block_bwd)


@jax.custom_vjp
def ffn_block_saved(w1: jax.Array, w2: jax.Array, x: jax.Array) -> jax.Array:
    """FFN block differentiated by ``ffn_bwd_saved`` — the no-recompute
    fast path. Same forward, same gradients (the mask identity makes the
    two rules produce identical values)."""
    return ffn_fwd(w1, w2, x)


def _ffn_block_saved_fwd(w1, w2, x):
    h = linear_fwd(w1, x)
    a = relu_fwd(h)
    return linear_fwd(w2, a), (w1, w2, x, a)


def _ffn_block_saved_bwd(res, dy):
    w1, w2, x, a = res
    dx, (dw1, dw2) = ffn_bwd_saved(dy, w1, w2, x, a)
    return dw1, dw2, dx


ffn_block_saved.defvjp(_ffn_block_saved_fwd, _ffn_block_saved_bwd)


# --- Mixed-precision block: bf16 on the MXU, fp32 params/accumulation -----
#
# The TPU-first precision policy (absent from the fp32 reference): matmul
# *inputs* are cast to bfloat16 — the MXU's native format — while params,
# gradients, and every accumulation stay float32 (`preferred_element_type`).
# Residuals are saved in bf16, halving activation HBM traffic. The backward
# is still the hand-written rule, not autograd.

def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


@jax.custom_vjp
def ffn_block_mixed(w1: jax.Array, w2: jax.Array, x: jax.Array) -> jax.Array:
    """linear -> ReLU -> linear with bf16 MXU compute, fp32 accumulate."""
    y, _ = _ffn_block_mixed_fwd(w1, w2, x)
    return y


def _ffn_block_mixed_fwd(w1, w2, x):
    bf = jnp.bfloat16
    xb, w1b, w2b = x.astype(bf), w1.astype(bf), w2.astype(bf)
    h = _dot(xb, w1b, (((1,), (1,))))          # [T,d]@[ffn,d]^T -> [T,ffn] f32
    ab = jnp.maximum(h, 0.0).astype(bf)        # saved post-ReLU, bf16
    y = _dot(ab, w2b, (((1,), (1,))))          # [T,ffn]@[d,ffn]^T -> [T,d] f32
    return y, (w1b, w2b, xb, ab)


def _mixed_bwd_core(dy, w1b, w2b, xb, ab):
    """The one copy of the mixed backward math, shared by the custom_vjp
    block and the pair-form dialect below — bit-identity between the two
    is BY CONSTRUCTION, not by parallel maintenance. All inputs except
    ``dy`` are bf16; returns f32 ``(dx, dw1, dw2)``."""
    bf = jnp.bfloat16
    dyb = dy.astype(bf)
    dw2 = _dot(dyb, ab, (((0,), (0,))))        # dy^T a   -> [d,ffn] f32
    da = _dot(dyb, w2b, (((1,), (0,))))        # dy  w2   -> [T,ffn] f32
    dhb = jnp.where(ab > 0, da, jnp.zeros((), jnp.float32)).astype(bf)
    dw1 = _dot(dhb, xb, (((0,), (0,))))        # dh^T x   -> [ffn,d] f32
    dx = _dot(dhb, w1b, (((1,), (0,))))        # dh  w1   -> [T,d]   f32
    return dx, dw1, dw2


def _ffn_block_mixed_bwd(res, dy):
    w1b, w2b, xb, ab = res
    dx, dw1, dw2 = _mixed_bwd_core(dy, w1b, w2b, xb, ab)
    return dw1, dw2, dx


ffn_block_mixed.defvjp(_ffn_block_mixed_fwd, _ffn_block_mixed_bwd)


@jax.custom_vjp
def ffn_block_mixed_remat(w1: jax.Array, w2: jax.Array,
                          x: jax.Array) -> jax.Array:
    """``ffn_block_mixed``'s math under the remat residual policy: the
    backward recomputes the pre-activation from the BLOCK INPUT (the
    reference's checkpoint stance, ``train_ffns.py:63``) and the stashed
    input is bf16 — the saved-bytes half of the mixed policy applied to
    the recompute policy's only residual. On an MXU-saturated shape the
    matmul time is identical to f32 (default-precision f32 matmuls are
    single bf16 passes anyway); the bf16 stash is the one lever that can
    move the single-chip headline."""
    y, _ = _ffn_block_mixed_remat_fwd(w1, w2, x)
    return y


def _ffn_block_mixed_remat_fwd(w1, w2, x):
    return ffn_fwd_mixed(w1, w2, x), (w1, w2, x.astype(jnp.bfloat16))


def _ffn_block_mixed_remat_bwd(res, dy):
    w1, w2, xb = res
    dx, (dw1, dw2) = ffn_bwd_mixed(dy, w1, w2, xb)
    return dw1, dw2, dx


# --- Pair-form mixed blocks: the hook-surface dialect ---------------------
#
# The distributed strategies (ddp/fsdp/tp/hybrid) inject collectives
# through ``ops.stack``'s ``block_fwd``/``block_bwd`` pair interface, where
# the backward RECOMPUTES from the saved block input (the reference's
# checkpoint policy, ``train_ffns.py:63``). These are ``ffn_block_mixed``'s
# math in that dialect: bf16 matmul inputs on the MXU, fp32
# params/grads/accumulation — the TPU-first precision policy threaded to
# every strategy (VERDICT r3 #3). Weights already in bf16 (e.g. FSDP's
# half-width gathered shards) pass through the casts unchanged.

def ffn_fwd_mixed(w1: jax.Array, w2: jax.Array, x: jax.Array) -> jax.Array:
    """linear -> ReLU -> linear, bf16 MXU inputs, f32 accumulate/output."""
    bf = jnp.bfloat16
    h = _dot(x.astype(bf), w1.astype(bf), ((1,), (1,)))   # [T, ffn] f32
    ab = jnp.maximum(h, 0.0).astype(bf)
    return _dot(ab, w2.astype(bf), ((1,), (1,)))          # [T, d] f32


def ffn_bwd_mixed(dy: jax.Array, w1: jax.Array, w2: jax.Array,
                  x: jax.Array):
    """Manual block VJP, bf16 compute, f32 accumulation, pre-activation
    recomputed from the block input (never saved). The ReLU mask uses the
    bf16 post-activation (``ab > 0``) so the recompute path produces
    bit-identical gradients to ``ffn_block_mixed``'s saved-residual rule.

    Returns ``(dx, (dw1, dw2))`` — all f32."""
    bf = jnp.bfloat16
    xb, w1b, w2b = x.astype(bf), w1.astype(bf), w2.astype(bf)
    h = _dot(xb, w1b, ((1,), (1,)))                       # recompute, f32
    ab = jnp.maximum(h, 0.0).astype(bf)
    dx, dw1, dw2 = _mixed_bwd_core(dy, w1b, w2b, xb, ab)
    return dx, (dw1, dw2)


ffn_block_mixed_remat.defvjp(_ffn_block_mixed_remat_fwd,
                             _ffn_block_mixed_remat_bwd)
