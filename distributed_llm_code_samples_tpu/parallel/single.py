"""Single-device trainer — reference semantics for the ops layer.

Parity target: ``train_1gpu`` (``train_ffns.py:101-116``): per step, forward
the stack, hand-written backward, functional SGD rebuild ``p - LR*g``. The
step loop is a ``lax.scan`` over the seed schedule so the whole run is one
XLA program (steps/sec is measured without per-step dispatch overhead).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .. import LR
from ..data import batch_from_seed
from ..models.ffn_stack import FFNStackParams, clone_params
from ..optim import sgd
from ..ops.ffn import ffn_fwd, ffn_bwd
from ..ops.stack import (accumulated_grads, stack_fwd, stack_bwd,
                         stack_grads)
from ..runtime.tracing import PhaseTimer

_PHASES = PhaseTimer("train")   # never begun: annotations, no stamps


def make_step(batch_size: int, model_size: int, lr: float = LR,
              unroll: bool = True, use_pallas: bool = False,
              interpret: bool = False, manual_loop: bool = False,
              remat: bool | None = None, mixed: bool = False,
              accum: int = 1):
    """Build one training step ``(params, seed) -> params`` — forward,
    manual backward, inline SGD (``train_ffns.py:105-114``).

    By default the chain is composed functionally (``ops.stack.stack_grads``):
    each block still runs the hand-written VJP rule via ``custom_vjp``, but
    residual plumbing is left to XLA. ``manual_loop=True`` selects the literal
    reference-shaped loops (``stack_fwd``/``stack_bwd``); both paths run the
    same per-block math and agree to float tolerance (allclose-verified in
    tests/test_ops.py — XLA may schedule the two programs differently, so
    equality is not bitwise).

    ``use_pallas`` swaps the per-block compute for the fused Pallas TPU
    kernels (``ops.pallas_ffn``); ``interpret`` runs them in interpreter
    mode for CPU testing.

    ``remat=False`` saves the post-ReLU activation instead of recomputing
    the ffn1 pre-activation in the backward (``ops.ffn.ffn_block_saved``)
    — one fewer matmul per block backward, same hand-written math, same
    gradients. The default keeps the reference's memory-lean recompute
    policy (``train_ffns.py:63``). (Every speed comparison of these
    policies made before PR 47 — "throughput-equal", "~10% faster" — was
    read on a step that spent over half its time drawing its batch again
    inside its matrix products, ``data.batch_from_seed``; none has been
    read again on the step as it is now: ROADMAP A7 / C3.)

    ``mixed`` selects the TPU-first precision policy: bf16 matmul
    inputs on the MXU, fp32 params/gradients/accumulation, bf16
    residuals. Composes with the residual policy (same default as f32 —
    the reference's recompute stance): ``remat=True``/None recomputes
    the pre-activation from a bf16-stashed block input
    (``ops.ffn.ffn_block_mixed_remat``); ``remat=False`` saves the bf16
    post-ReLU (``ops.ffn.ffn_block_mixed``). The MXU time is identical to
    f32 either way (default-precision f32 matmuls are single bf16
    passes); the halved stash bytes are the single-chip lever. Which
    residual policy wins is unmeasured: the one trainer cell,
    ``ffn-d8192.train-single``, runs the default (ROADMAP A7 / C3).

    ``accum`` splits the step's tokens into that many gradient-
    accumulation chunks (``lax.scan``, summed grads, one update): peak
    activation memory drops ~1/accum while the math is exactly the
    full-batch step (grads are linear in the batch; the mock loss has no
    mean to rescale — SUM semantics throughout, ``train_ffns.py:165``)."""
    if mixed and (use_pallas or manual_loop):
        raise ValueError("mixed=True is its own block implementation; it "
                         "cannot combine with use_pallas/manual_loop")
    if use_pallas and remat is False:
        raise ValueError("the Pallas block has its own residual policy; "
                         "remat=False cannot combine with use_pallas")
    if remat is None:
        remat = True  # the reference's recompute policy is the default

    def accumulate(grad_fn, x, dy):
        return accumulated_grads(grad_fn, x, dy, accum)

    if manual_loop:
        if use_pallas:
            from ..ops.pallas_ffn import ffn_fwd_pallas, ffn_bwd_pallas
            block_fwd = lambda w1, w2, x: ffn_fwd_pallas(  # noqa: E731
                w1, w2, x, interpret=interpret)
            block_bwd = lambda dy, w1, w2, x: ffn_bwd_pallas(  # noqa: E731
                dy, w1, w2, x, interpret=interpret)
        else:
            block_fwd, block_bwd = ffn_fwd, ffn_bwd

        def step(params: FFNStackParams, seed) -> FFNStackParams:
            # named-scope regions (single/fwd, single/bwd, single/optim):
            # stable trace/HLO names, utils/trace_analysis.SCOPES
            with jax.named_scope("single"):
                x, dloss_dx = batch_from_seed(seed, batch_size, model_size,
                                              params.w1.dtype)

                def grad_fn(x, dy):
                    _, acts = stack_fwd(params.w1, params.w2, x,
                                        block_fwd=block_fwd, unroll=unroll)
                    _, (g1, g2) = stack_bwd(dy, params.w1, params.w2, acts,
                                            block_bwd=block_bwd,
                                            unroll=unroll)
                    return FFNStackParams(g1, g2)

                grads = accumulate(grad_fn, x, dloss_dx)
                with jax.named_scope("optim"):
                    return sgd(params, grads, lr)

        return step

    if use_pallas:
        from ..ops.pallas_ffn import pallas_ffn_block
        block = lambda w1, w2, x: pallas_ffn_block(  # noqa: E731
            w1, w2, x, interpret)
    elif mixed:
        if remat:
            from ..ops.ffn import ffn_block_mixed_remat as block
        else:
            from ..ops.ffn import ffn_block_mixed as block
    elif remat:
        from ..ops.ffn import ffn_block as block
    else:
        from ..ops.ffn import ffn_block_saved as block

    def step(params: FFNStackParams, seed) -> FFNStackParams:
        with jax.named_scope("single"):
            x, dloss_dx = batch_from_seed(seed, batch_size, model_size,
                                          params.w1.dtype)

            def grad_fn(x, dy):
                return FFNStackParams(*stack_grads(
                    params.w1, params.w2, x, dy, block=block,
                    unroll=unroll)[1])

            grads = accumulate(grad_fn, x, dloss_dx)
            with jax.named_scope("optim"):
                return sgd(params, grads, lr)

    return step


@partial(jax.jit, static_argnums=tuple(range(2, 12)), donate_argnums=0)
def _run(params, seeds, batch_size, model_size, lr, unroll, use_pallas,
         interpret, manual_loop, remat, mixed, accum):
    step = make_step(batch_size, model_size, lr, unroll, use_pallas,
                     interpret, manual_loop, remat, mixed, accum)
    return lax.scan(lambda p, s: (step(p, s), None), params, seeds)[0]


@partial(jax.jit, static_argnums=tuple(range(3, 14)), donate_argnums=0)
def _run_guarded(params, gstate, seeds, batch_size, model_size, lr,
                 unroll, use_pallas, interpret, manual_loop, remat, mixed,
                 accum, guard):
    """The guarded scan: every step's candidate params pass the in-graph
    finite check and a bad step is ``jnp.where``-skipped — params
    untouched, skip counter advanced (``runtime/guardrails.py``).
    ``guard`` is a frozen (hashable) config, so it rides the static-args
    cache like the rest of the step configuration."""
    from ..runtime.guardrails import guarded_scan_step
    step = make_step(batch_size, model_size, lr, unroll, use_pallas,
                     interpret, manual_loop, remat, mixed, accum)
    gstep = guarded_scan_step(step, guard)
    return lax.scan(lambda c, s: (gstep(c, s), None), (params, gstate),
                    seeds)[0]


def train_single(params: FFNStackParams, seeds, batch_size: int,
                 model_size: int, mesh=None, lr: float = LR,
                 unroll: bool = True, use_pallas: bool = False,
                 interpret: bool = False, manual_loop: bool = False,
                 remat: bool | None = None, mixed: bool = False,
                 accum: int = 1, guard=None, guard_state=None,
                 return_guard: bool = False) -> FFNStackParams:
    """Uniform launcher signature (SURVEY.md L4); ``mesh`` ignored.

    ``guard`` (a ``runtime.guardrails.GuardrailConfig``) compiles the
    in-graph skip-step guardrail into the scan; with ``return_guard``
    the final ``GuardState`` (skip counters) returns alongside the
    params. The single-device path carries no collectives, so the
    finite flag needs no reduction; loss scaling is a mixed-strategy
    (DDP/FSDP) surface."""
    from ..runtime.guardrails import check_guard_args, host_state
    check_guard_args(guard, guard_state, return_guard)
    if guard is not None and guard.scaling:
        raise ValueError(
            "guard.loss_scale > 0 but train_single has no loss-scale "
            "hook: dynamic scaling is a mixed-precision DDP/FSDP "
            "surface — pass loss_scale=0 here")
    # host events train:clone / train:run in a profiler trace: the
    # donated copy, then trace + cache look-up + enqueue of the program
    with _PHASES.phase("clone"):
        owned = clone_params(params)
    static = (batch_size, model_size, lr, unroll, use_pallas, interpret,
              manual_loop, remat, mixed, accum)
    if guard is None:
        with _PHASES.phase("run"):
            return _run(owned, jnp.asarray(seeds), *static)
    with _PHASES.phase("run"):
        out, g = _run_guarded(owned, host_state(guard_state, guard),
                              jnp.asarray(seeds), *static, guard)
    return (out, g) if return_guard else out
