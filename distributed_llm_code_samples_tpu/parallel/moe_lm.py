"""MoE-LM trainers — GShard's expert-parallel layout under the real loss.

Same composition as ``parallel/moe_transformer.py`` (attention
data-parallel on strided seed columns, MoE FFN expert-parallel through
the ``all_to_all`` dispatch) with the objective upgraded from the mocked
upstream gradient to the LM family's hand-VJP cross-entropy plus the
router's load-balancing auxiliary loss: per shard
``loss = xent(local tokens) + aux_coef * aux``, gradients SUM-reduced
over the expert axis for every replicated leaf (embedding, positions,
attention, LNs, router — ``train_ffns.py:165`` semantics), expert FFN
weights complete on their owner shard.

``train_moe_lm_dense`` is the no-mesh oracle (``n_groups=n`` reproduces
the n-shard EP run exactly, grouped capacity and all).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import LR
from ..data import lm_batch_from_seed, shard_seeds_strided
from ..models.ffn_stack import clone_params
from ..models.moe_lm import MoELMParams, moe_lm_loss_aux
from ..optim import sgd
from .collectives import grad_reduce, vary
from .expert import _local_capacity, moe_layer_ep
from .launcher import launch_strided
from .mesh import EXPERT_AXIS, require_axes
from .moe_transformer import EP_SPECS, _REPLICATED, _validate

EP_LM_SPECS = MoELMParams(wte=P(), wpe=P(), blocks=EP_SPECS, ln_f=P())


def _validate_lm(params: MoELMParams, batch_size: int, seq_len: int,
                 n: int, model_size: int, n_heads: int) -> int:
    t_local = _validate(params.blocks, batch_size, seq_len, n,
                        model_size, n_heads)
    if seq_len > params.max_seq_len:
        raise ValueError(f"seq_len={seq_len} exceeds the model's "
                         f"max_seq_len={params.max_seq_len}")
    return t_local


def _reduce_replicated(grads: MoELMParams,
                       force: bool = False) -> MoELMParams:
    """psum the per-shard partials of every replicated leaf (vma-aware:
    leaves whose plain-op transposes already auto-reduced are skipped;
    ``force`` applies the vma-off unconditional-psum contract,
    ``collectives.grad_reduce``)."""
    grads = grads._replace(
        wte=grad_reduce(grads.wte, EXPERT_AXIS, force=force),
        wpe=grad_reduce(grads.wpe, EXPERT_AXIS, force=force),
        ln_f=grad_reduce(grads.ln_f, EXPERT_AXIS, force=force),
        blocks=grads.blocks._replace(**{
            f: grad_reduce(getattr(grads.blocks, f), EXPERT_AXIS,
                           force=force)
            for f in _REPLICATED}))
    return grads


def train_moe_lm_ep(params: MoELMParams, seeds, batch_size: int,
                    model_size: int, mesh, lr: float = LR, *,
                    seq_len: int, n_heads: int, causal: bool = True,
                    capacity_factor: float = 2.0, k: int = 1,
                    aux_coef: float = 0.0,
                    attn_impl: str | None = None,
                    dispatch: str = "dense",
                    head_impl: str | None = None) -> MoELMParams:
    """Run the GShard-LM schedule; ``batch_size`` is global tokens per
    step (each shard trains ``batch_size/n`` tokens of its own strided
    seed column). ``head_impl="fused"`` swaps the tied head + xent for
    the fused Pallas kernels per shard (``parallel.lm.resolve_head``;
    the launcher then runs the vma-off reduction contract on CPU)."""
    from .lm import _vma_check, resolve_head
    from .transformer import resolve_attn
    require_axes(mesh, EXPERT_AXIS)
    n = mesh.shape[EXPERT_AXIS]
    t_local = _validate_lm(params, batch_size, seq_len, n, model_size,
                           n_heads)
    b_local = t_local // seq_len
    vocab = params.vocab
    attn = resolve_attn(attn_impl)
    head = resolve_head(head_impl)
    check = _vma_check(attn_impl, head_impl)

    def moe_fn(wg, w1_local, w2_local, h):
        return moe_layer_ep(wg, w1_local, w2_local, h, capacity_factor,
                            EXPERT_AXIS, k, dispatch)

    def step(params: MoELMParams, seed) -> MoELMParams:
        tokens, targets = lm_batch_from_seed(seed, b_local, seq_len, vocab)

        def loss_fn(p):
            loss, aux = moe_lm_loss_aux(p, tokens, targets, n_heads,
                                        causal, moe_fn=moe_fn, attn=attn,
                                        head=head)
            return loss + aux_coef * aux.astype(loss.dtype)

        # named-scope regions (moe_lm/fwd, moe_lm/comm, moe_lm/optim;
        # the a2a dispatch inside moe_layer_ep adds nested comm scopes)
        with jax.named_scope("moe_lm"):
            with jax.named_scope("fwd"):
                # replicated leaves enter the hand-written rules typed
                # varying; "comm" sums their per-shard partials
                grads = jax.grad(loss_fn)(vary(params, EXPERT_AXIS))
            with jax.named_scope("comm"):
                grads = _reduce_replicated(grads, force=not check)
            with jax.named_scope("optim"):
                return sgd(params, grads, lr)

    return launch_strided(step, clone_params(params), seeds, mesh,
                          EXPERT_AXIS, EP_LM_SPECS, check_vma=check)


def train_moe_lm_dense(params: MoELMParams, seeds, batch_size: int,
                       model_size: int, lr: float = LR, *, seq_len: int,
                       n_heads: int, causal: bool = True,
                       capacity_factor: float = 2.0, k: int = 1,
                       aux_coef: float = 0.0, n_groups: int = 1,
                       attn_impl: str | None = None) -> MoELMParams:
    """Single-device dense trainer with EP's exact semantics — the
    oracle for ``train_moe_lm_ep`` (``n_groups=n``), or plain dense
    MoE-LM training (``n_groups=1``)."""
    from .transformer import resolve_attn
    t_local = _validate_lm(params, batch_size, seq_len, n_groups,
                           model_size, n_heads)
    b_local = t_local // seq_len
    cap = _local_capacity(t_local, n_groups, params.n_experts,
                          capacity_factor)
    rows = shard_seeds_strided(seeds, n_groups)
    vocab = params.vocab
    attn = resolve_attn(attn_impl)

    def step(p, row):
        toks, tgts = jax.vmap(
            lambda s: lm_batch_from_seed(s, b_local, seq_len, vocab))(row)

        def loss_fn(p):
            losses, auxes = jax.vmap(
                lambda tok, tg: moe_lm_loss_aux(
                    p, tok, tg, n_heads, causal, capacity_factor, k, cap,
                    attn=attn))(toks, tgts)
            # sum over groups == the EP shards' psum (SUM, unscaled LR)
            return (jnp.sum(losses)
                    + aux_coef * jnp.sum(auxes).astype(losses.dtype))

        grads = jax.grad(loss_fn)(p)
        return sgd(p, grads, lr), None

    run = jax.jit(lambda p, rows: lax.scan(step, p, rows)[0],
                  donate_argnums=0)
    return run(clone_params(params), rows)
