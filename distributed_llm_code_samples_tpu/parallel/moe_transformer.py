"""MoE-transformer trainers — GShard's layout on one ``"expert"`` axis.

Attention runs **data-parallel** (each shard owns whole sequences of its
own seed column) while the MoE FFN runs **expert-parallel** (experts
sharded, tokens routed through the ``all_to_all`` dispatch of
``parallel.expert``) — the composition GShard trains with, on this
framework's transformer (``models.moe_transformer``).

Gradients: attention projections, LayerNorms, and the router are
replicated, so their per-shard partials take one ``psum`` over the
expert axis (SUM, unscaled LR — ``train_ffns.py:165`` semantics);
expert FFN weights are complete on their owner shard (the a2a is the
reduction's data movement, ``parallel/expert.py``).

``train_moe_transformer_dense`` is the no-mesh oracle: ``n_groups=n``
reproduces the n-shard EP run exactly (strided seed split, grouped
dispatch with the per-group capacity share, summed replicated-weight
grads) — the user-facing differential check, like ``train_moe_dense``
for the flat MoE stack.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import LR
from ..data import batch_from_seed, shard_seeds_strided
from ..models.ffn_stack import clone_params
from ..models.moe_transformer import (MoETransformerParams,
                                      moe_transformer_fwd_aux)
from ..optim import sgd
from .expert import _local_capacity, moe_layer_ep
from .collectives import grad_reduce, vary
from .launcher import launch_strided
from .mesh import EXPERT_AXIS, require_axes

# Expert FFN weights sharded on the expert dim; everything else replicated.
EP_SPECS = MoETransformerParams(
    ln1=P(), wq=P(), wk=P(), wv=P(), wo=P(), ln2=P(), wg=P(),
    w1=P(None, EXPERT_AXIS), w2=P(None, EXPERT_AXIS))

# grads for these leaves are per-shard partials over the expert axis
_REPLICATED = ("ln1", "wq", "wk", "wv", "wo", "ln2", "wg")


def _validate(params, batch_size: int, seq_len: int, n: int,
              model_size: int, n_heads: int) -> int:
    if model_size % n_heads:
        raise ValueError(f"model_size={model_size} not divisible by "
                         f"n_heads={n_heads} (head dim must be whole)")
    if batch_size % n:
        raise ValueError(f"batch_size={batch_size} tokens not divisible "
                         f"by {n} expert shards")
    t_local = batch_size // n
    if t_local % seq_len:
        raise ValueError(f"per-shard tokens {t_local} not divisible by "
                         f"seq_len={seq_len} (shards own whole sequences)")
    if params.n_experts % n:
        raise ValueError(f"n_experts={params.n_experts} not divisible by "
                         f"expert-axis size {n}")
    return t_local


def train_moe_transformer_ep(params: MoETransformerParams, seeds,
                             batch_size: int, model_size: int, mesh,
                             lr: float = LR, *, seq_len: int, n_heads: int,
                             causal: bool = True,
                             capacity_factor: float = 2.0, k: int = 1,
                             aux_coef: float = 0.0,
                             attn_impl: str | None = None,
                             dispatch: str = "dense"
                             ) -> MoETransformerParams:
    """Run the GShard schedule; ``batch_size`` is global tokens per step
    (each shard trains ``batch_size/n`` tokens of its own strided seed
    column as ``[B/n, seq_len, d]`` sequences). ``attn_impl`` selects the
    attention core like every transformer trainer (None/'oracle' or
    'flash' for the fused Pallas kernels)."""
    from .transformer import resolve_attn
    require_axes(mesh, EXPERT_AXIS)
    n = mesh.shape[EXPERT_AXIS]
    t_local = _validate(params, batch_size, seq_len, n,
                        model_size, n_heads)
    b_local = t_local // seq_len
    attn = resolve_attn(attn_impl)

    def moe_fn(wg, w1_local, w2_local, h):
        return moe_layer_ep(wg, w1_local, w2_local, h, capacity_factor,
                            EXPERT_AXIS, k, dispatch)

    def step(params: MoETransformerParams, seed) -> MoETransformerParams:
        x, dloss_dx = batch_from_seed(seed, t_local, model_size,
                                      params.w1.dtype)
        x = x.reshape(b_local, seq_len, model_size)
        dloss_dx = dloss_dx.reshape(b_local, seq_len, model_size)
        # named-scope regions (moe_tf/fwd, moe_tf/bwd, moe_tf/comm,
        # moe_tf/optim; the a2a pair adds nested comm scopes)
        with jax.named_scope("moe_tf"):
            with jax.named_scope("fwd"):
                _, vjp = jax.vjp(
                    lambda p: moe_transformer_fwd_aux(
                        p, x, n_heads, causal, moe_fn=moe_fn, attn=attn),
                    # replicated leaves enter the hand-written rules
                    # typed varying; "comm" sums their partials
                    vary(params, EXPERT_AXIS))
            coef = lax.pcast(jnp.asarray(aux_coef, jnp.float32),
                             EXPERT_AXIS, to="varying")
            with jax.named_scope("bwd"):
                grads = vjp((dloss_dx, coef))[0]
            with jax.named_scope("comm"):
                grads = grads._replace(**{
                    f: grad_reduce(getattr(grads, f), EXPERT_AXIS)
                    for f in _REPLICATED})
            with jax.named_scope("optim"):
                return sgd(params, grads, lr)

    return launch_strided(step, clone_params(params), seeds, mesh,
                          EXPERT_AXIS, EP_SPECS)


def train_moe_transformer_dense(params: MoETransformerParams, seeds,
                                batch_size: int, model_size: int,
                                lr: float = LR, *, seq_len: int,
                                n_heads: int, causal: bool = True,
                                capacity_factor: float = 2.0, k: int = 1,
                                aux_coef: float = 0.0, n_groups: int = 1,
                                attn_impl: str | None = None
                                ) -> MoETransformerParams:
    """Single-device dense trainer with EP's exact semantics — the
    user-facing oracle for ``train_moe_transformer_ep`` (``n_groups=n``),
    or plain dense MoE-transformer training (``n_groups=1``)."""
    from .transformer import resolve_attn
    t_local = _validate(params, batch_size, seq_len, n_groups,
                        model_size, n_heads)
    b_local = t_local // seq_len
    cap = _local_capacity(t_local, n_groups, params.n_experts,
                          capacity_factor)
    rows = shard_seeds_strided(seeds, n_groups)
    attn = resolve_attn(attn_impl)

    def fwd_aux(p, xs):  # xs [n_groups, b_local, seq, d]
        y, aux = jax.vmap(lambda x: moe_transformer_fwd_aux(
            p, x, n_heads, causal, capacity_factor, k, cap,
            attn=attn))(xs)
        return y, jnp.sum(aux)

    def step(p, row):
        xs, dls = jax.vmap(lambda s: batch_from_seed(
            s, t_local, model_size, p.w1.dtype))(row)
        xs = xs.reshape(n_groups, b_local, seq_len, model_size)
        dls = dls.reshape(n_groups, b_local, seq_len, model_size)
        _, vjp = jax.vjp(lambda p: fwd_aux(p, xs), p)
        grads = vjp((dls, jnp.asarray(aux_coef, jnp.float32)))[0]
        return sgd(p, grads, lr), None

    run = jax.jit(lambda p, rows: lax.scan(step, p, rows)[0],
                  donate_argnums=0)
    return run(clone_params(params), rows)
