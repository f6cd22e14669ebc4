"""Expert parallelism: experts sharded over the mesh, all_to_all dispatch.

No reference counterpart (SURVEY.md section 2.2: expert parallelism absent);
this is the framework's EP extension, built the same way as the other
strategies: the per-shard program and its collectives written out by hand
inside ``shard_map``.

Layout (GShard-style, data group == expert group): tokens are sharded over
the ``"expert"`` mesh axis (each shard routes its own ``T/n`` tokens); the
``E`` experts' FFN weights are sharded over the same axis (``E/n`` experts
live on each device); the router is replicated. Per layer:

- each shard routes locally and builds its ``[T_local, E, C]`` dispatch,
- ``all_to_all`` (split experts, concat capacity) carries every shard's
  slots for experts ``e`` onto the device that owns ``e``,
- the local experts run the hand-VJP ``ffn_block`` on their combined
  ``[E_local, n*C, d]`` slot block,
- the reverse ``all_to_all`` returns results for the shard's own tokens,
  and the gate-scaled combine finishes the layer.

Capacity is derived from the **global** token count (``T_local * n``) and
split evenly across source shards (``C_local = ceil(C_global / n)``), so
EP and the dense oracle agree on how many slots each expert exposes. Drop
*order* is grouped (each shard fills only its own ``C_local`` share —
GShard's grouped dispatch): a shard routing unusually many tokens to one
expert drops locally even if another shard left slots free. The oracle
emulates this exactly by routing each shard's tokens independently with
the same per-group capacity (``tests/test_moe.py``).

Gradients: expert-weight grads are complete locally (every token routed to
an expert arrives on its device — the a2a *is* the reduction's data
movement); router grads are per-shard partial sums and get an explicit
``psum`` (SUM, matching the framework's unscaled-LR convention,
``train_ffns.py:165``). The backward through the a2a pair is the transposed
a2a pair, composed by ``jax.vjp`` around the hand-written block rules.
The Switch load-balancing auxiliary loss (``aux_coef > 0``) is computed
per shard on local tokens (GShard's per-group convention) and folds into
the same router psum.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import LR
from ..data import batch_from_seed, shard_seeds_strided
from ..models.moe import MoEStackParams
from ..models.ffn_stack import clone_params
from ..ops.ffn import ffn_block
from ..ops.moe import (dispatch_tensor, dispatch_tensor_topk,
                       expert_capacity, moe_stack_fwd_aux, route_flat,
                       route_top1, route_topk, router_aux_loss,
                       scatter_combine, scatter_dispatch)
from ..optim import sgd
from .collectives import all_to_all, grad_reduce, vary
from .launcher import launch, launch_strided
from .mesh import DATA_AXIS, EXPERT_AXIS, require_axes


def _local_capacity(t_local: int, n_shards: int, n_experts: int,
                    capacity_factor: float) -> int:
    """This shard's slice of the global per-expert capacity: derive from
    the global token count, then ceil-split across source shards."""
    cap_global = expert_capacity(t_local * n_shards, n_experts,
                                 capacity_factor)
    return max(1, -(-cap_global // n_shards))


def moe_layer_ep(wg, w1_local, w2_local, x, capacity_factor: float = 2.0,
                 axis: str = EXPERT_AXIS, k: int = 1,
                 dispatch: str = "dense", comm: str = "psum"):
    """One expert-parallel MoE layer, per-shard view (no residual here —
    the step adds it).

    ``wg [E, d]`` (replicated), ``w1_local [E/n, ffn, d]``,
    ``w2_local [E/n, d, ffn]``, ``x [T_local, d]``. ``dispatch``:
    ``"dense"`` one-hot einsum movement or ``"scatter"`` (O(T*d)
    scatter/gather around the same pair of ``all_to_all``s — identical
    routing/capacity/priority semantics, differential-pinned).
    ``comm="pallas_a2a"`` carries both exchanges (and their backward
    transposes) on the hand-scheduled peer fan-out kernel
    (``ops.pallas_ring.all_to_all_dma_dims``)."""
    if comm == "pallas_a2a":
        from ..ops.pallas_ring import all_to_all_dma_dims
        _a2a = lambda t, sd, cd: all_to_all_dma_dims(  # noqa: E731
            t, axis, sd, cd, None)
    elif comm == "psum":
        _a2a = lambda t, sd, cd: all_to_all(t, axis, split_dim=sd,  # noqa: E731
                                            concat_dim=cd)
    else:
        raise ValueError(f"unknown comm {comm!r} "
                         "(expected 'psum' or 'pallas_a2a')")

    def a2a(t, sd, cd):
        with jax.named_scope("comm"):  # dispatch/return -> ep/.../comm
            return _a2a(t, sd, cd)
    n_experts = wg.shape[0]
    t = x.shape[0]
    cap = _local_capacity(t, lax.axis_size(axis), n_experts,
                          capacity_factor)
    if dispatch == "scatter":
        # O(T*d) movement form — the ops.moe scatter helpers (shared
        # slot bookkeeping) around the SAME pair of all_to_alls
        idx_flat, gates = route_flat(wg, x, k)
        xe, dest, keep = scatter_dispatch(idx_flat, x, n_experts, cap)
        xe = a2a(xe, 0, 1)
        ye = jax.vmap(ffn_block)(w1_local, w2_local, xe)
        ye = a2a(ye, 1, 0)
        return scatter_combine(ye, dest, keep, gates, t)
    if dispatch == "gather":
        # gather-only movement (ops.moe custom-VJP permutation gathers,
        # same slot bookkeeping) around the SAME pair of all_to_alls
        from ..ops.moe import (combine_from_slots, gather_metadata,
                               permute_to_slots)
        idx_flat, gates = route_flat(wg, x, k)
        dest, slot_tok, slot_choice, keep = gather_metadata(
            idx_flat, t, n_experts, cap)
        xe = permute_to_slots(x, dest, slot_tok).reshape(
            n_experts, cap, -1)
        xe = a2a(xe, 0, 1)
        ye = jax.vmap(ffn_block)(w1_local, w2_local, xe)
        ye = a2a(ye, 1, 0)
        return combine_from_slots(ye, gates, dest, slot_tok,
                                  slot_choice, keep)
    if dispatch != "dense":
        raise ValueError(f"unknown dispatch {dispatch!r}")
    if k == 1:
        idx, gate = route_top1(wg, x)
        disp = dispatch_tensor(idx, n_experts, cap, x.dtype)  # [T_loc, E, C]
        comb = disp * gate[:, None, None]
    else:
        idx, gates = route_topk(wg, x, k)
        disp_k = dispatch_tensor_topk(idx, n_experts, cap, x.dtype)
        disp = jnp.sum(disp_k, axis=0)
        comb = jnp.einsum("ktec,tk->tec", disp_k, gates)
    xe = jnp.einsum("tec,td->ecd", disp, x)              # [E, C, d]
    # experts -> their owners; slots from all shards stack on the cap axis
    xe = a2a(xe, 0, 1)                                    # [E/n, n*C, d]
    ye = jax.vmap(ffn_block)(w1_local, w2_local, xe)      # [E/n, n*C, d]
    # results return to the tokens' home shards
    ye = a2a(ye, 1, 0)                                    # [E, C, d]
    return jnp.einsum("tec,ecd->td", comb, ye)


def make_step(batch_size: int, model_size: int, lr: float = LR,
              capacity_factor: float = 2.0, axis: str = EXPERT_AXIS,
              k: int = 1, aux_coef: float = 0.0,
              data_axis: str | None = None, dispatch: str = "dense",
              comm: str = "psum"):
    """One EP step for one shard: local fwd (residual per layer),
    ``jax.vjp``-composed backward over the hand-written rules, optional
    load-balancing aux term, explicit router-grad psum, local SGD.

    Fwd and aux come from ONE stack walk returning ``(y, aux)``; the
    combined gradient is a single vjp with cotangents
    ``(dloss_dx, aux_coef)`` — no second forward, no duplicated a2a.

    ``comm="pallas_a2a"`` implies the launcher runs ``check_vma=False``
    (the Mosaic interpreter's vma propagation is incomplete), which
    erases the provenance signal ``grad_reduce`` keys on — so this path
    reduces the router (and 2-D data-axis) grads with an UNCONDITIONAL
    psum. Empirically pinned both ways: the pure-XLA psum path run under
    ``check_vma=False`` reproduces the exact under-reduction this
    corrects (EP's router cotangents arrive partial there — they flow
    through custom_vjp rules, which vma-off leaves unreduced), and the
    corrected path equals the vma-on psum path leaf for leaf
    (``tests/test_pallas_ring.py``) — i.e. no double reduction either.
    """

    axes = (axis,) if data_axis is None else (axis, data_axis)
    reducer = (grad_reduce if comm == "psum"
               else (lambda g, ax: lax.psum(g, ax)))

    def fwd_aux(params: MoEStackParams, x):
        aux = jnp.asarray(0.0, jnp.float32)
        for l in range(params.w1.shape[0]):
            aux = aux + router_aux_loss(params.wg[l], x)
            x = x + moe_layer_ep(params.wg[l], params.w1[l], params.w2[l],
                                 x, capacity_factor, axis, k, dispatch,
                                 comm)
        return x, aux

    def step(params: MoEStackParams, seed) -> MoEStackParams:
        # named-scope regions (ep/fwd, ep/bwd, nested comm on the a2a
        # pair and the router psum, ep/optim)
        with jax.named_scope("ep"):
            x, dloss_dx = batch_from_seed(seed, batch_size, model_size,
                                          params.w1.dtype)
            with jax.named_scope("fwd"):
                # the replicated router (and, on a 2-D mesh, the
                # data-replicated experts) enter the hand-written rules
                # typed varying like this shard's tokens; "comm" below
                # sums the per-shard partials that come back
                _, vjp = jax.vjp(lambda p: fwd_aux(p, x),
                                 vary(params, axes))
            # the aux output is shard-varying under shard_map; its cotangent
            # (the constant aux coefficient) must be cast to match — over
            # every axis the aux varies on (a 2-D mesh adds "data")
            coef = lax.pcast(jnp.asarray(aux_coef, jnp.float32), axes,
                             to="varying")
            with jax.named_scope("bwd"):
                grads = vjp((dloss_dx, coef))[0]
            with jax.named_scope("comm"):
                # router is replicated; its per-shard partial grads sum
                # across the expert axis (train_ffns.py:165 semantics) —
                # and across the data axis on a 2-D mesh. Expert grads
                # are complete on their owner shard within an EP group;
                # the data axis replicates the groups, so they too sum
                # over data (grad_reduce is vma-aware: it never touches
                # the expert axis for them).
                grads = grads._replace(wg=reducer(grads.wg, axes))
                if data_axis is not None:
                    grads = grads._replace(
                        w1=reducer(grads.w1, data_axis),
                        w2=reducer(grads.w2, data_axis))
            with jax.named_scope("optim"):
                return sgd(params, grads, lr)

    return step


def train_moe_ep(params: MoEStackParams, seeds, batch_size: int,
                 model_size: int, mesh, lr: float = LR,
                 capacity_factor: float = 2.0, k: int = 1,
                 aux_coef: float = 0.0,
                 dispatch: str = "dense",
                 comm: str = "psum") -> MoEStackParams:
    """Run the EP schedule; returns fully-assembled final params.

    ``batch_size`` is the *global token count per EP group* per step; each
    shard routes ``batch_size/n`` tokens (data and experts shard over the
    same axis). Seeds shard stride-wise like the DP strategies
    (``train_ffns.py:182``). ``k`` selects top-k routing; ``aux_coef``
    scales the Switch load-balancing loss into the router gradients.

    A 2-D ``(data, expert)`` mesh replicates the EP group ``dp`` times
    (DDP-style): seeds stride over the flattened ``dp x n`` grid, each
    replica routes independently with its own group capacities, and
    router/expert grads take one extra ``psum`` over the data axis.
    Exactly ``train_moe_dense(batch_size*dp, n_groups=dp*n,
    capacity_groups=n)`` — the differential test.
    """
    require_axes(mesh, EXPERT_AXIS)
    n = mesh.shape[EXPERT_AXIS]
    dp = dict(mesh.shape).get(DATA_AXIS, 1)
    if params.n_experts % n != 0:
        raise ValueError(f"n_experts={params.n_experts} not divisible by "
                         f"expert-axis size {n}")
    if batch_size % n != 0:
        raise ValueError(f"batch_size={batch_size} not divisible by "
                         f"expert-axis size {n}")
    step = make_step(batch_size // n, model_size, lr, capacity_factor,
                     k=k, aux_coef=aux_coef,
                     data_axis=DATA_AXIS if dp > 1 else None,
                     dispatch=dispatch, comm=comm)
    specs = MoEStackParams(wg=P(), w1=P(None, EXPERT_AXIS),
                           w2=P(None, EXPERT_AXIS))
    # a2a-kernel outputs are typed shard-varying (see ddp.train_ddp)
    check = comm == "psum"
    if dp > 1:
        # 2-D data x expert: the seed schedule strides over BOTH axes —
        # shard (d, e) of step t consumes seeds[t*dp*n + d*n + e], the
        # flat strided order the grouped dense oracle reproduces with
        # n_groups=dp*n
        cols = shard_seeds_strided(seeds, dp * n).reshape(-1, dp, n)
        return launch(step, clone_params(params), cols, mesh,
                      param_specs=specs,
                      seed_spec=P(None, DATA_AXIS, EXPERT_AXIS),
                      select_local=lambda s: s[:, 0, 0],
                      check_vma=check)
    return launch_strided(step, clone_params(params), seeds, mesh,
                          EXPERT_AXIS, specs, check_vma=check)


def train_moe_dense(params: MoEStackParams, seeds, batch_size: int,
                    model_size: int, lr: float = LR,
                    capacity_factor: float = 2.0, k: int = 1,
                    aux_coef: float = 0.0, n_groups: int = 1,
                    capacity_groups: int | None = None,
                    dispatch: str = "dense") -> MoEStackParams:
    """Single-device dense MoE trainer with EP's exact semantics — no mesh,
    no collectives; the user-facing oracle for ``train_moe_ep``.

    ``n_groups=1`` is plain dense MoE training (capacity from the global
    token count). ``n_groups=n`` emulates the ``n``-shard EP run *exactly*:
    the strided seed split (``train_ffns.py:182``), GShard's grouped
    dispatch (each group routes its ``batch_size/n`` tokens independently
    against its ``ceil(C_global/n)`` capacity share), per-group aux terms,
    and router grads summed across groups (SUM, unscaled LR,
    ``train_ffns.py:165`` semantics) — so
    ``train_moe_ep(p, seeds, B, d, mesh_n) ==
    train_moe_dense(p, seeds, B, d, n_groups=n)`` is the --method 7
    differential check, runnable without a device mesh.

    ``dispatch``: ``"dense"`` one-hot einsum movement, ``"scatter"``
    (``ops.moe.moe_layer_scatter`` — same math, O(T*d) scatter-add
    movement), or ``"gather"`` (``ops.moe.moe_layer_gather`` —
    gather-only movement in both directions). No cell runs an expert
    trainer: the choice among the three is unmeasured (ROADMAP W2).
    """
    if batch_size % n_groups:
        raise ValueError(f"batch_size={batch_size} not divisible by "
                         f"n_groups={n_groups}")
    t_local = batch_size // n_groups
    # capacity_groups: EP derives each group's slot share from its OWN
    # EP-group size (the expert-axis extent) — on a 2-D data x expert
    # mesh that is n_expert_shards, not the total dp*n group count
    cap = _local_capacity(t_local,
                          capacity_groups if capacity_groups is not None
                          else n_groups,
                          params.n_experts, capacity_factor)
    rows = shard_seeds_strided(seeds, n_groups)  # [global_steps, n_groups]

    def fwd_aux(p, xs):  # xs [n_groups, t_local, d]
        y, aux = jax.vmap(
            lambda x: moe_stack_fwd_aux(p, x, capacity_factor, k, cap,
                                        dispatch))(xs)
        return y, jnp.sum(aux)

    def step(p, row):
        xs, dls = jax.vmap(
            lambda s: batch_from_seed(s, t_local, model_size,
                                      p.w1.dtype))(row)
        _, vjp = jax.vjp(lambda p: fwd_aux(p, xs), p)
        grads = vjp((dls, jnp.asarray(aux_coef, jnp.float32)))[0]
        return sgd(p, grads, lr), None

    run = jax.jit(lambda p, rows: lax.scan(step, p, rows)[0],
                  donate_argnums=0)
    return run(clone_params(params), rows)
