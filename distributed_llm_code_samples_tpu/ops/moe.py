"""MoE routing + dispatch/combine ops (single-device oracle for EP).

Built TPU-first: the router is top-k (k=1 Switch-style, k=2 GShard-style)
with a **static capacity** per expert, and dispatch/combine are dense
one-hot einsums — every shape is static, every FLOP lands on the MXU, and
there is no data-dependent control flow for XLA to choke on.

Capacity semantics (Switch/GShard): tokens overflowing an expert's
capacity are dropped from the expert computation; the *stack* passes every
token through a residual connection (``moe_stack_fwd``), so a dropped
token keeps its input activation instead of zeroing out for the rest of
the stack — the standard Switch drop behavior. ``moe_layer`` itself (the
raw layer, no residual) emits zeros for dropped tokens. With k=2, rank-0
choices of *all* tokens claim slots before any rank-1 choice (choice-major
priority), the GShard ordering.

Load balancing: ``router_aux_loss`` is the Switch auxiliary loss
``E * sum_e f_e * P_e`` (``f_e`` = fraction of tokens whose top-1 choice
is expert ``e``, ``P_e`` = mean router probability of ``e``) — minimized
at uniform routing, differentiable through ``P_e``. Trainers add
``aux_coef * d(aux)/d(params)`` to the gradients.

Differentiation follows the framework's stance (``train_ffns.py:1-3``): the
expert FFN compute runs the hand-written ``ffn_block`` VJP (vmapped over
experts); dispatch/combine are *linear* one-hot contractions whose VJPs are
exact transposes that ``jax.vjp`` composes; the router gradient flows
through the softmax gate that scales the combine (the argmax one-hot itself
is piecewise-constant — zero gradient — as in Switch).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .ffn import ffn_block


def expert_capacity(tokens: int, n_experts: int,
                    capacity_factor: float = 2.0) -> int:
    """Static per-expert slot count: ``ceil(tokens/E * factor)``."""
    return max(1, int(math.ceil(tokens / n_experts * capacity_factor)))


def route_top1(wg: jax.Array, x: jax.Array):
    """Top-1 router. ``wg [E, d]``, ``x [T, d]`` -> ``(idx [T], gate [T])``
    where ``gate`` is the chosen expert's softmax probability (the
    differentiable path to the router weights)."""
    logits = x @ wg.T                      # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argmax(logits, axis=-1)      # [T]
    gate = jnp.take_along_axis(probs, idx[:, None], axis=-1)[:, 0]
    return idx, gate


def route_topk(wg: jax.Array, x: jax.Array, k: int = 2,
               renormalize: bool = True):
    """Top-k router. Returns ``(idx [T, k], gates [T, k])``; with
    ``renormalize`` the k gates sum to 1 per token (the GShard top-2
    convention; k=1 + renormalize=False reduces to ``route_top1``)."""
    logits = x @ wg.T                              # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(logits, k)              # [T, k], distinct experts
    gates = jnp.take_along_axis(probs, idx, axis=-1)
    if renormalize:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return idx, gates


def dispatch_tensor(idx: jax.Array, n_experts: int, capacity: int,
                    dtype=jnp.float32):
    """One-hot dispatch ``D [T, E, C]``: ``D[t, e, c] = 1`` iff token ``t``
    is the ``c``-th token routed to expert ``e`` (first-come-first-served in
    token order; overflow rows are all-zero — the token is dropped).

    Slot positions are counted in f32 regardless of ``dtype`` (a bf16
    cumsum misorders slots past 256 tokens); only the output adopts it.
    """
    onehot = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)   # [T, E]
    pos = jnp.cumsum(onehot, axis=0) * onehot - onehot           # [T, E]
    keep = (pos < capacity).astype(jnp.float32) * onehot
    slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                          dtype=jnp.float32)                      # [T, E, C]
    return (slot * keep[:, :, None]).astype(dtype)


def dispatch_tensor_topk(idx: jax.Array, n_experts: int, capacity: int,
                         dtype=jnp.float32):
    """Top-k dispatch ``D [k, T, E, C]`` with choice-major slot priority:
    every token's rank-0 choice claims its slot before any token's rank-1
    choice (GShard ordering), so under pressure second choices drop first.

    ``idx [T, k]``. Each (token, choice) pair gets at most one slot;
    summing over ``k`` gives the combined ``[T, E, C]`` dispatch (a token's
    k choices are distinct experts, so slots never collide).
    """
    t, k = idx.shape
    flat = idx.T.reshape(-1)                       # [k*T], choice-major
    disp = dispatch_tensor(flat, n_experts, capacity, dtype)  # [k*T, E, C]
    return disp.reshape(k, t, n_experts, capacity)


def _slot_positions(idx_flat: jax.Array, n_experts: int, capacity: int):
    """Per-(token, choice) slot bookkeeping without the ``[N, E, C]``
    tensor: position of each flat choice within its chosen expert
    (first-come-first-served in flat order — identical semantics to
    ``dispatch_tensor``'s cumsum) and the capacity keep-mask. O(N*E)
    elementwise work, no O(N*E*C) anything."""
    onehot = jax.nn.one_hot(idx_flat, n_experts, dtype=jnp.float32)
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot - onehot,
                  axis=-1)                                     # [N]
    keep = pos < capacity
    return pos.astype(jnp.int32), keep


def route_flat(wg: jax.Array, x: jax.Array, k: int):
    """Routing in the scatter paths' flat choice-major layout:
    ``(idx_flat [k*T], gates [T, k])`` — rank-0 choices of all tokens
    precede any rank-1 choice, the GShard priority order."""
    if k == 1:
        idx, gates = route_top1(wg, x)
        return idx, gates[:, None]
    idx2, gates = route_topk(wg, x, k)
    return idx2.T.reshape(-1), gates


def scatter_dispatch(idx_flat: jax.Array, x: jax.Array, n_experts: int,
                     capacity: int):
    """Scatter tokens into the ``[E, C, d]`` expert-slot buffer:
    O(N*d) movement, dropped choices land in a dummy row that is sliced
    off. Returns ``(xe [E, C, d], dest [N], keep [N])`` — ``dest`` and
    ``keep`` feed ``scatter_combine``. Shared by the single-device and
    EP scatter paths so the slot bookkeeping cannot drift."""
    t, d = x.shape
    pos, keep = _slot_positions(idx_flat, n_experts, capacity)
    dest = jnp.where(keep, idx_flat * capacity + pos,
                     n_experts * capacity)
    tok = jnp.tile(jnp.arange(t), idx_flat.shape[0] // t)
    xe = jnp.zeros((n_experts * capacity + 1, d),
                   x.dtype).at[dest].add(x[tok])
    return xe[:-1].reshape(n_experts, capacity, d), dest, keep


def scatter_combine(ye: jax.Array, dest: jax.Array, keep: jax.Array,
                    gates: jax.Array, t: int) -> jax.Array:
    """Gather expert outputs back to their tokens and apply the gate
    scale: ``ye [E, C, d]`` -> ``[t, d]`` (dropped choices contribute
    zero via the dummy row)."""
    ec, d = ye.shape[0] * ye.shape[1], ye.shape[-1]
    padded = jnp.concatenate([ye.reshape(ec, d),
                              jnp.zeros((1, d), ye.dtype)])
    y_choice = padded[dest] * keep[:, None].astype(ye.dtype)
    return jnp.einsum("ktd,tk->td", y_choice.reshape(-1, t, d),
                      gates.astype(ye.dtype))


def gather_metadata(idx_flat: jax.Array, t: int, n_experts: int,
                    capacity: int):
    """Routing metadata for the gather dispatch: ``dest [N]`` (each flat
    choice's slot, dummy ``E*C`` when dropped), ``slot_tok [E*C]`` (the
    token filling each slot, dummy ``t`` when unclaimed), ``slot_choice
    [E*C]`` (the flat choice claiming each slot, dummy ``N``), ``keep
    [N]``. The only scatters in the whole gather path live here, and
    they move O(N) int32 elements — not O(N*d) rows."""
    n = idx_flat.shape[0]
    pos, keep = _slot_positions(idx_flat, n_experts, capacity)
    dest = jnp.where(keep, idx_flat * capacity + pos,
                     n_experts * capacity)
    tok = jnp.tile(jnp.arange(t, dtype=jnp.int32), n // t)
    slots = n_experts * capacity
    slot_tok = jnp.full((slots + 1,), t, jnp.int32).at[dest].set(tok)
    slot_choice = jnp.full((slots + 1,), n, jnp.int32).at[dest].set(
        jnp.arange(n, dtype=jnp.int32))
    return dest, slot_tok[:-1], slot_choice[:-1], keep


@jax.custom_vjp
def permute_to_slots(x: jax.Array, dest: jax.Array, slot_tok: jax.Array):
    """Dispatch as a PERMUTATION GATHER: ``xe[s] = x[slot_tok[s]]``
    (zero row for unclaimed slots). The kept (token, choice) -> slot map
    is a bijection, so the VJP is ALSO a gather — ``dx[t] = sum_k
    dxe[dest[k*T + t]]`` — instead of the scatter-add ``jax.vjp`` would
    derive from a forward scatter. On TPU gathers vectorize while
    scatter serializes; this removes every O(N*d) scatter from the
    dispatch path, both directions."""
    xp = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
    return xp[slot_tok]                                   # [E*C, d]


def _pts_fwd(x, dest, slot_tok):
    return permute_to_slots(x, dest, slot_tok), (x.shape[0], dest)


def _pts_bwd(res, dxe):
    t, dest = res
    dxp = jnp.concatenate([dxe, jnp.zeros((1, dxe.shape[1]), dxe.dtype)])
    dx = jnp.sum(dxp[dest].reshape(-1, t, dxe.shape[1]), axis=0)
    f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731
    return dx, f0(dest), f0(jnp.zeros(dxe.shape[0], jnp.int32))


def _combine_gather(ye_flat, dest, keep, gates, t):
    """Shared fwd math: gather each choice's slot row, gate-scale, sum
    over choices. ``ye_flat [E*C, d]``."""
    d = ye_flat.shape[-1]
    padded = jnp.concatenate([ye_flat, jnp.zeros((1, d), ye_flat.dtype)])
    y_choice = padded[dest] * keep[:, None].astype(ye_flat.dtype)
    return jnp.einsum("ktd,tk->td", y_choice.reshape(-1, t, d),
                      gates.astype(ye_flat.dtype)), y_choice


@jax.custom_vjp
def combine_from_slots(ye: jax.Array, gates: jax.Array, dest: jax.Array,
                       slot_tok: jax.Array, slot_choice: jax.Array,
                       keep: jax.Array):
    """Combine with a gather-only VJP. Forward is ``scatter_combine``'s
    math exactly (gather slot rows by ``dest``, gate-scale, sum over
    choices); the backward uses the slot->token/choice inverse maps so
    ``dye[s] = gate[slot_choice[s]] * dy[slot_tok[s]]`` is a gather too
    — where autodiff's transpose of the forward gather would be an
    O(N*d) scatter-add."""
    ye_flat = ye.reshape(-1, ye.shape[-1])
    t = gates.shape[0]
    y, _ = _combine_gather(ye_flat, dest, keep, gates, t)
    return y


def _cfs_fwd(ye, gates, dest, slot_tok, slot_choice, keep):
    ye_flat = ye.reshape(-1, ye.shape[-1])
    t = gates.shape[0]
    y, y_choice = _combine_gather(ye_flat, dest, keep, gates, t)
    return y, (y_choice, gates, dest, slot_tok, slot_choice, keep,
               ye.shape)


def _cfs_bwd(res, dy):
    y_choice, gates, dest, slot_tok, slot_choice, keep, ye_shape = res
    t, k = gates.shape
    d = dy.shape[-1]
    # dye[s]: the gate of the choice that claimed s, times dy of the
    # token that claimed s — dummy rows of the padded operands make
    # unclaimed slots come out exactly zero
    gates_flat = (gates.T.reshape(-1)
                  * keep.astype(gates.dtype))            # [k*T] choice-major
    gates_pad = jnp.concatenate([gates_flat,
                                 jnp.zeros((1,), gates.dtype)])
    dy_pad = jnp.concatenate([dy, jnp.zeros((1, d), dy.dtype)])
    dye = (gates_pad[slot_choice][:, None].astype(dy.dtype)
           * dy_pad[slot_tok]).reshape(ye_shape)
    # dgates[t, k] = <dy[t], y_choice[k, t]> (y_choice already carries
    # the keep mask; it is the UN-gated slot row gathered in fwd)
    dgates = jnp.einsum("td,ktd->tk",
                        dy, y_choice.reshape(k, t, d)).astype(gates.dtype)
    f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731
    return (dye, dgates, f0(dest), f0(slot_tok), f0(slot_choice),
            np.zeros(keep.shape, jax.dtypes.float0))


permute_to_slots.defvjp(_pts_fwd, _pts_bwd)
combine_from_slots.defvjp(_cfs_fwd, _cfs_bwd)


def moe_layer_gather(wg: jax.Array, w1: jax.Array, w2: jax.Array,
                     x: jax.Array, capacity_factor: float = 2.0,
                     k: int = 1, capacity: int | None = None
                     ) -> jax.Array:
    """``moe_layer`` with the gather dispatch: identical routing,
    capacity drops, and GShard choice-major priority — but every
    O(T*d) data movement in BOTH directions is a gather
    (``permute_to_slots`` / ``combine_from_slots``), with only O(k*T)
    int32 scatters for the slot bookkeeping. The third dispatch
    formulation next to ``moe_layer`` (one-hot einsums, O(k*T^2*cf*d)
    MXU work) and ``moe_layer_scatter`` (scatter-add rows, serialized
    on TPU). No cell of ``BENCHMARK.json`` trains through any of the
    three, so which one the chip defends is unmeasured (ROADMAP W2 /
    C3)."""
    n_experts = w1.shape[0]
    t = x.shape[0]
    cap = (expert_capacity(t, n_experts, capacity_factor)
           if capacity is None else capacity)
    idx_flat, gates = route_flat(wg, x, k)
    dest, slot_tok, slot_choice, keep = gather_metadata(
        idx_flat, t, n_experts, cap)
    xe = permute_to_slots(x, dest, slot_tok).reshape(n_experts, cap, -1)
    ye = jax.vmap(ffn_block)(w1, w2, xe)
    return combine_from_slots(ye, gates, dest, slot_tok, slot_choice,
                              keep)


def moe_layer_scatter(wg: jax.Array, w1: jax.Array, w2: jax.Array,
                      x: jax.Array, capacity_factor: float = 2.0,
                      k: int = 1, capacity: int | None = None
                      ) -> jax.Array:
    """``moe_layer`` with scatter/gather dispatch — same routing, same
    capacity drops, same GShard choice-major priority, bitwise-same
    top-k/gates — but the token movement is O(T*d) scatter-add into the
    ``[E*C, d]`` expert buffer and an O(T*d) gather back, instead of the
    dense one-hot einsums' O(T*E*C*d) MXU work (``T*E*C = k*T^2 *
    capacity_factor``: QUADRATIC in tokens at fixed capacity factor,
    which at bench scale dwarfs the expert FFN compute itself).

    Every shape is static: dropped choices scatter into a dummy row
    (``E*C``) that is sliced off before the expert compute. All moves
    are linear (scatter-add / gather), so ``jax.vjp`` differentiates
    them exactly, and the router gradient still flows through the gate
    scale — the framework's linear-op stance unchanged. Differential-
    pinned leaf-for-leaf against ``moe_layer`` (tests/test_moe.py)."""
    n_experts = w1.shape[0]
    t = x.shape[0]
    cap = (expert_capacity(t, n_experts, capacity_factor)
           if capacity is None else capacity)
    idx_flat, gates = route_flat(wg, x, k)
    xe, dest, keep = scatter_dispatch(idx_flat, x, n_experts, cap)
    ye = jax.vmap(ffn_block)(w1, w2, xe)
    return scatter_combine(ye, dest, keep, gates, t)


def router_aux_loss(wg: jax.Array, x: jax.Array) -> jax.Array:
    """Switch load-balancing loss ``E * sum_e f_e * P_e`` on one layer's
    input tokens. ``f_e`` uses the (non-differentiable) top-1 assignment;
    the gradient flows through ``P_e``. Equals 1 at perfectly uniform
    routing; rises as routing collapses."""
    logits = x @ wg.T
    n_experts = wg.shape[0]
    probs = jax.nn.softmax(logits, axis=-1)                  # [T, E]
    top1 = jax.lax.stop_gradient(
        jax.nn.one_hot(jnp.argmax(logits, axis=-1), n_experts,
                       dtype=probs.dtype))
    f = jnp.mean(top1, axis=0)                               # [E]
    p = jnp.mean(probs, axis=0)                              # [E]
    return n_experts * jnp.sum(f * p)


def moe_layer(wg: jax.Array, w1: jax.Array, w2: jax.Array, x: jax.Array,
              capacity_factor: float = 2.0, k: int = 1,
              capacity: int | None = None) -> jax.Array:
    """One MoE FFN layer, dense single-device form (no residual here —
    the stack adds it).

    ``wg [E, d]``, ``w1 [E, ffn, d]``, ``w2 [E, d, ffn]``, ``x [T, d]``.
    Dispatch -> per-expert hand-VJP FFN (``ffn_block`` vmapped over the
    expert axis) -> gate-scaled combine. Dropped (token, choice) pairs
    contribute zero. ``capacity`` overrides the per-expert slot count
    (the EP-emulating dense oracle passes the grouped EP capacity, which
    ceil-rounds differently from deriving it from this ``x``'s tokens).
    """
    n_experts = w1.shape[0]
    cap = (expert_capacity(x.shape[0], n_experts, capacity_factor)
           if capacity is None else capacity)
    if k == 1:
        idx, gate = route_top1(wg, x)
        disp = dispatch_tensor(idx, n_experts, cap, x.dtype)  # [T, E, C]
        comb = disp * gate[:, None, None]
    else:
        idx, gates = route_topk(wg, x, k)
        disp_k = dispatch_tensor_topk(idx, n_experts, cap, x.dtype)
        disp = jnp.sum(disp_k, axis=0)                        # [T, E, C]
        comb = jnp.einsum("ktec,tk->tec", disp_k, gates)
    xe = jnp.einsum("tec,td->ecd", disp, x)                   # [E, C, d]
    ye = jax.vmap(ffn_block)(w1, w2, xe)                      # [E, C, d]
    return jnp.einsum("tec,ecd->td", comb, ye)


def moe_stack_fwd_aux(params, x: jax.Array, capacity_factor: float = 2.0,
                      k: int = 1, capacity: int | None = None,
                      dispatch: str = "dense"):
    """Stack of MoE layers (``MoEStackParams``) with a residual around each
    layer (Switch semantics: a capacity-dropped token passes through
    unchanged rather than zeroing for the rest of the stack). Returns
    ``(y, aux)`` where ``aux`` is the total ``router_aux_loss``, each
    layer scored on its own residual-chained input — one walk computes
    both, so trainers can take a single ``vjp`` with cotangents
    ``(dloss_dx, aux_coef)``. ``dispatch`` selects the token movement:
    ``"dense"`` one-hot einsums, ``"scatter"`` (``moe_layer_scatter`` —
    same math, O(T*d) scatter-add movement), or ``"gather"``
    (``moe_layer_gather`` — gather-only movement both directions)."""
    layers = {"dense": moe_layer, "scatter": moe_layer_scatter,
              "gather": moe_layer_gather}
    if dispatch not in layers:
        raise ValueError(f"unknown dispatch {dispatch!r}")
    layer = layers[dispatch]
    aux = jnp.asarray(0.0, jnp.float32)
    for l in range(params.w1.shape[0]):
        aux = aux + router_aux_loss(params.wg[l], x)
        x = x + layer(params.wg[l], params.w1[l], params.w2[l], x,
                      capacity_factor, k, capacity)
    return x, aux


def moe_stack_fwd(params, x: jax.Array, capacity_factor: float = 2.0,
                  k: int = 1, capacity: int | None = None,
                  dispatch: str = "dense") -> jax.Array:
    """Output half of ``moe_stack_fwd_aux``."""
    return moe_stack_fwd_aux(params, x, capacity_factor, k, capacity,
                             dispatch)[0]


def moe_stack_aux(params, x: jax.Array, capacity_factor: float = 2.0,
                  k: int = 1, capacity: int | None = None) -> jax.Array:
    """Aux half of ``moe_stack_fwd_aux``."""
    return moe_stack_fwd_aux(params, x, capacity_factor, k, capacity)[1]
