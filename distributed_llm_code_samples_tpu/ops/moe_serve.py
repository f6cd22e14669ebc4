"""The serving expert layer: dropless, and told which experts it holds.

``ops/moe.py`` is the trainers' layer (softmax gates, a static capacity,
overflowing tokens dropped, hand VJPs). Serving has no capacity to run
out of: every row reaches every expert it chose. And a chip may hold
only a contiguous range of a layer's experts (expert parallelism: the
range is the chip's share), so the layer here is in two parts:

- ``route``: over ALL ``n_routed`` experts, in float32 at ``highest``
  (a choice flips on a rounding where two scores nearly tie, and the
  layer's output jumps with it): ``s = sigmoid(W_r a)``, or ``softmax(W_r
  a)`` over all of them, as the model's family says (``SCORES``: a
  property of the model, read from its spec; no option of the engine);
  the ``top_k`` of ``s + b`` are chosen (``b`` the choice bias where
  the family has one: it moves the choice and never the weight);
  ``w_k = scale * s_k / sum_chosen s``.
- ``held_part``: the weighted part of the result that the held experts
  ``[first, first + E_held)`` give, each the gated SiLU MLP
  ``W_down (silu(W_gate a) * W_up a)``, and the rows each of them
  received. A choice that falls outside the range adds nothing here: it
  is another holder's. The parts of all holders add up to the layer
  (``tests/test_mla_moe_lm.py``, ``tests/test_lfm2_moe_lm.py``); a shared
  expert is the caller's, once.

What every served expert family keeps of the layer is here too, once:
the stack of an expert layer's weights (``ExpertStack``), the routed
part of layer ``x`` over it (``routed``) and the cut of a model to one
holder's range (``holder``). ``models/mla_moe_lm.py`` adds a shared
expert beside the routed part; ``models/lfm2_moe_lm.py`` has none.

ONE formulation, chosen on the chip (``PERF.md`` section 6 has both
readings): every held expert runs over every row and the gates, zero
where a row did not choose the expert, weigh the hidden activations
before the down projection. That is ``E_held / top_k`` times the
arithmetic of the chosen pairs alone and the same weight bytes, read
once as three plain matrix products — at serving batches the layer is
bound by those bytes, and the MXU has the room.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST

# a router's score function over the logits ``[N, E]``, by name
SCORES = {"sigmoid": jax.nn.sigmoid,
          "softmax": lambda z: jax.nn.softmax(z, axis=-1)}


class ExpertStack(NamedTuple):
    """The expert layers' routed part, stacked ``[L_e, ...]``: the
    router over all ``E`` experts (float32) and the ``E_held`` held."""
    w_router: jax.Array  # [L_e, E, d] float32
    # [L_e, E] float32, used for the choice only; None: no choice bias
    bias: jax.Array | None
    w_gate: jax.Array    # [L_e, E_held, F, d]
    w_up: jax.Array      # [L_e, E_held, F, d]
    w_down: jax.Array    # [L_e, E_held, d, F]


def route(a: jax.Array, w_router: jax.Array, bias, top_k: int,
          scale: float, score: str = "sigmoid"):
    """``a [N, d]``, ``w_router [E, d]``, ``bias [E]`` or None -> ``(idx
    [N, k] int32, w [N, k] float32)``: the chosen experts of each row
    and their weights, which sum to ``scale``; ``score`` names the
    family's score function (``SCORES``)."""
    s = SCORES[score](jnp.matmul(a.astype(jnp.float32),
                                 w_router.astype(jnp.float32).T,
                                 precision=HI))
    _, idx = jax.lax.top_k(
        s if bias is None else s + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def held_part(a: jax.Array, idx: jax.Array, w: jax.Array,
              w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
              first: int = 0):
    """``a [N, d]``; ``idx, w [N, k]`` from ``route``; the held experts'
    ``w_gate, w_up [E_held, F, d]``, ``w_down [E_held, d, F]`` ->
    ``(y [N, d] float32, rows [E_held] int32)``."""
    held, f, d = w_gate.shape
    hit = idx[:, :, None] == first + jnp.arange(held)       # [N, k, held]
    rows = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)
    # row n's weight for held expert e, 0 where it did not choose it
    g = jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1)
    x = a.astype(w_gate.dtype)

    def up(m):                          # [N, d] x [held*F, d] -> [N, held, F]
        return jnp.matmul(x, m.reshape(held * f, d).T,
                          preferred_element_type=jnp.float32
                          ).reshape(-1, held, f)

    act = jax.nn.silu(up(w_gate)) * up(w_up) * g[:, :, None]
    y = jnp.einsum("nef,edf->nd", act.astype(w_down.dtype), w_down,
                   preferred_element_type=jnp.float32)
    return y, rows


def routed(e: ExpertStack, x: int, h: jax.Array, top_k: int, scale: float,
           first: int = 0, score: str = "sigmoid"):
    """Expert layer ``x`` of the stack over ``h [N, d]``: ``route`` over
    all experts, then the held experts' part -> ``(y [N, d], rows
    [E_held])``, ``first`` the global id of the first held expert."""
    idx, w = route(h, e.w_router[x], None if e.bias is None else e.bias[x],
                   top_k, scale, score)
    return held_part(h, idx, w, e.w_gate[x], e.w_up[x], e.w_down[x], first)


def holder(p, first: int, count: int):
    """The same model holding experts ``[first, first + count)`` of
    every expert layer: what one of ``E / count`` chips that share the
    layers would be given (the router stays whole). ``p`` is a family's
    params: a dataclass with an ``experts: ExpertStack`` and the global
    id of its first held expert, ``expert_first``."""
    lo = first - p.expert_first
    e = p.experts
    cut = e._replace(**{k: getattr(e, k)[:, lo:lo + count]
                        for k in ("w_gate", "w_up", "w_down")})
    return dataclasses.replace(p, experts=cut, expert_first=first)
