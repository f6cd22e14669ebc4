"""What the serving engine asks of a model family.

A family is one file under ``models/``: a params class (a pytree of the
weights) with the methods of ``ServedModel``. ``decode/programs.py``
builds every compiled step program from those answers and from the
cache (``decode/paged.py``); neither it nor the scheduler asks which
class the params are, reads a weight by name or calls a family's
arithmetic. ``models/lm.py``, ``models/hybrid_lm.py`` and
``models/mla_moe_lm.py`` are the families that exist;
``tests/test_model_face.py`` serves one more that lives in the test
alone. The builder keeps the cache write and read of an attention layer
(between ``attn_qkv`` and ``attn_out``, or for a latent-cache layer
between ``latent_qrow`` and ``latent_out``), the row of the recurrent
state a sequence owns, the residual adds, the expert layers' counters
and, under a mesh, the collectives.

What more than one family is written from lives below the face: ``mm``,
``rmsnorm``, the gated SiLU MLP, the attention stack and its q/k/v
(``qkv_heads``: QK-norm and the rotary base where the model has them);
the expert layer's is ``ops/moe_serve.py``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Protocol

import jax
import jax.numpy as jnp

from .attention import rope

ATTN = "attn"           # the layer kind whose cache is paged KV blocks
# ... and the kind whose paged cache row is ONE latent vector a token:
# no head axis and no K/V pair (multi-head latent attention, absorbed)
LATENT = "latent"


class CacheSpec(NamedTuple):
    """What a model keeps per served sequence, as sizes: ``kv_layers``
    layers own paged KV of ``kv_heads`` heads of ``head_dim`` lanes;
    ``rec_layers`` layers own a recurrent state of inner width
    ``d_inner``, state size ``d_state`` and ``d_conv`` convolution taps
    (0 for a model with none; ``d_state`` 0 with ``rec_layers`` > 0 is
    a recurrent layer that carries its convolution's tail and NO scan
    state). ``latent_rank`` > 0 says the ``kv_layers``
    are ``LATENT`` ones: a token's row is one vector of ``head_dim``
    lanes (``kv_heads`` 1) whose first ``latent_rank`` are also its
    values. Pool and state are built from this. Beside what is kept:
    ``expert_layers`` layers route their rows over ``n_experts`` held
    experts and count them, which sizes the counters a step program
    returns after its picks (0 for a model with no expert layer)."""
    kv_layers: int
    kv_heads: int
    head_dim: int
    rec_layers: int = 0
    d_inner: int = 0
    d_state: int = 0
    d_conv: int = 0
    latent_rank: int = 0
    expert_layers: int = 0
    n_experts: int = 0


class ServedModel(Protocol):
    """The face. ``l`` is a model layer, ``i`` the index a layer has in
    its own kind's weights and cache (``layers`` gives both), ``a`` the
    normed residual stream ``[N, d]``. A family without recurrent
    layers is never asked for the three ``recurrent_`` methods, one
    without ``LATENT`` layers never for the two ``latent_`` ones, one
    whose ``cache_spec`` names no expert layer never for
    ``ffn_counted``."""
    vocab: int
    d_model: int
    n_layers: int
    max_seq_len: int
    wte: jax.Array          # [V, d]: runtime/weights.py fingerprints row 0
    layers: tuple           # (kind, i) per model layer; kind ATTN or other
    norm_in: jax.Array      # [L, d] the gains before each mixer
    norm_ff: jax.Array      # [L, d] ... and before each FFN

    def cache_spec(self, n_heads: int) -> CacheSpec: ...

    # [N] -> [N, d]; lookup(table, tokens) is ``take`` below, or its
    # stand-in where the table is vocab-sharded
    def embed(self, tokens, positions, lookup): ...

    def norm(self, g, x): ...       # the family's norm with gain g

    # -> q [N, H, dh], k, v [N, H_kv, dh]: local head counts off the
    # weights' shapes, rotary inside when asked
    def attn_qkv(self, i, a, positions, head_dim, use_rope): ...

    def attn_out(self, i, y): ...   # y [N, H*dh] -> [N, d]

    # a LATENT layer: -> (q [N, H, m], row [N, m]), ``m`` the stored
    # row's lanes. ``row`` is what the cache keeps of each token, ``q``
    # the query FOR the stored rows, rotated and scaled already: the
    # read is ``p = softmax_t(q . row_t)`` and ``o = sum_t p_t
    # row_t[:latent_rank]``, no factor added
    def latent_qrow(self, i, a, positions): ...

    def latent_out(self, i, o): ...  # o [N, H, latent_rank] -> [N, d]

    # one token of b rows, a [b, d]: the state is advanced where it is
    # stored (``decode/paged.py::RecurrentState``: conv [L_r, S, 1,
    # (K-1)*D], ssm [L_r, S, N, D], both WHOLE), rows [b] naming each
    # row's slot -> (y, conv, ssm). Where the model's ``d_state`` is 0
    # there is no scan state: ``ssm`` is None in and out
    def recurrent_step(self, i, a, conv, ssm, rows): ...

    # a chunk of one row: a [c, d], tail [K-1, D], state [N, D] (None
    # where ``d_state`` is 0) -> (y, tail, state)
    def recurrent_chunk(self, i, a, tail, state): ...

    # a decode batch's b rows and then ONE sequence's chunk, a [b + c,
    # d], with the mixer's weights read once: the first ``len(rows)``
    # rows as ``recurrent_step``, the rest as ``recurrent_chunk`` from
    # ``tail, state`` -> (y, conv, ssm, tail, state)
    def recurrent_mixed(self, i, a, conv, ssm, rows, tail, state): ...

    def ffn(self, l, h): ...

    # the same for a family with expert layers: -> (y, rows), ``rows
    # [n_experts]`` int32 the rows of ``h`` each held expert received,
    # None for a layer that routes nothing
    def ffn_counted(self, l, h): ...

    # final norm and head: [N, d] -> [N, V] (the local V/n columns
    # of a vocab-sharded embedding)
    def head(self, x): ...


def layers_of(kinds: tuple) -> tuple:
    """``(kind, index)`` per model layer of a stack whose kinds mix: the
    index is the layer's place in its own kind's stack and in its
    kind's cache."""
    seen: dict = {}
    out = []
    for kind in kinds:
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return tuple(out)


def take(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return table[tokens]


def mm(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x [.., in] @ w[out, in].T``. Operands of one type multiply as
    they are; otherwise the activations take the weights' type and the
    product accumulates in float32."""
    if x.dtype == w.dtype:
        return x @ w.T
    return jnp.matmul(x.astype(w.dtype), w.T,
                      preferred_element_type=jnp.float32)


def rmsnorm(g: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    """Gain-only RMSNorm over the last axis, in float32."""
    x = x.astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return g.astype(jnp.float32) * (x * jax.lax.rsqrt(ms + eps))


class MLPStack(NamedTuple):
    """Gated SiLU MLPs, stacked ``[L, ...]``."""
    w_gate: jax.Array    # [L, F, d]
    w_up: jax.Array      # [L, F, d]
    w_down: jax.Array    # [L, d, F]


def gated_mlp(mlp: MLPStack, l: int, h: jax.Array) -> jax.Array:
    """``W_down (silu(W_gate h) * W_up h)`` of stack entry ``l``."""
    return mm(jax.nn.silu(mm(h, mlp.w_gate[l])) * mm(h, mlp.w_up[l]),
              mlp.w_down[l])


class AttnStack(NamedTuple):
    """The attention mixers, stacked ``[L_a, ...]`` (GQA by shape)."""
    wq: jax.Array        # [L_a, H*dh, d]
    wk: jax.Array        # [L_a, H_kv*dh, d]
    wv: jax.Array        # [L_a, H_kv*dh, d]
    wo: jax.Array        # [L_a, d, H*dh]


def qkv_heads(wq, wk, wv, i: int, a, positions, head_dim: int,
              use_rope: bool, theta: float | None = None, qk_norm=None):
    """Attention layer ``i`` of stacks ``[L_a, out, d]``: ``a [N, d] ->
    q [N, h_loc, dh], k/v [N, kv_loc, dh]``, rotated by ``positions
    [N]`` when asked, at the base ``theta`` where the model states one
    (``rope``'s own otherwise); the local head counts come off the
    (possibly head-sharded) weights' shapes. ``qk_norm = (g_q [dh], g_k
    [dh], eps)`` norms every head of ``q`` and of ``k`` over its own
    lanes (gain-only RMSNorm, float32) between the projection and the
    rotation."""
    q = mm(a, wq[i]).reshape(-1, wq.shape[1] // head_dim, head_dim)
    k = mm(a, wk[i]).reshape(-1, wk.shape[1] // head_dim, head_dim)
    v = mm(a, wv[i]).reshape(-1, wv.shape[1] // head_dim, head_dim)
    if qk_norm is not None:
        g_q, g_k, eps = qk_norm
        q, k = rmsnorm(g_q, q, eps), rmsnorm(g_k, k, eps)
    if use_rope:
        at = rope if theta is None else functools.partial(rope, base=theta)
        rot = jax.vmap(lambda x, pos: at(x[:, None, :], pos[None])[:, 0, :])
        q = rot(q, positions)
        k = rot(k, positions)
    return q, k, v
