"""What the serving engine asks of a model family.

A family is one file under ``models/``: a params class (a pytree of the
weights) with the methods of ``ServedModel``. ``decode/programs.py``
builds every compiled step program from those answers and from the
cache (``decode/paged.py``); neither it nor the scheduler asks which
class the params are, reads a weight by name or calls a family's
arithmetic. ``models/lm.py`` and ``models/hybrid_lm.py`` are the two
families that exist; ``tests/test_model_face.py`` serves a third that
lives in the test alone. The builder keeps the cache write and read of
an attention layer (between ``attn_qkv`` and ``attn_out``), the row of
the recurrent state a sequence owns, the residual adds and, under a
mesh, the collectives.
"""

from __future__ import annotations

from typing import NamedTuple, Protocol

import jax
import jax.numpy as jnp

from .attention import rope

ATTN = "attn"           # the layer kind whose cache is paged KV blocks


class CacheSpec(NamedTuple):
    """What a model keeps per served sequence, as sizes: ``kv_layers``
    layers own paged KV of ``kv_heads`` heads of ``head_dim`` lanes;
    ``rec_layers`` layers own a recurrent state of inner width
    ``d_inner``, state size ``d_state`` and ``d_conv`` convolution taps
    (0 for a model with none). Pool and state are built from this."""
    kv_layers: int
    kv_heads: int
    head_dim: int
    rec_layers: int = 0
    d_inner: int = 0
    d_state: int = 0
    d_conv: int = 0


class ServedModel(Protocol):
    """The face. ``l`` is a model layer, ``i`` the index a layer has in
    its own kind's weights and cache (``layers`` gives both), ``a`` the
    normed residual stream ``[N, d]``. A family without recurrent
    layers is never asked for the two ``recurrent_`` methods."""
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

    # one token of b rows: a [b, d], tail [b, K-1, D], state [b, N, D];
    # a chunk of one row: a [c, d], tail [K-1, D], state [N, D]
    # -> (y, tail, state)
    def recurrent_step(self, i, a, tail, state): ...
    def recurrent_chunk(self, i, a, tail, state): ...

    def ffn(self, l, h): ...

    # final norm and tied head: [N, d] -> [N, V] (the local V/n columns
    # of a vocab-sharded embedding)
    def head(self, x): ...


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


def qkv_heads(wq, wk, wv, i: int, a, positions, head_dim: int,
              use_rope: bool):
    """Attention layer ``i`` of stacks ``[L_a, out, d]``: ``a [N, d] ->
    q [N, h_loc, dh], k/v [N, kv_loc, dh]``, rotated by ``positions
    [N]`` when asked; the local head counts come off the (possibly
    head-sharded) weights' shapes."""
    q = mm(a, wq[i]).reshape(-1, wq.shape[1] // head_dim, head_dim)
    k = mm(a, wk[i]).reshape(-1, wk.shape[1] // head_dim, head_dim)
    v = mm(a, wv[i]).reshape(-1, wv.shape[1] // head_dim, head_dim)
    if use_rope:
        rot = jax.vmap(lambda x, pos: rope(x[:, None, :],
                                           pos[None])[:, 0, :])
        q = rot(q, positions)
        k = rot(k, positions)
    return q, k, v
