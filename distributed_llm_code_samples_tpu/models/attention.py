"""Hand-written scaled-dot-product attention (single-device oracle).

The reference has **no attention at all** — FFN sublayers only
(``README.md:6``; SURVEY.md section 5 "long-context: absent"). Long-context
support is a first-class extension of this framework, so the model family
grows an attention op built in the same first-principles style as the FFN
core: forward written out, backward derived by hand and installed as the
``custom_vjp`` rule.

Shapes are single-head ``[T, d]``; multi-head is ``jax.vmap`` over a heads
axis (kept out of the op to keep the math readable). The distributed
sequence-parallel form (ring attention over ``ppermute``) lives in
``parallel.sequence``; this module is its correctness oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def causal_mask(Tq: int, Tk: int, q_offset: int = 0, k_offset: int = 0):
    """True where query position may attend key position (q_pos >= k_pos).

    Offsets give the *global* positions of the local blocks — the thing a
    sequence-sharded ring step needs (``parallel.sequence``)."""
    q_pos = q_offset + jnp.arange(Tq)[:, None]
    k_pos = k_offset + jnp.arange(Tk)[None, :]
    return q_pos >= k_pos


def attn_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
             causal: bool = True):
    """Softmax attention forward; returns ``(y, (p,))`` with the probability
    matrix saved for the manual backward."""
    d = q.shape[-1]
    s = (q @ k.T) / jnp.sqrt(jnp.asarray(d, q.dtype))
    if causal:
        s = jnp.where(causal_mask(q.shape[0], k.shape[0]), s,
                      jnp.asarray(-jnp.inf, s.dtype))
    p = jax.nn.softmax(s, axis=-1)
    return p @ v, (p,)


def attn_bwd(dy: jax.Array, q, k, v, p, causal: bool = True):
    """Manual attention VJP.

    With ``y = p v``, ``p = softmax(s)``, ``s = q k^T / sqrt(d)``:
    ``dv = p^T dy``; ``dp = dy v^T``;
    ``ds = p * (dp - rowsum(dp * p))`` (softmax VJP);
    ``dq = ds k / sqrt(d)``; ``dk = ds^T q / sqrt(d)``.
    """
    d = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, q.dtype))
    dv = p.T @ dy
    dp = dy @ v.T
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    dq = (ds @ k) * scale
    dk = (ds.T @ q) * scale
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = True) -> jax.Array:
    """Attention whose differentiation rule is the hand-written VJP.

    ``causal`` is a static (nondiff) argument: it selects the mask at trace
    time, so the op works identically in eager code and under jit/shard_map
    (as an operand it would be traced and break the Python branch)."""
    y, _ = attn_fwd(q, k, v, causal)
    return y


def _attention_fwd(q, k, v, causal):
    y, (p,) = attn_fwd(q, k, v, causal)
    return y, (q, k, v, p)


def _attention_bwd(causal, res, dy):
    q, k, v, p = res
    dq, dk, dv = attn_bwd(dy, q, k, v, p, causal)
    return dq, dk, dv


attention.defvjp(_attention_fwd, _attention_bwd)


def mha(q: jax.Array, k: jax.Array, v: jax.Array,
        causal: bool = True) -> jax.Array:
    """Multi-head convenience: vmap ``attention`` over a leading heads axis
    (``[H, T, d] -> [H, T, d]``)."""
    return jax.vmap(lambda q, k, v: attention(q, k, v, causal))(q, k, v)


def rope(x: jax.Array, positions: jax.Array,
         base: float = 10000.0) -> jax.Array:
    """Rotary position embedding (Su et al.): rotate each head-dim pair
    ``(x_i, x_{i+dh/2})`` by ``pos * base^(-2i/dh)`` — attention scores
    then depend only on *relative* position. ``x [..., T, dh]`` (``dh``
    even), ``positions [T]`` (absolute indices; decode passes the single
    write position). Linear in ``x``, so ``jax.vjp``'s exact transpose
    (the inverse rotation) differentiates it — the framework's stance for
    linear ops."""
    dh = x.shape[-1]
    if dh % 2:
        raise ValueError(f"rope needs an even head dim, got {dh}")
    half = dh // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freqs   # [T, half]
    cos = jnp.cos(ang).astype(x.dtype)
    sin = jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1)


def rope_mha(q: jax.Array, k: jax.Array, v: jax.Array,
             causal: bool = True) -> jax.Array:
    """Multi-head attention with rotary positions: rotates q and k by
    their in-window indices (``0..T-1``) before the hand-VJP kernel.
    Plugs into the trainers' ``attn`` hook (``attn_impl="rope"``); GQA
    shapes (fewer k heads) compose — the rotation is per-head-pair.

    Note: the relative-position property holds for this op; the LM
    family still adds its learned absolute embeddings (``wpe``) to the
    residual stream, so a rope-trained LM is rotary-IN-ATTENTION layered
    on learned positions, not relative-only."""
    t = q.shape[-2]
    pos = jnp.arange(t)
    op = mha if q.shape[0] == k.shape[0] else gqa
    return op(rope(q, pos), rope(k, pos), v, causal)


rope_mha.supports_gqa = True  # handles fewer k heads (see attn_sublayer)


# ---------------------------------------------------------------------------
# Paged-KV reads (the decode engine's block-table layout, decode/paged.py):
# the cache lives as a pool of fixed-size blocks and each sequence names
# its blocks through an int32 table — the KV read is a gather, so
# sequences of different lengths share one static-shape pool and freeing
# a sequence is a table edit, never a recompile.


def gather_paged_kv(pool_k: jax.Array, pool_v: jax.Array, layer: int,
                    table: jax.Array, head_dim: int):
    """Materialize one sequence's contiguous KV view from the block pool.

    ``pool_k/pool_v [L, n_blocks, block, H_kv*dh]`` (the WHOLE pool, as
    ``decode/paged.py`` stores it: a token's row holds its heads side by
    side), ``layer`` a Python int, ``table [max_blocks]`` int32 physical
    block ids, in sequence order. The layer rides inside the gather's
    indices, so no one-layer ``[n_blocks, ...]`` slab is sliced out of
    the pool first.
    Returns ``(k, v)`` each ``[H_kv, max_blocks * block, dh]`` — exactly
    the contiguous cache layout ``_decode_attn`` reads, so downstream
    attention is bit-identical to a contiguous cache holding the same
    values (the gather only moves bytes). Positions beyond the sequence
    length read whatever the table's tail blocks hold (the engine points
    unassigned table slots at the reserved scratch block); callers mask
    them, as with the zero tail of a contiguous cache.

    This gather + ``decode_attn`` two-pass is the DIFFERENTIAL ORACLE
    of the decode engine's cache read, and what the lockstep
    ``generate`` and the engine's prefill chunk (``chunk_attn`` over
    one slot's view) compute. The engine's decode-side programs do not
    run it: they attend over the gathered rows as stored
    (``decode/paged.py::stored_decode_attn`` — no f32 head-split copy
    of the view; held to this oracle in tests/test_paged_layout.py)."""
    layers = jnp.full_like(table, layer)
    k = pool_k[layers, table]              # [MB, block, H_kv*dh]
    v = pool_v[layers, table]
    mb, blk, m = k.shape
    hkv = m // head_dim
    k = k.reshape(mb * blk, hkv, head_dim).transpose(1, 0, 2)
    v = v.reshape(mb * blk, hkv, head_dim).transpose(1, 0, 2)
    return k, v


def chunk_attn(q: jax.Array, ck: jax.Array, cv: jax.Array,
               q_offset) -> jax.Array:
    """Prefill-chunk attention of ``Tq`` queries against a (gathered)
    cache that already holds the chunk's own keys: ``q [H, Tq, dh]``,
    ``ck/cv [H_kv, T_cap, dh]`` with ``H % H_kv == 0`` (GQA groups).
    The mask is the global causal rule via ``causal_mask(Tq, T_cap,
    q_offset)`` — query ``i`` (global position ``q_offset + i``) sees
    cache positions ``<= q_offset + i``, which also hides every
    not-yet-written pool position. ``q_offset`` may be a traced scalar
    (the chunked-prefill loop passes the running write head)."""
    h, tq, dh = q.shape
    hkv, tcap, _ = ck.shape
    if h % hkv:
        raise ValueError(f"query heads {h} not divisible by kv heads "
                         f"{hkv}")
    qg = q.reshape(hkv, h // hkv, tq, dh)
    s = jnp.einsum("kgqd,ktd->kgqt", qg, ck) / jnp.sqrt(
        jnp.asarray(dh, q.dtype))
    mask = causal_mask(tq, tcap, q_offset=q_offset)
    s = jnp.where(mask, s, jnp.asarray(-1e30, s.dtype))
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("kgqt,ktd->kgqd", p, cv).reshape(h, tq, dh)


def gqa(q: jax.Array, k: jax.Array, v: jax.Array,
        causal: bool = True) -> jax.Array:
    """Grouped-query attention: ``q [H, T, dh]``, ``k/v [H_kv, T, dh]``
    with ``H % H_kv == 0`` — each KV head serves ``H/H_kv`` query heads
    (the decode-memory optimization: KV-cache bytes drop by the group
    factor). Runs the same hand-VJP ``attention`` kernel per (kv-head,
    group) pair; ``H_kv == H`` reduces exactly to ``mha``."""
    hq, hkv = q.shape[0], k.shape[0]
    if hq % hkv:
        raise ValueError(f"query heads {hq} not divisible by kv heads "
                         f"{hkv}")
    qg = q.reshape(hkv, hq // hkv, *q.shape[1:])
    y = jax.vmap(lambda qs, k1, v1: jax.vmap(
        lambda q1: attention(q1, k1, v1, causal))(qs))(qg, k, v)
    return y.reshape(hq, *q.shape[1:])
