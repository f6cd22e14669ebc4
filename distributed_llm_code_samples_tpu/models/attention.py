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
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def causal_mask(Tq: int, Tk: int, q_offset: int = 0, k_offset: int = 0):
    """True where query position may attend key position (q_pos >= k_pos).

    Offsets give the *global* positions of the local blocks — the thing a
    sequence-sharded ring step needs (``parallel.sequence``)."""
    q_pos = q_offset + jnp.arange(Tq)[:, None]
    k_pos = k_offset + jnp.arange(Tk)[None, :]
    return q_pos >= k_pos


def window_mask(q_pos, k_pos, window: int):
    """The sliding-window rule over broadcastable GLOBAL positions: a
    query at ``p`` sees the key at ``t`` where ``0 <= t <= p`` and ``p -
    t < window`` (the current token counts: ``window`` positions in
    all). A negative ``t`` is an entry that holds no position yet
    (``decode/paged.py::ring_positions``)."""
    return (k_pos >= 0) & (k_pos <= q_pos) & (q_pos - k_pos < window)


def aligned_mask(q_pos, k_pos, window: int):
    """The ALIGNED-window rule over broadcastable GLOBAL positions
    (``models/face.py::CHUNKED``): windows are the multiples of
    ``window`` and do not slide; a query at ``p`` sees the key at ``t``
    where ``t <= p`` lies in ``p``'s own window, ``t // window == p //
    window``. A negative ``t`` is an entry that holds no position yet."""
    return ((k_pos >= 0) & (k_pos <= q_pos)
            & (k_pos // window == q_pos // window))


def softmax_stats(s: jax.Array, sink=None):
    """Softmax over the last axis with its statistics: ``(p, m, l)``,
    the probabilities beside each row's maximum and its sum of ``exp(s
    - m)`` — what a join with another read of the same queries needs
    (``decode/paged.py::join_reads``). ``sink`` (broadcastable to
    ``s.shape[:-1]``): one more term of the denominator and of nothing
    else, ``m = max(sink, max_j s_j)`` and ``l = exp(sink - m) + sum_j
    exp(s_j - m)``; it has no probability of its own, so ``p`` sums to
    less than 1 (an attention sink: a head may put mass nowhere)."""
    m = jnp.max(s, axis=-1)
    if sink is not None:
        m = jnp.maximum(m, sink)
    e = jnp.exp(s - m[..., None])
    l = jnp.sum(e, axis=-1)
    if sink is not None:
        l = l + jnp.exp(sink - m)
    return e / l[..., None], m, l


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


def rope(x: jax.Array, positions: jax.Array, base: float = 10000.0,
         freqs=None, scale: float = 1.0) -> jax.Array:
    """Rotary position embedding (Su et al.): rotate each head-dim pair
    ``(x_i, x_{i+dh/2})`` by ``pos * base^(-2i/dh)`` — attention scores
    then depend only on *relative* position. ``x [..., T, dh]`` (``dh``
    even), ``positions [T]`` (absolute indices; decode passes the single
    write position). Linear in ``x``, so ``jax.vjp``'s exact transpose
    (the inverse rotation) differentiates it — the framework's stance for
    linear ops. ``freqs [dh/2]`` takes the place of the geometric
    ladder and ``scale`` multiplies ``cos`` and ``sin`` where a model's
    rotary is scaled (``Rotary``)."""
    dh = x.shape[-1]
    if dh % 2:
        raise ValueError(f"rope needs an even head dim, got {dh}")
    half = dh // 2
    if freqs is None:
        freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freqs   # [T, half]
    cos = jnp.cos(ang).astype(x.dtype)
    sin = jnp.sin(ang).astype(x.dtype)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1)


class Rotary(NamedTuple):
    """One layer type's rotary as a published ``rope_parameters`` entry
    states it: the base, the share of a head's lanes that is rotated
    (the FIRST ``partial_rotary_factor * dh``, paired half-split among
    themselves; the rest pass through) and, for ``rope_type: yarn``,
    the scaling (Peng et al., YaRN): pair ``i`` of the rotated lanes
    turns at ``f_i = theta^(-2i/D_rot)`` where it makes more than
    ``beta_fast`` turns over the ``original`` positions, at ``f_i /
    factor`` where it makes fewer than ``beta_slow``, on a linear ramp
    in between, and ``cos`` / ``sin`` are multiplied by
    ``attention_factor``. ``factor`` 1 is ``rope_type: default``."""
    theta: float = 10000.0
    partial: float = 1.0
    factor: float = 1.0
    original: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    @classmethod
    def from_config(cls, entry: dict) -> "Rotary":
        """A ``rope_parameters`` entry; a ``rope_type`` other than
        ``default`` and ``yarn`` is refused by name."""
        kind = entry.get("rope_type", "default")
        base = dict(theta=float(entry.get("rope_theta", 10000.0)),
                    partial=float(entry.get("partial_rotary_factor", 1.0)))
        if kind == "default":
            return cls(**base)
        if kind != "yarn":
            raise ValueError(f"rope_type {kind!r}: the rotary is served "
                             "as 'default' or 'yarn' only")
        factor = float(entry["factor"])
        af = entry.get("attention_factor")
        return cls(**base, factor=factor,
                   original=int(entry["original_max_position_embeddings"]),
                   beta_fast=float(entry.get("beta_fast", 32.0)),
                   beta_slow=float(entry.get("beta_slow", 1.0)),
                   attention_factor=float(
                       0.1 * math.log(factor) + 1.0 if af is None else af))

    def rot_dim(self, head_dim: int) -> int:
        return int(head_dim * self.partial)

    def freqs(self, head_dim: int) -> np.ndarray:
        """``[D_rot / 2]`` float32: each rotated pair's turn a position."""
        d = self.rot_dim(head_dim)
        i = np.arange(d // 2, dtype=np.float64)
        f = self.theta ** (-2.0 * i / d)
        if self.factor == 1.0:
            return f.astype(np.float32)

        def pair_of(turns):     # the pair that makes ``turns`` turns
            return (d * math.log(self.original / (turns * 2 * math.pi))
                    / (2 * math.log(self.theta)))

        lo = max(math.floor(pair_of(self.beta_fast)), 0)
        hi = min(math.ceil(pair_of(self.beta_slow)), d - 1)
        keep = 1.0 - np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
        return (f / self.factor * (1.0 - keep) + f * keep).astype(
            np.float32)

    def __call__(self, x: jax.Array, positions: jax.Array) -> jax.Array:
        """``rope`` of the first ``D_rot`` lanes of ``x [..., T, dh]``."""
        d = self.rot_dim(x.shape[-1])
        y = rope(x[..., :d], positions, freqs=self.freqs(x.shape[-1]),
                 scale=self.attention_factor)
        return y if d == x.shape[-1] else jnp.concatenate(
            [y, x[..., d:]], axis=-1)


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
                    table: jax.Array, head_dim: int,
                    v_head_dim: int | None = None):
    """Materialize one sequence's contiguous KV view from the block pool.

    ``pool_k/pool_v [L, n_blocks, block, H_kv*dh]`` (the WHOLE pool, as
    ``decode/paged.py`` stores it: a token's row holds its heads side by
    side), ``layer`` a Python int, ``table [max_blocks]`` int32 physical
    block ids, in sequence order. The layer rides inside the gather's
    indices, so no one-layer ``[n_blocks, ...]`` slab is sliced out of
    the pool first.
    Returns ``(k, v)`` each ``[H_kv, max_blocks * block, dh]`` (``v``'s
    lanes a head are ``v_head_dim`` where the two sides' rows differ:
    ``pool_v [L, n_blocks, block, H_kv*dv]``) — exactly
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
    run it: they attend over the rows as stored
    (``decode/paged.py::stored_decode_attn`` — for the full kind and a
    window layer's ring a walk over each row's live blocks where they
    lie, ``ops/kv_walk.py``; for a latent or int8 pool a gather of the
    rows' tables and two products over the copy,
    ``gathered_decode_attn``; ``paged.walks``
    decides from the pool. No f32 head-split copy of the view either
    way; both held to this oracle in tests/test_paged_layout.py)."""
    layers = jnp.full_like(table, layer)
    k = pool_k[layers, table]              # [MB, block, H_kv*dh]
    v = pool_v[layers, table]
    mb, blk, m = k.shape
    hkv = m // head_dim
    k = k.reshape(mb * blk, hkv, head_dim).transpose(1, 0, 2)
    v = v.reshape(mb * blk, hkv, v_head_dim or head_dim).transpose(1, 0, 2)
    return k, v


def chunk_attn(q: jax.Array, ck: jax.Array, cv: jax.Array,
               q_offset, mask=None, stats: bool = False, sink=None):
    """Prefill-chunk attention of ``Tq`` queries against a (gathered)
    cache that already holds the chunk's own keys: ``q [H, Tq, dh]``,
    ``ck [H_kv, T_cap, dh]``, ``cv [H_kv, T_cap, dv]`` with ``H % H_kv
    == 0`` (GQA groups); the result is ``[H, Tq, dv]``.
    The mask is the global causal rule via ``causal_mask(Tq, T_cap,
    q_offset)`` — query ``i`` (global position ``q_offset + i``) sees
    cache positions ``<= q_offset + i``, which also hides every
    not-yet-written pool position. ``q_offset`` may be a traced scalar
    (the chunked-prefill loop passes the running write head). ``mask
    [Tq, T_cap]`` takes the causal rule's place where the view is not
    in position order (a window layer's ring: ``window_mask``).
    ``stats``: ``(y, m [H, Tq], l [H, Tq])``, the result beside each
    row's score maximum and its sum of ``exp(s - m)``. ``sink [H]``:
    each head's sink, one more term of its softmax's denominator
    (``softmax_stats``)."""
    h, tq, dh = q.shape
    hkv, tcap, _ = ck.shape
    dv = cv.shape[-1]
    if h % hkv:
        raise ValueError(f"query heads {h} not divisible by kv heads "
                         f"{hkv}")
    qg = q.reshape(hkv, h // hkv, tq, dh)
    s = jnp.einsum("kgqd,ktd->kgqt", qg, ck) / jnp.sqrt(
        jnp.asarray(dh, q.dtype))
    if mask is None:
        mask = causal_mask(tq, tcap, q_offset=q_offset)
    s = jnp.where(mask, s, jnp.asarray(-1e30, s.dtype))
    if stats or sink is not None:
        p, m, l = softmax_stats(
            s, None if sink is None else sink.reshape(hkv, h // hkv, 1))
        y = jnp.einsum("kgqt,ktd->kgqd", p, cv).reshape(h, tq, dv)
        return (y, m.reshape(h, tq), l.reshape(h, tq)) if stats else y
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("kgqt,ktd->kgqd", p, cv).reshape(h, tq, dv)


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
