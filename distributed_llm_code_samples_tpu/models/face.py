"""What the serving engine asks of a model family.

A family is one file under ``models/``: a params class (a pytree of the
weights) with the methods of ``ServedModel``. ``decode/programs.py``
builds every compiled step program from those answers and from the
cache (``decode/paged.py``); neither it nor the scheduler asks which
class the params are, reads a weight by name or calls a family's
arithmetic. ``models/lm.py``, ``models/hybrid_lm.py``,
``models/mla_moe_lm.py``, ``models/lfm2_moe_lm.py``,
``models/laguna_lm.py``, ``models/evabyte_lm.py``,
``models/mimo_v2_flash_lm.py`` and ``models/qwen3_next_lm.py`` are the
families that exist;
``tests/test_model_face.py`` serves one more that lives in the test
alone. The builder keeps the cache write and read of an attention layer
(between ``attn_qkv`` and ``attn_out``; for a latent-cache layer
between ``latent_qrow`` and ``latent_out``; for a sliding-window layer
between ``window_qkv`` and ``window_out``, with ``window_sink`` for the
term a head may add to its softmax's denominator; for a chunk-summarised layer
between ``chunked_qkv`` and ``chunked_out``, with ``chunk_summary`` for
the row it keeps of every finished chunk), the row of the recurrent
state a sequence owns, the residual adds, the expert layers' counters
and, under a mesh, the collectives.

Four layer kinds keep a PAGED cache, each with its own weight stack
and its own cache index: ``ATTN`` (K/V blocks over the whole sequence),
``LATENT`` (one latent row a token in the same pool's place),
``WINDOW`` (K/V blocks of the last ``CacheSpec.window`` positions only,
in a pool and a block table of their own beside the full kind's:
``decode/paged.py``) and ``CHUNKED``, whose ONE layer owns TWO stores
under the same index: the window kind's ring for the exact K/V of the
current ALIGNED window, and a row of the full kind's pool for every
finished chunk of ``CacheSpec.chunk`` positions (its summary), the two
reads joined under one softmax. Every other kind is recurrent: a state
row by slot, whose sizes the model states once (``StateRow``: a Mamba-1
scan state ``[N, D]`` behind a convolution over the same ``D`` lanes, a
gated short convolution's tail alone, a gated delta rule's matrix a
head behind a convolution over ``q``, ``k`` and ``v``).

What more than one family is written from lives below the face: ``mm``,
``rmsnorm``, the gated SiLU MLP, the attention stack and its q/k/v
(``qkv_heads``: QK-norm and the model's rotary where it has them), the
per-head output gate (``head_gate``); the expert layer's is
``ops/moe_serve.py``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Protocol

import jax
import jax.numpy as jnp

from .attention import Rotary, rope

ATTN = "attn"           # the layer kind whose cache is paged KV blocks
# ... and the kind whose paged cache row is ONE latent vector a token:
# no head axis and no K/V pair (multi-head latent attention, absorbed)
LATENT = "latent"
# ... and the kind that attends over the last ``window`` positions only:
# paged KV blocks in a pool of their own, a sequence's blocks reused as
# a ring once every position in them is behind the window
WINDOW = "window"
# ... and the kind that sees the keys of its own ALIGNED window of
# ``window`` positions exactly (the window kind's ring) and every
# earlier window as one summary row a finished chunk of ``chunk``
# positions (rows of the full kind's pool), under ONE softmax
CHUNKED = "chunked"


class KVRow(NamedTuple):
    """One paged store's row, the ONE description its pool, the writes,
    the reads, the byte counts and the host's block documents take
    their widths from: ``heads`` KV heads side by side, each a key of
    ``k_dim`` lanes in the K side's row and a value of ``v_dim`` lanes
    in the V side's (the two sides are arrays of their own, so their
    rows need not be equally wide)."""
    heads: int
    k_dim: int
    v_dim: int


class StateRow(NamedTuple):
    """What ONE recurrent layer keeps of ONE sequence, the ONE
    description the store (``decode/paged.py::init_state``), the prefill
    program's slices, the byte counts and the counters take their sizes
    from: the last ``taps - 1`` inputs of a depthwise causal convolution
    over ``conv_lanes`` lanes (the tail), and a state of ``rows`` rows
    of ``lanes`` lanes (``rows`` 0: the layer carries its tail and NO
    state). The two widths are the layer's own and need not agree: a
    Mamba-1 mixer convolves the ``D`` lanes its ``[N, D]`` scan state
    has; a gated delta-rule mixer convolves ``q``, ``k`` and ``v`` side
    by side (``2 H_k d_k + H_v d_v`` lanes) and keeps a matrix a value
    head, ``[d_k, H_v * d_v]``, a head's ``[d_k, d_v]`` block whole
    lane tiles of it. Both float32 (``ops/ssm.py``)."""
    conv_lanes: int
    taps: int
    rows: int
    lanes: int

    @property
    def tail_lanes(self) -> int:
        """Lanes of the stored tail: its taps end to end."""
        return (self.taps - 1) * self.conv_lanes

    @property
    def tail_bytes(self) -> int:
        return 4 * self.tail_lanes

    @property
    def state_bytes(self) -> int:
        return 4 * self.rows * self.lanes

    @property
    def bytes(self) -> int:
        """Bytes a sequence holds in one recurrent layer."""
        return self.tail_bytes + self.state_bytes


class CacheSpec(NamedTuple):
    """What a model keeps per served sequence, as sizes: ``kv_layers``
    layers own paged KV of ``kv_heads`` heads of ``head_dim`` lanes
    (``v_head_dim`` > 0: a value head has that many lanes, a key head
    ``head_dim``; ``row`` is the store's ``KVRow``);
    ``rec_layers`` layers own a recurrent state, each sequence's row of
    it ``state_row`` (``StateRow``: the convolution's lanes and taps,
    the state's rows and lanes; None for a model with no recurrent
    layer). ``latent_rank`` > 0 says the ``kv_layers``
    are ``LATENT`` ones: a token's row is one vector of ``head_dim``
    lanes (``kv_heads`` 1) whose first ``latent_rank`` are also its
    values. Pool and state are built from this. Beside what is kept:
    ``expert_layers`` layers route their rows over ``n_experts`` held
    experts and count them, which sizes the counters a step program
    returns after its picks (0 for a model with no expert layer).
    ``win_layers`` ``WINDOW`` layers own paged KV over the last
    ``window`` positions of a sequence (0 for a model with none), in a
    row of their own where the model states one (``win_row``: its own
    KV heads and widths; None: the full kind's row, ``window_row``
    either way). ``chunk`` > 0 says the
    layers are ``CHUNKED`` ones: each has an index in BOTH stores
    (``kv_layers == win_layers``), a row of the full kind's pool stands
    for one finished chunk of ``chunk`` positions, and the window is
    aligned to multiples of ``window`` (it does not slide)."""
    kv_layers: int
    kv_heads: int
    head_dim: int
    rec_layers: int = 0
    state_row: StateRow | None = None
    latent_rank: int = 0
    expert_layers: int = 0
    n_experts: int = 0
    win_layers: int = 0
    window: int = 0
    chunk: int = 0
    v_head_dim: int = 0
    win_row: KVRow | None = None

    @property
    def row(self) -> KVRow:
        """The full kind's store."""
        return KVRow(self.kv_heads, self.head_dim,
                     self.v_head_dim or self.head_dim)

    @property
    def window_row(self) -> KVRow:
        """The window kind's store."""
        return self.win_row or self.row


class ServedModel(Protocol):
    """The face. ``l`` is a model layer, ``i`` the index a layer has in
    its own kind's weights and cache (``layers`` gives both), ``a`` the
    normed residual stream ``[N, d]``. A family without recurrent
    layers is never asked for the three ``recurrent_`` methods, one
    without ``LATENT`` layers never for the two ``latent_`` ones, one
    without ``WINDOW`` layers never for the two ``window_`` ones, one
    without ``CHUNKED`` layers never for the three ``chunk`` ones, one
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

    # -> q [N, H, dh], k [N, H_kv, dh], v [N, H_kv, dv] (``dv`` the
    # store's ``v_dim``: ``dh`` for most families): local head counts
    # off the weights' shapes, rotary inside when asked
    def attn_qkv(self, i, a, positions, head_dim, use_rope): ...

    # y [N, H*dh] the read's result, a the layer's normed input (what
    # an output gate is computed from; most families ignore it) -> [N, d]
    def attn_out(self, i, y, a): ...

    # a WINDOW layer ``i`` (its own stack, its own head count and
    # rotary): as ``attn_qkv`` / ``attn_out``; the builder writes k, v
    # to the window pool and reads the last ``window`` positions
    def window_qkv(self, i, a, positions): ...

    # [H] float32 or None: head ``h``'s SINK, a learned scalar that
    # joins the denominator of the layer's softmax beside the scores
    # and has no value row (``p_j = exp(s_j - m) / (exp(sink_h - m) +
    # sum_j' exp(s_j' - m))``); None for a family without one
    def window_sink(self, i): ...

    def window_out(self, i, y, a): ...

    # a CHUNKED layer ``i``: as ``attn_qkv`` / ``attn_out``; the builder
    # writes k, v to the ring, reads the row's aligned window there and
    # the summaries of every earlier window from the full kind's pool,
    # and joins the two reads under one softmax
    def chunked_qkv(self, i, a, positions): ...

    # one finished chunk AS STORED, k_blk / v_blk [n, chunk, H_kv*dh]
    # -> (ktilde, vtilde) [n, H_kv, dh] float32: the ONE key and value
    # a head keeps of the chunk (the builder writes them where the
    # chunk's last position is written)
    def chunk_summary(self, i, k_blk, v_blk): ...

    def chunked_out(self, i, y, a): ...

    # a LATENT layer: -> (q [N, H, m], row [N, m]), ``m`` the stored
    # row's lanes. ``row`` is what the cache keeps of each token, ``q``
    # the query FOR the stored rows, rotated and scaled already: the
    # read is ``p = softmax_t(q . row_t)`` and ``o = sum_t p_t
    # row_t[:latent_rank]``, no factor added
    def latent_qrow(self, i, a, positions): ...

    def latent_out(self, i, o): ...  # o [N, H, latent_rank] -> [N, d]

    # one token of b rows, a [b, d]: the state is advanced where it is
    # stored (``decode/paged.py::RecurrentState``: conv [L_r, S, 1,
    # (K-1)*C], ssm [L_r, S, N, D], both WHOLE; ``C, K, N, D`` the
    # model's ``StateRow``), rows [b] naming each row's slot -> (y,
    # conv, ssm). Where the row has no state (``rows`` 0) ``ssm`` is
    # None in and out
    def recurrent_step(self, i, a, conv, ssm, rows): ...

    # a chunk of one row: a [c, d], tail [K-1, C], state [N, D] (None
    # where the row has none) -> (y, tail, state)
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


def mm_held(x: jax.Array, w: jax.Array) -> jax.Array:
    """``mm(x, w)`` with the product held as it is written: its result
    ``[N, out]`` passes an optimization barrier before anything reshapes
    it. Where ``w`` is layer ``i`` of a stack and the result's heads are
    split next (or split and merged again by the cache write), this
    compiler otherwise moves that reshape onto the weight, the operand
    becomes ``bitcast(slice(stack))``, the slice no longer fuses into
    the product, and every layer of the stack is written out to HBM and
    read back (MiMo-V2-Flash's ``W_q``: 2.9 GB of traffic a decode
    program for 1.1 GB of weights, PR 51). Held, the product is
    ``dot(x, slice(stack))`` as the output and MLP products are, and a
    layer's weights cross HBM once. The barrier stands on the small
    result, so it costs no copy."""
    return jax.lax.optimization_barrier(mm(x, w))


def qkv_heads(wq, wk, wv, i: int, a, positions, head_dim: int,
              use_rope: bool, theta: float | None = None, qk_norm=None,
              rotary: Rotary | None = None, v_head_dim: int | None = None):
    """Attention layer ``i`` of stacks ``[L_a, out, d]``: ``a [N, d] ->
    q [N, h_loc, dh], k [N, kv_loc, dh], v [N, kv_loc, dv]`` (``dv`` is
    ``v_head_dim`` where a value head is not as wide as a key head,
    else ``dh``), rotated by ``positions
    [N]`` when asked, at the base ``theta`` where the model states one
    (``rope``'s own otherwise) or as the model's ``rotary`` says (part
    of a head's lanes, YaRN: ``models/attention.py::Rotary``); the
    local head counts come off the (possibly head-sharded) weights'
    shapes. ``qk_norm = (g_q [dh], g_k
    [dh], eps)`` norms every head of ``q`` and of ``k`` over its own
    lanes (gain-only RMSNorm, float32) between the projection and the
    rotation."""
    q = mm_held(a, wq[i]).reshape(-1, wq.shape[1] // head_dim, head_dim)
    k = mm_held(a, wk[i]).reshape(-1, wk.shape[1] // head_dim, head_dim)
    dv = v_head_dim or head_dim
    v = mm_held(a, wv[i]).reshape(-1, wv.shape[1] // dv, dv)
    if qk_norm is not None:
        g_q, g_k, eps = qk_norm
        q, k = rmsnorm(g_q, q, eps), rmsnorm(g_k, k, eps)
    if use_rope:
        at = rotary or (rope if theta is None
                        else functools.partial(rope, base=theta))
        rot = jax.vmap(lambda x, pos: at(x[:, None, :], pos[None])[:, 0, :])
        q = rot(q, positions)
        k = rot(k, positions)
    return q, k, v


def head_gate(wg, i: int, a, y, head_dim: int):
    """The per-head output gate (the head-wise form of arXiv:2505.06708):
    head ``h`` of the read's result ``y [N, H*dh]`` is scaled by
    ``sigmoid(W_g a)_h``, one scalar a head from the layer's normed
    input ``a [N, d]``, ``wg [L_a, H, d]``; float32."""
    g = jax.nn.sigmoid(mm(a, wg[i]).astype(jnp.float32))     # [N, H]
    return (y.reshape(y.shape[0], -1, head_dim) * g[:, :, None]
            ).reshape(y.shape)
