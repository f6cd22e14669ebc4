"""Plain reference for the window-and-full-attention, gated-head,
sparse-expert LM (``model_type: laguna``: Laguna-S-2.1).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: the whole forward pass over one padded sequence with the two
masks, no cache, no batching, no kernels, nothing imported from the
program. It is handed the program's own weight arrays (bfloat16 as
served) and upcasts ONE EXPERT (three matrices, inside a scan over the
held experts), one KV head's group of query heads over one block of
query rows and one block of the vocabulary at a time, never a layer, so
it fits beside the engine.

``x [T, d]`` is the residual stream, RMSNorm gain-only, every matrix
``[out, in]``, no bias:

- layer ``l``: ``h = x + attn_l(rms(x; norm_in[l]))``; ``x = h +
  ffn_l(rms(h; norm_ff[l]))``; ``logits = rms(x; g_f) @ w_head.T``
  (untied). No position is added to the embedding.
- ``attn_l``: ``q = W_q a`` (``H_l`` heads of dh, the layer's own
  count), ``k, v = W_k a, W_v a`` (H_kv heads); rotary by the layer's
  type (``rope_parameters``): the FIRST ``partial_rotary_factor * dh``
  lanes of a head are rotated, lane ``i`` of them paired with ``i +
  D_rot / 2``, the others pass; pair ``i`` turns ``pos * inv_freq_i``
  with ``f_i = theta^(-2i/D_rot)`` and, for ``rope_type: yarn``,
  ``inv_freq_i = f_i / factor * (1 - m_i) + f_i * m_i``, ``m_i = 1 -
  clip((i - lo) / (hi - lo), 0, 1)``, ``lo, hi`` the pairs that make
  ``beta_fast`` and ``beta_slow`` turns over the original positions
  (floor / ceil, clipped to the lanes), ``cos`` and ``sin`` times
  ``attention_factor``. ``s = q k^T / sqrt(dh)`` over ``t <= p``, and
  for ``sliding_attention`` only ``p - t < sliding_window``; ``o_h =
  softmax(s_h) v``, ``H_l / H_kv`` query heads a KV head; ``o_h <-
  sigmoid(W_g a)_h * o_h``; ``W_o``.
- FFN of a ``dense`` layer: ``W_down (silu(W_gate h) * W_up h)``. Of a
  ``sparse`` one: ``s = softmax(W_r h)`` in float32 over ALL
  ``router_experts``; the ``top_k`` largest chosen; ``w_k =
  moe_routed_scaling_factor * s_k / sum_chosen s``; ``FFN(h) = sum over
  the chosen experts HELD here of w_k expert_k(h) + shared(h)`` — the
  held experts are ``[expert_first, expert_first + num_experts)``, and a
  choice that falls on another adds nothing, here as in the program.
  No token is dropped.

Departures from the published block, each listed in the configuration's
``assumed``: the gate's form, the router's score function, the shared
expert ungated, no QK-norm, the window counting the current token, the
rotary's half-split pairing.

The weights are named leaves (``configs/laguna_engine_driver.py``):
``full.*`` / ``window.*`` stacked over the layers of their type with
their gates ``wg_full`` / ``wg_window``, ``dense.*`` over the dense
layers, ``shared.*`` / ``experts.*`` over the sparse ones, the norms
over all.

``mode`` runs the same mathematics in a lower precision — the control
that ``correct`` has to refuse:

- ``"f32"`` (or None): float32, every product at ``highest``.
- ``"bf16"``: weights, activations, router, gates and every
  intermediate in bfloat16.
- ``"int8"``: every matrix product on symmetric int8 operands (weights
  per output row, activations per token), float32 elsewhere.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
ATTN = ("wq", "wk", "wv", "wo")
MLP = ("w_gate", "w_up", "w_down")
VOCAB_BLOCK = 16384
QUERY_BLOCK = 512


def _rms(g, x, eps):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, -1, keepdims=True)
    return (g.astype(jnp.float32) * x32 * jax.lax.rsqrt(ms + eps)).astype(
        x.dtype)


def _q8(a, axis):
    """Symmetric int8 fake-quantisation along ``axis``."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(a / s) * s


def _mm(x, w, mode):
    """``x [T, in] @ w[out, in].T``."""
    if mode == "int8":
        x, w = _q8(x, -1), _q8(w, -1)
    return jnp.matmul(x, w.T, precision=HI)


def inv_freq(rope: dict, dh: int) -> np.ndarray:
    """``[D_rot / 2]``: each rotated pair's turn a position, from one
    ``rope_parameters`` entry."""
    d = int(dh * float(rope.get("partial_rotary_factor", 1.0)))
    i = np.arange(d // 2, dtype=np.float64)
    theta = float(rope["rope_theta"])
    f = theta ** (-2.0 * i / d)
    if rope.get("rope_type", "default") == "default":
        return f.astype(np.float32)
    original = int(rope["original_max_position_embeddings"])

    def pair(turns):
        return d * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    lo = max(math.floor(pair(float(rope["beta_fast"]))), 0)
    hi = min(math.ceil(pair(float(rope["beta_slow"]))), d - 1)
    m = 1.0 - np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (f / float(rope["factor"]) * (1.0 - m) + f * m).astype(
        np.float32)


def _rope(x, freqs, factor):
    """``x [T, heads, dh]``: the first ``2 * len(freqs)`` lanes rotated
    by the row's position ``0..T-1``, the rest passed."""
    half = freqs.shape[0]
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * freqs)[:, None, :]
    cos = (factor * jnp.cos(ang)).astype(x.dtype)
    sin = (factor * jnp.sin(ang)).astype(x.dtype)
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           -1)


@partial(jax.jit, static_argnames=("dh", "window", "factor", "mode"))
def _attn(a, wq, wk, wv, wo, wg, freqs, *, dh, window, factor, mode):
    """``window`` 0: every earlier position."""
    t = a.shape[0]
    q = _rope(_mm(a, wq, mode).reshape(t, -1, dh), freqs, factor)
    k = _rope(_mm(a, wk, mode).reshape(t, -1, dh), freqs, factor)
    v = _mm(a, wv, mode).reshape(t, -1, dh)
    hkv = k.shape[1]
    qb = math.gcd(t, QUERY_BLOCK)
    cols = jnp.arange(t)[None, :]

    def group(qkv):
        """One KV head and its query heads, ``q [g, T, dh]``, a block
        of query rows at a time."""
        qg, kk, vv = qkv

        def rows(start):
            p = start + jnp.arange(qb)[:, None]
            mask = cols <= p
            if window:
                mask = mask & (p - cols < window)
            qs = jax.lax.dynamic_slice_in_dim(qg, start, qb, 1)
            s = jnp.einsum("gqd,td->gqt", qs, kk, precision=HI) / jnp.sqrt(
                jnp.asarray(dh, a.dtype))
            s = jnp.where(mask, s, -jnp.inf)
            pr = jax.nn.softmax(s.astype(jnp.float32), -1).astype(a.dtype)
            return jnp.einsum("gqt,td->gqd", pr, vv, precision=HI)

        y = jax.lax.map(rows, jnp.arange(0, t, qb))       # [nb, g, qb, dh]
        return y.transpose(1, 0, 2, 3).reshape(qg.shape)

    # [H_kv, g, T, dh]: query head h belongs to KV head h // g
    qg = q.reshape(t, hkv, -1, dh).transpose(1, 2, 0, 3)
    y = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    y = y.transpose(2, 0, 1, 3).reshape(t, -1, dh)        # [T, H, dh]
    gate = jax.nn.sigmoid(_mm(a, wg, mode))               # [T, H]
    return _mm((y * gate[:, :, None]).reshape(t, -1), wo, mode)


@partial(jax.jit, static_argnames=("mode",))
def _mlp(a, w_gate, w_up, w_down, *, mode):
    return _mm(jax.nn.silu(_mm(a, w_gate, mode)) * _mm(a, w_up, mode),
               w_down, mode)


@partial(jax.jit, static_argnames=("top_k", "scale", "first", "held"))
def _route(a, w_r, *, top_k, scale, first, held):
    """``[T, held]`` weights: row ``t``'s weight for held expert ``e``,
    0 where it did not choose it. In the type of ``a`` (float32 unless
    the mode is the all-bfloat16 control); ``w_r`` is float32 as
    stored."""
    dt = a.dtype
    s = jax.nn.softmax(jnp.matmul(a, w_r.astype(dt).T, precision=HI), -1)
    chosen, idx = jax.lax.top_k(s, top_k)
    w = scale * chosen / jnp.sum(chosen, -1, keepdims=True)
    hit = idx[:, :, None] == first + jnp.arange(held)
    return jnp.sum(jnp.where(hit, w[:, :, None], 0), 1).astype(dt)


@partial(jax.jit, static_argnames=("eps",))
def _norm(g, x, *, eps):
    return _rms(g, x, eps)


@partial(jax.jit, static_argnames=("mode",))
def _held_sum(a, gates, w_gate, w_up, w_down, x, *, mode):
    """``sum_e gates[:, e] * expert_e(a)`` over the held experts of
    sparse layer ``x`` (``w_* [L_e, E_held, ...]`` as stored), one
    expert's three matrices sliced out and upcast at a time. An expert
    runs over every row and its column of the weights zeroes the rows
    that did not choose it."""
    dt = a.dtype

    def one(y, e):
        wg, wu, wd = (m[x, e].astype(dt) for m in (w_gate, w_up, w_down))
        h = jax.nn.silu(_mm(a, wg, mode)) * _mm(a, wu, mode)
        return y + gates[:, e][:, None] * _mm(h, wd, mode), None

    return jax.lax.scan(one, jnp.zeros_like(a),
                        jnp.arange(w_gate.shape[1]))[0]


def _experts(w: dict, x, a, config: dict, dt, mode: str):
    """``sum over held chosen w_k expert_k(a) + shared(a)`` of sparse
    layer ``x``."""
    gates = _route(a, w["experts.w_router"][x],
                   top_k=int(config["num_experts_per_tok"]),
                   scale=float(config.get("moe_routed_scaling_factor", 1.0)),
                   first=int(config.get("expert_first", 0)),
                   held=w["experts.w_gate"].shape[1])
    return (_mlp(a, *(w["shared." + k][x].astype(dt) for k in MLP),
                 mode=mode)
            + _held_sum(a, gates, *(w["experts." + k] for k in MLP),
                        jnp.int32(x), mode=mode))


def hidden(w: dict, tokens, config: dict, mode: str | None = None):
    """Final residual stream ``[T, d]`` of one sequence."""
    mode = mode or "f32"
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    eps = float(config["rms_norm_eps"])
    dh = int(config["head_dim"])
    window = int(config["sliding_window"])
    ropes = config["rope_parameters"]
    stack = {"full_attention": "full", "sliding_attention": "window"}
    x = w["wte"][jnp.asarray(tokens, jnp.int32)].astype(dt)
    seen = {"full": 0, "window": 0, "dense": 0, "sparse": 0}
    for l, kind in enumerate(config["layer_types"]):
        s = stack[kind]
        i = seen[s]
        seen[s] += 1
        rope = ropes[kind]
        a = _norm(w["norm_in"][l].astype(dt), x, eps=eps)
        x = x + _attn(
            a, *(w[f"{s}.{k}"][i].astype(dt) for k in ATTN),
            w["wg_" + s][i].astype(dt), jnp.asarray(inv_freq(rope, dh)),
            dh=dh, window=window if s == "window" else 0,
            factor=float(rope.get("attention_factor", 1.0)), mode=mode)
        a = _norm(w["norm_ff"][l].astype(dt), x, eps=eps)
        m = config["mlp_layer_types"][l]
        j = seen[m]
        seen[m] += 1
        if m == "dense":
            x = x + _mlp(a, *(w["dense." + k][j].astype(dt) for k in MLP),
                         mode=mode)
        else:
            x = x + _experts(w, j, a, config, dt, mode)
    return x


@partial(jax.jit, static_argnames=("mode",), donate_argnums=(0,))
def _head_block(out, a, w_blk, start, *, mode):
    return jax.lax.dynamic_update_slice(
        out, _mm(a, w_blk, mode).astype(jnp.float32), (0, start))


def logits(w: dict, tokens, config: dict, mode: str | None = None):
    """``[T, V]`` float32 next-token logits of one sequence, the untied
    head one block of the vocabulary at a time."""
    mode = mode or "f32"
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    x = hidden(w, tokens, config, mode)
    a = _norm(w["g_f"].astype(dt), x, eps=float(config["rms_norm_eps"]))
    head = w["w_head"]
    out = jnp.zeros((a.shape[0], head.shape[0]), jnp.float32)
    for start in range(0, head.shape[0], VOCAB_BLOCK):
        out = _head_block(out, a, head[start:start + VOCAB_BLOCK].astype(dt),
                          start, mode=mode)
    return out
