"""Plain reference for the latent-attention, sparse-expert LM
(``model_type: glm4_moe_lite``: GLM-4.7-Flash).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision, in the NAIVE form: every token's keys and values expanded
from its latent through ``W_kvb``, full causal attention over them, no
cache, no batching, no kernels, nothing imported from the program. It is
handed the program's own weight arrays (bfloat16 as served) and upcasts
ONE EXPERT (three matrices) and one block of the vocabulary at a time,
never a layer, so it fits beside the engine.

``h [T, d]`` is the residual stream, RMSNorm gain-only, every matrix
``[out, in]``, no bias:

- layer ``l``: ``h += MLA_l(rms(h; norm_in[l]))``; ``h += FFN_l(rms(h;
  norm_ff[l]))``, the dense gated MLP ``W_down (silu(W_gate a) * W_up
  a)`` for ``l < first_k_dense_replace`` and the expert layer after;
  ``logits = rms(h; g_f) @ W_head.T`` (untied). No position is added to
  the embedding.
- MLA: ``c_q = rms(W_qa a; g_q)``; per head ``[q_nope | q_rope] = W_qb
  c_q``; ``[c_kv | k_rope] = W_kva a``; ``c = rms(c_kv; g_kv)``;
  ``q_rope``, ``k_rope`` rotated by position at ``rope_theta`` over all
  their lanes (lane ``i`` paired with ``i + dr/2``); per head ``k_nope =
  W_uk c``, ``v = W_uv c``; ``k = [k_nope | k_rope]`` (``k_rope`` the
  same for every head); causal ``softmax(q k^T / sqrt(dn + dr)) v``;
  ``W_o`` over the heads' values side by side.
- expert layer: ``s = sigmoid(W_r a)`` in float32; the ``top_k`` of ``s
  + b`` chosen (``b`` for the choice only); ``w_k = routed_scaling_factor
  * s_k / sum_chosen s``; ``FFN(a) = shared(a) + sum_k w_k expert_k(a)``.
  No token is dropped.

The weights are named leaves (``configs/glm_moe_engine_driver.py``):
``mla.*`` and the norms stacked over all layers, ``dense.*`` over the
leading dense ones, ``shared.*`` and ``experts.*`` over the expert
layers. ``W_kvb`` lies split per head, as the program keeps it:
``mla.w_uk [L, H, dn, R]`` and ``mla.w_uv [L, H, dv, R]``.

``mode`` runs the same mathematics in a lower precision — the control
that ``correct`` has to refuse:

- ``"f32"`` (or None): float32, every product at ``highest``.
- ``"bf16"``: weights, activations, router and every intermediate in
  bfloat16.
- ``"int8"``: every matrix product on symmetric int8 operands (weights
  per output row, activations per token), float32 elsewhere.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
MLA = ("w_qa", "g_q", "w_qb", "w_kva", "g_kv", "w_uk", "w_uv", "w_o")
MLP = ("w_gate", "w_up", "w_down")
VOCAB_BLOCK = 16384


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


def _rope(x, theta):
    """``x [T, ..., dr]`` rotated by its row's position ``0..T-1``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    ang = ang.reshape(x.shape[0], *([1] * (x.ndim - 2)), half)
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnames=("eps", "theta", "mode"))
def _mla(a, w_qa, g_q, w_qb, w_kva, g_kv, w_uk, w_uv, w_o, *, eps, theta,
         mode):
    t = a.shape[0]
    h, dn, r = w_uk.shape
    dv = w_uv.shape[1]
    dr = w_kva.shape[0] - r
    q = _mm(_rms(g_q, _mm(a, w_qa, mode), eps), w_qb, mode).reshape(
        t, h, dn + dr)
    ckr = _mm(a, w_kva, mode)
    c = _rms(g_kv, ckr[:, :r], eps)
    k_rope = _rope(ckr[:, r:], theta)                       # [T, dr]
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
    # every token's keys and values, expanded from its latent
    k_nope = _mm(c, w_uk.reshape(h * dn, r), mode).reshape(t, h, dn)
    v = _mm(c, w_uv.reshape(h * dv, r), mode).reshape(t, h, dv)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None, :], (t, h, dr))], -1)
    s = jnp.einsum("qhd,thd->hqt", q, k, precision=HI) / jnp.sqrt(
        jnp.asarray(dn + dr, a.dtype))
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(a.dtype)
    y = jnp.einsum("hqt,thv->qhv", p, v, precision=HI)
    return _mm(y.reshape(t, h * dv), w_o, mode)


@partial(jax.jit, static_argnames=("mode",))
def _mlp(a, w_gate, w_up, w_down, *, mode):
    return _mm(jax.nn.silu(_mm(a, w_gate, mode)) * _mm(a, w_up, mode),
               w_down, mode)


@partial(jax.jit, static_argnames=("top_k", "scale"))
def _route(a, w_r, bias, *, top_k, scale):
    """``[T, E]`` weights: row ``t``'s weight for expert ``e``, 0 where
    it did not choose it. In the type of ``a`` (float32 unless the mode
    is the all-bfloat16 control); ``w_r`` and ``bias`` are float32 as
    stored."""
    dt = a.dtype
    s = jax.nn.sigmoid(jnp.matmul(a, w_r.astype(dt).T, precision=HI))
    _, idx = jax.lax.top_k(s + bias.astype(dt), top_k)
    chosen = jnp.take_along_axis(s, idx, -1)
    w = scale * chosen / jnp.sum(chosen, -1, keepdims=True)
    hit = idx[:, :, None] == jnp.arange(w_r.shape[0])
    return jnp.sum(jnp.where(hit, w[:, :, None], 0), 1).astype(dt)


@partial(jax.jit, static_argnames=("eps",))
def _norm(g, x, *, eps):
    return _rms(g, x, eps)


def _experts(w: dict, x, a, config: dict, dt, mode: str):
    """``shared(a) + sum_k w_k expert_k(a)`` of expert layer ``x``, one
    expert's three matrices upcast at a time. An expert runs over every
    row and its column of the weights zeroes the rows that did not
    choose it: the sum over experts is the sum over each row's chosen."""
    gates = _route(a, w["experts.w_router"][x], w["experts.bias"][x],
                   top_k=int(config["num_experts_per_tok"]),
                   scale=float(config["routed_scaling_factor"]))
    y = _mlp(a, *(w["shared." + k][x].astype(dt) for k in MLP), mode=mode)
    for e in range(w["experts.w_gate"].shape[1]):
        y = y + gates[:, e:e + 1] * _mlp(
            a, *(w["experts." + k][x, e].astype(dt) for k in MLP),
            mode=mode)
    return y


def hidden(w: dict, tokens, config: dict, mode: str | None = None):
    """Final residual stream ``[T, d]`` of one sequence."""
    mode = mode or "f32"
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    eps = float(config["rms_norm_eps"])
    first_dense = int(config["first_k_dense_replace"])
    x = w["wte"][jnp.asarray(tokens, jnp.int32)].astype(dt)
    for l in range(int(config["num_hidden_layers"])):
        a = _norm(w["norm_in"][l].astype(dt), x, eps=eps)
        x = x + _mla(a, *(w["mla." + k][l].astype(dt) for k in MLA),
                     eps=eps, theta=float(config["rope_theta"]), mode=mode)
        a = _norm(w["norm_ff"][l].astype(dt), x, eps=eps)
        if l < first_dense:
            x = x + _mlp(a, *(w["dense." + k][l].astype(dt) for k in MLP),
                         mode=mode)
        else:
            x = x + _experts(w, l - first_dense, a, config, dt, mode)
    return x


@partial(jax.jit, static_argnames=("mode",), donate_argnums=(0,))
def _head_block(out, a, w_blk, start, *, mode):
    return jax.lax.dynamic_update_slice(
        out, _mm(a, w_blk, mode).astype(jnp.float32), (0, start))


def logits(w: dict, tokens, config: dict, mode: str | None = None):
    """``[T, V]`` float32 next-token logits of one sequence, the head one
    block of the vocabulary at a time."""
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
