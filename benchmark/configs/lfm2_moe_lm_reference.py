"""Plain reference for the gated short-convolution, grouped-query
attention, sparse-expert LM (``model_type: lfm2_moe``: LFM2-24B-A2B).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: full causal attention over the whole sequence, the
convolution as ``K`` shifted copies of the whole sequence, no cache, no
batching, no kernels, nothing imported from the program. It is handed
the program's own weight arrays (bfloat16 as served) and upcasts ONE
EXPERT (three matrices), one KV head's group of query heads and one
block of the vocabulary at a time, never a layer, so it fits beside the
engine.

``x [T, d]`` is the residual stream, RMSNorm gain-only, every matrix
``[out, in]``, no bias:

- layer ``l``: ``h = x + op_l(rms(x; norm_in[l]))``; ``x = h +
  ffn_l(rms(h; norm_ff[l]))``; ``op_l`` by ``layer_types[l]``; ``logits
  = rms(x; g_f) @ wte.T`` (tied). No position is added to the embedding.
- ``conv``: ``[B; C; X] = W_in a``; ``u = B * X``; ``v_t = sum_j w[j] *
  u_{t-(K-1)+j}`` (depthwise, causal, ``K = conv_L_cache`` taps, ``u``
  zero before the sequence, no bias, no activation); ``y = W_out (C *
  v)``.
- ``full_attention``: ``q = W_q a`` (H heads of dh), ``k, v = W_k a, W_v
  a`` (H_kv heads); ``q`` and ``k`` each through a gain-only RMSNorm over
  a head's dh lanes (one gain vector a layer each); both rotated by
  position at ``rope_theta`` over all their lanes (lane ``i`` paired
  with ``i + dh/2``); causal ``softmax(q k^T / sqrt(dh)) v`` with ``H /
  H_kv`` query heads a KV head; ``W_o``.
- FFN: ``W_down (silu(W_gate h) * W_up h)`` for ``l < num_dense_layers``;
  after them ``s = sigmoid(W_r h)`` in float32; the ``top_k`` of ``s +
  b`` chosen (``b`` for the choice only); ``w_k = routed_scaling_factor *
  s_k / sum_chosen s``; ``FFN(h) = sum_k w_k expert_k(h)``, no shared
  expert. No token is dropped.

Departures from the published block, each listed in the configuration's
``assumed``: the head tied to the embedding, the rotary's half-split
pairing, QK-norm as one ``[dh]`` gain a layer for ``q`` and one for ``k``
(the config states none of the three); ``conv_w [L_c, K, d]`` lies as the
program keeps it, tap ``K-1`` on the current token (published ``[d, 1,
K]``).

The weights are named leaves (``configs/lfm2_moe_engine_driver.py``):
``conv.*`` and ``attn.*`` stacked over the layers of their kind (``g_q``,
``g_k`` with ``attn.*``'s), ``dense.*`` over the leading dense layers,
``experts.*`` over the others, the norms over all.

``mode`` runs the same mathematics in a lower precision — the control
that ``correct`` has to refuse:

- ``"f32"`` (or None): float32, every product at ``highest``.
- ``"bf16"``: weights, activations, router, convolution and every
  intermediate in bfloat16.
- ``"int8"``: every matrix product on symmetric int8 operands (weights
  per output row, activations per token), float32 elsewhere.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
CONV = ("w_in", "conv_w", "w_out")
ATTN = ("wq", "wk", "wv", "wo")
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
    """``x [T, heads, dh]`` rotated by its row's position ``0..T-1``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * freqs)[:, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnames=("mode",))
def _conv(a, w_in, conv_w, w_out, *, mode):
    t = a.shape[0]
    k = conv_w.shape[0]
    b, c, x = jnp.split(_mm(a, w_in, mode), 3, axis=-1)
    u = b * x
    # u_{t-(K-1)+j}: the sequence shifted down by K-1-j rows, zeros in
    # front
    full = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), u.dtype), u], 0)
    v = sum(conv_w[j] * full[j:j + t] for j in range(k))
    return _mm(c * v, w_out, mode)


@partial(jax.jit, static_argnames=("dh", "eps", "theta", "mode"))
def _attn(a, wq, wk, wv, wo, g_q, g_k, *, dh, eps, theta, mode):
    t = a.shape[0]
    q = _rope(_rms(g_q, _mm(a, wq, mode).reshape(t, -1, dh), eps), theta)
    k = _rope(_rms(g_k, _mm(a, wk, mode).reshape(t, -1, dh), eps), theta)
    v = _mm(a, wv, mode).reshape(t, -1, dh)
    hkv = k.shape[1]
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    def group(qkv):
        """One KV head and its query heads: ``q [g, T, dh]``."""
        qg, kk, vv = qkv
        s = jnp.einsum("gqd,td->gqt", qg, kk, precision=HI) / jnp.sqrt(
            jnp.asarray(dh, a.dtype))
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(a.dtype)
        return jnp.einsum("gqt,td->gqd", p, vv, precision=HI)

    # [H_kv, g, T, dh]: query head h belongs to KV head h // g
    qg = q.reshape(t, hkv, -1, dh).transpose(1, 2, 0, 3)
    y = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return _mm(y.transpose(2, 0, 1, 3).reshape(t, -1), wo, mode)


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
    """``sum_k w_k expert_k(a)`` of expert layer ``x``, one expert's
    three matrices upcast at a time. An expert runs over every row and
    its column of the weights zeroes the rows that did not choose it:
    the sum over experts is the sum over each row's chosen."""
    gates = _route(a, w["experts.w_router"][x], w["experts.bias"][x],
                   top_k=int(config["num_experts_per_tok"]),
                   scale=float(config.get("routed_scaling_factor", 1.0)))
    y = jnp.zeros_like(a)
    for e in range(w["experts.w_gate"].shape[1]):
        y = y + gates[:, e:e + 1] * _mlp(
            a, *(w["experts." + k][x, e].astype(dt) for k in MLP),
            mode=mode)
    return y


def hidden(w: dict, tokens, config: dict, mode: str | None = None):
    """Final residual stream ``[T, d]`` of one sequence."""
    mode = mode or "f32"
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    eps = float(config["norm_eps"])
    first_dense = int(config["num_dense_layers"])
    heads = int(config["num_attention_heads"])
    dh = int(config.get("head_dim") or int(config["hidden_size"]) // heads)
    theta = float(config["rope_parameters"]["rope_theta"])
    x = w["wte"][jnp.asarray(tokens, jnp.int32)].astype(dt)
    seen = {"conv": 0, "full_attention": 0}
    for l, kind in enumerate(config["layer_types"]):
        i = seen[kind]
        seen[kind] += 1
        a = _norm(w["norm_in"][l].astype(dt), x, eps=eps)
        if kind == "conv":
            x = x + _conv(a, *(w["conv." + k][i].astype(dt) for k in CONV),
                          mode=mode)
        else:
            x = x + _attn(a, *(w["attn." + k][i].astype(dt) for k in ATTN),
                          w["g_q"][i].astype(dt), w["g_k"][i].astype(dt),
                          dh=dh, eps=eps, theta=theta, mode=mode)
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
    """``[T, V]`` float32 next-token logits of one sequence, the tied
    head one block of the vocabulary at a time."""
    mode = mode or "f32"
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    x = hidden(w, tokens, config, mode)
    a = _norm(w["g_f"].astype(dt), x, eps=float(config["norm_eps"]))
    head = w["wte"]
    out = jnp.zeros((a.shape[0], head.shape[0]), jnp.float32)
    for start in range(0, head.shape[0], VOCAB_BLOCK):
        out = _head_block(out, a, head[start:start + VOCAB_BLOCK].astype(dt),
                          start, mode=mode)
    return out
