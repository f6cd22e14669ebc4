"""Plain reference for the gated delta-rule and gated full-attention,
fine-grained sparse-expert LM (``model_type: qwen3_next``:
Qwen3-Next-80B-A3B).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: the whole forward pass over one padded sequence, no cache, no
batching, no kernels, nothing imported from the program. The recurrence
is a per-token ``lax.scan`` over the whole sequence from a zero state,
the convolution a padded causal sum, the experts a scan over the held
ones. It is handed the program's own weight arrays (bfloat16 as served)
and upcasts ONE EXPERT (three matrices), one KV head's group of query
heads over one block of 512 query rows and one block of the vocabulary
at a time, never a layer's experts, and WAITS for each mixer and each FFN
before it upcasts the next one's weights (``mimo_v2_flash_lm_reference``
says why), so it fits beside the engine.

``x [T, d]`` is the residual stream; every matrix ``[out, in]``, no
bias; every norm of the trunk, the final one and the two QK-norms are
RMSNorm with a unit offset, ``(1 + g) x / rms(x)`` at ``rms_norm_eps``:

- layer ``l``: ``h = x + mix_l(rms(x; 1 + norm_in[l]))``; ``x = h +
  ffn_l(rms(h; 1 + norm_ff[l]))``; ``logits = rms(x; 1 + g_f) @
  w_head.T`` (untied). Layer ``l`` is full attention where ``(l + 1) %
  full_attention_interval == 0``, a gated delta-rule mixer otherwise.
- the gated delta mixer: ``[q; k; v] = W_qkv a`` (``q, k`` of ``H_k =
  linear_num_key_heads`` heads of ``d_k = linear_key_head_dim`` lanes,
  ``v`` of ``H_v = linear_num_value_heads`` heads of ``d_v =
  linear_value_head_dim``), ``z = W_z a``, ``[b; alpha] = W_ba a``.
  ``[q; k; v]`` pass a depthwise causal convolution of
  ``linear_conv_kernel_dim`` taps (tap ``K - 1`` on the current token,
  zeros before the sequence), no bias, then SiLU. Each ``q`` and ``k``
  head: ``x / sqrt(sum x^2 + 1e-6)``; ``q`` times ``d_k^-0.5``; value
  head ``j`` reads key head ``j // (H_v / H_k)``. Per value head, with
  ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(alpha + dt_bias)``,
  ``S [d_k, d_v]`` from 0: ``S <- exp(g) S``; ``u = beta (v - S^T k)``;
  ``S <- S + k u^T``; ``o = S^T q``. ``y = g_n o / rms(o) * silu(z)``
  a head (``g_n`` with no offset), then ``W_out``.
- full attention: ``q = W_q a``, gate ``W_g a`` (``H`` heads of ``dh``
  lanes each), ``k, v`` of ``H_kv`` heads; ``q``, ``k`` normed a head
  (``1 + g_q``, ``1 + g_k``); rotary on the FIRST ``R = int(dh *
  partial_rotary_factor)`` lanes of every ``q`` and ``k`` head, lane ``i
  < R / 2`` paired with ``i + R / 2``, pair ``i`` turned by ``pos *
  rope_theta^(-2i/R)``; ``s_j = q . k_j / sqrt(dh)`` over ``j <= p``,
  softmax; ``y = sigmoid(gate) * sum_j p_j v_j`` lane by lane; ``W_o``.
- the expert layer: ``s = softmax(W_r h)`` in float32 over ALL
  ``router_experts``; the ``num_experts_per_tok`` largest chosen; ``w_k =
  s_k / sum_chosen s``; ``sum over the chosen experts HELD here of w_k
  expert_k(h)`` — the held experts are ``[expert_first, expert_first +
  num_experts)``, a choice on another adds nothing, here as in the
  program — plus ``sigmoid(w_sg . h) shared(h)``, each a gated SiLU MLP.

The weights are named leaves (``configs/qwen3_next_engine_driver.py``):
``delta.*`` / ``full.*`` stacked over the layers of their kind (with the
full layers' ``w_gate``, ``g_q``, ``g_k``), ``experts.*`` / ``shared.*``
/ ``w_sg`` and the norms over all layers.

``mode`` runs the same mathematics in a lower precision — the control
that ``correct`` has to refuse:

- ``"f32"`` (or None): float32, every product at ``highest``.
- ``"bf16"``: weights, activations, state and every intermediate in
  bfloat16.
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
DELTA = ("w_qkv", "w_z", "w_ba", "conv_w", "a_log", "dt_bias", "g_norm",
         "w_out")
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


def inv_freq(theta: float, dh: int, partial_factor: float) -> np.ndarray:
    """``[R / 2]``: each rotated pair's turn a position."""
    r = int(dh * partial_factor)
    i = np.arange(r // 2, dtype=np.float64)
    return (float(theta) ** (-2.0 * i / r)).astype(np.float32)


def _rope(x, freqs):
    """``x [T, heads, dh]``: the first ``2 * len(freqs)`` lanes rotated
    by the row's position ``0..T-1``, the rest passed."""
    half = freqs.shape[0]
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * freqs)[:, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           -1)


@partial(jax.jit, static_argnames=("dh", "eps", "mode"))
def _attn(a, wq, wk, wv, wo, w_gate, g_q, g_k, freqs, *, dh, eps, mode):
    t = a.shape[0]
    one = jnp.asarray(1.0, jnp.float32)
    q = _rope(_rms(one + g_q, _mm(a, wq, mode).reshape(t, -1, dh), eps),
              freqs)
    k = _rope(_rms(one + g_k, _mm(a, wk, mode).reshape(t, -1, dh), eps),
              freqs)
    v = _mm(a, wv, mode).reshape(t, -1, dh)
    hkv = k.shape[1]
    qb = math.gcd(t, QUERY_BLOCK)
    cols = jnp.arange(t)[None, :]

    def group(qkv):
        """One KV head and its query heads, ``q [g, T, dh]``, a block
        of query rows at a time."""
        qg, kk, vv = qkv

        def rows(start):
            mask = cols <= start + jnp.arange(qb)[:, None]
            qs = jax.lax.dynamic_slice_in_dim(qg, start, qb, 1)
            s = jnp.einsum("gqd,td->gqt", qs, kk, precision=HI) / jnp.sqrt(
                jnp.asarray(dh, a.dtype))
            pr = jax.nn.softmax(
                jnp.where(mask, s, -jnp.inf).astype(jnp.float32), -1)
            return jnp.einsum("gqt,td->gqd", pr.astype(a.dtype), vv,
                              precision=HI)

        y = jax.lax.map(rows, jnp.arange(0, t, qb))       # [nb, g, qb, dh]
        return y.transpose(1, 0, 2, 3).reshape(qg.shape[0], t, dh)

    # [H_kv, g, T, dh]: query head h belongs to KV head h // g
    qg = q.reshape(t, hkv, -1, dh).transpose(1, 2, 0, 3)
    y = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    y = y.transpose(2, 0, 1, 3).reshape(t, -1)            # [T, H * dh]
    return _mm(y * jax.nn.sigmoid(_mm(a, w_gate, mode)), wo, mode)


@partial(jax.jit, static_argnames=("h_k", "d_k", "d_v", "eps", "mode"))
def _delta(a, w_qkv, w_z, w_ba, conv_w, a_log, dt_bias, g_norm, w_out, *,
           h_k, d_k, d_v, eps, mode):
    dt = a.dtype
    t = a.shape[0]
    taps = conv_w.shape[0]
    x = _mm(a, w_qkv, mode)                               # [T, C]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), dt), x])
    x = jax.nn.silu(sum(conv_w[j] * padded[j:j + t] for j in range(taps)))

    def unit(h):
        return h * jax.lax.rsqrt(jnp.sum(h * h, -1, keepdims=True)
                                 + jnp.asarray(1e-6, dt))

    q = unit(x[:, :h_k * d_k].reshape(t, h_k, d_k)) * jnp.asarray(
        d_k ** -0.5, dt)
    k = unit(x[:, h_k * d_k:2 * h_k * d_k].reshape(t, h_k, d_k))
    v = x[:, 2 * h_k * d_k:].reshape(t, -1, d_v)
    h_v = v.shape[1]
    q, k = (jnp.repeat(h, h_v // h_k, axis=1) for h in (q, k))
    b, alpha = jnp.split(_mm(a, w_ba, mode), 2, axis=-1)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(a_log) * jax.nn.softplus(alpha + dt_bias)

    def token(s, inp):
        """``s [H_v, d_k, d_v]``, one token of every head."""
        q_t, k_t, v_t, g_t, beta_t = inp
        s = jnp.exp(g_t)[:, None, None] * s
        u = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t,
                                                 precision=HI))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=HI)

    _, o = jax.lax.scan(token, jnp.zeros((h_v, d_k, d_v), dt),
                        (q, k, v, g, beta))               # [T, H_v, d_v]
    z = _mm(a, w_z, mode).reshape(t, h_v, d_v)
    y = _rms(g_norm, o, eps) * jax.nn.silu(z)
    return _mm(y.reshape(t, -1), w_out, mode)


@partial(jax.jit, static_argnames=("top_k", "first", "held"))
def _route(a, w_r, *, top_k, first, held):
    """``[T, held]`` weights: row ``t``'s weight for held expert ``e``,
    0 where it did not choose it. In the type of ``a`` (float32 unless
    the mode is the all-bfloat16 control); ``w_r`` is float32 as
    stored."""
    dt = a.dtype
    sc = jax.nn.softmax(jnp.matmul(a, w_r.astype(dt).T, precision=HI), -1)
    chosen, idx = jax.lax.top_k(sc, top_k)
    w = chosen / jnp.sum(chosen, -1, keepdims=True)
    hit = idx[:, :, None] == first + jnp.arange(held)
    return jnp.sum(jnp.where(hit, w[:, :, None], 0), 1).astype(dt)


@partial(jax.jit, static_argnames=("eps",))
def _norm(g, x, *, eps):
    return _rms(jnp.asarray(1.0, jnp.float32) + g.astype(jnp.float32), x,
                eps)


@partial(jax.jit, static_argnames=("mode",))
def _held_sum(a, gates, w_gate, w_up, w_down, x, *, mode):
    """``sum_e gates[:, e] * expert_e(a)`` over the held experts of
    layer ``x`` (``w_* [L, E_held, ...]`` as stored), one expert's
    three matrices sliced out and upcast at a time."""
    dt = a.dtype

    def one(y, e):
        wg, wu, wd = (m[x, e].astype(dt) for m in (w_gate, w_up, w_down))
        h = jax.nn.silu(_mm(a, wg, mode)) * _mm(a, wu, mode)
        return y + gates[:, e][:, None] * _mm(h, wd, mode), None

    return jax.lax.scan(one, jnp.zeros_like(a),
                        jnp.arange(w_gate.shape[1]))[0]


@partial(jax.jit, static_argnames=("mode",))
def _shared(a, w_gate, w_up, w_down, w_sg, *, mode):
    y = _mm(jax.nn.silu(_mm(a, w_gate, mode)) * _mm(a, w_up, mode), w_down,
            mode)
    return jax.nn.sigmoid(_mm(a, w_sg[None, :], mode)) * y


def _ffn(w: dict, l, a, config: dict, dt, mode: str):
    gates = _route(a, w["experts.w_router"][l],
                   top_k=int(config["num_experts_per_tok"]),
                   first=int(config.get("expert_first", 0)),
                   held=w["experts.w_gate"].shape[1])
    routed = _held_sum(a, gates, *(w["experts." + k] for k in MLP),
                       jnp.int32(l), mode=mode)
    return routed + _shared(a, *(w["shared." + k][l].astype(dt) for k in MLP),
                            w["w_sg"][l].astype(dt), mode=mode)


def hidden(w: dict, tokens, config: dict, mode: str | None = None):
    """Final residual stream ``[T, d]`` of one sequence."""
    mode = mode or "f32"
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    eps = float(config["rms_norm_eps"])
    dh = int(config["head_dim"])
    period = int(config["full_attention_interval"])
    freqs = jnp.asarray(inv_freq(
        config["rope_theta"], dh,
        float(config.get("partial_rotary_factor", 1.0))))
    x = w["wte"][jnp.asarray(tokens, jnp.int32)].astype(dt)
    seen = {"full": 0, "delta": 0}
    for l in range(int(config["num_hidden_layers"])):
        s = "full" if (l + 1) % period == 0 else "delta"
        i = seen[s]
        seen[s] += 1
        a = _norm(w["norm_in"][l], x, eps=eps)
        if s == "full":
            y = _attn(a, *(w["full." + k][i].astype(dt) for k in ATTN),
                      *(w[k][i].astype(dt) for k in ("w_gate", "g_q", "g_k")),
                      freqs, dh=dh, eps=eps, mode=mode)
        else:
            y = _delta(a, *(w["delta." + k][i].astype(dt) for k in DELTA),
                       h_k=int(config["linear_num_key_heads"]),
                       d_k=int(config["linear_key_head_dim"]),
                       d_v=int(config["linear_value_head_dim"]), eps=eps,
                       mode=mode)
        x = jax.block_until_ready(x + y)
        a = _norm(w["norm_ff"][l], x, eps=eps)
        # the module docstring says why each layer is waited for
        x = jax.block_until_ready(x + _ffn(w, l, a, config, dt, mode))
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
    a = _norm(w["g_f"], x, eps=float(config["rms_norm_eps"]))
    head = w["w_head"]
    out = jnp.zeros((a.shape[0], head.shape[0]), jnp.float32)
    for start in range(0, head.shape[0], VOCAB_BLOCK):
        out = jax.block_until_ready(_head_block(
            out, a, head[start:start + VOCAB_BLOCK].astype(dt), start,
            mode=mode))
    return out
