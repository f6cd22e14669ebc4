"""Plain reference for the Jamba-family hybrid LM (``model_type: jamba``).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: no cache, no batching, no kernels, a ``lax.scan`` over time
for the recurrence, nothing imported from the program. It is handed the
program's own weight arrays (bfloat16 as served) and upcasts one layer at
a time, so it fits beside the engine.

Layer ``i`` of ``num_hidden_layers`` is attention where ``i %
attn_layer_period == attn_layer_offset`` and a Mamba-1 mixer otherwise;
``h`` is the residual stream ``[T, d]``:

- ``h += mixer_i(rms(h; norm_in[i]))``, then ``a = rms(h; norm_ff[i])``,
  ``h += W_down (silu(W_gate a) * W_up a)``; after the last layer
  ``logits = rms(h; ln_f) @ wte.T``. No position is added anywhere.
- attention: ``q = W_q a`` (H heads), ``k, v = W_k a, W_v a`` (H_kv
  heads), causal ``softmax(q k^T / sqrt(dh)) v``, ``W_o``. No rotary.
- Mamba-1: ``[x; z] = W_in a``; ``x = silu(conv(x))`` (depthwise,
  causal, kernel K, bias); ``[delta; B; C] = W_x x``, each through its
  own gain-only RMSNorm; ``dt = softplus(W_dt delta + b_dt)``; ``A =
  -exp(A_log)``; ``s_t = exp(dt_t A) * s_{t-1} + (dt_t x_t) B_t^T``;
  ``y_t = s_t C_t + D x_t``; out ``= W_out (y * silu(z))``.

The weights are named leaves, every matrix ``[out, in]``, stacked over
the layers of their kind (``mamba.*`` and ``attn.*`` over theirs,
``mlp.*`` and the norms over all). Two leaves lie as the program keeps
them and not as published: ``mamba.a_log [L_m, N, D]`` (published ``[D,
N]``) and ``mamba.conv_w [L_m, K, D]`` with tap ``K-1`` on the current
token (published ``[D, 1, K]``).

``mode`` runs the same mathematics in a lower precision — the control
that ``correct`` has to refuse:

- ``"f32"`` (or None): float32, every product at ``highest``.
- ``"bf16"``: weights, activations, state and every intermediate in
  bfloat16.
- ``"int8"``: every matrix product on symmetric int8 operands (weights
  per output row, activations per token), float32 elsewhere.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
MAMBA = ("w_in", "conv_w", "conv_b", "w_x", "g_dt", "g_b", "g_c", "w_dt",
         "b_dt", "a_log", "d", "w_out")
ATTN = ("wq", "wk", "wv", "wo")
MLP = ("w_gate", "w_up", "w_down")


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


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "mode"))
def _attention(a, wq, wk, wv, wo, *, n_heads, n_kv, mode):
    t = a.shape[0]
    dh = wq.shape[0] // n_heads
    q = _mm(a, wq, mode).reshape(t, n_kv, n_heads // n_kv, dh)
    k = _mm(a, wk, mode).reshape(t, n_kv, dh)
    v = _mm(a, wv, mode).reshape(t, n_kv, dh)
    s = jnp.einsum("qkgd,tkd->kgqt", q, k, precision=HI) / jnp.sqrt(
        jnp.asarray(dh, a.dtype))
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(a.dtype)
    y = jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI)
    return _mm(y.reshape(t, n_heads * dh), wo, mode)


@partial(jax.jit, static_argnames=("eps", "mode"))
def _mamba(a, w_in, conv_w, conv_b, w_x, g_dt, g_b, g_c, w_dt, b_dt,
           a_log, d, w_out, *, eps, mode):
    t = a.shape[0]
    kk, dd = conv_w.shape
    r, n = g_dt.shape[0], g_b.shape[0]
    xz = _mm(a, w_in, mode)
    x, z = xz[:, :dd], xz[:, dd:]
    # depthwise causal convolution: K-1 zeros in front, tap j on the
    # token K-1-j places back
    xp = jnp.concatenate([jnp.zeros((kk - 1, dd), x.dtype), x], 0)
    x = conv_b + sum(conv_w[j] * xp[j:j + t] for j in range(kk))
    x = jax.nn.silu(x)
    dbc = _mm(x, w_x, mode)
    delta = _rms(g_dt, dbc[:, :r], eps)
    b = _rms(g_b, dbc[:, r:r + n], eps)
    c = _rms(g_c, dbc[:, r + n:], eps)
    dt = jax.nn.softplus(_mm(delta, w_dt, mode) + b_dt)
    neg_a = -jnp.exp(a_log)                             # [N, D]

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t[None, :] * neg_a) * s + (
            dt_t * x_t)[None, :] * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], 0) + d * x_t

    # unroll: the same steps in the same order, eight to a loop trip (a
    # trip of the chip's loop costs more than a step's arithmetic)
    _, y = jax.lax.scan(step, jnp.zeros((n, dd), x.dtype), (x, dt, b, c),
                        unroll=8)
    return _mm(y * jax.nn.silu(z), w_out, mode)


@partial(jax.jit, static_argnames=("eps", "mode"))
def _mlp(x, g, w_gate, w_up, w_down, *, eps, mode):
    a = _rms(g, x, eps)
    return x + _mm(jax.nn.silu(_mm(a, w_gate, mode)) * _mm(a, w_up, mode),
                   w_down, mode)


@partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x, ln_f, wte, *, eps, mode):
    return _mm(_rms(ln_f, x, eps), wte, mode).astype(jnp.float32)


def hidden(w: dict, tokens, config: dict, mode: str | None = None):
    """Final residual stream ``[T, d]`` of one sequence, layer by layer
    (one layer's weights upcast at a time)."""
    mode = mode or "f32"
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    eps = float(config["rms_norm_eps"])
    x = w["wte"][jnp.asarray(tokens, jnp.int32)].astype(dt)
    i_attn = i_mamba = 0
    for i in range(int(config["num_hidden_layers"])):
        a = _rms(w["norm_in"][i].astype(dt), x, eps)
        if i % int(config["attn_layer_period"]) == int(
                config["attn_layer_offset"]):
            x = x + _attention(
                a, *(w["attn." + k][i_attn].astype(dt) for k in ATTN),
                n_heads=int(config["num_attention_heads"]),
                n_kv=int(config["num_key_value_heads"]), mode=mode)
            i_attn += 1
        else:
            x = x + _mamba(
                a, *(w["mamba." + k][i_mamba].astype(dt) for k in MAMBA),
                eps=eps, mode=mode)
            i_mamba += 1
        x = _mlp(x, w["norm_ff"][i].astype(dt),
                 *(w["mlp." + k][i].astype(dt) for k in MLP),
                 eps=eps, mode=mode)
    return x


def logits(w: dict, tokens, config: dict, mode: str | None = None):
    """``[T, V]`` float32 next-token logits of one sequence."""
    mode = mode or "f32"
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    x = hidden(w, tokens, config, mode)
    return _head(x, w["ln_f"].astype(dt), w["wte"].astype(dt),
                 eps=float(config["rms_norm_eps"]), mode=mode)
