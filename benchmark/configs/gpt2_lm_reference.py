"""Plain reference for the GPT-2-shaped LM this repo serves and trains.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: no cache, no batching, no kernels, nothing imported from the
program. Departures from published GPT-2, all of them what
``models/lm.py`` is (listed under ``assumed`` in the configuration
files): ReLU for ``gelu_new``, no biases, gain-only LayerNorm.

``logits(w, tokens, config)`` is the forward pass of one sequence;
``mode`` runs the same mathematics in a lower precision — the control
that ``correct`` has to refuse:

- ``"f32"``: float32, every product at ``highest``.
- ``"bf16"``: weights, activations and every intermediate in bfloat16.
- ``"int8"``: every matrix product on symmetric int8 operands (weights
  per output row, activations per token), float32 elsewhere.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

EPS = 1e-5
HI = jax.lax.Precision.HIGHEST


def _ln(g, x):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    xc = x32 - mu
    var = jnp.mean(xc * xc, -1, keepdims=True)
    return (g.astype(jnp.float32) * xc * jax.lax.rsqrt(var + EPS)).astype(
        x.dtype)


def _q8(a, axis):
    """Symmetric int8 fake-quantisation along ``axis`` (values stay
    float32 but take only the 255 int8 levels times a scale)."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(a / s) * s


def _mm(x, w, mode):
    """``x [T, in] @ w[out, in].T``."""
    if mode == "int8":
        x, w = _q8(x, -1), _q8(w, -1)
    return jnp.matmul(x, w.T, precision=HI)


@partial(jax.jit, static_argnames=("n_heads", "mode"))
def _layer(x, ln1, wq, wk, wv, wo, ln2, w1, w2, *, n_heads, mode):
    t, d = x.shape
    dh = d // n_heads
    a = _ln(ln1, x)
    q = _mm(a, wq, mode).reshape(t, n_heads, dh).transpose(1, 0, 2)
    k = _mm(a, wk, mode).reshape(t, n_heads, dh).transpose(1, 0, 2)
    v = _mm(a, wv, mode).reshape(t, n_heads, dh).transpose(1, 0, 2)
    s = jnp.einsum("hqd,hkd->hqk", q, k, precision=HI) / jnp.sqrt(
        jnp.asarray(dh, x.dtype))
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
    y = jnp.einsum("hqk,hkd->hqd", p, v, precision=HI)
    y = y.transpose(1, 0, 2).reshape(t, d)
    x = x + _mm(y, wo, mode)
    h = _ln(ln2, x)
    return x + _mm(jnp.maximum(_mm(h, w1, mode), 0), w2, mode)


@partial(jax.jit, static_argnames=("mode",))
def _head(x, ln_f, wte, *, mode):
    return _mm(_ln(ln_f, x), wte, mode).astype(jnp.float32)


@jax.jit
def _embed(wte, wpe, tokens):
    return wte[tokens] + wpe[:tokens.shape[0]]


def hidden(w: dict, tokens, config: dict, mode: str = "f32"):
    """Final residual stream ``[T, d]`` of one sequence, layer by layer
    (one layer's weights sliced at a time, so it fits beside the
    program's state)."""
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    x = _embed(w["wte"], w["wpe"], jnp.asarray(tokens, jnp.int32)).astype(dt)
    for l in range(w["w1"].shape[0]):
        x = _layer(x, *(w[k][l].astype(dt) for k in
                        ("ln1", "wq", "wk", "wv", "wo", "ln2", "w1", "w2")),
                   n_heads=config["n_head"], mode=mode)
    return x


def logits(w: dict, tokens, config: dict, mode: str = "f32"):
    """``[T, V]`` float32 next-token logits of one sequence."""
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    x = hidden(w, tokens, config, mode)
    return _head(x, w["ln_f"].astype(dt), w["wte"].astype(dt), mode=mode)
