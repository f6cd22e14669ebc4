"""Plain reference for the chunk-summarised (EVA) attention byte LM
(``model_type: evabyte``: EvaByte).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: the whole forward pass over one padded sequence, no cache,
no batching, no kernels, nothing imported from the program. It is
handed the program's own weight arrays (bfloat16 as served) and upcasts
one layer's matrices at a time; queries (and the MLP's rows) are taken
in blocks, one head at a time, so that 9,216 positions fit beside the
engine.

``x [T, d]`` is the residual stream, RMSNorm gain-only with a unit
offset where the configuration says so (``norm_add_unit_offset``: the
gain is ``1 + g``), every matrix ``[out, in]``, no bias, ``W`` =
``window_size``, ``C`` = ``chunk_size``, ``u(t) = t // W``:

- layer ``l``: ``h = x + attn_l(rms(x; 1 + norm_in[l]))``; ``x = h +
  W_down (silu(W_gate b) * W_up b)``, ``b = rms(h; 1 + norm_ff[l])``;
  ``logits = rms(x; 1 + g_f) @ w_head.T -> [num_pred_heads, V]``
  (untied; head ``i`` predicts byte ``t + 1 + i``). No position is
  added to the embedding.
- ``attn_l``: ``q, k, v = W_q a, W_k a, W_v a`` as ``H`` heads of ``dh``
  (no grouping); ``q_t``, ``k_t`` rotated at position ``t``: lane ``i``
  paired with ``i + dh / 2``, pair ``i`` turning ``t * theta^(-2i /
  dh)``. CHUNK ``j`` is positions ``C j .. C j + C - 1``; with the
  layer's ``phi_h, mu_h in R^dh``: ``alpha_{j,m} = softmax_{m in chunk
  j}(k_m . phi_h / sqrt(dh))``, ``ktilde_j = sum_m alpha_{j,m} k_m +
  mu_h``, ``vtilde_j = sum_m alpha_{j,m} v_m``, from the float32
  rotated keys. The query at ``t`` sees the keys ``S_t = {m : u(m) =
  u(t), m <= t}`` and the summaries ``R_t = {j : j < (W / C) u(t)}``
  under ONE softmax over the two sets' scores ``q . k_m / sqrt(dh)``,
  ``q . ktilde_j / sqrt(dh)``; ``W_o``.

Departures from the published block, each listed in the configuration's
``assumed``: ``alpha``'s logits (no ``-|k|^2 / 2`` term), the summaries
from the rotated keys, ``mu`` on the summarised key only, one ``phi`` and
``mu`` a layer, the eight heads as one linear map, the rotary pairing.

The weights are named leaves (``configs/evabyte_engine_driver.py``):
``attn.*`` and ``mlp.*`` stacked over the layers, ``phi`` / ``mu [L, H,
dh]``, the norms' stored gains.

``mode`` runs the same mathematics in a lower precision — the control
that ``correct`` has to refuse:

- ``"f32"`` (or None): float32, every product at ``highest``.
- ``"bf16"``: weights, activations and every intermediate in bfloat16.
- ``"int8"``: every matrix product on symmetric int8 operands (weights
  per output row, activations per token), float32 elsewhere.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
ATTN = ("wq", "wk", "wv", "wo")
MLP = ("w_gate", "w_up", "w_down")
ROW_BLOCK = 512


def _rms(g, x, eps, offset):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, -1, keepdims=True)
    g32 = g.astype(jnp.float32) + (1.0 if offset else 0.0)
    return (g32 * x32 * jax.lax.rsqrt(ms + eps)).astype(x.dtype)


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
    """``x [T, H, dh]`` rotated by the row's position ``0..T-1``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * freqs)[:, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnames=("dh", "window", "chunk", "theta",
                                   "mode"))
def _attn(a, wq, wk, wv, wo, phi, mu, *, dh, window, chunk, theta, mode):
    t = a.shape[0]
    dt = a.dtype
    scale = 1.0 / math.sqrt(dh)
    q = _rope(_mm(a, wq, mode).reshape(t, -1, dh), theta)
    k = _rope(_mm(a, wk, mode).reshape(t, -1, dh), theta)
    v = _mm(a, wv, mode).reshape(t, -1, dh)
    # one (ktilde, vtilde) a finished chunk a head
    n, h = t // chunk, k.shape[1]
    kc = k[:n * chunk].reshape(n, chunk, h, dh)
    vc = v[:n * chunk].reshape(n, chunk, h, dh)
    alpha = jax.nn.softmax(
        (jnp.einsum("nchd,hd->nch", kc, phi, precision=HI)
         * scale).astype(jnp.float32), axis=1).astype(dt)
    kt = jnp.einsum("nch,nchd->nhd", alpha, kc, precision=HI) + mu
    vt = jnp.einsum("nch,nchd->nhd", alpha, vc, precision=HI)
    qb = math.gcd(t, ROW_BLOCK)
    cols, chunks = jnp.arange(t)[None, :], jnp.arange(n)[None, :]

    def head(qkv):
        """One head, ``q / k / v [T, dh]``, ``kt / vt [n, dh]``, a block
        of query rows at a time."""
        qh, kh, vh, kth, vth = qkv

        def rows(start):
            p = start + jnp.arange(qb)[:, None]
            exact = (cols <= p) & (cols // window == p // window)
            summed = chunks < (window // chunk) * (p // window)
            qs = jax.lax.dynamic_slice_in_dim(qh, start, qb, 0)
            s = jnp.concatenate(
                [jnp.where(exact, jnp.matmul(qs, kh.T, precision=HI), -jnp.inf),
                 jnp.where(summed, jnp.matmul(qs, kth.T, precision=HI),
                           -jnp.inf)], axis=1) * scale
            pr = jax.nn.softmax(s.astype(jnp.float32), -1).astype(dt)
            return jnp.matmul(pr, jnp.concatenate([vh, vth], 0),
                              precision=HI)

        return jax.lax.map(rows, jnp.arange(0, t, qb)).reshape(t, dh)

    y = jax.lax.map(head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                           v.transpose(1, 0, 2), kt.transpose(1, 0, 2),
                           vt.transpose(1, 0, 2)))          # [H, T, dh]
    return _mm(y.transpose(1, 0, 2).reshape(t, -1), wo, mode)


@partial(jax.jit, static_argnames=("mode",))
def _mlp(a, w_gate, w_up, w_down, *, mode):
    qb = math.gcd(a.shape[0], ROW_BLOCK)

    def rows(x):
        return _mm(jax.nn.silu(_mm(x, w_gate, mode)) * _mm(x, w_up, mode),
                   w_down, mode)

    return jax.lax.map(rows, a.reshape(-1, qb, a.shape[1])).reshape(a.shape)


@partial(jax.jit, static_argnames=("eps", "offset"))
def _norm(g, x, *, eps, offset):
    return _rms(g, x, eps, offset)


def hidden(w: dict, tokens, config: dict, mode: str | None = None):
    """Final residual stream ``[T, d]`` of one sequence."""
    mode = mode or "f32"
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    norm = partial(_norm, eps=float(config["rms_norm_eps"]),
                   offset=bool(config.get("norm_add_unit_offset", False)))
    dh = int(config["hidden_size"]) // int(config["num_attention_heads"])
    x = w["wte"][jnp.asarray(tokens, jnp.int32)].astype(dt)
    for l in range(int(config["num_hidden_layers"])):
        a = norm(w["norm_in"][l].astype(dt), x)
        x = x + _attn(
            a, *(w["attn." + k][l].astype(dt) for k in ATTN),
            w["phi"][l].astype(dt), w["mu"][l].astype(dt), dh=dh,
            window=int(config["window_size"]),
            chunk=int(config["chunk_size"]),
            theta=float(config["rope_theta"]), mode=mode)
        a = norm(w["norm_ff"][l].astype(dt), x)
        x = x + _mlp(a, *(w["mlp." + k][l].astype(dt) for k in MLP),
                     mode=mode)
    return norm(w["g_f"].astype(dt), x)


def logits_all(w: dict, tokens, config: dict, mode: str | None = None):
    """``[T, num_pred_heads, V]`` float32: head ``i``'s logits for byte
    ``t + 1 + i`` at every position of one sequence."""
    mode = mode or "f32"
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    y = hidden(w, tokens, config, mode)
    out = _mm(y, w["w_head"].astype(dt), mode).astype(jnp.float32)
    return out.reshape(y.shape[0], -1, int(config["vocab_size"]))


def logits(w: dict, tokens, config: dict, mode: str | None = None):
    """``[T, V]`` float32 next-byte logits of one sequence: head 0."""
    mode = mode or "f32"
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    y = hidden(w, tokens, config, mode)
    v = int(config["vocab_size"])
    return _mm(y, w["w_head"][:v].astype(dt), mode).astype(jnp.float32)
