"""Plain reference for the window-and-full-attention, sink-softmax,
sparse-expert LM whose K and V rows differ in width (``model_type:
mimo_v2_flash``: MiMo-V2-Flash).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: the whole forward pass over one padded sequence with the two
masks, no cache, no batching, no kernels, nothing imported from the
program. It is handed the program's own weight arrays (bfloat16 as
served) and upcasts ONE EXPERT (three matrices, inside a scan over the
held experts), one KV head's group of query heads over one block of 512
query rows and one block of the vocabulary at a time, never a layer, and
WAITS for each mixer and each FFN before it upcasts the next one's
weights (the host would otherwise enqueue every layer's float32 copies
ahead of the device, and their buffers are taken when they are
enqueued: the first form of this file ran the chip's memory to 15.71 GB
beside an engine of 12.53; PERF.md section 6, PR 49), so it fits beside
the engine.

``x [T, d]`` is the residual stream, RMSNorm gain-only at
``layernorm_epsilon``, every matrix ``[out, in]``, no bias:

- layer ``l``: ``h = x + attn_l(rms(x; norm_in[l]))``; ``x = h +
  ffn_l(rms(h; norm_ff[l]))``; ``logits = rms(x; g_f) @ w_head.T``
  (untied). No position is added to the embedding.
- ``attn_l``, ``hybrid_layer_pattern[l]`` 0 a full layer, 1 a sliding
  window one: ``q = W_q a`` (``H`` heads of ``dk = head_dim`` lanes),
  ``k = W_k a`` (the kind's KV heads, ``num_key_value_heads`` or
  ``swa_num_key_value_heads``, of ``dk``), ``v = attention_value_scale
  * W_v a`` (the same KV heads of ``dv = v_head_dim`` lanes); rotary on
  the FIRST ``R = int(dk * partial_rotary_factor)`` lanes of every
  ``q`` and ``k`` head, lane ``i < R / 2`` paired with ``i + R / 2``,
  pair ``i`` turned by ``pos * theta^(-2i/R)``, ``theta`` the kind's
  (``rope_theta`` / ``swa_rope_theta``), the other lanes pass. ``s_j =
  q . k_j / sqrt(dk)`` over ``j <= p``; a window layer only ``p - j <
  sliding_window``, and its head ``h`` has a sink, a scalar that joins
  the softmax's denominator and nothing else: ``p_j = exp(s_j - m) /
  (exp(sink_h - m) + sum_j' exp(s_j' - m))``, ``m = max(sink_h, max_j
  s_j)``. ``o_h = sum_j p_j v_j``, ``H / H_kv`` query heads a KV head;
  ``W_o [d, H * dv]``.
- FFN of a dense layer (``moe_layer_freq[l]`` 0): ``W_down (silu(W_gate
  h) * W_up h)``. Of an expert layer: ``sc = sigmoid(W_r h)`` in
  float32 over ALL ``router_experts``; the ``top_k`` largest of ``sc +
  bias`` chosen; ``w_k = sc_k / sum_chosen sc`` (times
  ``routed_scaling_factor``, null = 1); ``FFN(h) = sum over the chosen
  experts HELD here of w_k expert_k(h)`` — the held experts are
  ``[expert_first, expert_first + n_routed_experts)``, and a choice that
  falls on another adds nothing, here as in the program. No shared
  expert; no token is dropped.

Departures from the published block, each listed in the configuration's
``assumed``: the window counting the current token, the rotary's
half-split pairing, the value scale's place, the seeded draws.

The weights are named leaves (``configs/mimo_v2_flash_engine_driver.py``):
``full.*`` / ``window.*`` stacked over the layers of their kind, the
window layers' ``sinks [L_w, H]``, ``dense.*`` over the dense layers,
``experts.*`` over the expert ones, the norms over all.

``mode`` runs the same mathematics in a lower precision — the control
that ``correct`` has to refuse:

- ``"f32"`` (or None): float32, every product at ``highest``.
- ``"bf16"``: weights, activations, router, sinks and every
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


def inv_freq(theta: float, dk: int, partial_factor: float) -> np.ndarray:
    """``[R / 2]``: each rotated pair's turn a position."""
    r = int(dk * partial_factor)
    i = np.arange(r // 2, dtype=np.float64)
    return (float(theta) ** (-2.0 * i / r)).astype(np.float32)


def _rope(x, freqs):
    """``x [T, heads, dk]``: the first ``2 * len(freqs)`` lanes rotated
    by the row's position ``0..T-1``, the rest passed."""
    half = freqs.shape[0]
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * freqs)[:, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           -1)


@partial(jax.jit, static_argnames=("dk", "dv", "window", "v_scale", "mode"))
def _attn(a, wq, wk, wv, wo, sink, freqs, *, dk, dv, window, v_scale, mode):
    """``window`` 0: every earlier position and no sink (``sink`` is
    then not read)."""
    t = a.shape[0]
    q = _rope(_mm(a, wq, mode).reshape(t, -1, dk), freqs)
    k = _rope(_mm(a, wk, mode).reshape(t, -1, dk), freqs)
    v = (_mm(a, wv, mode) * jnp.asarray(v_scale, a.dtype)).reshape(t, -1, dv)
    hkv = k.shape[1]
    qb = math.gcd(t, QUERY_BLOCK)
    cols = jnp.arange(t)[None, :]

    def group(qkvs):
        """One KV head and its query heads, ``q [g, T, dk]``, a block
        of query rows at a time; ``sk [g]`` the group's sinks."""
        qg, kk, vv, sk = qkvs

        def rows(start):
            p = start + jnp.arange(qb)[:, None]
            mask = cols <= p
            if window:
                mask = mask & (p - cols < window)
            qs = jax.lax.dynamic_slice_in_dim(qg, start, qb, 1)
            s = jnp.einsum("gqd,td->gqt", qs, kk, precision=HI) / jnp.sqrt(
                jnp.asarray(dk, a.dtype))
            s = jnp.where(mask, s, -jnp.inf).astype(jnp.float32)
            if not window:
                pr = jax.nn.softmax(s, -1)
            else:
                # the sink: one more term of the denominator
                sk32 = sk.astype(jnp.float32)[:, None, None]
                m = jnp.maximum(jnp.max(s, -1, keepdims=True), sk32)
                e = jnp.exp(s - m)
                pr = e / (jnp.exp(sk32 - m) + jnp.sum(e, -1, keepdims=True))
            return jnp.einsum("gqt,td->gqd", pr.astype(a.dtype), vv,
                              precision=HI)

        y = jax.lax.map(rows, jnp.arange(0, t, qb))       # [nb, g, qb, dv]
        return y.transpose(1, 0, 2, 3).reshape(qg.shape[0], t, dv)

    # [H_kv, g, T, dk]: query head h belongs to KV head h // g
    qg = q.reshape(t, hkv, -1, dk).transpose(1, 2, 0, 3)
    y = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2),
                            sink.reshape(hkv, -1)))
    y = y.transpose(2, 0, 1, 3).reshape(t, -1)            # [T, H * dv]
    return _mm(y, wo, mode)


@partial(jax.jit, static_argnames=("mode",))
def _mlp(a, w_gate, w_up, w_down, *, mode):
    return _mm(jax.nn.silu(_mm(a, w_gate, mode)) * _mm(a, w_up, mode),
               w_down, mode)


@partial(jax.jit, static_argnames=("top_k", "scale", "first", "held"))
def _route(a, w_r, bias, *, top_k, scale, first, held):
    """``[T, held]`` weights: row ``t``'s weight for held expert ``e``,
    0 where it did not choose it. In the type of ``a`` (float32 unless
    the mode is the all-bfloat16 control); ``w_r`` and ``bias`` are
    float32 as stored. The bias moves the choice, never the weight."""
    dt = a.dtype
    sc = jax.nn.sigmoid(jnp.matmul(a, w_r.astype(dt).T, precision=HI))
    _, idx = jax.lax.top_k(sc + bias.astype(dt), top_k)
    chosen = jnp.take_along_axis(sc, idx, -1)
    w = scale * chosen / jnp.sum(chosen, -1, keepdims=True)
    hit = idx[:, :, None] == first + jnp.arange(held)
    return jnp.sum(jnp.where(hit, w[:, :, None], 0), 1).astype(dt)


@partial(jax.jit, static_argnames=("eps",))
def _norm(g, x, *, eps):
    return _rms(g, x, eps)


@partial(jax.jit, static_argnames=("mode",))
def _held_sum(a, gates, w_gate, w_up, w_down, x, *, mode):
    """``sum_e gates[:, e] * expert_e(a)`` over the held experts of
    expert layer ``x`` (``w_* [L_e, E_held, ...]`` as stored), one
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
    """``sum over held chosen w_k expert_k(a)`` of expert layer ``x``."""
    scale = config.get("routed_scaling_factor")
    gates = _route(a, w["experts.w_router"][x], w["experts.bias"][x],
                   top_k=int(config["num_experts_per_tok"]),
                   scale=1.0 if scale is None else float(scale),
                   first=int(config.get("expert_first", 0)),
                   held=w["experts.w_gate"].shape[1])
    return _held_sum(a, gates, *(w["experts." + k] for k in MLP),
                     jnp.int32(x), mode=mode)


def hidden(w: dict, tokens, config: dict, mode: str | None = None):
    """Final residual stream ``[T, d]`` of one sequence."""
    mode = mode or "f32"
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    eps = float(config["layernorm_epsilon"])
    dk, dv = int(config["head_dim"]), int(config["v_head_dim"])
    window = int(config["sliding_window"])
    part = float(config.get("partial_rotary_factor", 1.0))
    v_scale = float(config.get("attention_value_scale") or 1.0)
    heads = int(config["num_attention_heads"])
    x = w["wte"][jnp.asarray(tokens, jnp.int32)].astype(dt)
    seen = {"full": 0, "window": 0, "dense": 0, "experts": 0}
    for l, sliding in enumerate(config["hybrid_layer_pattern"]):
        s = "window" if sliding else "full"
        i = seen[s]
        seen[s] += 1
        theta = config["swa_rope_theta"] if sliding else config["rope_theta"]
        sink = (w["sinks"][i].astype(dt) if sliding
                else jnp.zeros((heads,), dt))
        a = _norm(w["norm_in"][l].astype(dt), x, eps=eps)
        x = jax.block_until_ready(x + _attn(
            a, *(w[f"{s}.{k}"][i].astype(dt) for k in ATTN), sink,
            jnp.asarray(inv_freq(theta, dk, part)), dk=dk, dv=dv,
            window=window if sliding else 0, v_scale=v_scale, mode=mode))
        a = _norm(w["norm_ff"][l].astype(dt), x, eps=eps)
        m = "experts" if config["moe_layer_freq"][l] else "dense"
        j = seen[m]
        seen[m] += 1
        if m == "dense":
            x = x + _mlp(a, *(w["dense." + k][j].astype(dt) for k in MLP),
                         mode=mode)
        else:
            x = x + _experts(w, j, a, config, dt, mode)
        x = jax.block_until_ready(x)    # the module docstring says why
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
    a = _norm(w["g_f"].astype(dt), x, eps=float(config["layernorm_epsilon"]))
    head = w["w_head"]
    out = jnp.zeros((a.shape[0], head.shape[0]), jnp.float32)
    for start in range(0, head.shape[0], VOCAB_BLOCK):
        out = jax.block_until_ready(_head_block(
            out, a, head[start:start + VOCAB_BLOCK].astype(dt), start,
            mode=mode))
    return out
