"""Language model: token/position embeddings + transformer stack + tied head.

The reference trains on mocked data with a mocked upstream gradient — no
tokens, no loss (``train_ffns.py:12, :144-151``). This family completes the
path from token ids to a real scalar objective while keeping the framework's
stance: raw stacked arrays in a NamedTuple (``train_ffns.py:38-39``), no
biases (``:35``), hand-written VJPs for every nonlinear op (blocks:
``models.transformer``; loss: ``ops.xent``) with the linear pieces — the
embedding gather and the tied-head matmul — left to ``jax.vjp``'s exact
transposes (gather <-> scatter-add).

GPT-2 shape conventions: learned positional embeddings, pre-LN blocks, a
final LayerNorm, and the LM head tied to the token embedding
(``logits = h @ wte.T``) so ``wte`` receives gradient from both ends.

Decode (``generate``) is inference-only — a jitted ``lax.scan`` over
positions with a static-shape KV cache updated via
``dynamic_update_slice`` — so it uses plain jnp ops (no VJP rules needed)
and never retraces as the sequence grows: the TPU-native shape discipline
(one compiled program, no per-token recompilation).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.norm import layernorm
from ..ops.xent import xent_loss
from .face import ATTN, CacheSpec, mm, qkv_heads
from .transformer import (TransformerParams, init_transformer,
                          transformer_fwd)


class LMParams(NamedTuple):
    """``wte [V, d]`` token embedding (tied LM head); ``wpe [T_max, d]``
    learned positions; ``blocks`` the pre-LN transformer stack; ``ln_f [d]``
    the final LayerNorm gain."""
    wte: jax.Array
    wpe: jax.Array
    blocks: TransformerParams
    ln_f: jax.Array

    @property
    def vocab(self) -> int:
        return self.wte.shape[0]

    @property
    def d_model(self) -> int:
        return self.wte.shape[1]

    @property
    def max_seq_len(self) -> int:
        return self.wpe.shape[0]

    @property
    def n_layers(self) -> int:
        return self.blocks.n_layers

    def num_params(self) -> int:
        return (self.wte.size + self.wpe.size + self.ln_f.size +
                self.blocks.num_params())

    # The model face (``models/face.py::ServedModel``): what
    # ``decode/programs.py`` builds the serving programs from. GPT-2's
    # answers: learned positions, the gain-only LayerNorm, the ReLU
    # FFN, every layer attention with the cache index of its own
    # number.
    @property
    def layers(self) -> tuple:
        return tuple((ATTN, l) for l in range(self.n_layers))

    @property
    def norm_in(self) -> jax.Array:
        return self.blocks.ln1

    @property
    def norm_ff(self) -> jax.Array:
        return self.blocks.ln2

    def cache_spec(self, n_heads: int) -> CacheSpec:
        dh = self.d_model // n_heads
        return CacheSpec(self.n_layers, self.blocks.wk.shape[1] // dh, dh)

    def embed(self, tokens, positions, lookup):
        return lookup(self.wte, tokens) + self.wpe[positions]

    def norm(self, g, x):
        return layernorm(g, x)

    def attn_qkv(self, i, a, positions, head_dim, use_rope):
        blk = self.blocks
        return qkv_heads(blk.wq, blk.wk, blk.wv, i, a, positions,
                         head_dim, use_rope)

    def attn_out(self, i, y, a):
        return mm(y, self.blocks.wo[i])

    def ffn(self, l, h):
        blk = self.blocks
        return jnp.maximum(h @ blk.w1[l].T, 0.0) @ blk.w2[l].T

    def head(self, x):
        return mm(layernorm(self.ln_f, x), self.wte)

    # The CLI's uniform per-layer report reads ``.w1``/``.w2``
    # (train_ffns.py:370-371 prints layers_params[0]); delegate to the
    # block stack's FFN pair.
    @property
    def w1(self) -> jax.Array:
        return self.blocks.w1

    @property
    def w2(self) -> jax.Array:
        return self.blocks.w2


def init_lm(key: jax.Array, vocab: int, d_model: int, n_layers: int,
            max_seq_len: int, ffn_dim: int | None = None,
            scale: float = 2e-2, dtype=jnp.float32,
            n_heads: int | None = None,
            n_kv_heads: int | None = None) -> LMParams:
    """Same init family as the rest of the framework: ``scale * normal``
    (``train_ffns.py:35-36``), LN gains at 1.

    ``n_kv_heads`` (with ``n_heads``) initializes grouped-query attention
    weights: wk/wv project to ``n_kv_heads * head_dim`` dims, shrinking
    the KV cache by ``n_heads/n_kv_heads`` — the forward/decode paths
    pick up the grouping from the shapes alone."""
    kv_dim = None
    if n_heads is not None and d_model % n_heads:
        raise ValueError(f"d_model={d_model} not divisible by "
                         f"n_heads={n_heads}")
    if n_kv_heads is not None:
        if n_heads is None:
            raise ValueError("n_kv_heads needs n_heads (head_dim = "
                             "d_model / n_heads)")
        if n_kv_heads < 1:
            raise ValueError(f"n_kv_heads must be >= 1, got {n_kv_heads}")
        if n_heads % n_kv_heads:
            raise ValueError(
                f"n_heads={n_heads} not divisible by "
                f"n_kv_heads={n_kv_heads}")
        kv_dim = (d_model // n_heads) * n_kv_heads
    ke, kp, kb = jax.random.split(key, 3)
    return LMParams(
        wte=scale * jax.random.normal(ke, (vocab, d_model), dtype),
        wpe=scale * jax.random.normal(kp, (max_seq_len, d_model), dtype),
        blocks=init_transformer(kb, d_model, n_layers, ffn_dim, scale,
                                dtype, kv_dim=kv_dim),
        ln_f=jnp.ones((d_model,), dtype))


def lm_hidden(params: LMParams, tokens: jax.Array, n_heads: int,
              attn=None) -> jax.Array:
    """Embed + blocks + final LN. ``tokens [B, T]`` int -> ``[B, T, d]``."""
    t = tokens.shape[1]
    x = params.wte[tokens] + params.wpe[:t]
    x = transformer_fwd(params.blocks, x, n_heads, causal=True, attn=attn)
    return layernorm(params.ln_f, x)


def lm_logits(params: LMParams, tokens: jax.Array, n_heads: int,
              attn=None) -> jax.Array:
    """``tokens [B, T]`` -> logits ``[B, T, V]`` via the tied head."""
    h = lm_hidden(params, tokens, n_heads, attn)
    return h @ params.wte.T


def lm_loss(params: LMParams, tokens: jax.Array, targets: jax.Array,
            n_heads: int, attn=None, head=None,
            mixed: bool = False) -> jax.Array:
    """Mean next-token cross-entropy. ``tokens, targets [B, T]`` int.

    ``head`` swaps the tied-head + loss computation: None materializes
    ``[N, V]`` logits and runs the hand-VJP xent (the oracle);
    a callable ``(h [N, d], wte [V, d], targets [N]) -> scalar`` takes
    the trunk output directly — the fused Pallas head
    (``ops.pallas_xent.head_xent`` via ``parallel.lm.resolve_head``)
    never builds the logits at all.

    ``mixed`` is the LM family's bf16 policy (the ``train_single(
    mixed=True)`` stance extended over the transformer trunk): the
    TRUNK — embedding gather, blocks, final LN — runs on a bf16 cast of
    the params with a bf16 residual stream in HBM (half the activation
    traffic; MXU time is unchanged since default-precision f32 matmuls
    are single bf16 passes anyway), while the head + cross-entropy stay
    f32 on the f32 master ``wte``. Params, grads, and the update remain
    f32 end to end — the embedding contribution to ``wte``'s gradient
    arrives through the bf16 cast's transpose (a cast back to f32),
    summing with the head's f32 contribution."""
    if mixed:
        trunk = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), params)
        h = lm_hidden(trunk, tokens, n_heads, attn)
        h = h.reshape(-1, h.shape[-1]).astype(jnp.float32)
        if head is not None:
            return head(h, params.wte, targets.reshape(-1))
        logits = h @ params.wte.T
        return xent_loss(logits, targets.reshape(-1))
    if head is not None:
        h = lm_hidden(params, tokens, n_heads, attn)
        return head(h.reshape(-1, h.shape[-1]), params.wte,
                    targets.reshape(-1))
    logits = lm_logits(params, tokens, n_heads, attn)
    v = logits.shape[-1]
    return xent_loss(logits.reshape(-1, v), targets.reshape(-1))


# ---------------------------------------------------------------------------
# Decode: static-shape KV cache + greedy generation under one jitted scan.


class KVCache(NamedTuple):
    """Per-layer key/value blocks, ``[L, B, H, T_max, dh]`` each, written
    in place (functionally) at the current position each decode step."""
    k: jax.Array
    v: jax.Array


def init_cache(params: LMParams, batch: int, n_heads: int,
               dtype=None) -> KVCache:
    """Cache sized by the model's KV head count (``wk``'s output dim over
    the head dim) — under GQA that is ``n_kv_heads``, so cache bytes
    shrink by the group factor with no other change."""
    dh = params.d_model // n_heads
    kv_heads = params.blocks.wk.shape[1] // dh
    shape = (params.n_layers, batch, kv_heads, params.max_seq_len, dh)
    dtype = params.wte.dtype if dtype is None else dtype
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def decode_attn(q, ck, cv, lengths):
    """Single-query attention over the cache. ``q [B, H, dh]``,
    ``ck/cv [B, H_kv, T_max, dh]`` with ``H % H_kv == 0`` (GQA groups;
    ``H_kv == H`` is plain MHA); positions ``>= lengths`` are masked
    (the cache beyond the write head is zeros — or, under the decode
    engine's block tables, stale bytes — never probability mass).
    ``lengths`` is the per-sequence live-token count: a scalar for the
    lockstep ``generate`` scan, or ``[B]`` for the decode engine's
    continuously-batched slots, each at its own position."""
    b, h, dh = q.shape
    hkv = ck.shape[1]
    qg = q.reshape(b, hkv, h // hkv, dh)
    s = jnp.einsum("bkgd,bktd->bkgt", qg, ck) / jnp.sqrt(
        jnp.asarray(dh, q.dtype))
    lengths = jnp.asarray(lengths)
    mask = jnp.arange(ck.shape[2]) < lengths[..., None]  # [T] or [B, T]
    if mask.ndim == 2:
        mask = mask[:, None, None, :]                    # -> [B, 1, 1, T]
    s = jnp.where(mask, s, jnp.asarray(-1e30, s.dtype))
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgt,bktd->bkgd", p, cv).reshape(b, h, dh)


def _decode_attn(q, ck, cv, pos):
    """The lockstep form: every sequence at the same scalar ``pos``."""
    return decode_attn(q, ck, cv, jnp.asarray(pos) + 1)


def cached_attn_step(ln1_l, wq_l, wk_l, wv_l, wo_l, cache_k, cache_v,
                     layer: int, x: jax.Array, pos,
                     use_rope: bool = False):
    """One decode attention sublayer, shared by the dense, MoE, and TP
    decode paths: LN, QKV projection of this path's (possibly
    head-sharded) weights, cache write at ``pos``, single-query attention
    over the cache, output projection. Returns ``(y_proj, cache_k,
    cache_v)`` with the residual add (and, under TP, the psum) left to
    the caller — ``y_proj`` may be a partial sum over sharded heads.
    Head counts (query AND kv — GQA falls out) and head dim come from
    the weight/cache shapes. ``use_rope`` rotates q and the new k by
    ``pos`` before the cache write — the cache then stores rotated keys,
    exactly matching training under ``attn_impl="rope"``."""
    from .attention import rope
    b = x.shape[0]
    dh = cache_k.shape[-1]
    h_loc = wq_l.shape[0] // dh
    kv_loc = wk_l.shape[0] // dh
    a = layernorm(ln1_l, x)
    q = (a @ wq_l.T).reshape(b, h_loc, dh)
    k = (a @ wk_l.T).reshape(b, kv_loc, dh)
    v = (a @ wv_l.T).reshape(b, kv_loc, dh)
    if use_rope:
        p1 = jnp.asarray(pos)[None]
        q = rope(q[:, :, None, :], p1)[:, :, 0, :]
        k = rope(k[:, :, None, :], p1)[:, :, 0, :]
    cache_k = lax.dynamic_update_slice(
        cache_k, k[None, :, :, None, :], (layer, 0, 0, pos, 0))
    cache_v = lax.dynamic_update_slice(
        cache_v, v[None, :, :, None, :], (layer, 0, 0, pos, 0))
    y = _decode_attn(q, cache_k[layer], cache_v[layer], pos)
    return y.reshape(b, h_loc * dh) @ wo_l.T, cache_k, cache_v


def decode_step(params: LMParams, cache: KVCache, token: jax.Array,
                pos: jax.Array, n_heads: int, use_rope: bool = False):
    """One token through the stack at position ``pos`` (traced scalar).

    ``token [B]`` int -> ``(logits [B, V], cache')``. Static shapes
    throughout: the cache is written at ``pos`` via
    ``dynamic_update_slice``, attention masks the unwritten tail.
    """
    p = params.blocks
    if cache.k.shape[-1] * n_heads != params.d_model:
        raise ValueError(
            f"cache head dim {cache.k.shape[-1]} inconsistent with "
            f"n_heads={n_heads} at d_model={params.d_model}")
    x = params.wte[token] + params.wpe[pos]                  # [B, d]
    new_k, new_v = cache.k, cache.v
    for l in range(p.n_layers):
        y, new_k, new_v = cached_attn_step(
            p.ln1[l], p.wq[l], p.wk[l], p.wv[l], p.wo[l],
            new_k, new_v, l, x, pos, use_rope)
        x = x + y
        h = layernorm(p.ln2[l], x)
        x = x + jnp.maximum(h @ p.w1[l].T, 0.0) @ p.w2[l].T
    h = layernorm(params.ln_f, x)
    return h @ params.wte.T, KVCache(new_k, new_v)


def decode_loop(step_fn, cache, prompt: jax.Array, n_new: int,
                max_seq_len: int, pick) -> jax.Array:
    """Shared prefill+generate scan for any cached decoder.
    ``step_fn(cache, token [B], pos) -> (logits [B, V], cache)`` runs one
    token through the stack; ``pick(logits, pos) -> [B]`` chooses the next
    token (argmax for greedy, a categorical draw for sampling). One
    ``lax.scan`` covers prefill and generation: step ``t`` feeds the
    prompt token while ``t < T0`` (teacher-forced prefill filling the
    cache) and the previous pick after — so the compiled program is
    independent of where the prompt ends, and a whole batch decodes in
    one dispatch."""
    b, t0 = prompt.shape
    total = t0 + n_new
    if total > max_seq_len:
        raise ValueError(f"prompt {t0} + n_new {n_new} exceeds "
                         f"max_seq_len {max_seq_len}")
    padded = jnp.concatenate(
        [prompt, jnp.zeros((b, n_new), prompt.dtype)], axis=1)

    def step(carry, pos):
        cache, toks, prev = carry
        token = jnp.where(pos < t0, toks[:, pos], prev)
        logits, cache = step_fn(cache, token, pos)
        nxt = pick(logits, pos).astype(toks.dtype)
        toks = lax.dynamic_update_slice(
            toks, jnp.where(pos + 1 < t0, toks[:, pos + 1], nxt)[:, None],
            (0, pos + 1))
        return (cache, toks, nxt), None

    init = (cache, padded, padded[:, 0])
    (_, toks, _), _ = lax.scan(step, init, jnp.arange(total - 1))
    return toks


def _decode_loop(params: LMParams, prompt: jax.Array, n_new: int,
                 n_heads: int, pick, use_rope: bool = False) -> jax.Array:
    return decode_loop(
        lambda cache, token, pos: decode_step(params, cache, token, pos,
                                              n_heads, use_rope),
        init_cache(params, prompt.shape[0], n_heads), prompt, n_new,
        params.max_seq_len, pick)


def generate(params: LMParams, prompt: jax.Array, n_new: int,
             n_heads: int, *, use_rope: bool = False) -> jax.Array:
    """Greedy decode: ``prompt [B, T0]`` -> ``[B, T0 + n_new]``.
    ``use_rope`` must match how the model was trained
    (``attn_impl="rope"``)."""
    return _decode_loop(params, prompt, n_new, n_heads,
                        lambda z, pos: jnp.argmax(z, axis=-1), use_rope)


def sample_pick(temperature: float, top_k: int, vocab: int, seed: int):
    """Build the stochastic ``pick(logits, pos)`` for ``decode_loop``:
    temperature-scaled, optionally top-k-truncated categorical draws.
    Deterministic given ``seed`` — the per-position key is
    ``fold_in(fold_in(base, seed), pos)``, the same counter-RNG contract
    as the data layer, so a sampled continuation is reproducible without
    any carried RNG state. Shared by the dense and MoE samplers."""
    if temperature <= 0:
        raise ValueError(f"temperature must be > 0, got {temperature} "
                         "(use the greedy decoder — generate/"
                         "moe_generate — for the argmax limit)")
    if top_k < 0 or top_k > vocab:
        raise ValueError(f"top_k={top_k} outside [0, vocab={vocab}]")
    base = jax.random.fold_in(jax.random.PRNGKey(0x5A3), seed)

    def pick(logits, pos):
        z = logits / temperature
        if top_k:
            kth = lax.top_k(z, top_k)[0][:, -1:]
            z = jnp.where(z < kth, -jnp.inf, z)
        return jax.random.categorical(jax.random.fold_in(base, pos), z,
                                      axis=-1)

    return pick


def sample(params: LMParams, prompt: jax.Array, n_new: int, n_heads: int,
           *, temperature: float = 1.0, top_k: int = 0,
           seed: int = 0, use_rope: bool = False) -> jax.Array:
    """Stochastic decode (see ``sample_pick``). ``top_k=0`` samples the
    full distribution; ``top_k=1`` degenerates to greedy."""
    return _decode_loop(params, prompt, n_new, n_heads,
                        sample_pick(temperature, top_k, params.vocab,
                                    seed), use_rope)
