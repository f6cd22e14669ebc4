"""A chunk-summarised (EVA) attention byte LM (``model_type: evabyte``).

EvaByte's block, served only. With ``x [T, d]`` the residual stream
(float32: ``fp32_skip_add``), RMSNorm gain-only with a unit offset
(``norm_add_unit_offset``: the gain is ``1 + g``), every matrix ``[out,
in]`` and no bias anywhere, ``W`` = ``window_size``, ``C`` =
``chunk_size``, ``u(t) = t // W``:

- layer ``l``: ``h = x + attn_l(rms(x; 1 + norm_in[l]))`` then ``x = h +
  mlp_l(rms(h; 1 + norm_ff[l]))``, the gated SiLU MLP. After the last
  layer ``logits = rms(x; 1 + g_f) @ w_head.T -> [num_pred_heads, V]``
  in float32 (``fp32_logits``): head ``i`` predicts byte ``t + 1 + i``.
  ``V`` is 320: bytes. No position is added to the embedding.
- ``attn_l``: ``q, k, v = W_q a, W_k a, W_v a`` as ``H`` heads of
  ``dh`` (no grouping), ``q`` and ``k`` rotated at their position
  (half-split pairing, base ``rope_theta``, every lane). CHUNK ``j`` is
  positions ``C j .. C j + C - 1``; with the layer's learned ``phi_h,
  mu_h in R^dh``: ``alpha_{j,m} = softmax_{m in chunk j}(k_m . phi_h /
  sqrt(dh))``, ``ktilde_j = sum_m alpha_{j,m} k_m + mu_h``, ``vtilde_j
  = sum_m alpha_{j,m} v_m`` (one pair a chunk a head, from the ROTATED
  keys: EVA's control variate of a chunk, arXiv:2302.04542, its
  random-feature sample replaced by the learned ``phi``). The query at
  ``t`` sees, exactly, the keys of its own ALIGNED window up to itself,
  ``S_t = {m : u(m) = u(t), m <= t}``, and, as summaries, every chunk
  of every earlier window, ``R_t = {j : j < (W / C) u(t)}``, under ONE
  softmax: ``o_t = (sum_S e^{q.k_m / sqrt(dh)} v_m + sum_R e^{q.ktilde_j
  / sqrt(dh)} vtilde_j) / (sum_S e^{..} + sum_R e^{..})``; ``W_o``.

The engine keeps the two sets in two stores under the layer's one
index (``models/face.py::CHUNKED``): the window kind's ring for ``S_t``
and one row of the full kind's pool a finished chunk for ``R_t``;
``decode/paged.py`` reads each and joins them.

Precision (``mixedp_attn``): the residual stream, norms, rotary, the
softmaxes and the summaries in float32; a matrix product takes its
activations in the weights' type and accumulates in float32 (``mm``).

Independently, the same equations: ``benchmark/configs/
evabyte_lm_reference.py``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .face import (CHUNKED, AttnStack, CacheSpec, MLPStack, gated_mlp, mm,
                   qkv_heads, rmsnorm)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["wte", "w_head", "norm_in", "norm_ff", "g_f", "attn",
                 "phi", "mu", "mlp"],
    meta_fields=["head_dim", "window", "chunk", "theta", "eps",
                 "unit_offset", "max_seq_len"])
@dataclasses.dataclass(frozen=True)
class EvaByteLMParams:
    """``wte [V, d]``, ``w_head [P * V, d]`` (``P`` prediction heads,
    head ``i`` rows ``[i V, (i + 1) V)``), ``norm_in`` / ``norm_ff [L,
    d]``, ``g_f [d]`` (the stored gains ``g``: the norm multiplies by
    ``1 + g`` where ``unit_offset``), ``attn`` the mixers ``[L, ...]``,
    ``phi`` / ``mu [L, H, dh]`` the chunk summaries' learned vectors,
    ``mlp`` the gated MLPs ``[L, ...]``. Static: ``head_dim``,
    ``window`` and ``chunk`` (positions), the rotary base ``theta``,
    ``eps``, ``unit_offset`` and ``max_seq_len`` (bounds what
    ``submit`` accepts)."""
    wte: jax.Array
    w_head: jax.Array
    norm_in: jax.Array
    norm_ff: jax.Array
    g_f: jax.Array
    attn: AttnStack
    phi: jax.Array
    mu: jax.Array
    mlp: MLPStack
    head_dim: int
    window: int
    chunk: int
    theta: float
    eps: float
    unit_offset: bool
    max_seq_len: int

    @property
    def vocab(self) -> int:
        return self.wte.shape[0]

    @property
    def d_model(self) -> int:
        return self.wte.shape[1]

    @property
    def n_layers(self) -> int:
        return self.norm_in.shape[0]

    @property
    def n_pred(self) -> int:
        return self.w_head.shape[0] // self.vocab

    @property
    def layers(self) -> tuple:
        return tuple((CHUNKED, i) for i in range(self.n_layers))

    def num_params(self) -> int:
        return sum(x.size for x in jax.tree_util.tree_leaves(self))

    # -- the model face (``models/face.py::ServedModel``) --------------

    def cache_spec(self, n_heads: int) -> CacheSpec:
        layers = self.n_layers
        return CacheSpec(
            kv_layers=layers,
            kv_heads=self.attn.wk.shape[1] // self.head_dim,
            head_dim=self.head_dim, win_layers=layers, window=self.window,
            chunk=self.chunk)

    def embed(self, tokens, positions, lookup):
        return lookup(self.wte, tokens).astype(jnp.float32)

    def norm(self, g, x):
        g = g.astype(jnp.float32)
        return rmsnorm(1.0 + g if self.unit_offset else g, x, self.eps)

    def chunked_qkv(self, i, a, positions):
        return qkv_heads(self.attn.wq, self.attn.wk, self.attn.wv, i, a,
                         positions, self.head_dim, True, theta=self.theta)

    def chunk_summary(self, i, k_blk, v_blk):
        with jax.named_scope("summarise"):
            return chunk_summary(self.phi[i], self.mu[i], k_blk, v_blk)

    def chunked_out(self, i, y, a):
        return mm(y, self.attn.wo[i])

    def ffn(self, l, h):
        return gated_mlp(self.mlp, l, h)

    def head(self, x):
        """Head 0's ``[N, V]``: the next byte, what a step program
        picks from."""
        return mm(self.norm(self.g_f, x), self.w_head[:self.vocab])

    def head_all(self, x):
        """All ``P`` heads' ``[N, P, V]`` (head ``i``: byte ``t + 1 +
        i``). No step program calls it: the further heads are a drafter
        nobody reads yet."""
        return mm(self.norm(self.g_f, x), self.w_head).reshape(
            x.shape[0], self.n_pred, self.vocab)


def chunk_summary(phi, mu, k_blk, v_blk):
    """The ONE key and value a head keeps of each finished chunk:
    ``k_blk / v_blk [n, C, H*dh]`` (stored rows, heads side by side),
    ``phi / mu [H, dh]`` -> ``(ktilde, vtilde) [n, H, dh]`` float32,
    ``alpha = softmax_C(k . phi / sqrt(dh))``, ``ktilde = sum alpha k +
    mu``, ``vtilde = sum alpha v``."""
    n, c, _ = k_blk.shape
    h, dh = phi.shape
    k = k_blk.astype(jnp.float32).reshape(n, c, h, dh)
    v = v_blk.astype(jnp.float32).reshape(n, c, h, dh)
    s = jnp.einsum("nchd,hd->nch", k, phi.astype(jnp.float32)) * dh ** -0.5
    alpha = jax.nn.softmax(s, axis=1)
    return (jnp.einsum("nch,nchd->nhd", alpha, k) + mu.astype(jnp.float32),
            jnp.einsum("nch,nchd->nhd", alpha, v))


class EvaByteSpec(NamedTuple):
    """The sizes a published ``config.json`` gives (``spec_from_config``)."""
    vocab: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    ffn: int
    window: int
    chunk: int
    n_pred: int
    theta: float
    eps: float
    unit_offset: bool
    max_seq_len: int

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def spec_from_config(config: dict) -> EvaByteSpec:
    """A ``model_type: evabyte`` ``config.json`` (the published keys) as
    sizes. What this file cannot serve is refused by name, never read
    as something else."""
    if config.get("model_type") != "evabyte":
        raise ValueError(f"model_type {config.get('model_type')!r}: "
                         "models/evabyte_lm.py serves 'evabyte' only")
    if config.get("attention_class", "eva") != "eva":
        raise ValueError(f"attention_class {config['attention_class']!r}: "
                         "the attention is served as 'eva' only")
    if config.get("attention_bias"):
        raise ValueError("attention_bias: no projection has a bias")
    if config.get("rope_scaling") is not None:
        raise ValueError("rope_scaling: the rotary is served unscaled only")
    if config.get("tie_word_embeddings", False):
        raise ValueError("the head is served untied only")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {config['hidden_act']!r}: the MLP is "
                         "served gated with SiLU only")
    if not config.get("fp32_logits", True):
        raise ValueError("fp32_logits false: the logits are served in "
                         "float32 only")
    if config.get("num_chunks") is not None:
        raise ValueError("num_chunks: a chunk is served by its size "
                         "(chunk_size) only")
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    hkv = int(config.get("num_key_value_heads", h))
    if d % h or hkv != h:
        raise ValueError("num_key_value_heads: served with one KV head a "
                         "query head only (phi and mu are a head's own), "
                         "and hidden_size a whole number of heads")
    window, chunk = int(config["window_size"]), int(config["chunk_size"])
    if window % chunk:
        raise ValueError(f"window_size {window} is no whole number of "
                         f"chunks of chunk_size {chunk}")
    return EvaByteSpec(
        vocab=int(config["vocab_size"]), d_model=d,
        n_layers=int(config["num_hidden_layers"]), n_heads=h, n_kv_heads=hkv,
        ffn=int(config["intermediate_size"]), window=window, chunk=chunk,
        n_pred=int(config.get("num_pred_heads", 1)),
        theta=float(config.get("rope_theta", 10000.0)),
        eps=float(config["rms_norm_eps"]),
        unit_offset=bool(config.get("norm_add_unit_offset", False)),
        max_seq_len=int(config["max_position_embeddings"]))


@functools.partial(jax.jit, static_argnames=("spec", "dtype", "scale"))
def init_evabyte_lm(key: jax.Array, spec: EvaByteSpec, dtype=jnp.float32,
                    scale: float = 0.01275) -> EvaByteLMParams:
    """Seeded weights, made on the device in one call: every matrix,
    ``phi``, ``mu`` and the stored gains ``scale * normal`` (the
    published ``init_std``) clipped to +-1 in ``dtype`` (a gain multiplies by ``1 + g``, so it starts
    near 1 and not at it: the offset is in every product)."""
    s = spec
    n_l, d, dh = s.n_layers, s.d_model, s.head_dim
    hd = s.n_heads * dh
    ks = iter(jax.random.split(key, 16))

    def w(*shape):
        return jnp.clip(scale * jax.random.normal(
            next(ks), shape, jnp.float32), -1.0, 1.0).astype(dtype)

    def gain(*shape):
        g = w(*shape)
        return g if s.unit_offset else (1.0 + g).astype(dtype)

    return EvaByteLMParams(
        wte=w(s.vocab, d), w_head=w(s.n_pred * s.vocab, d),
        norm_in=gain(n_l, d), norm_ff=gain(n_l, d), g_f=gain(d),
        attn=AttnStack(wq=w(n_l, hd, d), wk=w(n_l, hd, d), wv=w(n_l, hd, d),
                       wo=w(n_l, d, hd)),
        phi=w(n_l, s.n_heads, dh), mu=w(n_l, s.n_heads, dh),
        mlp=MLPStack(w_gate=w(n_l, s.ffn, d), w_up=w(n_l, s.ffn, d),
                     w_down=w(n_l, d, s.ffn)),
        head_dim=dh, window=s.window, chunk=s.chunk, theta=s.theta,
        eps=s.eps, unit_offset=s.unit_offset, max_seq_len=s.max_seq_len)
