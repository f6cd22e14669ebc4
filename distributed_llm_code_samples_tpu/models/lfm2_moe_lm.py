"""A gated short-convolution, grouped-query-attention, sparse-expert LM
(``model_type: lfm2_moe``).

The LFM2-MoE family's block, served only. With ``x [T, d]`` the residual
stream, RMSNorm gain-only, every matrix ``[out, in]`` and no bias
anywhere:

- layer ``l``: ``h = x + op_l(rms(x; norm_in[l]))`` then ``x = h +
  ffn_l(rms(h; norm_ff[l]))``; ``op_l`` is a gated short convolution or
  grouped-query attention as ``layer_types[l]`` says (``conv`` /
  ``full_attention``). After the last layer ``logits = rms(x; g_f) @
  wte.T``, the head tied to the embedding. No position is added to the
  embedding.
- ``conv`` layer: ``[B; C; X] = W_in a`` (``W_in [3d, d]``); ``u = B *
  X``; ``v_t = sum_j w[j] * u_{t-(K-1)+j}``, depthwise and causal with
  ``K = conv_L_cache`` taps (tap ``K-1`` on the current token; no bias,
  no activation); ``y = W_out (C * v)``. What a sequence carries is the
  last ``K-1`` values of ``u`` — the convolution's tail in
  ``decode/paged.py::RecurrentState``, by slot — and NO scan state
  (``cache_spec().state_row.rows`` 0). The convolution itself is
  ``ops/ssm.py``'s: ``conv_chunk`` for a prefill chunk,
  ``conv_step_in_place`` on the stored rows for a decode batch; the two
  gate products round it are plain.
- ``full_attention`` layer: ``q = W_q a`` as ``H`` heads of ``dh``, ``k,
  v = W_k a, W_v a`` as ``H_kv`` heads; ``q`` and ``k`` each through an
  RMSNorm over a head's ``dh`` lanes with one gain vector a layer
  (QK-norm), then rotary over the whole head at ``rope_theta``; causal
  ``softmax(q k^T / sqrt(dh))`` with ``H / H_kv`` query heads a KV head;
  ``W_o``. The rotary is the model's own: the engine's ``use_rope`` (an
  option of the GPT-2 family) is not consulted.
- FFN of the ``num_dense_layers`` leading layers: the gated SiLU MLP
  ``W_down (silu(W_gate h) * W_up h)``. Of the others the expert layer
  (``ops/moe_serve.py``): a float32 sigmoid router with a choice-only
  bias (``use_expert_bias``), ``top_k`` of ``num_experts`` gated SiLU
  experts weighted ``routed_scale * s_k / sum_chosen s``, NO shared
  expert; no row is dropped. The params hold the contiguous range
  ``[expert_first, expert_first + E_held)`` of every layer's experts
  (all of them unless a holder was cut out) and compute that range's
  part of the result.

The rotary pairs lane ``i`` with lane ``i + dh / 2``
(``models/attention.py::rope``).

Precision: as ``models/hybrid_lm.py`` — the residual stream, norms,
rotary, softmax, the convolution and its tail in float32; a matrix
product takes its activations in the weights' type and accumulates in
float32 (``mm``); the router is float32 at ``highest`` whatever the
weights' type.

Independently, the same equations: ``benchmark/configs/
lfm2_moe_lm_reference.py``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops import moe_serve, ssm
from ..ops.moe_serve import ExpertStack, holder  # noqa: F401  (the family's names)
from .face import (ATTN, AttnStack, CacheSpec, MLPStack, StateRow,
                   gated_mlp, layers_of, mm, qkv_heads, rmsnorm)

CONV = "conv"
# config.json's ``layer_types`` -> the engine's layer kinds
KINDS = {"conv": CONV, "full_attention": ATTN}
# the seeded choice bias: ``BIAS_SCALE * normal``. The router's scores
# of one expert spread ~0.2 over a batch's rows at these widths, so this
# moves choices where two scores nearly tie and leaves the load as even
# as a trained bias keeps it (PERF.md section 6, PR 33 has the reading;
# PR 31's 0.1 concentrated its cell's load sevenfold)
BIAS_SCALE = 0.01


class ConvStack(NamedTuple):
    """The gated short-convolution mixers, stacked ``[L_c, ...]``; the
    inner width is the model's ``d``."""
    w_in: jax.Array      # [L_c, 3d, d]  -> [B; C; X]
    conv_w: jax.Array    # [L_c, K, d]   tap K-1 on the current token
    w_out: jax.Array     # [L_c, d, d]


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["wte", "norm_in", "norm_ff", "g_f", "conv", "attn", "g_q",
                 "g_k", "dense", "experts"],
    meta_fields=["kinds", "head_dim", "top_k", "routed_scale", "rope_theta",
                 "eps", "max_seq_len", "expert_first"])
@dataclasses.dataclass(frozen=True)
class Lfm2MoeLMParams:
    """``wte [V, d]`` (tied head), ``norm_in`` / ``norm_ff [L, d]``,
    ``g_f [d]``; ``conv`` the convolution mixers ``[L_c, ...]``, ``attn``
    the attention mixers ``[L_a, ...]`` with their QK-norm gains ``g_q``
    / ``g_k [L_a, dh]``; ``dense`` the leading layers' MLPs ``[L_d,
    ...]``, ``experts`` the others' ``[L_e, ...]``. Static: ``kinds``
    (``CONV`` / ``ATTN`` per layer), ``head_dim``, the router's
    ``top_k`` and ``routed_scale``, ``rope_theta``, ``eps``,
    ``max_seq_len`` (bounds what ``submit`` accepts) and
    ``expert_first``, the global id of the first held expert."""
    wte: jax.Array
    norm_in: jax.Array
    norm_ff: jax.Array
    g_f: jax.Array
    conv: ConvStack
    attn: AttnStack
    g_q: jax.Array
    g_k: jax.Array
    dense: MLPStack
    experts: ExpertStack
    kinds: tuple
    head_dim: int
    top_k: int
    routed_scale: float
    rope_theta: float
    eps: float
    max_seq_len: int
    expert_first: int = 0

    @property
    def vocab(self) -> int:
        return self.wte.shape[0]

    @property
    def d_model(self) -> int:
        return self.wte.shape[1]

    @property
    def n_layers(self) -> int:
        return len(self.kinds)

    @property
    def layers(self) -> tuple:
        """``(kind, index)`` per model layer: the index is the layer's
        place in its own kind's stack and in its kind's cache."""
        return layers_of(self.kinds)

    def num_params(self) -> int:
        """Parameters, the tied embedding counted once."""
        return sum(x.size for x in jax.tree_util.tree_leaves(self))

    # -- the model face (``models/face.py::ServedModel``) --------------

    def cache_spec(self, n_heads: int) -> CacheSpec:
        c = self.conv
        return CacheSpec(
            kv_layers=self.attn.wq.shape[0],
            kv_heads=self.attn.wk.shape[1] // self.head_dim,
            head_dim=self.head_dim, rec_layers=c.w_in.shape[0],
            state_row=StateRow(conv_lanes=c.conv_w.shape[2],
                               taps=c.conv_w.shape[1], rows=0, lanes=0),
            expert_layers=self.experts.w_gate.shape[0],
            n_experts=self.experts.w_gate.shape[1])

    def embed(self, tokens, positions, lookup):
        return lookup(self.wte, tokens).astype(jnp.float32)

    def norm(self, g, x):
        return rmsnorm(g, x, self.eps)

    def attn_qkv(self, i, a, positions, head_dim, use_rope):
        with jax.named_scope("attn"):
            return qkv_heads(self.attn.wq, self.attn.wk, self.attn.wv, i, a,
                             positions, head_dim, True, self.rope_theta,
                             (self.g_q[i], self.g_k[i], self.eps))

    def attn_out(self, i, y, a):
        return mm(y, self.attn.wo[i])

    def recurrent_step(self, i, a, conv, state, rows):
        """Convolution mixer ``i`` for one token of each of ``b``
        sequences, ``a [b, d]``: ``conv`` is the WHOLE store of tails
        (``decode/paged.py::RecurrentState``), of which rows ``rows
        [b]`` of layer ``i`` are advanced in place. ``state`` is None:
        the layer has no scan state."""
        y, conv = _gated_conv(self, i, a, conv, functools.partial(
            ssm.conv_step_in_place, layer=i, rows=rows))
        return y, conv, state

    def recurrent_chunk(self, i, a, tail, state):
        """Convolution mixer ``i`` over a chunk of ONE sequence: ``a
        [c, d]`` the normed residual, ``tail [K-1, d]`` what the
        sequence carries (zeros at position 0). Returns ``(out [c, d],
        tail, state)``, ``state`` None as it came."""
        y, tail = _gated_conv(self, i, a, tail, ssm.conv_chunk)
        return y, tail, state

    def recurrent_mixed(self, i, a, conv, state, rows, tail, s):
        """Convolution mixer ``i`` over a decode batch's ``len(rows)``
        rows and then ONE sequence's chunk, ``a [b + c, d]``, its
        weights read once (``state`` and ``s`` None as they came)."""
        y, (conv, tail) = _gated_conv(
            self, i, a, (conv, tail), functools.partial(
                ssm.conv_mixed, layer=i, rows=rows))
        return y, conv, state, tail, s

    def ffn_counted(self, l, h):
        first_dense = self.dense.w_gate.shape[0]
        if l < first_dense:
            return gated_mlp(self.dense, l, h), None
        with jax.named_scope("moe"):
            return moe_serve.routed(self.experts, l - first_dense, h,
                                    self.top_k, self.routed_scale,
                                    self.expert_first)

    def ffn(self, l, h):
        return self.ffn_counted(l, h)[0]

    def head(self, x):
        return mm(rmsnorm(self.g_f, x, self.eps), self.wte)


def _gated_conv(p: Lfm2MoeLMParams, i: int, a, tail, conv):
    """``W_out (C * conv(B * X))`` with ``[B; C; X] = W_in a``; ``conv(u,
    tail, w, bias) -> (v, tail)`` is the chunk's or the batch's form of
    the one convolution (``ops/ssm.py``), without a bias."""
    c = p.conv
    with jax.named_scope("conv"):
        b, gate, x = jnp.split(mm(a, c.w_in[i]), 3, axis=-1)
        v, tail = conv(b * x, tail, c.conv_w[i].astype(jnp.float32), None)
        return mm(gate * v, c.w_out[i]), tail


class Lfm2MoeSpec(NamedTuple):
    """The sizes a published ``config.json`` gives (``spec_from_config``)."""
    vocab: int
    d_model: int
    kinds: tuple
    first_dense: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_conv: int
    ffn: int
    n_experts: int
    expert_ffn: int
    top_k: int
    routed_scale: float
    rope_theta: float
    eps: float
    max_seq_len: int


def spec_from_config(config: dict) -> Lfm2MoeSpec:
    """A ``model_type: lfm2_moe`` ``config.json`` (the published keys) as
    sizes. What this file cannot serve is refused by name, never read
    as something else."""
    if config.get("model_type") != "lfm2_moe":
        raise ValueError(f"model_type {config.get('model_type')!r}: "
                         "models/lfm2_moe_lm.py serves 'lfm2_moe' only")
    if config.get("conv_bias"):
        raise ValueError("conv_bias true: the short convolution is "
                         "served without a bias only")
    rope = config.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r}: the rotary "
                         "is served unscaled ('default') only")
    if not config.get("norm_topk_prob", True):
        raise ValueError("norm_topk_prob false: the chosen weights are "
                         "served normalised only")
    if not config.get("use_expert_bias", True):
        raise ValueError("use_expert_bias false: the router is served "
                         "with its choice bias only")
    if not config.get("tie_word_embeddings", True):
        raise ValueError("the head is served tied to the embedding only")
    n = int(config["num_hidden_layers"])
    types = config["layer_types"]
    unknown = sorted(set(types) - set(KINDS))
    if unknown:
        raise ValueError(f"layer_types {unknown}: served are "
                         f"{sorted(KINDS)}")
    if len(types) != n:
        raise ValueError(f"layer_types names {len(types)} layers, "
                         f"num_hidden_layers {n}")
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    return Lfm2MoeSpec(
        vocab=int(config["vocab_size"]), d_model=d,
        kinds=tuple(KINDS[t] for t in types),
        first_dense=int(config["num_dense_layers"]), n_heads=heads,
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim") or d // heads),
        d_conv=int(config["conv_L_cache"]),
        ffn=int(config["intermediate_size"]),
        n_experts=int(config["num_experts"]),
        expert_ffn=int(config["moe_intermediate_size"]),
        top_k=int(config["num_experts_per_tok"]),
        routed_scale=float(config.get("routed_scaling_factor", 1.0)),
        rope_theta=float(rope.get("rope_theta", 10000.0)),
        eps=float(config["norm_eps"]),
        max_seq_len=int(config["max_position_embeddings"]))


@functools.partial(jax.jit, static_argnames=("spec", "dtype", "scale"))
def init_lfm2_moe_lm(key: jax.Array, spec: Lfm2MoeSpec, dtype=jnp.float32,
                     scale: float = 2e-2) -> Lfm2MoeLMParams:
    """Seeded weights, made on the device in one call: matrices ``scale
    * normal`` in ``dtype``, gains 1, the router float32 with its choice
    bias ``BIAS_SCALE * normal``, and the convolution's taps uniform in
    ``+-K**-0.5`` as ``models/hybrid_lm.py`` draws its own (at ``scale``
    the taps would pass a fiftieth of their input and the mixer would
    add nothing to the stream)."""
    s = spec
    la = sum(k == ATTN for k in s.kinds)
    lc, n_l = len(s.kinds) - la, len(s.kinds)
    ld, le = s.first_dense, n_l - s.first_dense
    d, f = s.d_model, s.expert_ffn
    hq, hkv = s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    ks = iter(jax.random.split(key, 16))

    def w(*shape, dt=dtype, sc=scale):
        return (sc * jax.random.normal(next(ks), shape,
                                       jnp.float32)).astype(dt)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    bound = s.d_conv ** -0.5
    taps = jax.random.uniform(next(ks), (lc, s.d_conv, d), jnp.float32,
                              -bound, bound).astype(dtype)
    return Lfm2MoeLMParams(
        wte=w(s.vocab, d), norm_in=ones(n_l, d), norm_ff=ones(n_l, d),
        g_f=ones(d),
        conv=ConvStack(w_in=w(lc, 3 * d, d), conv_w=taps, w_out=w(lc, d, d)),
        attn=AttnStack(wq=w(la, hq, d), wk=w(la, hkv, d), wv=w(la, hkv, d),
                       wo=w(la, d, hq)),
        g_q=ones(la, s.head_dim), g_k=ones(la, s.head_dim),
        dense=MLPStack(w_gate=w(ld, s.ffn, d), w_up=w(ld, s.ffn, d),
                       w_down=w(ld, d, s.ffn)),
        experts=ExpertStack(
            w_router=w(le, s.n_experts, d, dt=jnp.float32),
            bias=w(le, s.n_experts, dt=jnp.float32, sc=BIAS_SCALE),
            w_gate=w(le, s.n_experts, f, d), w_up=w(le, s.n_experts, f, d),
            w_down=w(le, s.n_experts, d, f)),
        kinds=s.kinds, head_dim=s.head_dim, top_k=s.top_k,
        routed_scale=s.routed_scale, rope_theta=s.rope_theta, eps=s.eps,
        max_seq_len=s.max_seq_len)
