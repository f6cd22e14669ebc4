"""A latent-attention, sparse-expert LM (``model_type: glm4_moe_lite``).

The GLM-4.7-Flash / DeepSeek-V2-Lite shape of block, served only. With
``h [T, d]`` the residual stream, RMSNorm gain-only, every matrix
``[out, in]`` and no bias anywhere:

- layer ``l``: ``h += MLA_l(rms(h; norm_in[l]))`` then ``h +=
  FFN_l(rms(h; norm_ff[l]))``. ``FFN_l`` is a dense gated SiLU MLP for
  the ``first_dense`` leading layers and the expert layer after them.
  After the last layer ``logits = rms(h; g_f) @ W_head.T``, the head
  separate from the embedding. No position is added to the embedding.
- multi-head latent attention (MLA): ``c_q = rms(W_qa a; g_q)``; per
  head ``[q_nope | q_rope] = W_qb c_q``; ``[c_kv | k_rope] = W_kva a``;
  ``c = rms(c_kv; g_kv)``; ``q_rope`` and ``k_rope`` (ONE vector shared
  by all heads) rotated by position over all their lanes at
  ``rope_theta``; per head ``k_nope = W_uk c``, ``v = W_uv c``; causal
  ``softmax([q_nope | q_rope] . [k_nope | k_rope] / sqrt(dn + dr)) v``;
  ``W_o`` over the heads' values side by side.
- what is SERVED is the same, absorbed: ``q_lat = q_nope W_uk`` (the
  latent's width), ``score_t = (q_lat . c_t + q_rope . k_rope_t) /
  sqrt(dn + dr)``, ``o_lat = sum_t p_t c_t``, ``o = W_uv o_lat``. The
  cache keeps ONE row ``[c_t | k_rope_t]`` a token a layer, normed and
  rotated before it is stored, with no head axis and no K/V pair; the
  values are the row's first ``kv_lora_rank`` lanes
  (``models/face.py::LATENT``, ``decode/paged.py``). The row is filled
  with zeros up to a multiple of ``ROW_LANES`` (576 -> 640 at the
  published widths): the chip keeps a pool row-major only where its
  rows are whole 128-lane tiles, and turns any other inside out, block
  index innermost, with two copies of the whole pool a program
  (``PERF.md`` section 6, PR 31; PR 26 met the same at 320 lanes).
- the expert layer (``ops/moe_serve.py``): a float32 sigmoid router
  with a choice-only bias, ``top_k`` of ``n_routed`` gated SiLU experts
  weighted ``routed_scale * s_k / sum_chosen s``, beside one shared
  expert every row takes; no row is dropped. The params hold the
  contiguous range ``[expert_first, expert_first + E_held)`` of every
  layer's experts (all of them unless a holder was cut out) and compute
  that range's part of the result.

Stored forms that differ from a published checkpoint's: ``W_kvb`` is
kept split per head as ``w_uk [L, H, dn, R]`` and ``w_uv [L, H, dv,
R]`` (published: one ``[H * (dn + dv), R]`` matrix, a head's ``k_nope``
rows before its ``v`` rows); the rotary pairs lane ``i`` with lane ``i
+ dr / 2`` (``models/attention.py::rope``).

Precision: as ``models/hybrid_lm.py`` — the residual stream, norms,
rotary and softmax in float32; a matrix product takes its activations
in the weights' type and accumulates in float32 (``mm``); the router is
float32 at ``highest`` whatever the weights' type.

The multi-token-prediction block a published checkpoint carries after
the last layer is not built: generation drops it.

Independently, the same equations in the naive (unabsorbed) form:
``benchmark/configs/glm_moe_lm_reference.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops import moe_serve
from ..ops.moe_serve import ExpertStack, holder  # noqa: F401  (the family's names)
from .attention import rope
from .face import LATENT, CacheSpec, MLPStack, gated_mlp, mm, rmsnorm


ROW_LANES = 128      # a stored row is whole tiles of this many lanes


class MLAStack(NamedTuple):
    """The latent-attention mixers, stacked ``[L, ...]``. ``Rq`` / ``R``
    the query's and the cache's latent ranks, ``dn`` / ``dr`` / ``dv``
    a head's no-position, rotary and value widths."""
    w_qa: jax.Array      # [L, Rq, d]
    g_q: jax.Array       # [L, Rq]
    w_qb: jax.Array      # [L, H*(dn+dr), Rq]  a head's nope before rope
    w_kva: jax.Array     # [L, R+dr, d]        -> [c_kv | k_rope]
    g_kv: jax.Array      # [L, R]
    w_uk: jax.Array      # [L, H, dn, R]
    w_uv: jax.Array      # [L, H, dv, R]
    w_o: jax.Array       # [L, d, H*dv]


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["wte", "w_head", "norm_in", "norm_ff", "g_f", "mla",
                 "dense", "shared", "experts"],
    meta_fields=["top_k", "routed_scale", "rope_theta", "eps",
                 "max_seq_len", "expert_first"])
@dataclasses.dataclass(frozen=True)
class MlaMoeLMParams:
    """``wte`` / ``w_head [V, d]``, ``norm_in`` / ``norm_ff [L, d]``,
    ``g_f [d]``; ``dense`` the leading layers' MLPs ``[L_d, ...]``,
    ``shared`` and ``experts`` the expert layers' ``[L_e, ...]``.
    Static: the router's ``top_k`` and ``routed_scale``, ``rope_theta``,
    ``eps``, ``max_seq_len`` (bounds what ``submit`` accepts) and
    ``expert_first``, the global id of the first held expert."""
    wte: jax.Array
    w_head: jax.Array
    norm_in: jax.Array
    norm_ff: jax.Array
    g_f: jax.Array
    mla: MLAStack
    dense: MLPStack
    shared: MLPStack
    experts: ExpertStack
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
        return self.norm_in.shape[0]

    @property
    def layers(self) -> tuple:
        return tuple((LATENT, l) for l in range(self.n_layers))

    def num_params(self) -> int:
        return sum(x.size for x in jax.tree_util.tree_leaves(self))

    # -- the model face (``models/face.py::ServedModel``) --------------

    def cache_spec(self, n_heads: int) -> CacheSpec:
        m = self.mla
        return CacheSpec(
            kv_layers=self.n_layers, kv_heads=1,
            head_dim=-(-m.w_kva.shape[1] // ROW_LANES) * ROW_LANES,
            latent_rank=m.g_kv.shape[1],
            expert_layers=self.experts.w_gate.shape[0],
            n_experts=self.experts.w_gate.shape[1])

    def embed(self, tokens, positions, lookup):
        return lookup(self.wte, tokens).astype(jnp.float32)

    def norm(self, g, x):
        return rmsnorm(g, x, self.eps)

    def latent_qrow(self, i, a, positions):
        """``a [N, d]`` at ``positions [N]`` -> the absorbed query ``[N,
        H, m]`` (scaled by ``1/sqrt(dn+dr)``, the head's own width) and
        the row to store ``[N, m]``: ``R + dr`` lanes and zeros up to
        ``m``, a multiple of ``ROW_LANES``."""
        m = self.mla
        n = a.shape[0]
        h, dn, r = m.w_uk.shape[1:]
        dr = m.w_kva.shape[1] - r
        q = mm(rmsnorm(m.g_q[i], mm(a, m.w_qa[i]), self.eps),
               m.w_qb[i]).astype(jnp.float32).reshape(n, h, dn + dr)
        ckr = mm(a, m.w_kva[i]).astype(jnp.float32)
        c = rmsnorm(m.g_kv[i], ckr[:, :r], self.eps)
        # one position a row: rope's [T, dh] with T = 1
        rot = jax.vmap(lambda x, pos: rope(x[..., None, :], pos[None],
                                           self.rope_theta)[..., 0, :])
        q_rope = rot(q[:, :, dn:], positions)
        k_rope = rot(ckr[:, r:], positions)
        q_lat = jnp.einsum("nhd,hdr->nhr", q[:, :, :dn].astype(m.w_uk.dtype),
                           m.w_uk[i], preferred_element_type=jnp.float32)
        fill = -(r + dr) % ROW_LANES
        q_row = jnp.concatenate(
            [q_lat, q_rope, jnp.zeros((n, h, fill), jnp.float32)],
            -1) / math.sqrt(dn + dr)
        return q_row, jnp.concatenate(
            [c, k_rope, jnp.zeros((n, fill), jnp.float32)], -1)

    def latent_out(self, i, o):
        """``o [N, H, R]``, the latent result -> ``[N, d]``."""
        m = self.mla
        y = jnp.einsum("nhr,hvr->nhv", o.astype(m.w_uv.dtype), m.w_uv[i],
                       preferred_element_type=jnp.float32)
        return mm(y.reshape(o.shape[0], -1), m.w_o[i])

    def ffn_counted(self, l, h):
        first_dense = self.dense.w_gate.shape[0]
        if l < first_dense:
            return gated_mlp(self.dense, l, h), None
        x = l - first_dense
        with jax.named_scope("moe"):
            y, rows = moe_serve.routed(self.experts, x, h, self.top_k,
                                       self.routed_scale, self.expert_first)
            return y + gated_mlp(self.shared, x, h), rows

    def ffn(self, l, h):
        return self.ffn_counted(l, h)[0]

    def head(self, x):
        return mm(rmsnorm(self.g_f, x, self.eps), self.w_head)


class MlaMoeSpec(NamedTuple):
    """The sizes a published ``config.json`` gives (``spec_from_config``)."""
    vocab: int
    d_model: int
    n_layers: int
    first_dense: int
    n_heads: int
    q_rank: int
    kv_rank: int
    d_nope: int
    d_rope: int
    d_v: int
    ffn: int
    n_routed: int
    n_shared: int
    expert_ffn: int
    top_k: int
    routed_scale: float
    rope_theta: float
    eps: float
    max_seq_len: int


def spec_from_config(config: dict) -> MlaMoeSpec:
    """A ``model_type: glm4_moe_lite`` ``config.json`` (the published
    keys) as sizes. What this file cannot serve is refused by name,
    never read as something else."""
    if config.get("model_type") != "glm4_moe_lite":
        raise ValueError(f"model_type {config.get('model_type')!r}: "
                         "models/mla_moe_lm.py serves 'glm4_moe_lite' "
                         "only")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {config['hidden_act']!r}: the "
                         "gated MLPs are SiLU only")
    if config.get("attention_bias"):
        raise ValueError("attention_bias: no projection has a bias")
    if config.get("rope_scaling") is not None:
        raise ValueError("rope_scaling: the rotary is served unscaled "
                         "only")
    if float(config.get("partial_rotary_factor", 1)) != 1:
        raise ValueError("partial_rotary_factor: every lane of the "
                         "rotary part is rotated")
    if config.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError(f"topk_method {config['topk_method']!r}: the "
                         "router is sigmoid scores with a choice bias "
                         "('noaux_tc') only")
    if (int(config.get("n_group", 1)) != 1
            or int(config.get("topk_group", 1)) != 1):
        raise ValueError("n_group / topk_group: the router has no group "
                         "step")
    if not config.get("norm_topk_prob", True):
        raise ValueError("norm_topk_prob false: the chosen weights are "
                         "served normalised only")
    if config.get("tie_word_embeddings", False):
        raise ValueError("the head is served untied only")
    if int(config["num_key_value_heads"]) != int(
            config["num_attention_heads"]):
        raise ValueError("num_key_value_heads differs from "
                         "num_attention_heads: every head expands its "
                         "own keys and values from the one latent")
    if not config.get("q_lora_rank"):
        raise ValueError("q_lora_rank: the query is served through its "
                         "latent only")
    return MlaMoeSpec(
        vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        first_dense=int(config["first_k_dense_replace"]),
        n_heads=int(config["num_attention_heads"]),
        q_rank=int(config["q_lora_rank"]),
        kv_rank=int(config["kv_lora_rank"]),
        d_nope=int(config["qk_nope_head_dim"]),
        d_rope=int(config["qk_rope_head_dim"]),
        d_v=int(config["v_head_dim"]),
        ffn=int(config["intermediate_size"]),
        n_routed=int(config["n_routed_experts"]),
        n_shared=int(config["n_shared_experts"]),
        expert_ffn=int(config["moe_intermediate_size"]),
        top_k=int(config["num_experts_per_tok"]),
        routed_scale=float(config["routed_scaling_factor"]),
        rope_theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["max_position_embeddings"]))


@functools.partial(jax.jit, static_argnames=("spec", "dtype", "scale"))
def init_mla_moe_lm(key: jax.Array, spec: MlaMoeSpec, dtype=jnp.float32,
                    scale: float = 2e-2) -> MlaMoeLMParams:
    """Seeded weights, made on the device in one call: matrices ``scale
    * normal`` in ``dtype``, gains 1, the router float32, and the
    router's choice bias drawn ``0.1 * normal`` — small beside the
    scores' spread and not zero, so that it moves choices (a trained
    model's balances its experts' load)."""
    s = spec
    le, ld = s.n_layers - s.first_dense, s.first_dense
    d, h = s.d_model, s.n_heads
    ks = iter(jax.random.split(key, 24))

    def w(*shape, dt=dtype, sc=scale):
        return (sc * jax.random.normal(next(ks), shape,
                                       jnp.float32)).astype(dt)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    def mlps(n, f):
        return MLPStack(w_gate=w(n, f, d), w_up=w(n, f, d),
                        w_down=w(n, d, f))

    f = s.expert_ffn
    return MlaMoeLMParams(
        wte=w(s.vocab, d), w_head=w(s.vocab, d),
        norm_in=ones(s.n_layers, d), norm_ff=ones(s.n_layers, d),
        g_f=ones(d),
        mla=MLAStack(
            w_qa=w(s.n_layers, s.q_rank, d), g_q=ones(s.n_layers, s.q_rank),
            w_qb=w(s.n_layers, h * (s.d_nope + s.d_rope), s.q_rank),
            w_kva=w(s.n_layers, s.kv_rank + s.d_rope, d),
            g_kv=ones(s.n_layers, s.kv_rank),
            w_uk=w(s.n_layers, h, s.d_nope, s.kv_rank),
            w_uv=w(s.n_layers, h, s.d_v, s.kv_rank),
            w_o=w(s.n_layers, d, h * s.d_v)),
        dense=mlps(ld, s.ffn), shared=mlps(le, s.n_shared * f),
        experts=ExpertStack(
            w_router=w(le, s.n_routed, d, dt=jnp.float32),
            bias=w(le, s.n_routed, dt=jnp.float32, sc=0.1),
            w_gate=w(le, s.n_routed, f, d), w_up=w(le, s.n_routed, f, d),
            w_down=w(le, s.n_routed, d, f)),
        top_k=s.top_k, routed_scale=s.routed_scale,
        rope_theta=s.rope_theta, eps=s.eps, max_seq_len=s.max_seq_len)
