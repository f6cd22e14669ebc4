"""A window-and-full-attention, gated-head, sparse-expert LM
(``model_type: laguna``).

The Laguna family's block, served only. With ``x [T, d]`` the residual
stream, RMSNorm gain-only, every matrix ``[out, in]`` and no bias
anywhere:

- layer ``l``: ``h = x + attn_l(rms(x; norm_in[l]))`` then ``x = h +
  ffn_l(rms(h; norm_ff[l]))``. After the last layer ``logits = rms(x;
  g_f) @ w_head.T``, the head untied. No position is added to the
  embedding.
- ``attn_l`` is grouped-query attention of the layer's OWN number of
  query heads (``num_attention_heads_per_layer``) over ``H_kv`` KV heads
  of ``dh`` lanes, as ``layer_types[l]`` says: a ``full_attention``
  layer sees every earlier position, a ``sliding_attention`` layer the
  last ``sliding_window`` ones (the current token counts). Each type
  has its own rotary (``rope_parameters`` by layer type:
  ``models/attention.py::Rotary`` — the first ``partial_rotary_factor``
  of a head's lanes rotated, paired half-split among themselves;
  ``rope_type: yarn`` scales the slow pairs and multiplies ``cos`` and
  ``sin`` by ``attention_factor``). ``q = W_q a``, ``k, v = W_k a, W_v
  a``; ``s = q k^T / sqrt(dh)``; ``o_h = softmax(s_h) v``; every head's
  output is scaled by a gate of its own, ``o_h <- sigmoid(W_g a)_h *
  o_h`` (``gating: per-head``; ``models/face.py::head_gate``); ``W_o``.
  The two types do not stack (their head counts differ): ``full`` and
  ``window`` are two ``AttnStack``s, each with its gate matrix, and the
  engine keeps the second kind's K/V in a pool of its own
  (``models/face.py::WINDOW``).
- ``ffn_l`` of a ``dense`` layer (``mlp_layer_types``): the gated SiLU
  MLP. Of a ``sparse`` layer the expert layer (``ops/moe_serve.py``): a
  float32 router over ALL ``router_experts`` experts with SOFTMAX
  scores and no choice bias, ``top_k`` of them weighted
  ``routed_scale * s_k / sum_chosen s``, beside ONE shared expert that
  every row passes, ungated; no row is dropped. The params hold the
  contiguous range ``[expert_first, expert_first + E_held)`` of every
  layer's experts (``num_experts`` of the configuration: the chip's
  share of an expert-parallel deployment, or all of them) and compute
  that range's part of the result.

Precision: as ``models/lfm2_moe_lm.py`` — the residual stream, norms,
rotary, softmax and the gates in float32; a matrix product takes its
activations in the weights' type and accumulates in float32 (``mm``);
the router is float32 at ``highest`` whatever the weights' type.

Independently, the same equations: ``benchmark/configs/
laguna_lm_reference.py``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops import moe_serve
from ..ops.moe_serve import ExpertStack, holder  # noqa: F401  (the family's names)
from .attention import Rotary
from .face import (ATTN, WINDOW, AttnStack, CacheSpec, MLPStack, gated_mlp,
                   head_gate, layers_of, mm, qkv_heads, rmsnorm)

# config.json's ``layer_types`` -> the engine's layer kinds
KINDS = {"full_attention": ATTN, "sliding_attention": WINDOW}
SCORE = "softmax"       # the family's router (``moe_serve.SCORES``)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["wte", "w_head", "norm_in", "norm_ff", "g_f", "full",
                 "wg_full", "window", "wg_window", "dense", "shared",
                 "experts"],
    meta_fields=["kinds", "dense_layers", "head_dim", "sliding_window",
                 "rot_full", "rot_window", "top_k", "routed_scale", "eps",
                 "max_seq_len", "expert_first"])
@dataclasses.dataclass(frozen=True)
class LagunaLMParams:
    """``wte`` / ``w_head [V, d]``, ``norm_in`` / ``norm_ff [L, d]``,
    ``g_f [d]``; ``full`` the full-attention mixers ``[L_f, ...]`` with
    their gates ``wg_full [L_f, H_f, d]``, ``window`` the
    sliding-window mixers ``[L_w, ...]`` with ``wg_window [L_w, H_w,
    d]``; ``dense`` the dense layers' MLPs ``[L_d, ...]``, ``shared``
    and ``experts`` the sparse layers' ``[L_e, ...]``. Static: ``kinds``
    (``ATTN`` / ``WINDOW`` per layer), ``dense_layers`` (which layers'
    FFN is dense), ``head_dim``, ``sliding_window``, the two rotaries,
    the router's ``top_k`` and ``routed_scale``, ``eps``,
    ``max_seq_len`` (bounds what ``submit`` accepts) and
    ``expert_first``, the global id of the first held expert."""
    wte: jax.Array
    w_head: jax.Array
    norm_in: jax.Array
    norm_ff: jax.Array
    g_f: jax.Array
    full: AttnStack
    wg_full: jax.Array
    window: AttnStack
    wg_window: jax.Array
    dense: MLPStack
    shared: MLPStack
    experts: ExpertStack
    kinds: tuple
    dense_layers: tuple
    head_dim: int
    sliding_window: int
    rot_full: Rotary
    rot_window: Rotary
    top_k: int
    routed_scale: float
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
        return sum(x.size for x in jax.tree_util.tree_leaves(self))

    # -- the model face (``models/face.py::ServedModel``) --------------

    def cache_spec(self, n_heads: int) -> CacheSpec:
        return CacheSpec(
            kv_layers=self.full.wq.shape[0],
            kv_heads=self.full.wk.shape[1] // self.head_dim,
            head_dim=self.head_dim,
            expert_layers=self.experts.w_gate.shape[0],
            n_experts=self.experts.w_gate.shape[1],
            win_layers=self.window.wq.shape[0], window=self.sliding_window)

    def embed(self, tokens, positions, lookup):
        return lookup(self.wte, tokens).astype(jnp.float32)

    def norm(self, g, x):
        return rmsnorm(g, x, self.eps)

    def attn_qkv(self, i, a, positions, head_dim, use_rope):
        with jax.named_scope("attn.full"):
            return _qkv(self.full, i, a, positions, self.head_dim,
                        self.rot_full)

    def attn_out(self, i, y, a):
        with jax.named_scope("attn.full"):
            return mm(head_gate(self.wg_full, i, a, y, self.head_dim),
                      self.full.wo[i])

    def window_qkv(self, i, a, positions):
        with jax.named_scope("attn.window"):
            return _qkv(self.window, i, a, positions, self.head_dim,
                        self.rot_window)

    def window_sink(self, i):
        return None             # the family's softmax has no sink

    def window_out(self, i, y, a):
        with jax.named_scope("attn.window"):
            return mm(head_gate(self.wg_window, i, a, y, self.head_dim),
                      self.window.wo[i])

    def ffn_counted(self, l, h):
        if l in self.dense_layers:
            return gated_mlp(self.dense, self.dense_layers.index(l), h), None
        x = l - sum(d < l for d in self.dense_layers)
        with jax.named_scope("moe"):
            y, rows = moe_serve.routed(self.experts, x, h, self.top_k,
                                       self.routed_scale, self.expert_first,
                                       SCORE)
            return y + gated_mlp(self.shared, x, h), rows

    def ffn(self, l, h):
        return self.ffn_counted(l, h)[0]

    def head(self, x):
        return mm(rmsnorm(self.g_f, x, self.eps), self.w_head)


def _qkv(stack: AttnStack, i, a, positions, head_dim, rotary):
    return qkv_heads(stack.wq, stack.wk, stack.wv, i, a, positions,
                     head_dim, True, rotary=rotary)


class LagunaSpec(NamedTuple):
    """The sizes a published ``config.json`` gives (``spec_from_config``)."""
    vocab: int
    d_model: int
    kinds: tuple
    heads: tuple            # query heads, per layer
    dense_layers: tuple
    n_kv_heads: int
    head_dim: int
    sliding_window: int
    rot_full: Rotary
    rot_window: Rotary
    ffn: int
    n_routed: int           # the router's width: every published expert
    n_held: int             # ... of which this chip holds
    expert_first: int
    expert_ffn: int
    shared_ffn: int
    top_k: int
    routed_scale: float
    eps: float
    max_seq_len: int


def spec_from_config(config: dict) -> LagunaSpec:
    """A ``model_type: laguna`` ``config.json`` (the published keys) as
    sizes. ``num_experts`` is how many experts of a layer are HELD
    here; where that is a share of them, ``router_experts`` beside it
    states the published count the router scores (and ``expert_first``
    the first held). What this file cannot serve is refused by name,
    never read as something else."""
    if config.get("model_type") != "laguna":
        raise ValueError(f"model_type {config.get('model_type')!r}: "
                         "models/laguna_lm.py serves 'laguna' only")
    if config.get("attention_bias"):
        raise ValueError("attention_bias: no projection has a bias")
    if config.get("gating", "per-head") not in ("per-head", True):
        raise ValueError(f"gating {config['gating']!r}: the attention "
                         "output is served with its per-head gate only")
    if set(config.get("gating_types", ())) - {"per_head"}:
        raise ValueError("gating_types: every layer's gate is served "
                         "'per_head' only")
    if float(config.get("moe_router_logit_softcapping", 0) or 0):
        raise ValueError("moe_router_logit_softcapping: the router's "
                         "logits are served uncapped (0) only")
    if config.get("moe_apply_router_weight_on_input"):
        raise ValueError("moe_apply_router_weight_on_input: the chosen "
                         "weights scale the experts' outputs only")
    if not config.get("norm_topk_prob", True):
        raise ValueError("norm_topk_prob false: the chosen weights are "
                         "served normalised only")
    if config.get("tie_word_embeddings", False):
        raise ValueError("the head is served untied only")
    n = int(config["num_hidden_layers"])
    types, mlps = config["layer_types"], config["mlp_layer_types"]
    heads = config["num_attention_heads_per_layer"]
    for name, got in (("layer_types", types), ("mlp_layer_types", mlps),
                      ("num_attention_heads_per_layer", heads)):
        if len(got) != n:
            raise ValueError(f"{name} names {len(got)} layers, "
                             f"num_hidden_layers {n}")
    unknown = sorted(set(types) - set(KINDS))
    if unknown:
        raise ValueError(f"layer_types {unknown}: served are "
                         f"{sorted(KINDS)}")
    if set(mlps) - {"dense", "sparse"}:
        raise ValueError(f"mlp_layer_types {sorted(set(mlps))}: served "
                         "are ['dense', 'sparse']")
    if not (ATTN in map(KINDS.get, types) and WINDOW in map(KINDS.get,
                                                            types)):
        raise ValueError("layer_types: served with at least one layer "
                         "of each type only")
    for t in KINDS:
        if len({h for h, k in zip(heads, types) if k == t}) > 1:
            raise ValueError("num_attention_heads_per_layer: the layers "
                             f"of type {t!r} are served with one head "
                             "count only (they are one weight stack)")
    if all(m == "dense" for m in mlps) or all(m == "sparse" for m in mlps):
        raise ValueError("mlp_layer_types: served with at least one "
                         "dense and one sparse layer only")
    rope = config["rope_parameters"]
    n_held = int(config["num_experts"])
    return LagunaSpec(
        vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        kinds=tuple(KINDS[t] for t in types),
        heads=tuple(int(h) for h in heads),
        dense_layers=tuple(l for l, m in enumerate(mlps) if m == "dense"),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        sliding_window=int(config["sliding_window"]),
        rot_full=Rotary.from_config(rope["full_attention"]),
        rot_window=Rotary.from_config(rope["sliding_attention"]),
        ffn=int(config["intermediate_size"]),
        n_routed=int(config.get("router_experts", n_held)), n_held=n_held,
        expert_first=int(config.get("expert_first", 0)),
        expert_ffn=int(config["moe_intermediate_size"]),
        shared_ffn=int(config["shared_expert_intermediate_size"]),
        top_k=int(config["num_experts_per_tok"]),
        routed_scale=float(config.get("moe_routed_scaling_factor", 1.0)),
        eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["max_position_embeddings"]))


@functools.partial(jax.jit, static_argnames=("spec", "dtype", "scale"))
def init_laguna_lm(key: jax.Array, spec: LagunaSpec, dtype=jnp.float32,
                   scale: float = 2e-2) -> LagunaLMParams:
    """Seeded weights, made on the device in one call: matrices ``scale
    * normal`` in ``dtype``, gains 1, the router float32 over all
    ``n_routed`` experts, no choice bias, and the ``n_held`` experts
    this chip holds."""
    s = spec
    n_l, d, dh = len(s.kinds), s.d_model, s.head_dim
    ld = len(s.dense_layers)
    le = n_l - ld
    stacks = {k: [h for h, t in zip(s.heads, s.kinds) if t == k]
              for k in (ATTN, WINDOW)}
    hkv = s.n_kv_heads * dh
    ks = iter(jax.random.split(key, 32))

    def w(*shape, dt=dtype):
        return (scale * jax.random.normal(next(ks), shape,
                                          jnp.float32)).astype(dt)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    def attn(kind):
        n, h = len(stacks[kind]), stacks[kind][0]
        return (AttnStack(wq=w(n, h * dh, d), wk=w(n, hkv, d),
                          wv=w(n, hkv, d), wo=w(n, d, h * dh)),
                w(n, h, d))

    def mlps(n, f):
        return MLPStack(w_gate=w(n, f, d), w_up=w(n, f, d),
                        w_down=w(n, d, f))

    full, wg_full = attn(ATTN)
    window, wg_window = attn(WINDOW)
    f = s.expert_ffn
    return LagunaLMParams(
        wte=w(s.vocab, d), w_head=w(s.vocab, d), norm_in=ones(n_l, d),
        norm_ff=ones(n_l, d), g_f=ones(d), full=full, wg_full=wg_full,
        window=window, wg_window=wg_window, dense=mlps(ld, s.ffn),
        shared=mlps(le, s.shared_ffn),
        experts=ExpertStack(
            w_router=w(le, s.n_routed, d, dt=jnp.float32), bias=None,
            w_gate=w(le, s.n_held, f, d), w_up=w(le, s.n_held, f, d),
            w_down=w(le, s.n_held, d, f)),
        kinds=s.kinds, dense_layers=s.dense_layers, head_dim=dh,
        sliding_window=s.sliding_window, rot_full=s.rot_full,
        rot_window=s.rot_window, top_k=s.top_k,
        routed_scale=s.routed_scale, eps=s.eps, max_seq_len=s.max_seq_len,
        expert_first=s.expert_first)
