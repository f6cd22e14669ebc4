"""A window-and-full-attention, sink-softmax, sparse-expert LM whose K
and V rows differ in width (``model_type: mimo_v2_flash``).

The MiMo-V2-Flash block, served only. With ``x [T, d]`` the residual
stream, RMSNorm gain-only at ``layernorm_epsilon``, every matrix ``[out,
in]`` and no bias anywhere:

- layer ``l``: ``h = x + attn_l(rms(x; norm_in[l]))`` then ``x = h +
  ffn_l(rms(h; norm_ff[l]))``. After the last layer ``logits = rms(x;
  g_f) @ w_head.T``, the head untied. No position is added to the
  embedding.
- ``attn_l``, as ``hybrid_layer_pattern[l]`` says (0 full, 1 sliding
  window): ``H`` query heads over the layer KIND's own number of KV
  heads (``num_key_value_heads`` on a full layer,
  ``swa_num_key_value_heads`` on a window layer); a query and a key
  head have ``dk = head_dim`` lanes, a value head ``dv = v_head_dim``
  (192 and 128 as published), so ``W_q [H*dk, d]``, ``W_k [H_kv*dk,
  d]``, ``W_v [H_kv*dv, d]``, ``W_o [d, H*dv]``. Rotary on the FIRST
  ``int(dk * partial_rotary_factor)`` lanes of every ``q`` and ``k``
  head, paired half-split among themselves, the rest pass
  (``models/attention.py::Rotary``), at the base ``rope_theta`` on a
  full layer and ``swa_rope_theta`` on a window one. ``s_j = q . k_j /
  sqrt(dk)``. A full layer: causal, ``p = softmax(s)``. A window layer:
  the keys ``p - window + 1 .. p`` (the current token counts), and head
  ``h``'s learned scalar ``sink_h`` joins the DENOMINATOR and nothing
  else: ``p_j = exp(s_j - m) / (exp(sink_h - m) + sum_j' exp(s_j' -
  m))``, ``m = max(sink_h, max_j s_j)``; the sink has no value row
  (``add_swa_attention_sink_bias``). ``o = sum_j p_j v_j`` with ``v_j =
  attention_value_scale * W_v a_j``: the scale is applied to the
  projected values before they are cached (any placement is the same
  function up to rounding). The two kinds do not stack (their KV head
  counts differ): ``full`` and ``window`` are two ``AttnStack``s, and
  the engine keeps the second kind's K/V in a pool of its own, in a row
  of its own (``models/face.py::WINDOW``, ``CacheSpec.win_row``).
- ``ffn_l`` of a dense layer (``moe_layer_freq[l]`` 0): the gated SiLU
  MLP. Of an expert layer (``ops/moe_serve.py``): ``sc = sigmoid(W_r
  a)`` over ALL ``router_experts`` experts in float32; the ``top_k``
  largest of ``sc + bias`` chosen (``topk_method: noaux_tc`` with one
  group: the bias moves the choice, never the weight); weights
  ``routed_scale * sc_chosen / sum(sc_chosen)`` (``norm_topk_prob``;
  ``routed_scaling_factor`` null is 1); no shared expert; no row is
  dropped. The params hold the contiguous range ``[expert_first,
  expert_first + E_held)`` of every layer's experts (``n_routed_experts``
  of the configuration: the chip's share of an expert-parallel
  deployment, or all of them) and compute that range's part.

Precision: as ``models/laguna_lm.py`` — the residual stream, norms,
rotary, softmax and the sinks in float32; a matrix product takes its
activations in the weights' type and accumulates in float32 (``mm``);
the router is float32 at ``highest`` whatever the weights' type.

Independently, the same equations: ``benchmark/configs/
mimo_v2_flash_lm_reference.py``.
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
from .attention import Rotary
from .face import (ATTN, WINDOW, AttnStack, CacheSpec, KVRow, MLPStack,
                   gated_mlp, layers_of, mm, qkv_heads, rmsnorm)

# config.json's ``hybrid_layer_pattern`` -> the engine's layer kinds
KINDS = {0: ATTN, 1: WINDOW}
SCORE = "sigmoid"       # the family's router (``moe_serve.SCORES``)
# the seeded choice bias, ``BIAS_SCALE * normal``: LFM2's draw, which
# moves choices where two scores nearly tie and leaves the load even
# (``models/lfm2_moe_lm.py``; GLM's 0.1 concentrated its cell's load)
BIAS_SCALE = 0.01
# the seeded sinks, ``SINK_MEAN + SINK_SPREAD * normal``: at seeded
# weights a window layer's scores are near 0, so a sink near ``ln 32``
# holds ``32 / (32 + 128)``, a fifth, of a full window's mass — a
# program that leaves the sink out is then far from the reference
SINK_MEAN = math.log(32.0)
SINK_SPREAD = 0.5


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["wte", "w_head", "norm_in", "norm_ff", "g_f", "full",
                 "window", "sinks", "dense", "experts"],
    meta_fields=["kinds", "dense_layers", "head_dim", "v_head_dim",
                 "sliding_window", "rot_full", "rot_window", "value_scale",
                 "top_k", "routed_scale", "eps", "max_seq_len",
                 "expert_first"])
@dataclasses.dataclass(frozen=True)
class MimoV2FlashLMParams:
    """``wte`` / ``w_head [V, d]``, ``norm_in`` / ``norm_ff [L, d]``,
    ``g_f [d]``; ``full`` the full-attention mixers ``[L_f, ...]``,
    ``window`` the sliding-window mixers ``[L_w, ...]`` with their
    sinks ``sinks [L_w, H]``; ``dense`` the dense layers' MLPs ``[L_d,
    ...]``, ``experts`` the expert layers' ``[L_e, ...]`` (with the
    choice bias). Static: ``kinds`` (``ATTN`` / ``WINDOW`` per layer),
    ``dense_layers`` (which layers' FFN is dense), ``head_dim`` (a
    query's and a key's lanes), ``v_head_dim`` (a value's),
    ``sliding_window``, the two rotaries, ``value_scale``, the router's
    ``top_k`` and ``routed_scale``, ``eps``, ``max_seq_len`` (bounds
    what ``submit`` accepts) and ``expert_first``, the global id of the
    first held expert."""
    wte: jax.Array
    w_head: jax.Array
    norm_in: jax.Array
    norm_ff: jax.Array
    g_f: jax.Array
    full: AttnStack
    window: AttnStack
    sinks: jax.Array
    dense: MLPStack
    experts: ExpertStack
    kinds: tuple
    dense_layers: tuple
    head_dim: int
    v_head_dim: int
    sliding_window: int
    rot_full: Rotary
    rot_window: Rotary
    value_scale: float
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
        dk, dv = self.head_dim, self.v_head_dim
        return CacheSpec(
            kv_layers=self.full.wq.shape[0],
            kv_heads=self.full.wk.shape[1] // dk, head_dim=dk,
            expert_layers=self.experts.w_gate.shape[0],
            n_experts=self.experts.w_gate.shape[1],
            win_layers=self.window.wq.shape[0], window=self.sliding_window,
            v_head_dim=dv,
            win_row=KVRow(self.window.wk.shape[1] // dk, dk, dv))

    def embed(self, tokens, positions, lookup):
        return lookup(self.wte, tokens).astype(jnp.float32)

    def norm(self, g, x):
        return rmsnorm(g, x, self.eps)

    def attn_qkv(self, i, a, positions, head_dim, use_rope):
        with jax.named_scope("attn.full"):
            return _qkv(self, self.full, i, a, positions, self.rot_full)

    def attn_out(self, i, y, a):
        with jax.named_scope("attn.full"):
            return mm(y, self.full.wo[i])

    def window_qkv(self, i, a, positions):
        with jax.named_scope("attn.window"):
            return _qkv(self, self.window, i, a, positions, self.rot_window)

    def window_sink(self, i):
        return self.sinks[i].astype(jnp.float32)

    def window_out(self, i, y, a):
        with jax.named_scope("attn.window"):
            return mm(y, self.window.wo[i])

    def ffn_counted(self, l, h):
        if l in self.dense_layers:
            return gated_mlp(self.dense, self.dense_layers.index(l), h), None
        x = l - sum(d < l for d in self.dense_layers)
        with jax.named_scope("moe"):
            return moe_serve.routed(self.experts, x, h, self.top_k,
                                    self.routed_scale, self.expert_first,
                                    SCORE)

    def ffn(self, l, h):
        return self.ffn_counted(l, h)[0]

    def head(self, x):
        return mm(rmsnorm(self.g_f, x, self.eps), self.w_head)


def _qkv(p: MimoV2FlashLMParams, stack: AttnStack, i, a, positions, rotary):
    """``q, k`` of ``head_dim`` lanes a head, rotated; ``v`` of
    ``v_head_dim``, scaled as it is cached."""
    q, k, v = qkv_heads(stack.wq, stack.wk, stack.wv, i, a, positions,
                        p.head_dim, True, rotary=rotary,
                        v_head_dim=p.v_head_dim)
    return q, k, v * p.value_scale


class MimoV2FlashSpec(NamedTuple):
    """The sizes a published ``config.json`` gives (``spec_from_config``)."""
    vocab: int
    d_model: int
    kinds: tuple
    dense_layers: tuple
    heads: int              # query heads, both kinds
    kv_full: int
    kv_window: int
    head_dim: int
    v_head_dim: int
    sliding_window: int
    rot_full: Rotary
    rot_window: Rotary
    value_scale: float
    ffn: int
    n_routed: int           # the router's width: every published expert
    n_held: int             # ... of which this chip holds
    expert_first: int
    expert_ffn: int
    top_k: int
    routed_scale: float
    eps: float
    max_seq_len: int


def spec_from_config(config: dict) -> MimoV2FlashSpec:
    """A ``model_type: mimo_v2_flash`` ``config.json`` (the published
    keys) as sizes. ``n_routed_experts`` is how many experts of a layer
    are HELD here; where that is a share of them, ``router_experts``
    beside it states the published count the router scores (and
    ``expert_first`` the first held). What this file cannot serve is
    refused by name, never read as something else."""
    if config.get("model_type") != "mimo_v2_flash":
        raise ValueError(f"model_type {config.get('model_type')!r}: "
                         "models/mimo_v2_flash_lm.py serves "
                         "'mimo_v2_flash' only")
    if config.get("attention_bias"):
        raise ValueError("attention_bias: no projection has a bias")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {config['hidden_act']!r}: the MLPs "
                         "are served gated with 'silu' only")
    if not config.get("add_swa_attention_sink_bias", False):
        raise ValueError("add_swa_attention_sink_bias false: a window "
                         "layer's softmax is served with its sink only")
    if config.get("add_full_attention_sink_bias", False):
        raise ValueError("add_full_attention_sink_bias true: a full "
                         "layer's softmax is served without a sink only")
    if config.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError(f"scoring_func {config['scoring_func']!r}: the "
                         "router is served with 'sigmoid' scores only")
    if config.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError(f"topk_method {config['topk_method']!r}: the "
                         "choice is served as 'noaux_tc' only")
    if int(config.get("n_group", 1)) != 1 or int(
            config.get("topk_group", 1)) != 1:
        raise ValueError("n_group / topk_group: the choice is served "
                         "over one group of all experts only")
    if not config.get("norm_topk_prob", True):
        raise ValueError("norm_topk_prob false: the chosen weights are "
                         "served normalised only")
    if config.get("n_shared_experts"):
        raise ValueError("n_shared_experts: served without a shared "
                         "expert only")
    if config.get("tie_word_embeddings", False):
        raise ValueError("the head is served untied only")
    scaling = config.get("rope_scaling") or {}
    if scaling.get("rope_type", scaling.get("type", "default")) != "default":
        raise ValueError("rope_scaling: the rotary is served as "
                         "'default' only")
    n = int(config["num_hidden_layers"])
    pattern, freq = config["hybrid_layer_pattern"], config["moe_layer_freq"]
    for name, got in (("hybrid_layer_pattern", pattern),
                      ("moe_layer_freq", freq)):
        if len(got) != n:
            raise ValueError(f"{name} names {len(got)} layers, "
                             f"num_hidden_layers {n}")
    unknown = sorted(set(pattern) - set(KINDS))
    if unknown:
        raise ValueError(f"hybrid_layer_pattern {unknown}: served are "
                         "0 (full) and 1 (sliding window)")
    if set(freq) - {0, 1}:
        raise ValueError(f"moe_layer_freq {sorted(set(freq))}: served are "
                         "0 (dense) and 1 (experts)")
    if len(set(pattern)) < 2:
        raise ValueError("hybrid_layer_pattern: served with at least one "
                         "layer of each kind only")
    if len(set(freq)) < 2:
        raise ValueError("moe_layer_freq: served with at least one dense "
                         "and one expert layer only")
    heads = int(config["num_attention_heads"])
    dk, dv = int(config["head_dim"]), int(config["v_head_dim"])
    for key, want in (("swa_num_attention_heads", heads),
                      ("swa_head_dim", dk), ("swa_v_head_dim", dv)):
        if int(config.get(key, want)) != want:
            raise ValueError(f"{key} {config[key]}: the window layers are "
                             "served with the full layers' query heads "
                             f"and head widths only ({want})")
    window = int(config["sliding_window"])
    if int(config.get("sliding_window_size", window)) != window:
        raise ValueError("sliding_window_size: served equal to "
                         "sliding_window only")
    partial = float(config.get("partial_rotary_factor", 1.0))
    n_held = int(config["n_routed_experts"])
    scale = config.get("routed_scaling_factor")
    return MimoV2FlashSpec(
        vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        kinds=tuple(KINDS[t] for t in pattern),
        dense_layers=tuple(l for l, m in enumerate(freq) if not m),
        heads=heads, kv_full=int(config["num_key_value_heads"]),
        kv_window=int(config["swa_num_key_value_heads"]),
        head_dim=dk, v_head_dim=dv, sliding_window=window,
        rot_full=Rotary(theta=float(config["rope_theta"]), partial=partial),
        rot_window=Rotary(theta=float(config["swa_rope_theta"]),
                          partial=partial),
        value_scale=float(config.get("attention_value_scale") or 1.0),
        ffn=int(config["intermediate_size"]),
        n_routed=int(config.get("router_experts", n_held)), n_held=n_held,
        expert_first=int(config.get("expert_first", 0)),
        expert_ffn=int(config["moe_intermediate_size"]),
        top_k=int(config["num_experts_per_tok"]),
        routed_scale=1.0 if scale is None else float(scale),
        eps=float(config["layernorm_epsilon"]),
        max_seq_len=int(config["max_position_embeddings"]))


@functools.partial(jax.jit, static_argnames=("spec", "dtype", "scale"))
def init_mimo_v2_flash_lm(key: jax.Array, spec: MimoV2FlashSpec,
                          dtype=jnp.float32,
                          scale: float = 2e-2) -> MimoV2FlashLMParams:
    """Seeded weights, made on the device in one call: matrices ``scale
    * normal`` in ``dtype``, gains 1, the sinks ``SINK_MEAN +
    SINK_SPREAD * normal`` in ``dtype``, the router float32 over all
    ``n_routed`` experts with the choice bias ``BIAS_SCALE * normal``
    (float32), and the ``n_held`` experts this chip holds."""
    s = spec
    n_l, d, dk, dv = len(s.kinds), s.d_model, s.head_dim, s.v_head_dim
    ld = len(s.dense_layers)
    le = n_l - ld
    ks = iter(jax.random.split(key, 32))

    def w(*shape, dt=dtype, sc=scale):
        return (sc * jax.random.normal(next(ks), shape,
                                       jnp.float32)).astype(dt)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    def attn(kind, hkv):
        n = sum(k == kind for k in s.kinds)
        return AttnStack(wq=w(n, s.heads * dk, d), wk=w(n, hkv * dk, d),
                         wv=w(n, hkv * dv, d), wo=w(n, d, s.heads * dv))

    full, window = attn(ATTN, s.kv_full), attn(WINDOW, s.kv_window)
    sinks = (SINK_MEAN + SINK_SPREAD * jax.random.normal(
        next(ks), (window.wq.shape[0], s.heads), jnp.float32)).astype(dtype)
    f = s.expert_ffn
    return MimoV2FlashLMParams(
        wte=w(s.vocab, d), w_head=w(s.vocab, d), norm_in=ones(n_l, d),
        norm_ff=ones(n_l, d), g_f=ones(d), full=full, window=window,
        sinks=sinks,
        dense=MLPStack(w_gate=w(ld, s.ffn, d), w_up=w(ld, s.ffn, d),
                       w_down=w(ld, d, s.ffn)),
        experts=ExpertStack(
            w_router=w(le, s.n_routed, d, dt=jnp.float32),
            bias=w(le, s.n_routed, dt=jnp.float32, sc=BIAS_SCALE),
            w_gate=w(le, s.n_held, f, d), w_up=w(le, s.n_held, f, d),
            w_down=w(le, s.n_held, d, f)),
        kinds=s.kinds, dense_layers=s.dense_layers, head_dim=dk,
        v_head_dim=dv, sliding_window=s.sliding_window,
        rot_full=s.rot_full, rot_window=s.rot_window,
        value_scale=s.value_scale, top_k=s.top_k,
        routed_scale=s.routed_scale, eps=s.eps, max_seq_len=s.max_seq_len,
        expert_first=s.expert_first)
