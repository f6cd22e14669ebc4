"""A gated delta-rule and gated full-attention, fine-grained sparse-expert
LM (``model_type: qwen3_next``).

The Qwen3-Next block, served only. With ``x [T, d]`` the residual
stream, every matrix ``[out, in]`` and no bias anywhere; every norm of
the trunk, the final one and the two QK-norms are RMSNorm with a UNIT
OFFSET, ``(1 + g) x / rms(x)`` at ``rms_norm_eps``, float32:

- layer ``l``: ``h = x + mix_l(rms(x; 1 + norm_in[l]))`` then ``x = h +
  ffn_l(rms(h; 1 + norm_ff[l]))``. Layer ``l`` is full attention where
  ``(l + 1) % full_attention_interval == 0`` and a gated delta-rule
  mixer otherwise; every layer's FFN is the expert layer. After the
  last layer ``logits = rms(x; 1 + g_f) @ w_head.T``, the head untied.
  No position is added to the embedding.
- the gated delta mixer (arXiv:2412.06464; ``H_k`` key heads, ``H_v``
  value heads, ``d_k``, ``d_v`` lanes): ``[q; k; v] = W_qkv a`` (``q, k
  [H_k, d_k]``, ``v [H_v, d_v]``), ``z = W_z a`` (``[H_v, d_v]``),
  ``[b; alpha] = W_ba a`` (``[H_v]`` each). The ``2 H_k d_k + H_v d_v``
  lanes ``[q; k; v]`` pass a depthwise causal convolution of
  ``linear_conv_kernel_dim`` taps, no bias, then SiLU (``ops/ssm.py``,
  as it is); ``z``, ``b`` and ``alpha`` do not. Each ``q`` and ``k``
  head is L2-normalised over its lanes (``x / sqrt(sum x^2 + 1e-6)``)
  and ``q`` scaled by ``d_k^-0.5``; value head ``j`` reads key head ``j
  // (H_v / H_k)``. Per value head, float32, with ``beta = sigmoid(b)``
  and ``g = -exp(A_log) softplus(alpha + dt_bias)`` (``A_log``,
  ``dt_bias`` one scalar a head), the state ``S [d_k, d_v]`` starts at 0
  and a token does ``S <- exp(g) S``; ``u = beta (v - S^T k)``; ``S <-
  S + k u^T``; ``o = S^T q`` (``ops/delta_rule.py``). The output is
  normed a head and gated, ``y = g_n o / rms(o) * silu(z)`` (the gain
  ``g_n [d_v]`` shared by the heads, NO unit offset), then ``W_out``.
  What a sequence keeps of the layer: ``S`` of all value heads, side by
  side as ``[d_k, H_v * d_v]``, and the convolution's last inputs
  (``models/face.py::StateRow``: the convolution's lanes and the
  state's are two widths).
- the full-attention mixer: ``q = W_q a`` and a GATE ``W_g a`` of ``H x
  dh`` lanes each, ``k, v`` of ``H_kv`` heads; ``q`` and ``k`` normed a
  head (unit offset); rotary on the first ``dh * partial_rotary_factor``
  lanes of every ``q`` and ``k`` head, half-split among themselves, at
  ``rope_theta``; ``s_j = q . k_j / sqrt(dh)``, causal softmax over the
  whole sequence; ``y = sigmoid(W_g a) * (sum_j p_j v_j)`` LANE BY LANE
  (``face.head_gate`` is one scalar a head); ``W_o``.
- the expert layer (``ops/moe_serve.py``): ``s = softmax(W_r a)`` over
  ALL ``router_experts`` experts in float32; the ``top_k`` largest
  chosen; weights ``s_chosen / sum(s_chosen)`` (``norm_topk_prob``);
  each expert a gated SiLU MLP; beside them the shared expert, a gated
  SiLU MLP, times ``sigmoid(w_sg . a)``, one scalar a row; the two parts
  add. The params hold the contiguous range ``[expert_first,
  expert_first + E_held)`` of every layer's experts (``num_experts`` of
  the configuration: the chip's share of an expert-parallel deployment,
  or all of them) and compute that range's part, and the shared expert
  whole (every holder has it; the exchange counts it once).

The checkpoint interleaves ``in_proj_qkvz`` and ``in_proj_ba`` a key
head and ``q_proj``'s query and gate a head; here each is a stack of its
own (``w_qkv``, ``w_z``, ``w_ba``; ``wq``, ``w_gate``): a row permutation
a loader applies, the same function.

Precision: the residual stream, norms, rotary, softmax, the
convolution, the recurrence and its state in float32; a matrix product
takes its activations in the weights' type and accumulates in float32
(``mm``); the router is float32 at ``highest`` whatever the weights'
type.

Independently, the same equations: ``benchmark/configs/
qwen3_next_lm_reference.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops import delta_rule, moe_serve, ssm
from ..ops.moe_serve import ExpertStack, holder  # noqa: F401  (the family's names)
from .attention import Rotary
from .face import (ATTN, AttnStack, CacheSpec, MLPStack, StateRow, gated_mlp,
                   layers_of, mm, mm_held, qkv_heads, rmsnorm)

DELTA = "delta"         # the gated delta-rule mixer's layer kind
SCORE = "softmax"       # the family's router (``moe_serve.SCORES``)
L2_EPS = 1e-6           # under the root of a q or k head's L2 norm
# the seeded decay, the layer's published initialisation (FLA): ``A``
# uniform in (0, A_MAX), ``dt`` log-uniform in (DT_MIN, DT_MAX) and
# ``dt_bias`` its inverse softplus, so that ``exp(g)`` keeps from a
# token's worth to thousands of tokens' across a layer's heads
A_MAX = 16.0
DT_MIN, DT_MAX = 1e-3, 1e-1


class DeltaStack(NamedTuple):
    """The gated delta-rule mixers, stacked ``[L_d, ...]`` (``C = 2 H_k
    d_k + H_v d_v`` the convolved lanes, ``D = H_v d_v``)."""
    w_qkv: jax.Array     # [L_d, C, d]: q, k, v in one product
    w_z: jax.Array       # [L_d, D, d]: the output gate
    w_ba: jax.Array      # [L_d, 2 H_v, d]: b then alpha
    conv_w: jax.Array    # [L_d, K, C], tap K-1 on the current token
    a_log: jax.Array     # [L_d, H_v]
    dt_bias: jax.Array   # [L_d, H_v]
    g_norm: jax.Array    # [L_d, d_v]: the gated norm's gain, no offset
    w_out: jax.Array     # [L_d, d, D]


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["wte", "w_head", "norm_in", "norm_ff", "g_f", "delta",
                 "full", "w_gate", "g_q", "g_k", "experts", "shared",
                 "w_sg"],
    meta_fields=["kinds", "head_dim", "rotary", "key_heads", "key_dim",
                 "top_k", "eps", "max_seq_len", "expert_first"])
@dataclasses.dataclass(frozen=True)
class Qwen3NextLMParams:
    """``wte`` / ``w_head [V, d]``, ``norm_in`` / ``norm_ff [L, d]``,
    ``g_f [d]`` (stored gains: a norm multiplies by ``1 + g``);
    ``delta`` the gated delta-rule mixers ``[L_d, ...]``; ``full`` the
    full-attention mixers ``[L_f, ...]`` with their output gates
    ``w_gate [L_f, H*dh, d]`` and QK-norm gains ``g_q``, ``g_k [L_f,
    dh]``; ``experts`` every layer's routed experts ``[L, ...]`` (no
    choice bias), ``shared`` the shared experts ``[L, ...]`` and their
    gates ``w_sg [L, d]``. Static: ``kinds`` (``ATTN`` / ``DELTA`` per
    layer), ``head_dim`` and ``rotary`` of the full layers,
    ``key_heads`` and ``key_dim`` of the delta layers (the value heads
    and their lanes follow from the arrays), the router's ``top_k``,
    ``eps``, ``max_seq_len`` (bounds what ``submit`` accepts) and
    ``expert_first``, the global id of the first held expert."""
    wte: jax.Array
    w_head: jax.Array
    norm_in: jax.Array
    norm_ff: jax.Array
    g_f: jax.Array
    delta: DeltaStack
    full: AttnStack
    w_gate: jax.Array
    g_q: jax.Array
    g_k: jax.Array
    experts: ExpertStack
    shared: MLPStack
    w_sg: jax.Array
    kinds: tuple
    head_dim: int
    rotary: Rotary
    key_heads: int
    key_dim: int
    top_k: int
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
        m, dh = self.delta, self.head_dim
        return CacheSpec(
            kv_layers=self.full.wq.shape[0],
            kv_heads=self.full.wk.shape[1] // dh, head_dim=dh,
            rec_layers=m.w_qkv.shape[0],
            state_row=StateRow(conv_lanes=m.conv_w.shape[2],
                               taps=m.conv_w.shape[1], rows=self.key_dim,
                               lanes=m.w_z.shape[1]),
            expert_layers=self.experts.w_gate.shape[0],
            n_experts=self.experts.w_gate.shape[1])

    def embed(self, tokens, positions, lookup):
        return lookup(self.wte, tokens).astype(jnp.float32)

    def norm(self, g, x):
        return rmsnorm(1.0 + g.astype(jnp.float32), x, self.eps)

    def attn_qkv(self, i, a, positions, head_dim, use_rope):
        f = self.full
        offset = (1.0 + self.g_q[i].astype(jnp.float32),
                  1.0 + self.g_k[i].astype(jnp.float32), self.eps)
        return qkv_heads(f.wq, f.wk, f.wv, i, a, positions, self.head_dim,
                         True, qk_norm=offset, rotary=self.rotary)

    def attn_out(self, i, y, a):
        # the gate: one scalar a LANE of the read's result
        gate = jax.nn.sigmoid(mm_held(a, self.w_gate[i]).astype(jnp.float32))
        return mm(y * gate, self.full.wo[i])

    def recurrent_step(self, i, a, conv, state, rows):
        """Delta mixer ``i`` for one token of each of ``b`` sequences,
        ``a [b, d]``: ``conv`` and ``state`` are the WHOLE stores
        (``decode/paged.py::RecurrentState``), of which rows ``rows
        [b]`` of layer ``i`` are advanced in place."""
        at = dict(layer=i, rows=rows)
        return _gated_delta(
            self, i, a, conv, state,
            functools.partial(ssm.conv_step_in_place, **at),
            functools.partial(delta_rule.delta_step_in_place, **at))

    def recurrent_chunk(self, i, a, tail, state):
        """Delta mixer ``i`` over a chunk of ONE sequence: ``a [c, d]``,
        ``tail [K-1, C]`` and ``state [d_k, D]`` what the sequence
        carries (zeros at position 0). Returns ``(out [c, d], tail,
        state)`` after the chunk."""
        return _gated_delta(self, i, a, tail, state, ssm.conv_chunk,
                            delta_rule.delta_chunk)

    def recurrent_mixed(self, i, a, conv, state, rows, tail, s):
        """Delta mixer ``i`` over a decode batch's ``len(rows)`` rows
        and then ONE sequence's chunk, ``a [b + c, d]``, its weights
        read once."""
        at = dict(layer=i, rows=rows)
        y, (conv, tail), (state, s) = _gated_delta(
            self, i, a, (conv, tail), (state, s),
            functools.partial(ssm.conv_mixed, **at),
            functools.partial(delta_rule.delta_mixed, **at))
        return y, conv, state, tail, s

    def ffn_counted(self, l, h):
        with jax.named_scope("moe"):
            y, rows = moe_serve.routed(self.experts, l, h, self.top_k, 1.0,
                                       self.expert_first, SCORE)
            # the shared expert, weighed by its own gate: a scalar a row
            gate = jax.nn.sigmoid(
                mm(h, self.w_sg[l][None, :]).astype(jnp.float32))
            return y + gate * gated_mlp(self.shared, l, h), rows

    def ffn(self, l, h):
        return self.ffn_counted(l, h)[0]

    def head(self, x):
        return mm(self.norm(self.g_f, x), self.w_head)


def _gated_delta(p: Qwen3NextLMParams, i: int, a, tail, s, conv, delta):
    m = p.delta
    f32 = jnp.float32
    n, h_k, d_k = a.shape[0], p.key_heads, p.key_dim
    d_v = m.g_norm.shape[1]
    h_v = m.w_z.shape[1] // d_v
    qkv, tail = conv(mm_held(a, m.w_qkv[i]).astype(f32), tail,
                     m.conv_w[i].astype(f32), None)
    qkv = jax.nn.silu(qkv)
    q, k = (_l2norm(qkv[:, j * h_k * d_k:(j + 1) * h_k * d_k].reshape(
        n, h_k, d_k)) for j in (0, 1))
    v = qkv[:, 2 * h_k * d_k:].reshape(n, h_v, d_v)
    b, alpha = jnp.split(mm(a, m.w_ba[i]).astype(f32), 2, axis=-1)
    g = -jnp.exp(m.a_log[i].astype(f32)) * jax.nn.softplus(
        alpha + m.dt_bias[i].astype(f32))
    y, s = delta(q * d_k ** -0.5, k, v, g, jax.nn.sigmoid(b), s)
    z = mm_held(a, m.w_z[i]).astype(f32).reshape(n, h_v, d_v)
    y = rmsnorm(m.g_norm[i], y.reshape(n, h_v, d_v), p.eps) * jax.nn.silu(z)
    return mm(y.reshape(n, -1), m.w_out[i]), tail, s


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


class Qwen3NextSpec(NamedTuple):
    """The sizes a published ``config.json`` gives (``spec_from_config``)."""
    vocab: int
    d_model: int
    kinds: tuple
    heads: int              # the full layers' query heads
    kv_heads: int
    head_dim: int
    rotary: Rotary
    key_heads: int          # the delta layers' H_k, d_k, H_v, d_v, K
    key_dim: int
    value_heads: int
    value_dim: int
    conv_taps: int
    n_routed: int           # the router's width: every published expert
    n_held: int             # ... of which this chip holds
    expert_first: int
    expert_ffn: int
    shared_ffn: int
    top_k: int
    eps: float
    max_seq_len: int


def spec_from_config(config: dict) -> Qwen3NextSpec:
    """A ``model_type: qwen3_next`` ``config.json`` (the published
    keys) as sizes. ``num_experts`` is how many experts of a layer are
    HELD here; where that is a share of them, ``router_experts`` beside
    it states the published count the router scores (and
    ``expert_first`` the first held). What this file cannot serve is
    refused by name, never read as something else."""
    if config.get("model_type") != "qwen3_next":
        raise ValueError(f"model_type {config.get('model_type')!r}: "
                         "models/qwen3_next_lm.py serves 'qwen3_next' only")
    if config.get("mlp_only_layers"):
        raise ValueError(f"mlp_only_layers {config['mlp_only_layers']}: "
                         "every layer's FFN is served as the expert layer "
                         "only")
    if int(config.get("decoder_sparse_step", 1)) != 1:
        raise ValueError(f"decoder_sparse_step "
                         f"{config['decoder_sparse_step']}: every layer's "
                         "FFN is served as the expert layer only (1)")
    if config.get("use_sliding_window"):
        raise ValueError("use_sliding_window: the attention layers are "
                         "served full only")
    if config.get("rope_scaling"):
        raise ValueError("rope_scaling: the rotary is served unscaled only")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {config['hidden_act']!r}: the MLPs "
                         "are served gated with 'silu' only")
    if not config.get("norm_topk_prob", True):
        raise ValueError("norm_topk_prob false: the chosen weights are "
                         "served normalised only")
    if config.get("tie_word_embeddings", False):
        raise ValueError("the head is served untied only")
    h_k = int(config["linear_num_key_heads"])
    h_v = int(config["linear_num_value_heads"])
    if h_v % h_k:
        raise ValueError(f"linear_num_value_heads {h_v} is no multiple of "
                         f"linear_num_key_heads {h_k}: a value head reads "
                         "one key head")
    n = int(config["num_hidden_layers"])
    period = int(config["full_attention_interval"])
    n_held = int(config["num_experts"])
    return Qwen3NextSpec(
        vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        kinds=tuple(ATTN if (l + 1) % period == 0 else DELTA
                    for l in range(n)),
        heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        rotary=Rotary(theta=float(config["rope_theta"]),
                      partial=float(config.get("partial_rotary_factor",
                                               1.0))),
        key_heads=h_k, key_dim=int(config["linear_key_head_dim"]),
        value_heads=h_v, value_dim=int(config["linear_value_head_dim"]),
        conv_taps=int(config["linear_conv_kernel_dim"]),
        n_routed=int(config.get("router_experts", n_held)), n_held=n_held,
        expert_first=int(config.get("expert_first", 0)),
        expert_ffn=int(config["moe_intermediate_size"]),
        shared_ffn=int(config["shared_expert_intermediate_size"]),
        top_k=int(config["num_experts_per_tok"]),
        eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["max_position_embeddings"]))


@functools.partial(jax.jit, static_argnames=("spec", "dtype", "scale"))
def init_qwen3_next_lm(key: jax.Array, spec: Qwen3NextSpec,
                       dtype=jnp.float32,
                       scale: float = 2e-2) -> Qwen3NextLMParams:
    """Seeded weights, made on the device in one call: matrices and the
    stored unit-offset gains ``scale * normal`` in ``dtype`` (a norm
    multiplies by ``1 + g``, so it starts near 1), the gated norm's gain
    1, the router float32 over all ``n_routed`` experts, the ``n_held``
    experts this chip holds, the convolution's taps uniform in ``+-
    K**-0.5`` (at ``scale`` they would pass a fiftieth of their input)
    and the decay the layer's published initialisation (``A_MAX``,
    ``DT_MIN``, ``DT_MAX`` above), NOT a constant: under ``dt_bias`` 1
    the heads whose ``A`` is over 1 forget their state within a token or
    two and a program that drops the state would pass for one that
    carries it."""
    s = spec
    n_l, d = len(s.kinds), s.d_model
    ld = sum(k == DELTA for k in s.kinds)
    lf = n_l - ld
    dh, dk, dv = s.head_dim, s.key_dim, s.value_dim
    c, dd = 2 * s.key_heads * dk + s.value_heads * dv, s.value_heads * dv
    ks = iter(jax.random.split(key, 32))

    def w(*shape, dt=dtype):
        return (scale * jax.random.normal(next(ks), shape,
                                          jnp.float32)).astype(dt)

    def uniform(*shape, lo=0.0, hi=1.0):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)

    bound = s.conv_taps ** -0.5
    step = jnp.exp(uniform(ld, s.value_heads, lo=math.log(DT_MIN),
                           hi=math.log(DT_MAX)))
    f, fs = s.expert_ffn, s.shared_ffn
    return Qwen3NextLMParams(
        wte=w(s.vocab, d), w_head=w(s.vocab, d), norm_in=w(n_l, d),
        norm_ff=w(n_l, d), g_f=w(d),
        delta=DeltaStack(
            w_qkv=w(ld, c, d), w_z=w(ld, dd, d),
            w_ba=w(ld, 2 * s.value_heads, d),
            conv_w=uniform(ld, s.conv_taps, c, lo=-bound,
                           hi=bound).astype(dtype),
            a_log=jnp.log(uniform(ld, s.value_heads, lo=1e-3,
                                  hi=A_MAX)).astype(dtype),
            # softplus(dt_bias) == step
            dt_bias=(step + jnp.log(-jnp.expm1(-step))).astype(dtype),
            g_norm=jnp.ones((ld, dv), dtype), w_out=w(ld, d, dd)),
        full=AttnStack(wq=w(lf, s.heads * dh, d), wk=w(lf, s.kv_heads * dh, d),
                       wv=w(lf, s.kv_heads * dh, d),
                       wo=w(lf, d, s.heads * dh)),
        w_gate=w(lf, s.heads * dh, d), g_q=w(lf, dh), g_k=w(lf, dh),
        experts=ExpertStack(
            w_router=w(n_l, s.n_routed, d, dt=jnp.float32), bias=None,
            w_gate=w(n_l, s.n_held, f, d), w_up=w(n_l, s.n_held, f, d),
            w_down=w(n_l, s.n_held, d, f)),
        shared=MLPStack(w_gate=w(n_l, fs, d), w_up=w(n_l, fs, d),
                        w_down=w(n_l, d, fs)),
        w_sg=w(n_l, d),
        kinds=s.kinds, head_dim=dh, rotary=s.rotary, key_heads=s.key_heads,
        key_dim=dk, top_k=s.top_k, eps=s.eps, max_seq_len=s.max_seq_len,
        expert_first=s.expert_first)
