"""A hybrid LM: Mamba-1 mixers and attention mixers in one stack.

The Jamba family's block (``model_type: jamba``), served only: every
layer is ``h += mixer(RMSNorm(h))`` then ``h += W_down(SiLU(W_gate a) *
W_up a)`` with ``a = RMSNorm(h)``; the mixer of layer ``i`` is attention
where ``i % period == offset`` and a Mamba-1 mixer otherwise; a final
RMSNorm and the head tied to the embedding. No position embedding of any
kind (the recurrent layers carry order). No biases but the
convolution's.

The repo's stance: raw stacked arrays in NamedTuples, one stack per
layer kind — Mamba leaves ``[L_m, ...]``, attention leaves ``[L_a,
...]``, MLP and norm leaves ``[L, ...]`` — every matrix ``[out, in]``.
The pattern is static (pytree metadata): ``layers`` says, per model
layer, which kind it is and which index of its kind's stack — and of its
kind's CACHE (``decode/paged.py``: KV blocks for attention, a recurrent
state row for Mamba) — it owns. ``HybridLMParams``' methods are the
family's answers to ``models/face.py::ServedModel``, from which
``decode/programs.py`` builds the serving programs.

Precision: the residual stream, the norms, the convolution and the
recurrence are float32 whatever the weights' type; a matrix product
takes its activations in the weights' type (bfloat16 as served) and
accumulates in float32 (``mm``).

The mixer's equations are in ``ops/ssm.py`` and, independently, in the
plain reference ``benchmark/configs/jamba_lm_reference.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops import ssm
from .face import (ATTN, AttnStack, CacheSpec, MLPStack, StateRow,
                   gated_mlp, layers_of, mm, qkv_heads, rmsnorm)

MAMBA = "mamba"


class MambaStack(NamedTuple):
    """The Mamba-1 mixers, stacked ``[L_m, ...]``. ``D`` inner width,
    ``N`` state size, ``R`` dt rank, ``K`` convolution kernel.
    ``a_log`` is ``[L_m, N, D]`` — the published ``[D, N]`` transposed,
    so the inner width is the minor axis (``ops/ssm.py`` says why)."""
    w_in: jax.Array      # [L_m, 2D, d]   -> [x; z]
    conv_w: jax.Array    # [L_m, K, D]    tap K-1 on the current token
    conv_b: jax.Array    # [L_m, D]
    w_x: jax.Array       # [L_m, R+2N, D] -> [delta; B; C]
    g_dt: jax.Array      # [L_m, R]       RMSNorm gains on delta, B, C
    g_b: jax.Array       # [L_m, N]
    g_c: jax.Array       # [L_m, N]
    w_dt: jax.Array      # [L_m, D, R]
    b_dt: jax.Array      # [L_m, D]
    a_log: jax.Array     # [L_m, N, D]
    d: jax.Array         # [L_m, D]
    w_out: jax.Array     # [L_m, d, D]


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["wte", "norm_in", "norm_ff", "ln_f", "mamba", "attn",
                 "mlp"],
    meta_fields=["kinds", "head_dim", "eps", "max_seq_len"])
@dataclasses.dataclass(frozen=True)
class HybridLMParams:
    """``wte [V, d]`` (tied head), ``norm_in`` / ``norm_ff [L, d]`` the
    RMSNorm gains before each mixer and MLP, ``ln_f [d]`` the final
    one. Static: ``kinds`` (one of ``ATTN`` / ``MAMBA`` per layer),
    ``head_dim``, ``eps``, ``max_seq_len`` (no table depends on it: the
    model has no positions; it bounds what ``submit`` accepts)."""
    wte: jax.Array
    norm_in: jax.Array
    norm_ff: jax.Array
    ln_f: jax.Array
    mamba: MambaStack
    attn: AttnStack
    mlp: MLPStack
    kinds: tuple
    head_dim: int
    eps: float
    max_seq_len: int

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
        m = self.mamba
        return CacheSpec(
            kv_layers=self.attn.wq.shape[0],
            kv_heads=self.attn.wk.shape[1] // self.head_dim,
            head_dim=self.head_dim, rec_layers=m.w_in.shape[0],
            state_row=StateRow(conv_lanes=m.conv_w.shape[2],
                               taps=m.conv_w.shape[1],
                               rows=m.a_log.shape[1],
                               lanes=m.conv_w.shape[2]))

    def embed(self, tokens, positions, lookup):
        # no position of any kind: the recurrent layers carry order
        return lookup(self.wte, tokens).astype(jnp.float32)

    def norm(self, g, x):
        return rmsnorm(g, x, self.eps)

    def attn_qkv(self, i, a, positions, head_dim, use_rope):
        return qkv_heads(self.attn.wq, self.attn.wk, self.attn.wv, i, a,
                         positions, head_dim, use_rope)

    def attn_out(self, i, y, a):
        return mm(y, self.attn.wo[i])

    def recurrent_step(self, i, a, conv, state, rows):
        """Mamba mixer ``i`` for one token of each of ``b`` sequences,
        ``a [b, d]``: ``conv`` and ``state`` are the WHOLE stores
        (``decode/paged.py::RecurrentState``), of which rows ``rows
        [b]`` of layer ``i`` are advanced in place."""
        at = dict(layer=i, rows=rows)
        return _mamba(self, i, a, conv, state,
                      functools.partial(ssm.conv_step_in_place, **at),
                      functools.partial(ssm.scan_step_in_place, **at))

    def recurrent_chunk(self, i, a, tail, state):
        """Mamba mixer ``i`` over a chunk of ONE sequence: ``a [c, d]``
        the normed residual, ``tail [K-1, D]`` and ``state [N, D]`` what
        the sequence carries (zeros at position 0). Returns ``(out [c,
        d], tail, state)`` after the chunk."""
        return _mamba(self, i, a, tail, state, ssm.conv_chunk,
                      ssm.scan_chunk)

    def recurrent_mixed(self, i, a, conv, state, rows, tail, s):
        """Mamba mixer ``i`` over a decode batch's ``len(rows)`` rows
        and then ONE sequence's chunk, ``a [b + c, d]``, its weights
        read once: the first rows advance the stores as
        ``recurrent_step`` does, the rest scan from ``tail, s`` as
        ``recurrent_chunk`` does."""
        at = dict(layer=i, rows=rows)
        y, (conv, tail), (state, s) = _mamba(
            self, i, a, (conv, tail), (state, s),
            functools.partial(ssm.conv_mixed, **at),
            functools.partial(ssm.scan_mixed, **at))
        return y, conv, state, tail, s

    def ffn(self, l, h):
        return gated_mlp(self.mlp, l, h)

    def head(self, x):
        return mm(rmsnorm(self.ln_f, x, self.eps), self.wte)


class HybridSpec(NamedTuple):
    """The sizes a published ``config.json`` gives (``spec_from_config``)."""
    vocab: int
    d_model: int
    kinds: tuple
    n_heads: int
    n_kv_heads: int
    head_dim: int
    ffn: int
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int
    eps: float
    max_seq_len: int


def spec_from_config(config: dict) -> HybridSpec:
    """A ``model_type: jamba`` ``config.json`` (the published keys) as
    sizes. What this file cannot serve is refused by name, never read
    as something else."""
    if config.get("model_type") != "jamba":
        raise ValueError(f"model_type {config.get('model_type')!r}: "
                         "models/hybrid_lm.py serves 'jamba' only")
    if config.get("num_experts", 1) != 1:
        raise ValueError(f"num_experts {config['num_experts']}: only the "
                         "dense MLP (num_experts 1) is served")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {config['hidden_act']!r}: the "
                         "gated MLP is SiLU only")
    if config.get("sliding_window") is not None:
        raise ValueError("sliding_window: attention is full only")
    if config.get("mamba_proj_bias") or not config.get(
            "mamba_conv_bias", True):
        raise ValueError("the mixer is served with a convolution bias "
                         "and no projection bias only")
    if not config.get("tie_word_embeddings", True):
        raise ValueError("the head is served tied to the embedding only")
    d = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    period = int(config["attn_layer_period"])
    offset = int(config["attn_layer_offset"])
    n = int(config["num_hidden_layers"])
    kinds = tuple(ATTN if i % period == offset else MAMBA
                  for i in range(n))
    dt_rank = config.get("mamba_dt_rank", "auto")
    return HybridSpec(
        vocab=int(config["vocab_size"]), d_model=d, kinds=kinds,
        n_heads=heads, n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim") or d // heads),
        ffn=int(config["intermediate_size"]),
        d_inner=int(config["mamba_expand"]) * d,
        d_state=int(config["mamba_d_state"]),
        d_conv=int(config["mamba_d_conv"]),
        dt_rank=(math.ceil(d / 16) if dt_rank == "auto"
                 else int(dt_rank)),
        eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["max_position_embeddings"]))


@functools.partial(jax.jit, static_argnames=("spec", "dtype", "scale"))
def init_hybrid_lm(key: jax.Array, spec: HybridSpec, dtype=jnp.float32,
                   scale: float = 2e-2) -> HybridLMParams:
    """Seeded weights, made on the device in one call. Matrices
    ``scale * normal``, gains 1, and for the mixer's own parameters
    Mamba-1's published initialisation, so that the recurrence has the
    time constants of a trained model and is neither dead nor
    divergent: ``A_log = log(1..N)`` per channel, ``b_dt`` the inverse
    softplus of a log-uniform draw in [1e-3, 1e-1], ``D = 1``, the
    convolution's taps and bias uniform in ``+-K**-0.5`` (at ``scale``
    the taps would pass a fiftieth of their input and the mixer would
    add nothing to the stream)."""
    lm = sum(k == MAMBA for k in spec.kinds)
    la = len(spec.kinds) - lm
    n_l = len(spec.kinds)
    d, dd, n, r = spec.d_model, spec.d_inner, spec.d_state, spec.dt_rank
    hq = spec.n_heads * spec.head_dim
    hkv = spec.n_kv_heads * spec.head_dim
    ks = iter(jax.random.split(key, 16))

    def w(*shape):
        return (scale * jax.random.normal(next(ks), shape,
                                          jnp.float32)).astype(dtype)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    def conv(*shape):
        bound = spec.d_conv ** -0.5
        return jax.random.uniform(next(ks), shape, jnp.float32, -bound,
                                  bound).astype(dtype)

    dt = jnp.exp(jax.random.uniform(next(ks), (lm, dd), jnp.float32)
                 * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    b_dt = dt + jnp.log(-jnp.expm1(-dt))        # softplus(b_dt) == dt
    a_log = jnp.broadcast_to(
        jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[None, :, None],
        (lm, n, dd))
    return HybridLMParams(
        wte=w(spec.vocab, d), norm_in=ones(n_l, d), norm_ff=ones(n_l, d),
        ln_f=ones(d),
        mamba=MambaStack(
            w_in=w(lm, 2 * dd, d), conv_w=conv(lm, spec.d_conv, dd),
            conv_b=conv(lm, dd), w_x=w(lm, r + 2 * n, dd),
            g_dt=ones(lm, r), g_b=ones(lm, n), g_c=ones(lm, n),
            w_dt=w(lm, dd, r), b_dt=b_dt.astype(dtype),
            a_log=a_log.astype(dtype), d=ones(lm, dd),
            w_out=w(lm, d, dd)),
        attn=AttnStack(wq=w(la, hq, d), wk=w(la, hkv, d),
                       wv=w(la, hkv, d), wo=w(la, d, hq)),
        mlp=MLPStack(w_gate=w(n_l, spec.ffn, d), w_up=w(n_l, spec.ffn, d),
                     w_down=w(n_l, d, spec.ffn)),
        kinds=spec.kinds, head_dim=spec.head_dim, eps=spec.eps,
        max_seq_len=spec.max_seq_len)


# -- the block's pieces (the face's methods above put them together) ----


def _mamba_dt_b_c(p: HybridLMParams, i: int, x: jax.Array):
    """``x [.., D]`` (after the convolution and SiLU) -> the step sizes
    ``dt [.., D]`` and the input and output maps ``B, C [.., N]``."""
    m = p.mamba
    r, n = m.g_dt.shape[-1], m.g_b.shape[-1]
    dbc = mm(x, m.w_x[i])
    delta = rmsnorm(m.g_dt[i], dbc[..., :r], p.eps)
    b = rmsnorm(m.g_b[i], dbc[..., r:r + n], p.eps)
    c = rmsnorm(m.g_c[i], dbc[..., r + n:], p.eps)
    dt = jax.nn.softplus(mm(delta, m.w_dt[i])
                         + m.b_dt[i].astype(jnp.float32))
    return dt, b, c


def _mamba(p: HybridLMParams, i: int, a, tail, s, conv, scan):
    m = p.mamba
    f32 = jnp.float32
    x, z = jnp.split(mm(a, m.w_in[i]), 2, axis=-1)
    x, tail = conv(x, tail, m.conv_w[i].astype(f32),
                   m.conv_b[i].astype(f32))
    x = jax.nn.silu(x)
    dt, b, c = _mamba_dt_b_c(p, i, x)
    y, s = scan(x, dt, -jnp.exp(m.a_log[i].astype(f32)), b, c,
                m.d[i].astype(f32), s)
    return mm(y * jax.nn.silu(z), m.w_out[i]), tail, s
