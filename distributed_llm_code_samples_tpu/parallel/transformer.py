"""Transformer trainers: single-device, DDP, FSDP/ZeRO-3, and Megatron TP.

The strategies mirror the FFN-stack ones (``ddp.py``, ``fsdp.py``,
``tp.py``) applied to the full pre-LN block stack (``models.transformer``).
The backward composes the hand-written block rules via ``jax.vjp`` (the
framework's composition precedent), with the collectives placed by hand:

- **DDP**: replicated params, strided seed shards, one grad ``psum`` per
  step (SUM, unscaled LR — ``train_ffns.py:165`` semantics).
- **FSDP**: every param stack sharded over the data axis, layers
  ``all_gather``-ed transiently per step; the gather's AD transpose is
  ``psum_scatter``, which sums grads across shards and scatters them onto
  the local chunks in one collective.
- **TP**: Megatron attention + FFN sharding on the ``"model"`` axis. Heads
  are column-parallel (``wq/wk/wv`` split on the output dim — each shard
  runs ``H/n`` whole heads), ``wo`` row-parallel, FFN ``w1``/``w2``
  column/row-parallel (the existing ``tp.py`` layout), LN replicated. The
  Megatron f/g operator pair is explicit: ``g`` is the forward ``psum``
  after each sublayer's row-parallel matmul (backward: identity — ``psum``'s
  transpose); ``f`` is ``_f_gate`` below — identity forward, ``psum``
  backward — applied to each sublayer's post-LN input so the partial
  input-gradients of the column-parallel projections are summed before
  flowing into the (replicated) LayerNorm backward. Omitting ``f`` leaves
  ``dx`` partial and silently wrong — the TP==single differential test is
  the guard.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import LR
from ..data import batch_from_seed
from ..models.ffn_stack import clone_params, reshard_copy
from ..models.transformer import (TransformerParams, attn_sublayer,
                                  transformer_block, transformer_fwd)
from ..ops.ffn import ffn_block
from ..ops.norm import layernorm
from ..optim import sgd
from .collectives import (all_gather, all_reduce, axis_index, grad_reduce,
                          reduce_scatter, vary)
from .launcher import launch, launch_strided
from .mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS, require_axes

# TP layout: column-parallel projections shard the output dim (heads for
# attention, ffn features for w1); row-parallel shard the input dim.
TP_SPECS = TransformerParams(
    ln1=P(), wq=P(None, MODEL_AXIS, None), wk=P(None, MODEL_AXIS, None),
    wv=P(None, MODEL_AXIS, None), wo=P(None, None, MODEL_AXIS),
    ln2=P(), w1=P(None, MODEL_AXIS, None), w2=P(None, None, MODEL_AXIS))

# FSDP layout: every stack sharded on its first per-layer dim (stacked
# axis 1) across the data axis — the reference's chunk-along-dim-0
# (train_ffns.py:265-266) on the transformer's parameter surface.
FSDP_SPECS = TransformerParams(
    ln1=P(None, DATA_AXIS), wq=P(None, DATA_AXIS, None),
    wk=P(None, DATA_AXIS, None), wv=P(None, DATA_AXIS, None),
    wo=P(None, DATA_AXIS, None), ln2=P(None, DATA_AXIS),
    w1=P(None, DATA_AXIS, None), w2=P(None, DATA_AXIS, None))


def _shard(params: TransformerParams, mesh, specs) -> TransformerParams:
    """Lay params out per a spec pytree (fresh buffers, launcher-owned)."""
    return reshard_copy(params, jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), specs,
        is_leaf=lambda v: isinstance(v, P)))


def _f_gate(axis: str):
    """Megatron's ``f`` operator: identity forward, all-reduce backward.
    In JAX's varying-manual-axes typing that is a replicated activation
    re-typed as varying over the model axis on the way in (``pcast``, no
    data movement — what follows multiplies it by this shard's weight
    columns), and the per-shard partial input-grads summed on the way
    back. The rule honours the custom_vjp type contract both ways: the
    output varies, the cotangent it hands back for ``x`` does not.
    (``grad_reduce``, not a bare ``psum``: in a vma-off launch — the
    interpret-mode Pallas kernels — the gate stands down like every
    non-forced reduction, ``collectives.grad_reduce``.)"""

    @jax.custom_vjp
    def f(x):
        return vary(x, axis)

    f.defvjp(lambda x: (vary(x, axis), None),
             lambda _, dy: (grad_reduce(dy, axis),))
    return f


def _reshape_batch(seed, tokens: int, seq_len: int, model_size: int, dtype):
    x, dloss_dx = batch_from_seed(seed, tokens, model_size, dtype)
    b = tokens // seq_len
    return (x.reshape(b, seq_len, model_size),
            dloss_dx.reshape(b, seq_len, model_size))


def _validate_shapes(batch_size: int, seq_len: int, model_size: int,
                     n_heads: int) -> None:
    if batch_size % seq_len:
        raise ValueError(f"tokens {batch_size} not divisible by "
                         f"seq_len {seq_len}")
    if model_size % n_heads:
        raise ValueError(f"model_size={model_size} not divisible by "
                         f"n_heads={n_heads} (head dim must be whole)")


def resolve_attn(attn_impl: str | None):
    """Map an ``attn_impl`` name to the multi-head attention op the model
    plugs in (``models.transformer.attn_sublayer``): None/"oracle" = the
    quadratic hand-VJP ``mha``; "flash" = the fused Pallas kernels
    (interpret mode automatically off-TPU), custom-VJP'd end to end,
    GQA shapes via repeat-KV fan-out; "rope" = rotary positions applied
    to q/k before the hand-VJP kernel (GQA shapes compose)."""
    if attn_impl in (None, "oracle"):
        return None
    if attn_impl == "flash":
        from ..ops.pallas_attention import flash_mha
        interpret = jax.default_backend() != "tpu"
        fn = lambda q, k, v, causal: flash_mha(q, k, v, causal, interpret)
        fn.supports_gqa = flash_mha.supports_gqa  # single declaration
        return fn
    if attn_impl == "rope":
        from ..models.attention import rope_mha
        return rope_mha
    raise ValueError(f"unknown attn_impl {attn_impl!r} "
                     "(expected 'oracle', 'flash', or 'rope')")


def _make_single_step(tokens: int, model_size: int, seq_len: int,
                      n_heads: int, lr: float, causal: bool = True,
                      attn=None, mixed: bool = False):
    def step(params: TransformerParams, seed) -> TransformerParams:
        # named-scope regions (tf/fwd, tf/bwd, tf/optim) — the naming
        # map lives in utils/trace_analysis.SCOPES
        with jax.named_scope("tf"):
            x, dloss_dx = _reshape_batch(seed, tokens, seq_len,
                                         model_size, params.w1.dtype)
            if mixed:
                # the LM family's bf16 stance (models.lm.lm_loss(mixed=)),
                # head-less: bf16 params + activations through the blocks,
                # f32 master params/grads/update — the cotangent enters in
                # bf16 (the fwd output's dtype) and the grads come back f32
                # through the cast transposes
                xm = x.astype(jnp.bfloat16)

                def fwd(p):
                    pc = jax.tree_util.tree_map(
                        lambda a: a.astype(jnp.bfloat16), p)
                    return transformer_fwd(pc, xm, n_heads, causal, attn)

                with jax.named_scope("fwd"):
                    _, vjp = jax.vjp(fwd, params)
                with jax.named_scope("bwd"):
                    grads = vjp(dloss_dx.astype(jnp.bfloat16))[0]
            else:
                with jax.named_scope("fwd"):
                    _, vjp = jax.vjp(
                        lambda p: transformer_fwd(p, x, n_heads, causal,
                                                  attn), params)
                with jax.named_scope("bwd"):
                    grads = vjp(dloss_dx)[0]
            with jax.named_scope("optim"):
                return sgd(params, grads, lr)

    return step


@partial(jax.jit, static_argnums=tuple(range(2, 10)), donate_argnums=0)
def _run_single(params, seeds, batch_size, model_size, lr, seq_len,
                n_heads, causal, attn_impl, mixed=False):
    """Module-level jit (the ``single.py`` pattern): repeat calls with the
    same static config reuse the compiled program instead of re-tracing —
    load-bearing for the bench's best-of-N timing loops."""
    step = _make_single_step(batch_size, model_size, seq_len, n_heads, lr,
                             causal, resolve_attn(attn_impl), mixed)
    return lax.scan(lambda p, s: (step(p, s), None), params, seeds)[0]


def train_transformer_single(params: TransformerParams, seeds,
                             batch_size: int, model_size: int, mesh=None,
                             lr: float = LR, *, seq_len: int, n_heads: int,
                             causal: bool = True,
                             attn_impl: str | None = None,
                             mixed: bool = False
                             ) -> TransformerParams:
    """Single-device trainer; ``batch_size`` is tokens/step (seq folded,
    CLI convention ``train_ffns.py:379``), unfolded to
    ``[batch_size/seq_len, seq_len, d]`` for attention. ``mixed`` runs
    the blocks in bf16 with f32 master params/grads/update."""
    _validate_shapes(batch_size, seq_len, model_size, n_heads)
    return _run_single(clone_params(params), jnp.asarray(seeds),
                       batch_size, model_size, lr, seq_len, n_heads,
                       causal, attn_impl, mixed)


def train_transformer_ddp(params: TransformerParams, seeds, batch_size: int,
                          model_size: int, mesh, lr: float = LR, *,
                          seq_len: int, n_heads: int, causal: bool = True,
                          attn_impl: str | None = None) -> TransformerParams:
    """DDP: each shard trains its seed column on the full replicated model;
    grads psum per step."""
    require_axes(mesh, DATA_AXIS)
    _validate_shapes(batch_size, seq_len, model_size, n_heads)
    attn = resolve_attn(attn_impl)

    def step(params: TransformerParams, seed) -> TransformerParams:
        with jax.named_scope("tf"):
            x, dloss_dx = _reshape_batch(seed, batch_size, seq_len,
                                         model_size, params.w1.dtype)
            with jax.named_scope("fwd"):
                # replicated weights meet this shard's batch inside the
                # hand-written rules: typed varying going in, their
                # cotangents come back per-shard partials for "comm"
                _, vjp = jax.vjp(
                    lambda p: transformer_fwd(p, x, n_heads, causal,
                                              attn),
                    vary(params, DATA_AXIS))
            with jax.named_scope("bwd"):
                grads = vjp(dloss_dx)[0]
            with jax.named_scope("comm"):
                grads = jax.tree_util.tree_map(
                    lambda g: grad_reduce(g, DATA_AXIS), grads)
            with jax.named_scope("optim"):
                return sgd(params, grads, lr)

    return launch_strided(step, clone_params(params), seeds, mesh,
                          DATA_AXIS, P())


def train_transformer_fsdp(params: TransformerParams, seeds,
                           batch_size: int, model_size: int, mesh,
                           lr: float = LR, *, seq_len: int, n_heads: int,
                           causal: bool = True,
                           attn_impl: str | None = None
                           ) -> TransformerParams:
    """FSDP/ZeRO-3 on the transformer: every param stack sharded over the
    data axis, each layer ``all_gather``-ed transiently per step (the
    unrolled loop lets XLA prefetch layer l+1's gathers during layer l's
    compute, ``train_ffns.py:200-249``). The backward needs no explicit
    collective at all: the AD transpose of the forward's ``all_gather`` IS
    ``psum_scatter``, so grads come back simultaneously summed across the
    data shards and scattered onto the local chunks (the gather/
    reduce-scatter correspondence the reference built by hand at
    ``:245-256``). Sharded SGD on the local chunk only.
    """
    require_axes(mesh, DATA_AXIS)
    n = mesh.shape[DATA_AXIS]
    _validate_shapes(batch_size, seq_len, model_size, n_heads)
    for name, leaf in zip(TransformerParams._fields, params):
        if leaf.shape[1] % n:
            raise ValueError(f"{name} dim {leaf.shape[1]} not divisible by "
                             f"{n} shards")
    attn = resolve_attn(attn_impl)

    def step(params: TransformerParams, seed) -> TransformerParams:
        x, dloss_dx = _reshape_batch(seed, batch_size, seq_len, model_size,
                                     params.w1.dtype)

        def fwd(p):
            y = x
            for l in range(p.w1.shape[0]):
                # gather this layer's full params (transient, never stored)
                # and run the exact single-device block on them
                with jax.named_scope("comm"):
                    full = [all_gather(leaf[l], DATA_AXIS, dim=0)
                            for leaf in p]
                y = transformer_block(*full, y, n_heads, causal, attn)
            return y

        with jax.named_scope("tf"):
            with jax.named_scope("fwd"):
                _, vjp = jax.vjp(fwd, params)
            with jax.named_scope("bwd"):
                # psum_scatter'd by the gather transpose
                grads = vjp(dloss_dx)[0]
            with jax.named_scope("optim"):
                return sgd(params, grads, lr)

    return launch_strided(step, _shard(params, mesh, FSDP_SPECS), seeds,
                          mesh, DATA_AXIS, FSDP_SPECS)


def tp_block(ln1, wq, wk, wv, wo, ln2, w1, w2, x, n_heads_local: int,
             axis: str = MODEL_AXIS, causal: bool = True, attn=None):
    """One TP transformer block, per-shard view (local weights)."""
    f = _f_gate(axis)

    def g(t):  # Megatron g: the forward psum, named for trace analysis
        with jax.named_scope("comm"):
            return all_reduce(t, axis)

    b, s, d = x.shape
    a = f(layernorm(ln1, x))
    x = x + g(attn_sublayer(wq, wk, wv, wo, a, n_heads_local, causal,
                            attn))
    h = f(layernorm(ln2, x)).reshape(b * s, d)
    y = g(ffn_block(w1, w2, h))
    return x + y.reshape(b, s, d)


def sp_block(ln1, wq, wk, wv, wo, ln2, w1, w2, x_s, n_heads_local: int,
             axis: str = MODEL_AXIS, causal: bool = True, attn=None):
    """One sequence-parallel TP transformer block (Korthikanti et al.),
    per-shard view: ``x_s [b, s/n, d]`` — the residual stream, LayerNorms,
    and both residual adds live on this rank's **token shard**; only the
    sublayer cores see full tokens, via ``all_gather`` (sequence in) +
    ``reduce_scatter`` (sequence out) — the ring-equal decomposition of
    ``tp_block``'s two ``psum``s, with every stream activation 1/n the
    size. The gathers/scatters differentiate by their exact transposes
    (gather <-> scatter+sum), composed by ``jax.vjp`` around the
    hand-written sublayer rules; the ``_f_gate`` is subsumed — the
    backward's ``reduce_scatter`` already sums the column-parallel
    projections' partial input-grads."""
    def g(t):
        with jax.named_scope("comm"):
            return all_gather(t, axis, dim=1)

    def rs(t):
        with jax.named_scope("comm"):
            return reduce_scatter(t, axis, dim=1)

    b, s_local, d = x_s.shape
    a = g(layernorm(ln1, x_s))                          # [b, s, d] full
    x_s = x_s + rs(
        attn_sublayer(wq, wk, wv, wo, a, n_heads_local, causal, attn))
    h = g(layernorm(ln2, x_s))
    full_tokens = b * s_local * lax.axis_size(axis)
    y = rs(ffn_block(w1, w2, h.reshape(full_tokens, d)).reshape(b, -1, d))
    return x_s + y


def _validate_tp(params, n_heads: int, n: int) -> int:
    if n_heads % n:
        raise ValueError(f"n_heads={n_heads} not divisible by model-axis "
                         f"size {n}")
    dh = params.wq.shape[1] // n_heads
    kv_heads = params.wk.shape[1] // dh
    if kv_heads % n:
        raise ValueError(f"n_kv_heads={kv_heads} (GQA) not divisible by "
                         f"model-axis size {n}")
    ffn_dim = params.w1.shape[1]
    if ffn_dim % n:
        raise ValueError(f"ffn_dim={ffn_dim} not divisible by model-axis "
                         f"size {n}")
    return n_heads // n


def train_transformer_tp(params: TransformerParams, seeds, batch_size: int,
                         model_size: int, mesh, lr: float = LR, *,
                         seq_len: int, n_heads: int, causal: bool = True,
                         attn_impl: str | None = None,
                         sequence_parallel: bool = False
                         ) -> TransformerParams:
    """Megatron TP over the ``"model"`` axis: data replicated, heads and
    FFN features sharded, two psums per block per direction
    (``train_ffns.py:303, :309`` cadence on the transformer block).

    ``sequence_parallel=True`` selects the Korthikanti et al. form
    (``sp_block``): the residual stream, LayerNorms, and dropout-free
    elementwise work live token-sharded (``[b, s/n, d]``), each psum
    decomposed into ``all_gather`` + ``reduce_scatter``. Same math
    (differential-tested against this trainer's plain form and the
    single-device oracle), 1/n the stream activations. LN gains then see
    only the shard's tokens, so their grads pick up one ``psum`` over the
    model axis; projection/FFN grads stay shard-complete."""
    require_axes(mesh, MODEL_AXIS)
    n = mesh.shape[MODEL_AXIS]
    h_local = _validate_tp(params, n_heads, n)
    _validate_shapes(batch_size, seq_len, model_size, n_heads)
    step = make_tp_step(batch_size, model_size, seq_len, h_local, n, lr,
                        causal, resolve_attn(attn_impl), sequence_parallel)
    return launch(step, _shard(params, mesh, TP_SPECS), jnp.asarray(seeds),
                  mesh, param_specs=TP_SPECS, seed_spec=P())


def make_tp_step(batch_size: int, model_size: int, seq_len: int,
                 h_local: int, n_shards: int, lr: float = LR,
                 causal: bool = True, attn=None,
                 sequence_parallel: bool = False):
    """One TP step for one shard — the shared builder behind
    ``train_transformer_tp`` (tests shard_map this directly to pin the
    comms schedule against the real implementation)."""
    if sequence_parallel and seq_len % n_shards:
        raise ValueError(f"seq_len={seq_len} not divisible by model-axis "
                         f"size {n_shards} (sequence-parallel TP shards "
                         "tokens)")
    t_local = seq_len // n_shards if sequence_parallel else seq_len
    block = sp_block if sequence_parallel else tp_block

    def step(params: TransformerParams, seed) -> TransformerParams:
        x, dloss_dx = _reshape_batch(seed, batch_size, seq_len, model_size,
                                     params.w1.dtype)
        if sequence_parallel:
            r = axis_index(MODEL_AXIS)
            x, dloss_dx = (
                lax.dynamic_slice_in_dim(t, r * t_local, t_local, 1)
                for t in (x, dloss_dx))

        def fwd(p):
            y = x
            for l in range(p.w1.shape[0]):
                y = block(p.ln1[l], p.wq[l], p.wk[l], p.wv[l], p.wo[l],
                          p.ln2[l], p.w1[l], p.w2[l], y, h_local,
                          causal=causal, attn=attn)
            return y

        with jax.named_scope("tf"):
            with jax.named_scope("fwd"):
                # sequence-parallel LN gains see only this shard's
                # tokens: typed varying like the token shard they scale
                _, vjp = jax.vjp(fwd, params._replace(
                    ln1=vary(params.ln1, MODEL_AXIS),
                    ln2=vary(params.ln2, MODEL_AXIS))
                    if sequence_parallel else params)
            with jax.named_scope("bwd"):
                grads = vjp(dloss_dx)[0]
            if sequence_parallel:
                with jax.named_scope("comm"):
                    # LN gains saw only this shard's tokens: sum over the
                    # model axis. Everything else saw full (gathered)
                    # tokens and is complete per shard.
                    grads = grads._replace(
                        ln1=grad_reduce(grads.ln1, MODEL_AXIS),
                        ln2=grad_reduce(grads.ln2, MODEL_AXIS))
            # projection/FFN grads are shard-local (each shard owns its
            # heads/features); in the plain form LN grads replicate —
            # data and dx are identical on all shards after the f-gate
            # psums
            with jax.named_scope("optim"):
                return sgd(params, grads, lr)

    return step


def train_transformer_seq(params: TransformerParams, seeds,
                          batch_size: int, model_size: int, mesh,
                          lr: float = LR, *, seq_len: int, n_heads: int,
                          causal: bool = True,
                          seq_impl: str = "ring") -> TransformerParams:
    """Long-context training: the sequence dim sharded over the ``"seq"``
    axis — the first-class path that makes ring attention / Ulysses a
    *training* capability rather than an op-level demo.

    Everything token-pointwise (LN, projections, FFN, residuals) runs on
    the shard's own ``T/n`` tokens untouched; only attention crosses
    shards, via the hand-written ring (KV blocks rotating over
    ``ppermute``, ``sequence.ring_attention``) or Ulysses (two
    ``all_to_all``s trading heads for sequence). No device ever holds the
    full ``[T, T]`` score matrix — or, for the ring, even the full
    sequence of activations.

    Within a data replica, data is replicated like TP (every seq shard
    generates the step's full batch from the seed and slices its own
    token block — global causal positions stay exact); weight grads are
    per-shard partials over the token dim, summed with one ``psum`` per
    step (SUM, unscaled LR, ``train_ffns.py:165`` semantics).

    A 2-D ``(data, seq)`` mesh composes long context with data
    parallelism: the seed schedule shards strided over ``data`` (each
    data replica trains its own steps, DDP-style) while each replica's
    sequence shards over ``seq`` — the grad psum then rides both axes.
    Differential guarantees (tests/test_transformer.py):
    seq-only == ``train_transformer_single``; data x seq ==
    ``train_transformer_ddp`` over the data axis alone.
    """
    from .sequence import resolve_seq_attn
    require_axes(mesh, SEQ_AXIS)
    n = mesh.shape[SEQ_AXIS]
    dp = dict(mesh.shape).get(DATA_AXIS, 1)
    _validate_shapes(batch_size, seq_len, model_size, n_heads)
    attn = resolve_seq_attn(seq_impl, n, n_heads, seq_len)
    t_local = seq_len // n

    def step(params: TransformerParams, seed) -> TransformerParams:
        x, dloss_dx = _reshape_batch(seed, batch_size, seq_len, model_size,
                                     params.w1.dtype)
        r = axis_index(SEQ_AXIS)
        # this shard's token block (global batch regenerated from the
        # seed, so positions/causality are exact without a scatter)
        x, dloss_dx = (lax.dynamic_slice_in_dim(t, r * t_local, t_local, 1)
                       for t in (x, dloss_dx))

        # weight grads are partial sums over this shard's tokens — and,
        # on a 2-D mesh, over the data replicas (DDP semantics)
        axes = (SEQ_AXIS, DATA_AXIS) if dp > 1 else (SEQ_AXIS,)
        with jax.named_scope("seq"):
            with jax.named_scope("fwd"):
                _, vjp = jax.vjp(
                    lambda p: transformer_fwd(p, x, n_heads, causal,
                                              attn), vary(params, axes))
            with jax.named_scope("bwd"):
                grads = vjp(dloss_dx)[0]
            with jax.named_scope("comm"):
                # one fused psum over both axes per leaf, not one per
                # axis
                grads = jax.tree_util.tree_map(
                    lambda g: grad_reduce(g, axes),
                    grads)
            with jax.named_scope("optim"):
                return sgd(params, grads, lr)

    if dp > 1:
        return launch_strided(step, clone_params(params), seeds, mesh,
                              DATA_AXIS, P())
    return launch(step, clone_params(params), jnp.asarray(seeds), mesh,
                  param_specs=P(), seed_spec=P())


def train_transformer_hybrid(params: TransformerParams, seeds,
                             batch_size: int, model_size: int, mesh,
                             lr: float = LR, *, seq_len: int, n_heads: int,
                             causal: bool = True,
                             attn_impl: str | None = None
                             ) -> TransformerParams:
    """Hybrid DDP x TP on a 2-D ``(data, model)`` mesh — the BASELINE
    config-4 composition on the transformer: TP's two per-block psums ride
    the ``"model"`` axis inside each block, DDP's weight-grad psum rides
    the orthogonal ``"data"`` axis once per step (``hybrid.py`` semantics
    on the transformer surface). Seeds shard strided over ``data``
    (``train_ffns.py:182``); params shard over ``model`` only."""
    require_axes(mesh, DATA_AXIS, MODEL_AXIS)
    n = mesh.shape[MODEL_AXIS]
    h_local = _validate_tp(params, n_heads, n)
    _validate_shapes(batch_size, seq_len, model_size, n_heads)
    attn = resolve_attn(attn_impl)

    def step(params: TransformerParams, seed) -> TransformerParams:
        x, dloss_dx = _reshape_batch(seed, batch_size, seq_len, model_size,
                                     params.w1.dtype)

        def fwd(p):
            y = x
            for l in range(p.w1.shape[0]):
                y = tp_block(p.ln1[l], p.wq[l], p.wk[l], p.wv[l], p.wo[l],
                             p.ln2[l], p.w1[l], p.w2[l], y, h_local,
                             causal=causal, attn=attn)
            return y

        with jax.named_scope("tf"):
            with jax.named_scope("fwd"):
                _, vjp = jax.vjp(fwd, vary(params, DATA_AXIS))
            with jax.named_scope("bwd"):
                grads = vjp(dloss_dx)[0]
            with jax.named_scope("comm"):
                # TP leaves weight grads complete within a model shard;
                # the data axis still needs the DDP reduction (orthogonal
                # psums, the 2-D mesh composition)
                grads = jax.tree_util.tree_map(
                    lambda g: grad_reduce(g, DATA_AXIS), grads)
            with jax.named_scope("optim"):
                return sgd(params, grads, lr)

    # params: sharded over model, replicated over data; seeds: one strided
    # column per data shard, same column for every model shard
    return launch_strided(step, _shard(params, mesh, TP_SPECS), seeds,
                          mesh, DATA_AXIS, TP_SPECS)
