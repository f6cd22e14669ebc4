"""Language-model trainers: single-device, DDP, FSDP/ZeRO-3, Megatron TP.

The LM family (``models.lm``) is the transformer stack plus the pieces the
reference mocked away — embeddings, a real cross-entropy objective
(``ops.xent``), a tied head — so the strategies here are the transformer
trainers (``parallel/transformer.py``) extended over that surface:

- **DDP**: replicated params, strided seed shards, one grad ``psum`` per
  step (SUM, unscaled LR — ``train_ffns.py:165`` semantics).
- **FSDP/ZeRO-3**: every leaf sharded over the data axis (blocks on their
  stacked layer dim, ``wte``/``wpe`` on rows, ``ln_f`` on features),
  gathered transiently; grads return pre-scattered through the gathers'
  ``psum_scatter`` transposes.
- **TP (Megatron-LM)**: the block stack shards as in
  ``parallel/transformer.py`` (heads column-, ``wo``/``w2`` row-parallel);
  the embedding and the tied head shard the **vocab** dim — each shard owns
  ``V/n`` rows of ``wte``, looks up / scores only its own slice, and the
  cross-entropy runs **vocab-parallel**: max, normalizer, and target-logit
  terms each complete with one collective over the model axis
  (``vp_xent``), so the full ``[N, V]`` logits never exist on any device —
  the memory-critical piece at real vocab sizes, where the logits would
  dwarf every activation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import LR
from ..data import lm_batch_from_seed
from ..models.ffn_stack import clone_params
from ..models.lm import LMParams, lm_loss
from ..models.transformer import transformer_block, transformer_fwd
from ..ops.norm import layernorm
from ..ops.xent import xent_loss
from ..optim import check_state_args, sgd
from .collectives import (all_gather, all_reduce, axis_index,
                          grad_reduce, vary)
from .launcher import launch, launch_strided
from .mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS, require_axes
from .transformer import (TP_SPECS, _f_gate, _shard, _validate_shapes,
                          _validate_tp, resolve_attn, tp_block)

def _lm_fsdp_specs() -> LMParams:
    from .transformer import FSDP_SPECS
    return LMParams(wte=P(DATA_AXIS, None), wpe=P(DATA_AXIS, None),
                    blocks=FSDP_SPECS, ln_f=P(DATA_AXIS))


def _lm_tp_specs() -> LMParams:
    return LMParams(wte=P(MODEL_AXIS, None), wpe=P(), blocks=TP_SPECS,
                    ln_f=P())


def _validate_lm(batch_size: int, seq_len: int, model_size: int,
                 n_heads: int, params: LMParams) -> None:
    _validate_shapes(batch_size, seq_len, model_size, n_heads)
    if seq_len > params.max_seq_len:
        raise ValueError(f"seq_len={seq_len} exceeds the model's "
                         f"max_seq_len={params.max_seq_len}")


def resolve_head(head_impl: str | None):
    """Map a ``head_impl`` name to the LM head+loss op ``models.lm.lm_loss``
    plugs in: None/"oracle" = materialized logits + hand-VJP xent
    (``ops/xent.py``); "fused" = the fused Pallas head
    (``ops.pallas_xent.head_xent`` — online logsumexp over vocab tiles,
    no ``[N, V]`` array in either direction; interpret mode
    automatically off-TPU)."""
    if head_impl in (None, "oracle"):
        return None
    if head_impl == "fused":
        from ..ops.pallas_xent import head_xent
        interpret = jax.default_backend() != "tpu"
        return lambda h, w, t: head_xent(h, w, t, interpret)
    raise ValueError(f"unknown head_impl {head_impl!r} "
                     "(expected 'oracle' or 'fused')")


def _make_step(batch_size: int, model_size: int, seq_len: int,
               n_heads: int, lr: float, attn=None, reduce_axes=(),
               optimizer=None, batch_fn=None, head=None,
               force_reduce: bool = False, mixed: bool = False):
    """One update step on the real LM objective; ``batch_size`` is
    tokens/step (seq folded, CLI convention ``train_ffns.py:379``).
    Without ``optimizer`` it's the reference's stateless inline SGD
    (``(params, seed) -> params``); with one, the carry is ``(params,
    opt_state)`` — the full LLM loop (AdamW + clipping + schedules all
    compose through ``optim.py``). ``batch_fn(seed) -> (tokens,
    targets)`` overrides the synthetic seeds-as-dataset source — the hook
    real-text training plugs into (``data.text_batch_from_seed``)."""
    b = batch_size // seq_len

    def grads_of(params, seed):
        tokens, targets = (batch_fn(seed) if batch_fn is not None else
                           lm_batch_from_seed(seed, b, seq_len,
                                              params.vocab))
        with jax.named_scope("fwd"):
            # autodiff strategy: jax.grad traces forward and transpose in
            # one call, so the "fwd" region also tags the backward ops
            # (the naming-map caveat, utils/trace_analysis.py)
            # replicated weights enter the hand-written rules typed
            # varying over the axes this shard's batch varies on; their
            # cotangents come back per-shard partials for "comm"
            grads = jax.grad(lm_loss)(vary(params, reduce_axes), tokens,
                                      targets, n_heads, attn, head, mixed)
        if reduce_axes:
            with jax.named_scope("comm"):
                # force_reduce: the launcher runs check_vma=False
                # (interpret-mode multi-tile Pallas kernels can't
                # type-check), which erases the provenance signal
                # grad_reduce keys on AND stops the transpose machinery's
                # auto-psum — cotangents of replicated params arrive
                # partial. Unconditional psum is then the correct (single)
                # reduction — the expert.py pallas_a2a contract, pinned
                # there both ways.
                grads = jax.tree_util.tree_map(
                    lambda g: grad_reduce(g, reduce_axes,
                                          force=force_reduce), grads)
        return grads

    def step(params: LMParams, seed) -> LMParams:
        # named-scope regions (lm/fwd, lm/comm on DDP meshes, lm/optim)
        with jax.named_scope("lm"):
            grads = grads_of(params, seed)
            with jax.named_scope("optim"):
                return sgd(params, grads, lr)

    def step_opt(carry, seed):
        params, state = carry
        with jax.named_scope("lm"):
            grads = grads_of(params, seed)
            with jax.named_scope("optim"):
                return optimizer.update(grads, state, params, lr)

    return step if optimizer is None else step_opt


def train_lm_single(params: LMParams, seeds, batch_size: int,
                    model_size: int, mesh=None, lr: float = LR, *,
                    seq_len: int, n_heads: int,
                    attn_impl: str | None = None, optimizer=None,
                    opt_state=None, return_state: bool = False,
                    batch_fn=None, head_impl: str | None = None,
                    mixed: bool = False):
    """Single-device LM trainer — the oracle the parallel forms are pinned
    to. ``optimizer``/``opt_state``/``return_state`` follow the DDP
    contract (``ddp.py``): stateful rules thread ``(params, state)``
    through the scan and segments resume exactly. ``batch_fn(seed) ->
    (tokens, targets)`` swaps the synthetic data source for a real one
    (e.g. ``data.text_batch_from_seed`` windows over the embedded
    corpus). ``mixed`` runs the bf16-trunk / f32-head-and-master policy
    (``models.lm.lm_loss(mixed=True)``).

    Compile-cache caveat: ``optimizer`` and ``batch_fn`` are STATIC jit
    arguments hashed by identity — reuse the SAME objects across calls
    (segmented runs, checkpoint resume, bench loops). A fresh lambda or
    optimizer per call silently recompiles every call and grows the jit
    cache."""
    _validate_lm(batch_size, seq_len, model_size, n_heads, params)
    check_state_args(optimizer, opt_state, return_state)

    if optimizer is None:
        return _run_lm_single(clone_params(params), jnp.asarray(seeds),
                              batch_size, model_size, lr, seq_len,
                              n_heads, attn_impl, batch_fn, head_impl,
                              mixed)

    state = optimizer.init(params) if opt_state is None else opt_state
    out, state = _run_lm_single_opt(
        (clone_params(params), state), jnp.asarray(seeds), batch_size,
        model_size, lr, seq_len, n_heads, attn_impl, optimizer, batch_fn,
        head_impl, mixed)
    return (out, state) if return_state else out


@functools.partial(jax.jit, static_argnums=tuple(range(2, 11)),
                   donate_argnums=0)
def _run_lm_single(params, seeds, batch_size, model_size, lr, seq_len,
                   n_heads, attn_impl, batch_fn, head_impl,
                   mixed=False):
    """Module-level jit (the ``single.py`` pattern): repeat calls with
    the same static config — including the same ``optimizer``/``batch_fn``
    *objects*, which hash by identity — reuse the compiled program.
    Segmented runs (checkpointing, bench best-of-N loops,
    ``train_real_text.py``) pay one compile instead of one per call."""
    step = _make_step(batch_size, model_size, seq_len, n_heads, lr,
                      resolve_attn(attn_impl), batch_fn=batch_fn,
                      head=resolve_head(head_impl), mixed=mixed)
    return lax.scan(lambda p, s: (step(p, s), None), params, seeds)[0]


@functools.partial(jax.jit, static_argnums=tuple(range(2, 12)))
def _run_lm_single_opt(carry, seeds, batch_size, model_size, lr, seq_len,
                       n_heads, attn_impl, optimizer, batch_fn, head_impl,
                       mixed=False):
    # no donation: callers may hold/reuse the opt_state they passed in
    step = _make_step(batch_size, model_size, seq_len, n_heads, lr,
                      resolve_attn(attn_impl), optimizer=optimizer,
                      batch_fn=batch_fn, head=resolve_head(head_impl),
                      mixed=mixed)
    return lax.scan(lambda c, s: (step(c, s), None), carry, seeds)[0]


def _vma_check(attn_impl, head_impl=None) -> bool:
    """Whether the launcher may run shard_map's vma typing.

    Flash attention: off only in interpret mode (the Pallas
    interpreter's vma propagation is incomplete — jax's own error
    suggests check_vma=False); the compiled TPU kernels pass full
    checking (the AOT tests pin it).

    The fused head: off on EVERY backend. Under vma-on, the tied
    ``wte``'s cotangent has MIXED provenance — the embedding-gather
    contribution arrives auto-psummed (plain-op transpose) while the
    kernel's hand-written ``dw`` arrives partial — and their sum is
    typed varying, so any downstream psum double-counts the
    already-reduced embedding part (scaled by the axis size). The
    vma-off force-reduce contract (``grad_reduce(force=True)``) keeps
    every cotangent partial and reduces exactly once; the oracle head
    never hits this because both of its wte uses are plain ops."""
    if head_impl == "fused":
        return False
    return not (attn_impl == "flash"
                and jax.default_backend() != "tpu")


def train_lm_ddp(params: LMParams, seeds, batch_size: int, model_size: int,
                 mesh, lr: float = LR, *, seq_len: int, n_heads: int,
                 attn_impl: str | None = None, optimizer=None,
                 opt_state=None, return_state: bool = False,
                 head_impl: str | None = None, mixed: bool = False,
                 guard=None, guard_state=None, return_guard: bool = False):
    """DDP: replicated params, strided seeds, grads summed per step.
    ``optimizer`` threads replicated state (the ``ddp.py`` contract).
    ``head_impl="fused"`` swaps the tied head + xent for the fused
    Pallas kernels (``ops/pallas_xent.py``) per shard. ``mixed`` runs
    each shard's step under the LM bf16 policy (bf16 trunk, f32
    head/grads — grads stay f32, so the psum semantics are unchanged
    and the DDP==FSDP==single differentials hold in mixed mode).
    ``guard``/``guard_state``/``return_guard``: the launcher-level
    in-graph skip-step guardrail (``runtime/guardrails.py``)."""
    require_axes(mesh, DATA_AXIS)
    _validate_lm(batch_size, seq_len, model_size, n_heads, params)
    check_state_args(optimizer, opt_state, return_state)
    from ..runtime.guardrails import check_guard_args
    check_guard_args(guard, guard_state, return_guard)
    check = _vma_check(attn_impl, head_impl)
    # force_reduce under vma-off: the unconditional-psum reduction
    # contract (see _make_step)
    step = _make_step(batch_size, model_size, seq_len, n_heads, lr,
                      resolve_attn(attn_impl), reduce_axes=(DATA_AXIS,),
                      optimizer=optimizer, head=resolve_head(head_impl),
                      force_reduce=not check, mixed=mixed)
    gkw = ({} if guard is None
           else dict(guard=guard, guard_state=guard_state))
    if optimizer is None:
        out = launch_strided(step, clone_params(params), seeds, mesh,
                             DATA_AXIS, P(), check_vma=check, **gkw)
    else:
        state = optimizer.init(params) if opt_state is None else opt_state
        out = launch_strided(step, clone_params(params), seeds, mesh,
                             DATA_AXIS, P(), state=state, state_specs=P(),
                             return_state=return_state, check_vma=check,
                             **gkw)
    if guard is not None and not return_guard:
        out = out[0]
    return out


def train_lm_fsdp(params: LMParams, seeds, batch_size: int, model_size: int,
                  mesh, lr: float = LR, *, seq_len: int, n_heads: int,
                  attn_impl: str | None = None, optimizer=None,
                  opt_state=None, return_state: bool = False,
                  head_impl: str | None = None, mixed: bool = False):
    """FSDP/ZeRO-3 over the whole LM surface: block stacks gathered layer
    by layer (the transformer FSDP loop), the embedding/head table and
    positions gathered once per step — transiently, so peak param memory
    stays ``O(|params|/n + one layer)``. All grads come back pre-scattered
    through the gathers' ``psum_scatter`` transposes; sharded update.

    With ``optimizer``, its state is created from — and lives as — the
    LOCAL param shards: full ZeRO-3 on the LM (params, grads, AND
    optimizer state all 1/n per device; the elementwise update needs no
    collective).

    ``mixed`` (the LM bf16 policy): block shards are cast to bf16
    BEFORE their per-layer gathers — half the collective bytes, the
    FFN-FSDP mixed stance — and the trunk runs bf16; ``wte`` gathers
    once in f32 (it serves the f32 head) with the embedding lookup cast
    after, so the math matches ``lm_loss(mixed=True)`` leaf for leaf
    and the FSDP==DDP==single differentials keep their power."""
    require_axes(mesh, DATA_AXIS)
    n = mesh.shape[DATA_AXIS]
    _validate_lm(batch_size, seq_len, model_size, n_heads, params)
    check_state_args(optimizer, opt_state, return_state)
    for name, leaf in [("wte", params.wte), ("wpe", params.wpe),
                       ("ln_f", params.ln_f)]:
        if leaf.shape[0] % n:
            raise ValueError(f"{name} dim {leaf.shape[0]} not divisible by "
                             f"{n} shards")
    for name, leaf in zip(params.blocks._fields, params.blocks):
        if leaf.shape[1] % n:
            raise ValueError(f"blocks.{name} dim {leaf.shape[1]} not "
                             f"divisible by {n} shards")
    attn = resolve_attn(attn_impl)
    head = resolve_head(head_impl)
    b = batch_size // seq_len
    vocab = params.vocab  # the global count — p.wte is a shard inside step

    def grads_of(params: LMParams, seed):
        tokens, targets = lm_batch_from_seed(seed, b, seq_len, vocab)

        def loss_fn(p: LMParams):
            bf16 = jnp.bfloat16
            with jax.named_scope("comm"):
                wte = all_gather(p.wte, DATA_AXIS, dim=0)
                wpe = all_gather(p.wpe, DATA_AXIS, dim=0)
                ln_f = all_gather(p.ln_f, DATA_AXIS, dim=0)
            if mixed:
                # trunk in bf16 (embedding lookup + positions cast
                # after the f32 wte gather — wte also serves the f32
                # head); ln_f cast matches lm_loss(mixed=True)
                x = wte.astype(bf16)[tokens] + wpe[:seq_len].astype(bf16)
                ln_f = ln_f.astype(bf16)
            else:
                x = wte[tokens] + wpe[:seq_len]
            for l in range(p.blocks.w1.shape[0]):
                # mixed: shards cast BEFORE the gather — half the
                # collective bytes (the FFN-FSDP mixed stance); cast of
                # the shard then concat == concat then cast, so the
                # values equal the single-device bf16 trunk's
                with jax.named_scope("comm"):
                    full = [all_gather(leaf[l].astype(bf16) if mixed
                                       else leaf[l], DATA_AXIS, dim=0)
                            for leaf in p.blocks]
                x = transformer_block(*full, x, n_heads, causal=True,
                                      attn=attn)
            h = layernorm(ln_f, x)
            if mixed:
                h = h.astype(jnp.float32)
            if head is not None:
                return head(h.reshape(-1, h.shape[-1]), wte,
                            targets.reshape(-1))
            logits = h.reshape(-1, h.shape[-1]) @ wte.T
            return xent_loss(logits.reshape(-1, wte.shape[0]),
                             targets.reshape(-1))

        with jax.named_scope("fwd"):
            return jax.grad(loss_fn)(params)

    def step(params: LMParams, seed) -> LMParams:
        with jax.named_scope("lm"):
            grads = grads_of(params, seed)
            with jax.named_scope("optim"):
                return sgd(params, grads, lr)

    def step_opt(carry, seed):
        params, state = carry
        with jax.named_scope("lm"):
            grads = grads_of(params, seed)
            with jax.named_scope("optim"):
                return optimizer.update(grads, state, params, lr)

    sharded = _shard(params, mesh, _lm_fsdp_specs())
    check = _vma_check(attn_impl, head_impl)
    if optimizer is None:
        return launch_strided(step, sharded, seeds, mesh, DATA_AXIS,
                              _lm_fsdp_specs(), check_vma=check)
    # zeros_like of the sharded params keeps their shardings: the state
    # enters shard_map already 1/n per device; scalars replicate
    state = optimizer.init(sharded) if opt_state is None else opt_state
    return launch_strided(step_opt, sharded, seeds, mesh, DATA_AXIS,
                          _lm_fsdp_specs(), state=state,
                          state_specs=_lm_state_specs(
                              state, _lm_fsdp_specs()),
                          return_state=return_state, check_vma=check)


# ---------------------------------------------------------------------------
# Vocab-parallel pieces (Megatron-LM): embedding + cross-entropy over the
# model axis, hand-differentiated where nonlinear.


def vp_embed(wte_local: jax.Array, tokens: jax.Array,
             axis: str = MODEL_AXIS) -> jax.Array:
    """Vocab-parallel embedding lookup: each shard resolves only tokens in
    its ``[offset, offset + V/n)`` row range (zeros elsewhere) and one
    ``psum`` completes the rows. Linear, so ``jax.vjp``'s exact transposes
    (psum -> identity, gather -> scatter-add) give each shard the complete
    gradient for its own rows."""
    v_local = wte_local.shape[0]
    offset = axis_index(axis) * v_local
    local = tokens - offset
    in_range = (local >= 0) & (local < v_local)
    rows = wte_local[jnp.clip(local, 0, v_local - 1)]
    return all_reduce(jnp.where(in_range[..., None], rows, 0), axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def vp_xent(logits_local: jax.Array, targets: jax.Array,
            axis: str = MODEL_AXIS) -> jax.Array:
    """Vocab-parallel cross-entropy: ``logits_local [N, V/n]`` is this
    shard's slice of the row; the row max (``pmax``), normalizer
    (``psum`` of local sum-exp), and target logit (``psum`` of the
    in-range pick) each complete with one collective — no shard ever holds
    a full ``[N, V]`` row. Backward is the hand-written
    ``(softmax - onehot) * dy / N`` restricted to the local slice, with no
    collective at all (the residuals are already local)."""
    loss, _ = _vp_xent_fwd(logits_local, targets, axis)
    return loss


def _vp_xent_fwd(logits_local, targets, axis):
    v_local = logits_local.shape[-1]
    offset = axis_index(axis) * v_local
    m = lax.pmax(jnp.max(logits_local, axis=-1, keepdims=True), axis)
    e = jnp.exp(logits_local - m)
    sumexp = all_reduce(jnp.sum(e, axis=-1, keepdims=True), axis)
    lse = jnp.log(sumexp) + m                                   # [N, 1]
    local_t = targets - offset
    in_range = (local_t >= 0) & (local_t < v_local)
    picked = jnp.take_along_axis(
        logits_local, jnp.clip(local_t, 0, v_local - 1)[:, None],
        axis=-1)[:, 0]
    z_t = all_reduce(jnp.where(in_range, picked, 0.0), axis)
    loss = jnp.mean(lse[:, 0] - z_t)
    return loss, (e / sumexp, jnp.clip(local_t, 0, v_local - 1), in_range)


def _vp_xent_bwd(axis, res, dy):
    probs_local, local_t, in_range = res
    n = probs_local.shape[0]
    dz = probs_local * (dy / n)
    dz = dz.at[jnp.arange(n), local_t].add(
        jnp.where(in_range, -dy / n, 0.0))
    return dz, None


vp_xent.defvjp(_vp_xent_fwd, _vp_xent_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def vp_head_xent(h: jax.Array, wte_local: jax.Array, targets: jax.Array,
                 axis: str = MODEL_AXIS,
                 interpret: bool = False) -> jax.Array:
    """Vocab-parallel FUSED head + cross-entropy: ``vp_xent``'s
    collective structure with ``ops.pallas_xent``'s kernels underneath —
    no shard ever materializes even its LOCAL ``[N, V/n]`` logits (the
    oracle path builds and residual-saves them; ~400 MB/shard at the
    bench family shape). Each shard's kernel pass produces merge-ready
    ``(lse_local, tz_local)`` statistics over its own vocab rows; one
    ``pmax`` + two ``psum``s complete the row max, normalizer, and
    target pick — the same three collectives as ``vp_xent``. Backward
    recomputes logit tiles per shard: ``dw`` is shard-complete (its own
    vocab rows), ``dh`` comes back PARTIAL over the model axis — the
    caller's ``_f_gate`` completes it, exactly like the materialized
    path's ``h @ wte_local.T`` transpose."""
    loss, _ = _vp_head_xent_fwd(h, wte_local, targets, axis, interpret)
    return loss


def _vp_head_xent_fwd(h, wte_local, targets, axis, interpret):
    from ..ops.pallas_xent import head_xent_stats
    v_local = wte_local.shape[0]
    t_local = targets - axis_index(axis) * v_local
    lse_l, tz_l = head_xent_stats(h, wte_local, t_local,
                                  interpret=interpret)
    # stable cross-shard logsumexp merge: lse_g = M + log(sum exp(lse-M))
    m = lax.pmax(lse_l, axis)
    lse_g = m + jnp.log(all_reduce(jnp.exp(lse_l - m), axis))
    z_t = all_reduce(tz_l, axis)  # the target lives in exactly one slice
    loss = jnp.mean(lse_g - z_t)
    return loss, (h, wte_local, t_local, lse_g)


def _vp_head_xent_bwd(axis, interpret, res, dy):
    from ..ops.pallas_xent import head_xent_bwd
    h, wte_local, t_local, lse_g = res
    # the kernels compute dz = (exp(z - lse_g) - onehot) / N on this
    # shard's slice: dw complete for its rows, dh a partial sum
    dh, dw = head_xent_bwd(dy, h, wte_local, t_local, lse_g,
                           interpret=interpret)
    return dh, dw, None


vp_head_xent.defvjp(_vp_head_xent_fwd, _vp_head_xent_bwd)


def _make_tp_step(batch_size: int, model_size: int, seq_len: int,
                  h_local: int, vocab: int, lr: float, attn=None,
                  data_axes=(), optimizer=None,
                  head_impl: str | None = None,
                  force_reduce: bool = False,
                  interpret: bool | None = None):
    """One vocab-parallel TP step for one model shard; ``data_axes`` adds
    the orthogonal DDP reduction for the hybrid 2-D mesh (every leaf is a
    partial sum over those axes; LN/positions additionally over the model
    axis — one fused psum per leaf, ``grad_reduce`` on an axis tuple).
    With ``optimizer``, the carry is ``(params, opt_state)`` and the state
    shards exactly like the params (elementwise update — no collective)."""
    b = batch_size // seq_len

    def grads_of(params: LMParams, seed):
        tokens, targets = lm_batch_from_seed(seed, b, seq_len, vocab)
        f = _f_gate(MODEL_AXIS)

        def loss_fn(p: LMParams):
            x = vp_embed(p.wte, tokens) + p.wpe[:seq_len]
            for l in range(p.blocks.w1.shape[0]):
                blk = p.blocks
                x = tp_block(blk.ln1[l], blk.wq[l], blk.wk[l], blk.wv[l],
                             blk.wo[l], blk.ln2[l], blk.w1[l], blk.w2[l],
                             x, h_local, causal=True, attn=attn)
            h = f(layernorm(p.ln_f, x))       # dx from the head: psum
            if head_impl == "fused":
                interp = (jax.default_backend() != "tpu"
                          if interpret is None else interpret)
                return vp_head_xent(
                    h.reshape(-1, model_size), p.wte,
                    targets.reshape(-1), MODEL_AXIS, interp)
            logits_local = h.reshape(-1, model_size) @ p.wte.T
            return vp_xent(logits_local, targets.reshape(-1))

        with jax.named_scope("fwd"):
            # hybrid: every leaf is replicated over the data axes and
            # meets that replica's batch — typed varying going in
            grads = jax.grad(loss_fn)(vary(params, data_axes))
        with jax.named_scope("comm"):
            # wpe and the LN gains saw complete, replicated dx — but the
            # cotangents produced inside the hand-written rules come back
            # typed varying; grad_reduce psums exactly the pending ones.
            # Head/projection/FFN grads are shard-complete on the model
            # axis and reduce only over the data axes (hybrid).
            # force_reduce: vma-off launch (interpret-mode fused head) —
            # unconditional psum, the _make_step contract.
            model_and_data = (MODEL_AXIS,) + data_axes
            grads = grads._replace(
                wpe=grad_reduce(grads.wpe, model_and_data,
                                force=force_reduce),
                ln_f=grad_reduce(grads.ln_f, model_and_data,
                                 force=force_reduce),
                blocks=grads.blocks._replace(
                    ln1=grad_reduce(grads.blocks.ln1, model_and_data,
                                    force=force_reduce),
                    ln2=grad_reduce(grads.blocks.ln2, model_and_data,
                                    force=force_reduce)))
            if data_axes:
                # the four leaves above are already fully reduced (their
                # psum covered the data axes too); under force their
                # second psum would NOT no-op — restore them after the
                # sweep
                done = (grads.wpe, grads.ln_f, grads.blocks.ln1,
                        grads.blocks.ln2)
                grads = jax.tree_util.tree_map(
                    lambda g: grad_reduce(g, data_axes,
                                          force=force_reduce), grads)
                grads = grads._replace(
                    wpe=done[0], ln_f=done[1],
                    blocks=grads.blocks._replace(ln1=done[2],
                                                 ln2=done[3]))
        return grads

    def step(params: LMParams, seed) -> LMParams:
        with jax.named_scope("lm"):
            grads = grads_of(params, seed)
            with jax.named_scope("optim"):
                return sgd(params, grads, lr)

    def step_opt(carry, seed):
        params, state = carry
        with jax.named_scope("lm"):
            grads = grads_of(params, seed)
            with jax.named_scope("optim"):
                return optimizer.update(grads, state, params, lr)

    return step if optimizer is None else step_opt


def train_lm_tp(params: LMParams, seeds, batch_size: int, model_size: int,
                mesh, lr: float = LR, *, seq_len: int, n_heads: int,
                attn_impl: str | None = None, optimizer=None,
                opt_state=None, return_state: bool = False,
                head_impl: str | None = None, guard=None,
                guard_state=None, return_guard: bool = False):
    """Megatron-LM TP over the model axis: blocks shard heads/features
    (``tp_block``), ``wte`` shards vocab rows serving both the parallel
    embedding and the tied parallel head, and the loss runs vocab-parallel
    (``vp_xent``). ``wpe``/LN grads replicate (complete ``dx`` on every
    shard, the ``_f_gate`` discipline); ``wte``/block grads are
    shard-complete. Data replicated, as in ``train_transformer_tp``.

    ``optimizer`` threads state sharded exactly like the params
    (``zeros_like`` of the sharded leaves; the elementwise update needs
    no collective) — Megatron's optimizer layout."""
    require_axes(mesh, MODEL_AXIS)
    n = mesh.shape[MODEL_AXIS]
    h_local = _validate_tp(params.blocks, n_heads, n)
    _validate_lm(batch_size, seq_len, model_size, n_heads, params)
    check_state_args(optimizer, opt_state, return_state)
    if params.vocab % n:
        raise ValueError(f"vocab={params.vocab} not divisible by "
                         f"model-axis size {n}")
    resolve_head(head_impl)  # shared validation (one accepted set)
    check = _vma_check(attn_impl, head_impl)
    # check_vma/force_reduce follow _vma_check (the fused head runs the
    # vma-off reduction contract on EVERY backend); interpret is a
    # separate, backend-only decision — the fused head must still run
    # the COMPILED kernels on TPU. interpret=None lets _make_tp_step's
    # backend fallback decide (tying it to `not check` ran
    # the Pallas head in interpret mode on real TPU).
    step = _make_tp_step(batch_size, model_size, seq_len, h_local,
                         params.vocab, lr, resolve_attn(attn_impl),
                         optimizer=optimizer, head_impl=head_impl,
                         force_reduce=not check, interpret=None)
    from ..runtime.guardrails import check_guard_args
    check_guard_args(guard, guard_state, return_guard)
    gkw = ({} if guard is None
           else dict(guard=guard, guard_state=guard_state))
    sharded = _shard(params, mesh, _lm_tp_specs())
    if optimizer is None:
        out = launch(step, sharded, jnp.asarray(seeds), mesh,
                     param_specs=_lm_tp_specs(), seed_spec=P(),
                     check_vma=check, **gkw)
    else:
        # zeros_like of sharded params keeps their shardings; scalar
        # bookkeeping (step counts) replicates
        state = optimizer.init(sharded) if opt_state is None else opt_state
        out = launch(step, sharded, jnp.asarray(seeds), mesh,
                     param_specs=_lm_tp_specs(), seed_spec=P(),
                     state=state,
                     state_specs=_lm_state_specs(state, _lm_tp_specs()),
                     return_state=return_state, check_vma=check, **gkw)
    if guard is not None and not return_guard:
        out = out[0]
    return out


def tp_generate(params: LMParams, prompt, n_new: int, mesh, *,
                n_heads: int, use_rope: bool = False) -> jax.Array:
    """Megatron-sharded greedy decode: the KV cache shards over **heads**
    on the model axis (each shard caches and attends its own ``H/n``
    heads — the inference memory win: cache bytes per chip drop 1/n),
    the tied head scores **vocab-parallel** (each shard's ``V/n``
    columns), and the global argmax completes with one tiny
    ``all_gather`` of per-shard ``(max, index)`` pairs per position.
    One jitted ``shard_map`` scan decodes the whole batch; the result is
    replicated. Differential-pinned to the single-device ``generate``.
    GQA models compose: the cache is sized by each shard's LOCAL kv
    heads (``KV % n`` validated), so the inference memory win multiplies
    with the group factor. The compiled program is cached on the static
    decode config (``_tp_decode_program``), so repeat decodes don't
    re-trace."""
    return _tp_decode(params, prompt, n_new, mesh, n_heads, use_rope,
                      temperature=0.0, seed=0)


def tp_sample(params: LMParams, prompt, n_new: int, mesh, *,
              n_heads: int, temperature: float = 1.0, seed: int = 0,
              use_rope: bool = False) -> jax.Array:
    """Stochastic Megatron-sharded decode: ``tp_generate``'s program with
    the pick swapped for a Gumbel-max categorical draw from
    ``softmax(logits / temperature)`` — an EXACT sample computed without
    ever materializing softmax probabilities across the vocab-parallel
    shards (each shard perturbs its local logits with iid Gumbel noise
    keyed on ``(seed, position, shard)``; the greedy path's tiny
    ``(max, index)`` all_gather completes the draw). Deterministic given
    ``seed``; draws differ from the single-device ``sample``'s (a
    different noise stream), but the DISTRIBUTION is identical."""
    if temperature <= 0:
        raise ValueError(f"temperature must be > 0, got {temperature} "
                         "(use tp_generate for greedy decode)")
    return _tp_decode(params, prompt, n_new, mesh, n_heads, use_rope,
                      temperature=float(temperature), seed=seed)


def tp_decode_specs() -> LMParams:
    """The Megatron decode layout's partition specs (vocab-sharded
    ``wte``, head-sharded blocks, replicated positions/LNs) — one
    definition shared by ``tp_generate``/``tp_sample`` and the serving
    engine (``decode/engine.py``), so the two decode paths can never
    drift onto different layouts."""
    return _lm_tp_specs()


def _validate_tp_decode(params: LMParams, n_heads: int, mesh) -> int:
    """What the Megatron decode layout needs of a model, checked before
    anything is laid out: heads, KV heads, FFN width and vocabulary all
    divide by the model axis. Returns the axis size."""
    require_axes(mesh, MODEL_AXIS)
    n = mesh.shape[MODEL_AXIS]
    _validate_tp(params.blocks, n_heads, n)
    if params.vocab % n:
        raise ValueError(f"vocab={params.vocab} not divisible by "
                         f"model-axis size {n}")
    return n


def tp_shard_params(params: LMParams, mesh,
                    n_heads: int | None = None) -> LMParams:
    """Lay the LM params out in the Megatron decode layout (vocab/head
    sharded) ONCE. ``tp_generate``/``tp_sample`` and the decode engine
    detect the layout and skip their per-call reshard copy, so repeat
    decodes (serving loops) pay neither a retrace
    (the program is cached) nor a per-call host-side param copy. With
    ``n_heads`` (the serving engine's ``--tp`` set-up) the layout's
    divisibility is checked first."""
    if n_heads is None:
        require_axes(mesh, MODEL_AXIS)
    else:
        _validate_tp_decode(params, n_heads, mesh)
    if _tp_sharded_already(params, mesh):
        return params
    return _shard(params, mesh, _lm_tp_specs())


def _tp_sharded_already(params: LMParams, mesh) -> bool:
    """True iff every param leaf already carries the exact decode
    NamedSharding (as produced by ``tp_shard_params``)."""
    specs = jax.tree_util.tree_leaves(
        _lm_tp_specs(), is_leaf=lambda v: isinstance(v, P))
    leaves = jax.tree_util.tree_leaves(params)
    return len(leaves) == len(specs) and all(
        getattr(a, "sharding", None) == NamedSharding(mesh, s)
        for a, s in zip(leaves, specs))


def _tp_decode(params, prompt, n_new, mesh, n_heads, use_rope,
               temperature, seed):
    """Shared validate-and-launch for the TP decode pair; the seed is a
    RUNTIME operand (new seeds draw new continuations from the SAME
    compiled program — no retrace, no cache thrash). Params already in
    the ``tp_shard_params`` layout skip the reshard copy."""
    n = _validate_tp_decode(params, n_heads, mesh)
    fn = _tp_decode_program(mesh, n_new, n_heads, params.vocab // n,
                            params.max_seq_len,
                            params.d_model // n_heads, use_rope,
                            temperature=temperature)
    sharded = (params if _tp_sharded_already(params, mesh)
               else _shard(params, mesh, _lm_tp_specs()))
    return fn(sharded, jnp.asarray(prompt), jnp.int32(seed))


@functools.lru_cache(maxsize=16)
def _tp_decode_program(mesh, n_new: int, n_heads: int, v_local: int,
                       max_t: int, dh: int, use_rope: bool,
                       temperature: float = 0.0):
    """Build (once per static decode config) the jitted shard_map decode
    program ``(sharded_params, prompt) -> tokens``. jax.jit's own cache
    then handles shape-polymorphic re-traces; repeat decodes hit the
    compiled program directly.
    ``temperature > 0`` switches the pick from greedy to an EXACT
    categorical sample via the Gumbel-max trick: each shard perturbs its
    local ``logits/T`` with iid Gumbel noise (key folded on
    ``(seed, position, shard)``) and the SAME tiny ``(max, index)``
    all_gather that completes the greedy argmax then completes the
    sample — softmax probabilities never materialize, sharded or not."""
    from ..models.lm import KVCache, decode_loop

    def decode_step_tp(p: LMParams, cache: KVCache, token, pos):
        from ..models.lm import cached_attn_step
        blk = p.blocks
        x = vp_embed(p.wte, token) + p.wpe[pos]             # [B, d]
        new_k, new_v = cache.k, cache.v
        for l in range(blk.w1.shape[0]):
            y, new_k, new_v = cached_attn_step(
                blk.ln1[l], blk.wq[l], blk.wk[l], blk.wv[l], blk.wo[l],
                new_k, new_v, l, x, pos, use_rope)          # local heads
            x = x + all_reduce(y, MODEL_AXIS)                # Megatron g
            h = layernorm(blk.ln2[l], x)
            x = x + all_reduce(
                jnp.maximum(h @ blk.w1[l].T, 0.0) @ blk.w2[l].T,
                MODEL_AXIS)                                  # Megatron g
        h = layernorm(p.ln_f, x)
        logits_local = h @ p.wte.T                           # [B, V/n]
        return logits_local, KVCache(new_k, new_v)

    def pick_global(logits_local, pos, seed):
        """argmax over the sharded vocab: each shard offers its local
        ``(max value, global index)`` pair, packed into ONE tiny
        ``[2, B]`` all_gather per position. The pack rides in f32
        regardless of the params' dtype: a bf16 lane would round the
        index (8-bit mantissa); f32 is exact while vocab < 2^24.
        With ``temperature > 0`` the local values are Gumbel-perturbed
        first (iid per global vocab index: the key folds in the shard),
        so the global argmax IS a categorical draw from softmax(z/T)."""
        z = logits_local
        if temperature > 0.0:
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(seed), pos),
                axis_index(MODEL_AXIS))
            z = (z.astype(jnp.float32) / temperature
                 + jax.random.gumbel(key, z.shape, jnp.float32))
        local_best = jnp.argmax(z, axis=-1)                  # [B]
        local_val = jnp.take_along_axis(
            z, local_best[:, None], axis=-1)[:, 0]
        offset = axis_index(MODEL_AXIS) * v_local
        packed = jnp.stack([
            local_val.astype(jnp.float32),
            (local_best + offset).astype(jnp.float32)])      # [2, B]
        g = all_gather(packed[None], MODEL_AXIS, dim=0)      # [n, 2, B]
        win = jnp.argmax(g[:, 0, :], axis=0)                 # [B]
        return jnp.take_along_axis(
            g[:, 1, :], win[None], axis=0)[0].astype(jnp.int32)

    def run(p: LMParams, prompt, seed):
        b = prompt.shape[0]
        # cache sized by the shard's LOCAL kv heads (wk's sharded row
        # count / dh): GQA shrinks it by the group factor, exactly as in
        # the single-device decode; contiguous head sharding keeps each
        # shard's q heads grouped with its own kv heads (KV % n == 0,
        # validated by _validate_tp)
        kv_local = p.blocks.wk.shape[1] // dh
        cache = KVCache(
            k=jnp.zeros((p.blocks.w1.shape[0], b, kv_local, max_t, dh),
                        p.wpe.dtype),
            v=jnp.zeros((p.blocks.w1.shape[0], b, kv_local, max_t, dh),
                        p.wpe.dtype))
        return decode_loop(
            lambda cache, token, pos: decode_step_tp(p, cache, token, pos),
            cache, prompt, n_new, max_t,
            lambda z, pos: pick_global(z, pos, seed))

    return jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(_lm_tp_specs(), P(), P()),
        out_specs=P(), check_vma=False))


def _lm_state_specs(state, specs):
    """Optimizer-state specs for a sharded-param layout: param-shaped
    subtrees (momentum velocities, Adam moments — ``LMParams`` instances)
    shard like the params (``specs`` — pass the caller's own layout);
    scalar bookkeeping (step counters) replicates."""

    def rec(s):
        if isinstance(s, LMParams):
            return specs
        if hasattr(s, "_fields"):                 # e.g. AdamState
            return type(s)(*(rec(x) for x in s))
        if isinstance(s, tuple):                  # scheduled-wrapper pairs
            return tuple(rec(x) for x in s)
        return P()

    return rec(state)


def train_lm_hybrid(params: LMParams, seeds, batch_size: int,
                    model_size: int, mesh, lr: float = LR, *, seq_len: int,
                    n_heads: int, attn_impl: str | None = None) -> LMParams:
    """Hybrid DDP x vocab-parallel TP on a 2-D ``(data, model)`` mesh:
    TP's per-block and vocab collectives ride the ``"model"`` axis inside
    each replica, DDP's weight-grad psum rides the orthogonal ``"data"``
    axis once per step (strided seeds, SUM, unscaled LR —
    ``train_ffns.py:182, :165`` semantics)."""
    require_axes(mesh, DATA_AXIS, MODEL_AXIS)
    n = mesh.shape[MODEL_AXIS]
    h_local = _validate_tp(params.blocks, n_heads, n)
    _validate_lm(batch_size, seq_len, model_size, n_heads, params)
    if params.vocab % n:
        raise ValueError(f"vocab={params.vocab} not divisible by "
                         f"model-axis size {n}")
    step = _make_tp_step(batch_size, model_size, seq_len, h_local,
                         params.vocab, lr, resolve_attn(attn_impl),
                         data_axes=(DATA_AXIS,))
    return launch_strided(step, _shard(params, mesh, _lm_tp_specs()),
                          seeds, mesh, DATA_AXIS, _lm_tp_specs())


def train_lm_seq(params: LMParams, seeds, batch_size: int, model_size: int,
                 mesh, lr: float = LR, *, seq_len: int, n_heads: int,
                 seq_impl: str = "ring",
                 attn_impl: str | None = None,
                 head_impl: str | None = None) -> LMParams:
    """Long-context LM training: the sequence dim sharded over the
    ``"seq"`` axis, attention crossing shards via the hand-written ring
    (or Ulysses), the real objective computed per token block.

    Everything token-pointwise — embedding lookup, positions, LNs,
    projections, FFN, the tied head, and the cross-entropy itself — runs
    on the shard's own ``T/n`` tokens. The global loss is the mean over
    all tokens, i.e. the mean of the (equal-sized) shard means scaled by
    ``1/n``; scaling each shard's local loss by ``1/n`` before ``psum``-ing
    the weight grads reproduces the single-device gradient exactly
    (pinned by the differential test). On a 2-D ``(data, seq)`` mesh the
    seed schedule additionally shards strided over ``data`` and the same
    psum rides both axes.

    ``attn_impl="flash"`` fuses the block compute (per ring hop / per
    Ulysses-local head) onto the Pallas flash kernels — the long-context
    path end to end: ICI ring across chips, online-softmax tiling in
    VMEM within each. ``head_impl="fused"`` does the same for the tied
    head + xent on the shard's own token block
    (``ops/pallas_xent.py``)."""
    from .sequence import resolve_seq_attn
    require_axes(mesh, SEQ_AXIS)
    n = mesh.shape[SEQ_AXIS]
    dp = dict(mesh.shape).get(DATA_AXIS, 1)
    _validate_lm(batch_size, seq_len, model_size, n_heads, params)
    attn = resolve_seq_attn(seq_impl, n, n_heads, seq_len,
                            attn_impl=attn_impl,
                            interpret=jax.default_backend() != "tpu")
    t_local = seq_len // n
    b = batch_size // seq_len
    vocab = params.vocab
    head = resolve_head(head_impl)
    check = _vma_check(attn_impl, head_impl)

    def step(params: LMParams, seed) -> LMParams:
        tokens, targets = lm_batch_from_seed(seed, b, seq_len, vocab)
        r = axis_index(SEQ_AXIS)
        # this shard's token block (full batch regenerated from the seed,
        # so ring causality over global positions stays exact)
        tokens, targets = (
            lax.dynamic_slice_in_dim(t, r * t_local, t_local, 1)
            for t in (tokens, targets))

        def loss_fn(p: LMParams):
            x = p.wte[tokens] + lax.dynamic_slice_in_dim(
                p.wpe, r * t_local, t_local, 0)
            x = transformer_fwd(p.blocks, x, n_heads, causal=True,
                                attn=attn)
            h = layernorm(p.ln_f, x)
            if head is not None:
                # local mean / n == this shard's share of the global mean
                return head(h.reshape(-1, h.shape[-1]), p.wte,
                            targets.reshape(-1)) / n
            logits = h @ p.wte.T
            # local mean / n == this shard's share of the global mean
            return xent_loss(logits.reshape(-1, vocab),
                             targets.reshape(-1)) / n

        axes = (SEQ_AXIS, DATA_AXIS) if dp > 1 else (SEQ_AXIS,)
        with jax.named_scope("lm"):
            with jax.named_scope("fwd"):
                grads = jax.grad(loss_fn)(vary(params, axes))
            with jax.named_scope("comm"):
                # vma-off (interpret-mode flash/fused head): force the
                # psum — grad_reduce would silently no-op on the partial
                # cotangents
                grads = jax.tree_util.tree_map(
                    lambda g: grad_reduce(g, axes, force=not check), grads)
            with jax.named_scope("optim"):
                return sgd(params, grads, lr)
    if dp > 1:
        return launch_strided(step, clone_params(params), seeds, mesh,
                              DATA_AXIS, P(), check_vma=check)
    return launch(step, clone_params(params), jnp.asarray(seeds), mesh,
                  param_specs=P(), seed_spec=P(), check_vma=check)
