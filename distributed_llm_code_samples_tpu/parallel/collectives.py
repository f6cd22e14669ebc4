"""Raw collective primitives, named after the reference's NCCL surface.

This is the native-surface ledger of SURVEY.md section 2.7 made code: every
collective the reference consumed through ``torch.distributed``/NCCL has a
TPU-native equivalent here, lowering to XLA collective HLOs that ride ICI:

=====================  ==============================  =======================
reference (NCCL)        usage                           here (XLA over ICI)
=====================  ==============================  =======================
``all_reduce(SUM)``     ``train_ffns.py:165,303,309``   ``lax.psum``
``all_gather``          ``train_ffns.py:203``           ``lax.all_gather``
``reduce_scatter(SUM)`` ``train_ffns.py:255-256``       ``lax.psum_scatter``
send/recv rings         (absent; BASELINE config 3)     ``lax.ppermute``
async handles+wait      ``train_ffns.py:165,170``       XLA async start/done
                                                        pairs, scheduler-driven
=====================  ==============================  =======================

All functions must be called under ``jax.shard_map`` with the named axis
bound by the mesh. Asynchrony is not expressed in user code: XLA emits
``all-reduce-start``/``all-reduce-done`` pairs and its latency-hiding
scheduler moves independent compute between them — the role the reference's
``async_op=True`` + ``handle.wait()`` discipline played by hand.
"""

from __future__ import annotations

import jax
from jax import lax


def all_reduce(x, axis_name: str):
    """Sum across the mesh axis — NCCL ``all_reduce(SUM)`` / ``dist.all_reduce``."""
    return lax.psum(x, axis_name)


def all_gather(x, axis_name: str, *, dim: int = 0):
    """Concatenate shards along ``dim`` across the axis — NCCL ``all_gather``.

    ``tiled=True`` matches the reference's ``torch.cat(sharded_ps)``
    re-assembly (``train_ffns.py:209``): output dim = shard dim * axis size.
    """
    return lax.all_gather(x, axis_name, axis=dim, tiled=True)


def reduce_scatter(x, axis_name: str, *, dim: int = 0):
    """Sum then scatter shards along ``dim`` — NCCL ``reduce_scatter(SUM)``
    (``train_ffns.py:255-256``)."""
    return lax.psum_scatter(x, axis_name, scatter_dimension=dim, tiled=True)


def vary(tree, axis_name):
    """Type every leaf of ``tree`` as varying over one axis (or a tuple
    of axes) — ``lax.pcast``, no data movement — where it is not already.

    This is how a replicated operand enters a hand-written ``custom_vjp``
    rule next to shard-varying ones. JAX holds a rule to its primal's
    type: the cotangent it returns for an argument must vary over exactly
    the axes the argument does. A rule fed a replicated weight and a
    shard's activations computes a per-shard partial — varying — for a
    primal that is not, and is refused. Cast first, and the partial is
    the right type; ``grad_reduce`` then sums it, once, where the
    strategy says so."""
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)

    def one(x):
        need = tuple(a for a in axes if a not in jax.typeof(x).vma)
        return lax.pcast(x, need, to="varying") if need else x

    return jax.tree_util.tree_map(one, tree)


def grad_reduce(g, axis_name, force: bool = False):
    """Sum a *gradient* across one axis (or a tuple of axes, one fused
    ``psum``) iff it is still a partial sum there.

    Under JAX's varying-manual-axes (vma) typing, a cotangent's type says
    which state it is in. Differentiated against a replicated primal, a
    plain op's transpose ends in the ``psum`` that undoes the implicit
    ``pcast``: the cotangent arrives already summed (axis absent from
    ``typeof(g).vma``). Differentiated against a primal the strategy
    first typed varying (``vary`` above — the only way a replicated
    weight may enter a hand-written ``custom_vjp`` rule beside a shard's
    activations), it arrives as the rule built it: a per-shard partial
    (axis present). An unconditional ``psum`` would double-reduce the
    former — grads scale by the axis size. The check is static at trace
    time.
    """
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    if force:
        # the vma-off contract (launcher ran check_vma=False — the
        # interpret-mode Pallas launches): typing is erased, transposes
        # do NOT auto-psum, every cotangent arrives partial — the
        # unconditional psum is then the correct single reduction.
        # Non-forced calls no-op in that regime (empty vma), which is
        # also part of the contract: the gates stand down and each
        # strategy's explicit force sweep reduces each leaf once.
        return lax.psum(g, axes)
    pending = tuple(a for a in axes if a in jax.typeof(g).vma)
    return lax.psum(g, pending) if pending else g


def all_to_all(x, axis_name: str, *, split_dim: int, concat_dim: int):
    """Transpose shard ownership of one dimension — NCCL ``all_to_all``
    (absent from the reference, which has no EP/Ulysses paths; SURVEY.md
    section 2.2). Splits ``split_dim`` across the axis and concatenates the
    received blocks on ``concat_dim`` (``tiled``)."""
    return lax.all_to_all(x, axis_name, split_axis=split_dim,
                          concat_axis=concat_dim, tiled=True)


def ring_shift(x, axis_name: str, *, shift: int = 1):
    """Neighbor exchange on the axis ring via ``ppermute`` — the send/recv
    primitive (used by ring attention and the pipeline path; the reference
    has no p2p, SURVEY.md section 2.2)."""
    n = lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def axis_index(axis_name: str):
    """This shard's coordinate on the axis — the reference's ``local_rank``."""
    return lax.axis_index(axis_name)


def barrier(x, axis_name: str):
    """In-program ordering fence across the axis: a zero-byte-ish psum that
    orders everything before it on every shard before anything after it —
    the SPMD answer to ``mp.Barrier`` (``test_mp_barrier_gpus.py:32-34``)."""
    token = lax.psum(jax.numpy.zeros(()), axis_name)
    return lax.optimization_barrier((x, token))[0]
