"""Fused LM-head + xent kernels (``ops/pallas_xent.py``) vs the oracle.

The oracle is the materialized path the models use by default:
``xent_loss(h @ w.T, targets)`` (``ops/xent.py`` — itself pinned against
``jax.grad`` in test_ops). The fused kernels must reproduce its loss and
both gradients without ever building ``[N, V]``, across single-tile and
multi-tile grids, through the public custom_vjp, and through the
single-device LM trainer. AOT: the kernels must Mosaic-compile for a
real v5e at the bench family shape.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_llm_code_samples_tpu.ops.pallas_xent import (
    head_xent, head_xent_bwd, head_xent_fwd)
from distributed_llm_code_samples_tpu.ops.xent import xent_loss


def _case(n=64, d=32, v=384, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = jax.random.normal(k1, (n, d))
    w = 0.02 * jax.random.normal(k2, (v, d))
    t = jax.random.randint(k3, (n,), 0, v)
    return h, w, t


def test_fwd_matches_oracle_single_tile():
    h, w, t = _case()
    loss, lse = head_xent_fwd(h, w, t, interpret=True)
    ref = xent_loss(h @ w.T, t)
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-6)
    ref_lse = jax.scipy.special.logsumexp(h @ w.T, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("bn,bv", [(16, 128), (64, 128), (16, 384)])
def test_multi_tile_grids_match_oracle(bn, bv):
    """The online-logsumexp accumulation across vocab tiles and the
    one-tile-owns-the-target pick must be exact for every grid shape."""
    h, w, t = _case()
    loss, lse = head_xent_fwd(h, w, t, block_n=bn, block_v=bv,
                              interpret=True)
    np.testing.assert_allclose(float(loss), float(xent_loss(h @ w.T, t)),
                               rtol=1e-6)
    dh, dw = head_xent_bwd(jnp.float32(1.0), h, w, t, lse, block_n=bn,
                           block_v=bv, interpret=True)
    g = jax.grad(lambda h, w: xent_loss(h @ w.T, t), argnums=(0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(dh), np.asarray(g[0]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(g[1]),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("v", [61, 200])
def test_prime_and_unaligned_vocab_pads(v):
    """Real vocabularies rarely have a lane-multiple divisor (GPT-2's
    50257 is prime): the vocab axis is zero-padded to the block multiple
    and the padded columns masked out — loss and grads must equal the
    oracle exactly, and dw must come back at the TRUE vocab size."""
    h, w, t = _case(v=v, seed=9)
    loss, lse = head_xent_fwd(h, w, t, block_v=128, interpret=True)
    np.testing.assert_allclose(float(loss), float(xent_loss(h @ w.T, t)),
                               rtol=1e-6)
    dh, dw = head_xent_bwd(jnp.float32(1.0), h, w, t, lse, block_v=128,
                           interpret=True)
    assert dw.shape == (v, w.shape[1])
    g = jax.grad(lambda h, w: xent_loss(h @ w.T, t), argnums=(0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(dh), np.asarray(g[0]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(g[1]),
                               rtol=1e-5, atol=1e-6)


def test_custom_vjp_grads_match_oracle():
    h, w, t = _case(seed=3)
    g0 = jax.grad(lambda h, w: xent_loss(h @ w.T, t), argnums=(0, 1))(h, w)
    g1 = jax.grad(lambda h, w: head_xent(h, w, t, True),
                  argnums=(0, 1))(h, w)
    for a, b in zip(g0, g1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_nonuniform_dy_scales_linearly():
    """The dy cotangent multiplies OUTSIDE the kernels; a non-unit
    upstream gradient must scale both grads exactly."""
    h, w, t = _case(seed=5)
    g1 = jax.grad(lambda h, w: head_xent(h, w, t, True),
                  argnums=(0, 1))(h, w)
    g3 = jax.grad(lambda h, w: 3.0 * head_xent(h, w, t, True),
                  argnums=(0, 1))(h, w)
    for a, b in zip(g1, g3):
        np.testing.assert_allclose(3.0 * np.asarray(a), np.asarray(b),
                                   rtol=1e-6)


def test_train_lm_single_fused_head_matches_oracle():
    """head_impl='fused' through the public trainer: same final params
    as the oracle path over a multi-step run."""
    from distributed_llm_code_samples_tpu.data import make_seed_schedule
    from distributed_llm_code_samples_tpu.models import init_lm
    from distributed_llm_code_samples_tpu.parallel import train_lm_single

    params = init_lm(jax.random.PRNGKey(0), 384, 32, 2, 64, n_heads=2)
    seeds = make_seed_schedule(3, random_seed=7)
    outs = [train_lm_single(params, seeds, 2 * 64, 32, lr=0.1, seq_len=64,
                            n_heads=2, head_impl=impl)
            for impl in (None, "fused")]
    for a, b in zip(jax.tree_util.tree_leaves(outs[0]),
                    jax.tree_util.tree_leaves(outs[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)


def test_lm_ddp_fsdp_fused_head_match_oracle():
    """head_impl='fused' through the DISTRIBUTED LM trainers on a
    4-device data mesh: DDP and FSDP (where the fused kernel consumes the
    all-gathered wte inside shard_map and dw flows back through the
    gather's psum_scatter transpose) both reproduce their oracle-head
    runs."""
    from distributed_llm_code_samples_tpu.data import make_seed_schedule
    from distributed_llm_code_samples_tpu.models import init_lm
    from distributed_llm_code_samples_tpu.parallel import (
        DATA_AXIS, make_mesh)
    from distributed_llm_code_samples_tpu.parallel.lm import (
        train_lm_ddp, train_lm_fsdp)

    params = init_lm(jax.random.PRNGKey(0), 384, 32, 2, 64, n_heads=2)
    seeds = make_seed_schedule(4, random_seed=7)
    mesh = make_mesh({DATA_AXIS: 4})
    for fn in (train_lm_ddp, train_lm_fsdp):
        outs = [fn(params, seeds, 4 * 64, 32, mesh, lr=0.1, seq_len=64,
                   n_heads=2, head_impl=impl)
                for impl in (None, "fused")]
        for a, b in zip(jax.tree_util.tree_leaves(outs[0]),
                        jax.tree_util.tree_leaves(outs[1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-6,
                                       err_msg=fn.__name__)


def test_resolve_head_rejects_unknown():
    from distributed_llm_code_samples_tpu.parallel.lm import resolve_head
    with pytest.raises(ValueError, match="unknown head_impl"):
        resolve_head("nope")


def test_train_lm_tp_fused_head_leaves_interpret_to_backend(monkeypatch):
    """Regression: ``train_lm_tp`` tied ``interpret`` to the
    vma decision (``not _vma_check(...)``), so ``head_impl='fused'`` —
    which runs vma-off on EVERY backend — forced the Pallas head into
    interpret mode on real TPU too, defeating the compiled kernels the
    AOT test pins. The trainer must pass ``interpret=None`` (the
    backend fallback inside ``_make_tp_step`` decides) while keeping
    ``force_reduce`` tied to the vma contract."""
    from distributed_llm_code_samples_tpu.data import make_seed_schedule
    from distributed_llm_code_samples_tpu.models import init_lm
    from distributed_llm_code_samples_tpu.parallel import (
        MODEL_AXIS, make_mesh)
    import distributed_llm_code_samples_tpu.parallel.lm as lm_mod

    seen = {}
    real = lm_mod._make_tp_step

    def spy(*a, **kw):
        seen.update(kw)
        return real(*a, **kw)

    monkeypatch.setattr(lm_mod, "_make_tp_step", spy)
    params = init_lm(jax.random.PRNGKey(0), 384, 32, 1, 64, n_heads=4)
    seeds = make_seed_schedule(1, random_seed=7)
    lm_mod.train_lm_tp(params, seeds, 2 * 64, 32,
                       make_mesh({MODEL_AXIS: 4}), lr=0.1,
                       seq_len=64, n_heads=4, head_impl="fused")
    assert seen["interpret"] is None
    assert seen["force_reduce"] is True


def test_vp_fused_head_matches_vp_oracle():
    """Vocab-parallel TP with the FUSED head (vp_head_xent: kernels per
    shard + the same pmax/psum merge as vp_xent, no local logits
    materialized) == the materialized vp_xent path, final params, on a
    4-way model mesh."""
    from distributed_llm_code_samples_tpu.data import make_seed_schedule
    from distributed_llm_code_samples_tpu.models import init_lm
    from distributed_llm_code_samples_tpu.parallel import (
        MODEL_AXIS, make_mesh)
    from distributed_llm_code_samples_tpu.parallel.lm import train_lm_tp

    params = init_lm(jax.random.PRNGKey(0), 384, 32, 2, 64, n_heads=4)
    seeds = make_seed_schedule(3, random_seed=7)
    mesh = make_mesh({MODEL_AXIS: 4})
    outs = [train_lm_tp(params, seeds, 2 * 64, 32, mesh, lr=0.1,
                        seq_len=64, n_heads=4, head_impl=impl)
            for impl in (None, "fused")]
    for a, b in zip(jax.tree_util.tree_leaves(outs[0]),
                    jax.tree_util.tree_leaves(outs[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)


def test_vp_fused_head_matches_single_device():
    """And transitively: the fused vocab-parallel path == the
    single-device oracle (the reference's cross-strategy allclose
    discipline, train_ffns.py:386-391, on the fused TP head)."""
    from distributed_llm_code_samples_tpu.data import make_seed_schedule
    from distributed_llm_code_samples_tpu.models import init_lm
    from distributed_llm_code_samples_tpu.parallel import (
        MODEL_AXIS, make_mesh, train_lm_single)
    from distributed_llm_code_samples_tpu.parallel.lm import train_lm_tp

    params = init_lm(jax.random.PRNGKey(2), 384, 32, 2, 64, n_heads=4)
    seeds = make_seed_schedule(3, random_seed=11)
    single = train_lm_single(params, seeds, 2 * 64, 32, lr=0.1,
                             seq_len=64, n_heads=4)
    mesh = make_mesh({MODEL_AXIS: 4})
    tp = train_lm_tp(params, seeds, 2 * 64, 32, mesh, lr=0.1,
                     seq_len=64, n_heads=4, head_impl="fused")
    for a, b in zip(jax.tree_util.tree_leaves(single),
                    jax.tree_util.tree_leaves(tp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-5)


def test_vp_fused_loss_value_with_pad_range_targets():
    """The PRIMAL loss under the fused vocab-parallel head, checked as a
    value (not through params): with V/n not lane-aligned, shifted
    out-of-slice targets can land in a shard's padded [V/n, vp) range —
    the -1e30 padding sentinel must not leak into the target-logit psum
    (the match is gated on true vocab columns)."""
    import functools
    from jax.sharding import PartitionSpec as P
    from distributed_llm_code_samples_tpu.ops.xent import xent_loss
    from distributed_llm_code_samples_tpu.parallel import (
        MODEL_AXIS, make_mesh)
    from distributed_llm_code_samples_tpu.parallel.lm import vp_head_xent

    # V=200, 4 shards -> v_local=50, vp pads to 128: shifted targets in
    # [50, 128) exist for every target in the NEXT shard's first rows
    N, d, V = 32, 16, 200
    h = jax.random.normal(jax.random.PRNGKey(0), (N, d))
    w = 0.02 * jax.random.normal(jax.random.PRNGKey(1), (V, d))
    t = jnp.arange(N, dtype=jnp.int32) + 50  # every slice-boundary case
    mesh = make_mesh({MODEL_AXIS: 4})
    f = jax.jit(jax.shard_map(
        functools.partial(vp_head_xent, axis=MODEL_AXIS, interpret=True),
        mesh=mesh, in_specs=(P(), P(MODEL_AXIS), P()), out_specs=P(),
        check_vma=False))
    loss = float(f(h, w, t))  # P(MODEL_AXIS) slices 50 rows per shard
    ref = float(xent_loss(h @ w.T, t))
    np.testing.assert_allclose(loss, ref, rtol=1e-6)


def test_moe_lm_ep_fused_head_matches_oracle():
    """head_impl='fused' through the expert-parallel MoE-LM trainer ==
    its oracle-head run on the 4-way expert mesh (router aux and the
    vma-off forced reduction included)."""
    from distributed_llm_code_samples_tpu.data import make_seed_schedule
    from distributed_llm_code_samples_tpu.models import init_moe_lm
    from distributed_llm_code_samples_tpu.parallel import (
        EXPERT_AXIS, make_mesh, train_moe_lm_ep)

    params = init_moe_lm(jax.random.PRNGKey(0), 384, 32, 2, 4, 64)
    seeds = make_seed_schedule(4, random_seed=7)
    mesh = make_mesh({EXPERT_AXIS: 4})
    outs = [train_moe_lm_ep(params, seeds, 4 * 64, 32, mesh, lr=0.1,
                            seq_len=64, n_heads=4, k=2, aux_coef=0.01,
                            head_impl=impl)
            for impl in (None, "fused")]
    for a, b in zip(jax.tree_util.tree_leaves(outs[0]),
                    jax.tree_util.tree_leaves(outs[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)


def test_vma_check_contract():
    """The fused head must run the vma-off force-reduce contract on
    EVERY backend: under vma-on the tied wte's cotangent mixes an
    auto-psummed embedding-gather part with the kernel's partial dw, and
    a downstream psum would double-count the former (scaled by the axis
    size). Flash alone keeps full checking on TPU."""
    from distributed_llm_code_samples_tpu.parallel.lm import _vma_check
    assert _vma_check(None, "fused") is False
    assert _vma_check("flash", "fused") is False
    # flash-only: off here exactly when interpreting (CPU suite)
    assert _vma_check("flash", None) == (jax.default_backend() == "tpu")
    assert _vma_check(None, None) is True
