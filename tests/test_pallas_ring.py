"""The hand-scheduled ICI ring collectives (``ops/pallas_ring.py``) —
closing SURVEY §2.7's explicit-control ledger row.

Differential pins run the kernels under the Mosaic TPU *interpreter* on
the fake 8-device mesh (real semaphore/remote-DMA semantics, the same
code path a chip runs minus the silicon); the AOT test compiles the ring
against a real v5e-8 topology, proving the kernel passes actual Mosaic
constraints and that the lowered module carries OUR custom call where
``psum`` would have emitted an XLA all-reduce."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from distributed_llm_code_samples_tpu.ops.pallas_ring import (
    ppermute_dma, ring_all_reduce)
from distributed_llm_code_samples_tpu.parallel import DATA_AXIS


def _sm(mesh, fn):
    # check_vma=False: the Mosaic interpreter's vma propagation is
    # incomplete (JAX asks for exactly this workaround); the kernels
    # type their outputs shard-varying via out_shape vma regardless
    return jax.shard_map(fn, mesh=mesh, in_specs=P(DATA_AXIS, None),
                         out_specs=P(DATA_AXIS, None), check_vma=False)


def test_ppermute_dma_matches_lax_ppermute(mesh8):
    """One explicit RDMA hop == lax.ppermute's right rotation, exactly."""
    x = jax.random.normal(jax.random.PRNGKey(0), (8 * 4, 16))
    got = _sm(mesh8, functools.partial(ppermute_dma, axis_name=DATA_AXIS,
                                       interpret=True))(x)
    want = _sm(mesh8, lambda v: lax.ppermute(
        v, DATA_AXIS, [(i, (i + 1) % 8) for i in range(8)]))(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ring_all_reduce_matches_psum(mesh8):
    """The 2-phase ring == lax.psum to f32 reduction-order tolerance,
    across several draws (the kernel's semaphore protocol is concurrent:
    repeats catch ordering races a single run can miss)."""
    ring = _sm(mesh8, functools.partial(ring_all_reduce,
                                        axis_name=DATA_AXIS,
                                        interpret=True))
    oracle = _sm(mesh8, lambda v: lax.psum(v, DATA_AXIS))
    for i in range(3):
        x = jax.random.normal(jax.random.PRNGKey(i), (8 * 16, 32))
        np.testing.assert_allclose(np.asarray(ring(x)),
                                   np.asarray(oracle(x)),
                                   rtol=1e-6, atol=1e-6)


def test_ring_all_reduce_3d_operand(mesh8):
    """Non-2D operands reshape through the ring unchanged."""
    x = jax.random.normal(jax.random.PRNGKey(5), (8 * 8, 4, 8))
    got = _sm(mesh8, functools.partial(ring_all_reduce,
                                       axis_name=DATA_AXIS,
                                       interpret=True))(x)
    want = _sm(mesh8, lambda v: lax.psum(v, DATA_AXIS))(x)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_ring_all_reduce_rejects_indivisible(mesh8):
    """Chunking needs leading-dim divisibility by the ring size."""
    x = jnp.ones((8 * 9, 8))  # local rows 9, not divisible by 8
    with pytest.raises(ValueError, match="not divisible by ring"):
        _sm(mesh8, functools.partial(ring_all_reduce,
                                     axis_name=DATA_AXIS,
                                     interpret=True))(x)


def test_ring_identifying_contributions(mesh8):
    """Every device's contribution reaches every chunk exactly once:
    device r contributes 10^r, so any lost/duplicated hop shows as a
    wrong digit — the test that caught both semaphore races during
    development (phase-2 backpressure, inter-phase capacity leakage)."""
    n = 8
    contrib = jnp.asarray([float(10 ** r) for r in range(n)])
    x = jnp.repeat(contrib, n)[:, None] * jnp.ones((n * n, 8))
    got = _sm(mesh8, functools.partial(ring_all_reduce,
                                       axis_name=DATA_AXIS,
                                       interpret=True))(x)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.full((n * n, 8), 11111111.0))


def test_ddp_with_pallas_ring_comm_matches_psum(mesh4):
    """The escape hatch load-bearing in a real strategy: train_ddp with
    comm="pallas_ring" (per-layer grad reduction through the
    hand-scheduled RDMA ring) == the psum path, to ring-order
    tolerance."""
    from distributed_llm_code_samples_tpu.data import make_seed_schedule
    from distributed_llm_code_samples_tpu.models import init_ffn_stack
    from distributed_llm_code_samples_tpu.parallel import train_ddp
    params = init_ffn_stack(jax.random.PRNGKey(42), 64, 3)
    seeds = make_seed_schedule(8, random_seed=7)
    want = train_ddp(params, seeds, 32, 64, mesh4, lr=0.1)
    got = train_ddp(params, seeds, 32, 64, mesh4, lr=0.1,
                    comm="pallas_ring")
    np.testing.assert_allclose(np.asarray(got.w1), np.asarray(want.w1),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(got.w2), np.asarray(want.w2),
                               rtol=1e-5, atol=1e-7)


def test_ddp_rejects_unknown_comm(mesh4):
    from distributed_llm_code_samples_tpu.data import make_seed_schedule
    from distributed_llm_code_samples_tpu.models import init_ffn_stack
    from distributed_llm_code_samples_tpu.parallel import train_ddp
    params = init_ffn_stack(jax.random.PRNGKey(0), 64, 2)
    with pytest.raises(ValueError, match="unknown comm"):
        train_ddp(params, make_seed_schedule(4, random_seed=1), 32, 64,
                  mesh4, lr=0.1, comm="nccl")


@pytest.mark.parametrize("n", [2, 4])
def test_ring_all_reduce_small_rings(n):
    """Edge ring sizes: n=2 has a single step per phase (no capacity
    waits at all — the drain accounting must still zero the semaphores);
    n=4 covers the odd leftover split."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:n]), (DATA_AXIS,))
    x = jax.random.normal(jax.random.PRNGKey(3), (n * 2 * n, 8))
    got = _sm(mesh, functools.partial(ring_all_reduce,
                                      axis_name=DATA_AXIS,
                                      interpret=True))(x)
    want = _sm(mesh, lambda v: lax.psum(v, DATA_AXIS))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_ring_all_gather_and_reduce_scatter_match_xla(mesh8):
    """The standalone phase kernels == their XLA counterparts (the
    all_gather/reduce_scatter conventions the FSDP strategy consumes)."""
    from distributed_llm_code_samples_tpu.parallel.collectives import (
        all_gather, reduce_scatter)
    from distributed_llm_code_samples_tpu.ops.pallas_ring import (
        ring_all_gather, ring_reduce_scatter)
    x = jax.random.normal(jax.random.PRNGKey(2), (8 * 16, 32))
    got = _sm(mesh8, functools.partial(ring_all_gather,
                                       axis_name=DATA_AXIS,
                                       interpret=True))(x)
    want = _sm(mesh8, lambda v: all_gather(v, DATA_AXIS, dim=0))(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got = _sm(mesh8, functools.partial(ring_reduce_scatter,
                                       axis_name=DATA_AXIS,
                                       interpret=True))(x)
    want = _sm(mesh8, lambda v: reduce_scatter(v, DATA_AXIS, dim=0))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_fsdp_with_pallas_ring_comm_matches_psum(mesh4):
    """FSDP's ENTIRE comm pattern through the ring kernels (per-layer
    ring_all_gather of the param shards, ring_reduce_scatter of the
    grads) == the XLA path — plain and under the bf16 gather policy."""
    from distributed_llm_code_samples_tpu.data import make_seed_schedule
    from distributed_llm_code_samples_tpu.models import init_ffn_stack
    from distributed_llm_code_samples_tpu.parallel import train_fsdp
    params = init_ffn_stack(jax.random.PRNGKey(42), 64, 3)
    seeds = make_seed_schedule(8, random_seed=7)
    for mixed in (False, True):
        want = train_fsdp(params, seeds, 32, 64, mesh4, lr=0.1,
                          mixed=mixed)
        got = train_fsdp(params, seeds, 32, 64, mesh4, lr=0.1,
                         mixed=mixed, comm="pallas_ring")
        np.testing.assert_allclose(np.asarray(got.w1),
                                   np.asarray(want.w1),
                                   rtol=1e-5, atol=1e-7,
                                   err_msg=f"mixed={mixed}")
        np.testing.assert_allclose(np.asarray(got.w2),
                                   np.asarray(want.w2),
                                   rtol=1e-5, atol=1e-7,
                                   err_msg=f"mixed={mixed}")


def test_all_to_all_dma_matches_lax(mesh8):
    """The dense peer fan-out kernel == lax.all_to_all (tiled, dim 0/0
    — the EP-dispatch/Ulysses transport shape), exactly, repeated (all
    n-1 transfers are in flight at once; repeats catch ordering races)."""
    from distributed_llm_code_samples_tpu.parallel.collectives import (
        all_to_all)
    from distributed_llm_code_samples_tpu.ops.pallas_ring import (
        all_to_all_dma)
    for i in range(3):
        x = jax.random.normal(jax.random.PRNGKey(i), (8 * 16, 32))
        got = _sm(mesh8, functools.partial(all_to_all_dma,
                                           axis_name=DATA_AXIS,
                                           interpret=True))(x)
        want = _sm(mesh8, lambda v: all_to_all(v, DATA_AXIS,
                                               split_dim=0,
                                               concat_dim=0))(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_all_to_all_dma_identifying_blocks(mesh8):
    """Every (source, destination) block lands exactly once and exactly
    where it belongs: block (r, j) carries the value 10*r + j; after the
    exchange device r must hold 10*j + r at position j."""
    n = 8
    r_ids = jnp.repeat(jnp.arange(n, dtype=jnp.float32), n)
    j_ids = jnp.tile(jnp.arange(n, dtype=jnp.float32), n)
    x = (10 * r_ids + j_ids)[:, None] * jnp.ones((n * n, 8))
    from distributed_llm_code_samples_tpu.ops.pallas_ring import (
        all_to_all_dma)
    got = _sm(mesh8, functools.partial(all_to_all_dma,
                                       axis_name=DATA_AXIS,
                                       interpret=True))(x)
    want = (10 * j_ids + r_ids)[:, None] * jnp.ones((n * n, 8))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_moe_ep_with_pallas_a2a_matches_psum(mesh4_expert):
    """Expert parallelism with comm="pallas_a2a": both dispatch/return
    exchanges (and their autodiff transposes inside the step's vjp)
    through the peer fan-out kernel == the XLA all_to_all path, for both
    dispatch forms."""
    from distributed_llm_code_samples_tpu.data import make_seed_schedule
    from distributed_llm_code_samples_tpu.models import init_moe_stack
    from distributed_llm_code_samples_tpu.parallel import train_moe_ep
    params = init_moe_stack(jax.random.PRNGKey(0), 32, 2, 8)
    seeds = make_seed_schedule(8, random_seed=5)
    for dispatch in ("dense", "scatter"):
        want = train_moe_ep(params, seeds, 64, 32, mesh4_expert, lr=0.1,
                            k=2, aux_coef=0.01, dispatch=dispatch)
        got = train_moe_ep(params, seeds, 64, 32, mesh4_expert, lr=0.1,
                           k=2, aux_coef=0.01, dispatch=dispatch,
                           comm="pallas_a2a")
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7,
                                       err_msg=dispatch)
