"""CPU-oracle collective tests — the ``test_nccl.py`` pattern (compute the
expected result with numpy, run the real collective on the 8-device mesh,
assert equality), plus the process-group-lifecycle and barrier probes of
``test_torch_distributed.py`` / ``test_mp_barrier_gpus.py`` in SPMD form."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from distributed_llm_code_samples_tpu.parallel import collectives as coll
from distributed_llm_code_samples_tpu.parallel import DATA_AXIS

N = 8


def _shard_run(fn, mesh, x, in_spec=P(DATA_AXIS), out_spec=P(DATA_AXIS)):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(in_spec,),
                                 out_specs=out_spec))(x)


def test_all_reduce_matches_numpy_oracle(mesh8):
    x = np.random.default_rng(0).normal(size=(N, 4, 5)).astype(np.float32)
    # oracle: every shard ends up with the sum over shards (test_nccl.py:22-27)
    expected = np.broadcast_to(x.sum(axis=0), (N, 4, 5))
    got = _shard_run(lambda s: coll.all_reduce(s, DATA_AXIS), mesh8,
                     jnp.asarray(x).reshape(N * 4, 5),
                     in_spec=P(DATA_AXIS), out_spec=P(DATA_AXIS))
    np.testing.assert_allclose(np.asarray(got).reshape(N, 4, 5), expected,
                               rtol=1e-6)


def test_all_gather_matches_numpy_oracle(mesh8):
    x = np.random.default_rng(1).normal(size=(N * 3, 4)).astype(np.float32)
    # oracle: every shard holds the concatenation (test_nccl.py:8-19)
    got = _shard_run(lambda s: coll.all_gather(s, DATA_AXIS, dim=0), mesh8,
                     jnp.asarray(x), out_spec=P(DATA_AXIS))
    got = np.asarray(got).reshape(N, N * 3, 4)
    for r in range(N):
        np.testing.assert_array_equal(got[r], x)


def test_reduce_scatter_matches_numpy_oracle(mesh8):
    rng = np.random.default_rng(2)
    # each shard holds a full [N*2, 3] array; after reduce_scatter shard r
    # holds rows [2r:2r+2] of the sum over shards (test_nccl.py:29-38)
    per_shard = rng.normal(size=(N, N * 2, 3)).astype(np.float32)
    expected = per_shard.sum(axis=0)

    def body(s):
        return coll.reduce_scatter(s, DATA_AXIS, dim=0)

    got = _shard_run(body, mesh8,
                     jnp.asarray(per_shard).reshape(N * N * 2, 3),
                     in_spec=P(DATA_AXIS), out_spec=P(DATA_AXIS))
    np.testing.assert_allclose(np.asarray(got), expected, rtol=1e-5,
                               atol=1e-6)


def test_reduce_scatter_is_gather_inverse(mesh8):
    # all_gather then reduce_scatter with a single contributor == identity*N
    x = np.random.default_rng(3).normal(size=(N * 2, 3)).astype(np.float32)

    def body(s):
        full = coll.all_gather(s, DATA_AXIS, dim=0)
        return coll.reduce_scatter(full, DATA_AXIS, dim=0)

    got = _shard_run(body, mesh8, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), N * x, rtol=1e-5)


def test_ring_shift_matches_numpy_roll(mesh8):
    x = np.arange(N * 2, dtype=np.float32).reshape(N * 2, 1)

    def body(s):
        return coll.ring_shift(s, DATA_AXIS, shift=1)

    got = np.asarray(_shard_run(body, mesh8, jnp.asarray(x)))
    # shard r receives shard r-1's rows: a roll by one shard (2 rows)
    np.testing.assert_array_equal(got, np.roll(x, 2, axis=0))


def test_ring_shift_full_cycle_identity(mesh8):
    x = np.random.default_rng(4).normal(size=(N, 3)).astype(np.float32)

    def body(s):
        y = s
        for _ in range(N):
            y = coll.ring_shift(y, DATA_AXIS, shift=1)
        return y

    got = np.asarray(_shard_run(body, mesh8, jnp.asarray(x)))
    np.testing.assert_allclose(got, x, rtol=1e-6)


def test_axis_index_is_rank(mesh8):
    def body(s):
        return s + coll.axis_index(DATA_AXIS).astype(jnp.float32)

    got = np.asarray(_shard_run(body, mesh8, jnp.zeros((N, 1))))
    np.testing.assert_array_equal(got[:, 0], np.arange(N, dtype=np.float32))


def test_barrier_preserves_value(mesh8):
    x = np.random.default_rng(5).normal(size=(N, 3)).astype(np.float32)

    def body(s):
        return coll.barrier(s, DATA_AXIS)

    got = np.asarray(_shard_run(body, mesh8, jnp.asarray(x)))
    np.testing.assert_array_equal(got, x)


def test_grad_reduce_both_regimes(mesh8):
    """grad_reduce must sum exactly once whether the cotangent was already
    auto-reduced (plain-op transpose against the replicated ``w``) or
    arrives partial (a hand-written custom_vjp rule, which JAX holds to
    its primal's type — so ``w`` enters it through ``coll.vary`` and the
    rule's per-shard cotangent is legitimately varying).
    Both losses below are mathematically identical: sum over shards of
    w . x_shard, so dw = sum(x) in both cases."""
    x = np.random.default_rng(7).normal(size=(N, 4)).astype(np.float32)
    w = np.random.default_rng(8).normal(size=(4,)).astype(np.float32)

    @jax.custom_vjp
    def dot_manual(w, xs):
        return jnp.vdot(w, xs)

    dot_manual.defvjp(lambda w, xs: (jnp.vdot(w, xs), (w, xs)),
                      lambda res, dy: (dy * res[1], dy * res[0]))

    def make_loss(dot, enter=lambda w: w):
        def body(w, xs):  # w replicated, xs one shard row
            g = jax.grad(lambda w: dot(w, xs[0]))(enter(w))
            return coll.grad_reduce(g, DATA_AXIS)

        return jax.jit(jax.shard_map(body, mesh=mesh8,
                                     in_specs=(P(), P(DATA_AXIS)),
                                     out_specs=P()))

    expected = x.sum(axis=0)
    plain = make_loss(lambda w, xs: jnp.vdot(w, xs))(jnp.asarray(w),
                                                     jnp.asarray(x))
    manual = make_loss(dot_manual, lambda w: coll.vary(w, DATA_AXIS))(
        jnp.asarray(w), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(plain), expected, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(manual), expected, rtol=1e-6)


def test_repeated_collective_rounds(mesh8):
    # test_torch_distributed.py:13-21 — 10 rounds of all_reduce on the same
    # group; value after k rounds of summing N copies is x * N^k.
    x = np.full((N, 1), 1.0, dtype=np.float32)

    def body(s):
        y = s
        for _ in range(3):
            y = coll.all_reduce(y, DATA_AXIS)
        return y

    got = np.asarray(_shard_run(body, mesh8, jnp.asarray(x)))
    np.testing.assert_allclose(got, x * N ** 3, rtol=1e-6)
