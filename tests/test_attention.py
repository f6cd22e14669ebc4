"""Attention + sequence-parallel (ring attention) tests — the long-context
extension (absent from the reference, SURVEY.md section 5). Oracles: plain
softmax attention + jax autograd."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_code_samples_tpu.models.attention import (
    attention, attn_fwd, attn_bwd, mha, causal_mask)
from distributed_llm_code_samples_tpu.parallel import make_mesh, SEQ_AXIS
from distributed_llm_code_samples_tpu.parallel.sequence import (
    ring_attention, sequence_parallel_attention)

T, D = 64, 16


@pytest.fixture(scope="module")
def qkv():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(k1, (T, D)), jax.random.normal(k2, (T, D)),
            jax.random.normal(k3, (T, D)))


def _plain(q, k, v, causal):
    s = (q @ k.T) / jnp.sqrt(jnp.asarray(D, q.dtype))
    if causal:
        s = jnp.where(causal_mask(T, T), s, -jnp.inf)
    return jax.nn.softmax(s, -1) @ v


@pytest.mark.parametrize("causal", [True, False])
def test_attn_fwd_matches_plain(qkv, causal):
    q, k, v = qkv
    y, _ = attn_fwd(q, k, v, causal)
    np.testing.assert_allclose(y, _plain(q, k, v, causal), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("causal", [True, False])
def test_attn_bwd_matches_autograd(qkv, causal):
    q, k, v = qkv
    dy = jax.random.normal(jax.random.PRNGKey(9), (T, D))
    _, vjp = jax.vjp(lambda q, k, v: _plain(q, k, v, causal), q, k, v)
    dq_r, dk_r, dv_r = vjp(dy)
    _, (p,) = attn_fwd(q, k, v, causal)
    dq, dk, dv = attn_bwd(dy, q, k, v, p, causal)
    np.testing.assert_allclose(dq, dq_r, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dk, dk_r, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dv, dv_r, rtol=1e-5, atol=1e-6)


def test_custom_vjp_installs_manual_rule(qkv):
    q, k, v = qkv
    dy = jax.random.normal(jax.random.PRNGKey(3), (T, D))
    _, vjp_ref = jax.vjp(lambda q, k, v: _plain(q, k, v, True), q, k, v)
    _, vjp_man = jax.vjp(lambda q, k, v: attention(q, k, v, True), q, k, v)
    for a, b in zip(vjp_man(dy), vjp_ref(dy)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_mha_vmaps_over_heads():
    H = 4
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (H, T, D))
    k = jax.random.normal(k2, (H, T, D))
    v = jax.random.normal(k3, (H, T, D))
    y = mha(q, k, v, True)
    assert y.shape == (H, T, D)
    for h in range(H):
        np.testing.assert_allclose(y[h], _plain(q[h], k[h], v[h], True),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_oracle(qkv, causal):
    q, k, v = qkv
    mesh = make_mesh({SEQ_AXIS: 8})
    y = sequence_parallel_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(y), _plain(q, k, v, causal),
                               rtol=1e-5, atol=1e-6)


def test_ring_attention_4_shards(qkv):
    q, k, v = qkv
    mesh = make_mesh({SEQ_AXIS: 4})
    y = sequence_parallel_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(y), _plain(q, k, v, True),
                               rtol=1e-5, atol=1e-6)


def test_ring_attention_grad_flows(qkv):
    # autograd transposes the ring (ppermute transpose = reverse permute)
    from jax.sharding import PartitionSpec as P
    q, k, v = qkv
    mesh = make_mesh({SEQ_AXIS: 4})
    spec = P(SEQ_AXIS, None)

    def loss(q, k, v):
        f = jax.shard_map(lambda q, k, v: ring_attention(q, k, v, SEQ_AXIS),
                          mesh=mesh, in_specs=(spec, spec, spec),
                          out_specs=spec)
        return jnp.sum(f(q, k, v) ** 2)

    g_ring = jax.grad(loss)(q, k, v)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(_plain(q, k, v, True) ** 2))(
        q, k, v)
    np.testing.assert_allclose(g_ring, g_ref, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_grad_matches_oracle_all_inputs(causal):
    """The hand-written backward ring: dq/dk/dv each match the quadratic
    oracle's grads (non-causal exercises the all-blocks path; causal the
    skip-masked path)."""
    from jax.sharding import PartitionSpec as P
    key = jax.random.PRNGKey(7)
    q, k, v = (jax.random.normal(kk, (T, D)) for kk in jax.random.split(key, 3))
    mesh = make_mesh({SEQ_AXIS: 4})
    spec = P(SEQ_AXIS, None)
    f = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, SEQ_AXIS, causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    cot = jax.random.normal(jax.random.PRNGKey(9), (T, D))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * cot)

    g_ring = jax.grad(loss(f), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(lambda q, k, v: _plain(q, k, v, causal)),
                     argnums=(0, 1, 2))(q, k, v)
    for got, ref, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


def test_ring_attention_residual_memory_constant_in_ring_size():
    """The point of the hand-written backward (VERDICT r1 item 5): the
    forward saves O(T_local * d) residuals — per-shard compiled memory of
    the grad program must NOT grow with the ring size. Autograd through
    the rotation loop would stash every step's KV blocks
    (O(n * T_local * d)) and fail this."""
    from jax.sharding import PartitionSpec as P
    from distributed_llm_code_samples_tpu.utils.memory import compiled_memory
    t_local, d = 64, 32

    def mem_for(n):
        mesh = make_mesh({SEQ_AXIS: n})
        spec = P(SEQ_AXIS, None)
        f = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, SEQ_AXIS, True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)

        def loss(q, k, v):
            return jnp.sum(f(q, k, v))

        q = jax.device_put(
            jnp.ones((n * t_local, d)),
            jax.sharding.NamedSharding(mesh, spec))
        return compiled_memory(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)

    m2, m8 = mem_for(2), mem_for(8)
    if m2 is None or m8 is None:
        pytest.skip("backend exposes no memory analysis")
    # temps hold the residuals; identical T_local => identical per-shard
    # footprint regardless of ring size (small slack for scheduling noise)
    assert m8["temp_bytes"] <= m2["temp_bytes"] * 1.1, (m2, m8)


def test_sequence_parallel_rejects_indivisible(qkv):
    q, k, v = qkv
    mesh = make_mesh({SEQ_AXIS: 8})
    with pytest.raises(ValueError):
        sequence_parallel_attention(q[:60], k[:60], v[:60], mesh)


# --- Ulysses (all_to_all head-scatter) ------------------------------------

H = 8


@pytest.fixture(scope="module")
def qkv_heads():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    return (jax.random.normal(k1, (H, T, D)),
            jax.random.normal(k2, (H, T, D)),
            jax.random.normal(k3, (H, T, D)))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shards", [4, 8])
def test_ulysses_matches_mha_oracle(qkv_heads, causal, shards):
    from distributed_llm_code_samples_tpu.parallel import (
        ulysses_parallel_attention)
    q, k, v = qkv_heads
    mesh = make_mesh({SEQ_AXIS: shards})
    y = ulysses_parallel_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(y), np.asarray(mha(q, k, v, causal)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_flash_matches_oracle(qkv_heads, causal):
    """Ulysses with the fused Pallas flash kernels as the local attention
    (attn_impl='flash'): the a2a re-shard hands each shard full sequences
    of H/n heads, which flash tiles without materializing [T, T]; results
    equal the quadratic-oracle Ulysses path."""
    from distributed_llm_code_samples_tpu.parallel import (
        ulysses_parallel_attention)
    q, k, v = qkv_heads
    mesh = make_mesh({SEQ_AXIS: 4})
    y = ulysses_parallel_attention(q, k, v, mesh, causal=causal,
                                   attn_impl="flash")
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(mha(q, k, v, causal)),
                               rtol=1e-4, atol=1e-5)


def test_ulysses_equals_ring_per_head(qkv_heads):
    """The two sequence-parallel schemes agree with each other."""
    from distributed_llm_code_samples_tpu.parallel import (
        ulysses_parallel_attention)
    q, k, v = qkv_heads
    mesh = make_mesh({SEQ_AXIS: 4})
    y_u = ulysses_parallel_attention(q, k, v, mesh, causal=True)
    for h in range(H):
        y_r = sequence_parallel_attention(q[h], k[h], v[h], mesh, causal=True)
        np.testing.assert_allclose(np.asarray(y_u[h]), np.asarray(y_r),
                                   rtol=1e-5, atol=1e-6)


def test_ulysses_grad_flows(qkv_heads):
    from jax.sharding import PartitionSpec as P
    from distributed_llm_code_samples_tpu.parallel.sequence import (
        ulysses_attention)
    q, k, v = qkv_heads
    mesh = make_mesh({SEQ_AXIS: 4})
    spec = P(None, SEQ_AXIS, None)

    def loss(q, k, v):
        f = jax.shard_map(lambda q, k, v: ulysses_attention(q, k, v, SEQ_AXIS),
                          mesh=mesh, in_specs=(spec, spec, spec),
                          out_specs=spec)
        return jnp.sum(f(q, k, v) ** 2)

    g_u = jax.grad(loss)(q, k, v)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(mha(q, k, v, True) ** 2))(q, k, v)
    for a, b in zip(g_u, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_ulysses_rejects_indivisible_heads(qkv_heads):
    from distributed_llm_code_samples_tpu.parallel import (
        ulysses_parallel_attention)
    q, k, v = qkv_heads
    mesh = make_mesh({SEQ_AXIS: 8})
    with pytest.raises(ValueError, match="head count"):
        ulysses_parallel_attention(q[:6], k[:6], v[:6], mesh)


# --- Flash-within-ring: the fused long-context path (VERDICT r3 #8) ------

@pytest.mark.parametrize("causal", [False, True])
def test_flash_ring_matches_oracle_fwd_and_bwd(causal):
    """ring_attention(attn_impl="flash"): per-hop Pallas flash block
    compute inside the cross-chip ring == the full-sequence quadratic
    oracle, forward and all three gradients. The three hop programs
    (earlier block = non-causal kernel, diagonal = causal kernel, later
    = skipped) and the stable logsumexp merge are all on this path.
    check_vma=False: the Pallas interpreter's vma propagation is
    incomplete (jax's own error suggests exactly this workaround); the
    real-TPU path compiles with full checking."""
    from jax.sharding import PartitionSpec as P
    key = jax.random.PRNGKey(11)
    q, k, v = (jax.random.normal(kk, (T, D)) for kk in jax.random.split(key, 3))
    mesh = make_mesh({SEQ_AXIS: 4})
    spec = P(SEQ_AXIS, None)
    f = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, SEQ_AXIS, causal,
                                       attn_impl="flash", interpret=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    np.testing.assert_allclose(np.asarray(f(q, k, v)),
                               _plain(q, k, v, causal),
                               rtol=2e-5, atol=2e-5)
    cot = jax.random.normal(jax.random.PRNGKey(9), (T, D))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * cot)

    g_got = jax.grad(loss(f), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(lambda q, k, v: _plain(q, k, v, causal)),
                     argnums=(0, 1, 2))(q, k, v)
    for got, ref, name in zip(g_got, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


def test_flash_ring_matches_plain_ring_8_shards():
    """Fused and plain rings agree shard-for-shard at ring size 8 (odd
    skip/diagonal splits per rank)."""
    from jax.sharding import PartitionSpec as P
    key = jax.random.PRNGKey(13)
    q, k, v = (jax.random.normal(kk, (T, D)) for kk in jax.random.split(key, 3))
    mesh = make_mesh({SEQ_AXIS: 8})
    spec = P(SEQ_AXIS, None)

    def run(impl):
        return jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, SEQ_AXIS, True,
                                           attn_impl=impl,
                                           interpret=impl == "flash"),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=impl is None)(q, k, v)

    np.testing.assert_allclose(np.asarray(run("flash")),
                               np.asarray(run(None)),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_pallas_a2a_transport(qkv_heads):
    """Ulysses with comm="pallas_a2a": both re-shards (and their VJP
    transposes) through the hand-scheduled peer fan-out kernel == the
    XLA all_to_all path, forward and gradients."""
    import functools
    from jax.sharding import PartitionSpec as P
    from distributed_llm_code_samples_tpu.parallel.sequence import (
        ulysses_attention)
    q, k, v = qkv_heads
    mesh = make_mesh({SEQ_AXIS: 4})
    spec = P(None, SEQ_AXIS, None)

    def run(comm):
        return jax.shard_map(
            functools.partial(ulysses_attention, axis_name=SEQ_AXIS,
                              comm=comm),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=comm == "psum")

    np.testing.assert_allclose(
        np.asarray(run("pallas_a2a")(q, k, v)),
        np.asarray(run("psum")(q, k, v)), rtol=1e-6, atol=1e-6)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    g_dma = jax.grad(loss(run("pallas_a2a")), argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(loss(run("psum")), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_dma, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
