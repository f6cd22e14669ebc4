"""Pallas flash-attention kernel tests (interpret mode on CPU).

Oracle: the plain hand-VJP attention op (``models.attention``) and jax
autograd over it — forward values, lse policy, and all three gradients,
causal and bidirectional, across tile-boundary shapes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_code_samples_tpu.models.attention import attention, mha
from distributed_llm_code_samples_tpu.ops.pallas_attention import (
    flash_attention, flash_attention_fwd, flash_mha)

T, DH = 64, 16


@pytest.fixture(scope="module")
def qkv():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return tuple(jax.random.normal(k, (T, DH)) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_fwd_matches_oracle(qkv, causal):
    q, k, v = qkv
    y = flash_attention(q, k, v, causal, True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(attention(q, k, v, causal)),
                               rtol=1e-5, atol=1e-5)


def test_flash_fwd_multiple_kv_tiles(qkv):
    """Force >1 kv tile so the online-softmax accumulation path runs."""
    q, k, v = qkv
    y, lse = flash_attention_fwd(q, k, v, causal=True, block_q=16,
                                 block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(attention(q, k, v, True)),
                               rtol=1e-5, atol=1e-5)
    # lse is the true log-sum-exp of the scaled, masked scores
    s = (q @ k.T) / jnp.sqrt(jnp.asarray(DH, jnp.float32))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    np.testing.assert_allclose(np.asarray(lse),
                               np.asarray(jax.nn.logsumexp(s, axis=-1)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grads_match_autograd(qkv, causal):
    q, k, v = qkv
    dy = 0.1 * jax.random.normal(jax.random.PRNGKey(7), (T, DH))
    _, vjp_f = jax.vjp(lambda q, k, v: flash_attention(q, k, v, causal,
                                                       True), q, k, v)
    _, vjp_r = jax.vjp(lambda q, k, v: attention(q, k, v, causal), q, k, v)
    for name, a, b in zip("qkv", vjp_f(dy), vjp_r(dy)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5, err_msg=f"d{name}")


def test_flash_grads_across_tiles(qkv):
    """Gradients with small tiles — exercises the recompute-p path over
    many (i, j) blocks including fully-masked causal tiles."""
    q, k, v = qkv
    dy = 0.1 * jax.random.normal(jax.random.PRNGKey(8), (T, DH))

    def f(q, k, v):
        from distributed_llm_code_samples_tpu.ops.pallas_attention import (
            flash_attention_bwd, flash_attention_fwd)
        y, lse = flash_attention_fwd(q, k, v, causal=True, block_q=16,
                                     block_k=16, interpret=True)
        return flash_attention_bwd(dy, q, k, v, y, lse, causal=True,
                                   block_q=16, block_k=16, interpret=True)

    _, vjp_r = jax.vjp(lambda q, k, v: attention(q, k, v, True), q, k, v)
    for name, a, b in zip("qkv", f(q, k, v), vjp_r(dy)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5, err_msg=f"d{name}")


def test_flash_mha_matches_mha():
    H = 4
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (H, T, DH)) for kk in ks)
    y = flash_mha(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(mha(q, k, v, True)),
                               rtol=1e-5, atol=1e-5)


def test_flash_gqa_matches_oracle():
    """Grouped-query shapes through flash_mha (repeat-KV fan-out) ==
    the hand-VJP gqa oracle, values and all three grads; indivisible
    head counts rejected."""
    from distributed_llm_code_samples_tpu.models.attention import gqa

    H, HKV, T, DH = 4, 2, 64, 64
    kq, kk, kv, kd = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(kq, (H, T, DH))
    k = jax.random.normal(kk, (HKV, T, DH))
    v = jax.random.normal(kv, (HKV, T, DH))
    dy = jax.random.normal(kd, (H, T, DH))

    y0, vjp0 = jax.vjp(lambda q, k, v: gqa(q, k, v, True), q, k, v)
    y1, vjp1 = jax.vjp(lambda q, k, v: flash_mha(q, k, v, True, True),
                       q, k, v)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=1e-5, atol=1e-5)
    for a, b, name in zip(vjp0(dy), vjp1(dy), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5, err_msg=name)

    bad_k = jax.random.normal(kk, (3, T, DH))
    with pytest.raises(ValueError, match="not divisible"):
        flash_mha(q, bad_k, bad_k, True, True)


def test_gqa_trainer_accepts_flash():
    """init_lm(n_kv_heads=...) + attn_impl='flash' trains and matches
    the oracle-attention run (the CLI guard that rejected this combo is
    gone)."""
    from distributed_llm_code_samples_tpu.data import make_seed_schedule
    from distributed_llm_code_samples_tpu.models import init_lm
    from distributed_llm_code_samples_tpu.parallel import train_lm_single

    params = init_lm(jax.random.PRNGKey(0), 128, 64, 2, 32, n_heads=4,
                     n_kv_heads=2)
    seeds = make_seed_schedule(2, random_seed=3)
    o = train_lm_single(params, seeds, 2 * 32, 64, lr=0.1, seq_len=32,
                        n_heads=4)
    f = train_lm_single(params, seeds, 2 * 32, 64, lr=0.1, seq_len=32,
                        n_heads=4, attn_impl="flash")
    for a, b in zip(jax.tree_util.tree_leaves(o),
                    jax.tree_util.tree_leaves(f)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)
