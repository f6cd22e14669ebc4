"""Fused Pallas paged-attention kernel: CPU interpret-mode correctness
(ISSUE 8 satellite).

The contract (``ops/pallas_paged_attention.py``): the block-table walk
must equal the engine's gather two-pass — ``gather_layer`` then
``models.lm.decode_attn`` — to within ``ULPS`` units in the last place
of the output row's scale, at every pool dtype (GQA, per-slot lengths,
scratch-padded tables): same stored bytes, same dequant multiply, same
f32 ops, but the walk keeps the row tiled per block so its softmax and
PV sums run over a different f32 reduction tree than the oracle's
contiguous row — and two separately compiled XLA programs owe each
other no bit identity in the first place. Where the 1-4 ULP of jax
0.9.0 came from, measured on the old contiguous-row kernel: the oracle
compiled as ONE fused program differs from the SAME oracle run op by
op by as much as the kernel differed from either (and the kernel sat
closer to the op-by-op oracle) — XLA:CPU rounds the fused softmax
differently, so the op order was never the variable. The int8
stream must sit within the established per-write quantization bound of
its f32 source. Engine-level token identity (rope included) closes the
loop: a ``kernel="fused"`` engine emits the gather engine's exact
tokens.

Model shapes match tests/test_decode_engine.py fixtures so engine
programs share XLA cache entries.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_code_samples_tpu.decode import (DecodeEngine,
                                                     EngineConfig,
                                                     gather_layer,
                                                     init_pool)
from distributed_llm_code_samples_tpu.decode.paged import (
    _quantize, _rows_major, fused_decode_attn)
from distributed_llm_code_samples_tpu.models import init_lm
from distributed_llm_code_samples_tpu.models.lm import decode_attn
from distributed_llm_code_samples_tpu.ops.pallas_paged_attention import (
    paged_decode_attn)

V, D, L, H = 64, 32, 2, 4
# the stated bound: |fused - oracle| <= ULPS * 2^-23 * max|oracle|.
# Measured on the CPU interpreter across these cases: <= 1.9.
ULPS = 8


def _assert_within_ulps(y, want):
    assert y.dtype == np.float32
    np.testing.assert_allclose(
        y, want, rtol=0, atol=ULPS * 2.0 ** -23 * np.abs(want).max())

BASE = dict(block_size=8, n_blocks=33, max_slots=3, max_blocks_per_seq=6,
            prefill_chunk=8)


def _pool_with_content(kv_dtype, n_blocks=9, hkv=2, blk=8, dh=8, seed=0):
    """A one-layer pool with random content in blocks 1..n-1 (block 0
    stays the factory-zero scratch block), plus the f32 source values
    the quantized dtypes were stored from."""
    rng = np.random.default_rng(seed)
    src_k = rng.normal(size=(n_blocks, hkv, blk, dh)).astype(np.float32)
    src_v = rng.normal(size=(n_blocks, hkv, blk, dh)).astype(np.float32)
    src_k[0] = src_v[0] = 0.0                       # scratch block
    pool = init_pool(1, n_blocks, hkv, blk, dh, kv_dtype)
    if kv_dtype == "int8":
        valid = jnp.ones((n_blocks, hkv, blk), bool)
        qk, ks = _quantize(jnp.asarray(src_k), valid)
        qv, vs = _quantize(jnp.asarray(src_v), valid)
        pool = pool._replace(k=_rows_major(qk)[None],
                             v=_rows_major(qv)[None], k_scale=ks[None],
                             v_scale=vs[None])
    else:
        dt = pool.k.dtype
        pool = pool._replace(k=_rows_major(jnp.asarray(src_k, dt))[None],
                             v=_rows_major(jnp.asarray(src_v, dt))[None])
    return pool, src_k, src_v


def _case(hq=4, hkv=2, b=3, mb=4, blk=8, dh=8, kv_dtype="f32", seed=0):
    """One kernel-vs-oracle case: random q, scratch-padded tables,
    per-slot lengths spanning partial-block, cross-block and full-
    capacity coverage."""
    pool, src_k, src_v = _pool_with_content(kv_dtype, hkv=hkv, blk=blk,
                                            dh=dh, seed=seed)
    rng = np.random.default_rng(seed + 1)
    q = jnp.asarray(rng.normal(size=(b, hq, dh)), jnp.float32)
    # distinct physical blocks per slot; tails padded with scratch
    tables = np.zeros((b, mb), np.int32)
    blocks = iter(range(1, pool.n_blocks))
    lengths = np.asarray([3, blk + 5, mb * blk])[:b].astype(np.int32)
    for i in range(b):
        used = -(-int(lengths[i]) // blk)
        tables[i, :used] = [next(blocks) for _ in range(used)]
    return pool, q, jnp.asarray(tables), jnp.asarray(lengths), src_k


def _oracle(pool, q, tables, lengths):
    ck, cv = jax.vmap(lambda t: gather_layer(pool, 0, t))(tables)
    return decode_attn(q, ck, cv, lengths)


@pytest.mark.parametrize("kv_dtype", ["f32", "bf16", "int8"])
def test_fused_matches_gather_bitwise(kv_dtype):
    """The oracle agreement, per dtype, under jit (the engine's
    compiled context): same pool bytes in, same f32 ops, the output
    within the module's ULP bound (the test keeps its name so the
    suite's history stays comparable)."""
    pool, q, tables, lengths, _ = _case(kv_dtype=kv_dtype)

    def fused(q):
        return fused_decode_attn(pool, 0, q, tables, lengths,
                                 interpret=True)

    def ref(q):
        return _oracle(pool, q, tables, lengths)

    _assert_within_ulps(np.asarray(jax.jit(fused)(q)),
                        np.asarray(jax.jit(ref)(q)))


def test_fused_gqa_grouping_and_mha():
    """GQA groupings (G = H/H_kv > 1) and the degenerate MHA case
    (G = 1) walk the same pool within the ULP bound; exact PICK
    identity is the engine-level tests' below, which is the contract
    serving actually needs."""
    for hq, hkv in ((4, 2), (4, 1), (2, 2)):
        pool, q, tables, lengths, _ = _case(hq=hq, hkv=hkv, seed=hq)
        y = np.asarray(jax.jit(lambda q: fused_decode_attn(
            pool, 0, q, tables, lengths, interpret=True))(q))
        want = np.asarray(jax.jit(lambda q: _oracle(
            pool, q, tables, lengths))(q))
        _assert_within_ulps(y, want)


def test_fused_skips_are_mask_exact():
    """Blocks past a slot's length are SKIPPED by the walk (their tiles
    pinned to the mask value / zero) — the result must still agree with the
    oracle, which reads and then masks them. Length-1 rows (the
    engine's pad convention: attend scratch position 0 only) included."""
    pool, q, tables, _, _ = _case()
    lengths = jnp.asarray([1, 2, 9], jnp.int32)      # heavy skipping
    y = np.asarray(jax.jit(lambda q: fused_decode_attn(
        pool, 0, q, tables, lengths, interpret=True))(q))
    want = np.asarray(jax.jit(lambda q: _oracle(
        pool, q, tables, lengths))(q))
    _assert_within_ulps(y, want)


def test_fused_int8_within_per_write_bound():
    """The int8 stream the kernel dequantizes sits within the
    established per-write quantization bound of its f32 source
    (2 * amax / 127 per block — test_decode_engine's bound), and the
    attention output tracks the f32-source attention accordingly."""
    pool, q, tables, lengths, src_k = _case(kv_dtype="int8")
    # the dequantized stream (via the bit-equal gather view)
    ck, _ = gather_layer(pool, 0, tables[0])
    blk = pool.block_size
    n = int(lengths[0])
    for pos in range(n):
        phys = int(tables[0, pos // blk])
        got = np.asarray(ck)[:, pos]
        want = src_k[phys, :, pos % blk]
        amax = np.abs(src_k[phys]).max(axis=(1, 2))     # per kv head
        err = np.abs(got - want).max(axis=1)
        assert (err <= 2 * amax / 127 + 1e-7).all()
    # f32-source oracle vs the fused int8 output: same bound's drift
    # through one convex combination (softmax weights sum to 1), so
    # the output error is of the same order as the value error
    f32_pool, _, _ = _pool_with_content("f32")
    want_y = np.asarray(jax.jit(lambda q: _oracle(
        f32_pool, q, tables, lengths))(q))
    y = np.asarray(jax.jit(lambda q: fused_decode_attn(
        pool, 0, q, tables, lengths, interpret=True))(q))
    amax = np.abs(src_k).max()
    assert np.abs(y - want_y).max() <= 12 * amax / 127


# ---------------------------------------------------------------------------
# through the engine (the kernel= knob end to end)


@pytest.fixture(scope="module")
def lm_params():
    return init_lm(jax.random.PRNGKey(0), V, D, L, max_seq_len=64)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(1)
    return [rng.integers(0, V, size=n).tolist() for n in (5, 9, 13)]


@pytest.mark.parametrize("kv_dtype", ["f32", "bf16", "int8"])
def test_fused_engine_token_identity(lm_params, prompts, kv_dtype):
    """Acceptance: fused-kernel picks == gather-path picks — the
    engines emit identical tokens at every KV dtype."""
    want = DecodeEngine(lm_params, H, EngineConfig(
        **BASE, kv_dtype=kv_dtype)).generate(prompts, 8)
    got = DecodeEngine(lm_params, H, EngineConfig(
        **BASE, kv_dtype=kv_dtype, kernel="fused")).generate(prompts, 8)
    assert got == want


def test_fused_engine_gqa_rope_identity(prompts):
    """GQA + rope through the fused engine: the kernel sees rotated
    keys (rope happens upstream of the cache write) and grouped query
    rows — tokens must still match the gather engine's."""
    gqa = init_lm(jax.random.PRNGKey(3), V, D, L, max_seq_len=64,
                  n_heads=H, n_kv_heads=2)
    want = DecodeEngine(gqa, H, EngineConfig(
        **BASE, use_rope=True)).generate(prompts, 6)
    got = DecodeEngine(gqa, H, EngineConfig(
        **BASE, use_rope=True, kernel="fused")).generate(prompts, 6)
    assert got == want


def test_fused_with_speculation_identity(lm_params, prompts):
    """Both tentpole halves composed: speculate + fused == the plain
    gather engine, token for token."""
    want = DecodeEngine(lm_params, H,
                        EngineConfig(**BASE)).generate(prompts, 10)
    got = DecodeEngine(lm_params, H, EngineConfig(
        **BASE, speculate=3, kernel="fused")).generate(prompts, 10)
    assert got == want


def test_fused_rejects_tp(lm_params, mesh_model4):
    with pytest.raises(ValueError, match="single-device"):
        DecodeEngine(lm_params, H, EngineConfig(**BASE, kernel="fused"),
                     mesh=mesh_model4)
