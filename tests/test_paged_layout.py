"""The paged pool's stored form against a NumPy model of it.

``decode/paged.py`` stores ``k/v [L, n_blocks, block, H_kv*dh]``: a
token's row holds its heads side by side. Everything that writes,
reads, copies or exports the pool is held here to a plain model —
``model[layer][block][off] = [H_kv, dh]`` — at every ``kv_dtype``:
rows come back from the gather at the right (position, head), a block
document is still head-major ``[L, n, H_kv, block, dh]`` and implants
bit-identically, and the block-level edits touch the named block in
every layer and nothing else.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_code_samples_tpu.decode.paged import (
    KV_DTYPES, copy_block, copy_block_rows, corrupt_block, extract_blocks,
    gather_layer, implant_block, init_pool, scrub_blocks, write_chunk,
    write_rows)

L, NB, HKV, BLK, DH = 2, 7, 3, 4, 8


def _rows(rng, n):
    return rng.normal(size=(n, HKV, DH)).astype(np.float32)


def _tolerance(kv_dtype, want):
    """Per-(head) bound of one stored row against its f32 source:
    exact, bf16's 8 mantissa bits, or two int8 steps of the block's
    amax (a later write may re-quantize a row once more)."""
    amax = np.abs(want).max()
    return {"f32": 0.0, "bf16": amax * 2.0 ** -8,
            "int8": 2 * amax / 127 + 1e-7}[kv_dtype]


def _written_pool(kv_dtype, seed=0):
    """A pool written the three ways the engine writes one — a
    whole-block prefill chunk, a part-block chunk, batched decode rows —
    through ``table``, different values in every layer; and the model:
    ``want[side][layer]`` ``[T, H_kv, dh]`` f32 by global position."""
    rng = np.random.default_rng(seed)
    pool = init_pool(L, NB, HKV, BLK, DH, kv_dtype)
    table = jnp.asarray([3, 1, 5, 0], jnp.int32)    # tail -> scratch
    other = jnp.asarray([6, 0, 0, 0], jnp.int32)    # a second sequence
    want = {"k": [], "v": []}
    for layer in range(L):
        k, v = _rows(rng, 10), _rows(rng, 10)
        want["k"].append(k)
        want["v"].append(v)
        pool = write_chunk(pool, layer, table, jnp.int32(0),
                           jnp.asarray(k[:4]), jnp.asarray(v[:4]), kv_dtype)
        pool = write_chunk(pool, layer, table, jnp.int32(4),
                           jnp.asarray(k[4:6]), jnp.asarray(v[4:6]),
                           kv_dtype)
        # decode-style: one row a slot a dispatch, two slots batched
        # (the other sequence's rows must not leak into this one's)
        for pos in range(6, 10):
            ko, vo = _rows(rng, 1), _rows(rng, 1)
            phys = jnp.stack([table[pos // BLK], other[(pos - 6) // BLK]])
            off = jnp.asarray([pos % BLK, (pos - 6) % BLK], jnp.int32)
            pool = write_rows(
                pool, layer, phys, off,
                jnp.concatenate([jnp.asarray(k[pos:pos + 1]),
                                 jnp.asarray(ko)]),
                jnp.concatenate([jnp.asarray(v[pos:pos + 1]),
                                 jnp.asarray(vo)]), kv_dtype)
    return pool, table, want


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_written_rows_come_back_at_position_and_head(kv_dtype):
    pool, table, want = _written_pool(kv_dtype)
    assert pool.k.shape == (L, NB, BLK, HKV * DH)
    assert (pool.n_blocks, pool.block_size, pool.kv_heads) == (NB, BLK, HKV)
    for layer in range(L):
        got = dict(zip("kv", gather_layer(pool, layer, table)))
        for side in "kv":
            view = np.asarray(got[side])            # [H_kv, T_cap, dh]
            assert view.shape == (HKV, len(table) * BLK, DH)
            assert view.dtype == np.float32
            w = want[side][layer].transpose(1, 0, 2)    # [H_kv, 10, dh]
            tol = _tolerance(kv_dtype, w)
            assert np.abs(view[:, :10] - w).max() <= tol
            # ...and never a neighbour's: a row one position or one
            # head off is a different normal draw, far outside ``tol``
            assert np.abs(view[:, 1:10] - w[:, :9]).max() > 0.5
            assert np.abs(view[1:, :10] - w[:-1]).max() > 0.5
            # past the write head: block 5's unwritten rows, scratch
            assert not view[:, 10:12].any()
    if kv_dtype == "f32":
        # the stored array itself: head h of the row at (block, off)
        # sits at lanes [h*dh, (h+1)*dh)
        for pos, (blk, off) in {0: (3, 0), 5: (1, 1), 9: (5, 1)}.items():
            row = np.asarray(pool.k[1, blk, off]).reshape(HKV, DH)
            np.testing.assert_array_equal(row, want["k"][1][pos])


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_extract_implant_is_bit_identical(kv_dtype):
    """A block document is head-major ``[L, n, H_kv, block, dh]`` at the
    storage dtype, whatever the stored form; implanted into another pool
    it reads back bit for bit."""
    pool, table, want = _written_pool(kv_dtype)
    doc = extract_blocks(pool, [3, 1, 5])
    for side in "kv":
        assert doc[side].shape == (L, 3, HKV, BLK, DH)
        assert doc[side].dtype == np.asarray(pool.k).dtype
        assert doc[side].flags["C_CONTIGUOUS"]
    if kv_dtype == "int8":
        assert doc["k_scale"].shape == (L, 3, HKV)
    else:
        assert doc["k_scale"] is None and doc["v_scale"] is None
    if kv_dtype == "f32":
        # document block 1 is table block 1 (physical 1): positions 4..7
        np.testing.assert_array_equal(
            doc["v"][0, 1], want["v"][0][4:8].transpose(1, 0, 2))
    fresh = init_pool(L, NB, HKV, BLK, DH, kv_dtype)
    dsts = [2, 6, 4]
    for i, dst in enumerate(dsts):
        scales = ([] if doc["k_scale"] is None else
                  [jnp.asarray(doc["k_scale"][:, i]),
                   jnp.asarray(doc["v_scale"][:, i])])
        fresh = implant_block(fresh, jnp.int32(dst),
                              jnp.asarray(doc["k"][:, i]),
                              jnp.asarray(doc["v"][:, i]), *scales)
    back = extract_blocks(fresh, dsts)
    for key, arr in doc.items():
        if arr is None:
            assert back[key] is None
        else:
            assert back[key].tobytes() == arr.tobytes(), key
    # the stored rows moved whole: same bytes at the new block ids
    for src, dst in zip([3, 1, 5], dsts):
        assert (np.asarray(fresh.k[:, dst]).tobytes()
                == np.asarray(pool.k[:, src]).tobytes())
    # ...and the new pool reads as the old one through the new table
    for layer in range(L):
        for a, b in zip(gather_layer(pool, layer, table),
                        gather_layer(fresh, layer,
                                     jnp.asarray(dsts + [0], jnp.int32))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _model(pool):
    """The pool as plain f32/NumPy arrays, by field."""
    return {f: (None if getattr(pool, f) is None
                else np.asarray(getattr(pool, f)).astype(np.float32))
            for f in ("k", "v", "k_scale", "v_scale")}


def _copy_block(m, kv_dtype):
    for f in m:
        if m[f] is not None:
            m[f][:, 2] = m[f][:, 3]


def _copy_block_rows(m, kv_dtype):
    for f in ("k", "v"):
        m[f][:, 2] = 0
        m[f][:, 2, :3] = m[f][:, 3, :3]     # rows are the axis after block
    if m["k_scale"] is not None:            # scales freeze whole
        m["k_scale"][:, 2] = m["k_scale"][:, 3]
        m["v_scale"][:, 2] = m["v_scale"][:, 3]


def _scrub(m, kv_dtype):
    for f in m:
        if m[f] is not None:
            m[f][:, [1, 5]] = 0


def _corrupt(m, kv_dtype):
    # int8 codes have no NaN: the scales carry it
    for f in (("k_scale", "v_scale") if kv_dtype == "int8" else ("k", "v")):
        m[f][:, 3] = np.nan


BLOCK_OPS = {
    "copy_block": (lambda p: copy_block(p, 3, 2), _copy_block),
    "copy_block_rows": (lambda p: copy_block_rows(p, 3, 2, 3),
                        _copy_block_rows),
    "scrub_blocks": (lambda p: scrub_blocks(p, [1, 5]), _scrub),
    "corrupt_block": (lambda p: corrupt_block(p, 3), _corrupt),
}


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
@pytest.mark.parametrize("op", sorted(BLOCK_OPS))
def test_block_edit_touches_exactly_the_named_block(op, kv_dtype):
    """Every layer's named block changes as the model says; every other
    byte of values and scales stays."""
    pool, _, _ = _written_pool(kv_dtype, seed=3)
    apply, model_apply = BLOCK_OPS[op]
    want = _model(pool)
    model_apply(want, kv_dtype)
    got = _model(apply(pool))
    for f, arr in want.items():
        if arr is None:
            assert got[f] is None
        else:
            np.testing.assert_array_equal(got[f], arr, err_msg=f)
    # the fixture's content makes that a test: the named blocks held
    # something to change in every layer
    assert all(np.asarray(pool.k[layer, b]).any()
               for layer in range(L) for b in (1, 3, 5))
