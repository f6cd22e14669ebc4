"""The paged pool's stored form against a NumPy model of it.

``decode/paged.py`` stores ``k/v [L, n_blocks, block, H_kv*dh]``: a
token's row holds its heads side by side. Everything that writes,
reads, copies or exports the pool is held here to a plain model —
``model[layer][block][off] = [H_kv, dh]`` — at every ``kv_dtype``:
rows come back from the gather at the right (position, head), a block
document is still head-major ``[L, n, H_kv, block, dh]`` and implants
bit-identically, and the block-level edits touch the named block in
every layer and nothing else. The decode programs' attention over the
rows as stored (``stored_decode_attn``) is held to the oracle
``decode_attn(q, *vmap(gather_layer))`` in both its forms: the plain
gather and two products (``gathered_decode_attn``; also to a NumPy
model of its own arithmetic) and the walk over each row's live blocks
(``ops/kv_walk.py``), a full pool's table, a window layer's ring and a
latent pool's one-sided rows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jaxpr_eqns

from distributed_llm_code_samples_tpu.decode.paged import (
    KV_DTYPES, SCRATCH_BLOCK, _heads_major, _quantize, _rows_major,
    copy_block, copy_block_rows, corrupt_block, extract_blocks,
    gather_layer, gathered_decode_attn, implant_block, init_pool,
    ring_positions, ring_start, scrub_blocks, stored_decode_attn, walks,
    write_chunk, write_rows)
from distributed_llm_code_samples_tpu.models.lm import decode_attn

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


# ---------------------------------------------------------------------
# the decode programs' attention over the rows as stored, against the
# oracle: (query heads, KV heads, head dim) of GPT-2 large's MHA, a GQA
# group and the hybrid's 20 heads over one KV head of 128 lanes
ATTN_SHAPES = {"mha20x64": (20, 20, 64), "gqa8over2": (8, 2, 16),
               "mqa20over1x128": (20, 1, 128)}
A_B, A_MB, A_BLK = 4, 5, 8
# ragged: one position, mid-block, the whole capacity, a block boundary
A_LENGTHS = np.asarray([1, A_BLK + 3, A_MB * A_BLK, 2 * A_BLK], np.int32)


def _bf16(x):
    return np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))


def _attn_case(shape, kv_dtype, stale, seed=0):
    """A one-layer pool of ``A_B`` sequences with disjoint tables,
    random content at positions below ``A_LENGTHS`` and ``stale`` beyond
    (``"large"``: the largest finite bytes a freed sequence could leave,
    ``"zero"``). Returns the pool, ``q``, the tables and, for the model,
    the stored values ``[b, H_kv, T, dh]`` as floats with their per-
    position scales ``[b, H_kv, T]`` (ones unless int8)."""
    hq, hkv, dh = ATTN_SHAPES[shape]
    rng = np.random.default_rng(seed)
    nb, t_cap = 1 + A_B * A_MB, A_MB * A_BLK
    tables = 1 + rng.permutation(A_B * A_MB).reshape(A_B, A_MB)
    live = np.zeros((nb, A_BLK), bool)          # by (block, offset)
    for b in range(A_B):
        pos = np.arange(t_cap)[: A_LENGTHS[b]]
        live[tables[b][pos // A_BLK], pos % A_BLK] = True
    pool = init_pool(1, nb, hkv, A_BLK, dh, kv_dtype)
    sides, model = [], []
    for _ in "kv":
        src = rng.normal(size=(nb, hkv, A_BLK, dh)).astype(np.float32)
        mask = live[:, None, :, None]
        if kv_dtype == "int8":
            codes, scale = _quantize(jnp.asarray(src),
                                     jnp.ones((nb, hkv, A_BLK), bool))
            codes = np.where(mask, np.asarray(codes),
                             127 if stale == "large" else 0).astype(np.int8)
            sides.append((_rows_major(jnp.asarray(codes))[None],
                          scale[None]))
            vals, sc = codes.astype(np.float32), np.asarray(scale)
        else:
            dt = pool.k.dtype
            big = 3e4 if stale == "large" else 0.0
            stored = jnp.asarray(np.where(mask, src, big), dt)
            sides.append((_rows_major(stored)[None], None))
            vals = np.asarray(stored.astype(jnp.float32))
            sc = np.ones((nb, hkv), np.float32)
        # [b, MB, H_kv, blk, dh] -> [b, H_kv, T, dh]
        model.append((
            vals[tables].transpose(0, 2, 1, 3, 4).reshape(A_B, hkv, t_cap,
                                                          dh),
            np.repeat(sc[tables].transpose(0, 2, 1), A_BLK, axis=2)))
    (k, ks), (v, vs) = sides
    pool = pool._replace(k=k, v=v, k_scale=ks, v_scale=vs)
    q = jnp.asarray(rng.normal(size=(A_B, hq, dh)), jnp.float32)
    return pool, q, jnp.asarray(tables, jnp.int32), model


def _two_roundings_bound(qg, kc, vc, live, ks=None, vs=None):
    """What rounding the two small operands to bf16 (``u = 2**-9``) may
    move a head's output by, ``[b, H_kv, g]``: a score moves by at most
    ``ds = u * max_t sum_j |q_j k_tj| / sqrt(dh)``, so a probability by
    a factor within ``exp(+-2 ds)``, and its own rounding adds ``u``:
    ``(exp(2 ds) - 1 + u) * max_t |v_t|``. ``qg [b, H_kv, g, dh]``,
    ``kc/vc [b, H_kv, T, dh]`` the stored values as floats, ``live [b,
    T]``, ``ks/vs [b, H_kv, T]`` their scales (int8)."""
    u = 2.0 ** -9
    one = np.ones(kc.shape[:3])
    ks, vs = one if ks is None else ks, one if vs is None else vs
    ds = u * np.where(live[:, None, None, :], np.einsum(
        "bkgd,bktd->bkgt", np.abs(qg), np.abs(kc)) * ks[:, :, None],
        0).max(-1) / np.sqrt(qg.shape[-1])                  # [b, k, g]
    vmax = np.where(live[:, None, :, None], np.abs(vc) * vs[..., None],
                    0).max((2, 3))                          # [b, k]
    return (np.expm1(2 * ds) + u) * vmax[:, :, None]


def _stored(pool, q, tables):
    return np.asarray(jax.jit(lambda q: gathered_decode_attn(
        pool, 0, q, tables, jnp.asarray(A_LENGTHS)))(q))


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
@pytest.mark.parametrize("shape", sorted(ATTN_SHAPES))
def test_gathered_decode_attn_matches_the_oracle(shape, kv_dtype):
    """``gathered_decode_attn`` (the plain decode-side read: every
    int8 pool's, and what the walk is held to below) against
    ``decode_attn(q,
    *vmap(gather_layer))`` on the same pool bytes, ragged lengths from 1
    to the capacity, stale rows beyond them at the largest finite
    values. An f32 pool: the same f32 products in another order, 1e-5
    of the output's scale. A bf16 or int8 pool: the function rounds its
    two small operands to bf16 (``u = 2**-9``) where the oracle keeps
    them f32, and nothing else differs —

    - against a NumPy model of exactly that arithmetic (``q`` rounded,
      exact products, the probabilities times the value scales
      rounded): 1e-4 of the output's scale, 40 times under the
      roundings' own effect;
    - against the oracle, the bound the two roundings give: a score
      moves by at most ``ds = u * max_t sum_j |q_j k_tj| / sqrt(dh)``,
      so a probability by a factor within ``exp(+-2 ds)``, and its own
      rounding adds ``u``: ``|y - oracle| <= (exp(2 ds) - 1 + u) *
      max_t |v_t|`` per head."""
    pool, q, tables, ((kc, ks), (vc, vs)) = _attn_case(shape, kv_dtype,
                                                       "large")
    got = _stored(pool, q, tables)
    lengths = jnp.asarray(A_LENGTHS)
    want = np.asarray(jax.jit(lambda q: decode_attn(
        q, *jax.vmap(lambda t: gather_layer(pool, 0, t))(tables),
        lengths))(q))
    assert got.shape == want.shape and got.dtype == np.float32
    scale = np.abs(want).max()
    if kv_dtype == "f32":
        assert np.abs(got - want).max() <= 1e-5 * scale
        return
    b, h, dh = q.shape
    hkv = kc.shape[1]
    qg = np.asarray(q, np.float64).reshape(b, hkv, h // hkv, dh)
    live = np.arange(kc.shape[2]) < A_LENGTHS[:, None]          # [b, T]
    mask = live[:, None, None, :]

    def attend(qg, round_p):
        s = np.einsum("bkgd,bktd->bkgt", qg, kc) * ks[:, :, None] \
            / np.sqrt(dh)
        s = np.where(mask, s, -1e30)
        p = np.exp(s - s.max(-1, keepdims=True))
        p = round_p(p / p.sum(-1, keepdims=True) * vs[:, :, None])
        return np.einsum("bkgt,bktd->bkgd", p, vc).reshape(b, h, dh)

    model = attend(_bf16(qg).astype(np.float64), _bf16)
    assert np.abs(got - model).max() <= 1e-4 * scale
    # the model with nothing rounded is the oracle
    assert np.abs(attend(qg, lambda p: p) - want).max() <= 1e-5 * scale
    bound = _two_roundings_bound(qg, kc, vc, live, ks, vs)
    err = np.abs(got - want).reshape(b, hkv, h // hkv, dh).max(-1)
    assert (err <= bound + 1e-5 * scale).all()
    # the roundings are really there (bf16 operands, not an f32 upcast
    # of the cache): the difference is far above f32 reduction noise
    assert err.max() > 1e-4 * scale


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_stale_bytes_beyond_the_length_carry_no_mass(kv_dtype):
    """Positions at and beyond ``lengths`` meet a probability that is
    exactly 0: the largest finite bytes there (a freed sequence's rows,
    int8 codes of 127) and zeros give the same output, bit for bit."""
    large = _stored(*_attn_case("mha20x64", kv_dtype, "large")[:3])
    zero = _stored(*_attn_case("mha20x64", kv_dtype, "zero")[:3])
    assert np.isfinite(large).all()
    np.testing.assert_array_equal(large, zero)


# ---------------------------------------------------------------------
# the walk over each row's live blocks (``ops/kv_walk.py``: what
# ``stored_decode_attn`` runs where ``paged.walks`` says so), against the
# oracle it replaces: the four serving cells' head layouts — GPT-2's
# MHA of 64, LFM2's 8 KV heads of 64 with 4 query heads a group, the
# hybrid's ONE KV head of 128 under 20, Laguna's 8 of 128 with 6 a group
WALK_SHAPES = {"mha4x64": (4, 4, 64), "gqa32over8x64": (32, 8, 64),
               "mqa20over1x128": (20, 1, 128),
               "gqa48over8x128": (48, 8, 128)}
# ... and the LATENT kind (heads, the row's lanes, of which the values:
# GLM's 20 heads over ONE row of 640 lanes whose first 512 are the
# values, in small): the pool has no V side, the kernel walks it
# one-sided, and the query comes scaled and laid out for the row
LATENT_SHAPES = {"latent20x40of32": (20, 40, 32)}
W_MB, W_BLK = 5, 8
# ragged in one batch: one position, a block less one, a whole block, a
# block and one, the capacity; and a bucket's padded row (its table all
# scratch, one position)
W_LENGTHS = np.asarray([1, W_BLK - 1, W_BLK, W_BLK + 1, W_MB * W_BLK, 1],
                       np.int32)
W_POISONED = 3          # the row of ``W_BLK + 1`` positions: 2 live blocks


def _walk_case(shape, kv_dtype, poison, lengths=W_LENGTHS):
    """A two-layer pool (the read is of layer 1), every byte random and
    finite but the stale ones beyond each row's length, which are the
    largest a freed sequence could leave; permuted tables; then
    ``poison``: a NaN in a DEAD block of row ``W_POISONED``'s table
    (``"dead"``) or beyond its length inside its last live block
    (``"last"``). A shape of ``LATENT_SHAPES``: a latent pool, the K
    side its rows and no V side, ``q`` each head's query for the row.
    ``lengths``: another batch of as many rows, the last still the
    padded one; a row of length 0 is padded too (its table all
    scratch)."""
    if shape in LATENT_SHAPES:
        (hq, dh, rank), hkv = LATENT_SHAPES[shape], 1
    else:
        (hq, hkv, dh), rank = WALK_SHAPES[shape], 0
    rng = np.random.default_rng(1)
    b = len(W_LENGTHS)
    nb = 1 + (b - 1) * W_MB
    tables = 1 + rng.permutation((b - 1) * W_MB).reshape(b - 1, W_MB)
    tables = np.concatenate(
        [tables, np.full((1, W_MB), SCRATCH_BLOCK)]).astype(np.int32)
    tables[lengths == 0] = SCRATCH_BLOCK
    pool = init_pool(2, nb, hkv, W_BLK, dh, kv_dtype, latent_rank=rank)
    live = np.zeros((nb, W_BLK), bool)
    for r in range(b):
        pos = np.arange(lengths[r])
        live[tables[r][pos // W_BLK], pos % W_BLK] = True
    sides = []
    for side in (pool.k, pool.v):       # a latent pool's ``v``: no lanes
        src = rng.normal(size=side.shape).astype(np.float32)
        src = np.where(live[None, :, :, None], src, 3e4)
        if poison == "dead":
            src[:, tables[W_POISONED, 2:]] = np.nan
        elif poison == "last":
            src[:, tables[W_POISONED, 1], 1:] = np.nan
        sides.append(jnp.asarray(src, pool.k.dtype))
    pool = pool._replace(k=sides[0], v=sides[1])
    q = jnp.asarray(rng.normal(size=(b, hq, dh)), jnp.float32)
    return pool, q, jnp.asarray(tables)


def _walked(pool, q, tables, lengths=W_LENGTHS):
    assert walks(pool)
    return np.asarray(jax.jit(lambda q: stored_decode_attn(
        pool, 1, q, tables, jnp.asarray(lengths)))(q))


def _latent_oracle(pool, q, tables):
    """The latent read written down plainly in float64 over layer 1's
    rows: scores over the WHOLE row (the query is scaled already),
    values the row's first ``latent_rank`` lanes. Returns the result
    ``[b, H, rank]`` beside ``(qg, kc, vc)`` in the K/V oracle's form,
    one KV head, the query times ``sqrt(m)`` so that
    ``_two_roundings_bound``'s ``1 / sqrt(m)`` is this read's 1."""
    b, h, m = q.shape
    rows = np.asarray(pool.k[1].astype(jnp.float32), np.float64)[
        np.asarray(tables)].reshape(b, 1, -1, m)
    kc, vc = rows, rows[..., :pool.latent_rank]
    qg = np.asarray(q, np.float64)[:, None]                 # [b, 1, H, m]
    s = np.einsum("bkgd,bktd->bkgt", qg, kc)
    live = np.arange(kc.shape[2]) < W_LENGTHS[:, None]
    s = np.where(live[:, None, None, :], s, -np.inf)
    e = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bkgt,bktd->bkgd", e / e.sum(-1, keepdims=True),
                     np.where(live[:, None, :, None], vc, 0))
    return want.reshape(b, h, -1), (qg * np.sqrt(m), kc, vc)


@pytest.mark.parametrize("poison", ["none", "dead", "last"])
@pytest.mark.parametrize("kv_dtype", ["f32", "bf16"])
@pytest.mark.parametrize("shape", sorted({**WALK_SHAPES, **LATENT_SHAPES}))
def test_walked_stored_decode_attn_matches_the_oracle(shape, kv_dtype, poison):
    """``stored_decode_attn`` over a pool that takes the walk, against
    ``decode_attn(q, *vmap(gather_layer))`` on the same bytes, under the
    plain form's bounds: an f32 pool to reduction order (1e-5 of the
    output's scale), a bf16 pool to the two roundings of the small
    operands (the online softmax rounds ``exp(s - m)`` for the running
    maximum ``m`` where the plain form rounds the normalised
    probability: one relative ``u`` either way). And what the walk
    changes, stated: a NaN in a DEAD block of a row's table does not
    reach the row, bit for bit; one beyond the length inside its last
    live block poisons that row and no other, as in the plain form.
    The latent kind's oracle is ``_latent_oracle``: the same bounds over
    ONE fetch of each row for both products."""
    pool, q, tables = _walk_case(shape, kv_dtype, "none")
    got = _walked(pool, q, tables)
    lengths = jnp.asarray(W_LENGTHS)
    if poison != "none":
        bad = _walked(*_walk_case(shape, kv_dtype, poison))
        rows = np.arange(len(W_LENGTHS)) != W_POISONED
        np.testing.assert_array_equal(bad[rows], got[rows])
        if poison == "dead":
            np.testing.assert_array_equal(bad, got)
        else:
            assert np.isnan(bad[W_POISONED]).all()
        return
    b, h, dh = q.shape
    if pool.latent_rank:
        want, (qg, kc, vc) = _latent_oracle(pool, q, tables)
        # the rank's lanes and no more are what the read hands on
        assert got.shape == (b, h, pool.latent_rank)
    else:
        kc, vc = jax.vmap(lambda t: gather_layer(pool, 1, t))(tables)
        want = np.asarray(jax.jit(decode_attn)(q, kc, vc, lengths))
        qg = np.asarray(q, np.float64).reshape(b, kc.shape[1], -1, dh)
    assert got.shape == want.shape and got.dtype == np.float32
    assert np.isfinite(got).all()
    scale = np.abs(want).max()
    if kv_dtype == "f32":
        assert np.abs(got - want).max() <= 1e-5 * scale
        return
    hkv = kc.shape[1]
    live = np.arange(kc.shape[2]) < W_LENGTHS[:, None]
    bound = _two_roundings_bound(qg, np.asarray(kc, np.float64),
                                 np.asarray(vc, np.float64), live)
    err = np.abs(got - want).reshape(b, hkv, h // hkv, -1).max(-1)
    assert (err <= bound + 1e-5 * scale).all()
    # bf16 operands, not an f32 upcast of the cache
    assert err.max() > 1e-4 * scale


# a latent batch of its own: a padded row of length 0 and one of length 1
# (their tables all scratch), a row of one block, one at capacity, one
# that ends mid-block three blocks deep, one position
L_LENGTHS = np.asarray([0, W_BLK, W_MB * W_BLK, 3 * W_BLK - 2, 1, 1],
                       np.int32)


@pytest.mark.parametrize("steps", ["rule", "2-a-step"])
@pytest.mark.parametrize("kv_dtype", ["f32", "bf16"])
def test_walked_latent_read_matches_the_plain_form(monkeypatch, kv_dtype,
                                                   steps):
    """The one-sided walk against ``gathered_decode_attn`` (the plain
    form: one gather of capacity, both products over the copy) on the
    same latent pool: every row that has a position agrees, an f32 pool
    to reduction order, a bf16 pool within twice the two roundings' own
    bound (both sides round). Ragged lengths in one batch, a padded row
    of length 1 and one of length 0 (which attends over nothing: any
    finite numbers, and no other row's). ``2-a-step``: two blocks a copy
    step in place of the rule's whole table, so a row takes up to three
    steps, the ONE pair of buffers alternates within a row and across
    rows, and a last step's unfetched block is zeroed in the buffer both
    products read."""
    from distributed_llm_code_samples_tpu.ops import kv_walk
    if steps != "rule":
        monkeypatch.setattr(kv_walk, "blocks_a_step", lambda *a, **k: 2)
    pool, q, tables = _walk_case("latent20x40of32", kv_dtype, "none",
                                 L_LENGTHS)
    lengths = jnp.asarray(L_LENGTHS)
    got = _walked(pool, q, tables, L_LENGTHS)
    plain = np.asarray(jax.jit(lambda q: gathered_decode_attn(
        pool, 1, q, tables, lengths))(q))
    assert got.shape == plain.shape == (*q.shape[:2], pool.latent_rank)
    assert np.isfinite(got).all()
    some = L_LENGTHS > 0
    scale = np.abs(plain[some]).max()
    if kv_dtype == "f32":
        assert np.abs(got - plain)[some].max() <= 1e-5 * scale
        return
    b, h, m = q.shape
    rows = np.asarray(pool.k[1].astype(jnp.float32), np.float64)[
        np.asarray(tables)].reshape(b, 1, -1, m)
    live = np.arange(rows.shape[2]) < L_LENGTHS[:, None]
    bound = _two_roundings_bound(
        np.asarray(q, np.float64)[:, None] * np.sqrt(m), rows,
        rows[..., :pool.latent_rank], live)[:, 0]           # [b, H]
    err = np.abs(got - plain).max(-1)
    assert (err <= 2 * bound + 1e-5 * scale)[some].all()


def _pallas_calls(jaxpr):
    return [e for e in jaxpr_eqns(jaxpr) if e.primitive.name == "pallas_call"]


def test_latent_walk_fetches_one_side_and_hands_on_the_rank_lanes():
    """What the kernel is handed and what leaves the read: the V side of
    no lanes is no operand of the ``pallas_call`` (ONE pool, one pair of
    buffers, one semaphore a buffer: one copy a block for both
    products), the kernel's result is the heads' sums over the WHOLE
    stored row, ``f32[b, H, m]`` (what a K/V pool's call returns of ITS
    row, and what the benchmark's reader knows the call by), and
    ``stored_decode_attn`` hands on its first ``latent_rank`` lanes
    alone: the rotary lanes' sums, which are not zero, stop there."""
    from distributed_llm_code_samples_tpu.ops import kv_walk
    pool, q, tables = _walk_case("latent20x40of32", "f32", "none")
    b, h, m = q.shape
    lengths = jnp.asarray(W_LENGTHS)
    call, = _pallas_calls(jax.make_jaxpr(lambda q: stored_decode_attn(
        pool, 1, q, tables, lengths))(q).jaxpr)
    operands = [v.aval.shape for v in call.invars]
    assert operands.count(pool.k.shape) == 1 and pool.v.shape not in operands
    assert [(v.aval.shape, v.aval.dtype) for v in call.outvars] == [
        ((b, h, m), jnp.float32)]
    scratch = [x.shape for x in call.params["grid_mapping"].scratch_avals]
    assert [x[::2] for x in scratch if len(x) == 3] == [(2, m)]
    assert (1, 2) in scratch and (2, 2) not in scratch      # semaphores
    # a K/V pool of the same rows: both sides, a pair of buffers each
    both, q2, _ = _walk_case("mqa20over1x128", "f32", "none")
    call, = _pallas_calls(jax.make_jaxpr(lambda q: stored_decode_attn(
        both, 1, q, tables, lengths))(q2).jaxpr)
    operands = [v.aval.shape for v in call.invars]
    assert operands.count(both.k.shape) == 2
    scratch = [x.shape for x in call.params["grid_mapping"].scratch_avals]
    assert [x[::2] for x in scratch if len(x) == 3] == 2 * [(2, 128)]
    assert (2, 2) in scratch
    got = np.asarray(stored_decode_attn(pool, 1, q, tables, lengths))
    full = np.asarray(kv_walk.walk_attn(
        pool.k, pool.v, 1, q, tables, jnp.zeros_like(lengths), lengths, 1.0))
    assert full.shape == (b, h, m) and got.shape == (b, h, pool.latent_rank)
    np.testing.assert_array_equal(got, full[..., :pool.latent_rank])
    assert np.abs(full[..., pool.latent_rank:]).min() > 0


# ---------------------------------------------------------------------
# the walk over a RING: a window layer's short table, block ``j`` of the
# sequence in entry ``j mod entries``, read from the block that holds
# the row's first attendable position to the one that holds its last,
# under the sliding rule (Laguna) and the aligned one (EvaByte)
R_HQ, R_HKV, R_DH = 6, 2, 8
R_BLK, R_WINDOW = 4, 16
R_ENTRIES = R_WINDOW // R_BLK + 2
# in one batch: before the ring's first wrap; exactly at a window
# boundary (``last % window == 0``: ONE live position under the aligned
# rule; a start that is no block multiple under the sliding one); one
# short of it (a whole window under both); wrapped twice; a start inside
# a block before the first wrap; a bucket's padded row
R_LENGTHS = np.asarray([7, 33, 32, 55, 22, 1], np.int32)
R_POISONED = 3          # ``last`` 54: block 13, in entry 1, offset 2


def _ring_case(kv_dtype, poison):
    """A two-layer window pool (the read is of layer 1), every byte
    random and finite: a ring holds stale rows of its entries' last use
    wherever the mask hides them, the largest a freed sequence could
    leave beyond each row's last position. ``poison``: a NaN in a DEAD
    entry of row ``R_POISONED``'s ring (the one after the block being
    written: behind the window under both rules) or in the stale rows
    of its last live block."""
    rng = np.random.default_rng(2)
    b = len(R_LENGTHS)
    nb = 1 + (b - 1) * R_ENTRIES
    tables = 1 + rng.permutation((b - 1) * R_ENTRIES).reshape(b - 1, -1)
    tables = np.concatenate(
        [tables, np.full((1, R_ENTRIES), SCRATCH_BLOCK)]).astype(np.int32)
    pool = init_pool(2, nb, R_HKV, R_BLK, R_DH, kv_dtype)
    pos = np.asarray(ring_positions(R_LENGTHS - 1, R_ENTRIES, R_BLK))
    beyond = np.zeros((nb, R_BLK), bool)
    for r in range(b):
        late = (pos[r] >= R_LENGTHS[r]).reshape(R_ENTRIES, R_BLK)
        beyond[tables[r]] |= late
    last = R_LENGTHS[R_POISONED] - 1
    sides = []
    for _ in "kv":
        src = rng.normal(size=(2, nb, R_BLK, R_HKV * R_DH)).astype(
            np.float32)
        src = np.where(beyond[None, :, :, None], 3e4, src)
        if poison == "dead":
            src[:, tables[R_POISONED, (last // R_BLK + 1) % R_ENTRIES]] = (
                np.nan)
        elif poison == "last":
            src[:, tables[R_POISONED, last // R_BLK % R_ENTRIES],
                last % R_BLK + 1:] = np.nan
        sides.append(jnp.asarray(src, pool.k.dtype))
    pool = pool._replace(k=sides[0], v=sides[1])
    q = jnp.asarray(rng.normal(size=(b, R_HQ, R_DH)), jnp.float32)
    return pool, q, jnp.asarray(tables), pos


@pytest.mark.parametrize("poison", ["none", "dead", "last"])
@pytest.mark.parametrize("stats", [False, True])
@pytest.mark.parametrize("kv_dtype", ["f32", "bf16"])
@pytest.mark.parametrize("rule", ["sliding", "aligned"])
def test_walked_ring_matches_the_plain_read_and_the_oracle(rule, kv_dtype,
                                                           stats, poison):
    """``stored_decode_attn`` over a window layer's ring, which takes
    the walk under the same rule as a full pool, against
    ``gathered_decode_attn`` (the form the tests hold the walk to:
    ``ring_positions`` and the mask functions) and against a NumPy model
    of the rule itself, ``softmax`` over the positions ``start <= t <=
    last`` of each row's ring, under the full kind's bounds: an f32 pool
    to reduction order, a bf16 pool to the two roundings of the small
    operands. With ``stats`` the score maximum and the sum beside it
    agree too. A NaN in a DEAD entry of a row's ring does not reach the
    row, bit for bit; one in the stale rows of its last live block
    poisons that row and no other, as in the plain read."""
    aligned = rule == "aligned"
    lengths = jnp.asarray(R_LENGTHS)

    def walked(pool, q, tables):
        assert walks(pool)
        return [np.asarray(x) for x in jax.tree.leaves(jax.jit(
            lambda q: stored_decode_attn(pool, 1, q, tables, lengths,
                                         R_WINDOW, aligned, stats))(q))]

    pool, q, tables, pos = _ring_case(kv_dtype, "none")
    got = walked(pool, q, tables)
    assert len(got) == (3 if stats else 1)
    if poison != "none":
        bad = walked(*_ring_case(kv_dtype, poison)[:3])
        rows = np.arange(len(R_LENGTHS)) != R_POISONED
        for x, y in zip(bad, got):
            np.testing.assert_array_equal(x[rows], y[rows])
        if poison == "dead":
            for x, y in zip(bad, got):
                np.testing.assert_array_equal(x, y)
        else:
            assert np.isnan(bad[0][R_POISONED]).all()
        return
    plain = [np.asarray(x) for x in jax.tree.leaves(jax.jit(
        lambda q: gathered_decode_attn(pool, 1, q, tables, lengths,
                                       R_WINDOW, aligned, stats))(q))]
    # the rule, written out: one range of positions a row
    last = R_LENGTHS - 1
    start = np.maximum(last // R_WINDOW * R_WINDOW if aligned
                       else last - R_WINDOW + 1, 0)
    assert (np.asarray(ring_start(last, R_WINDOW, aligned)) == start).all()
    live = (pos >= start[:, None]) & (pos <= last[:, None])     # [b, T]
    assert live.sum(1).tolist() == (
        [7, 1, 16, 7, 6, 1] if aligned else [7, 16, 16, 16, 16, 1])
    b, h, dh = q.shape
    kc, vc = (np.asarray(_heads_major(side[1][np.asarray(tables)], dh),
                         np.float64).transpose(0, 2, 1, 3, 4).reshape(
                             b, R_HKV, -1, dh) for side in (pool.k, pool.v))
    qg = np.asarray(q, np.float64).reshape(b, R_HKV, h // R_HKV, dh)
    s = np.einsum("bkgd,bktd->bkgt", qg, kc) / np.sqrt(dh)
    s = np.where(live[:, None, None, :], s, -np.inf)
    m = s.max(-1)
    e = np.exp(s - m[..., None])
    want = np.einsum("bkgt,bktd->bkgd", e / e.sum(-1, keepdims=True),
                     np.where(live[:, None, :, None], vc, 0))
    scale = np.abs(want).max()
    assert got[0].shape == (b, h, dh) and got[0].dtype == np.float32
    assert np.isfinite(got[0]).all()
    err = np.abs(got[0].reshape(want.shape) - want).max(-1)     # [b, k, g]
    if kv_dtype == "f32":
        assert err.max() <= 1e-5 * scale
        assert np.abs(got[0] - plain[0]).max() <= 1e-5 * scale
    else:
        bound = _two_roundings_bound(qg, kc, vc, live)
        assert (err <= bound + 1e-5 * scale).all()
        assert err.max() > 1e-4 * scale     # bf16 operands, no upcast
        assert (np.abs(got[0] - plain[0]).reshape(want.shape).max(-1)
                <= bound + 1e-5 * scale).all()
    if stats:
        # the same statistics from either form: what ``join_reads`` takes
        tol = 1e-5 if kv_dtype == "f32" else 2.0 ** -7
        np.testing.assert_allclose(got[1], m.reshape(b, h), rtol=tol,
                                   atol=tol)
        np.testing.assert_allclose(got[1], plain[1], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got[2], plain[2], rtol=1e-4)


# -- K and V rows of different widths, and a sink in the softmax -------------
# (``models/face.py::KVRow``: a key head of 12 lanes beside a value head
# of 8, as MiMo-V2-Flash's 192 / 128 in small)

S_HQ, S_HKV, S_DK, S_DV = 6, 2, 12, 8


def _split_case(kv_dtype, ring: bool):
    """``_ring_case``'s batch (or the same lengths as a full pool's
    tables, ``ring`` False) over a pool whose K row is ``2 x 12`` lanes
    and whose V row is ``2 x 8``, every byte random and finite, the
    stale rows beyond each row's last position large."""
    rng = np.random.default_rng(5)
    b = len(R_LENGTHS)
    entries = R_ENTRIES if ring else -(-int(R_LENGTHS.max()) // R_BLK)
    nb = 1 + (b - 1) * entries
    tables = 1 + rng.permutation((b - 1) * entries).reshape(b - 1, -1)
    tables = np.concatenate(
        [tables, np.full((1, entries), SCRATCH_BLOCK)]).astype(np.int32)
    pool = init_pool(2, nb, S_HKV, R_BLK, S_DK, kv_dtype, v_head_dim=S_DV)
    assert pool.k.shape[-1] == S_HKV * S_DK
    assert pool.v.shape[-1] == S_HKV * S_DV
    assert pool.row == (S_HKV, S_DK, S_DV) and pool.kv_heads == S_HKV
    pos = (np.asarray(ring_positions(R_LENGTHS - 1, entries, R_BLK)) if ring
           else np.broadcast_to(np.arange(entries * R_BLK),
                                (b, entries * R_BLK)))
    beyond = np.zeros((nb, R_BLK), bool)
    for r in range(b):
        beyond[tables[r]] |= (pos[r] >= R_LENGTHS[r]).reshape(entries, R_BLK)
    sides = []
    for side in (pool.k, pool.v):
        src = rng.normal(size=side.shape).astype(np.float32)
        sides.append(jnp.asarray(
            np.where(beyond[None, :, :, None], 3e4, src), side.dtype))
    pool = pool._replace(k=sides[0], v=sides[1])
    q = jnp.asarray(rng.normal(size=(b, S_HQ, S_DK)), jnp.float32)
    sink = jnp.asarray(rng.normal(size=(S_HQ,)) + 1.0, jnp.float32)
    return pool, q, jnp.asarray(tables), pos, sink


@pytest.mark.parametrize("case", ["ring-f32-sink", "ring-bf16-sink",
                                  "ring-f32-sink-stats", "full-f32-plain",
                                  "full-bf16-sink"])
def test_walk_over_split_widths_and_a_sink_matches_the_plain_read(case):
    """The walk with a K row and a V row of different widths (the
    result's lanes are V's) and a per-head sink (where the running
    maximum and sum start: ``ops/kv_walk.py``), against
    ``gathered_decode_attn`` handed the same and against a NumPy model
    that writes the sink as what it is: one more term of the
    denominator, ``exp(sink - m)``, with no value row. Only the cases
    that differ in a code path: the ring under the sliding rule at both
    dtypes, the statistics (which count the sink), the full kind with
    and without a sink."""
    kind, kv_dtype, *opts = case.split("-")
    ring, sunk, stats = kind == "ring", "sink" in opts, "stats" in opts
    pool, q, tables, pos, sink = _split_case(kv_dtype, ring)
    sink = sink if sunk else None
    lengths = jnp.asarray(R_LENGTHS)
    window = R_WINDOW if ring else 0

    def read(fn):
        return [np.asarray(x) for x in jax.tree.leaves(jax.jit(
            lambda q: fn(pool, 1, q, tables, lengths, window, False, stats,
                         sink))(q))]

    assert walks(pool)
    got, plain = read(stored_decode_attn), read(gathered_decode_attn)
    last = R_LENGTHS - 1
    start = np.maximum(last - R_WINDOW + 1, 0) if ring else 0 * last
    live = (pos >= start[:, None]) & (pos <= last[:, None])
    b, h, _ = q.shape
    kc = np.asarray(_heads_major(pool.k[1][np.asarray(tables)], S_DK),
                    np.float64).transpose(0, 2, 1, 3, 4).reshape(
                        b, S_HKV, -1, S_DK)
    vc = np.asarray(_heads_major(pool.v[1][np.asarray(tables)], S_DV),
                    np.float64).transpose(0, 2, 1, 3, 4).reshape(
                        b, S_HKV, -1, S_DV)
    qg = np.asarray(q, np.float64).reshape(b, S_HKV, h // S_HKV, S_DK)
    s = np.einsum("bkgd,bktd->bkgt", qg, kc) / np.sqrt(S_DK)
    s = np.where(live[:, None, None, :], s, -np.inf)
    m = s.max(-1)
    if sunk:
        sk = np.asarray(sink, np.float64).reshape(S_HKV, -1)
        m = np.maximum(m, sk)
    e = np.exp(s - m[..., None])
    total = e.sum(-1) + (np.exp(sk - m) if sunk else 0.0)
    want = np.einsum("bkgt,bktd->bkgd", e / total[..., None],
                     np.where(live[:, None, :, None], vc, 0))
    scale = np.abs(want).max()
    assert got[0].shape == (b, h, S_DV) and got[0].dtype == np.float32
    err = np.abs(got[0].reshape(want.shape) - want).max(-1)
    if kv_dtype == "f32":
        assert err.max() <= 1e-5 * scale
        assert np.abs(got[0] - plain[0]).max() <= 1e-5 * scale
    else:
        # ``_two_roundings_bound`` normalises by the scores' sum alone;
        # a sink only makes every probability smaller
        bound = _two_roundings_bound(qg, kc, vc, live)
        assert (err <= bound + 1e-5 * scale).all()
        assert (np.abs(got[0] - plain[0]).reshape(want.shape).max(-1)
                <= bound + 1e-5 * scale).all()
    if sunk:
        # the sink took mass: the same read without it lies far off
        bare = np.asarray(jax.jit(lambda q: stored_decode_attn(
            pool, 1, q, tables, lengths, window))(q))
        assert np.abs(bare - got[0]).max() > 0.05 * scale
    if stats:
        np.testing.assert_allclose(got[1], m.reshape(b, h), rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(got[2], total.reshape(b, h), rtol=1e-4)
        np.testing.assert_allclose(got[1], plain[1], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got[2], plain[2], rtol=1e-4)


@pytest.mark.parametrize("kv_dtype", ["f32", "bf16"])
def test_split_width_rows_come_back_at_position_head_and_lane(kv_dtype):
    """The writes, the chunk's view and the block documents take each
    side's width from the pool's row: rows written as a chunk and as
    decode rows come back from ``gather_layer`` as ``k [H_kv, T, 12]``
    and ``v [H_kv, T, 8]``; a block document is ``[L, n, H_kv, block,
    12]`` beside ``[L, n, H_kv, block, 8]`` and implants bit for bit;
    and a position's bytes are ``H_kv x (12 + 8)`` elements."""
    from distributed_llm_code_samples_tpu.decode.paged import (
        kv_bytes_per_token, pool_bytes)
    rng = np.random.default_rng(7)
    pool = init_pool(L, NB, HKV, BLK, S_DK, kv_dtype, v_head_dim=S_DV)
    table = jnp.asarray([3, 1, 5, 0], jnp.int32)
    k = rng.normal(size=(10, HKV, S_DK)).astype(np.float32)
    v = rng.normal(size=(10, HKV, S_DV)).astype(np.float32)
    pool = write_chunk(pool, 1, table, 0, jnp.asarray(k[:8]),
                       jnp.asarray(v[:8]), kv_dtype)
    for t in (8, 9):
        pool = write_rows(pool, 1, table[t // BLK][None],
                          jnp.asarray([t % BLK]), jnp.asarray(k[t:t + 1]),
                          jnp.asarray(v[t:t + 1]), kv_dtype)
    gk, gv = gather_layer(pool, 1, table)
    assert gk.shape == (HKV, 4 * BLK, S_DK) and gv.shape == (HKV, 4 * BLK,
                                                             S_DV)
    tol = _tolerance(kv_dtype, k)
    assert np.abs(np.asarray(gk)[:, :10].transpose(1, 0, 2) - k).max() <= tol
    assert np.abs(np.asarray(gv)[:, :10].transpose(1, 0, 2) - v).max() <= tol
    doc = extract_blocks(pool, [3, 1])
    assert doc["k"].shape == (L, 2, HKV, BLK, S_DK)
    assert doc["v"].shape == (L, 2, HKV, BLK, S_DV)
    fresh = init_pool(L, NB, HKV, BLK, S_DK, kv_dtype, v_head_dim=S_DV)
    fresh = implant_block(fresh, 6, jnp.asarray(doc["k"][:, 0]),
                          jnp.asarray(doc["v"][:, 0]))
    assert np.array_equal(np.asarray(fresh.k[:, 6], np.float32),
                          np.asarray(pool.k[:, 3], np.float32))
    assert np.array_equal(np.asarray(fresh.v[:, 6], np.float32),
                          np.asarray(pool.v[:, 3], np.float32))
    per_tok = kv_bytes_per_token(kv_dtype, L, HKV, S_DK, v_head_dim=S_DV)
    assert per_tok == L * HKV * (S_DK + S_DV) * pool.k.dtype.itemsize
    assert pool_bytes(pool)[0] == per_tok * NB * BLK
    # equal widths: the byte count it always was
    assert kv_bytes_per_token(kv_dtype, L, HKV, DH) == (
        2 * L * HKV * DH * pool.k.dtype.itemsize)
