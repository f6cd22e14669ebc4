"""The decode program's state update, in place, against its oracle.

``ops/ssm.py::conv_step_in_place`` and ``scan_step_in_place`` (two
Pallas kernels over the batch's rows) advance the state where
``decode/paged.py::RecurrentState`` stores it; ``conv_step`` /
``scan_step`` on gathered copies are the same mathematics and stay in
the tree as the oracle only. On the CPU the kernels run in the Pallas
interpreter; that they compile for the chip at the served widths is
``tests/test_chip_compile.py``'s.

Tolerance: ``TOL = 2e-4`` as in ``tests/test_hybrid_lm.py`` (float32 on
both sides; they differ in the order of the sum over the state index at
most: readings are 1e-6). Untouched rows are compared bit for bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_code_samples_tpu.decode.paged import init_state
from distributed_llm_code_samples_tpu.models.face import StateRow
from distributed_llm_code_samples_tpu.ops import ssm

TOL = 2e-4
LAYERS, SLOTS, N, D = 3, 6, 4, 128

# case -> (the batch's rows, d_conv); row SLOTS is the scratch row. A
# case named ``no-bias`` runs the convolution without one (None in the
# bias's place: the gated short convolution of ``models/lfm2_moe_lm.py``,
# ``K = 3``)
CASES = {
    "permuted-rows": ([4, 1, 5, 0, 3, 2], 4),
    "bucket-smaller-than-the-slots": ([3, 0], 4),
    "padded-rows-on-the-scratch-row": ([2, 5, SLOTS, SLOTS, SLOTS, SLOTS,
                                        SLOTS, SLOTS], 4),
    "one-row": ([1], 4),
    "two-taps-permuted": ([5, 2, 0, 1], 2),
    "two-taps-padded": ([4, SLOTS, SLOTS, SLOTS], 2),
    "three-taps-no-bias-permuted": ([5, 2, 0, 1, 4, 3], 3),
    "three-taps-no-bias-padded": ([4, 1, SLOTS, SLOTS], 3),
    "three-taps-no-bias-one-row": ([2], 3),
}


def _operands(rows, k, seed=0, bias=True):
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 12))

    def normal(*shape):
        return jax.random.normal(next(ks), shape, jnp.float32)

    b = len(rows)
    zero = init_state(LAYERS, SLOTS, StateRow(D, k, N, D))
    state = zero._replace(conv=normal(*zero.conv.shape),
                          ssm=normal(*zero.ssm.shape))
    return dict(
        state=state, rows=jnp.asarray(rows, jnp.int32), x=normal(b, D),
        w=normal(k, D), bias=normal(D) if bias else None,
        dt=jax.nn.softplus(normal(b, D)), a=-jnp.exp(normal(N, D)),
        b=normal(b, N), c=normal(b, N), d=normal(D))


def _untouched(store, rows, layer):
    """Every row of ``store [L, S, ..]`` but ``rows`` of ``layer``."""
    keep = np.ones(store.shape[:2], bool)
    keep[layer, np.asarray(rows)] = False
    return np.asarray(store)[keep]


@pytest.mark.parametrize("layer", [0, LAYERS - 1])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_tail_in_place_is_conv_step_on_gathered_copies(case, layer):
    rows, k = CASES[case]
    o = _operands(rows, k, bias="no-bias" not in case)
    store = o["state"].conv
    assert store.shape == (LAYERS, SLOTS + 1, 1, (k - 1) * D)
    want_y, want_tail = ssm.conv_step(
        o["x"], store[layer, o["rows"]].reshape(len(rows), k - 1, D),
        o["w"], o["bias"])
    y, new = ssm.conv_step_in_place(o["x"], store, o["w"], o["bias"],
                                    layer=layer, rows=o["rows"])
    assert new.shape == store.shape and new.dtype == store.dtype
    np.testing.assert_allclose(y, want_y, atol=TOL, rtol=0)
    real = np.asarray([i for i, r in enumerate(rows) if r != SLOTS])
    np.testing.assert_allclose(
        new[layer, o["rows"][real], 0],
        want_tail.reshape(len(rows), -1)[real], atol=TOL, rtol=0)
    assert np.array_equal(_untouched(new, rows, layer),
                          _untouched(store, rows, layer))


@pytest.mark.parametrize("layer", [0, LAYERS - 1])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_scan_state_in_place_is_scan_step_on_gathered_copies(case,
                                                                 layer):
    rows, k = CASES[case]
    o = _operands(rows, k, seed=1)
    store = o["state"].ssm
    args = (o["x"], o["dt"], o["a"], o["b"], o["c"], o["d"])
    want_y, want_s = ssm.scan_step(*args, store[layer, o["rows"]])
    y, new = ssm.scan_step_in_place(*args, store, layer=layer,
                                    rows=o["rows"])
    assert new.shape == store.shape and new.dtype == store.dtype
    np.testing.assert_allclose(y, want_y, atol=TOL, rtol=0)
    real = np.asarray([i for i, r in enumerate(rows) if r != SLOTS])
    np.testing.assert_allclose(new[layer, o["rows"][real]], want_s[real],
                               atol=TOL, rtol=0)
    assert np.abs(np.asarray(want_s[real]
                             - store[layer, o["rows"][real]])).max() > 0.1
    assert np.array_equal(_untouched(new, rows, layer),
                          _untouched(store, rows, layer))


@pytest.mark.parametrize("k,bias", [(4, True), (3, False), (2, True)])
def test_the_chunk_is_the_step_token_by_token(k, bias):
    """``conv_chunk`` over 9 tokens of one sequence is ``conv_step``
    nine times on a batch of that one row — output and the tail carried
    on — with a bias and without (``K = 3``: the short convolution's)."""
    o = _operands([0], k, seed=3, bias=bias)
    x = jax.random.normal(jax.random.PRNGKey(4), (9, D), jnp.float32)
    tail = jax.random.normal(jax.random.PRNGKey(5), (k - 1, D), jnp.float32)
    y, new = ssm.conv_chunk(x, tail, o["w"], o["bias"])
    step_tail, ys = tail[None], []
    for t in range(x.shape[0]):
        y_t, step_tail = ssm.conv_step(x[t:t + 1], step_tail, o["w"],
                                       o["bias"])
        ys.append(y_t[0])
    np.testing.assert_allclose(y, jnp.stack(ys), atol=TOL, rtol=0)
    np.testing.assert_allclose(new, step_tail[0], atol=TOL, rtol=0)
    if not bias:        # a zero input then reads zero: nothing is added
        zero, _ = ssm.conv_chunk(0 * x, 0 * tail, o["w"], None)
        assert not np.asarray(zero).any()


@pytest.mark.parametrize("case", ["permuted-rows",
                                  "padded-rows-on-the-scratch-row",
                                  "three-taps-no-bias-padded"])
def test_the_mixed_forms_are_the_step_on_the_batch_and_the_chunk_on_the_rest(
        case):
    """``conv_mixed`` / ``scan_mixed`` over a batch's rows and then one
    sequence's 5-token chunk (the mixed program's state seam, where a
    mixer's weight products run once over both kinds of row) are the
    in-place step on the first rows and the chunk form on the rest:
    outputs, the store and the sequence's own tail and state, bit for
    bit (the same calls on the same rows)."""
    rows, k = CASES[case]
    o = _operands(rows, k, seed=7, bias="no-bias" not in case)
    b, at = len(rows), dict(layer=1, rows=o["rows"])
    extra = iter(jax.random.split(jax.random.PRNGKey(8), 8))

    def more(x, n=5):       # the chunk's rows after the batch's
        return jnp.concatenate([x, jax.random.normal(
            next(extra), (n,) + x.shape[1:], jnp.float32)])

    x, tail = more(o["x"]), jax.random.normal(next(extra), (k - 1, D))
    y, (store, new_tail) = ssm.conv_mixed(
        x, (o["state"].conv, tail), o["w"], o["bias"], **at)
    yb, want_store = ssm.conv_step_in_place(
        x[:b], o["state"].conv, o["w"], o["bias"], **at)
    yc, want_tail = ssm.conv_chunk(x[b:], tail, o["w"], o["bias"])
    assert np.array_equal(y, jnp.concatenate([yb, yc]))
    assert np.array_equal(store, want_store)
    assert np.array_equal(new_tail, want_tail)

    dt, bm, cm = (more(o[name]) for name in ("dt", "b", "c"))
    dt = jnp.abs(dt)
    s = jax.random.normal(next(extra), (N, D))
    y, (store, new_s) = ssm.scan_mixed(
        x, dt, o["a"], bm, cm, o["d"], (o["state"].ssm, s), **at)
    yb, want_store = ssm.scan_step_in_place(
        x[:b], dt[:b], o["a"], bm[:b], cm[:b], o["d"], o["state"].ssm, **at)
    yc, want_s = ssm.scan_chunk(x[b:], dt[b:], o["a"], bm[b:], cm[b:],
                                o["d"], s)
    assert np.array_equal(y, jnp.concatenate([yb, yc]))
    assert np.array_equal(store, want_store)
    assert np.array_equal(new_s, want_s)
    assert y.shape == (b + 5, D)


@pytest.mark.parametrize("k", [4, 2])
def test_padded_rows_on_the_scratch_row_neither_fault_nor_leak(k):
    """Several rows of the batch name the one scratch row: several grid
    steps of both kernels read and write it in place. Nothing uses what
    it then holds; the calls must run, leave it finite (one of the
    padded rows' own updates, whole) and every real row exact."""
    rows = [0, SLOTS, SLOTS, SLOTS, 3, SLOTS, SLOTS, SLOTS]
    o = _operands(rows, k, seed=2)
    state = o["state"]
    _, conv = ssm.conv_step_in_place(o["x"], state.conv, o["w"],
                                     o["bias"], layer=1, rows=o["rows"])
    args = (o["x"], o["dt"], o["a"], o["b"], o["c"], o["d"])
    _, s = ssm.scan_step_in_place(*args, state.ssm, layer=1, rows=o["rows"])
    _, want_tail = ssm.conv_step(
        o["x"], state.conv[1, o["rows"]].reshape(len(rows), k - 1, D),
        o["w"], o["bias"])
    _, want_s = ssm.scan_step(*args, state.ssm[1, o["rows"]])
    scratch = state.scratch_row
    assert scratch == SLOTS
    assert np.isfinite(np.asarray(conv[1, scratch])).all()
    assert np.isfinite(np.asarray(s[1, scratch])).all()
    # the scratch row holds one of its padded rows' updates, whole
    pads = [i for i, r in enumerate(rows) if r == scratch]
    assert any(np.allclose(s[1, scratch], want_s[i], atol=TOL)
               for i in pads)
    assert any(np.allclose(conv[1, scratch, 0], want_tail[i].reshape(-1),
                           atol=TOL) for i in pads)
    for i in (0, 4):
        np.testing.assert_allclose(s[1, rows[i]], want_s[i], atol=TOL,
                                   rtol=0)
        np.testing.assert_allclose(conv[1, rows[i], 0],
                                   want_tail[i].reshape(-1), atol=TOL,
                                   rtol=0)


def test_a_width_the_chip_cannot_tile_refuses_in_one_line(monkeypatch):
    """Compiled (not interpreted), an inner width that is no multiple of
    128 lanes is refused by name before anything is lowered; the
    interpreter takes any width (the tier-1 toys)."""
    o = _operands([0, 1], 4)
    x = o["x"][:, :96]
    store = jnp.zeros((LAYERS, SLOTS + 1, N, 96), jnp.float32)
    tails = jnp.zeros((LAYERS, SLOTS + 1, 1, 3 * 96), jnp.float32)
    scan = (x, x, o["a"][:, :96], o["b"], o["c"], o["d"][:96], store)
    y, _ = ssm.scan_step_in_place(*scan, layer=0, rows=o["rows"])
    assert y.shape == (2, 96)
    monkeypatch.setattr(ssm, "_interpreted", lambda: False)
    with pytest.raises(ValueError, match="no multiple of 128 lanes"):
        ssm.scan_step_in_place(*scan, layer=0, rows=o["rows"])
    with pytest.raises(ValueError, match="no multiple of 128 lanes"):
        ssm.conv_step_in_place(x, tails, o["w"][:, :96], o["bias"][:96],
                               layer=0, rows=o["rows"])


def test_the_tile_follows_the_width_and_the_fast_memory():
    """One algorithm for every width: the tile is the whole inner width
    while a grid step's blocks fit, and the largest whole-lane divisor
    that fits beyond."""
    assert ssm._tile(5120, 96) == 5120       # the hybrid cell's
    assert ssm._tile(128, 96) == 128         # chip_smoke's toy
    wide = 1 << 17
    t = ssm._tile(wide, 96)
    assert t < wide and wide % t == 0 and t % 128 == 0
    assert 2 * 96 * t * 4 <= ssm._VMEM_BUDGET < 2 * 96 * 2 * t * 4
