"""The chunk-summarised (EVA) attention byte LM on the serving path, at
toy size: ``models/evabyte_lm.py`` through ``DecodeEngine`` against the
plain reference ``benchmark/configs/evabyte_lm_reference.py`` (float32
at ``highest``, the whole forward pass over one sequence with the
aligned mask and one softmax over keys and summaries, nothing from the
package). The one family whose every layer keeps TWO stores: the exact
K/V of the current aligned window in the window kind's ring, and one
row of the full kind's pool for every finished chunk
(``models/face.py::CHUNKED``, ``decode/paged.py``).

The toy has the published model's shape in small: d 64, 2 layers, 4
heads of 16 lanes (no grouping), a window of 64 positions in chunks of
16, rotary at theta 100,000, norms with the unit offset, all 320 byte
ids and all 8 prediction heads, float32. ``init_std`` 0.3: at d=64 the
published 0.01275 leaves the blocks' outputs too small for a dropped
one to show.

Tolerance, everywhere below: ``TOL = 2e-4`` on logits whose spread
(standard deviation) is over 1. Both sides are float32 and differ in
the order of their sums (two reads joined by their statistics against
one softmax over the concatenation, a chunk of c rows or a batch of b
against all T at once); 3e-5 was read. The summaries dropped, the
window sliding, ``mu`` or the unit offset left out each read hundreds
of times the tolerance (``test_a_fault_*``).
"""

import dataclasses
import importlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_code_samples_tpu.decode import (DecodeEngine,
                                                     EngineConfig, paged)
from distributed_llm_code_samples_tpu.decode.engine import (CHUNK_COUNTERS,
                                                            ServePolicy,
                                                            WINDOW_COUNTERS)
from distributed_llm_code_samples_tpu.decode.model_config import (
    engine_from_config)
from distributed_llm_code_samples_tpu.models import evabyte_lm
from distributed_llm_code_samples_tpu.models.attention import aligned_mask

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
WINDOW, CHUNK, HEADS, DH, PRED, VOCAB = 64, 16, 4, 16, 8, 320

TOY = dict(model_type="evabyte", attention_class="eva", hidden_size=64,
           intermediate_size=96, num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=4, window_size=WINDOW, chunk_size=CHUNK,
           num_pred_heads=PRED, norm_add_unit_offset=True, rope_theta=100000,
           rope_scaling=None, fp32_logits=True, fp32_skip_add=True,
           mixedp_attn=True, hidden_act="silu", attention_bias=False,
           rms_norm_eps=1e-5, tie_word_embeddings=False, vocab_size=VOCAB,
           max_position_embeddings=1024, init_std=0.3)


def _load(name):
    path = os.path.join(ROOT, "benchmark", "configs", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("evabyte_lm_reference")


@pytest.fixture(scope="module")
def driver():
    return _load("evabyte_engine_driver")


@pytest.fixture(scope="module")
def weights(driver):
    """The benchmark driver's named leaves and the params the engine
    takes, of one seed: the reference and the program get one set of
    arrays."""
    w = driver.make_weights(TOY, 11)
    return w, driver._params(TOY, w)


def engine(params, slots=3, mbps=2, chunk=16, block=16, **kw):
    """``mbps`` blocks of the summaries' pool a sequence: ``mbps * block
    * block`` positions."""
    cfg = EngineConfig(max_slots=slots, n_blocks=1 + slots * mbps,
                       max_blocks_per_seq=mbps, prefill_chunk=chunk,
                       block_size=block)
    policy = kw.pop("policy", None)
    return DecodeEngine(params, HEADS, dataclasses.replace(cfg, **kw),
                        policy=policy)


def prompts_of(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, n).tolist() for n in lens]


# -- (a) prefill + decode through both stores is the full forward -----------


def cached_logits(eng, tokens, chunks, decode_from=None, mixed_with=None,
                  upto=None):
    """All eight heads' logits ``[T, 8, V]`` of one sequence through the
    engine's own program bodies and BOTH stores, in slot 1: the first
    ``decode_from`` tokens prefilled in ``chunks``-sized pieces, the
    rest decoded one at a time. ``mixed_with``: another sequence's
    tokens, decoded one a step in slot 0 while every FULL chunk of the
    first rides in the same ``mixed`` program; its logits come back
    second. Also returns the cache as it stood at the end."""
    p, cfg, pr = eng.params, eng.cfg, eng.programs
    entries = pr.window_blocks
    t = len(tokens) if upto is None else upto
    decode_from = t if decode_from is None else decode_from

    def tables(first, wfirst):
        tab = (first + np.arange(cfg.max_blocks_per_seq)).astype(np.int32)
        return tab, (wfirst + np.arange(entries)).astype(np.int32)

    table, ring = tables(1, 1)
    cache = eng._cache()
    rows, other, pos = [], [], 0
    prefill = jax.jit(
        lambda p, cache, table, ring, pos0, toks, c: pr.prefill_hidden(
            c, p, cache, table, pos0, toks, jnp.int32(1), ring)[:2],
        static_argnums=6)
    decode = jax.jit(
        lambda p, cache, tabs, rings, lengths, toks: pr.decode_hidden(
            tabs.shape[0], p, cache, tabs, lengths, toks, None, rings)[:2])
    if mixed_with is not None:
        mtable, mring = tables(1 + cfg.max_blocks_per_seq, 1 + entries)
        mixed = jax.jit(
            lambda p, cache, f: pr.mixed_hidden(1, p, cache, f)[:2])
        mpos = 0
    while pos < decode_from:
        c = min(chunks, decode_from - pos)
        c = 1 << (c.bit_length() - 1)              # power-of-two chunks
        toks = jnp.asarray(tokens[pos:pos + c], jnp.int32)
        if mixed_with is not None and c == cfg.prefill_chunk:
            f = {"tables": jnp.asarray(mtable[None]),
                 "wtables": jnp.asarray(mring[None]),
                 "lengths": jnp.asarray([mpos], jnp.int32),
                 "tokens": jnp.asarray(mixed_with[mpos:mpos + 1], jnp.int32),
                 "rows": jnp.asarray([0], jnp.int32),
                 "table": jnp.asarray(table), "wtable": jnp.asarray(ring),
                 "pos0": jnp.int32(pos), "chunk": toks, "row": jnp.int32(1)}
            cache, x = mixed(p, cache, f)
            other.append(p.head_all(x[:1]))
            x = x[1:]
            mpos += 1
        else:
            cache, x = prefill(p, cache, jnp.asarray(table),
                               jnp.asarray(ring), jnp.int32(pos), toks, c)
        rows.append(p.head_all(x))
        pos += c
    while pos < t:
        cache, x = decode(p, cache, jnp.asarray(table[None]),
                          jnp.asarray(ring[None]),
                          jnp.asarray([pos], jnp.int32),
                          jnp.asarray(tokens[pos:pos + 1], jnp.int32))
        rows.append(p.head_all(x))
        pos += 1
    got = np.asarray(jnp.concatenate(rows, 0))
    if mixed_with is None:
        return got, cache
    return got, np.asarray(jnp.concatenate(other, 0)), cache


@pytest.mark.parametrize("chunk,chunks,decode_from", [
    (16, 16, 200), (16, 16, 70), (16, 8, 37), (8, 8, 100), (4, 4, 30),
    (16, 16, 0)])
def test_prefill_then_decode_through_both_stores_is_the_reference(
        ref, weights, chunk, chunks, decode_from):
    """230 bytes, three window boundaries crossed: prefilled in chunks
    (each finishing its chunk's summary where it ends on a boundary),
    then decoded one at a time while the ring of ``window / 16 + 2``
    entries turns over and the later rows join 4, 8 and 12 summaries to
    their window: every position's logits, of all eight heads, are the
    reference's full forward."""
    w, params = weights
    tokens = prompts_of([230], seed=1)[0]
    eng = engine(params, chunk=chunk)
    assert eng.programs.window_blocks == WINDOW // 16 + 2
    assert eng.capacity == 2 * 16 * 16
    got, _ = cached_logits(eng, tokens, chunks, decode_from)
    want = np.asarray(ref.logits_all(w, np.asarray(tokens), TOY))
    assert got.shape == want.shape == (230, PRED, VOCAB)
    assert want.std() > 1.0
    assert np.abs(got - want).max() < TOL
    # head 0 is what a step program picks from
    assert np.abs(want[:, 0] - np.asarray(
        ref.logits(w, np.asarray(tokens), TOY))).max() == 0


def test_a_chunk_riding_with_a_decode_row_is_the_reference(ref, weights):
    """The ``mixed`` program's two seams on both stores: every full
    chunk of one sequence (three windows of it) rides with another
    sequence's decode row, and both sequences' logits are the
    reference's."""
    w, params = weights
    a, b = prompts_of([208, 13], seed=2)
    eng = engine(params)
    got, other, _ = cached_logits(eng, a, 16, 208, mixed_with=b)
    assert np.abs(got - np.asarray(ref.logits_all(w, np.asarray(a), TOY))
                  ).max() < TOL
    want = np.asarray(ref.logits_all(w, np.asarray(b), TOY))[:len(other)]
    assert len(other) == 13 and np.abs(other - want).max() < TOL


def test_a_row_in_its_first_window_is_plain_causal_attention(ref, weights):
    """Up to the first boundary the summaries' read is empty (its mask
    hides every row, the join gives it no weight) and the layer IS
    causal attention: the reference with a window nothing reaches."""
    w, params = weights
    tokens = prompts_of([WINDOW], seed=3)[0]
    got, _ = cached_logits(engine(params), tokens, 16, 40)
    causal = np.asarray(ref.logits_all(w, np.asarray(tokens),
                                       dict(TOY, window_size=1 << 20)))
    assert np.abs(got - causal).max() < TOL
    # ... and one position later it is not
    longer = prompts_of([WINDOW + 16], seed=3)[0]
    eva = np.asarray(ref.logits_all(w, np.asarray(longer), TOY))
    causal = np.asarray(ref.logits_all(w, np.asarray(longer),
                                       dict(TOY, window_size=1 << 20)))
    assert np.abs(eva - causal)[:WINDOW].max() == 0
    assert np.abs(eva - causal)[WINDOW:].max() > 100 * TOL


FAULTS = {
    "summaries_dropped": lambda p: dataclasses.replace(
        p, mu=jnp.full_like(p.mu, -1e4)),
    "mu_left_out": lambda p: dataclasses.replace(p, mu=jnp.zeros_like(p.mu)),
    "phi_left_out": lambda p: dataclasses.replace(
        p, phi=jnp.zeros_like(p.phi)),
    "unit_offset_left_out": lambda p: dataclasses.replace(
        p, unit_offset=False),
    "another_rotary_base": lambda p: dataclasses.replace(p, theta=1e4),
    "window_sliding": None,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_one_mechanism_fails_the_tolerance(monkeypatch, ref,
                                                      weights, fault):
    """Each part of the mechanism rules the logits at this size: with
    one of them wrong in the program, the comparison fails by far."""
    w, params = weights
    tokens = prompts_of([150], seed=4)[0]
    if FAULTS[fault] is not None:
        params = FAULTS[fault](params)
    else:
        attention = importlib.import_module(
            "distributed_llm_code_samples_tpu.models.attention")
        monkeypatch.setattr(attention, "aligned_mask", attention.window_mask)
    got, _ = cached_logits(engine(params), tokens, 16, 100)
    want = np.asarray(ref.logits_all(w, np.asarray(tokens), TOY))
    assert np.abs(got - want).max() > 100 * TOL


def test_lower_precision_in_the_float32_toy_fails_the_tolerance(ref,
                                                                weights):
    """The reference's own lower-precision modes, the controls the
    benchmark's ``correct`` has to refuse, lie far outside ``TOL``."""
    w, _ = weights
    tokens = np.asarray(prompts_of([150], seed=5)[0])
    want = np.asarray(ref.logits(w, tokens, TOY))
    for mode in ("bf16", "int8"):
        low = np.asarray(ref.logits(w, tokens, TOY, mode))
        assert np.abs(low - want).max() > 100 * TOL, mode


# -- (b) the parts, each against its formula --------------------------------


def test_the_join_of_two_reads_is_one_softmax_over_both():
    """``paged.join_reads`` of two reads' statistics against one
    softmax over the concatenated scores; an EMPTY read (every score
    masked) gets no weight, whatever it returned, a NaN among it."""
    rng = np.random.default_rng(0)
    s1, s2 = rng.normal(size=(3, 4, 10)) * 3, rng.normal(size=(3, 4, 7)) * 3
    v1, v2 = rng.normal(size=(3, 10, 5)), rng.normal(size=(3, 7, 5))

    def read(s, v):
        m = s.max(-1)
        p = np.exp(s - m[..., None])
        l = p.sum(-1)
        return (jnp.asarray(np.einsum("bht,btd->bhd", p / l[..., None], v)),
                jnp.asarray(m), jnp.asarray(l))

    both = np.concatenate([s1, s2], -1)
    p = np.exp(both - both.max(-1, keepdims=True))
    want = np.einsum("bht,btd->bhd", p / p.sum(-1, keepdims=True),
                     np.concatenate([v1, v2], 1))
    got = np.asarray(paged.join_reads(read(s1, v1), read(s2, v2)))
    assert np.abs(got - want).max() < 1e-6
    # three reads, and the order does not matter
    s3, v3 = rng.normal(size=(3, 4, 2)), rng.normal(size=(3, 2, 5))
    a = paged.join_reads(read(s1, v1), read(s2, v2), read(s3, v3))
    b = paged.join_reads(read(s3, v3), read(s1, v1), read(s2, v2))
    assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-6
    empty = (jnp.full((3, 4, 5), jnp.nan), jnp.full((3, 4), -1e30),
             jnp.full((3, 4), 16.0))
    alone = np.asarray(paged.join_reads(read(s1, v1), empty))
    assert np.abs(alone - np.asarray(read(s1, v1)[0])).max() < 1e-6


def test_chunk_summary_against_the_direct_formula(weights):
    _, params = weights
    rng = np.random.default_rng(1)
    k = rng.normal(size=(5, CHUNK, HEADS * DH)).astype(np.float32)
    v = rng.normal(size=(5, CHUNK, HEADS * DH)).astype(np.float32)
    kt, vt = params.chunk_summary(1, jnp.asarray(k), jnp.asarray(v))
    phi, mu = np.asarray(params.phi[1]), np.asarray(params.mu[1])
    assert kt.shape == vt.shape == (5, HEADS, DH)
    for n in range(5):
        for h in range(HEADS):
            kh = k[n, :, h * DH:(h + 1) * DH]
            vh = v[n, :, h * DH:(h + 1) * DH]
            s = kh @ phi[h] / np.sqrt(DH)
            alpha = np.exp(s - s.max()) / np.exp(s - s.max()).sum()
            assert np.abs(alpha @ kh + mu[h] - kt[n, h]).max() < 1e-5
            assert np.abs(alpha @ vh - vt[n, h]).max() < 1e-5


def test_the_aligned_mask_against_its_rule():
    """``u(t_k) == u(t_q) and t_k <= t_q``, and a ring entry that holds
    no position yet (negative) is hidden; over the ring's own positions
    (``paged.ring_positions``) too."""
    q = np.arange(0, 200)[:, None]
    k = np.arange(-20, 200)[None, :]
    want = (k >= 0) & (k // WINDOW == q // WINDOW) & (k <= q)
    assert (np.asarray(aligned_mask(q, k, WINDOW)) == want).all()
    entries = WINDOW // 16 + 2
    for last in (0, 15, 63, 64, 100, 127, 128, 200):
        pos = np.asarray(paged.ring_positions(last, entries, 16))
        seen = pos[np.asarray(aligned_mask(last, pos, WINDOW))]
        lo = last // WINDOW * WINDOW
        assert sorted(seen.tolist()) == list(range(lo, last + 1)), last


@pytest.mark.parametrize("lengths", [(0, 1, 16, 40), (5, 0, 33, 17)])
def test_both_forms_of_the_read_hand_back_the_same_statistics(lengths):
    """``stored_decode_attn(stats=True)``: the walk (a float pool of the
    full kind) and the plain gather give the same result, maximum and
    sum; a row of length 0 reads masked whole from either."""
    rng = np.random.default_rng(2)
    b, mb = len(lengths), 3
    pool = paged.init_pool(2, 1 + b * mb, HEADS, 16, DH, "f32")
    pool = pool._replace(
        k=jnp.asarray(rng.normal(size=pool.k.shape), jnp.float32),
        v=jnp.asarray(rng.normal(size=pool.v.shape), jnp.float32))
    tables = jnp.asarray(1 + np.arange(b * mb).reshape(b, mb), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, HEADS, DH)), jnp.float32)
    n = jnp.asarray(lengths, jnp.int32)
    assert paged.walks(pool)
    walk = paged.stored_decode_attn(pool, 1, q, tables, n, stats=True)
    plain = paged.gathered_decode_attn(pool, 1, q, tables, n, stats=True)
    live = np.asarray(n) > 0
    for a, g in zip(walk, plain):
        assert np.abs(np.asarray(a) - np.asarray(g))[live].max() < 1e-5
    assert (np.asarray(walk[1])[~live] == -1e30).all()
    assert (np.asarray(plain[1])[~live] == -1e30).all()
    # without the statistics: the read as it was
    y = paged.stored_decode_attn(pool, 1, q, tables, n)
    assert np.abs(np.asarray(y) - np.asarray(walk[0]))[live].max() < 1e-6


def _direct_summaries(params, layer, k_rows, v_rows):
    """``chunk_summary`` of the stored rows ``[n, H*dh]`` by blocks."""
    n = k_rows.shape[0] // CHUNK
    kt, vt = params.chunk_summary(
        layer, k_rows[:n * CHUNK].reshape(n, CHUNK, -1),
        v_rows[:n * CHUNK].reshape(n, CHUNK, -1))
    return np.asarray(kt).reshape(n, -1), np.asarray(vt).reshape(n, -1)


def test_a_partial_chunks_summary_comes_from_the_step_that_completes_it(
        weights):
    """A prompt of 40 (chunks 16, 16, 8): rows 0 and 1 of the
    summaries' pool are written by the two full chunks, row 2 by no
    chunk and by no decode step before the one that writes position 47;
    each row is ``chunk_summary`` of the ring's block as stored."""
    _, params = weights
    tokens = prompts_of([60], seed=6)[0]
    eng = engine(params)

    def rows_of(cache, layer=1):
        pool, wpool = cache
        return (np.asarray(pool.k[layer, 1]), np.asarray(pool.v[layer, 1]),
                np.asarray(wpool.k[layer, 1:5]).reshape(64, -1),
                np.asarray(wpool.v[layer, 1:5]).reshape(64, -1))

    for upto, written in ((40, 2), (47, 2), (48, 3), (60, 3)):
        _, cache = cached_logits(engine(params), tokens, 16, 40, upto=upto)
        k, v, ring_k, ring_v = rows_of(cache)
        kt, vt = _direct_summaries(params, 1, jnp.asarray(ring_k),
                                   jnp.asarray(ring_v))
        assert np.abs(k[:written] - kt[:written]).max() < 1e-6, upto
        assert np.abs(v[:written] - vt[:written]).max() < 1e-6, upto
        assert not k[written:].any() and not v[written:].any(), upto
    assert eng.programs.window_blocks == 6


# -- (c) the two stores under the scheduler ---------------------------------


def assert_served_is_the_references(ref, w, full, plen):
    """Every served byte is the reference's first at its position."""
    lg = np.asarray(ref.logits(w, np.asarray(full), TOY))
    rows = lg[plen - 1:len(full) - 1]
    served = np.asarray(full[plen:])
    gap = rows.max(-1) - rows[np.arange(len(served)), served]
    assert gap.max() < TOL


def test_across_three_boundaries_a_ring_and_a_row_a_chunk_and_no_more(
        ref, weights):
    """Served through ``DecodeEngine`` across three window boundaries
    and more: a sequence never holds more than ``window / 16 + 2`` ring
    blocks, writes exactly ``len // 16`` summary rows, every served
    byte is the reference's, and both free lists come back whole."""
    w, params = weights
    eng = engine(params, slots=3, mbps=2)
    ring = eng.programs.window_blocks
    assert ring == 6 and eng.wpool.n_blocks == 1 + 3 * ring
    assert eng.pool.n_blocks == 1 + 3 * 2
    prompts = prompts_of([5, 37, 70, 8], seed=7)
    news = [220, 200, 150, 100]
    uids = [eng.submit(p, n) for p, n in zip(prompts, news)]
    written = summaries = 0
    while eng.active or eng.waiting:
        eng.step()
        held = [len(s.wblocks) for s in eng.slots if s is not None]
        assert all(n <= ring for n in held)
        assert len(eng.free_wblocks) + sum(held) == 3 * ring
        digest = eng.flight[-1]
        if digest["dispatches"]:
            assert 0 < digest["window_rows"] <= digest["full_rows"]
            assert digest["summary_rows"] % (WINDOW // 16) == 0
        written += digest["summaries_written"]
        summaries += digest["summary_rows"]
    for uid, p, n in zip(uids, prompts, news):
        full = eng.finished[uid]
        assert len(full) == len(p) + n
        assert_served_is_the_references(ref, w, full, len(p))
    # the last byte is returned and never cached
    assert written == sum((len(p) + n - 1) // 16
                          for p, n in zip(prompts, news))
    assert summaries > 0
    assert sorted(eng.free_wblocks) == list(range(1, 1 + 3 * ring))
    assert sorted(eng.free_blocks) == list(range(1, 7))
    assert eng.window_pool_utilization() == 0.0
    assert eng.kv_pool_utilization() == 0.0


def test_blocks_needed_counts_rows_of_chunks(weights):
    """A request of ``n`` cached positions needs ``ceil(ceil(n / 16) /
    16)`` blocks of the summaries' pool and a ring of at most ``window
    / 16 + 2``; what does not fit the table's ``mbps * 256`` positions
    is refused at ``submit``."""
    _, params = weights
    eng = engine(params, mbps=2)
    assert eng._blocks_needed(10, 7) == 1
    assert eng._blocks_needed(200, 57) == 1          # 256 positions
    assert eng._blocks_needed(200, 58) == 2
    assert eng._wblocks_needed(10, 7) == 1
    assert eng._wblocks_needed(40, 40) == 5
    assert eng._wblocks_needed(200, 58) == 6
    eng.submit([1] * 500, 13)                        # 512 positions
    with pytest.raises(ValueError, match="cache capacity 512"):
        eng.submit([1] * 500, 14)


def test_the_counters_of_a_step_are_its_rows_reads(weights):
    """``window_rows`` / ``summary_rows`` / ``summaries_written`` of the
    step that launched the rows: a row at position ``t`` sees ``t % 64
    + 1`` positions of its window and ``4 * (t // 64)`` summaries; a
    write that ends on a chunk's last position finishes one."""
    _, params = weights
    eng = engine(params, slots=2, mbps=2)
    eng.submit(prompts_of([72], seed=8)[0], 60)
    got = []
    while eng.active or eng.waiting:
        eng.step()
        d = eng.flight[-1]
        got.append((d["full_rows"], d["window_rows"], d["summary_rows"],
                    d["summaries_written"]))
    assert set(WINDOW_COUNTERS + CHUNK_COUNTERS) <= set(eng.flight[-1])
    # four full chunks: the views end at 15, 31, 47, 63, each finishing
    # a chunk; then 64-71 (8 rows in the second window, 4 summaries)
    # and the first decode row, at 72, in one step
    assert got[:4] == [(16, 16, 0, 1), (32, 32, 0, 1), (48, 48, 0, 1),
                       (64, 64, 0, 1)]
    assert got[4] == (72 + 73, 8 + 9, 4 + 4, 0)
    decode = got[5:5 + 58]
    assert len(decode) == 58
    for i, row in enumerate(decode):
        t = 73 + i
        assert row == (t + 1, t % 64 + 1, 4 * (t // 64),
                       int(t % 16 == 15)), t


def test_no_block_is_reused_and_no_summary_read_before_its_write(weights):
    """The device runs programs in launch order. Replaying the launches
    in that order — each row's writes, then its reads — every position
    a row attends over in its aligned window lies in the ring block its
    table names AND was the last thing written there, and every summary
    row it attends over was written, by an EARLIER launch, for that
    sequence's chunk: with a result still unread between most launches
    (counts advance at launch, values land a step late)."""
    _, params = weights
    eng = engine(params, slots=2, mbps=2, policy=ServePolicy())
    ring, blk = eng.programs.window_blocks, eng.cfg.block_size
    holds: dict = {}        # ring block -> (uid, block j)
    rows_of: dict = {}      # (summary block, offset) -> (uid, chunk j, launch)
    launches = []

    def spy(phase, bucket, fn, p, operand, land, _launch=eng._launch):
        f = eng.programs.wire(phase, bucket).unpack(operand)
        rows = []
        if "wtable" in f:               # the chunk's rows write first
            pos0, c = int(f["pos0"]), len(f.get("chunk", f["tokens"]))
            rows.append((int(np.ravel(f["uid"])[0]), f["wtable"],
                         f["table"], pos0, pos0 + c - 1))
        for j in range(len(f.get("lengths", ()))):
            if f["uids"][j] or f["lengths"][j]:
                rows.append((int(f["uids"][j]), f["wtables"][j],
                             f["tables"][j], int(f["lengths"][j]),
                             int(f["lengths"][j])))
        n = len(launches)
        launches.append((eng._inflight is not None, rows))
        for uid, wtable, table, first, last in rows:
            for pos in range(first, last + 1):
                holds[int(wtable[(pos // blk) % ring])] = (uid, pos // blk)
            if last % blk == blk - 1:
                j = last // blk
                rows_of[int(table[j // blk]), j % blk] = (uid, j, n)
            for pos in range(last // WINDOW * WINDOW, last + 1):
                assert holds[int(wtable[(pos // blk) % ring])] == (
                    uid, pos // blk), (uid, pos)
            for j in range(WINDOW // blk * (last // WINDOW)):
                who, chunk, when = rows_of[int(table[j // blk]), j % blk]
                assert (who, chunk) == (uid, j) and when < n, (uid, j)
        return _launch(phase, bucket, fn, p, operand, land)

    eng._launch = spy
    for p in prompts_of([20, 7, 70, 12], seed=9):
        eng.submit(p, 150)
    eng.run()
    assert len(eng.finished) == 4 and not eng.failed
    assert sum(len(rows) for _, rows in launches) > 500
    # most launches went out with the one before still unread
    assert sum(unread for unread, _ in launches) > len(launches) // 2
    # blocks of both kinds changed hands: 4 sequences through 2 slots
    assert len({uid for uid, _ in holds.values()}) <= 2
    assert len({uid for uid, _, _ in rows_of.values()}) >= 2


@pytest.mark.parametrize("seed", range(4))
def test_random_admit_finish_expire_preempt_leaves_both_lists_whole(
        weights, seed):
    """A random schedule over a small pool with deadlines and
    pool-pressure preemption: at every step no block, of either store,
    is in two tables or in a table and on its free list, and at the end
    both free lists are whole."""
    _, params = weights
    eng = engine(params, slots=3, mbps=2, n_blocks=1 + 4,
                 policy=ServePolicy(deadline_steps=150,
                                    preempt_after_steps=3, max_retries=1))
    rng = np.random.default_rng(seed)
    ring = eng.programs.window_blocks
    todo = 10

    def check():
        for tables, free, usable in (
                (eng.tables, eng.free_blocks, eng.cfg.n_blocks - 1),
                (eng.wtables, eng.free_wblocks, 3 * ring)):
            live = tables[tables > 0].tolist()
            assert len(live) == len(set(live))
            assert not set(live) & set(free)
            assert len(free) == len(set(free))
            assert len(live) + len(free) == usable
        for slot, seq in enumerate(eng.slots):
            held = [] if seq is None else seq.wblocks
            assert eng.wtables[slot][eng.wtables[slot] > 0].tolist() == held

    while todo or eng.active or eng.waiting:
        if todo and rng.random() < 0.2:
            eng.submit(
                rng.integers(0, VOCAB, int(rng.integers(3, 90))).tolist(),
                int(rng.integers(2, 300)))
            todo -= 1
        eng.step()
        check()
    eng.collect()
    check()
    assert sorted(eng.free_wblocks) == list(range(1, 1 + 3 * ring))
    assert sorted(eng.free_blocks) == list(range(1, 5))
    assert len(eng.finished) + len(eng.failed) == 10


def test_a_quarantined_sequence_leaves_no_poison_in_either_store(
        ref, weights):
    """A poisoned request's blocks of BOTH stores go back scrubbed, so
    the next sequence through the same ring and the same summary rows
    is the reference's, past a boundary."""
    w, params = weights
    eng = engine(params, slots=1, mbps=1)
    bad = eng.submit(prompts_of([70], seed=10)[0], 8)
    for _ in range(3):
        eng.step()
    eng.arm_poison(bad)
    eng.run()
    assert bad in eng.failed and eng.quarantined == 1
    assert sorted(eng.free_wblocks) == [1, 2, 3, 4, 5, 6]
    for pool in (eng.pool, eng.wpool):
        assert np.isfinite(np.asarray(pool.k, np.float32)).all()
        assert np.isfinite(np.asarray(pool.v, np.float32)).all()
    p = prompts_of([11], seed=11)[0]
    uid = eng.submit(p, 100)
    eng.run()
    assert_served_is_the_references(ref, w, eng.finished[uid], len(p))


def test_the_records_carry_the_counters_and_report_prints_them(
        tmp_path, capsys, weights):
    """With a writer attached every step's ``engine_step`` record
    (telemetry v24) carries ``summary_rows`` / ``summaries_written``
    beside the window's counters and is schema-valid; ``report`` says
    on its cache-reads line how many summaries a step's rows attended
    over and how many were written."""
    from distributed_llm_code_samples_tpu.report import report_main
    from distributed_llm_code_samples_tpu.runtime.telemetry import (
        METRICS_FILENAME, STEP_SPAN, TelemetryWriter, validate_record)
    _, params = weights
    cfg = EngineConfig(max_slots=2, n_blocks=5, max_blocks_per_seq=2)
    eng = DecodeEngine(params, HEADS, cfg,
                       metrics=TelemetryWriter(str(tmp_path)))
    eng.submit(prompts_of([70], seed=12)[0], 90)
    eng.run()
    eng.metrics.close()
    with open(os.path.join(str(tmp_path), METRICS_FILENAME)) as f:
        recs = [json.loads(l) for l in f]
    steps = [r for r in recs if r.get("span") == STEP_SPAN]
    assert len(steps) == eng.steps
    for rec in steps:
        ok, reason = validate_record(rec)
        assert ok, reason
    assert sum(r["summaries_written"] for r in steps) == (70 + 89) // 16
    assert max(r["summary_rows"] for r in steps) == 2 * (WINDOW // 16)
    assert report_main([str(tmp_path)]) == 0
    text = capsys.readouterr().out
    assert "chunk summaries a step beside the window's positions" in text
    assert "9 written" in text
    # v24: the ring's blocks the decode-side reads fetched, on a line of
    # their own, fewer than the rings' entries
    assert 0 < sum(r["ring_blocks_read"] for r in steps) == (
        eng.ring_blocks_read) < eng.ring_blocks_capacity
    assert "blocks a step fetched of the window layers' rings" in text


# -- (d) what the two stores cannot carry yet refuses, in one line ----------


def _export(eng):
    eng.submit([1, 2, 3], 4)
    eng.step()
    eng.export_sequence(0)


def _snapshot(eng):
    from distributed_llm_code_samples_tpu.decode.supervise import (
        snapshot_state)
    snapshot_state(eng)


def _resume(eng):
    from distributed_llm_code_samples_tpu.decode.supervise import (
        restore_engine_state)
    restore_engine_state(eng, {})


def _mesh():
    from distributed_llm_code_samples_tpu.parallel import (MODEL_AXIS,
                                                           make_mesh)
    return make_mesh({MODEL_AXIS: 2})


REFUSALS = {
    "speculate": lambda p: engine(p, speculate=2),
    "tp": lambda p: DecodeEngine(p, HEADS, EngineConfig(), mesh=_mesh()),
    "spill": lambda p: engine(p, spill_blocks=4),
    "prefix_partial": lambda p: engine(p, prefix_partial=True),
    "export": lambda p: _export(engine(p)),
    "import": lambda p: engine(p).import_sequence({}),
    "snapshot": lambda p: _snapshot(engine(p)),
    "resume": lambda p: _resume(engine(p)),
    "int8": lambda p: engine(p, kv_dtype="int8"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_moves_a_sequence_by_one_table_refuses_in_one_line(weights,
                                                                what):
    """``_refuse_kept_beside``'s line for a model with chunked layers,
    by what the model is and under no flag; an int8 pool by its own
    line. The prefix cache is simply off (no hit is valid for both
    stores yet)."""
    _, params = weights
    with pytest.raises(ValueError) as err:
        REFUSALS[what](params)
    msg = str(err.value)
    assert "\n" not in msg and "chunked layers" in msg
    assert engine(params).prefix is None


@pytest.mark.parametrize("kw,says", [
    (dict(chunk=32), "prefill_chunk 32 does not divide"),
    (dict(block=8, chunk=8), "block_size == the chunk only, got 8"),
    (dict(block=32, chunk=16), "block_size == the chunk only, got 32"),
])
def test_what_the_two_stores_hold_the_engine_to_is_refused_by_name(
        weights, kw, says):
    _, params = weights
    with pytest.raises(ValueError, match=says) as err:
        engine(params, **kw)
    assert "\n" not in str(err.value)


def test_a_layer_of_another_kind_beside_chunked_ones_is_refused(weights):
    _, params = weights

    class Mixed(type(params)):
        @property
        def layers(self):
            return (("chunked", 0), ("attn", 1))

    mixed = Mixed(**{f.name: getattr(params, f.name)
                     for f in dataclasses.fields(params)})
    with pytest.raises(ValueError, match="every layer chunked only"):
        DecodeEngine(mixed, HEADS, EngineConfig())


@pytest.mark.parametrize("key,value,says", [
    ("model_type", "llama", "serves 'evabyte' only"),
    ("attention_class", "mha", "'eva' only"),
    ("attention_bias", True, "no projection has a bias"),
    ("rope_scaling", {"type": "linear"}, "unscaled only"),
    ("tie_word_embeddings", True, "untied only"),
    ("hidden_act", "gelu", "SiLU only"),
    ("fp32_logits", False, "float32 only"),
    ("num_chunks", 4, "by its size"),
    ("num_key_value_heads", 2, "one KV head a query head"),
    ("window_size", 72, "no whole number of chunks"),
])
def test_what_the_family_cannot_serve_is_refused_by_name(key, value, says):
    with pytest.raises(ValueError, match=says):
        evabyte_lm.spec_from_config(dict(TOY, **{key: value}))


# -- the published sizes, and the entry point --------------------------------


def test_parameter_count_and_both_stores_at_published_widths():
    """The configuration's file counted from the arrays' shapes (no
    array is made): 1,630,932,992 parameters over 8 layers, a cached
    entry of 16,384 bytes a layer, the ring's pool and the summaries'
    as its ``serving.note`` states them."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "evabyte-6.5b-serve.json")) as f:
        config = json.load(f)
    spec = evabyte_lm.spec_from_config(config)
    assert (spec.n_layers, spec.n_heads, spec.head_dim) == (8, 32, 128)
    assert (spec.window, spec.chunk, spec.n_pred, spec.vocab) == (
        2048, 16, 8, 320)
    assert spec.unit_offset and spec.theta == 100000.0
    p = jax.eval_shape(lambda k: evabyte_lm.init_evabyte_lm(
        k, spec, dtype=jnp.bfloat16), jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(p)
    assert sum(x.size for x in leaves) == 1_630_932_992
    assert sum(x.size * x.dtype.itemsize for x in leaves) == 3_261_865_984
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128
    assert layer == 202_391_552
    assert 8 * layer + 320 * 4096 + 2560 * 4096 + 4096 == 1_630_932_992
    assert p.phi.shape == p.mu.shape == (8, 32, 128)
    assert p.w_head.shape == (8 * 320, 4096)
    driver = _load("evabyte_engine_driver")
    cfg = driver.engine_config(config)
    assert (cfg.max_slots, cfg.max_blocks_per_seq, cfg.n_blocks) == (
        24, 36, 1 + 24 * 36)
    assert driver.ring_blocks(config) == 130
    row = 32 * 128 * 2 * 2
    ring = 8 * (1 + 24 * 130) * 16 * row
    summaries = 8 * cfg.n_blocks * 16 * row
    assert (ring, summaries) == (6_545_211_392, 1_814_036_480)
    assert 3_261_865_984 + ring + summaries == 11_621_113_856
    assert str(11_621_113_856) in config["serving"]["note"].replace(",", "")
    # every published key but the depth is the catalog's
    assert config["reduced"] == ["num_hidden_layers", "serving"]
    assert config["published"]["num_hidden_layers"] == 32


def test_cli_and_library_build_the_same_engine(tmp_path, capsys, ref,
                                               driver):
    """``generate --model_config`` picks the family by ``model_type``
    and serves the model the one library function builds: the bytes of
    ``engine_from_config`` on the same seed, which are the reference's,
    past a window boundary; what moves a sequence by one table refuses
    at the entry."""
    from distributed_llm_code_samples_tpu.decode.generate_cli import (
        generate_main)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(TOY))
    assert generate_main(["--model_config", str(path), "-r", "11",
                          "--prompt_lens", "5,40", "--prompt_seed", "3",
                          "--max_new", "80", "--max_slots", "2"]) == 0
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    rng = np.random.default_rng(3)
    ps = [rng.integers(0, VOCAB, n).tolist() for n in (5, 40)]
    eng = engine_from_config(TOY, seed=11, engine_config=EngineConfig(
        max_slots=2, n_blocks=1 + 2 * 1, max_blocks_per_seq=1))
    assert isinstance(eng.params, evabyte_lm.EvaByteLMParams)
    assert eng.chunked and eng.windowed and eng.prefix is None
    got = eng.generate(ps, 80)
    assert [s["tokens"] for s in payload["sequences"]] == got
    w = driver.make_weights(TOY, 11)
    for full, pr in zip(got, ps):
        assert_served_is_the_references(ref, w, full, len(pr))
    base = ["--model_config", str(path), "--prompt_lens", "5",
            "--max_new", "2"]
    for more in (["--fleet", "2"], ["--snapshot_dir", str(tmp_path / "s")],
                 ["--tp", "2"], ["--speculate", "2"],
                 ["--kv_dtype", "int8"]):
        assert generate_main(base + more) == 2
        err = capsys.readouterr().err.strip().splitlines()[-1]
        assert err.startswith("error:") and "chunked layers" in err


def test_weights_come_in_the_type_the_config_states():
    from distributed_llm_code_samples_tpu.decode.model_config import (
        params_from_config)
    p = params_from_config(dict(TOY, precision={"weights": "bfloat16"}), 3)
    assert {x.dtype for x in jax.tree_util.tree_leaves(p)} == {
        jnp.dtype(jnp.bfloat16)}
    assert float(jnp.abs(p.attn.wq.astype(jnp.float32)).max()) <= 1.0
    # a stored gain is near 0: the norm multiplies by 1 + g
    assert float(jnp.abs(p.norm_in.astype(jnp.float32)).mean()) < 0.5
    x = jnp.ones((2, 64), jnp.float32)
    assert np.allclose(np.asarray(p.norm(jnp.zeros(64), x)), 1.0, atol=1e-4)
