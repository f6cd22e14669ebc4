"""The window-and-full-attention, gated-head, sparse-expert LM on the
serving path, at toy size: ``models/laguna_lm.py`` through
``DecodeEngine`` against the plain reference
``benchmark/configs/laguna_lm_reference.py`` (float32 at ``highest``,
the whole forward pass over one sequence with the two masks, nothing
from the package). The one family whose sequences keep blocks in TWO
pools: the full-attention layers' over the whole sequence, the
sliding-window layers' in a ring of the last ``window`` positions
(``models/face.py::WINDOW``, ``decode/paged.py``).

The toy has the published model's shape in small: d 64, 8 layers, the
period ``full_attention, sliding_attention x 3`` twice (the first layer
with the dense MLP of 160, the others sparse), 4 gated query heads on a
full layer and 6 on a sliding one over 2 KV heads of 16 lanes, a window
of 16 positions, YaRN on the first half of a full layer's head (factor
16 over 32 original positions) and the plain rotary on all of a sliding
layer's, a softmax router over 16 experts of 48 with the top 4 beside a
shared one of which this "chip" holds experts 4 to 11, V 96, float32.
``initializer_range`` 0.2: at d=64 the published 0.02 leaves the blocks'
outputs too small for a dropped one to show.

Tolerance, everywhere below: ``TOL = 2e-4`` on logits whose spread
(standard deviation) is over 1. Both sides are float32 and differ in
the order of their sums (a chunk of c rows or a batch of b against all T
at once, the stored rows' two products against per-head attention);
2e-5 was read. A window layer read as a full one, a dropped gate, a
full layer's head rotated on all its lanes or without YaRN each read
hundreds of times the tolerance (``test_a_fault_*``).
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_code_samples_tpu.decode import (DecodeEngine,
                                                     EngineConfig)
from distributed_llm_code_samples_tpu.decode.engine import (ServePolicy,
                                                            WINDOW_COUNTERS)
from distributed_llm_code_samples_tpu.decode.model_config import (
    engine_from_config)
from distributed_llm_code_samples_tpu.decode.paged import ring_positions
from distributed_llm_code_samples_tpu.models import laguna_lm
from distributed_llm_code_samples_tpu.models.attention import (Rotary, rope,
                                                               window_mask)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
WINDOW = 16

YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 16,
        "original_max_position_embeddings": 32, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.2772588722239782,
        "partial_rotary_factor": 0.5}
TOY = dict(model_type="laguna", hidden_size=64, intermediate_size=160,
           moe_intermediate_size=48, shared_expert_intermediate_size=48,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           num_experts=8, router_experts=16, expert_first=4,
           num_experts_per_tok=4, num_hidden_layers=8,
           layer_types=(["full_attention"] + ["sliding_attention"] * 3) * 2,
           mlp_layer_types=["dense"] + ["sparse"] * 7,
           num_attention_heads_per_layer=[4, 6, 6, 6] * 2,
           sliding_window=WINDOW, gating="per-head",
           rope_parameters={
               "full_attention": YARN,
               "sliding_attention": {"rope_type": "default",
                                     "rope_theta": 10000,
                                     "partial_rotary_factor": 1}},
           rms_norm_eps=1e-6, norm_topk_prob=True,
           moe_routed_scaling_factor=2.5, moe_router_logit_softcapping=0,
           tie_word_embeddings=False, attention_bias=False,
           vocab_size=96, max_position_embeddings=256,
           initializer_range=0.2)
HEADS, HELD, TOP_K, SPARSE = 4, 8, 4, 7


def _load(name):
    path = os.path.join(ROOT, "benchmark", "configs", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("laguna_lm_reference")


@pytest.fixture(scope="module")
def driver():
    return _load("laguna_engine_driver")


@pytest.fixture(scope="module")
def weights(driver):
    """The benchmark driver's named leaves and the params the engine
    takes, of one seed: the reference and the program get one set of
    arrays."""
    w = driver.make_weights(TOY, 11)
    return w, driver._params(TOY, w)


def engine(params, slots=3, mbps=8, chunk=16, block=16, **kw):
    cfg = EngineConfig(max_slots=slots, n_blocks=1 + slots * mbps,
                       max_blocks_per_seq=mbps, prefill_chunk=chunk,
                       block_size=block)
    policy = kw.pop("policy", None)
    return DecodeEngine(params, HEADS, dataclasses.replace(cfg, **kw),
                        policy=policy)


def prompts_of(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TOY["vocab_size"], n).tolist() for n in lens]


# -- (a) prefill + decode through both pools is the full forward ------------


def cached_logits(eng, tokens, chunks, decode_from=None, mixed_with=None):
    """Logits ``[T, V]`` of one sequence through the engine's own
    program bodies and BOTH pools, in slot 1: the first ``decode_from``
    tokens prefilled in ``chunks``-sized pieces, the rest decoded one at
    a time. ``mixed_with``: another sequence's tokens, decoded one a
    step in slot 0 while every FULL chunk of the first rides in the same
    ``mixed`` program; its logits come back second. Also returns the
    experts' counters of every dispatch in order."""
    p, cfg, pr = eng.params, eng.cfg, eng.programs
    blk, entries = cfg.block_size, pr.window_blocks
    t = len(tokens)
    decode_from = t if decode_from is None else decode_from

    def tables(first):
        """A sequence's whole table and its ring, from block ``first``."""
        tab = np.zeros(cfg.max_blocks_per_seq, np.int32)
        tab[:] = first + np.arange(cfg.max_blocks_per_seq)
        return tab, (first + np.arange(entries)).astype(np.int32)

    table, ring = tables(1)
    cache = eng._cache()
    rows, other, counts, pos = [], [], [], 0
    prefill = jax.jit(
        lambda p, cache, table, ring, pos0, toks, c: pr.prefill_hidden(
            c, p, cache, table, pos0, toks, jnp.int32(1), ring),
        static_argnums=6)
    decode = jax.jit(
        lambda p, cache, tabs, rings, lengths, toks: pr.decode_hidden(
            tabs.shape[0], p, cache, tabs, lengths, toks, None, rings))
    if mixed_with is not None:
        mtable, mring = tables(1 + cfg.max_blocks_per_seq)
        mixed = jax.jit(lambda p, cache, f: pr.mixed_hidden(1, p, cache, f))
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
            cache, x, cnt = mixed(p, cache, f)
            other.append(pr.logits(p, x[:1]))
            x = x[1:]
            mpos += 1
        else:
            cache, x, cnt = prefill(p, cache, jnp.asarray(table),
                                    jnp.asarray(ring), jnp.int32(pos), toks,
                                    c)
        rows.append(pr.logits(p, x))
        counts.append(np.asarray(cnt))
        pos += c
    while pos < t:
        cache, x, cnt = decode(p, cache, jnp.asarray(table[None]),
                               jnp.asarray(ring[None]),
                               jnp.asarray([pos], jnp.int32),
                               jnp.asarray(tokens[pos:pos + 1], jnp.int32))
        rows.append(pr.logits(p, x))
        counts.append(np.asarray(cnt))
        pos += 1
    got = np.asarray(jnp.concatenate(rows, 0))
    if mixed_with is None:
        return got, counts
    return got, np.asarray(jnp.concatenate(other, 0)), counts


@pytest.mark.parametrize("block,chunk,chunks,decode_from", [
    (16, 16, 16, 40), (16, 16, 8, 21), (4, 8, 8, 40), (4, 4, 4, 30),
    (8, 16, 16, 48)])
def test_prefill_then_decode_through_both_pools_is_the_reference(
        ref, weights, block, chunk, chunks, decode_from):
    """72 tokens, four and a half windows: prefilled in chunks whose
    rows straddle the window's edge (each row of a chunk has its own
    window start; a chunk of 16 over blocks of 8 writes two blocks
    before it reads), then decoded one at a time while the ring of
    ``window / block + chunk's blocks + 1`` entries turns over, every
    position's logits are the reference's full forward with both masks."""
    w, params = weights
    tokens = prompts_of([72], seed=1)[0]
    eng = engine(params, mbps=72 // block + 1, chunk=chunk, block=block)
    assert eng.programs.window_blocks == (
        WINDOW // block + max(1, chunk // block) + 1)
    got, counts = cached_logits(eng, tokens, chunks, decode_from)
    want = np.asarray(ref.logits(w, np.asarray(tokens), TOY))
    assert want.std() > 1.0
    assert np.abs(got - want).max() < TOL
    assert all(c.shape == (SPARSE, HELD) for c in counts)


def test_a_chunk_riding_with_a_decode_row_is_the_reference(ref, weights):
    """The ``mixed`` program's two seams on both pools: every full
    chunk of one sequence rides with another sequence's decode row, and
    both sequences' logits are the reference's."""
    w, params = weights
    a, b = prompts_of([64, 8], seed=2)
    eng = engine(params, mbps=5)
    got, other, _ = cached_logits(eng, a, 16, 64, mixed_with=b)
    assert np.abs(got - np.asarray(ref.logits(w, np.asarray(a), TOY))
                  ).max() < TOL
    want = np.asarray(ref.logits(w, np.asarray(b), TOY))[:len(other)]
    assert len(other) == 4 and np.abs(other - want).max() < TOL


FAULTS = {
    "window_layers_read_as_full": lambda p: dataclasses.replace(
        p, sliding_window=10 ** 6),
    "full_layer_rotated_on_all_lanes": lambda p: dataclasses.replace(
        p, rot_full=p.rot_full._replace(partial=1.0)),
    "full_layer_without_yarn": lambda p: dataclasses.replace(
        p, rot_full=Rotary(theta=p.rot_full.theta, partial=0.5)),
    "gate_dropped": None,
    "sigmoid_router": None,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_one_mechanism_fails_the_tolerance(monkeypatch, ref,
                                                      weights, fault):
    """Each of the block's mechanisms rules the logits: a program that
    computes the window layers as full ones, drops the per-head gate,
    rotates all lanes of a full layer's head, uses the default rotary
    there, or scores the router with the other family's sigmoid lies
    50 times the tolerance from the reference or further."""
    w, params = weights
    if fault == "gate_dropped":
        monkeypatch.setattr(laguna_lm, "head_gate",
                            lambda wg, i, a, y, head_dim: y)
    elif fault == "sigmoid_router":
        monkeypatch.setattr(laguna_lm, "SCORE", "sigmoid")
    else:
        params = FAULTS[fault](params)
    tokens = prompts_of([48], seed=3)[0]
    got, _ = cached_logits(engine(params, mbps=3), tokens, 16, 32)
    want = np.asarray(ref.logits(w, np.asarray(tokens), TOY))
    assert np.abs(got - want).max() > 50 * TOL


def test_lower_precision_in_the_float32_toy_fails_the_tolerance(ref,
                                                                weights):
    """The controls are other computations, not other names: the
    all-bfloat16 and the int8 forward of the same float32 weights each
    lie further from the reference than 50 times ``TOL``."""
    w, _ = weights
    tokens = np.asarray(prompts_of([24], seed=2)[0])
    full = np.asarray(ref.logits(w, tokens, TOY))
    assert np.array_equal(full, np.asarray(ref.logits(w, tokens, TOY,
                                                      "f32")))
    for mode in ("bf16", "int8"):
        low = np.asarray(ref.logits(w, tokens, TOY, mode))
        assert np.abs(low - full).max() > 50 * TOL, mode
        assert np.abs(low - full).mean() < 0.2 * full.std(), mode


# -- (b) the rotary, against the formula written out ------------------------


def _rotate_pairs(x, ang, scale):
    """Lane ``i`` of the rotated lanes paired with ``i + half``."""
    half = ang.shape[-1]
    out = np.array(x, np.float64)
    a, b = x[..., :half], x[..., half:2 * half]
    out[..., :half] = scale * (a * np.cos(ang) - b * np.sin(ang))
    out[..., half:2 * half] = scale * (a * np.sin(ang) + b * np.cos(ang))
    return out


def test_yarn_and_partial_rotary_against_the_formula():
    """Laguna-S-2.1's full-attention rotary at its published numbers: of
    a head's 128 lanes the first 64 are rotated (32 pairs), pair ``i``
    at ``f_i = 500000^(-i/32)``, kept where it makes more than 32 turns
    over 8,192 positions, divided by 128 where it makes fewer than one,
    ramped between (pairs 9 to 18 here); ``cos`` and ``sin`` times
    1.4852; the other 64 lanes pass. And the sliding layers': all 128
    lanes at theta 10,000, unscaled, which is ``rope`` itself."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna-s-2.1-serve.json")) as f:
        ropes = json.load(f)["rope_parameters"]
    rot = Rotary.from_config(ropes["full_attention"])
    assert rot.rot_dim(128) == 64 and rot.factor == 128.0
    i = np.arange(32)
    f = 500000.0 ** (-i / 32.0)
    turns = lambda n: 64 * np.log(8192 / (n * 2 * np.pi)) / (
        2 * np.log(500000.0))
    lo, hi = np.floor(turns(32)), np.ceil(turns(1))
    assert (lo, hi) == (9, 18)
    keep = 1 - np.clip((i - lo) / (hi - lo), 0, 1)
    want_f = f / 128 * (1 - keep) + f * keep
    assert np.allclose(rot.freqs(128), want_f, rtol=1e-6)
    assert want_f[0] == 1.0 and np.isclose(want_f[-1], f[-1] / 128)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((3, 5, 128)).astype(np.float32)   # [H, T, dh]
    pos = np.asarray([0, 1, 7, 600, 3000])
    got = np.asarray(rot(jnp.asarray(x), jnp.asarray(pos)))
    want = _rotate_pairs(x, pos[:, None] * want_f, 1.4852030263919618)
    assert np.allclose(got, want, atol=2e-4)
    assert np.array_equal(got[..., 64:], x[..., 64:])
    win = Rotary.from_config(ropes["sliding_attention"])
    assert win.rot_dim(128) == 128 and win.attention_factor == 1.0
    got = np.asarray(win(jnp.asarray(x), jnp.asarray(pos)))
    assert np.array_equal(got, np.asarray(rope(jnp.asarray(x),
                                               jnp.asarray(pos))))
    want = _rotate_pairs(x, pos[:, None] * 10000.0 ** (-np.arange(64) / 64),
                         1.0)
    assert np.allclose(got, want, atol=3e-3)    # float32 angles at 3000
    with pytest.raises(ValueError, match="rope_type 'linear'"):
        Rotary.from_config({"rope_type": "linear", "factor": 2})
    # the attention factor a config leaves out is YaRN's own
    assert np.isclose(Rotary.from_config(
        {k: v for k, v in ropes["full_attention"].items()
         if k != "attention_factor"}).attention_factor, 1.4852030263919618)


def test_ring_positions_and_the_window_rule():
    """Entry ``e`` of a ring of 5 blocks of 4 holds the newest block
    congruent to ``e``: after the write at position 30 (block 7) the
    entries hold blocks 5, 6, 7, 3, 4; a never-written entry reads
    negative; and a window of 8 at position 30 is positions 23 to 30."""
    pos = np.asarray(ring_positions(jnp.asarray([30, 6]), 5, 4))
    assert pos.shape == (2, 20)
    assert pos[0].reshape(5, 4)[:, 0].tolist() == [20, 24, 28, 12, 16]
    assert pos[1].reshape(5, 4)[:, 0].tolist() == [0, 4, -12, -8, -4]
    seen = np.asarray(window_mask(jnp.asarray([[30], [6]]), pos, 8))
    assert sorted(pos[0][seen[0]].tolist()) == list(range(23, 31))
    assert sorted(pos[1][seen[1]].tolist()) == list(range(0, 7))


# -- (c) the chip's share of the experts ------------------------------------


def test_the_holders_parts_add_up_to_the_uncut_layer(ref, driver):
    """Expert parallelism's contract (``ops/moe_serve.py``): four
    holders of 4 of the 16 experts each compute their range's part of a
    sparse layer; the parts, with the shared expert (which every chip
    computes alike) counted once, add up to the uncut reference's
    layer; and a holder's counters are the rows ITS experts got."""
    uncut = dict(TOY, num_experts=16, expert_first=0)
    w = driver.make_weights(uncut, 7)
    p = driver._params(uncut, w)
    a = jax.random.normal(jax.random.PRNGKey(3), (9, 64), jnp.float32)
    for l in (1, 5):
        x = l - 1
        want = np.asarray(ref._experts(w, x, a, uncut, jnp.float32, "f32"))
        shared = np.asarray(ref._mlp(a, *(w["shared." + k][x]
                                          for k in ref.MLP), mode="f32"))
        total, rows = shared.copy(), []
        for first in (0, 4, 8, 12):
            part, got = laguna_lm.holder(p, first, 4).ffn_counted(l, a)
            total += np.asarray(part) - shared
            rows.append(np.asarray(got))
        assert np.abs(total - want).max() < TOL / 4
        assert np.concatenate(rows).sum() == 9 * TOP_K
    # the dense layer routes nothing
    assert p.ffn_counted(0, a)[1] is None


def test_parameter_count_at_published_widths():
    """The configuration file's arithmetic is the program's, from the
    arrays' shapes (nothing is allocated): the first pipeline stage of
    Laguna-S-2.1 as one of its eight chips holds it."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna-s-2.1-serve.json")) as f:
        config = json.load(f)
    spec = laguna_lm.spec_from_config(config)
    p = jax.eval_shape(lambda k: laguna_lm.init_laguna_lm(
        k, spec, dtype=jnp.bfloat16), jax.random.PRNGKey(0))
    size = lambda st: sum(x.size for x in st if x is not None)
    assert p.num_params() == 4_325_526_528
    assert sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(p)) == 8_668_354_560
    assert size(p.full) + p.wg_full.size == 3 * 44_187_648
    assert size(p.window) + p.wg_window.size == 9 * 63_135_744
    assert size(p.dense) == 113_246_208 and size(p.shared) == 103_809_024
    assert size(p.experts) == 3_321_888_768 + 8_650_752
    assert p.experts.w_router.shape == (11, 256, 3072)
    assert p.experts.w_gate.shape == (11, 32, 1024, 3072)
    assert p.wte.shape == p.w_head.shape == (12_544, 3072)
    assert [k for k in p.kinds] == ["attn", "window", "window",
                                    "window"] * 3
    cs = p.cache_spec(48)
    assert (cs.kv_layers, cs.win_layers, cs.window) == (3, 9, 512)
    assert (cs.kv_heads, cs.head_dim, cs.rec_layers) == (8, 128, 0)
    assert (cs.expert_layers, cs.n_experts) == (11, 32)
    note = config["serving"]["note"]
    assert "4,325,526,528" in note and "8,668,354,560" in note
    assert "2,416,115,712" in note and "1,284,046,848" in note
    assert config["published"]["num_experts"] == 256
    assert config["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "num_experts", "vocab_size",
        "serving"]
    # the cell's pools, as its driver builds them: 64 rings of 34
    from distributed_llm_code_samples_tpu.decode.programs import StepPrograms
    cfg = _load("laguna_engine_driver").engine_config(config)
    programs = StepPrograms(cfg, cs, p.vocab)
    assert programs.window_blocks == 34
    pool = jax.eval_shape(lambda: programs.init_cache()[0])
    wpool = jax.eval_shape(programs.init_window)
    assert pool.k.shape == (3, 12_289, 16, 1024)
    assert wpool.k.shape == (9, 2_177, 16, 1024)
    nbytes = lambda x: x.size * x.dtype.itemsize
    assert nbytes(pool.k) + nbytes(pool.v) == 2_416_115_712
    assert nbytes(wpool.k) + nbytes(wpool.v) == 1_284_046_848


# -- (d) the engine: two pools, two tables, two free lists ------------------


def assert_served_is_the_references(ref, w, full, plen):
    """Every served token is the reference's first at its position."""
    lg = np.asarray(ref.logits(w, np.asarray(full), TOY))
    rows = lg[plen - 1:len(full) - 1]
    served = np.asarray(full[plen:])
    gap = rows.max(-1) - rows[np.arange(len(served)), served]
    assert gap.max() < TOL


def test_a_sequence_four_windows_long_holds_a_ring_and_no_more(ref, weights):
    """Served through ``DecodeEngine`` to four windows and more: the
    sequences never hold more window blocks than a ring each, every
    served token is the reference's, and both free lists come back
    whole."""
    w, params = weights
    eng = engine(params, slots=3, mbps=6)
    ring = eng.programs.window_blocks
    assert ring == 3 and eng.wpool.n_blocks == 1 + 3 * ring
    prompts = prompts_of([5, 19, 33, 8], seed=4)
    uids = [eng.submit(p, 60) for p in prompts]
    peak = released = 0
    while eng.active or eng.waiting:
        eng.step()
        held = [len(s.wblocks) for s in eng.slots if s is not None]
        assert all(n <= ring for n in held)
        assert len(eng.free_wblocks) + sum(held) == 3 * ring
        peak = max(peak, sum(held))
        digest = eng.flight[-1]
        assert digest["window_blocks_live"] <= 3 * ring
        assert 0 < digest["window_rows"] <= digest["full_rows"] or not (
            digest["dispatches"])
        released += digest["window_blocks_released"]
    assert peak == 3 * ring
    for uid, p in zip(uids, prompts):
        full = eng.finished[uid]
        assert len(full) == len(p) + 60
        assert_served_is_the_references(ref, w, full, len(p))
    assert sorted(eng.free_wblocks) == list(range(1, 1 + 3 * ring))
    assert len(eng.free_blocks) == eng.cfg.n_blocks - 1
    # every block a sequence wrote beyond its ring overwrote one, and
    # every ring went back whole
    blocks = sum(-(-(len(p) + 59) // 16) for p in prompts)
    assert released == blocks
    assert eng.window_pool_utilization() == 0.0
    assert eng.kv_pool_utilization() == 0.0


def test_the_counters_of_a_step_are_its_rows_reads(weights):
    """``window_rows`` / ``full_rows`` of the step that launched a
    decode batch: each row's positions up to its own, at most the
    window in a window layer; a chunk's one view is counted once."""
    _, params = weights
    eng = engine(params, slots=2, mbps=4)
    eng.submit(prompts_of([40], seed=5)[0], 4)
    eng.step()                                  # chunk 0-15
    d = eng.flight[-1]
    assert (d["full_rows"], d["window_rows"]) == (16, 16)
    eng.step()                                  # chunk 16-31
    d = eng.flight[-1]
    assert (d["full_rows"], d["window_rows"]) == (32, 16)
    eng.run()
    reads = [(d["full_rows"], d["window_rows"]) for d in eng.flight
             if d["decode_uids"]]
    # the prompt's tail (32-39) and the first decode row in one step
    assert reads == [(40 + 41, 16 + 16), (42, 16), (43, 16)]
    assert set(WINDOW_COUNTERS) <= set(eng.flight[-1])
    rec = eng.telemetry_record()
    assert rec["window_pool_utilization"] == 0.0


def test_a_reused_block_is_never_read_before_its_write(weights):
    """The device runs programs in launch order. Replaying the launches
    in that order — each row's write, then its read — every position a
    row attends over in a window layer lies in the physical block its
    table names AND was the last thing written there: a block of a ring
    (or of a finished sequence, handed to the next) is overwritten only
    behind the window of every row launched from then on, with a result
    still unread between the two."""
    _, params = weights
    eng = engine(params, slots=2, mbps=6, policy=ServePolicy())
    ring, blk = eng.programs.window_blocks, eng.cfg.block_size
    holds: dict = {}                    # physical block -> (uid, block j)
    launches = []

    def spy(phase, bucket, fn, p, operand, land, _launch=eng._launch):
        f = eng.programs.wire(phase, bucket).unpack(operand)
        rows = []
        if "wtable" in f:               # the chunk's rows write first
            pos0, c = int(f["pos0"]), len(f.get("chunk", f["tokens"]))
            rows.append((int(np.ravel(f["uid"])[0]), f["wtable"],
                         pos0, pos0 + c - 1))
        for j in range(len(f.get("lengths", ()))):
            if f["uids"][j] or f["lengths"][j]:
                rows.append((int(f["uids"][j]), f["wtables"][j],
                             int(f["lengths"][j]), int(f["lengths"][j])))
        launches.append((eng._inflight is not None, rows))
        for uid, table, first, last in rows:
            for pos in range(first, last + 1):
                holds[int(table[(pos // blk) % ring])] = (uid, pos // blk)
            for pos in range(max(0, last - WINDOW + 1), last + 1):
                assert holds[int(table[(pos // blk) % ring])] == (
                    uid, pos // blk), (uid, pos)
        return _launch(phase, bucket, fn, p, operand, land)

    eng._launch = spy
    for p in prompts_of([20, 7, 35, 12, 9], seed=6):
        eng.submit(p, 45)
    eng.run()
    assert len(eng.finished) == 5 and not eng.failed
    assert sum(len(rows) for _, rows in launches) > 200
    # most launches went out with the one before still unread
    assert sum(unread for unread, _ in launches) > len(launches) // 2
    # and blocks did change hands: 5 sequences through 2 rings
    assert len({uid for uid, _ in holds.values()}) <= 2


@pytest.mark.parametrize("seed", range(4))
def test_random_admit_finish_expire_preempt_leaves_both_lists_whole(
        weights, seed):
    """A random schedule over a small pool with deadlines and
    pool-pressure preemption: at every step no block, of either kind,
    is in two tables or in a table and on its free list, and at the end
    both free lists are whole."""
    _, params = weights
    eng = engine(params, slots=3, mbps=5, n_blocks=1 + 9,
                 policy=ServePolicy(deadline_steps=70,
                                    preempt_after_steps=3, max_retries=1))
    rng = np.random.default_rng(seed)
    ring = eng.programs.window_blocks
    todo = 12

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
        if todo and rng.random() < 0.3:
            eng.submit(rng.integers(0, 96, int(rng.integers(3, 30))).tolist(),
                       int(rng.integers(2, 50)))
            todo -= 1
        eng.step()
        check()
    eng.collect()
    check()
    assert sorted(eng.free_wblocks) == list(range(1, 1 + 3 * ring))
    assert sorted(eng.free_blocks) == list(range(1, 10))
    assert len(eng.finished) + len(eng.failed) == 12


def test_a_quarantined_sequence_leaves_no_poison_in_the_window_pool(
        ref, weights):
    """A poisoned request's rows are NaN in BOTH pools; its window
    blocks go back scrubbed, so the next sequence through the same ring
    is the reference's."""
    w, params = weights
    eng = engine(params, slots=1, mbps=4)
    bad = eng.submit(prompts_of([9], seed=7)[0], 8)
    eng.step()
    eng.arm_poison(bad)
    eng.run()
    assert bad in eng.failed and eng.quarantined == 1
    assert sorted(eng.free_wblocks) == [1, 2, 3]
    assert np.isfinite(np.asarray(eng.wpool.k, np.float32)).all()
    p = prompts_of([11], seed=8)[0]
    uid = eng.submit(p, 30)
    eng.run()
    assert_served_is_the_references(ref, w, eng.finished[uid], len(p))


# -- (e) what moves a sequence by ONE table refuses, in one line ------------


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
    """``_refuse_kept_beside``'s line for a model with window layers,
    by what the model is and under no flag; an int8 pool by its own
    line. The prefix cache is simply off (no hit is valid for both
    kinds of layer yet)."""
    _, params = weights
    with pytest.raises(ValueError) as err:
        REFUSALS[what](params)
    msg = str(err.value)
    assert "\n" not in msg and "window layers" in msg
    assert engine(params).prefix is None


@pytest.mark.parametrize("key,value,says", [
    ("model_type", "llama", "serves 'laguna' only"),
    ("gating", "per-layer", "per-head gate only"),
    ("gating_types", ["per_head", "none"], "'per_head' only"),
    ("moe_router_logit_softcapping", 30.0, "uncapped"),
    ("moe_apply_router_weight_on_input", True, "outputs only"),
    ("norm_topk_prob", False, "normalised only"),
    ("tie_word_embeddings", True, "untied only"),
    ("attention_bias", True, "no projection has a bias"),
    ("layer_types", ["full_attention"] * 8, "of each type"),
    ("layer_types", ["full_attention", "linear_attention"] * 4,
     "linear_attention"),
    ("mlp_layer_types", ["sparse"] * 8, "one dense and one sparse"),
    ("num_attention_heads_per_layer", [4, 6, 6, 8] * 2, "one head count"),
    ("num_hidden_layers", 7, "names 8 layers"),
])
def test_what_the_family_cannot_serve_is_refused_by_name(key, value, says):
    with pytest.raises(ValueError, match=says):
        laguna_lm.spec_from_config(dict(TOY, **{key: value}))


# -- the entry point ----------------------------------------------------------


def test_cli_and_library_build_the_same_engine(tmp_path, capsys, ref,
                                               driver):
    """``generate --model_config`` picks the family by ``model_type``
    and serves the model the one library function builds: the tokens of
    ``engine_from_config`` on the same seed, which are the
    reference's, past the window; what moves a sequence by one table
    refuses at the entry."""
    from distributed_llm_code_samples_tpu.decode.generate_cli import (
        generate_main)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(TOY))
    assert generate_main(["--model_config", str(path), "-r", "11",
                          "--prompt_lens", "5,19", "--prompt_seed", "3",
                          "--max_new", "30", "--max_slots", "2"]) == 0
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    rng = np.random.default_rng(3)
    ps = [rng.integers(0, TOY["vocab_size"], n).tolist() for n in (5, 19)]
    eng = engine_from_config(TOY, seed=11, engine_config=EngineConfig(
        max_slots=2, n_blocks=1 + 2 * 4, max_blocks_per_seq=4))
    got = eng.generate(ps, 30)
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
        assert err.startswith("error:") and "window layers" in err


def test_weights_come_in_the_type_the_config_states():
    """bfloat16 as served: every leaf but the router, which is float32
    whatever the type and has no choice bias; both pools take the
    cache's type."""
    bf16 = dict(TOY, precision={"weights": "bfloat16"})
    eng = engine_from_config(bf16, seed=1, engine_config=EngineConfig(
        max_slots=2, n_blocks=9, max_blocks_per_seq=4, kv_dtype="bf16"))
    p = eng.params
    assert p.experts.w_router.dtype == jnp.float32 and p.experts.bias is None
    others = [x for x in jax.tree_util.tree_leaves(p)
              if x is not p.experts.w_router]
    assert others and all(x.dtype == jnp.bfloat16 for x in others)
    assert eng.pool.k.dtype == eng.wpool.k.dtype == jnp.bfloat16
    assert eng.pool.k.shape[0] == 2 and eng.wpool.k.shape[0] == 6
