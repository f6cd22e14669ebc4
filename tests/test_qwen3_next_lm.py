"""The gated delta-rule and gated full-attention, fine-grained
sparse-expert LM on the serving path, at toy size:
``models/qwen3_next_lm.py`` through ``DecodeEngine`` against the plain
reference ``benchmark/configs/qwen3_next_lm_reference.py`` (float32 at
``highest``, the whole forward pass over one sequence, the recurrence a
per-token scan from a zero state, nothing from the package). The first
family whose state row has TWO widths (``models/face.py::StateRow``):
the convolution runs over ``q``, ``k`` and ``v`` side by side (128
lanes here), the state is a matrix a value head, ``[16, 4 x 16]``.

The toy has the published model's shape in small: d 64, 4 layers
``delta x 3, full`` (one period), 2 key and 4 value heads of 16 lanes
behind a convolution of 4 taps, 4 query heads of 32 lanes over 2 KV
heads with QK-norm (unit offset) and rotary on the first 8 lanes at
theta 1e7, a lane-wise output gate, a softmax router over 16 experts of
32 with the top 4 of which this "chip" holds experts 4 to 11, beside a
gated shared expert of 32, V 96, float32. ``initializer_range`` 0.2: at
d=64 the published 0.02 leaves the blocks' outputs too small for a
dropped one to show.

Tolerance, everywhere below: ``TOL = 2e-4`` on logits whose spread
(standard deviation) is over 1. Both sides are float32 and differ in
the order of their sums (a chunk of c rows or a batch of b against all T
at once, the state's contractions as products and sums over the key
lanes against ``einsum`` at ``highest``, the walk's online softmax
against a softmax over the row); 4e-5 was read. A program with a fault
in one mechanism (``FAULTS``) reads 50 times the tolerance or more.
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
                                                     EngineConfig, programs)
from distributed_llm_code_samples_tpu.decode.engine import STATE_ROW_BYTES
from distributed_llm_code_samples_tpu.decode.paged import init_state
from distributed_llm_code_samples_tpu.models import qwen3_next_lm as qwen
from distributed_llm_code_samples_tpu.models.face import StateRow, mm
from distributed_llm_code_samples_tpu.ops import delta_rule, moe_serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4

TOY = dict(model_type="qwen3_next", hidden_size=64, intermediate_size=160,
           moe_intermediate_size=32, shared_expert_intermediate_size=32,
           num_attention_heads=4, num_key_value_heads=2, head_dim=32,
           linear_num_key_heads=2, linear_num_value_heads=4,
           linear_key_head_dim=16, linear_value_head_dim=16,
           linear_conv_kernel_dim=4, full_attention_interval=4,
           num_hidden_layers=4, num_experts=8, router_experts=16,
           expert_first=4, num_experts_per_tok=4, norm_topk_prob=True,
           decoder_sparse_step=1, mlp_only_layers=[],
           partial_rotary_factor=0.25, rope_theta=10000000,
           rope_scaling=None, rms_norm_eps=1e-6, tie_word_embeddings=False,
           use_sliding_window=False, hidden_act="silu", vocab_size=96,
           max_position_embeddings=256, initializer_range=0.2)
HEADS, TOP_K, DELTA_LAYERS = 4, 4, 3
ROW = StateRow(conv_lanes=128, taps=4, rows=16, lanes=64)


def _load(name):
    path = os.path.join(ROOT, "benchmark", "configs", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("qwen3_next_lm_reference")


@pytest.fixture(scope="module")
def driver():
    return _load("qwen3_next_engine_driver")


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
    return DecodeEngine(params, HEADS, dataclasses.replace(cfg, **kw))


def prompts_of(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TOY["vocab_size"], n).tolist() for n in lens]


# -- (a) prefill + decode through the state store and the pool --------------


def cached_logits(eng, tokens, chunks, decode_from=None, mixed_with=None):
    """Logits ``[T, V]`` of one sequence through the engine's own
    program bodies, the K/V pool and the state rows, in slot 1: the
    first ``decode_from`` tokens prefilled in ``chunks``-sized pieces,
    the rest decoded one at a time. ``mixed_with``: another sequence's
    tokens, decoded one a step in slot 0 while every FULL chunk of the
    first rides in the same ``mixed`` program; its logits come back
    second. Also returns the carried cache."""
    p, cfg, pr = eng.params, eng.cfg, eng.programs
    t = len(tokens)
    decode_from = t if decode_from is None else decode_from

    def table_from(first):
        return (first + np.arange(cfg.max_blocks_per_seq)).astype(np.int32)

    table, mtable = table_from(1), table_from(1 + cfg.max_blocks_per_seq)
    cache = eng._cache()
    rows, other, pos, mpos = [], [], 0, 0
    prefill = jax.jit(
        lambda p, cache, table, pos0, toks, c: pr.prefill_hidden(
            c, p, cache, table, pos0, toks, jnp.int32(1)), static_argnums=5)
    decode = jax.jit(
        lambda p, cache, tabs, lengths, toks, slots: pr.decode_hidden(
            tabs.shape[0], p, cache, tabs, lengths, toks, slots))
    mixed = jax.jit(lambda p, cache, f: pr.mixed_hidden(1, p, cache, f))
    while pos < decode_from:
        c = min(chunks, decode_from - pos)
        c = 1 << (c.bit_length() - 1)              # power-of-two chunks
        toks = jnp.asarray(tokens[pos:pos + c], jnp.int32)
        if mixed_with is not None and c == cfg.prefill_chunk:
            f = {"tables": jnp.asarray(mtable[None]),
                 "lengths": jnp.asarray([mpos], jnp.int32),
                 "tokens": jnp.asarray(mixed_with[mpos:mpos + 1], jnp.int32),
                 "rows": jnp.asarray([0], jnp.int32),
                 "table": jnp.asarray(table), "pos0": jnp.int32(pos),
                 "chunk": toks, "row": jnp.int32(1)}
            cache, x, _ = mixed(p, cache, f)
            other.append(pr.logits(p, x[:1]))
            x, mpos = x[1:], mpos + 1
        else:
            cache, x, _ = prefill(p, cache, jnp.asarray(table),
                                  jnp.int32(pos), toks, c)
        rows.append(pr.logits(p, x))
        pos += c
    while pos < t:
        cache, x, _ = decode(p, cache, jnp.asarray(table[None]),
                             jnp.asarray([pos], jnp.int32),
                             jnp.asarray(tokens[pos:pos + 1], jnp.int32),
                             jnp.asarray([1], jnp.int32))
        rows.append(pr.logits(p, x))
        pos += 1
    got = np.asarray(jnp.concatenate(rows, 0))
    if mixed_with is None:
        return got, cache
    return got, np.asarray(jnp.concatenate(other, 0)), cache


@pytest.mark.parametrize("block,chunks,decode_from", [
    (16, 16, 40), (16, 8, 21), (8, 4, 48)])
def test_prefill_then_decode_through_state_and_pool_is_the_reference(
        ref, weights, block, chunks, decode_from):
    """56 tokens: prefilled in 16-token chunks (the convolution's tail
    and the heads' matrices crossing every chunk's edge through the
    slot's state row), then decoded one at a time through the in-place
    kernels and the walk, every position's logits are the reference's
    full forward — by LOGITS, over a state row whose convolution is 128
    lanes wide and whose state is ``[16, 64]``."""
    w, params = weights
    tokens = prompts_of([56], seed=1)[0]
    eng = engine(params, mbps=56 // block + 1, block=block)
    assert eng.spec.state_row == ROW and eng.recurrent == ["delta"]
    got, cache = cached_logits(eng, tokens, chunks, decode_from)
    want = np.asarray(ref.logits(w, np.asarray(tokens), TOY))
    assert want.std() > 1.0
    assert np.abs(got - want).max() < TOL
    state = cache[1]
    assert state.conv.shape == (DELTA_LAYERS, 4, 1, 3 * 128)
    assert state.ssm.shape == (DELTA_LAYERS, 4, 16, 64)
    # slot 1's row carries the sequence; no other row was written
    assert np.abs(np.asarray(state.ssm[:, 1])).max() > 1e-3
    assert not np.asarray(state.ssm[:, 0]).any()


def test_a_chunk_riding_with_a_decode_row_is_the_reference(ref, weights):
    """The ``mixed`` program's seams on the state store and the pool:
    every full chunk of one sequence rides with another sequence's
    decode row (the batch's row through the in-place kernels, the chunk
    through the scan, the mixer's weight products once over both), and
    both sequences' logits are the reference's."""
    w, params = weights
    a, b = prompts_of([64, 8], seed=2)
    eng = engine(params, mbps=5)
    got, other, _ = cached_logits(eng, a, 16, 64, mixed_with=b)
    assert np.abs(got - np.asarray(ref.logits(w, np.asarray(a), TOY))
                  ).max() < TOL
    want = np.asarray(ref.logits(w, np.asarray(b), TOY))[:len(other)]
    assert len(other) == 4 and np.abs(other - want).max() < TOL


def _with_operand(name, change):
    """``{function name: wrapper}`` for ``ops/delta_rule.py``'s three
    program forms with operand ``name`` (``g`` or ``beta``) changed."""
    at = {"g": 3, "beta": 4}[name]

    def wrap(fn):
        def run(*args, **kw):
            args = list(args)
            args[at] = change(args[at])
            return fn(*args, **kw)
        return run
    return {n: wrap(getattr(delta_rule, n))
            for n in ("delta_chunk", "delta_step_in_place", "delta_mixed")}


def _ungated_shared(self, l, h):
    y, rows = moe_serve.routed(self.experts, l, h, self.top_k, 1.0,
                               self.expert_first, qwen.SCORE)
    return y + qwen.gated_mlp(self.shared, l, h), rows


# fault -> [(object, attribute, replacement)] or params -> params
FAULTS = {
    "no_decay": _with_operand("g", jnp.zeros_like),
    "beta_one": _with_operand("beta", jnp.ones_like),
    "no_l2_norm": [(qwen, "_l2norm", lambda x: x)],
    "state_not_carried_across_a_chunk": [(
        programs.StepPrograms, "_keep_slot_state", staticmethod(
            lambda state, i, row, tail, s: state._replace(
                conv=state.conv.at[i, row].set(tail.reshape(1, -1)))))],
    "value_head_j_on_key_head_j": [(
        delta_rule, "_per_value_head",
        lambda x, h_v: jnp.tile(x, (1, h_v // x.shape[1], 1)))],
    "no_lane_gate": [(qwen.Qwen3NextLMParams, "attn_out",
                      lambda self, i, y, a: mm(y, self.full.wo[i]))],
    "no_shared_expert_gate": [(qwen.Qwen3NextLMParams, "ffn_counted",
                               _ungated_shared)],
    "unit_offset_dropped_from_a_norm": lambda p: dataclasses.replace(
        p, g_k=p.g_k - 1.0),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_one_mechanism_fails_the_tolerance(monkeypatch, ref,
                                                      weights, fault):
    """Each of the block's mechanisms rules the logits: a program whose
    state does not decay, whose ``beta`` is 1, that leaves ``q`` and
    ``k`` unnormalised, does not carry the matrix across a chunk's
    boundary, pairs value head ``j`` with key head ``j % H_k``, leaves
    out the attention's lane gate or the shared expert's gate, or drops
    the 1 from a unit-offset norm lies 50 times the tolerance from the
    reference or further."""
    w, params = weights
    how = FAULTS[fault]
    if callable(how):
        params = how(params)
    else:
        for obj, name, new in (how if isinstance(how, list) else [
                (delta_rule, n, f) for n, f in how.items()]):
            monkeypatch.setattr(obj, name, new)
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


# -- (b) the recurrence's four forms against a per-token loop ----------------

H_K, H_V, D_K, D_V, SLOTS, STORE_LAYERS = 2, 4, 8, 16, 5, 3


def _delta_operands(n, seed):
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 8))

    def normal(*shape):
        return jax.random.normal(next(ks), shape, jnp.float32)

    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    return (unit(normal(n, H_K, D_K)) * D_K ** -0.5, unit(normal(n, H_K, D_K)),
            normal(n, H_V, D_V), -jnp.exp(normal(n, H_V) - 1.0),
            jax.nn.sigmoid(normal(n, H_V)))


def _loop(q, k, v, g, beta, s0):
    """The gated delta rule, token by token and head by head, in
    float64: ``q, k [T, H_k, d_k]``, ``v [T, H_v, d_v]``, ``g, beta [T,
    H_v]``, ``s0 [d_k, H_v * d_v]`` -> ``(o [T, H_v * d_v], s)``."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    s = np.asarray(s0, np.float64).reshape(D_K, H_V, D_V).transpose(1, 0, 2)
    s, out = s.copy(), np.zeros((len(q), H_V, D_V))
    for t in range(len(q)):
        for j in range(H_V):
            h = j // (H_V // H_K)
            s[j] *= np.exp(g[t, j])
            u = beta[t, j] * (v[t, j] - s[j].T @ k[t, h])
            s[j] += np.outer(k[t, h], u)
            out[t, j] = s[j].T @ q[t, h]
    return out.reshape(len(q), -1), s.transpose(1, 0, 2).reshape(D_K, -1)


def _store(seed):
    zero = init_state(STORE_LAYERS, SLOTS, StateRow(64, 4, D_K, H_V * D_V))
    return jax.random.normal(jax.random.PRNGKey(seed), zero.ssm.shape)


def test_delta_chunk_is_the_loop():
    ops = _delta_operands(12, 0)
    s0 = _store(1)[1, 2]
    y, s = delta_rule.delta_chunk(*ops, s0)
    want_y, want_s = _loop(*ops, s0)
    assert np.abs(want_y).max() > 0.1
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)
    # ... and in two pieces, the state handed across
    y1, s1 = delta_rule.delta_chunk(*(x[:5] for x in ops), s0)
    y2, s2 = delta_rule.delta_chunk(*(x[5:] for x in ops), s1)
    np.testing.assert_allclose(jnp.concatenate([y1, y2]), want_y, atol=2e-5)
    np.testing.assert_allclose(s2, want_s, atol=2e-5)


# case -> the batch's rows; row SLOTS is the scratch row
ROWS = {"permuted-rows": [4, 1, 0, 3, 2],
        "bucket-smaller-than-the-slots": [3, 0],
        "padded-rows-on-the-scratch-row": [2, 4, SLOTS, SLOTS, SLOTS, SLOTS,
                                           SLOTS, SLOTS],
        "one-row": [1]}


@pytest.mark.parametrize("case", sorted(ROWS))
def test_delta_step_in_place_is_the_loop_and_the_gathered_step(case):
    """The kernel (interpreted here) advances rows ``rows`` of ONE layer
    of the store where they lie: each live row as the per-token loop
    and as ``delta_step`` on gathered copies, every other row and layer
    with the bits it had; the padded rows of a bucket all name the
    scratch row, which holds one of their writes."""
    rows = ROWS[case]
    layer, store = 1, _store(3)
    ops = _delta_operands(len(rows), 4)
    at = jnp.asarray(rows, jnp.int32)
    y, new = delta_rule.delta_step_in_place(*ops, store, layer=layer,
                                            rows=at)
    oracle_y, oracle_s = delta_rule.delta_step(*ops, store[layer, at])
    live = [i for i, r in enumerate(rows) if r != SLOTS]
    for i in live:
        want_y, want_s = _loop(*(x[i:i + 1] for x in ops),
                               store[layer, rows[i]])
        np.testing.assert_allclose(y[i], want_y[0], atol=2e-5)
        np.testing.assert_allclose(new[layer, rows[i]], want_s, atol=2e-5)
        np.testing.assert_allclose(oracle_y[i], want_y[0], atol=2e-5)
        np.testing.assert_allclose(oracle_s[i], want_s, atol=2e-5)
    untouched = [r for r in range(SLOTS) if r not in rows]
    assert np.array_equal(new[layer, untouched], store[layer, untouched])
    others = [l for l in range(STORE_LAYERS) if l != layer]
    assert np.array_equal(new[jnp.asarray(others)], store[jnp.asarray(others)])
    if SLOTS in rows:
        pads = [i for i, r in enumerate(rows) if r == SLOTS]
        assert any(np.allclose(new[layer, SLOTS], oracle_s[i], atol=2e-5)
                   for i in pads)
    else:
        assert np.array_equal(new[layer, SLOTS], store[layer, SLOTS])


def test_delta_mixed_is_the_kernel_then_the_chunk():
    rows = jnp.asarray([3, 0, SLOTS], jnp.int32)
    layer, store, c = 2, _store(5), 6
    ops = _delta_operands(3 + c, 6)
    s0 = _store(7)[0, 1]
    y, (new, s) = delta_rule.delta_mixed(*ops, (store, s0), layer=layer,
                                         rows=rows)
    yb, want_new = delta_rule.delta_step_in_place(
        *(x[:3] for x in ops), store, layer=layer, rows=rows)
    yc, want_s = _loop(*(x[3:] for x in ops), s0)
    assert np.array_equal(y[:3], yb)
    assert np.array_equal(new[layer, :SLOTS], want_new[layer, :SLOTS])
    np.testing.assert_allclose(y[3:], yc, atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)


# -- (c) the chip's share of the experts --------------------------------------


def test_the_eight_shares_and_the_shared_expert_once_are_the_uncut_layer(
        ref, driver):
    """Expert parallelism's contract (``ops/moe_serve.py``): eight
    holders, each told its own range of the 16 experts (``64 i .. 64 i +
    63`` of 512 at the published size; two each here), compute their
    ranges' routed parts through the PROGRAM's expert layer; the parts,
    plus the gated shared expert counted ONCE (every holder has it
    whole), add up to the uncut reference's layer, and the holders'
    counters are the rows THEIR experts got: ``rows x top_k`` in all."""
    uncut = dict(TOY, num_experts=16, expert_first=0)
    w = driver.make_weights(uncut, 7)
    p = driver._params(uncut, w)
    a = jax.random.normal(jax.random.PRNGKey(3), (9, 64), jnp.float32)
    for l in (0, 3):
        want = np.asarray(ref._ffn(w, l, a, uncut, jnp.float32, "f32"))
        shared = np.asarray(ref._shared(
            a, *(w["shared." + k][l] for k in ref.MLP), w["w_sg"][l],
            mode="f32"))
        total, rows = shared.copy(), []
        for first in range(0, 16, 2):
            part, got = moe_serve.routed(
                qwen.holder(p, first, 2).experts, l, a, TOP_K, 1.0, first,
                qwen.SCORE)
            total += np.asarray(part)
            rows.append(np.asarray(got))
            # a holder's whole layer is its routed part and the shared one
            whole, _ = qwen.holder(p, first, 2).ffn_counted(l, a)
            assert np.abs(np.asarray(whole) - np.asarray(part) - shared
                          ).max() < TOL / 4
        assert np.abs(total - want).max() < TOL / 4
        assert np.abs(want).max() > 0.1 and np.abs(shared).max() > 0.01
        assert np.concatenate(rows).sum() == 9 * TOP_K


def test_parameter_and_byte_counts_at_published_widths():
    """The configuration file's arithmetic is the program's, from the
    arrays' shapes (nothing is allocated): published layers 0 to 11 of
    Qwen3-Next-80B-A3B as one of a stage's eight chips holds them, the
    state's row and the pool."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-80b-a3b-serve.json")) as f:
        config = json.load(f)
    spec = qwen.spec_from_config(config)
    p = jax.eval_shape(lambda k: qwen.init_qwen3_next_lm(
        k, spec, dtype=jnp.bfloat16), jax.random.PRNGKey(0))
    size = lambda st: sum(x.size for x in st if x is not None)
    nbytes = lambda t: sum(x.size * x.dtype.itemsize
                           for x in jax.tree_util.tree_leaves(t))
    assert p.num_params() == 2_929_374_400
    assert nbytes(p) == 5_883_914_624
    assert size(p.delta) // 9 == 33_718_464
    assert (size(p.full) + p.w_gate.size + p.g_q.size
            + p.g_k.size) // 3 == 27_263_488
    e = p.experts
    assert e.w_router.size == 12_582_912 and e.w_router.dtype == jnp.float32
    assert size(e) - e.w_router.size == 2_415_919_104 and e.bias is None
    assert size(p.shared) + p.w_sg.size == 37_773_312
    assert p.norm_in.size + p.norm_ff.size + p.g_f.size == 51_200
    assert p.wte.size + p.w_head.size == 77_791_232
    assert [l for l, k in enumerate(p.kinds) if k == "attn"] == [3, 7, 11]
    cs = p.cache_spec(16)
    assert (cs.kv_layers, cs.kv_heads, cs.head_dim) == (3, 2, 256)
    row = cs.state_row
    assert cs.rec_layers == 9 and row == StateRow(8192, 4, 128, 4096)
    assert (row.state_bytes, row.tail_bytes, row.bytes) == (
        2_097_152, 98_304, 2_195_456)
    assert (cs.expert_layers, cs.n_experts) == (12, 64)
    serving = config["serving"]
    slots, per_seq = serving["max_slots"], serving["max_positions"] // 16
    state = jax.eval_shape(lambda: init_state(cs.rec_layers, slots, row))
    assert nbytes(state) == 2_548_924_416 == 129 * 9 * row.bytes
    kv = 3 * (1 + slots * per_seq) * 16 * 2 * (256 + 256) * 2
    assert kv == 2_416_017_408
    assert nbytes(p) + nbytes(state) + kv == 10_848_856_448
    for n in ("2,929,374,400", "5,883,914,624", "33,718,464", "27,263,488",
              "2,195,456", "2,548,924,416", "2,416,017,408",
              "10,848,856,448"):
        assert n in serving["note"], n
    assert config["published"]["num_hidden_layers"] == 48
    assert config["published"]["num_experts"] == 512


# -- (d) the engine: a slot's row reused, the records, the refusals -----------


def assert_greedy_matches(ref, w, full, plen):
    """Every served token is the reference's first, or within ``TOL`` of
    it (a near-tie)."""
    lg = np.asarray(ref.logits(w, np.asarray(full), TOY))
    rows = lg[plen - 1:len(full) - 1]
    served = np.asarray(full[plen:])
    gap = rows.max(-1) - rows[np.arange(len(served)), served]
    assert gap.max() < TOL, gap.max()


def test_engine_reuses_a_state_row_without_clearing_it(tmp_path, capsys, ref,
                                                       weights):
    """Seven requests through two slots: rows are admitted and retired,
    so each slot's state row is reused several times with what its
    predecessor left in it (the prefill program takes zeros at position
    0), chunks ride with the batch, and every served token is the
    reference's. The ``engine_step`` record's ``state_bytes`` counts the
    rows a step LAUNCHED, once each, at the row's two widths, which every
    record carries (``STATE_ROW_BYTES``) and ``report`` prints."""
    from distributed_llm_code_samples_tpu.report import report_main
    from distributed_llm_code_samples_tpu.runtime.telemetry import (
        METRICS_FILENAME, STEP_SPAN, STEP_SPAN_STATE_ROW, TelemetryWriter,
        read_metrics, validate_record)
    w, params = weights
    mdir = str(tmp_path / "m")
    with TelemetryWriter(mdir) as writer:
        eng = DecodeEngine(params, HEADS, EngineConfig(
            max_slots=2, n_blocks=1 + 2 * 8, max_blocks_per_seq=8,
            prefill_chunk=16), metrics=writer)
        assert eng.recurrent == ["delta"] and eng.prefix is None
        assert eng.state.scratch_row == 2
        assert eng.state_row_bytes == dict(zip(
            STATE_ROW_BYTES, (ROW.state_bytes, ROW.tail_bytes)))
        assert eng.state.bytes_per_slot == DELTA_LAYERS * ROW.bytes
        ps = prompts_of([5, 37, 11, 30, 7, 64, 2])
        uids = [eng.submit(pr, 10 + 2 * i) for i, pr in enumerate(ps)]
        out = eng.run()
    assert not eng.failed
    assert np.abs(np.asarray(eng.state.ssm[:, :2])).max() > 1e-3
    for u, pr in zip(uids, ps):
        assert len(out[u]) == len(pr) + 10 + 2 * uids.index(u)
        assert_greedy_matches(ref, w, out[u], len(pr))
    assert eng.flight[-1]["state_bytes"] == eng.state.bytes_per_slot
    records, problems = read_metrics(os.path.join(mdir, METRICS_FILENAME))
    assert problems == []
    steps = [r for r in records if r.get("span") == STEP_SPAN]
    assert len(steps) == eng.steps
    assert any(k == "mixed" for r in steps for k, _ in r["dispatches"])
    for r in steps:
        rows = sum(b for k, b in r["dispatches"] if k != "prefill")
        assert r["state_bytes"] % eng.state.bytes_per_slot == 0
        assert r["state_bytes"] <= rows * eng.state.bytes_per_slot
        assert (r["state_row_bytes"], r["tail_row_bytes"]) == (4096, 1536)
    # the pair: both or none, whole, not negative
    for over in ({"state_row_bytes": None}, {"tail_row_bytes": -1}):
        rec = {k: v for k, v in dict(steps[-1], **over).items()
               if k not in over or v is not None}
        ok, reason = validate_record(rec)
        assert not ok and "bytes a sequence" in reason, (over, reason)
    bare = {k: v for k, v in steps[-1].items()
            if k not in STEP_SPAN_STATE_ROW}
    assert validate_record(bare)[0]
    assert report_main([mdir]) == 0
    assert ("a slot keeps 5632 bytes a recurrent layer: 4096 of state, "
            "1536 of tail") in capsys.readouterr().out


def _export(eng):
    eng.submit([1, 2, 3], 4)
    eng.step()
    eng.export_sequence(0)


def _snapshot(eng):
    from distributed_llm_code_samples_tpu.decode.supervise import (
        snapshot_state)
    snapshot_state(eng)


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
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_cannot_carry_the_matrix_state_refuses_in_one_line(weights,
                                                                what):
    """What moves a sequence by its one block table or would have to
    undo a step (a mesh, a rejected draft, spill, handoff, snapshot)
    refuses for the ``delta`` layers by name, in the one line every
    recurrent layer's refusal has, under no flag; the prefix cache is
    off (a block hit is worth nothing without the matrix at its
    boundary: 19.8 MB a slot at the published widths)."""
    _, params = weights
    with pytest.raises(ValueError) as err:
        REFUSALS[what](params)
    msg = str(err.value)
    assert "\n" not in msg and "delta layers" in msg
    assert "recurrent state" in msg
    assert engine(params, prefix_cache=True).prefix is None


def test_cli_and_library_build_the_same_engine(tmp_path, capsys, ref,
                                               driver):
    """``generate --model_config`` picks the family by ``model_type``
    and serves the model the one library function builds, with no flag
    of the family's own: the tokens of ``engine_from_config`` on the
    same seed, which are the reference's; what cannot carry the state
    refuses at the entry."""
    from distributed_llm_code_samples_tpu.decode.generate_cli import (
        generate_main)
    from distributed_llm_code_samples_tpu.decode.model_config import (
        engine_from_config)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(TOY))
    assert generate_main(["--model_config", str(path), "-r", "11",
                          "--prompt_lens", "5,19", "--prompt_seed", "3",
                          "--max_new", "6", "--max_slots", "2"]) == 0
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    rng = np.random.default_rng(3)
    ps = [rng.integers(0, TOY["vocab_size"], n).tolist() for n in (5, 19)]
    eng = engine_from_config(TOY, seed=11, engine_config=EngineConfig(
        max_slots=2, n_blocks=1 + 2 * 2, max_blocks_per_seq=2))
    got = eng.generate(ps, 6)
    assert [s["tokens"] for s in payload["sequences"]] == got
    w = driver.make_weights(TOY, 11)
    for full, pr in zip(got, ps):
        assert_greedy_matches(ref, w, full, len(pr))
    base = ["--model_config", str(path), "--prompt_lens", "5",
            "--max_new", "2"]
    for more in (["--fleet", "2"], ["--snapshot_dir", str(tmp_path / "s")],
                 ["--tp", "2"], ["--speculate", "2"]):
        assert generate_main(base + more) == 2
        err = capsys.readouterr().err.strip().splitlines()[-1]
        assert err.startswith("error:") and "delta layers" in err


REFUSED = {
    "mlp_only_layers": [1],
    "decoder_sparse_step": 2,
    "use_sliding_window": True,
    "rope_scaling": {"rope_type": "yarn", "factor": 4.0},
    "hidden_act": "gelu",
    "linear_num_value_heads": 5,
    "norm_topk_prob": False,
    "tie_word_embeddings": True,
    "model_type": "qwen3_moe",
}


@pytest.mark.parametrize("key", sorted(REFUSED))
def test_spec_from_config_refuses_what_it_names(key):
    """What the family's file cannot serve is refused by name, never
    read as something else."""
    with pytest.raises(ValueError, match=key if key != "tie_word_embeddings"
                       else "untied"):
        qwen.spec_from_config(dict(TOY, **{key: REFUSED[key]}))
    assert qwen.spec_from_config(TOY).kinds == ("delta",) * 3 + ("attn",)


# -- the controls' script, at the toy's size -----------------------------------


@pytest.mark.parametrize("control", ["no_state", "no_decay"])
def test_delta_control_script_rehearsal_on_the_cpu(monkeypatch, capsys,
                                                   control):
    """``benchmark/tests/delta_control_on_chip.py`` at the gated
    delta-rule cell's toy size: the program with the carried state
    zeroed before every token, and with ``exp(g)`` fixed at 1, fails
    the toy's ``correct`` by a limit (the seeded decay keeps state over
    many tokens and forgets it: ``models/qwen3_next_lm.py``). Nothing
    here is a measurement (``tests/test_chip_compile.py``, the suite's
    longest file, rehearses the cell itself)."""
    monkeypatch.syspath_prepend(ROOT)
    from benchmark.tests import delta_control_on_chip, shrink_qwen3_next
    shrink_qwen3_next.step_clock(monkeypatch)
    rc = delta_control_on_chip.main(
        ["--seed", str(2**31 + 52), "--seconds", "1.5", "--control",
         control], shrink=shrink_qwen3_next.serve, put=monkeypatch.setattr)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["control"] == control
    assert out["correct"] is False
    gaps = [out["compared"]["served_logit_gap_" + k] for k in ("max", "mean")]
    assert any(g["value"] > 50 * g["limit"] for g in gaps), gaps
