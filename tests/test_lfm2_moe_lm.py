"""The gated short-convolution, grouped-query attention, sparse-expert LM
on the serving path, at toy size: ``models/lfm2_moe_lm.py`` through
``DecodeEngine`` against the plain reference
``benchmark/configs/lfm2_moe_lm_reference.py`` (float32 at ``highest``,
full causal attention and the convolution over the whole sequence,
nothing from the package). The one family that uses all three of the
trunk's seams in one step program: paged K/V (``ATTN``), a state row by
slot (the convolution's tail, and NO scan state) and counted experts.

The toy has the published model's shape in small: d 64, 6 layers
``conv, full_attention, conv, conv, full_attention, conv`` (the first
with the dense MLP of 160), 4 query heads over 2 KV heads of 16 lanes
with QK-norm and rotary at theta 1e6, 3 convolution taps, 16 experts of
48 with the top 4 and no shared one, V 96, float32.
``initializer_range`` 0.2: at d=64 the published 0.02 leaves the blocks'
outputs too small for a dropped one to show. The QK-norm gains are drawn
in [0.5, 1.5] here (the seeded model's are 1), so that a program that
dropped or misplaced them is seen.

Tolerance, everywhere below: ``TOL = 2e-4`` on logits whose spread
(standard deviation) is 1.5. Both sides are float32 and differ in the
order of their sums (a chunk of c rows or a batch of b against all T at
once, the stored rows' two products against per-head attention); 8.5e-6
was read. The all-bfloat16 control of the same toy reads 0.09 on average
and 1.0 at worst (an expert choice that flips), the int8 control 0.16
and 1.0; a wrong QK-norm gain or rotary base 1.0-1.9: thousands of times
the tolerance.
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
from distributed_llm_code_samples_tpu.decode.engine import (EXPERT_COUNTERS,
                                                            ServePolicy)
from distributed_llm_code_samples_tpu.decode.model_config import (
    engine_from_config, params_from_config)
from distributed_llm_code_samples_tpu.models import lfm2_moe_lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4

TOY = dict(model_type="lfm2_moe", hidden_size=64, intermediate_size=160,
           moe_intermediate_size=48, num_attention_heads=4,
           num_key_value_heads=2, num_experts=16, num_experts_per_tok=4,
           num_dense_layers=1, num_hidden_layers=6,
           layer_types=["conv", "full_attention", "conv", "conv",
                        "full_attention", "conv"],
           conv_L_cache=3, conv_bias=False, norm_eps=1e-5,
           norm_topk_prob=True, use_expert_bias=True,
           routed_scaling_factor=1,
           rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
           vocab_size=96, max_position_embeddings=256,
           initializer_range=0.2)
HEADS, EXPERTS, TOP_K, EXPERT_LAYERS, CONV_LAYERS = 4, 16, 4, 5, 4


def _load(name):
    path = os.path.join(ROOT, "benchmark", "configs", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("lfm2_moe_lm_reference")


@pytest.fixture(scope="module")
def driver():
    return _load("lfm2_moe_engine_driver")


@pytest.fixture(scope="module")
def weights(driver):
    """The benchmark driver's named leaves, and the params the engine
    takes, of one seed — the QK-norm gains redrawn away from 1: the
    reference and the program get one set of arrays."""
    w = driver.make_weights(TOY, 11)
    kq, kk = jax.random.split(jax.random.PRNGKey(5))
    w["g_q"] = jax.random.uniform(kq, w["g_q"].shape, minval=0.5, maxval=1.5)
    w["g_k"] = jax.random.uniform(kk, w["g_k"].shape, minval=0.5, maxval=1.5)
    return w, driver._params(TOY, w)


def engine(params, slots=3, mbps=8, chunk=16, **kw):
    cfg = EngineConfig(max_slots=slots, n_blocks=1 + slots * mbps,
                       max_blocks_per_seq=mbps, prefill_chunk=chunk)
    policy = kw.pop("policy", None)
    return DecodeEngine(params, HEADS, dataclasses.replace(cfg, **kw),
                        policy=policy)


def prompts_of(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TOY["vocab_size"], n).tolist() for n in lens]


def ref_gates(ref, w, tokens):
    """``[L_e, T, E]``: the reference's routing weights of every row at
    every expert layer (0 where a row did not choose the expert), from
    its own functions, layer by layer as ``ref.hidden`` walks them."""
    eps, x = TOY["norm_eps"], w["wte"][jnp.asarray(tokens)]
    seen = {"conv": 0, "full_attention": 0}
    out = []
    for l, kind in enumerate(TOY["layer_types"]):
        i = seen[kind]
        seen[kind] += 1
        a = ref._norm(w["norm_in"][l], x, eps=eps)
        if kind == "conv":
            x = x + ref._conv(a, *(w["conv." + k][i] for k in ref.CONV),
                              mode="f32")
        else:
            x = x + ref._attn(a, *(w["attn." + k][i] for k in ref.ATTN),
                              w["g_q"][i], w["g_k"][i], dh=16, eps=eps,
                              theta=1e6, mode="f32")
        a = ref._norm(w["norm_ff"][l], x, eps=eps)
        if l < TOY["num_dense_layers"]:
            x = x + ref._mlp(a, *(w["dense." + k][l] for k in ref.MLP),
                             mode="f32")
            continue
        e = l - TOY["num_dense_layers"]
        out.append(np.asarray(ref._route(
            a, w["experts.w_router"][e], w["experts.bias"][e], top_k=TOP_K,
            scale=1.0)))
        x = x + ref._experts(w, e, a, TOY, jnp.float32, "f32")
    return np.stack(out)


# -- (a) prefill + decode through the cache is the full forward -------------


def cached_logits(eng, tokens, chunks, decode_from=None):
    """Logits ``[T, V]`` of one sequence through the engine's own
    program bodies, K/V pool and state rows, in slot 1: the first
    ``decode_from`` tokens prefilled in ``chunks``-sized pieces, the
    rest decoded one at a time. Returns ``(logits, the experts' counters
    of every dispatch in order, the slot's tails [L_c, (K-1)*d])``."""
    p, cfg = eng.params, eng.cfg
    slot, t = 1, len(tokens)
    decode_from = t if decode_from is None else decode_from
    table = np.zeros(cfg.max_blocks_per_seq, np.int32)
    need = -(-t // cfg.block_size)
    table[:need] = 1 + np.arange(need)
    cache = eng._cache()
    rows, counts, pos = [], [], 0
    while pos < decode_from:
        c = min(chunks, decode_from - pos)
        c = 1 << (c.bit_length() - 1)              # power-of-two chunks
        cache, x, cnt = jax.jit(
            lambda p, cache, table, pos0, toks, row, c=c:
            eng.programs.prefill_hidden(c, p, cache, table, pos0, toks,
                                        row))(
                p, cache, jnp.asarray(table), jnp.int32(pos),
                jnp.asarray(tokens[pos:pos + c], jnp.int32),
                jnp.int32(slot))
        rows.append(eng.programs.logits(p, x))
        counts.append(np.asarray(cnt))
        pos += c
    body = jax.jit(lambda p, cache, tables, lengths, toks, rows:
                   eng.programs.decode_hidden(1, p, cache, tables, lengths,
                                              toks, rows))
    while pos < t:
        cache, x, cnt = body(p, cache, jnp.asarray(table[None]),
                             jnp.asarray([pos], jnp.int32),
                             jnp.asarray(tokens[pos:pos + 1], jnp.int32),
                             jnp.asarray([slot], jnp.int32))
        rows.append(eng.programs.logits(p, x))
        counts.append(np.asarray(cnt))
        pos += 1
    state = cache[1]
    assert state.ssm is None
    return (np.asarray(jnp.concatenate(rows, 0)), counts,
            np.asarray(state.conv[:, slot, 0]))


@pytest.mark.parametrize("chunks,decode_from", [(16, 24), (8, 13), (4, 40)])
def test_prefill_then_decode_through_the_cache_is_the_reference(
        ref, weights, chunks, decode_from):
    """40 tokens over three blocks of 16: prefilled in chunks (the
    convolution's tail crossing every chunk's edge), then decoded one at
    a time across a block boundary, every position's logits are the
    reference's full causal forward; and each dispatch's counters are
    the reference's count of the rows each expert got."""
    w, params = weights
    tokens = prompts_of([40], seed=1)[0]
    got, counts, _ = cached_logits(engine(params), tokens, chunks,
                                   decode_from)
    want = np.asarray(ref.logits(w, np.asarray(tokens), TOY))
    assert want.std() > 1.0
    assert np.abs(got - want).max() < TOL
    gates = ref_gates(ref, w, tokens)                   # [L_e, T, E]
    pos = 0
    for cnt in counts:
        n = int(cnt[0].sum()) // TOP_K                  # rows it carried
        assert cnt.shape == (EXPERT_LAYERS, EXPERTS)
        assert np.array_equal(cnt, (gates[:, pos:pos + n] > 0).sum(1))
        pos += n
    assert pos == len(tokens)


def test_bfloat16_arithmetic_in_the_float32_toy_fails_the_tolerance(
        ref, weights):
    """The controls are other computations, not other names, and the
    tolerance tells them: the all-bfloat16 and the int8 forward of the
    same float32 weights each lie further from the reference than 50
    times ``TOL`` (and nearer than a dropped layer would)."""
    w, _ = weights
    tokens = np.asarray(prompts_of([24], seed=2)[0])
    full = np.asarray(ref.logits(w, tokens, TOY))
    assert np.array_equal(full, np.asarray(ref.logits(w, tokens, TOY,
                                                      "f32")))
    for mode in ("bf16", "int8"):
        low = np.asarray(ref.logits(w, tokens, TOY, mode))
        assert np.abs(low - full).max() > 50 * TOL, mode
        assert np.abs(low - full).mean() < 0.2 * full.std(), mode


def test_parameter_count_at_published_widths():
    """The configuration file's arithmetic is the program's, from the
    arrays' shapes (nothing is allocated): 9 layers of LFM2-24B-A2B are
    5,177,950,976 parameters and 10,358,000,128 bytes as served, a
    convolution mixer 16,783,360, an attention mixer 10,485,760 (+ 128
    gains), the dense MLP 72,351,744, an expert layer's routed part
    604,110,912; K/V rows of 512 lanes over 2 layers, 7 layers of tails
    of 4,096 lanes and no scan state."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-24b-a2b-serve.json")) as f:
        config = json.load(f)
    spec = lfm2_moe_lm.spec_from_config(config)
    p = jax.eval_shape(lambda k: lfm2_moe_lm.init_lfm2_moe_lm(
        k, spec, dtype=jnp.bfloat16), jax.random.PRNGKey(0))
    per = lambda stack, n: sum(x.size for x in stack) // n
    assert p.num_params() == 5_177_950_976
    assert sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(p)) == 10_358_000_128
    assert per(p.conv, 7) == 16_783_360
    assert per(p.attn, 2) == 10_485_760 and p.g_q.shape == (2, 64)
    assert per(p.dense, 1) == 72_351_744
    assert per(p.experts, 8) == 604_110_912
    assert [i for i, k in enumerate(p.kinds) if k == "attn"] == [1, 5]
    cs = p.cache_spec(32)
    assert (cs.kv_layers, cs.kv_heads, cs.head_dim) == (2, 8, 64)
    assert (cs.rec_layers, cs.state_row) == (7, (2048, 3, 0, 0))
    assert (cs.state_row.tail_bytes, cs.state_row.bytes) == (16_384, 16_384)
    assert (cs.expert_layers, cs.n_experts, cs.latent_rank) == (8, 64, 0)
    assert "5,177,950,976" in config["serving"]["note"]
    assert "10,358,000,128" in config["serving"]["note"]
    assert config["published"]["num_hidden_layers"] == 40


# -- (b) the tail and the logits do not depend on the chunking ---------------


@pytest.fixture(scope="module")
def one_chunk(weights):
    _, params = weights
    tokens = prompts_of([32], seed=7)[0]
    return tokens, cached_logits(engine(params, chunk=32), tokens, 32)


@pytest.mark.parametrize("chunk", [1, 2, 4, 8, 16])
def test_tail_and_logits_do_not_depend_on_the_chunking(weights, one_chunk,
                                                       chunk):
    """A 32-token prompt prefilled in chunks of 1, 2, 4, 8 or 16 leaves
    the convolutions' tails, and gives the logits, of one chunk of 32
    (a chunk of 1 is shorter than the tail it carries: ``K - 1 = 2``)."""
    _, params = weights
    tokens, (want, _, tails) = one_chunk
    got, _, tails_c = cached_logits(engine(params, chunk=chunk), tokens,
                                    chunk)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert tails.shape == (CONV_LAYERS, 2 * 64)
    assert np.abs(tails).max() > 0.1
    np.testing.assert_allclose(tails_c, tails, atol=TOL, rtol=0)


# -- (c) QK-norm and the rotary base each matter -------------------------------


@pytest.mark.parametrize("wrong", ["g_q", "g_k", "rope_theta", "no_rope"])
def test_a_wrong_qk_norm_or_rotary_base_fails(ref, weights, wrong):
    """The program with one of them wrong — the query's or the key's
    gains left at 1, the rotary at ``rope``'s default base, positions
    all 0 — misses the reference by far more than the tolerance."""
    w, params = weights
    tokens = prompts_of([24], seed=3)[0]
    want = np.asarray(ref.logits(w, np.asarray(tokens), TOY))
    if wrong in ("g_q", "g_k"):
        bad = dataclasses.replace(params, **{
            wrong: jnp.ones_like(getattr(params, wrong))})
    elif wrong == "rope_theta":
        bad = dataclasses.replace(params, rope_theta=10000.0)
    else:
        # no rotation at all: theta so large that every angle is ~0
        bad = dataclasses.replace(params, rope_theta=1e30)
    got, _, _ = cached_logits(engine(bad), tokens, 8, 16)
    assert np.abs(got - want).max() > 50 * TOL


# -- (d) the router: no row dropped, a share of the experts ----------------------


def test_no_row_is_dropped_when_all_rows_choose_one_expert(ref, weights):
    """A router that sends all 64 rows to expert 5 first (a capacity of
    ``tokens / experts * factor`` would drop most of them): expert 5
    counts 64 rows, the counts sum to every (row, choice) pair, and each
    row's result is the reference's — with no shared expert beside."""
    w, p = weights
    n, layer = 64, 1
    a = jax.random.normal(jax.random.PRNGKey(8), (n, p.d_model))
    bias = p.experts.bias.at[layer, 5].set(10.0)
    crowded = dataclasses.replace(p, experts=p.experts._replace(bias=bias))
    y, rows = crowded.ffn_counted(TOY["num_dense_layers"] + layer, a)
    assert int(rows[5]) == n and int(rows.sum()) == n * TOP_K
    w2 = dict(w, **{"experts.bias": bias})
    want = ref._experts(w2, layer, a, TOY, jnp.float32, "f32")
    assert np.abs(np.asarray(want)).max() > 0.1
    assert np.abs(np.asarray(y - want)).max() < 2e-5
    dense, none = p.ffn_counted(0, a)
    assert none is None and dense.shape == a.shape


WIDE = dict(TOY, num_experts=64, num_hidden_layers=2,
            layer_types=["conv", "full_attention"])


def test_four_holders_parts_add_up_to_the_layer(ref, driver):
    """64 experts over 4 holders of 16 (what four chips sharing the
    layer would hold): each routes over all 64 and computes its own
    experts' part; the four parts are the uncut reference's whole layer
    (there is no shared expert to count once); the holders' counters
    side by side are the whole layer's."""
    w = driver.make_weights(WIDE, 4)
    p = driver._params(WIDE, w)
    a = jax.random.normal(jax.random.PRNGKey(2), (24, p.d_model))
    total, counts = 0, []
    for j in range(4):
        part = lfm2_moe_lm.holder(p, 16 * j, 16)
        assert part.cache_spec(HEADS).n_experts == 16
        assert part.experts.w_router.shape[1] == 64     # the router is whole
        y, rows = part.ffn_counted(1, a)
        total = total + y
        counts.append(np.asarray(rows))
    whole, rows = p.ffn_counted(1, a)
    assert np.array_equal(np.concatenate(counts), np.asarray(rows))
    assert int(rows.sum()) == 24 * TOP_K
    want = ref._experts(w, 0, a, WIDE, jnp.float32, "f32")
    assert np.abs(np.asarray(want)).max() > 0.1
    assert np.abs(np.asarray(total - want)).max() < 2e-5
    assert np.abs(np.asarray(whole - want)).max() < 2e-5


# -- (e) the engine: greedy tokens, slot reuse, preemption and replay ----------------


def assert_greedy_matches(ref, w, full, plen):
    """The engine returns picks only. A served token has to be the
    reference's argmax wherever the reference's top two logits lie
    more than ``2 * TOL`` apart; a nearer tie may go either way, so the
    comparison is teacher-forced on what was served."""
    lg = np.asarray(ref.logits(w, np.asarray(full), TOY))
    rows = lg[plen - 1:len(full) - 1]
    served = np.asarray(full[plen:])
    top2 = np.sort(rows, -1)[:, -2:]
    clear = top2[:, 1] - top2[:, 0] > 2 * TOL
    assert clear.mean() > 0.9
    assert np.array_equal(rows.argmax(-1)[clear], served[clear])
    best = rows.max(-1) - rows[np.arange(len(served)), served]
    assert best.max() <= 2 * TOL


def test_engine_greedy_tokens_are_the_reference_argmax(ref, weights):
    """Mixed lengths, more requests than slots, chunked prefill beside
    running decodes: ONE step program packs the state's rows on its
    operand and returns the experts' counters on its result. The step's
    counters and state bytes are in its flight digest and its
    ``engine_step`` record."""
    w, params = weights
    spans = []

    class Writer:
        def span(self, rec):
            spans.append(rec)

        def __getattr__(self, _):
            return lambda *a, **k: None

    eng = DecodeEngine(params, HEADS, EngineConfig(
        max_slots=3, n_blocks=1 + 3 * 8, max_blocks_per_seq=8,
        prefill_chunk=8), metrics=Writer())
    assert eng.recurrent == ["conv"] and eng.prefix is None
    assert eng.state.ssm is None
    assert eng.state.conv.shape == (CONV_LAYERS, 3 + 1, 1, 2 * 64)
    assert eng.state.scratch_row == 3
    assert eng.state.bytes_per_slot == CONV_LAYERS * 2 * 64 * 4
    assert "rows" in eng.programs.wire("decode", 2).fields
    ps = prompts_of([5, 37, 11, 30, 7, 64, 2])
    uids = [eng.submit(pr, 12 + 3 * i) for i, pr in enumerate(ps)]
    out = eng.run()
    assert not eng.failed
    for u, pr in zip(uids, ps):
        assert len(out[u]) == len(pr) + 12 + 3 * uids.index(u)
        assert_greedy_matches(ref, w, out[u], len(pr))
    # the last step decoded one row: 4 pairs a layer over 5 layers, one
    # row of the tails. The experts' counters are of the results a step
    # READ (the engine drained: the step before's one-row batch and
    # then its own), the state bytes of the rows it LAUNCHED
    pairs = EXPERT_LAYERS * TOP_K
    last = eng.flight[-1]
    assert last["readbacks"] == [eng.launches - 2, eng.launches - 1]
    assert [last[k] for k in EXPERT_COUNTERS] == [2 * pairs, 2 * pairs, 1]
    assert last["state_bytes"] == eng.state.bytes_per_slot
    steps = [s for s in spans if s["span"] == "engine_step"]
    assert len(steps) == eng.steps
    assert [steps[-1][k] for k in EXPERT_COUNTERS] == [2 * pairs,
                                                       2 * pairs, 1]
    assert [steps[-2][k] for k in EXPERT_COUNTERS] == [pairs, pairs, 1]
    assert steps[-1]["state_bytes"] == eng.state.bytes_per_slot
    both = [s for s in steps if s["expert_rows"] > pairs * 8]
    assert both and all(s["expert_rows"] % pairs == 0 for s in steps)
    assert all(s["experts_touched"] <= s["expert_rows"] for s in steps)


def test_a_reused_slot_starts_from_a_zero_tail(weights):
    """One slot, three requests one after another: each finds the tail
    and the blocks its predecessor left, and is served as by an engine
    that never held another."""
    _, params = weights
    ps = prompts_of([21, 9, 33], seed=2)
    eng = engine(params, slots=1, mbps=8)
    uids = [eng.submit(pr, 10) for pr in ps]
    out = eng.run()
    assert np.abs(np.asarray(eng.state.conv[:, 0])).max() > 0
    for u, pr in zip(uids, ps):
        fresh = engine(params, slots=1, mbps=8)
        fresh.submit(pr, 10, uid=u)
        assert fresh.run()[u] == out[u]


def test_preemption_replays_from_a_zero_tail(weights):
    """A pool too small for all three requests: the youngest is evicted
    back to WAITING, re-prefilled from position 0 (a zero tail, whatever
    its row holds) with its recorded tokens forced through the decode
    path, and ends with the tokens of an uninterrupted run."""
    _, params = weights
    ps = prompts_of([9, 8, 40], seed=4)
    want = {}
    for u, pr in enumerate(ps):
        alone = engine(params, slots=1, mbps=4)
        alone.submit(pr, 24, uid=u)
        want[u] = alone.run()[u]
    eng = engine(params, slots=3, mbps=4, n_blocks=1 + 6,
                 policy=ServePolicy(preempt_after_steps=2))
    for u, pr in enumerate(ps):
        eng.submit(pr, 24, uid=u)
    out = eng.run()
    assert eng.preempted >= 1 and not eng.failed
    assert out == want


# -- (f) what cannot carry the state refuses, in the hybrid's one line ---------------


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
def test_what_cannot_carry_the_tail_refuses_in_one_line(weights, what):
    """``_refuse_recurrent``'s line, as for the hybrid: by what the
    model is (``conv`` layers), under no flag."""
    _, params = weights
    with pytest.raises(ValueError) as err:
        REFUSALS[what](params)
    msg = str(err.value)
    assert "\n" not in msg and "conv layers" in msg
    assert "recurrent state" in msg


# -- the entry point --------------------------------------------------------------


def test_cli_and_library_build_the_same_engine(tmp_path, capsys, ref,
                                               driver):
    """``generate --model_config`` picks the family by ``model_type``
    and serves the model the one library function builds: the tokens of
    ``engine_from_config`` on the same seed, which are the
    reference's; what cannot carry the tails refuses at the entry."""
    from distributed_llm_code_samples_tpu.decode.generate_cli import (
        generate_main)
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
        assert err.startswith("error:") and "conv layers" in err


def test_weights_come_in_the_type_the_config_states():
    """bfloat16 as served: every leaf but the router, which is float32
    whatever the type; the pool takes the cache's type and the tails
    stay float32; the seeded choice bias is small and not zero; and
    what the file cannot serve is refused by name. (Served here over
    float32 weights: this CPU backend has no bfloat16 x bfloat16 ->
    float32 product at every shape; the chip's phase in
    ``chip_smoke.py`` and the benchmark's cell serve the bfloat16
    ones.)"""
    bf16 = dict(TOY, precision={"weights": "bfloat16"})
    p = params_from_config(bf16, 1)
    kinds = {k: x.dtype for k, x in zip(p.experts._fields, p.experts)}
    assert kinds["w_router"] == kinds["bias"] == jnp.float32
    assert kinds["w_gate"] == p.wte.dtype == p.conv.w_in.dtype == jnp.bfloat16
    assert p.g_q.dtype == p.attn.wq.dtype == jnp.bfloat16
    bias = np.asarray(p.experts.bias)
    assert 0 < np.abs(bias).max() < 0.1
    eng = engine_from_config(TOY, seed=1, engine_config=EngineConfig(
        kv_dtype="bf16"))
    assert eng.pool.k.dtype == eng.pool.v.dtype == jnp.bfloat16
    assert eng.pool.k.shape[0] == 2 and eng.pool.k.shape[-1] == 2 * 16
    assert eng.state.conv.dtype == jnp.float32 and eng.state.ssm is None
    eng.submit([1, 2, 3, 4, 5], 4)
    assert len(eng.run()[0]) == 9


REFUSED = {
    "model_type": ("jamba", "serves 'lfm2_moe' only"),
    "conv_bias": (True, "conv_bias"),
    "rope_parameters": ({"rope_theta": 1e6, "rope_type": "yarn"},
                        "rope_type"),
    "norm_topk_prob": (False, "norm_topk_prob"),
    "use_expert_bias": (False, "use_expert_bias"),
    "tie_word_embeddings": (False, "tied"),
    "layer_types": (["conv", "sliding_attention"] * 3, "layer_types"),
    "num_hidden_layers": (5, "num_hidden_layers"),
}


@pytest.mark.parametrize("key", sorted(REFUSED))
def test_spec_from_config_refuses_what_it_names(key):
    """What ``models/lfm2_moe_lm.py`` does not build is refused by name
    and in one line, never read as something else."""
    bad, why = REFUSED[key]
    with pytest.raises(ValueError, match=why) as err:
        lfm2_moe_lm.spec_from_config(dict(TOY, **{key: bad}))
    assert "\n" not in str(err.value)
    with pytest.raises(ValueError, match="served are"):
        params_from_config(dict(TOY, model_type="gpt2"))
