"""The latent-attention, sparse-expert LM on the serving path, at toy
size: ``models/mla_moe_lm.py`` and ``ops/moe_serve.py`` through
``DecodeEngine`` against the plain reference
``benchmark/configs/glm_moe_lm_reference.py`` (float32 at ``highest``,
the NAIVE attention — every token's keys and values expanded from its
latent — nothing from the package).

The toy has the published model's shape in small: d 64, 4 heads of 12 +
8 query/key lanes and 16 value lanes over a latent of 32 (+ 8 rotary),
a query latent of 24, one dense layer of 160 and three expert layers of
16 experts of 48, top 4, one shared expert, V 96, float32.
``initializer_range`` 0.2: at d=64 the published 0.02 leaves the blocks'
outputs too small for a dropped one to show.

Tolerance, everywhere below: ``TOL = 2e-4`` on logits whose spread
(standard deviation) is 1.5. Both sides are float32 and differ in the
order of their sums (absorbed against naive attention, a chunk of c rows
or a batch of b against all T at once); 2e-5 was read. Dropping a layer,
an expert, the shared expert, the bias or the scaling moves them by
thousands of times that.
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
from distributed_llm_code_samples_tpu.models import mla_moe_lm
from distributed_llm_code_samples_tpu.models.face import gated_mlp
from distributed_llm_code_samples_tpu.ops import moe_serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4

TOY = dict(model_type="glm4_moe_lite", hidden_size=64, intermediate_size=160,
           moe_intermediate_size=48, num_attention_heads=4,
           num_key_value_heads=4, n_routed_experts=16, n_shared_experts=1,
           num_experts_per_tok=4, routed_scaling_factor=1.8,
           first_k_dense_replace=1, num_hidden_layers=4, q_lora_rank=24,
           kv_lora_rank=32, qk_nope_head_dim=12, qk_rope_head_dim=8,
           v_head_dim=16, vocab_size=96, rms_norm_eps=1e-5,
           rope_theta=1000000, rope_scaling=None, partial_rotary_factor=1,
           tie_word_embeddings=False, topk_method="noaux_tc", n_group=1,
           topk_group=1, norm_topk_prob=True, hidden_act="silu",
           attention_bias=False, num_nextn_predict_layers=1,
           max_position_embeddings=256, initializer_range=0.2)
HEADS, EXPERTS, TOP_K = 4, 16, 4


def _load(name):
    path = os.path.join(ROOT, "benchmark", "configs", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("glm_moe_lm_reference")


@pytest.fixture(scope="module")
def driver():
    return _load("glm_moe_engine_driver")


@pytest.fixture(scope="module")
def weights(driver):
    """The benchmark driver's named leaves, and the params the engine
    takes, of one seed: the reference and the program get one set of
    arrays."""
    w = driver.make_weights(TOY, 11)
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
    eps = TOY["rms_norm_eps"]
    x = w["wte"][jnp.asarray(tokens)]
    out = []
    for l in range(TOY["num_hidden_layers"]):
        a = ref._norm(w["norm_in"][l], x, eps=eps)
        x = x + ref._mla(a, *(w["mla." + k][l] for k in ref.MLA), eps=eps,
                         theta=float(TOY["rope_theta"]), mode="f32")
        a = ref._norm(w["norm_ff"][l], x, eps=eps)
        if l < TOY["first_k_dense_replace"]:
            x = x + ref._mlp(a, *(w["dense." + k][l] for k in ref.MLP),
                             mode="f32")
            continue
        e = l - TOY["first_k_dense_replace"]
        out.append(np.asarray(ref._route(
            a, w["experts.w_router"][e], w["experts.bias"][e], top_k=TOP_K,
            scale=TOY["routed_scaling_factor"])))
        x = x + ref._experts(w, e, a, TOY, jnp.float32, "f32")
    return np.stack(out)


# -- (a) prefill + decode through the latent cache is the full forward ------


def cached_logits(eng, tokens, chunks, decode_from):
    """Logits ``[T, V]`` of one sequence through the engine's own
    program bodies and latent cache, in slot 1: the first
    ``decode_from`` tokens prefilled in ``chunks``-sized pieces, the
    rest decoded one at a time; and the experts' counters of every
    dispatch, in order."""
    p, cfg = eng.params, eng.cfg
    t = len(tokens)
    table = np.zeros(cfg.max_blocks_per_seq, np.int32)
    need = -(-t // cfg.block_size)
    table[:need] = 1 + np.arange(need)
    cache = eng._cache()
    rows, counts, pos = [], [], 0
    while pos < decode_from:
        c = min(chunks, decode_from - pos)
        c = 1 << (c.bit_length() - 1)              # power-of-two chunks
        cache, x, cnt = jax.jit(
            lambda p, cache, table, pos0, toks, c=c:
            eng.programs.prefill_hidden(c, p, cache, table, pos0, toks))(
                p, cache, jnp.asarray(table), jnp.int32(pos),
                jnp.asarray(tokens[pos:pos + c], jnp.int32))
        rows.append(eng.programs.logits(p, x))
        counts.append(np.asarray(cnt))
        pos += c
    body = jax.jit(lambda p, cache, tables, lengths, toks:
                   eng.programs.decode_hidden(1, p, cache, tables, lengths,
                                              toks))
    while pos < t:
        cache, x, cnt = body(p, cache, jnp.asarray(table[None]),
                             jnp.asarray([pos], jnp.int32),
                             jnp.asarray(tokens[pos:pos + 1], jnp.int32))
        rows.append(eng.programs.logits(p, x))
        counts.append(np.asarray(cnt))
        pos += 1
    return np.asarray(jnp.concatenate(rows, 0)), counts


@pytest.mark.parametrize("chunks,decode_from", [(16, 24), (8, 13), (4, 40)])
def test_prefill_then_decode_through_the_cache_is_the_reference(
        ref, weights, chunks, decode_from):
    """40 tokens over three blocks of 16: prefilled in chunks, then
    decoded one at a time across a block boundary, every position's
    logits are the reference's full causal forward; and each dispatch's
    counters are the reference's count of the rows each expert got."""
    w, params = weights
    tokens = prompts_of([40], seed=1)[0]
    got, counts = cached_logits(engine(params), tokens, chunks, decode_from)
    want = np.asarray(ref.logits(w, np.asarray(tokens), TOY))
    assert want.std() > 1.0
    assert np.abs(got - want).max() < TOL
    gates = ref_gates(ref, w, tokens)                   # [L_e, T, E]
    pos = 0
    for cnt in counts:
        n = int(cnt[0].sum()) // TOP_K                  # rows it carried
        assert cnt.shape == (3, EXPERTS)
        assert np.array_equal(cnt, (gates[:, pos:pos + n] > 0).sum(1))
        pos += n
    assert pos == len(tokens)


def test_reference_lower_precision_modes_differ(ref, weights):
    """The controls are other computations, not other names: bfloat16
    and int8 each move the logits, on average by less than a dropped
    layer would (single logits jump where a choice of experts flips)."""
    w, _ = weights
    tokens = np.asarray(prompts_of([24], seed=2)[0])
    full = np.asarray(ref.logits(w, tokens, TOY))
    for mode, lo in (("bf16", 1e-3), ("int8", 1e-3)):
        low = np.asarray(ref.logits(w, tokens, TOY, mode))
        assert lo < np.abs(low - full).mean() < 0.2 * full.std()


def test_parameter_count_at_published_widths():
    """The configuration file's arithmetic is the program's: 7 layers
    of GLM-4.7-Flash are 4,530,936,960 parameters, an expert layer
    635,311,424, and the cache row 640 lanes (576 filled to whole
    tiles)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glm47-flash-serve.json")) as f:
        config = json.load(f)
    spec = mla_moe_lm.spec_from_config(config)
    p = jax.eval_shape(lambda k: mla_moe_lm.init_mla_moe_lm(k, spec),
                       jax.random.PRNGKey(0))
    assert p.num_params() == 4_530_936_960
    per_layer = sum(x.size // x.shape[0] for x in jax.tree_util.tree_leaves(
        (p.experts, p.shared, p.mla))) + 2 * spec.d_model
    assert per_layer == 635_311_424
    cs = p.cache_spec(20)
    assert (cs.kv_layers, cs.kv_heads, cs.head_dim, cs.latent_rank) == (
        7, 1, 640, 512)
    assert (cs.expert_layers, cs.n_experts) == (6, 64)
    assert spec.n_layers == 7 and config["published"][
        "num_hidden_layers"] == 47


# -- (b) the absorbed attention is the naive form ------------------------------


def test_absorbed_attention_is_the_naive_form(ref, weights):
    """One layer, no cache: the model's query for the stored row
    against the rows it would store (scores over the whole row, values
    over its first ``kv_lora_rank`` lanes, ``W_uv`` and ``W_o`` after)
    is the reference's attention over keys and values expanded from
    the latent; and the row's filling lanes are zero."""
    w, p = weights
    t, layer = 21, 2
    a = jax.random.normal(jax.random.PRNGKey(5), (t, p.d_model))
    q, rows = p.latent_qrow(layer, a, jnp.arange(t))
    rank = p.cache_spec(HEADS).latent_rank
    assert q.shape == (t, HEADS, 128) and rows.shape == (t, 128)
    assert not np.asarray(rows[:, 40:]).any()
    s = jnp.einsum("qhj,tj->hqt", q, rows)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqt,tj->qhj", jax.nn.softmax(s, -1), rows[:, :rank])
    got = p.latent_out(layer, o)
    want = ref._mla(a, *(w["mla." + k][layer] for k in ref.MLA),
                    eps=TOY["rms_norm_eps"], theta=1e6, mode="f32")
    assert np.abs(np.asarray(want)).max() > 0.1
    assert np.abs(np.asarray(got - want)).max() < 2e-5


def test_the_walked_read_hands_latent_out_the_rank_lanes_alone(
        weights, monkeypatch):
    """The decode-side read of the latent pool is the one-sided walk
    (``paged.walks``; PR 50), whose kernel returns the heads' sums over
    the WHOLE stored row, 128 lanes here: what reaches ``latent_out``
    from every program, the decode batch's and a riding chunk's alike,
    is the first ``latent_rank`` lanes and no more."""
    from distributed_llm_code_samples_tpu.decode import paged
    _, p = weights
    seen, real = [], type(p).latent_out

    def spy(self, i, o):
        seen.append(o.shape)
        return real(self, i, o)
    monkeypatch.setattr(type(p), "latent_out", spy)
    eng = engine(p)
    assert paged.walks(eng.pool) and eng.pool.k.shape[-1] == 128
    eng.generate(prompts_of([5, 21, 9]), 6)
    kinds = {k for d in eng.flight for k, _ in d["dispatches"]}
    assert {"decode", "mixed"} <= kinds
    rank = eng.pool.latent_rank
    assert seen and {shape[1:] for shape in seen} == {(HEADS, rank)}


# -- (c) the router, by hand -----------------------------------------------------


def test_router_bias_moves_the_choice_and_not_the_weight():
    """Four experts, top 2, scores ``sigmoid([2, 1, 0, -1])``. With no
    bias experts 0 and 1 are chosen; a bias of 1 on expert 3 puts it
    first — with its OWN score 0.269 as its weight, not 1.269 — and the
    weights sum to the scale either way."""
    a = jnp.asarray([[1.0, 0.0]])
    w_r = jnp.asarray([[2.0, 0], [1.0, 0], [0.0, 0], [-1.0, 0]])
    s = 1 / (1 + np.exp(-np.asarray([2.0, 1, 0, -1])))
    idx, w = moe_serve.route(a, w_r, jnp.zeros(4), 2, 1.8)
    assert idx.tolist() == [[0, 1]]
    assert np.allclose(w, 1.8 * s[[0, 1]] / s[[0, 1]].sum(), atol=1e-6)
    idx, w = moe_serve.route(a, w_r, jnp.asarray([0, 0, 0, 1.0]), 2, 1.8)
    assert idx.tolist() == [[3, 0]]
    assert np.allclose(w, 1.8 * s[[3, 0]] / s[[3, 0]].sum(), atol=1e-6)
    assert np.isclose(float(w.sum()), 1.8, atol=1e-6)


def test_the_seeded_bias_moves_choices(weights):
    """The seeded ``e_score_correction_bias`` is not zero and changes
    which experts some rows choose (so a program that dropped it would
    be seen), while the chosen weights still come from the scores."""
    _, p = weights
    e = p.experts
    a = jax.random.normal(jax.random.PRNGKey(3), (64, p.d_model))
    with_b, w_b = moe_serve.route(a, e.w_router[0], e.bias[0], TOP_K, 1.8)
    without, _ = moe_serve.route(a, e.w_router[0], 0 * e.bias[0], TOP_K, 1.8)
    moved = (np.sort(np.asarray(with_b)) != np.sort(np.asarray(without)))
    assert 0.05 < moved.any(-1).mean() < 0.95
    assert np.allclose(np.asarray(w_b).sum(-1), 1.8, atol=1e-5)


def test_no_row_is_dropped_when_all_rows_choose_one_expert(ref, weights):
    """A router that sends all 64 rows to expert 5 first (a capacity of
    ``tokens / experts * factor`` would drop most of them): expert 5
    counts 64 rows, the counts sum to every (row, choice) pair, and each
    row's result is the reference's."""
    w, p = weights
    n, layer = 64, 1
    a = jax.random.normal(jax.random.PRNGKey(8), (n, p.d_model))
    bias = p.experts.bias.at[layer, 5].set(10.0)
    crowded = dataclasses.replace(p, experts=p.experts._replace(bias=bias))
    y, rows = crowded.ffn_counted(TOY["first_k_dense_replace"] + layer, a)
    assert int(rows[5]) == n and int(rows.sum()) == n * TOP_K
    w2 = dict(w, **{"experts.bias": bias})
    want = ref._experts(w2, layer, a, TOY, jnp.float32, "f32")
    assert np.abs(np.asarray(y - want)).max() < 2e-5


# -- (d) a share of the experts, tied to the whole layer ---------------------------

WIDE = dict(TOY, n_routed_experts=64, num_hidden_layers=2)


def test_four_holders_parts_add_up_to_the_layer(ref, driver):
    """64 experts over 4 holders of 16 (what four chips sharing the
    layer would hold): each routes over all 64 and computes its own
    experts' part; the four parts, with the shared expert counted once,
    are the reference's whole layer; the holders' counters side by side
    are the whole layer's."""
    w = driver.make_weights(WIDE, 4)
    p = driver._params(WIDE, w)
    a = jax.random.normal(jax.random.PRNGKey(2), (24, p.d_model))
    shared = gated_mlp(p.shared, 0, a)
    total, counts = shared, []
    for j in range(4):
        part = mla_moe_lm.holder(p, 16 * j, 16)
        assert part.cache_spec(HEADS).n_experts == 16
        assert part.experts.w_router.shape[1] == 64     # the router is whole
        y, rows = part.ffn_counted(1, a)
        total = total + (y - shared)
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
    running decodes; and the step's counters in its flight digest and
    its ``engine_step`` record."""
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
    assert eng.recurrent == [] and eng.state is None
    ps = prompts_of([5, 37, 11, 30, 7, 64, 2])
    uids = [eng.submit(pr, 12 + 3 * i) for i, pr in enumerate(ps)]
    out = eng.run()
    assert not eng.failed
    for u, pr in zip(uids, ps):
        assert len(out[u]) == len(pr) + 12 + 3 * uids.index(u)
        assert_greedy_matches(ref, w, out[u], len(pr))
    # the counters are of the results a step READ: the last step (the
    # engine drained) read the step before's one-row batch and then its
    # own, 4 pairs a layer over 3 layers each
    last = eng.flight[-1]
    assert last["readbacks"] == [eng.launches - 2, eng.launches - 1]
    assert [last[k] for k in EXPERT_COUNTERS] == [24, 24, 1]
    steps = [s for s in spans if s["span"] == "engine_step"]
    assert len(steps) == eng.steps
    assert [steps[-1][k] for k in EXPERT_COUNTERS] == [24, 24, 1]
    assert [steps[-2][k] for k in EXPERT_COUNTERS] == [12, 12, 1]
    # a step with a prefill chunk of 8 and a decode batch (bucket 4 or
    # 2: padded rows route too): both dispatches' pairs
    both = [s for s in steps if s["expert_rows"] > 3 * TOP_K * 8]
    assert both and all(s["expert_rows"] % (3 * TOP_K) == 0 for s in steps)
    assert all(s["experts_touched"] <= s["expert_rows"] for s in steps)


def test_a_reused_slot_is_served_as_a_fresh_engine_serves(weights):
    """One slot, three requests one after another, each in blocks its
    predecessor left (no prefix cache, so the stale latent rows are
    only ever masked): each is served as by an engine that never held
    another."""
    _, params = weights
    ps = prompts_of([21, 9, 33], seed=2)
    eng = engine(params, slots=1, mbps=8, prefix_cache=False)
    uids = [eng.submit(pr, 10) for pr in ps]
    out = eng.run()
    assert np.abs(np.asarray(eng.pool.k, np.float32)).max() > 0
    for u, pr in zip(uids, ps):
        fresh = engine(params, slots=1, mbps=8, prefix_cache=False)
        fresh.submit(pr, 10, uid=u)
        assert fresh.run()[u] == out[u]


def test_preemption_replays_through_the_latent_cache(weights):
    """A pool too small for all three requests: the youngest is evicted
    back to WAITING, re-prefilled from position 0 with its recorded
    tokens forced through the decode path, and ends with the tokens of
    an uninterrupted run."""
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


# -- (f) what moves a sequence by its blocks: works on latent rows, or refuses ------


def _base(params, ps, new=12):
    eng = engine(params, prefix_cache=False)
    uids = [eng.submit(pr, new) for pr in ps]
    out = eng.run()
    return [out[u] for u in uids]


def _prefix(params, ps):
    """A 32-token prefix shared by a later request: two blocks hit."""
    eng = engine(params)
    first = eng.submit(ps[0], 12)
    got = [eng.run()[first]]
    uids = [eng.submit(pr, 12) for pr in ps[1:]]
    out = eng.run()
    assert eng.prefix_hit_blocks >= 2 and eng.prefill_tokens_saved >= 32
    return got + [out[u] for u in uids]


def _partial(params, ps):
    eng = engine(params, prefix_partial=True)
    first = eng.submit(ps[0], 12)
    got = [eng.run()[first]]
    uids = [eng.submit(pr, 12) for pr in ps[1:]]
    out = eng.run()
    assert eng.prefill_tokens_saved >= 32
    return got + [out[u] for u in uids]


def _speculate(params, ps):
    eng = engine(params, speculate=2, prefix_cache=False)
    uids = [eng.submit(pr, 12) for pr in ps]
    out = eng.run()
    # a verify dispatch's counters are summed over its sub-steps
    assert eng.flight[-1]["expert_rows"] % (3 * TOP_K) == 0
    return [out[u] for u in uids]


def _spill(params, ps):
    eng = engine(params, spill_blocks=4)
    uids = [eng.submit(pr, 12) for pr in ps]
    out = eng.run()
    return [out[u] for u in uids]


def _handoff(params, ps):
    """Every sequence exported mid-decode as a block document (the
    zero-lane ``v`` side rides along) and finished on another engine."""
    got = []
    for pr in ps:
        a = engine(params, prefix_cache=False)
        u = a.submit(pr, 12)
        for _ in range(5):
            a.step()
        b = engine(params, prefix_cache=False)
        b.import_sequence(a.export_sequence(u))
        got.append(b.run()[u])
    return got


def _snapshot(params, ps):
    from distributed_llm_code_samples_tpu.decode.supervise import (
        restore_engine_state, snapshot_state)
    a = engine(params, prefix_cache=False)
    uids = [a.submit(pr, 12) for pr in ps]
    for _ in range(5):
        a.step()
    b = engine(params, prefix_cache=False)
    restore_engine_state(b, snapshot_state(a))
    out = b.run()
    return [out[u] for u in uids]


WORKS = {"prefix": _prefix, "prefix_partial": _partial,
         "speculate": _speculate, "spill": _spill, "handoff": _handoff,
         "snapshot": _snapshot}


@pytest.mark.parametrize("what", sorted(WORKS))
def test_what_moves_blocks_works_on_latent_rows(weights, what):
    """Block tables, copy-on-write, the prefix cache and its partial
    hits, the spill tier, speculation's verify program, export / import
    and snapshot / resume move or rewrite blocks by their ids: they run
    on a pool of latent rows as they are, and the tokens are those of
    the plain engine."""
    _, params = weights
    shared = prompts_of([32], seed=9)[0]
    ps = [shared + [5, 6], shared + [7, 8, 9], prompts_of([21], seed=3)[0]]
    assert WORKS[what](params, ps) == _base(params, ps)


def _mesh():
    from distributed_llm_code_samples_tpu.parallel import (MODEL_AXIS,
                                                           make_mesh)
    return make_mesh({MODEL_AXIS: 2})


REFUSALS = {
    "int8": (lambda p: engine(p, kv_dtype="int8"), "latent row has none"),
    "tp": (lambda p: DecodeEngine(p, HEADS, EngineConfig(), mesh=_mesh()),
           "latent row has none"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_needs_kv_heads_refuses_in_one_line(weights, what):
    """Per-head int8 scales and a head-sharded pool need KV heads, and
    a latent row has none: one line, by what the model is."""
    _, params = weights
    make, why = REFUSALS[what]
    with pytest.raises(ValueError) as err:
        make(params)
    assert "\n" not in str(err.value) and why in str(err.value)


# -- the entry point --------------------------------------------------------------


def test_cli_and_library_build_the_same_engine(tmp_path, capsys, ref,
                                               driver):
    """``generate --model_config`` picks the family by ``model_type``
    and serves the model the one library function builds: the tokens of
    ``engine_from_config`` on the same seed, which are the
    reference's."""
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
    assert generate_main(["--model_config", str(path), "--prompt_lens", "5",
                          "--max_new", "2", "--tp", "2"]) == 2
    assert "latent row has none" in capsys.readouterr().err


def test_weights_come_in_the_type_the_config_states():
    """bfloat16 as served: every leaf but the router, which is float32
    whatever the type; the latent pool takes the cache's type; and what
    the file cannot serve is refused by name. (Served here over float32
    weights: this CPU backend has no bfloat16 x bfloat16 -> float32
    product at every shape; the chip's phase in ``chip_smoke.py`` and the
    benchmark's cell serve the bfloat16 ones.)"""
    bf16 = dict(TOY, precision={"weights": "bfloat16"})
    p = params_from_config(bf16, 1)
    kinds = {k: x.dtype for k, x in zip(
        p.experts._fields, p.experts)}
    assert kinds["w_router"] == kinds["bias"] == jnp.float32
    assert kinds["w_gate"] == p.wte.dtype == p.mla.w_uk.dtype == jnp.bfloat16
    eng = engine_from_config(TOY, seed=1, engine_config=EngineConfig(
        kv_dtype="bf16"))
    assert eng.pool.k.dtype == eng.pool.v.dtype == jnp.bfloat16
    assert eng.pool.k.shape[0] == 4 and eng.pool.v.size == 0
    assert eng.pool.latent_rank == 32 and eng.pool.kv_heads == 1
    eng.submit([1, 2, 3, 4, 5], 4)
    assert len(eng.run()[0]) == 9
    with pytest.raises(ValueError, match="serves 'glm4_moe_lite' only"):
        mla_moe_lm.spec_from_config(dict(TOY, model_type="jamba"))
    with pytest.raises(ValueError, match="served are"):
        params_from_config(dict(TOY, model_type="gpt2"))
    for key, bad in (("rope_scaling", {"factor": 2}), ("n_group", 2),
                     ("tie_word_embeddings", True), ("topk_method", "greedy"),
                     ("partial_rotary_factor", 0.5)):
        with pytest.raises(ValueError, match=key.split("_")[0]):
            mla_moe_lm.spec_from_config(dict(TOY, **{key: bad}))
