"""The window-and-full-attention, sink-softmax, sparse-expert LM whose K
and V rows differ in width on the serving path, at toy size:
``models/mimo_v2_flash_lm.py`` through ``DecodeEngine`` against the plain
reference ``benchmark/configs/mimo_v2_flash_lm_reference.py`` (float32 at
``highest``, the whole forward pass over one sequence with the two
masks and the sink written as a term of the denominator, nothing from
the package). The first family whose two pools have ROWS OF THEIR OWN:
the full layers' store ``2 KV heads x (24 + 16)`` lanes a position, the
window layers' ``4 x (24 + 16)`` (``models/face.py::KVRow``).

The toy has the published model's shape in small: d 64, 7 layers
``full, sliding x 3, full, sliding x 2`` (the first with the dense MLP
of 160, the others with experts), 8 query heads over 2 KV heads on a
full layer and 4 on a sliding one, a key head of 24 lanes beside a value
head of 16, rotary on the first ``int(24 x 0.334)`` = 8 lanes at theta
5e6 (full) and 1e4 (sliding), a window of 16 positions with a sink a
head, values scaled by 0.707, a sigmoid router over 16 experts of 48
with the top 4 by score + bias of which this "chip" holds experts 4 to
11, no shared expert, V 96, float32. ``initializer_range`` 0.2: at d=64
the published 0.02 leaves the blocks' outputs too small for a dropped
one to show; the seeded choice bias (0.01 x normal) is multiplied by 30
for the same reason.

Tolerance, everywhere below: ``TOL = 2e-4`` on logits whose spread
(standard deviation) is over 1. Both sides are float32 and differ in
the order of their sums (a chunk of c rows or a batch of b against all T
at once, the stored rows' two products against per-head attention, the
walk's online softmax that STARTS at the sink against a softmax with one
more term); 3e-5 was read. A program without the sink, with a sink on
the full layers too, without the value scale, with a window of 17, with
the two thetas swapped, rotating all 24 lanes, weighing by score + bias
or not normalising the chosen scores each reads 50 times the tolerance
or more (``test_a_fault_*``).
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
from distributed_llm_code_samples_tpu.decode.engine import ROW_BYTES
from distributed_llm_code_samples_tpu.decode.model_config import (
    engine_from_config)
from distributed_llm_code_samples_tpu.models import mimo_v2_flash_lm as mimo
from distributed_llm_code_samples_tpu.models.face import KVRow
from distributed_llm_code_samples_tpu.ops import moe_serve
from test_laguna_lm import cached_logits

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
WINDOW = 16

TOY = dict(model_type="mimo_v2_flash", hidden_size=64, intermediate_size=160,
           moe_intermediate_size=48, num_attention_heads=8,
           swa_num_attention_heads=8, num_key_value_heads=2,
           swa_num_key_value_heads=4, head_dim=24, swa_head_dim=24,
           v_head_dim=16, swa_v_head_dim=16, n_routed_experts=8,
           router_experts=16, expert_first=4, num_experts_per_tok=4,
           num_hidden_layers=7, hybrid_layer_pattern=[0, 1, 1, 1, 0, 1, 1],
           moe_layer_freq=[0, 1, 1, 1, 1, 1, 1], sliding_window=WINDOW,
           sliding_window_size=WINDOW, attention_chunk_size=WINDOW,
           partial_rotary_factor=0.334, rope_theta=5000000,
           swa_rope_theta=10000, attention_value_scale=0.707,
           add_swa_attention_sink_bias=True,
           add_full_attention_sink_bias=False, layernorm_epsilon=1e-5,
           norm_topk_prob=True, scoring_func="sigmoid",
           topk_method="noaux_tc", n_group=1, topk_group=1,
           routed_scaling_factor=None, n_shared_experts=None,
           tie_word_embeddings=False, attention_bias=False,
           hidden_act="silu", vocab_size=96, max_position_embeddings=256,
           initializer_range=0.2)
HEADS, HELD, TOP_K, EXPERT_LAYERS = 8, 8, 4, 6


def _load(name):
    path = os.path.join(ROOT, "benchmark", "configs", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("mimo_v2_flash_lm_reference")


@pytest.fixture(scope="module")
def driver():
    return _load("mimo_v2_flash_engine_driver")


def _weights(driver, config, seed):
    w = driver.make_weights(config, seed)
    w["experts.bias"] = w["experts.bias"] * 30.0    # the docstring says why
    return w, driver._params(config, w)


@pytest.fixture(scope="module")
def weights(driver):
    """The benchmark driver's named leaves and the params the engine
    takes, of one seed: the reference and the program get one set of
    arrays."""
    return _weights(driver, TOY, 11)


def engine(params, slots=3, mbps=8, chunk=16, block=16, **kw):
    cfg = EngineConfig(max_slots=slots, n_blocks=1 + slots * mbps,
                       max_blocks_per_seq=mbps, prefill_chunk=chunk,
                       block_size=block)
    return DecodeEngine(params, HEADS, dataclasses.replace(cfg, **kw))


def prompts_of(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TOY["vocab_size"], n).tolist() for n in lens]


# -- (a) prefill + decode through both pools is the full forward ------------


@pytest.mark.parametrize("block,chunk,chunks,decode_from", [
    (16, 16, 16, 40), (16, 16, 8, 21), (4, 8, 8, 40), (8, 16, 16, 48)])
def test_prefill_then_decode_through_both_pools_is_the_reference(
        ref, weights, block, chunk, chunks, decode_from):
    """72 tokens, four and a half windows, so the ring wraps more than
    once: prefilled in chunks whose rows straddle the window's edge
    (each row of a chunk has its own window start, most of them inside a
    block), then decoded one at a time through the walk, whose softmax
    starts at the sink, every position's logits are the reference's full
    forward with both masks — by LOGITS, over pools whose K rows are 48
    and 96 lanes and whose V rows are 32 and 64."""
    w, params = weights
    tokens = prompts_of([72], seed=1)[0]
    eng = engine(params, mbps=72 // block + 1, chunk=chunk, block=block)
    assert eng.programs.window_blocks == (
        WINDOW // block + max(1, chunk // block) + 1)
    assert eng.pool.row == KVRow(2, 24, 16)
    assert eng.wpool.row == KVRow(4, 24, 16)
    got, counts = cached_logits(eng, tokens, chunks, decode_from)
    want = np.asarray(ref.logits(w, np.asarray(tokens), TOY))
    assert want.std() > 1.0
    assert np.abs(got - want).max() < TOL
    assert all(c.shape == (EXPERT_LAYERS, HELD) for c in counts)


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


def _route_with(weigh):
    """``moe_serve.route`` with the chosen experts' weights computed by
    ``weigh(scores, biased scores, idx)``."""
    def route(a, w_router, bias, top_k, scale, score="sigmoid"):
        s = moe_serve.SCORES[score](jnp.matmul(
            a.astype(jnp.float32), w_router.astype(jnp.float32).T,
            precision=moe_serve.HI))
        sb = s + bias.astype(jnp.float32)
        _, idx = jax.lax.top_k(sb, top_k)
        return idx, scale * weigh(jnp.take_along_axis(s, idx, -1),
                                  jnp.take_along_axis(sb, idx, -1))
    return route


FAULTS = {
    "no_sink": None,
    "a_sink_on_full_layers_too": None,
    "value_scale_dropped": lambda p: dataclasses.replace(p, value_scale=1.0),
    "window_off_by_one": lambda p: dataclasses.replace(
        p, sliding_window=WINDOW + 1),
    "thetas_swapped": lambda p: dataclasses.replace(
        p, rot_full=p.rot_window, rot_window=p.rot_full),
    "rotary_on_all_lanes": lambda p: dataclasses.replace(
        p, rot_full=p.rot_full._replace(partial=1.0),
        rot_window=p.rot_window._replace(partial=1.0)),
    "choice_bias_in_the_weights": _route_with(
        lambda s, sb: sb / jnp.sum(sb, -1, keepdims=True)),
    "chosen_scores_not_normalised": _route_with(lambda s, sb: s),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_one_mechanism_fails_the_tolerance(monkeypatch, ref,
                                                      weights, fault):
    """Each of the block's mechanisms rules the logits: a program that
    leaves the sink out of the window layers' softmax, puts one into the
    full layers' too, drops the value scale, reads a window of 17, swaps
    the two rotary bases, rotates all 24 lanes, lets the choice bias
    into the weights or does not normalise the chosen scores lies 50
    times the tolerance from the reference or further."""
    w, params = weights
    if fault == "no_sink":
        monkeypatch.setattr(mimo.MimoV2FlashLMParams, "window_sink",
                            lambda self, i: None)
    elif fault == "a_sink_on_full_layers_too":
        sink = params.sinks[0].astype(jnp.float32)
        for name in ("stored_decode_attn", "gathered_chunk_attn"):
            def sunk(*a, _fn=getattr(programs, name), **kw):
                return _fn(*a, **dict({"sink": sink}, **kw))
            monkeypatch.setattr(programs, name, sunk)
    elif fault.startswith("cho"):
        monkeypatch.setattr(moe_serve, "route", FAULTS[fault])
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


def test_the_sink_takes_mass_and_has_no_value(ref, weights):
    """What the reference's window layer computes, against the formula
    written out in NumPy for one head: ``p_j = exp(s_j - m) / (exp(sink -
    m) + sum exp(s_j' - m))`` over the keys ``p - 15 .. p``, and the
    seeded sinks (``ln 32 + 0.5 x normal``) hold a real share of the
    mass at the toy's scores."""
    w, params = weights
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    stack = {k: w["window." + k][1] for k in ref.ATTN}
    freqs = jnp.asarray(ref.inv_freq(10000, 24, 0.334))
    assert freqs.shape == (4,)
    got = np.asarray(ref._attn(a, *stack.values(), w["sinks"][1], freqs,
                               dk=24, dv=16, window=WINDOW, v_scale=0.707,
                               mode="f32"))
    a64 = np.asarray(a, np.float64)
    q, k, v = (a64 @ np.asarray(stack[n], np.float64).T
               for n in ("wq", "wk", "wv"))

    def rot(x, heads):
        x = x.reshape(40, heads, 24).copy()
        ang = np.arange(40)[:, None] * np.asarray(freqs, np.float64)
        x1, x2 = x[..., :4].copy(), x[..., 4:8].copy()
        c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
        x[..., :4], x[..., 4:8] = x1 * c - x2 * s, x1 * s + x2 * c
        return x

    q, k, v = rot(q, 8), rot(k, 4), 0.707 * v.reshape(40, 4, 16)
    sinks = np.asarray(w["sinks"][1], np.float64)
    out, mass = np.zeros((40, 8, 16)), []
    for h in range(8):
        for p in range(40):
            lo = max(0, p - WINDOW + 1)
            s = k[lo:p + 1, h // 2] @ q[p, h] / np.sqrt(24)
            m = max(s.max(), sinks[h])
            e = np.exp(s - m)
            total = e.sum() + np.exp(sinks[h] - m)
            out[p, h] = (e / total) @ v[lo:p + 1, h // 2]
            mass.append(np.exp(sinks[h] - m) / total)
    want = out.reshape(40, -1) @ np.asarray(stack["wo"], np.float64).T
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    assert 0.15 < np.mean(mass[-8:]) and np.mean(mass) < 0.9
    assert abs(float(np.mean(np.asarray(params.sinks))) - np.log(32)) < 0.3


# -- (b) the chip's share of the experts ------------------------------------


def test_the_sixteen_shares_add_up_to_the_uncut_layer(ref, driver):
    """Expert parallelism's contract (``ops/moe_serve.py``): sixteen
    holders, each told its own range of the 16 experts (``16 i .. 16 i +
    15`` of 256 at the published size; one expert each here), compute
    their ranges' parts of an expert layer through the PROGRAM's expert
    layer; the parts add up to the uncut reference's layer (there is no
    shared expert to count once), and the holders' counters are the rows
    THEIR experts got: ``rows x top_k`` in all."""
    uncut = dict(TOY, n_routed_experts=16, expert_first=0)
    w, p = _weights(driver, uncut, 7)
    a = jax.random.normal(jax.random.PRNGKey(3), (9, 64), jnp.float32)
    for l in (1, 5):
        want = np.asarray(ref._experts(w, l - 1, a, uncut, jnp.float32,
                                       "f32"))
        total, rows = np.zeros_like(want), []
        for first in range(16):
            part, got = mimo.holder(p, first, 1).ffn_counted(l, a)
            total += np.asarray(part)
            rows.append(np.asarray(got))
        assert np.abs(total - want).max() < TOL / 4
        assert np.abs(want).max() > 0.1
        assert np.concatenate(rows).sum() == 9 * TOP_K
    # the dense layer routes nothing
    assert p.ffn_counted(0, a)[1] is None


def test_parameter_and_byte_counts_at_published_widths():
    """The configuration file's arithmetic is the program's, from the
    arrays' shapes (nothing is allocated): published layers 0 to 10 of
    MiMo-V2-Flash as one of sixteen chips holds them, and the two pools
    in the rows the model states."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mimo-v2-flash-serve.json")) as f:
        config = json.load(f)
    spec = mimo.spec_from_config(config)
    p = jax.eval_shape(lambda k: mimo.init_mimo_v2_flash_lm(
        k, spec, dtype=jnp.bfloat16), jax.random.PRNGKey(0))
    size = lambda st: sum(x.size for x in st if x is not None)
    assert p.num_params() == 5_422_283_840
    assert sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(p)) == 10_865_544_320
    assert size(p.full) == 2 * 89_128_960
    assert size(p.window) + p.sinks.size == 9 * 94_371_904
    assert p.sinks.shape == (9, 64) and p.sinks.dtype == jnp.bfloat16
    assert size(p.dense) == 201_326_592
    assert size(p.experts) == 4_026_531_840 + 10_488_320
    assert p.experts.w_router.shape == (10, 256, 4096)
    assert p.experts.bias.shape == (10, 256)
    assert p.experts.bias.dtype == p.experts.w_router.dtype == jnp.float32
    assert p.experts.w_gate.shape == (10, 16, 2048, 4096)
    assert p.full.wk.shape == (2, 768, 4096)
    assert p.full.wv.shape == (2, 512, 4096)
    assert p.window.wk.shape == (9, 1536, 4096)
    assert p.window.wv.shape == (9, 1024, 4096)
    assert p.full.wo.shape == (2, 4096, 8192)
    assert p.wte.shape == p.w_head.shape == (19_072, 4096)
    assert [k for k in p.kinds] == ["attn"] + ["window"] * 4 + [
        "attn"] + ["window"] * 5
    assert p.rot_full.rot_dim(192) == p.rot_window.rot_dim(192) == 64
    assert (p.rot_full.theta, p.rot_window.theta) == (5e6, 1e4)
    cs = p.cache_spec(64)
    assert (cs.kv_layers, cs.win_layers, cs.window) == (2, 9, 128)
    assert cs.row == KVRow(4, 192, 128)
    assert cs.window_row == KVRow(8, 192, 128)
    assert (cs.expert_layers, cs.n_experts) == (10, 16)
    note = config["serving"]["note"]
    assert "5,422,283,840" in note and "10,865,544,320" in note
    assert "1,006,714,880" in note and "472,596,480" in note
    assert "12,344,855,680" in note
    assert config["published"]["n_routed_experts"] == 256
    assert config["reduced"] == [
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size", "serving"]
    # every published number of the catalog's row is in the file, equal
    # unless ``reduced`` names it (the row is not in the repo: its
    # numbers are)
    published = dict(hidden_size=4096, intermediate_size=16384,
                     moe_intermediate_size=2048, num_attention_heads=64,
                     num_key_value_heads=4, swa_num_key_value_heads=8,
                     head_dim=192, v_head_dim=128, swa_head_dim=192,
                     swa_v_head_dim=128, num_experts_per_tok=8,
                     sliding_window=128, partial_rotary_factor=0.334,
                     attention_value_scale=0.707, rope_theta=5000000,
                     swa_rope_theta=10000, layernorm_epsilon=1e-5,
                     max_position_embeddings=262144)
    assert {k: config[k] for k in published} == published
    # the cell's pools, as its driver builds them: 64 rings of 10
    from distributed_llm_code_samples_tpu.decode.paged import (
        kv_bytes_per_token)
    from distributed_llm_code_samples_tpu.decode.programs import StepPrograms
    cfg = _load("mimo_v2_flash_engine_driver").engine_config(config)
    progs = StepPrograms(cfg, cs, p.vocab)
    assert progs.window_blocks == 10
    pool = jax.eval_shape(lambda: progs.init_cache()[0])
    wpool = jax.eval_shape(progs.init_window)
    assert pool.k.shape == (2, 12_289, 16, 768)
    assert pool.v.shape == (2, 12_289, 16, 512)
    assert wpool.k.shape == (9, 641, 16, 1536)
    assert wpool.v.shape == (9, 641, 16, 1024)
    nbytes = lambda x: x.size * x.dtype.itemsize
    assert nbytes(pool.k) + nbytes(pool.v) == 1_006_714_880
    assert nbytes(wpool.k) + nbytes(wpool.v) == 472_596_480
    assert 10_865_544_320 + 1_006_714_880 + 472_596_480 == 12_344_855_680
    assert kv_bytes_per_token("bf16", 1, 4, 192, v_head_dim=128) == 2_560
    assert kv_bytes_per_token("bf16", 1, 8, 192, v_head_dim=128) == 5_120
    assert nbytes(pool.k) + nbytes(pool.v) == 2 * 2_560 * 12_289 * 16


# -- (c) the engine: two pools with rows of their own -----------------------


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
    served token is the reference's, both free lists come back whole,
    and the engine says each store's bytes a position from its arrays."""
    w, params = weights
    eng = engine(params, slots=3, mbps=6)
    ring = eng.programs.window_blocks
    assert ring == 3 and eng.wpool.n_blocks == 1 + 3 * ring
    assert eng.row_bytes == dict(zip(ROW_BYTES, (2 * 40 * 4, 4 * 40 * 4)))
    assert eng._kv_bytes_per_token() == 2 * 2 * 40 * 4
    prompts = prompts_of([5, 19, 33, 8], seed=4)
    uids = [eng.submit(p, 60) for p in prompts]
    while eng.active or eng.waiting:
        eng.step()
        held = [len(s.wblocks) for s in eng.slots if s is not None]
        assert all(n <= ring for n in held)
        assert len(eng.free_wblocks) + sum(held) == 3 * ring
    for uid, p in zip(uids, prompts):
        full = eng.finished[uid]
        assert len(full) == len(p) + 60
        assert_served_is_the_references(ref, w, full, len(p))
    assert sorted(eng.free_wblocks) == list(range(1, 1 + 3 * ring))
    assert len(eng.free_blocks) == eng.cfg.n_blocks - 1
    report = eng.decode_static_report()
    assert report["kv_pool_bytes"] == report["kv_pool_bytes_predicted"]
    rec = eng.telemetry_record()
    assert (rec["kv_row_bytes"], rec["window_row_bytes"]) == (320, 640)


def _export(eng):
    eng.submit([1, 2, 3], 4)
    eng.step()
    eng.export_sequence(0)


def _mesh():
    from distributed_llm_code_samples_tpu.parallel import (MODEL_AXIS,
                                                           make_mesh)
    return make_mesh({MODEL_AXIS: 2})


REFUSALS = {
    "speculate": lambda p: engine(p, speculate=2),
    "tp": lambda p: DecodeEngine(p, HEADS, EngineConfig(), mesh=_mesh()),
    "spill": lambda p: engine(p, spill_blocks=4),
    "export": lambda p: _export(engine(p)),
    "import": lambda p: engine(p).import_sequence({}),
    "int8": lambda p: engine(p, kv_dtype="int8"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_still_refuses_for_a_window_model_refuses_here(weights, what):
    """ROADMAP M3: what moves a sequence by ONE table refuses this
    family in the same lines as the first window family, by what the
    model is and under no flag."""
    _, params = weights
    with pytest.raises(ValueError) as err:
        REFUSALS[what](params)
    msg = str(err.value)
    assert "\n" not in msg and "window layers" in msg
    assert engine(params).prefix is None


@pytest.mark.parametrize("key,value,says", [
    ("model_type", "laguna", "serves 'mimo_v2_flash' only"),
    ("attention_bias", True, "no projection has a bias"),
    ("hidden_act", "gelu", "'silu' only"),
    ("add_swa_attention_sink_bias", False, "with its sink only"),
    ("add_full_attention_sink_bias", True, "without a sink only"),
    ("scoring_func", "softmax", "'sigmoid' scores only"),
    ("topk_method", "greedy", "'noaux_tc' only"),
    ("n_group", 4, "one group"),
    ("topk_group", 2, "one group"),
    ("norm_topk_prob", False, "normalised only"),
    ("n_shared_experts", 1, "without a shared expert"),
    ("tie_word_embeddings", True, "untied only"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4}, "'default' only"),
    ("hybrid_layer_pattern", [0] * 7, "of each kind"),
    ("hybrid_layer_pattern", [0, 1, 2, 1, 0, 1, 1], r"\[2\]"),
    ("moe_layer_freq", [1] * 7, "one dense and one expert"),
    ("moe_layer_freq", [0, 1, 1, 3, 1, 1, 1], "moe_layer_freq"),
    ("num_hidden_layers", 6, "names 7 layers"),
    ("swa_num_attention_heads", 4, "query heads"),
    ("swa_head_dim", 32, "head widths"),
    ("swa_v_head_dim", 24, "head widths"),
    ("sliding_window_size", 32, "equal to sliding_window"),
])
def test_what_the_family_cannot_serve_is_refused_by_name(key, value, says):
    with pytest.raises(ValueError, match=says):
        mimo.spec_from_config(dict(TOY, **{key: value}))


def test_the_spec_reads_the_published_keys():
    s = mimo.spec_from_config(TOY)
    assert (s.kv_full, s.kv_window, s.head_dim, s.v_head_dim) == (2, 4, 24,
                                                                  16)
    assert s.dense_layers == (0,) and s.routed_scale == 1.0
    assert (s.n_routed, s.n_held, s.expert_first) == (16, 8, 4)
    assert s.rot_full.rot_dim(24) == 8 and s.value_scale == 0.707
    assert mimo.spec_from_config(
        dict(TOY, routed_scaling_factor=2.5)).routed_scale == 2.5


# -- the entry point ----------------------------------------------------------


def test_cli_and_library_build_the_same_engine(tmp_path, capsys, ref,
                                               driver):
    """``generate --model_config`` picks the family by ``model_type``
    and serves the model the one library function builds: the tokens of
    ``engine_from_config`` on the same seed, which are the reference's,
    past the window; what moves a sequence by one table refuses at the
    entry."""
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
    for more in (["--fleet", "2"], ["--tp", "2"], ["--speculate", "2"],
                 ["--kv_dtype", "int8"]):
        assert generate_main(base + more) == 2
        err = capsys.readouterr().err.strip().splitlines()[-1]
        assert err.startswith("error:") and "window layers" in err


def test_weights_come_in_the_type_the_config_states():
    """bfloat16 as served: every leaf but the router and its choice
    bias, which are float32 whatever the type; both pools take the
    cache's type, each in its own row."""
    bf16 = dict(TOY, precision={"weights": "bfloat16"})
    eng = engine_from_config(bf16, seed=1, engine_config=EngineConfig(
        max_slots=2, n_blocks=9, max_blocks_per_seq=4, kv_dtype="bf16"))
    p = eng.params
    f32 = (p.experts.w_router, p.experts.bias)
    assert all(x.dtype == jnp.float32 for x in f32)
    others = [x for x in jax.tree_util.tree_leaves(p)
              if not any(x is y for y in f32)]
    assert others and all(x.dtype == jnp.bfloat16 for x in others)
    assert eng.pool.k.dtype == eng.wpool.k.dtype == jnp.bfloat16
    assert eng.pool.k.shape[0] == 2 and eng.wpool.k.shape[0] == 5
    assert eng.pool.k.shape[-1] == 48 and eng.pool.v.shape[-1] == 32
    assert eng.wpool.k.shape[-1] == 96 and eng.wpool.v.shape[-1] == 64
