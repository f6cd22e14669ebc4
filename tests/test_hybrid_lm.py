"""The hybrid (Mamba-1 + attention) LM on the serving path, at toy size:
``models/hybrid_lm.py`` and ``ops/ssm.py`` through ``DecodeEngine``
against the plain reference ``benchmark/configs/jamba_lm_reference.py``
(float32 at ``highest``, a ``lax.scan`` over time, nothing from the
package).

The toy has the published model's shape in small: d 64, inner 128,
state 4, dt_rank 8, 6 layers with attention at ``i % 3 == 1``, 4 heads
over 1 KV head, V 96, float32. ``initializer_range`` 0.2: at d=64 the
published 0.02 leaves the logits ruled by the tied embedding's
self-product, and no layer would be tested.

Tolerance, everywhere below: ``TOL = 2e-4`` on logits whose spread
(standard deviation) is 1.6. Both sides are float32; they differ in the
order of their sums: the program multiplies a chunk of c rows or a
batch of b, the reference all T at once, and the CPU's matmul blocks
each differently. 1.8e-5 was read against the reference, 1.4e-5 between
chunks of 1 and of 32 (chunks of 2 to 16 were bit-identical); 2e-4
leaves room for another CPU's blocking and is several thousand times
below what dropping a layer, a tap or the state would move.
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
from distributed_llm_code_samples_tpu.decode.engine import ServePolicy
from distributed_llm_code_samples_tpu.decode.model_config import (
    engine_from_config, params_from_config)
from distributed_llm_code_samples_tpu.models import hybrid_lm, init_lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4

TOY = dict(model_type="jamba", hidden_size=64, intermediate_size=128,
           mamba_expand=2, mamba_d_state=4, mamba_d_conv=4,
           mamba_dt_rank=8, mamba_conv_bias=True, mamba_proj_bias=False,
           num_hidden_layers=6, attn_layer_period=3, attn_layer_offset=1,
           num_attention_heads=4, num_key_value_heads=1, vocab_size=96,
           rms_norm_eps=1e-6, max_position_embeddings=256, num_experts=1,
           hidden_act="silu", tie_word_embeddings=True,
           sliding_window=None, initializer_range=0.2)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load(os.path.join(ROOT, "benchmark", "configs",
                              "jamba_lm_reference.py"), "jamba_ref")


@pytest.fixture(scope="module")
def driver():
    return _load(os.path.join(ROOT, "benchmark", "configs",
                              "jamba_engine_driver.py"), "jamba_driver")


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
    return DecodeEngine(params, TOY["num_attention_heads"],
                        dataclasses.replace(cfg, **kw), policy=policy)


def prompts_of(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TOY["vocab_size"], n).tolist() for n in lens]


def cached_logits(eng, tokens, chunks, decode_from=None):
    """Logits ``[T, V]`` of one sequence through the engine's own
    program bodies and cache, in slot 1: the first ``decode_from``
    tokens prefilled in ``chunks``-sized pieces, the rest decoded one
    at a time. What the compiled programs compute before they pick.
    Returns ``(logits, recurrent state of the slot)``."""
    p, cfg = eng.params, eng.cfg
    slot = 1
    t = len(tokens)
    decode_from = t if decode_from is None else decode_from
    table = np.zeros(cfg.max_blocks_per_seq, np.int32)
    need = -(-t // cfg.block_size)
    table[:need] = 1 + np.arange(need)
    cache = eng._cache()
    rows, pos = [], 0
    bodies = {}                     # one jitted body a chunk size

    def head(x):
        return eng.programs.logits(p, x)

    while pos < decode_from:
        c = min(chunks, decode_from - pos)
        c = 1 << (c.bit_length() - 1)              # power-of-two chunks
        # the prefill body returns picks only: run its trunk as it does
        body = bodies.setdefault(c, _prefill_rows(eng, c))
        cache, x, _ = body(p, cache, jnp.asarray(table), jnp.int32(pos),
                           jnp.asarray(tokens[pos:pos + c], jnp.int32),
                           jnp.int32(slot))
        rows.append(head(x))
        pos += c
    body = _decode_rows(eng, 1)
    while pos < t:
        cache, x, _ = body(p, cache, jnp.asarray(table[None]),
                           jnp.asarray([pos], jnp.int32),
                           jnp.asarray(tokens[pos:pos + 1], jnp.int32),
                           jnp.asarray([slot], jnp.int32))
        rows.append(head(x))
        pos += 1
    state = cache[1]
    return (np.asarray(jnp.concatenate(rows, 0)),
            (np.asarray(state.conv[:, slot]), np.asarray(state.ssm[:, slot])))


def _prefill_rows(eng, c):
    """The prefill program up to the head, every row's hidden state
    returned (the program keeps the last row and picks from it)."""
    return jax.jit(lambda p, cache, table, pos0, toks, row:
                   eng.programs.prefill_hidden(c, p, cache, table, pos0,
                                               toks, row))


def _decode_rows(eng, b):
    return jax.jit(lambda p, cache, tables, lengths, toks, rows:
                   eng.programs.decode_hidden(b, p, cache, tables, lengths,
                                              toks, rows))


# -- (a) model against the reference, on logits ---------------------------


def test_prefill_then_decode_through_the_cache_is_the_reference(ref,
                                                                weights):
    """Prefill in chunks of 8, then decode token by token through the
    cache: every position's logits are the reference's one full
    forward's."""
    w, params = weights
    eng = engine(params)
    tokens = prompts_of([41], seed=3)[0]
    got, _ = cached_logits(eng, tokens, chunks=8, decode_from=24)
    want = np.asarray(ref.logits(w, np.asarray(tokens), TOY))
    assert want.std() > 1.0                 # the layers rule the logits
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_reference_lower_precision_modes_differ(ref, weights):
    """The controls ``correct`` has to refuse are not the reference."""
    w, _ = weights
    toks = np.asarray(prompts_of([24], seed=5)[0])
    f32 = np.asarray(ref.logits(w, toks, TOY))
    assert np.array_equal(f32, np.asarray(ref.logits(w, toks, TOY, "f32")))
    for mode in ("bf16", "int8"):
        low = np.asarray(ref.logits(w, toks, TOY, mode))
        assert np.abs(low - f32).max() > 50 * TOL, mode


def test_parameter_count_at_published_widths():
    """ISSUE 27's own count from the row's keys: 3.029 B, a Mamba mixer
    41.24 M, an attention mixer 13.76 M, an MLP 62.91 M (shapes only:
    nothing is allocated)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "jamba2-3b-serve.json")) as f:
        spec = hybrid_lm.spec_from_config(json.load(f))
    p = jax.eval_shape(lambda k: hybrid_lm.init_hybrid_lm(k, spec),
                       jax.random.PRNGKey(0))
    assert [i for i, k in enumerate(p.kinds) if k == "attn"] == [7, 21]
    per = lambda stack, n: sum(x.size for x in stack) // n
    assert round(per(p.mamba, 26) / 1e6, 2) == 41.24
    assert round(per(p.attn, 2) / 1e6, 2) == 13.76
    assert round(per(p.mlp, 28) / 1e6, 2) == 62.91
    assert round(p.num_params() / 1e9, 3) == 3.029


# -- (b) the state and the logits do not depend on the chunking -----------


@pytest.fixture(scope="module")
def one_chunk(weights):
    _, params = weights
    tokens = prompts_of([32], seed=7)[0]
    return tokens, cached_logits(engine(params, chunk=32), tokens, 32)


@pytest.mark.parametrize("chunk", [1, 2, 4, 8, 16])
def test_state_and_logits_do_not_depend_on_the_chunking(weights, one_chunk,
                                                        chunk):
    """A 32-token prompt prefilled in chunks of 1, 2, 4, 8 or 16 leaves
    the state, and gives the logits, of one chunk of 32."""
    _, params = weights
    tokens, (want, (conv, ssm)) = one_chunk
    got, (conv_c, ssm_c) = cached_logits(engine(params, chunk=chunk),
                                         tokens, chunk)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert np.abs(ssm).max() > 0.1 and np.abs(conv).max() > 0.1
    np.testing.assert_allclose(ssm_c, ssm, atol=TOL, rtol=0)
    np.testing.assert_allclose(conv_c, conv, atol=TOL, rtol=0)


# -- (c) through DecodeEngine: greedy tokens against the argmax -----------


def assert_greedy_matches(ref, w, full, plen):
    """The engine returns picks only. A served token has to be the
    reference's argmax wherever the reference's top two logits lie
    more than ``2 * TOL`` apart (each may be off by ``TOL``); a nearer
    tie may go either way, and then the sequences part, so the
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
    running decodes."""
    w, params = weights
    eng = engine(params, slots=3, mbps=8, chunk=8)
    ps = prompts_of([5, 37, 11, 30, 7, 64, 2])
    uids = [eng.submit(pr, 12 + 3 * i) for i, pr in enumerate(ps)]
    out = eng.run()
    assert not eng.failed and eng.prefix is None
    for u, pr in zip(uids, ps):
        assert len(out[u]) == len(pr) + 12 + 3 * uids.index(u)
        assert_greedy_matches(ref, w, out[u], len(pr))
    last = eng.flight[-1]
    assert last["state_bytes"] == eng.state.bytes_per_slot
    assert last["state_slots"] == 0


# -- (d) a reused slot serves as a fresh engine does -----------------------


def test_a_reused_slot_starts_from_a_zero_state(weights):
    """One slot, three requests one after another: each finds the row
    its predecessor left, and is served as by an engine that never
    held another."""
    _, params = weights
    ps = prompts_of([21, 9, 33], seed=2)
    eng = engine(params, slots=1, mbps=8)
    uids = [eng.submit(pr, 10) for pr in ps]
    out = eng.run()
    assert np.abs(np.asarray(eng.state.ssm[:, 0])).max() > 0
    for u, pr in zip(uids, ps):
        fresh = engine(params, slots=1, mbps=8)
        fresh.submit(pr, 10, uid=u)
        assert fresh.run()[u] == out[u]


# -- (e) preemption and replay ---------------------------------------------


def test_preemption_replays_from_a_zero_state(weights):
    """A pool too small for both requests: the younger is evicted back
    to WAITING, re-prefilled from position 0 (a zero state, whatever
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


# -- (f) what cannot carry the state refuses, in one line -------------------


def _export(eng):
    eng.submit([1, 2, 3], 4)
    eng.step()
    eng.export_sequence(0)


def _snapshot(eng):
    from distributed_llm_code_samples_tpu.decode.supervise import (
        snapshot_state)
    snapshot_state(eng)


def _restore(eng):
    from distributed_llm_code_samples_tpu.decode.supervise import (
        restore_engine_state)
    restore_engine_state(eng, {})


def _mesh():
    from distributed_llm_code_samples_tpu.parallel import (MODEL_AXIS,
                                                           make_mesh)
    return make_mesh({MODEL_AXIS: 2})


REFUSALS = {
    "speculate": lambda p: engine(p, speculate=2),
    "tp": lambda p: DecodeEngine(p, 4, EngineConfig(), mesh=_mesh()),
    "spill": lambda p: engine(p, spill_blocks=4),
    "prefix_partial": lambda p: engine(p, prefix_partial=True),
    "export": lambda p: _export(engine(p)),
    "import": lambda p: engine(p).import_sequence({}),
    "snapshot": lambda p: _snapshot(engine(p)),
    "resume": lambda p: _restore(engine(p)),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_cannot_carry_the_state_refuses_in_one_line(weights, what):
    _, params = weights
    with pytest.raises(ValueError) as err:
        REFUSALS[what](params)
    msg = str(err.value)
    assert "\n" not in msg and "mamba layers" in msg
    assert "recurrent state" in msg


def test_prefix_hits_are_never_taken(weights):
    """Two requests share a 32-token prefix, the cache flag at its
    default (on): a model with recurrent layers builds no cache, takes
    no hit and inserts no block, and the second request prefills all
    of its prompt."""
    _, params = weights
    eng = engine(params, slots=2, mbps=8)
    assert eng.cfg.prefix_cache and eng.prefix is None
    shared = prompts_of([32], seed=9)[0]
    for tail in ([5, 6], [7, 8, 9]):
        eng.submit(shared + tail, 4)
    eng.run()
    assert eng.prefix_hit_blocks == 0 and eng.prefill_tokens_saved == 0
    # 34 = 16+16+2 and 35 = 16+16+2+1 tokens, nothing skipped
    assert eng.prefill_dispatches == 3 + 4
    lm = DecodeEngine(init_lm(jax.random.PRNGKey(0), 96, 32, 2, 64,
                              n_heads=4), 4, EngineConfig())
    assert lm.prefix is not None and lm.recurrent == [] and lm.state is None


def test_cli_refuses_fleet_and_snapshot_for_a_recurrent_model(tmp_path,
                                                              capsys):
    from distributed_llm_code_samples_tpu.decode.generate_cli import (
        generate_main)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(TOY))
    base = ["--model_config", str(path), "--prompt_lens", "5,9",
            "--max_new", "4"]
    for more in (["--fleet", "2"], ["--snapshot_dir", str(tmp_path / "s")],
                 ["--tp", "2"], ["--speculate", "2"]):
        assert generate_main(base + more) == 2
        err = capsys.readouterr().err.strip().splitlines()[-1]
        assert err.startswith("error:") and "mamba layers" in err


# -- the entry point ---------------------------------------------------------


def test_cli_and_library_build_the_same_engine(tmp_path, capsys, ref,
                                               driver):
    """``generate --model_config`` serves the model the one library
    function builds: the tokens of ``engine_from_config`` on the same
    seed, which are the reference's."""
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


def test_weights_come_in_the_type_the_config_states():
    bf16 = dict(TOY, precision={"weights": "bfloat16"})
    p = params_from_config(bf16, 1)
    assert {x.dtype for x in jax.tree_util.tree_leaves(p)} == {
        jnp.dtype(jnp.bfloat16)}
    eng = engine_from_config(bf16, p, engine_config=EngineConfig(
        kv_dtype="bf16"))
    assert eng.state.ssm.dtype == eng.state.conv.dtype == jnp.float32
    assert eng.pool.k.dtype == jnp.bfloat16 and eng.pool.k.shape[0] == 2
    eng.submit([1, 2, 3, 4, 5], 4)
    assert len(eng.run()[0]) == 9
    with pytest.raises(ValueError, match="serves 'jamba' only"):
        hybrid_lm.spec_from_config(dict(TOY, model_type="gpt2"))


# -- (g) the step programs are the inline references -----------------------
#
# What ``decode/programs.py`` builds from the model face lowers, op for
# op, to the programs written around each family's weights by name: the
# GPT-2 block as the engine inlined it before PR 27, the hybrid block as
# PR 27 inlined it, with the Megatron collectives where a mesh is set.
# The references live here, plain; the builder's own ``_embed``,
# ``_trunk`` and ``logits`` are stood in for by them, and the cache
# writes and reads, the head's poison / pick / flags and the wrapping
# stay the builder's on both sides.


def _rot(q, positions):
    from distributed_llm_code_samples_tpu.models.attention import rope
    return jax.vmap(lambda x, pos: rope(x[:, None, :],
                                        pos[None])[:, 0, :])(q, positions)


_held = jax.lax.optimization_barrier


class InlineGPT2:
    """``LMParams`` served as the engine wrote it before any seam."""

    def _embed(self, p, tokens, positions):
        from distributed_llm_code_samples_tpu.parallel.lm import vp_embed
        rows = (p.wte[tokens] if self.mesh is None
                else vp_embed(p.wte, tokens))
        return rows + p.wpe[positions]

    def _trunk(self, p, pool, x, positions, write_attn, mix=None,
               write_window=None, write_chunked=None):
        from distributed_llm_code_samples_tpu.ops.norm import layernorm
        from distributed_llm_code_samples_tpu.parallel.collectives import (
            all_reduce)
        from distributed_llm_code_samples_tpu.parallel.mesh import MODEL_AXIS
        reduce = ((lambda y: y) if self.mesh is None
                  else (lambda y: all_reduce(y, MODEL_AXIS)))
        blk = p.blocks
        n = x.shape[0]
        for l in range(p.n_layers):
            a = layernorm(blk.ln1[l], x)
            # the projections one at a time: each weight is sliced where
            # it is used, each product held as written before its heads
            # are split (``face.mm_held``, PR 51)
            dh = self.spec.head_dim
            q = _held(a @ blk.wq[l].T).reshape(-1, blk.wq.shape[1] // dh, dh)
            k = _held(a @ blk.wk[l].T).reshape(-1, blk.wk.shape[1] // dh, dh)
            v = _held(a @ blk.wv[l].T).reshape(-1, blk.wv.shape[1] // dh, dh)
            if self.cfg.use_rope:
                q, k = _rot(q, positions), _rot(k, positions)
            pool, y = write_attn(l, pool, q, k, v)
            x = x + reduce(y.reshape(n, -1) @ blk.wo[l].T)
            h = layernorm(blk.ln2[l], x)
            x = x + reduce(jnp.maximum(h @ blk.w1[l].T, 0.0) @ blk.w2[l].T)
        return pool, x, None

    def logits(self, p, x):
        from distributed_llm_code_samples_tpu.ops.norm import layernorm
        from distributed_llm_code_samples_tpu.parallel.collectives import (
            all_gather)
        from distributed_llm_code_samples_tpu.parallel.mesh import MODEL_AXIS
        logits = layernorm(p.ln_f, x) @ p.wte.T
        if self.mesh is not None:
            logits = all_gather(logits, MODEL_AXIS, dim=1)
        return logits


class InlineHybrid:
    """``HybridLMParams`` as PR 27 inlined it (float32 weights: every
    product is ``x @ w.T``); a recurrent layer's cache closure stays the
    builder's and reaches the mixer (``hybrid_lm._mamba``) as it did."""

    def _embed(self, p, tokens, positions):
        return p.wte[tokens].astype(jnp.float32)

    def _trunk(self, p, cache, x, positions, write_attn, mix=None,
               write_window=None, write_chunked=None):
        pool, state = cache
        n = x.shape[0]
        dh = p.head_dim
        for l, (kind, i) in enumerate(p.layers):
            a = hybrid_lm.rmsnorm(p.norm_in[l], x, p.eps)
            if kind == "attn":
                t = p.attn
                q = _held(a @ t.wq[i].T).reshape(-1, t.wq.shape[1] // dh, dh)
                k = _held(a @ t.wk[i].T).reshape(-1, t.wk.shape[1] // dh, dh)
                v = _held(a @ t.wv[i].T).reshape(-1, t.wv.shape[1] // dh, dh)
                pool, y = write_attn(i, pool, q, k, v)
                y = y.reshape(n, -1) @ t.wo[i].T
            else:
                state, y = mix(i, state, a)
            x = x + y
            h = hybrid_lm.rmsnorm(p.norm_ff[l], x, p.eps)
            gate = h @ p.mlp.w_gate[l].T
            x = x + (jax.nn.silu(gate) * (h @ p.mlp.w_up[l].T)
                     ) @ p.mlp.w_down[l].T
        return (pool, state), x, None

    def logits(self, p, x):
        return hybrid_lm.rmsnorm(p.ln_f, x, p.eps) @ p.wte.T


def _gpt2(**kw):
    return init_lm(jax.random.PRNGKey(0), 96, 32, 2, 64, n_heads=4, **kw)


def _mesh4():
    from distributed_llm_code_samples_tpu.parallel import (MODEL_AXIS,
                                                           make_mesh)
    return make_mesh({MODEL_AXIS: 4})


# case -> (inline reference, params, EngineConfig fields, mesh, kind)
PROGRAMS = {
    "gpt2-decode": (InlineGPT2, _gpt2, {}, None, "decode"),
    "gpt2-prefill": (InlineGPT2, _gpt2, {}, None, "prefill"),
    "gpt2-verify": (InlineGPT2, _gpt2, {"speculate": 2}, None, "verify"),
    "gpt2-rope-gqa-decode": (InlineGPT2, lambda: _gpt2(n_kv_heads=2),
                             {"use_rope": True, "kv_dtype": "bf16"}, None,
                             "decode"),
    "gpt2-int8-decode": (InlineGPT2, _gpt2, {"kv_dtype": "int8"}, None,
                         "decode"),
    "gpt2-tp4-decode": (InlineGPT2, _gpt2, {}, _mesh4, "decode"),
    "gpt2-tp4-prefill": (InlineGPT2, _gpt2, {}, _mesh4, "prefill"),
    "hybrid-decode": (InlineHybrid, lambda: params_from_config(TOY, 1), {},
                      None, "decode"),
    "hybrid-prefill": (InlineHybrid, lambda: params_from_config(TOY, 1),
                       {}, None, "prefill"),
}


@pytest.mark.parametrize("case", sorted(PROGRAMS))
def test_step_programs_are_the_inline_reference(monkeypatch, case):
    """Same operands, same StableHLO: for both families, every kind of
    step program, under the mesh and for verify."""
    from distributed_llm_code_samples_tpu.decode.programs import (
        StepPrograms)
    inline, make, fields, mesh, kind = PROGRAMS[case]
    params = make()
    cfg = EngineConfig(max_slots=2, n_blocks=9, max_blocks_per_seq=4,
                       **fields)
    bucket = 16 if kind == "prefill" else 2

    def lowered():
        eng = DecodeEngine(params, 4, cfg, mesh=mesh and mesh())
        wire = eng.programs.wire(kind, bucket)
        operand = wire.pack(**{name: np.zeros(shape, np.int32) for
                               name, (_, _, shape) in wire.fields.items()})
        return eng._program(kind, bucket).lower(
            eng.params, eng._carry(), operand).as_text()

    built = lowered()
    for name in ("_embed", "_trunk", "logits"):
        monkeypatch.setattr(StepPrograms, name, getattr(inline, name))
    assert lowered() == built
