"""The seam between the serving engine and the model
(``models/face.py``): a family is one file, the scheduler and the
program builder know none.

- a THIRD family, defined here and nowhere in the package (RMSNorm, a
  gated SiLU MLP, grouped-query attention in every layer, no position
  of any kind), is served by an unmodified ``DecodeEngine`` and its
  greedy tokens are those of its own plain full-sequence forward;
- the engine sizes the pool and the recurrent state from the model's
  ``CacheSpec`` alone;
- ``decode/engine.py`` and ``decode/programs.py`` hold no ``isinstance``
  on a params class and import no family's arithmetic; ``models/*``
  import nothing from ``decode/`` or ``parallel/``;
- the cache-read fork is gone with its option (the ``kernel`` field of
  ``EngineConfig``, the flag of that name on ``generate``).
"""

import ast
import dataclasses
import functools
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_code_samples_tpu.decode import (DecodeEngine,
                                                     EngineConfig)
from distributed_llm_code_samples_tpu.decode.model_config import (
    params_from_config)
from distributed_llm_code_samples_tpu.models import init_lm
from distributed_llm_code_samples_tpu.models.face import (ATTN, LATENT,
                                                          CacheSpec,
                                                          StateRow)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "distributed_llm_code_samples_tpu")

# -- a third family, in this file alone -------------------------------------

V, D, L, H, HKV, DH, F = 80, 32, 3, 4, 2, 8, 48


def _rms(g, x):
    return g * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["wte", "norm_in", "norm_ff", "g_f", "wq", "wk", "wv", "wo",
                 "w_gate", "w_up", "w_down"],
    meta_fields=["max_seq_len"])
@dataclasses.dataclass(frozen=True)
class NopeParams:
    """Every matrix ``[L, out, in]``; the head is tied to ``wte``."""
    wte: jax.Array
    norm_in: jax.Array
    norm_ff: jax.Array
    g_f: jax.Array
    wq: jax.Array
    wk: jax.Array
    wv: jax.Array
    wo: jax.Array
    w_gate: jax.Array
    w_up: jax.Array
    w_down: jax.Array
    max_seq_len: int

    vocab = property(lambda self: self.wte.shape[0])
    d_model = property(lambda self: self.wte.shape[1])
    n_layers = property(lambda self: self.wq.shape[0])
    layers = property(lambda self: tuple((ATTN, l)
                                         for l in range(self.n_layers)))

    def cache_spec(self, n_heads):
        dh = self.wq.shape[1] // n_heads
        return CacheSpec(self.n_layers, self.wk.shape[1] // dh, dh)

    def embed(self, tokens, positions, lookup):
        return lookup(self.wte, tokens)

    def norm(self, g, x):
        return _rms(g, x)

    def attn_qkv(self, i, a, positions, head_dim, use_rope):
        return tuple((a @ w[i].T).reshape(a.shape[0], -1, head_dim)
                     for w in (self.wq, self.wk, self.wv))

    def attn_out(self, i, y, a):
        return y @ self.wo[i].T

    def ffn(self, l, h):
        return (jax.nn.silu(h @ self.w_gate[l].T)
                * (h @ self.w_up[l].T)) @ self.w_down[l].T

    def head(self, x):
        return _rms(self.g_f, x) @ self.wte.T


def init_nope(seed):
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    w = lambda *s: 0.3 * jax.random.normal(next(ks), s, jnp.float32)
    return NopeParams(
        wte=w(V, D), norm_in=jnp.ones((L, D)), norm_ff=jnp.ones((L, D)),
        g_f=jnp.ones((D,)), wq=w(L, H * DH, D), wk=w(L, HKV * DH, D),
        wv=w(L, HKV * DH, D), wo=w(L, D, H * DH), w_gate=w(L, F, D),
        w_up=w(L, F, D), w_down=w(L, D, F), max_seq_len=64)


def nope_logits(p, tokens):
    """The plain forward: all ``T`` positions at once, causal softmax
    attention, no cache, none of the face's methods."""
    t = len(tokens)
    x = p.wte[jnp.asarray(tokens)]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for l in range(L):
        a = _rms(p.norm_in[l], x)
        q = (a @ p.wq[l].T).reshape(t, H, DH)
        k = jnp.repeat((a @ p.wk[l].T).reshape(t, HKV, DH), H // HKV, 1)
        v = jnp.repeat((a @ p.wv[l].T).reshape(t, HKV, DH), H // HKV, 1)
        s = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(DH)
        w = jax.nn.softmax(jnp.where(causal, s, -1e30), -1)
        x = x + jnp.einsum("hts,shd->thd", w, v).reshape(t, -1) @ p.wo[l].T
        h = _rms(p.norm_ff[l], x)
        x = x + (jax.nn.silu(h @ p.w_gate[l].T)
                 * (h @ p.w_up[l].T)) @ p.w_down[l].T
    return _rms(p.g_f, x) @ p.wte.T


# Half of what a served token's reference logit may lie below the
# reference's best, in units of the logits' spread (standard deviation,
# 1.76 here). f32 differs from the plain forward in the order of its
# sums only; bf16 KV rounds every cached value to 8 bits of mantissa;
# int8 to a 127th of its block's largest. Read on this CPU over the 60
# served positions: f32 and bf16 took the plain forward's first choice
# everywhere (its top two never nearer than 0.042 spreads), int8 took
# the second choice once, 0.042 spreads below the first.
SLACK = {"f32": 1e-4, "bf16": 0.02, "int8": 0.05}


@pytest.mark.parametrize("kv_dtype", ["f32", "bf16", "int8"])
def test_a_third_family_is_one_model_file(kv_dtype):
    """More requests than slots, chunked prefill beside running decodes,
    GQA: every served token is the plain forward's first choice
    wherever its top two lie further apart than the dtype's slack, and
    never further than the slack below it (teacher-forced on what was
    served, the idiom of ``tests/test_hybrid_lm.py``)."""
    p = init_nope(3)
    eng = DecodeEngine(p, H, EngineConfig(
        block_size=8, n_blocks=1 + 3 * 6, max_slots=3, max_blocks_per_seq=6,
        prefill_chunk=8, kv_dtype=kv_dtype))
    assert eng.recurrent == [] and eng.state is None
    assert eng.pool.k.shape == (L, 19, 8, HKV * DH)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, V, n).tolist() for n in (5, 19, 11, 30, 2)]
    uids = [eng.submit(pr, 12) for pr in prompts]
    out = eng.run()
    assert not eng.failed
    clear_n = total = 0
    for u, pr in zip(uids, prompts):
        full = out[u]
        assert len(full) == len(pr) + 12
        lg = np.asarray(nope_logits(p, full))
        tol = SLACK[kv_dtype] * lg.std()
        rows = lg[len(pr) - 1:len(full) - 1]
        served = np.asarray(full[len(pr):])
        top2 = np.sort(rows, -1)[:, -2:]
        clear = top2[:, 1] - top2[:, 0] > 2 * tol
        assert np.array_equal(rows.argmax(-1)[clear], served[clear])
        below = rows.max(-1) - rows[np.arange(len(served)), served]
        assert below.max() <= 2 * tol, (kv_dtype, below.max() / lg.std())
        clear_n, total = clear_n + clear.sum(), total + len(served)
    assert clear_n / total > 0.8        # the comparison is not vacuous


# -- the cache spec sizes the pool and the state ------------------------------

HYBRID_TOY = dict(
    model_type="jamba", hidden_size=64, intermediate_size=128,
    mamba_expand=2, mamba_d_state=4, mamba_d_conv=4, mamba_dt_rank=8,
    mamba_conv_bias=True, mamba_proj_bias=False, num_hidden_layers=6,
    attn_layer_period=3, attn_layer_offset=1, num_attention_heads=4,
    num_key_value_heads=1, vocab_size=96, rms_norm_eps=1e-6,
    max_position_embeddings=256, num_experts=1, hidden_act="silu",
    tie_word_embeddings=True, sliding_window=None, initializer_range=0.2)

SPECS = {
    "gpt2": (lambda: init_lm(jax.random.PRNGKey(0), 96, 32, 2, 64,
                             n_heads=4),
             CacheSpec(2, 4, 8)),
    "gpt2-gqa": (lambda: init_lm(jax.random.PRNGKey(0), 96, 32, 3, 64,
                                 n_heads=4, n_kv_heads=2),
                 CacheSpec(3, 2, 8)),
    "hybrid": (lambda: params_from_config(HYBRID_TOY, 1),
               CacheSpec(2, 1, 16, rec_layers=4,
                         state_row=StateRow(conv_lanes=128, taps=4, rows=4,
                                            lanes=128))),
}


def test_cache_spec_sizes_a_latent_pool(toy_latent_config):
    """A latent-cache, sparse-expert model in small
    (``models/mla_moe_lm.py``: one row of 32 + 8 lanes a token a layer,
    filled to a whole 128-lane tile). Its spec gives the pool ONE row a token a
    layer: ``k`` holds it, ``v`` has the same shape with no lanes (the
    harness's byte count over ``pool.k`` and ``pool.v`` is the row's),
    no state beside it, and the result's counters their size."""
    params = params_from_config(toy_latent_config, 1)
    want = CacheSpec(4, 1, 128, latent_rank=32, expert_layers=3,
                     n_experts=16)
    assert params.cache_spec(4) == want
    assert {kind for kind, _ in params.layers} == {LATENT}
    eng = DecodeEngine(params, 4, EngineConfig(
        block_size=8, n_blocks=11, max_slots=2, max_blocks_per_seq=5,
        kv_dtype="bf16"))
    pool = eng.pool
    assert eng.spec == want and eng.state is None and eng.recurrent == []
    assert pool.k.shape == (4, 11, 8, 128) and pool.v.shape == (4, 11, 8, 0)
    assert (pool.latent_rank, pool.kv_heads, pool.head_dim) == (32, 1, 128)
    per_token = ((pool.k.size * pool.k.dtype.itemsize
                  + pool.v.size * pool.v.dtype.itemsize)
                 / (pool.n_blocks * pool.block_size))
    assert per_token == eng._kv_bytes_per_token() == 4 * 128 * 2
    assert eng.programs.wire("decode", 2).fields.keys() == {
        "tables", "lengths", "tokens", "uids", "poison", "rows"}
    picks, rows = eng.programs.split("decode", np.arange(2 + 3 * 16))
    assert picks.tolist() == [0, 1] and rows.shape == (3, 16)


@pytest.mark.parametrize("family", sorted(SPECS))
def test_cache_spec_sizes_pool_and_state(family):
    """What the model says it keeps per sequence is what the engine
    allocates: the pool's layer axis, heads and head width, and for
    recurrent layers the state's by-slot rows (one scratch row more
    than slots)."""
    make, want = SPECS[family]
    params = make()
    assert params.cache_spec(4) == want
    cfg = EngineConfig(block_size=8, n_blocks=11, max_slots=2,
                       max_blocks_per_seq=5, kv_dtype="int8")
    eng = DecodeEngine(params, 4, cfg)
    assert eng.spec == want
    pool = eng.pool
    assert pool.k.shape == pool.v.shape == (
        want.kv_layers, 11, 8, want.kv_heads * want.head_dim)
    assert pool.k_scale.shape == (want.kv_layers, 11, want.kv_heads)
    assert pool.head_dim == want.head_dim
    if not want.rec_layers:
        assert eng.state is None and eng.recurrent == []
        assert set(eng.state_row_bytes.values()) == {0}
        return
    assert eng.recurrent == ["mamba"]
    row = want.state_row
    assert eng.state.conv.shape == (4, 3, 1, row.tail_lanes)
    assert eng.state.ssm.shape == (4, 3, row.rows, row.lanes)
    assert eng.state.bytes_per_slot == 4 * row.bytes
    # ... at the row's two widths, which every record carries
    assert eng.state_row_bytes == {"state_row_bytes": 4 * 4 * 128,
                                   "tail_row_bytes": 4 * 3 * 128}


# -- the structure -------------------------------------------------------------

SCHEDULER_AND_BUILDER = [os.path.join(PKG, "decode", name)
                         for name in ("engine.py", "programs.py")]
# a family's arithmetic, and what it is written from
ARITHMETIC = re.compile(r"(^|\.)(models\.(lm|hybrid_lm|mla_moe_lm|attention"
                        r"|transformer|moe\w*|ffn_stack)"
                        r"|ops\.(norm|ssm|ffn|activations|moe\w*))$")
FACE_NAMES = {"ATTN", "LATENT", "WINDOW", "CHUNKED", "CacheSpec",
              "ServedModel", "take"}


def _imports(path):
    """``(module as written, dots, names)`` of every import in a file."""
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            yield node.module or "", node.level, [a.name for a in node.names]
        elif isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, 0, []


@pytest.mark.parametrize("path", SCHEDULER_AND_BUILDER, ids=os.path.basename)
def test_scheduler_and_builder_know_no_family(path):
    with open(path) as f:
        src = f.read()
    for node in ast.walk(ast.parse(src)):
        if (isinstance(node, ast.Call)
                and getattr(node.func, "id", None) == "isinstance"):
            assert "Params" not in ast.unparse(node.args[1]), (
                ast.unparse(node))
    for module, _, names in _imports(path):
        assert not ARITHMETIC.search(module), module
        if module.endswith("models"):
            assert not names, (module, names)
        if module.endswith("models.face"):
            assert set(names) <= FACE_NAMES, names
    # the acceptance criteria's own three lines
    assert not re.search(r"isinstance\([^)]*Params", src)
    assert not re.search(r"hybrid_lm\.[a-z_]*\(|layernorm\(|rope\(|"
                         r"params\.mamba|params\.blocks|p\.blocks|"
                         r"mla_moe_lm|moe_serve|p\.experts|p\.mla", src)


def test_models_import_nothing_from_decode_or_parallel():
    files = glob.glob(os.path.join(PKG, "models", "*.py"))
    assert len(files) >= 10
    for path in files:
        for module, level, _ in _imports(path):
            top = module.split(".")[0 if level else 1:][:1]
            assert top not in (["decode"], ["parallel"]), (path, module)
        with open(path) as f:
            assert not re.search(r"from \.\.(decode|parallel)", f.read())


# -- the fork is gone with its option -------------------------------------------


def test_the_cache_read_has_no_option(capsys):
    """One cache-read path, so no knob: ``EngineConfig`` has no
    ``kernel`` field (17 fields) and ``generate`` no flag of that name
    (argparse's exit 2, an unknown argument)."""
    from distributed_llm_code_samples_tpu.decode.generate_cli import (
        generate_main)
    gone = "kernel"
    with pytest.raises(TypeError, match=gone):
        EngineConfig(**{gone: "gather"})
    assert len(dataclasses.fields(EngineConfig)) == 17
    with pytest.raises(SystemExit) as err:
        generate_main(["--" + gone, "fused", "--prompt_lens", "5",
                       "--max_new", "2"])
    assert err.value.code == 2
    assert f"unrecognized arguments: --{gone}" in capsys.readouterr().err


def test_cache_spec_gives_each_store_a_row_of_its_own():
    """``CacheSpec.row`` / ``.window_row`` (``models/face.py::KVRow``)
    are the ONE description each pool is built from: a spec that states
    neither a value width nor a window row (every family before the
    seventh) gives both stores the full kind's ``kv_heads x head_dim``
    row on either side, as it always did; one that states them gives the
    full kind's pool ``H_kv x dk`` lanes of K beside ``H_kv x dv`` of V
    and the window kind's pool its own KV heads, and the pool's specs
    under a mesh are the same tree as the pool."""
    from distributed_llm_code_samples_tpu.decode.programs import StepPrograms
    from distributed_llm_code_samples_tpu.models.face import KVRow
    cfg = EngineConfig(block_size=8, n_blocks=11, max_slots=2,
                       max_blocks_per_seq=5, kv_dtype="bf16")
    same = CacheSpec(2, 4, 8, win_layers=3, window=16)
    assert same.row == same.window_row == KVRow(4, 8, 8)
    progs = StepPrograms(cfg, same, 96)
    pool, wpool = progs.init_cache()[0], progs.init_window()
    assert pool.k.shape == pool.v.shape == (2, 11, 8, 32)
    assert wpool.k.shape == wpool.v.shape == (3, 1 + 2 * 5, 8, 32)
    assert pool.row == wpool.row == KVRow(4, 8, 8)
    split = CacheSpec(2, 2, 24, win_layers=5, window=16, v_head_dim=16,
                      win_row=KVRow(4, 24, 16))
    assert split.row == KVRow(2, 24, 16)
    assert split.window_row == KVRow(4, 24, 16)
    progs = StepPrograms(cfg, split, 96)
    pool, wpool = progs.init_cache()[0], progs.init_window()
    assert pool.k.shape == (2, 11, 8, 48) and pool.v.shape == (2, 11, 8, 32)
    assert wpool.k.shape == (5, 11, 8, 96)
    assert wpool.v.shape == (5, 11, 8, 64)
    assert (pool.row, wpool.row) == (split.row, split.window_row)
    assert pool.kv_heads == 2 and wpool.kv_heads == 4
    assert (jax.tree_util.tree_structure(progs.pool_specs())
            == jax.tree_util.tree_structure(pool))


def test_qkv_heads_splits_values_by_their_own_width():
    """``qkv_heads(v_head_dim=)``: ``W_v [H_kv * dv, d]`` gives ``v [N,
    H_kv, dv]`` beside ``k [N, H_kv, dh]``; without it a value head is
    as wide as a key head."""
    from distributed_llm_code_samples_tpu.models.face import qkv_heads
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(5, 32)), jnp.float32)
    wq, wk, wv = (jnp.asarray(rng.normal(size=(1, n, 32)), jnp.float32)
                  for n in (8 * 24, 2 * 24, 2 * 16))
    q, k, v = qkv_heads(wq, wk, wv, 0, a, jnp.arange(5), 24, False,
                        v_head_dim=16)
    assert (q.shape, k.shape, v.shape) == ((5, 8, 24), (5, 2, 24),
                                           (5, 2, 16))
    np.testing.assert_allclose(np.asarray(v).reshape(5, 32),
                               np.asarray(a @ wv[0].T), rtol=1e-6)
    _, _, v = qkv_heads(wq, wk, wk, 0, a, jnp.arange(5), 24, False)
    assert v.shape == (5, 2, 24)


def _qkv_case(name):
    """``(stacks, layer, activations, positions, head_dim, use_rope,
    keywords)`` of one caller's shape family, at a toy width."""
    from distributed_llm_code_samples_tpu.models.attention import Rotary
    rng = np.random.default_rng(sum(map(ord, name)))
    d, n, layers = 32, 5, 3
    h, hkv, dh, dv = 8, 2, 8, 8
    wdt = adt = jnp.float32
    use_rope, kw = True, {}
    if name == "gpt2_f32":          # lm.py: no grouping, no rotary
        hkv, use_rope = h, False
    elif name == "gqa_bf16":        # hybrid_lm.py: bf16 weights, f32 rows
        wdt, use_rope = jnp.bfloat16, False
    elif name == "bf16_both":       # operands of one type, not float32
        wdt = adt = jnp.bfloat16
    elif name == "narrow_v":        # mimo_v2_flash_lm.py: dv < dh
        dh, dv, wdt = 24, 16, jnp.bfloat16
        kw = dict(v_head_dim=dv, rotary=Rotary(theta=5e6, partial=0.334))
    elif name == "qk_norm":         # lfm2_moe_lm.py: norm, then theta
        g = [jnp.asarray(1 + 0.1 * rng.normal(size=dh), jnp.float32)
             for _ in range(2)]
        kw = dict(qk_norm=(g[0], g[1], 1e-5), theta=1e6)
        wdt = jnp.bfloat16
    elif name == "yarn_half":       # laguna_lm.py: YaRN on half a head
        wdt = jnp.bfloat16
        kw = dict(rotary=Rotary(theta=5e5, partial=0.5, factor=8.0,
                                original=16, attention_factor=1.2))
    elif name == "head_shard":      # lm.py under ``generate --tp 2``:
        hkv, use_rope = h, False    # the test takes the LOCAL half
    else:
        assert name == "rope_default", name
    stacks = tuple(jnp.asarray(0.3 * rng.normal(size=(layers, rows, d)), wdt)
                   for rows in (h * dh, hkv * dh, hkv * dv))
    a = jnp.asarray(rng.normal(size=(n, d)), adt)
    return stacks, 1, a, jnp.asarray(rng.integers(0, 40, n)), dh, use_rope, kw


@pytest.mark.parametrize("name", ["gpt2_f32", "gqa_bf16", "bf16_both",
                                  "narrow_v", "qk_norm", "yarn_half",
                                  "head_shard", "rope_default"])
@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
def test_qkv_heads_holds_its_products_and_changes_no_result(
        monkeypatch, name, jitted):
    """PR 51 changed how ``qkv_heads``' three products reach the
    compiler (each held as written behind a barrier on its result,
    ``face.mm_held``, so that the layer's slice stays inside the
    product), not what they compute: for every caller's shape family
    the three results have the shapes and dtypes of the predecessor's
    (the same function with the plain ``mm`` in the held one's place)
    and agree at the resolution of the type the product sums in."""
    from distributed_llm_code_samples_tpu.models import face
    (wq, wk, wv), i, a, pos, dh, use_rope, kw = _qkv_case(name)

    def run():
        wrapped = (lambda wq, wk, wv, a, pos: face.qkv_heads(
            wq, wk, wv, i, a, pos, dh, use_rope, **kw))
        return (jax.jit(wrapped) if jitted else wrapped)(wq, wk, wv, a, pos)

    got = run()
    with monkeypatch.context() as patch:
        patch.setattr(face, "mm_held", face.mm)
        want = run()
    same_type = a.dtype == wq.dtype
    tol = (2e-2 if same_type and a.dtype == jnp.bfloat16 else 2e-6)
    for x, y in zip(got, want):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=tol, atol=tol)
    heads = wq.shape[1] // dh
    assert got[0].shape == (a.shape[0], heads, dh)
    if name == "head_shard":
        # a shard's rows of each stack give that shard's heads of the
        # whole: the local count is read off the stack handed in
        half = heads // 2 * dh
        local = face.qkv_heads(wq[:, :half], wk[:, :half], wv[:, :half],
                               i, a, pos, dh, use_rope, **kw)
        for x, y in zip(local, got):
            assert x.shape == (a.shape[0], heads // 2, dh)
            np.testing.assert_allclose(np.asarray(x),
                                       np.asarray(y[:, :heads // 2]),
                                       rtol=tol, atol=tol)
