"""Data-layer tests: deterministic seeds-as-dataset semantics
(reference ``train_ffns.py:144-151, :182, :350-360``)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_code_samples_tpu import DLOSS_DX_COEF
from distributed_llm_code_samples_tpu.data import (
    POISON_INF_BIT, POISON_NAN_BIT, batch_from_seed, mock_data,
    make_seed_schedule, shard_seeds_strided)


def test_batch_deterministic():
    x1, d1 = batch_from_seed(jnp.int32(123), 8, 16)
    x2, d2 = batch_from_seed(jnp.int32(123), 8, 16)
    np.testing.assert_array_equal(x1, x2)
    np.testing.assert_array_equal(d1, d2)


def test_batch_differs_across_seeds():
    x1, _ = batch_from_seed(jnp.int32(1), 8, 16)
    x2, _ = batch_from_seed(jnp.int32(2), 8, 16)
    assert not np.allclose(x1, x2)


def test_batch_shapes_and_dloss_scale():
    x, dl = batch_from_seed(jnp.int32(5), 32, 8)
    assert x.shape == (32, 8) and dl.shape == (32, 8)
    # dloss_dx = 0.1 * normal — std should be ~DLOSS_DX_COEF (train_ffns.py:30)
    assert abs(float(jnp.std(dl)) - DLOSS_DX_COEF) < 0.03 * DLOSS_DX_COEF * 10


def test_batch_works_inside_jit_and_scan():
    def run(seeds):
        def body(c, s):
            x, dl = batch_from_seed(s, 4, 8)
            return c + x.sum() + dl.sum(), None
        return jax.lax.scan(body, 0.0, seeds)[0]

    seeds = jnp.arange(5, dtype=jnp.int32)
    eager = sum(float(x.sum() + dl.sum())
                for x, dl in mock_data(seeds, 4, 8))
    np.testing.assert_allclose(float(jax.jit(run)(seeds)), eager, rtol=1e-5)


def test_seed_schedule_reproducible():
    s1 = make_seed_schedule(10, random_seed=42)
    s2 = make_seed_schedule(10, random_seed=42)
    np.testing.assert_array_equal(s1, s2)
    assert s1.shape == (10,)
    assert int(s1.min()) >= 0 and int(s1.max()) < 100_000


def test_strided_shard_layout():
    # rank r's step t must consume global seed[t*n + r] (train_ffns.py:182)
    seeds = jnp.arange(12, dtype=jnp.int32)
    cols = shard_seeds_strided(seeds, 4)
    assert cols.shape == (3, 4)
    for r in range(4):
        np.testing.assert_array_equal(np.asarray(cols[:, r]),
                                      np.arange(12)[r::4])


def test_strided_shard_divisibility_error():
    with pytest.raises(ValueError):
        shard_seeds_strided(jnp.arange(10), 4)


def test_text_corpus_loads_real_bytes():
    from distributed_llm_code_samples_tpu.data import load_text_corpus
    corpus = load_text_corpus()
    assert corpus.dtype == np.uint8
    assert corpus.shape[0] > 100_000  # "a few hundred KB" of real text
    text = corpus.tobytes().decode("utf-8")
    # real English prose, not noise
    for phrase in ("License", "copyright", "distribute"):
        assert phrase in text


def test_text_batch_windows_and_determinism():
    from distributed_llm_code_samples_tpu.data import (load_text_corpus,
                                                       text_batch_from_seed)
    corpus = load_text_corpus()
    tok, tgt = text_batch_from_seed(jnp.int32(5), 4, 32)
    assert tok.shape == (4, 32) and tgt.shape == (4, 32)
    # targets are the next byte (windows are contiguous corpus slices)
    np.testing.assert_array_equal(np.asarray(tok[:, 1:]),
                                  np.asarray(tgt[:, :-1]))
    # every window is a verbatim corpus slice
    blob = corpus.tobytes()
    for row in np.asarray(tok, dtype=np.uint8):
        assert row.tobytes() in blob
    # counter-RNG contract: same seed == same batch, different seed differs
    tok2, _ = text_batch_from_seed(jnp.int32(5), 4, 32)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(tok2))
    tok3, _ = text_batch_from_seed(jnp.int32(6), 4, 32)
    assert not np.array_equal(np.asarray(tok), np.asarray(tok3))


def test_text_batch_traces_in_scan():
    # the seed may be a traced scalar: real text keeps the
    # seeds-as-dataset design (works under lax.scan like the synthetic
    # sources)
    from distributed_llm_code_samples_tpu.data import text_batch_from_seed
    import jax

    def body(c, s):
        tok, tgt = text_batch_from_seed(s, 2, 16)
        return c + tok.sum() + tgt.sum(), None

    total, _ = jax.jit(
        lambda seeds: jax.lax.scan(body, jnp.int32(0), seeds))(
            jnp.arange(3, dtype=jnp.int32))
    assert int(total) > 0


def test_real_text_training_loss_falls():
    """End to end on real bytes: a tiny LM trained through the batch_fn
    hook must beat its initial eval loss decisively (the capability
    synthetic seeds can't prove)."""
    from distributed_llm_code_samples_tpu.data import text_batch_from_seed
    from distributed_llm_code_samples_tpu.models import init_lm
    from distributed_llm_code_samples_tpu.models.lm import lm_loss
    from distributed_llm_code_samples_tpu.optim import adamw
    from distributed_llm_code_samples_tpu.parallel import train_lm_single
    import jax
    B, T, D_, H_ = 8, 32, 32, 4
    params = init_lm(jax.random.PRNGKey(0), 256, D_, 2, max_seq_len=T)
    etok, etgt = text_batch_from_seed(jnp.int32(999_983), B, T)
    loss0 = float(lm_loss(params, etok, etgt, H_))
    params, _ = train_lm_single(
        params, jnp.arange(30, dtype=jnp.int32), B * T, D_, lr=3e-3,
        seq_len=T, n_heads=H_, optimizer=adamw(weight_decay=0.01),
        return_state=True,
        batch_fn=lambda s: text_batch_from_seed(s, B, T))
    loss1 = float(lm_loss(params, etok, etgt, H_))
    assert loss1 < loss0 - 0.5, (loss0, loss1)


# ---------------------------------------------------------------------
# PR 47: the pair is returned from behind ``lax.optimization_barrier``
# (stored once, where a fused draw is repeated inside every matrix
# product that reads it). The barrier changes no value anywhere the
# draw is used.

def _plain_draw(seed, batch_size, model_size):
    """``batch_from_seed`` restated with no barrier: the same fold-in,
    split and two normal draws, the poison bits by hand."""
    seed = jnp.asarray(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(0),
                             jnp.bitwise_and(seed, jnp.int32(~(3 << 28))))
    kx, kd = jax.random.split(key)
    x = jax.random.normal(kx, (batch_size, model_size))
    dy = DLOSS_DX_COEF * jax.random.normal(kd, (batch_size, model_size))
    dy = jnp.where(jnp.bitwise_and(seed, jnp.int32(1 << 29)) != 0,
                   jnp.float32(jnp.nan), dy)
    dy = jnp.where(jnp.bitwise_and(seed, jnp.int32(1 << 28)) != 0,
                   jnp.float32(jnp.inf), dy)
    return x, dy


def _one_device_shard_map(draw):
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("one",))
    per_seed = jax.shard_map(draw, mesh=mesh, in_specs=P(), out_specs=P())
    return lambda seeds: jax.lax.map(per_seed, seeds)


def _seed_by_seed(draw):
    return lambda seeds: jax.tree.map(lambda *rows: jnp.stack(rows),
                                      *[draw(s) for s in seeds])


_SEEDS = jnp.asarray([0, 1, 77_777, 99_999], dtype=jnp.int32)
_WAYS = {
    "eager": _seed_by_seed,
    "jit": lambda draw: _seed_by_seed(jax.jit(draw)),
    "scan": lambda draw: jax.jit(lambda seeds: jax.lax.scan(
        lambda c, s: (c, draw(s)), 0, seeds)[1]),
    "vmap": lambda draw: jax.jit(jax.vmap(draw)),
    "shard_map": lambda draw: jax.jit(_one_device_shard_map(draw)),
}


@pytest.mark.parametrize("way", sorted(_WAYS))
def test_batch_is_the_plain_draw_bit_for_bit(way):
    """Each way of running the draw gives what the same way gives for
    its plain restatement (a compiled ``0.1 * normal`` rounds its last
    bit otherwise than the eager one, with or without the barrier), and
    every way gives the eager ``x``."""
    got = _WAYS[way](lambda s: batch_from_seed(s, 8, 16))(_SEEDS)
    want = _WAYS[way](lambda s: _plain_draw(s, 8, 16))(_SEEDS)
    assert got[0].shape == got[1].shape == (len(_SEEDS), 8, 16)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    for i, s in enumerate(_SEEDS):
        np.testing.assert_array_equal(np.asarray(got[0][i]),
                                      np.asarray(_plain_draw(s, 8, 16)[0]))


@pytest.mark.parametrize("way", ["eager", "scan", "vmap"])
@pytest.mark.parametrize("bit, bad", [(POISON_NAN_BIT, np.isnan),
                                      (POISON_INF_BIT, np.isposinf)],
                         ids=["nan", "inf"])
def test_poisoned_seed_keeps_x_and_poisons_dloss_dx(bit, bad, way):
    seeds = jnp.asarray([5, 5 | bit], dtype=jnp.int32)
    x, dy = _WAYS[way](lambda s: batch_from_seed(s, 8, 16))(seeds)
    np.testing.assert_array_equal(np.asarray(x[0]), np.asarray(x[1]))
    assert np.isfinite(np.asarray(dy[0])).all()
    assert bad(np.asarray(dy[1])).all()
    want = _WAYS[way](lambda s: _plain_draw(s, 8, 16))(seeds)
    for g, w in zip((x, dy), want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("policy", [
    {}, {"remat": False}, {"mixed": True}, {"manual_loop": True},
    {"accum": 2}, {"unroll": False}],
    ids=["recompute", "saved", "mixed", "manual_loop", "accum2", "scan"])
def test_single_step_is_the_step_over_a_stored_batch(monkeypatch, policy):
    """One ``single.make_step`` step gives bitwise the parameters the
    same step gives when its batch is handed to it as two stored arrays
    (the plain draw's, made outside the program): what the step was
    before the barrier, with nothing left to fuse."""
    from distributed_llm_code_samples_tpu.models import init_ffn_stack
    from distributed_llm_code_samples_tpu.parallel import single
    tokens, d, seed = 32, 16, jnp.int32(4711)
    params = init_ffn_stack(jax.random.PRNGKey(3), d, 2)
    step = single.make_step(tokens, d, lr=0.1, **policy)
    got = jax.jit(step)(params, seed)

    def over_stored(params, x, dy):
        monkeypatch.setattr(single, "batch_from_seed",
                            lambda *_: (x, dy))
        return step(params, seed)

    x, dy = jax.jit(lambda s: _plain_draw(s, tokens, d))(seed)
    want = jax.jit(over_stored)(params, x, dy)
    for g, w, p in zip(got, want, params):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert not np.array_equal(np.asarray(g), np.asarray(p))


def test_train_real_text_contract(tmp_path):
    """``train_real_text.py`` as a child process at toy sizes: falling
    train AND held-out loss curves over a held-out tail no training
    window samples, the best held-out loss as the headline, a sampled
    continuation, and the artifact file."""
    from conftest import load_scaled_timeout
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    art = str(tmp_path / "textlm.json")
    env = dict(os.environ, BENCH_PLATFORM="cpu", TEXTLM_STEPS="20",
               TEXTLM_SEGMENTS="2", TEXTLM_D="32", TEXTLM_LAYERS="1",
               TEXTLM_HEADS="2", TEXTLM_SEQ="32", TEXTLM_BATCH="4",
               TEXTLM_ARTIFACT=art)
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run([sys.executable, "train_real_text.py"],
                       capture_output=True, text=True, env=env, cwd=repo,
                       timeout=load_scaled_timeout(900))
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.loads([ln for ln in r.stdout.splitlines()
                          if ln.startswith("{")][-1])
    assert payload["metric"] == "real_text_lm_best_holdout_loss"
    curve = payload["loss_curve"]
    assert curve[0]["step"] == 0 and curve[-1]["step"] == 20
    best = min(curve[1:], key=lambda p: p["holdout_loss"])
    assert payload["value"] == best["holdout_loss"]
    assert payload["value"] < payload["initial_holdout_loss"], curve
    assert payload["best_step"] == best["step"]
    assert curve[-1]["train_loss"] < curve[0]["train_loss"], curve
    assert "generalization_gap" in payload
    assert "final_holdout_loss" in payload
    assert "warmup_cosine" in payload["schedule"]
    assert payload["train_bytes"] + payload["holdout_bytes"] \
        == payload["corpus_bytes"]
    assert isinstance(payload["sample"], str) and len(payload["sample"])
    assert os.path.exists(art)
