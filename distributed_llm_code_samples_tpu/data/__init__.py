"""Deterministic seeds-as-dataset data layer.

Parity target: the reference's mock data pipeline (``train_ffns.py:144-151``,
``:350, :356-360``) where **data distribution = seed distribution**: the
dataset is never materialized centrally; each training step is defined by one
integer seed, and each strategy decides which ranks consume which seeds.
This is what makes cross-strategy differential testing possible.

TPU-native translation: counter-based RNG. A step's ``(x, dloss_dx)`` pair is
a pure function of its integer seed via ``jax.random.fold_in`` — so the same
seed produces bit-identical data on every rank, on every strategy, inside or
outside ``jit``/``shard_map``/``scan`` (the idiomatic equivalent of the
reference's re-seeded ``torch.Generator`` per step).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import DLOSS_DX_COEF

# Base key folded with each per-step seed; fixed, like the reference's fresh
# torch.Generator per step (train_ffns.py:145-148).
_DATA_KEY = 0

# In-graph fault-injection flags (runtime/chaos.py with guardrails on):
# the seed IS the dataset, so a fault that must fire INSIDE a compiled
# multi-step chunk rides the seed value itself — the chaos layer sets a
# high bit on the target step's seed and `batch_from_seed` turns it into
# a poisoned upstream gradient via `jnp.where`, deterministically, on
# every strategy, with no per-strategy plumbing. Schedule seeds live in
# [0, 100_000) (make_seed_schedule), so bits 28/29 are always free.
POISON_NAN_BIT = 1 << 29
POISON_INF_BIT = 1 << 28
_POISON_MASK = POISON_NAN_BIT | POISON_INF_BIT


def strip_poison(seed):
    """The underlying schedule seed, poison flags cleared (traced-safe)."""
    return jnp.bitwise_and(jnp.asarray(seed), jnp.int32(~_POISON_MASK))


def batch_from_seed(seed: jax.Array, batch_size: int, model_size: int,
                    dtype=jnp.float32):
    """One step's ``(x, dloss_dx)`` from its integer seed.

    ``x = normal([batch, d])``; the loss is mocked by a randomized upstream
    gradient ``dloss_dx = 0.1 * normal([batch, d])`` "coming from the right"
    (``train_ffns.py:12, :30, :149-150``). ``seed`` may be a traced scalar —
    this works inside ``lax.scan`` over a seed schedule.

    A seed carrying a poison flag (``POISON_NAN_BIT``/``POISON_INF_BIT``,
    set by ``runtime.chaos`` for in-graph fault injection) produces the
    *same* ``x`` as its base seed but a NaN/Inf ``dloss_dx`` — the
    poisoned-gradient step the in-graph guardrails
    (``runtime/guardrails.py``) must catch and skip.
    """
    seed = jnp.asarray(seed)
    base = strip_poison(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(_DATA_KEY), base)
    kx, kd = jax.random.split(key)
    x = jax.random.normal(kx, (batch_size, model_size)).astype(dtype)
    dloss_dx = (DLOSS_DX_COEF *
                jax.random.normal(kd, (batch_size, model_size))).astype(dtype)
    nan_p = jnp.bitwise_and(seed, jnp.int32(POISON_NAN_BIT)) != 0
    inf_p = jnp.bitwise_and(seed, jnp.int32(POISON_INF_BIT)) != 0
    dloss_dx = jnp.where(nan_p, jnp.asarray(jnp.nan, dloss_dx.dtype),
                         dloss_dx)
    dloss_dx = jnp.where(inf_p, jnp.asarray(jnp.inf, dloss_dx.dtype),
                         dloss_dx)
    # The pair is STORED once before anything reads it. Without the
    # barrier the compiler fuses the draw (threefry + the inverse error
    # function) into every matrix product that reads x or dloss_dx, and a
    # producer fused into a product is evaluated again for every pass the
    # product makes over that operand: at d=8192 a draw that costs ~2 ms
    # alone cost 28-68 ms inside each of the step's four products (PERF.md
    # section 6, PR 47). Same values, same key, same compiled step.
    return jax.lax.optimization_barrier((x, dloss_dx))


def mock_data(seeds, batch_size: int, model_size: int, dtype=jnp.float32):
    """Eager generator over the seed schedule — host-side analogue of the
    reference's ``mock_data`` (``train_ffns.py:144-151``). The jitted training
    paths use ``batch_from_seed`` inside the step instead."""
    for seed in np.asarray(seeds).tolist():
        yield batch_from_seed(jnp.int32(seed), batch_size, model_size, dtype)


def lm_batch_from_seed(seed: jax.Array, batch: int, seq_len: int,
                       vocab: int):
    """One LM step's ``(tokens, targets)`` from its integer seed: a
    deterministic ``[batch, seq_len + 1]`` token draw, split next-token
    style (``targets`` = ``tokens`` shifted left by one). Same counter-RNG
    contract as ``batch_from_seed`` — bit-identical on every rank, traced
    or eager — so the LM strategies keep the framework's seeds-as-dataset
    differential-testing story."""
    # poison flags are an FFN-family (float-gradient) injection; integer
    # token draws strip them so a poisoned schedule stays deterministic
    key = jax.random.fold_in(jax.random.PRNGKey(_DATA_KEY),
                             strip_poison(seed))
    toks = jax.random.randint(key, (batch, seq_len + 1), 0, vocab,
                              dtype=jnp.int32)
    return toks[:, :-1], toks[:, 1:]


_CORPUS = None


def load_text_corpus() -> np.ndarray:
    """The embedded REAL-text corpus as a ``uint8`` byte array (~237 KB of
    English prose: the concatenated license texts shipped with every
    Debian image under ``/usr/share/common-licenses`` — freely
    redistributable verbatim, vendored at
    ``data_assets/corpus.txt``). Byte-level vocab (256): every byte is a
    token, so no tokenizer is needed and the LM family trains on real
    text end to end (the capability synthetic seeds can't demonstrate)."""
    global _CORPUS
    if _CORPUS is None:
        import os
        path = os.path.join(os.path.dirname(__file__), "..",
                            "data_assets", "corpus.txt")
        with open(path, "rb") as f:
            _CORPUS = np.frombuffer(f.read(), dtype=np.uint8)
    return _CORPUS


def text_batch_from_seed(seed: jax.Array, batch: int, seq_len: int,
                         corpus=None):
    """One real-text LM step from its integer seed: ``batch`` random
    windows of ``seq_len + 1`` bytes gathered from the corpus, split
    next-token style like ``lm_batch_from_seed``. Same counter-RNG
    contract (``fold_in`` on the seed), so it is deterministic, traceable
    (works inside ``lax.scan`` over a seed schedule), and identical on
    every rank — real text slots into the seeds-as-dataset design
    unchanged. ``corpus`` defaults to the embedded one; pass any 1-D
    ``uint8``/int array to train on other bytes."""
    data = jnp.asarray(load_text_corpus() if corpus is None else corpus)
    key = jax.random.fold_in(jax.random.PRNGKey(_DATA_KEY), seed)
    starts = jax.random.randint(key, (batch,), 0,
                                data.shape[0] - seq_len)  # exclusive: the
    # last valid window start is len - seq_len - 1, so every seq_len+1
    # window (incl. the corpus's final byte as a target) is reachable
    idx = starts[:, None] + jnp.arange(seq_len + 1)[None, :]
    seqs = data[idx].astype(jnp.int32)
    return seqs[:, :-1], seqs[:, 1:]


def make_seed_schedule(num_steps: int, random_seed: int = 0) -> jnp.ndarray:
    """``num_steps`` integer seeds in ``[0, 100_000)`` (``train_ffns.py:360``).

    ``random_seed != 0`` makes the schedule reproducible across runs
    (``train_ffns.py:350, :356-359``); ``0`` draws from OS entropy like the
    reference's default generator.
    """
    if random_seed != 0:
        rng = np.random.default_rng(random_seed)
    else:
        rng = np.random.default_rng()
    return jnp.asarray(rng.integers(0, 100_000, size=(num_steps,)),
                       dtype=jnp.int32)


def shard_seeds_strided(seeds, n_ranks: int) -> jnp.ndarray:
    """Strided seed split: returns ``[steps_per_rank, n_ranks]`` where column
    ``r`` is rank ``r``'s schedule — rank ``r``'s step ``t`` consumes global
    seed ``seeds[t * n_ranks + r]``, exactly the reference's
    ``seeds.reshape((-1, nGPUs)).chunk(nGPUs, dim=1)`` (``train_ffns.py:182``).

    Getting this wrong silently breaks DDP == FSDP differential tests
    (SURVEY.md section 7, "hard parts").
    """
    seeds = jnp.asarray(seeds)
    if seeds.shape[0] % n_ranks != 0:
        raise ValueError(
            f"num_steps={seeds.shape[0]} not divisible by n_ranks={n_ranks} "
            "(reference asserts the same, train_ffns.py:175)")
    return seeds.reshape(-1, n_ranks)


def shard_seeds_elastic(seeds, n_ranks: int, accum: int) -> jnp.ndarray:
    """Global-batch-preserving re-stride for topology-elastic resume:
    ``[T] -> [T / (accum * n_ranks), accum, n_ranks]`` where slot
    ``[t, j, r]`` is global seed ``seeds[t*N + j*n_ranks + r]`` with
    ``N = accum * n_ranks`` — the original device count at save time.

    Rank ``r``'s optimizer update ``t`` gradient-accumulates over its
    ``accum`` seeds, so the union of seeds per update is exactly
    ``seeds[t*N : (t+1)*N]`` — the same global batch the N-device run
    consumed (``shard_seeds_strided`` semantics). A checkpoint saved
    under N devices therefore resumes onto ``n_ranks = N/accum``
    survivors with the SAME update sequence: the post-resume batch order
    is deterministic and the loss trajectory matches the uninterrupted
    N-device run (tests/test_elastic.py pins it).

    ``accum=1`` degrades to ``shard_seeds_strided`` with an extra
    singleton axis."""
    seeds = jnp.asarray(seeds)
    if accum < 1:
        raise ValueError(f"accum must be >= 1, got {accum}")
    n = accum * n_ranks
    if seeds.shape[0] % n != 0:
        raise ValueError(
            f"num_steps={seeds.shape[0]} not divisible by the "
            f"{n}-seed global batch ({accum} accum x {n_ranks} ranks) "
            "— elastic resume preserves the save-time global batch")
    return seeds.reshape(-1, accum, n_ranks)
