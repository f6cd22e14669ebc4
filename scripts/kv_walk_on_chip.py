"""The decode-side K/V read alone, on the chip: the walk over live
blocks (``ops/kv_walk.py``) beside the plain gather and two products
(``decode/paged.py::gathered_decode_attn``) at the serving cells' shapes.

    python3 scripts/kv_walk_on_chip.py [--steps 8,16,32] [--cells gpt2,lfm2]

One jitted program a form: every full-kind layer's read of one batch,
as a decode-side program holds them, over a pool filled to the cell's
``kv_pool_util``; and the two cells' RINGS (``evabyte-ring``,
``laguna-ring``: every window layer's read of one batch over its short
tables, the rows at the cell's traced mix of window depths, and
``...-ring:full`` with every row a whole window deep); and the latent
cell's ONE-sided pool (``glm``: rows of 640 lanes, key and value both).
Prints and writes
(``chiprun_out/kv_walk.json``) ms a program and the live bytes a second
each form moved. A microbench: the cell decides (PERF.md section 6,
PR 32: the two did not agree there). Raises off a TPU: a CPU number is
no device time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llm_code_samples_tpu.decode import paged
from distributed_llm_code_samples_tpu.ops import kv_walk

# (rows, heads, KV heads, head dim, blocks a sequence, full-kind
# layers, the share of capacity that is live): the four cells with a
# full-kind layer
CELLS = {"gpt2": (12, 20, 20, 64, 64, 36, 0.365),
         "lfm2": (64, 32, 8, 64, 128, 2, 0.553),
         "laguna": (64, 48, 8, 128, 192, 3, 0.592),
         "jamba": (64, 20, 1, 128, 128, 2, 0.547)}
# (rows, heads, KV heads, head dim, ring entries, window layers, window,
# aligned rule, positions a row attends over at the cell's traced mix,
# the longest sequence): the two cells with a ring
RINGS = {"evabyte-ring": (24, 32, 32, 128, 130, 8, 2048, True, 587, 9216),
         "laguna-ring": (64, 72, 8, 128, 34, 9, 512, False, 460, 3072)}
# (rows, heads, the row's lanes, of which the values, blocks a sequence,
# layers, the share of capacity that is live): the cell with a latent pool
LATENT = {"glm": (64, 20, 640, 512, 128, 7, 0.34)}
BLOCK = 16


def _case(name, seed=0):
    """``(pool, q, tables, lengths, live bytes, (window, aligned))`` of
    one cell's batch."""
    rng = np.random.default_rng(seed)
    ring, _, fill = name.partition(":")
    rank = 0
    if ring in RINGS:
        (b, h, hkv, dh, mb, layers, window, aligned, mix,
         longest) = RINGS[ring]
        if fill == "full":          # every row a whole window deep
            depth = np.full(b, window)
        elif 2 * mix <= window:     # uniform depths of the traced mean
            depth = rng.integers(1, 2 * mix, size=b)
        else:                       # ... the rest of the rows a whole one
            whole = rng.random(b) < (2 * mix - window) / window
            depth = np.where(whole, window, rng.integers(1, window, b))
        # a sequence that deep: in a later window under the aligned
        # rule, past the window (or still inside it) under the sliding
        lengths = (depth + window * rng.integers(1, longest // window, b)
                   if aligned or fill == "full" else
                   np.where(depth < window, depth,
                            rng.integers(window, longest, b)))
        attended = depth
    else:
        if name in LATENT:
            b, h, dh, rank, mb, layers, util = LATENT[name]
            hkv = 1
        else:
            b, h, hkv, dh, mb, layers, util = CELLS[name]
        window = aligned = 0
        cap = mb * BLOCK
        lengths = np.clip(rng.uniform(0.1, 2 * util - 0.1, size=b) * cap,
                          1, cap).astype(np.int32)
        lengths[0] = cap                    # one row at the cap
        attended = lengths
    pool = paged.init_pool(layers, 1 + b * mb, hkv, BLOCK, dh, "bf16",
                           latent_rank=rank)
    key = jax.random.PRNGKey(seed)

    def side(key):      # one layer's draw in every layer: no 6 GB of bits
        return jnp.tile(jax.random.normal(key, (1, *pool.k.shape[1:]),
                                          jnp.bfloat16), (layers, 1, 1, 1))

    pool = pool._replace(k=side(key))
    if pool.v.shape[-1]:
        pool = pool._replace(v=side(jax.random.fold_in(key, 1)))
    tables = 1 + rng.permutation(b * mb).reshape(b, mb).astype(np.int32)
    q = jax.random.normal(jax.random.fold_in(key, 2), (layers, b, h, dh))
    sides = 2 if pool.v.shape[-1] else 1
    live = int(attended.sum()) * sides * hkv * dh * 2 * layers
    return (pool, q, jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32), live, (window, bool(aligned)))


def _program(read, layers, rule):
    @jax.jit
    def run(pool, q, tables, lengths):
        return sum(read(pool, l, q[l], tables, lengths, *rule)
                   for l in range(layers))
    return run


def _ms(run, args, reps=20):
    out = run(*args)
    out.block_until_ready()
    t = time.perf_counter()
    for _ in range(reps):
        out = run(*args)
    out.block_until_ready()
    return (time.perf_counter() - t) / reps * 1e3, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", default="")
    ap.add_argument("--cells", default=",".join(
        [*CELLS, *LATENT, *RINGS, *(r + ":full" for r in RINGS)]))
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("no TPU: a CPU time is no device time")
    rules = [None] + [int(s) for s in a.steps.split(",") if s]
    # the walk at EVERY cell's shape, also where ``paged.walks`` keeps
    # the plain read for what this script measured there
    paged.walks = lambda *_a, **_k: True
    rule = kv_walk.blocks_a_step
    out = {"device": jax.devices()[0].device_kind, "cells": {}}
    for name in a.cells.split(","):
        pool, q, tables, lengths, live, window = _case(name)
        layers = pool.k.shape[0]
        args = (pool, q, tables, lengths)
        plain_ms, want = _ms(
            _program(paged.gathered_decode_attn, layers, window), args)
        rows = {"live_bytes": live, "kv_lanes": pool.k.shape[-1],
                "plain_ms": plain_ms, "plain_gbs": live / plain_ms / 1e6}
        for steps in rules:
            if steps is not None:
                kv_walk.blocks_a_step = lambda *_, s=steps, **__: s
            got_steps = kv_walk.blocks_a_step(
                BLOCK, pool.k.shape[-1] * 2, tables.shape[1],
                sides=2 if pool.v.shape[-1] else 1)
            ms, got = _ms(
                _program(paged.stored_decode_attn, layers, window), args)
            kv_walk.blocks_a_step = rule
            err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
            rows[f"walk_ms@{got_steps}" + ("" if steps else "(rule)")] = ms
            rows[f"walk_gbs@{got_steps}" + ("" if steps else "(rule)")] = (
                live / ms / 1e6)
            rows[f"rel_err@{got_steps}"] = err
        out["cells"][name] = rows
        print(name, json.dumps(rows), flush=True)
        del pool, q, args, want, got
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kv_walk.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
