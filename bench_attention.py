#!/usr/bin/env python
"""Flash attention vs the quadratic XLA oracle on the real chip, long T.

The FFN Pallas kernels lost to XLA at the bench shape and said so
(``ops/pallas_ffn.py`` measured verdict). Attention is where hand fusion
has a real chance: the quadratic oracle (``models.attention.mha``)
materializes the ``[T, T]`` scores in HBM, so at long T it is
HBM-bandwidth-bound; the flash kernels (``ops/pallas_attention.py``)
keep score tiles in VMEM. This bench runs BOTH through a full
fwd+bwd step (the training-relevant direction: the flash backward
recomputes score tiles from ``q, k, lse``) at T in {1k, 4k, 8k} and
reports the per-T ratio.

Emits one JSON line:
``{"metric": "attn_pallas_vs_xla", ..., "per_T": {"1024": r, ...}}``
(ratio > 1.0: flash wins). Written to ``ATTENTION_r03.json`` when
``ATTN_ARTIFACT`` is set.

Timing: a single fwd+bwd at T=1024 is ~1 ms of kernel work, so
one-dispatch-per-rep timing measures the per-program dispatch floor,
not the kernels. This script times K grad-steps chained inside ONE jitted
``lax.scan`` program (each step's inputs perturbed by the previous
step's gradients, so the chain is sequentially dependent and cannot be
DCE'd or reordered), auto-calibrates K per (path, T) so the timed
program runs ~ATTN_TARGET_S seconds, measures the dispatch floor with a
null program, and reports floor-subtracted per-step times.

Run: ``python bench_attention.py`` (real TPU). Smoke:
``BENCH_PLATFORM=cpu ATTN_TS=128 python bench_attention.py``
(interpret-mode Pallas — slow, correctness only).
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

if os.environ.get("BENCH_PLATFORM"):
    jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])

H = int(os.environ.get("ATTN_HEADS", 8))
DH = int(os.environ.get("ATTN_DH", 64))
TS = tuple(int(t) for t in
           os.environ.get("ATTN_TS", "512,1024,4096,8192").split(","))
REPS = int(os.environ.get("ATTN_REPS", 5))
CAUSAL = os.environ.get("ATTN_CAUSAL", "1") != "0"
# target wall-clock of each timed program; K inner steps are calibrated
# to hit it so the dispatch floor stays a small fraction of the timing
TARGET_S = float(os.environ.get("ATTN_TARGET_S", 1.2))
# fixed inner step count (skips calibration) — for CPU smoke runs
INNER = int(os.environ.get("ATTN_INNER", 0))


def _flops(t: int) -> float:
    # matmul FLOPs of one attention fwd+bwd at seq len t: fwd QK^T + AV =
    # 2 * 2*t^2*dh per head; bwd ~2x fwd (dS, dQ, dK, dV recompute
    # included for flash — report against the MODEL's 3x accounting,
    # same numerator for both paths so the ratio is apples-to-apples)
    factor = 0.5 if CAUSAL else 1.0  # causal halves the useful tiles
    # (bench.py's `families` section uses the SAME causal convention —
    # 2T^2d score FLOPs, not 4T^2d — so MFU numbers compare directly)
    return 3 * 2 * 2 * t * t * DH * H * factor


def main() -> int:
    from distributed_llm_code_samples_tpu.runtime.init import (
        describe_devices, enable_compile_cache)
    enable_compile_cache()
    describe_devices()
    from distributed_llm_code_samples_tpu.models.attention import mha
    from distributed_llm_code_samples_tpu.ops.pallas_attention import (
        flash_mha)

    interpret = jax.default_backend() != "tpu"
    per_t, per_t_detail = {}, {}

    # Dispatch floor: best-of timing of a null program (one scalar
    # in, one scalar readback). Subtracted from every program timing.
    # The operand is staged to the device BEFORE the loop so each rep
    # pays exactly the one round-trip the timed programs pay — a
    # host-side jnp.float32(...) per rep would add a device_put and
    # bias the floor high (and the subtracted times low).
    null = jax.jit(lambda x: x + 1.0)
    one = jax.device_put(jnp.float32(1.0))
    float(null(one))
    floor = None
    for _ in range(max(REPS, 5)):
        t0 = time.perf_counter()
        float(null(one))
        dt = time.perf_counter() - t0
        floor = dt if floor is None else min(floor, dt)

    def make_prog(fn, n):
        # One jitted program of n sequentially-dependent grad steps.
        # Loss sums over ALL of q/k/v cotangents — grad wrt q alone
        # would let XLA DCE the dK/dV backward matmuls. Each step feeds
        # eps*grads back into the next step's inputs, so the scan chain
        # is a true data dependence (no reordering, no elision); the
        # perturbation is numerically irrelevant and the elementwise
        # cost is negligible vs the attention matmuls at T >= 1024.
        g = jax.grad(lambda qkv: jnp.sum(fn(*qkv)))

        def body(c, _):
            dq, dk, dv = g(c)
            q, k, v = c
            return (q + 1e-30 * dq, k + 1e-30 * dk, v + 1e-30 * dv), ()

        def prog(qkv):
            c, _ = jax.lax.scan(body, qkv, None, length=n)
            return c[0][0, 0, 0] + c[1][0, 0, 0] + c[2][0, 0, 0]

        return jax.jit(prog)

    def prog_time(p, qkv):
        float(p(qkv))  # compile + fence
        best = None
        for _ in range(REPS):
            t0 = time.perf_counter()
            float(p(qkv))
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    def step_time(fn, q, k, v):
        """Floor-subtracted seconds per fwd+bwd step, plus the K used
        and the achieved program duration (so a capped K — where the
        floor stays a visible fraction of the window — is
        distinguishable in the artifact from a converged one)."""
        qkv = (q, k, v)
        if INNER:
            n = INNER
        else:
            n0 = 8
            t0 = prog_time(make_prog(fn, n0), qkv)
            per = max((t0 - floor) / n0, 1e-7)
            # cap high enough that small-T points still reach the
            # target window (T=1024 steps are ~0.1 ms; the old 4096 cap
            # left the floor at ~16% of the timing there)
            n = int(max(8, min(65536, round(TARGET_S / per))))
        tn = prog_time(make_prog(fn, n), qkv)
        return max(tn - floor, 1e-9) / n, n, tn

    for t in TS:
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(t), 3)
        q = jax.random.normal(kq, (H, t, DH), jnp.float32)
        k = jax.random.normal(kk, (H, t, DH), jnp.float32)
        v = jax.random.normal(kv, (H, t, DH), jnp.float32)
        try:
            t_xla, n_xla, s_xla = step_time(
                lambda q, k, v: mha(q, k, v, CAUSAL), q, k, v)
            t_flash, n_flash, s_flash = step_time(
                lambda q, k, v: flash_mha(q, k, v, CAUSAL, interpret),
                q, k, v)
            per_t[str(t)] = round(t_xla / t_flash, 4)
            per_t_detail[str(t)] = {
                "xla_ms": round(t_xla * 1e3, 3),
                "flash_ms": round(t_flash * 1e3, 3),
                "xla_tflops": round(_flops(t) / t_xla / 1e12, 2),
                "flash_tflops": round(_flops(t) / t_flash / 1e12, 2),
                "inner_steps": {"xla": n_xla, "flash": n_flash},
                "program_s": {"xla": round(s_xla, 3),
                              "flash": round(s_flash, 3)},
                "floor_frac": {"xla": round(floor / s_xla, 3),
                               "flash": round(floor / s_flash, 3)},
            }
        except Exception as exc:  # noqa: BLE001
            per_t[str(t)] = f"error: {type(exc).__name__}: {str(exc)[:160]}"

    # Small-T tile sweep (VERDICT r4 #3: flash loses at short T with
    # the long-T-tuned default tiles): re-measure flash at the short
    # lengths under a grid of fwd/bwd tile combos — the env defaults
    # are read at trace time, so jax.clear_caches() re-tiles without
    # re-exec — and record the best ratio per T against the already-
    # measured XLA time. ATTN_SWEEP=0 skips (CPU smoke).
    sweep_out = {}
    if os.environ.get("ATTN_SWEEP", "1") != "0" and not interpret:
        combos = [(1024, 1024, 512, 512), (512, 512, 512, 512),
                  (512, 512, 256, 256), (256, 256, 256, 256)]
        envs = ("FLASH_BLOCK_Q", "FLASH_BLOCK_K",
                "FLASH_BWD_BLOCK_Q", "FLASH_BWD_BLOCK_K")
        sweep_ts = [int(t) for t in os.environ.get(
            "ATTN_SWEEP_TS", "512,1024").split(",") if t]
        for t in sweep_ts:
            base = per_t_detail.get(str(t), {})
            xla_ms = base.get("xla_ms")
            if not isinstance(xla_ms, float):
                continue
            kq, kk, kv = jax.random.split(jax.random.PRNGKey(t), 3)
            q = jax.random.normal(kq, (H, t, DH), jnp.float32)
            k = jax.random.normal(kk, (H, t, DH), jnp.float32)
            v = jax.random.normal(kv, (H, t, DH), jnp.float32)
            grid = {}
            # restore the caller's pre-sweep FLASH_BLOCK_* pins after
            # the grid (the bench.py sweep discipline): popping them
            # unconditionally would strip an operator's run-wide pin
            saved_envs = {name: os.environ.get(name) for name in envs}
            for combo in combos:
                if combo[0] > t:
                    continue  # _pick_block would clamp to the default
                for name, val in zip(envs, combo):
                    os.environ[name] = str(val)
                jax.clear_caches()
                try:
                    t_f, _, _ = step_time(
                        lambda q, k, v: flash_mha(q, k, v, CAUSAL,
                                                  interpret), q, k, v)
                    grid["x".join(map(str, combo))] = round(
                        (xla_ms / 1e3) / t_f, 4)
                except Exception as exc:  # noqa: BLE001
                    grid["x".join(map(str, combo))] = (
                        f"error: {type(exc).__name__}: {str(exc)[:80]}")
            for name, old in saved_envs.items():
                if old is None:
                    os.environ.pop(name, None)
                else:
                    os.environ[name] = old
            jax.clear_caches()
            nums = {k2: v for k2, v in grid.items()
                    if isinstance(v, float)}
            if nums:
                best = max(nums, key=nums.get)
                sweep_out[str(t)] = {"grid": grid, "best_tiles": best,
                                     "best_ratio": nums[best]}
                # the headline per-T ratio is the best measured config
                if (isinstance(per_t.get(str(t)), float)
                        and nums[best] > per_t[str(t)]):
                    per_t[str(t)] = nums[best]

    numeric = [v for v in per_t.values() if isinstance(v, float)]
    payload = {
        "metric": "attn_pallas_vs_xla",
        "value": max(numeric) if numeric else 0.0,
        "unit": "x (flash speedup over quadratic XLA, fwd+bwd)",
        "per_T": per_t,
        "detail": per_t_detail,
        "small_t_tile_sweep": sweep_out,
        "dispatch_floor_ms": round(floor * 1e3, 3),
        "timing": ("scanned dependent grad-steps per program, "
                   "floor-subtracted, best-of-REPS"),
        "shape": f"H{H}_dh{DH}_causal{int(CAUSAL)}",
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(payload))
    artifact = os.environ.get("ATTN_ARTIFACT")
    if artifact:
        with open(artifact, "w") as f:
            json.dump(payload, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
