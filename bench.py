#!/usr/bin/env python
"""Benchmark: flagship FFN-stack training throughput on real hardware.

Prints ONE JSON line:
``{"metric": ..., "value": N, "unit": "steps/s", "vs_baseline": N, ...}``

Workload: the BASELINE config-5 shape — GPT-2-small-width FFN stack
(d_model=768, 24 layers, ffn=3072) at 8*1024 tokens/step, fp32 (the
reference's precision). ``value`` is steps/sec **per chip** of this
framework's hand-written-VJP + scan + donation path, under the better of
its two residual policies at this shape (``policy`` records which):
recompute (the reference's ``train_ffns.py:63`` default) or
saved-activation — both are first-class paths, and at the bench shape
memory is abundant so the choice is free.

``vs_baseline`` is the speedup over a *naive straight port* of the
reference's training step: plain jnp ops differentiated with jax.vjp
(all activations saved, no recompute policy, no custom-VJP structure).
>1.0 means the TPU-first design beats the port.

Extra fields:
- ``mfu``: TRUE model-FLOPs utilization of the shipped (winning) path
  against the detected chip's bf16 peak (JAX's default f32 matmul
  precision on TPU lowers to single-pass bf16 MXU ops, so bf16 peak is
  the honest denominator). The numerator is always the model's 12Tdf
  per layer; the recompute policy's extra executed matmul shows up in
  ``remat_hfu`` (hardware-FLOPs utilization), never in MFU —
  ``value * model_tflops / peak_bf16_tflops`` reproduces the headline.
- ``gap_breakdown``: where the non-MFU time goes, measured by variant
  runs at the same shape — on-chip data generation (the step's RNG),
  the SGD update, and the residual (kernel inefficiency + non-matmul
  work). BENCH_BREAKDOWN=0 skips.
- ``families``: driver-run training throughput + MFU for the flagship
  transformer and LM families (attention + head FLOPs included in the
  accounting — fwd 1x, bwd 2x, autograd saved-activation policy).
  BENCH_FAMILIES=0 skips.
- ``bf16_steps_per_sec`` / ``bf16_mfu`` / ``bf16_vs_f32``: the bf16
  mixed-precision policy (``train_single(mixed=True)`` — bf16 MXU
  inputs, f32 accumulation, bf16 residuals) at the same shape; the
  ratio >1.0 means the policy beats the fp32 headline on chip.
  BENCH_BF16=0 skips.
- ``pallas_vs_xla``: fused Pallas FFN block (``ops/pallas_ffn.py``) vs
  the remat XLA path (identical math) at the same shape, on the same
  chip. (Absent or an error string if the Pallas path failed;
  BENCH_PALLAS=0 skips.)

Where it runs: on a TPU, or not at all — without one it exits non-zero
with one line on stderr (no payload: a number from anything else is not
this metric). The peak in the MFU denominator comes from the shared
table in ``runtime/telemetry.py``, keyed by the chip's ``device_kind``;
a chip the table does not know is an error, not a guess.

Timing methodology: BOTH paths run their full schedule as ONE compiled
program (lax.scan over steps), fenced by ``jax.block_until_ready``
(``utils/benchtime.py``); best of BENCH_REPS. Never time python-loop
dispatches here.
"""

import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
from jax import lax

# Workload shape — overridable (e.g. BENCH_D=64 BENCH_LAYERS=2
# BENCH_TOKENS=128 for a quick run on the chip).
D_MODEL = int(os.environ.get("BENCH_D", 768))
N_LAYERS = int(os.environ.get("BENCH_LAYERS", 24))
TOKENS = int(os.environ.get("BENCH_TOKENS", 8 * 1024))
TIMED_STEPS = int(os.environ.get("BENCH_STEPS", 64))
LR = 0.1

FFN = 4 * D_MODEL
# Hand-counted matmul FLOPs of one step. The MODEL does 12*T*d*f per
# layer (fwd 2 matmuls = 4Tdf, bwd 4 matmuls = 8Tdf) — that is the
# useful work and the MFU numerator for every path. The recompute policy
# EXECUTES 14Tdf (it re-runs the ffn1 matmul in backward,
# train_ffns.py:63): the extra 2Tdf counts toward its HFU (hardware-
# FLOPs utilization), never toward MFU.
_MODEL_FLOPS = 12 * TOKENS * D_MODEL * FFN * N_LAYERS
_REMAT_EXEC_FLOPS = 14 * TOKENS * D_MODEL * FFN * N_LAYERS

def _peak_flops(device_kind: str) -> float:
    """bf16 peak FLOP/s from the shared table in ``runtime/telemetry.py``
    (one accounting for the bench, the CLI metrics stream, and the report
    tool). A chip the table does not know is an error."""
    from distributed_llm_code_samples_tpu.runtime.telemetry import (
        peak_flops)
    peak = peak_flops(device_kind)
    if peak is None:
        raise ValueError(
            f"no bf16 peak for device_kind {device_kind!r}: add it to "
            "runtime/telemetry.py::PEAK_BF16_FLOPS with its source")
    return peak


_METRICS_WRITER = None


def _bench_writer():
    """The unified telemetry writer (``runtime/telemetry.py``), shared
    with the CLI metrics stream: with ``BENCH_METRICS_DIR`` set, every
    labeled measurement lands as one schema-versioned ``bench`` record
    in that dir's ``metrics.jsonl`` (the report tool folds them), and
    the final payload rides the same stream — replacing bench-private
    dict plumbing as the only record of per-measurement rows."""
    global _METRICS_WRITER
    mdir = os.environ.get("BENCH_METRICS_DIR")
    if not mdir:
        return None
    if _METRICS_WRITER is None:
        try:
            from distributed_llm_code_samples_tpu.runtime.telemetry \
                import TelemetryWriter
            _METRICS_WRITER = TelemetryWriter(mdir, meta={
                "source": "bench.py",
                "shape": f"d{D_MODEL}_L{N_LAYERS}_tok{TOKENS}"
                         f"_steps{TIMED_STEPS}"})
        except Exception:  # noqa: BLE001 — telemetry never breaks the bench
            return None
    return _METRICS_WRITER


def _bench_row(label: str, value: float, **extra) -> None:
    w = _bench_writer()
    if w is None:
        return
    try:
        w.bench({"metric": label, "value": round(float(value), 4),
                 "unit": "steps/s", **extra})
    except Exception:  # noqa: BLE001
        pass


def _metric_name():
    return f"ffn{N_LAYERS}_d{D_MODEL}_tok{TOKENS}_fp32_steps_per_sec_per_chip"


def _emit(payload):
    w = _bench_writer()
    if w is not None:
        try:
            w.bench(dict(payload))
            w.close()
        except Exception:  # noqa: BLE001
            pass
    print(json.dumps(payload))
    sys.stdout.flush()


_EMITTED = False


def _emit_once(payload):
    """Emit guarded by a flag so a section's hang timer (see
    ``_guarded_section``) and the normal exit can't both print."""
    global _EMITTED
    if _EMITTED:
        return
    _EMITTED = True
    _emit(payload)


def _naive_run():
    """Straight-port baseline: autograd over plain jnp ops, activations all
    saved, scan over steps (same dispatch structure as ours for fairness)."""
    from distributed_llm_code_samples_tpu.data import batch_from_seed

    def fwd(params, x):
        y = x
        for l in range(N_LAYERS):
            h = y @ params.w1[l].T
            y = jnp.maximum(h, 0.0) @ params.w2[l].T
        return y

    def step(params, seed):
        x, dloss_dx = batch_from_seed(seed, TOKENS, D_MODEL, jnp.float32)
        _, vjp = jax.vjp(lambda p: fwd(p, x), params)
        grads = vjp(dloss_dx)[0]
        return jax.tree_util.tree_map(lambda p, g: p - LR * g, params, grads)

    @jax.jit
    def run(params, seeds):
        return lax.scan(lambda p, s: (step(p, s), None), params, seeds)[0]

    return run


def _sync(tree) -> None:
    """The completion fence — shared methodology, see utils/benchtime.py."""
    from distributed_llm_code_samples_tpu.utils.benchtime import sync
    sync(tree)


def main():
    from distributed_llm_code_samples_tpu.runtime.init import (
        describe_devices, enable_compile_cache)
    enable_compile_cache()
    device = describe_devices()
    if device["platform"] != "tpu":
        print(f"bench: no TPU (JAX's first device is "
              f"{device['platform']!r}); nothing measured",
              file=sys.stderr)
        sys.exit(1)
    device_kind = device["kind"]
    peak = _peak_flops(device_kind)

    from distributed_llm_code_samples_tpu.data import make_seed_schedule
    from distributed_llm_code_samples_tpu.models import init_ffn_stack
    from distributed_llm_code_samples_tpu.parallel import train_single

    params = init_ffn_stack(jax.random.PRNGKey(0), D_MODEL, N_LAYERS)
    # warm schedule must have the SAME length as the timed one: the jitted
    # runs cache on the scan trip count, and a shape mismatch would put a
    # full recompile inside the timed window
    warm = make_seed_schedule(TIMED_STEPS, random_seed=1)
    timed = make_seed_schedule(TIMED_STEPS, random_seed=2)

    # best-of-5: run-to-run jitter is comparable to the true
    # ours-vs-naive gap at this MXU-saturated shape — more reps tighten
    # both bests toward their real ceilings
    reps = int(os.environ.get("BENCH_REPS", 5))

    from distributed_llm_code_samples_tpu.utils.benchtime import (
        steps_per_sec)

    def measure(run_fn, p0, label=None):
        sps = steps_per_sec(run_fn, p0, warm, timed, reps, TIMED_STEPS)
        if label:
            _bench_row(label, sps)
        return sps

    # both residual policies are first-class framework paths: remat is
    # the reference's memory-lean recompute (train_ffns.py:63), saved
    # skips the recompute matmul. At the bench shape memory is abundant,
    # so the policy is a free choice — the headline value is the better
    # of the two.
    remat_sps = measure(
        lambda p, s: train_single(p, s, TOKENS, D_MODEL, lr=LR), params,
        label="single_remat")
    saved_sps = measure(
        lambda p, s: train_single(p, s, TOKENS, D_MODEL, lr=LR,
                                  remat=False), params,
        label="single_saved")
    naive_sps = measure(_naive_run(), params, label="naive_port")

    policy = "saved" if saved_sps >= remat_sps else "remat"
    ours_sps = max(saved_sps, remat_sps)
    # Honest MFU: every path's numerator is the MODEL's 12Tdf — so the
    # headline "mfu" is the shipped (winning) policy's true model-FLOPs
    # utilization and value * model_tflops / peak reproduces it exactly.
    # The recompute policy's EXECUTED 14Tdf is reported as remat_hfu.
    remat_mfu = remat_sps * _MODEL_FLOPS / peak
    saved_mfu = saved_sps * _MODEL_FLOPS / peak
    remat_hfu = remat_sps * _REMAT_EXEC_FLOPS / peak
    naive_mfu = naive_sps * _MODEL_FLOPS / peak

    payload = {
        "metric": _metric_name(),
        "value": round(ours_sps, 4),
        "unit": "steps/s",
        "vs_baseline": round(ours_sps / naive_sps, 4),
        "mfu": round(max(remat_mfu, saved_mfu), 4),
        "policy": policy,
        "model_tflops": round(_MODEL_FLOPS / 1e12, 4),
        "remat_exec_tflops": round(_REMAT_EXEC_FLOPS / 1e12, 4),
        "platform": device["platform"],
        "device_kind": device_kind,
        "device_count": device["count"],
        "peak_bf16_tflops": round(peak / 1e12, 1),
        "remat_steps_per_sec": round(remat_sps, 4),
        "remat_mfu": round(remat_mfu, 4),
        "remat_hfu": round(remat_hfu, 4),
        "saved_steps_per_sec": round(saved_sps, 4),
        "saved_mfu": round(saved_mfu, 4),
        "naive_steps_per_sec": round(naive_sps, 4),
        "naive_mfu": round(naive_mfu, 4),
    }

    def _guarded_section(enabled_env: str, timeout_env: str,
                         default_timeout: float, label: str, fn):
        """Run an extras section so its failure or hang can never cost
        the headline payload: on hang the watchdog emits the payload in
        hand and exits; on error the section records an error string."""
        if os.environ.get(enabled_env, "1") == "0":
            return

        def bail_with_headline():
            payload[label] = f"error: {label} measurement hung"
            _emit_once(payload)
            os._exit(0)

        guard = threading.Timer(
            float(os.environ.get(timeout_env, default_timeout)),
            bail_with_headline)
        guard.daemon = True
        guard.start()
        try:
            fn()
        except Exception as exc:  # noqa: BLE001
            payload[label] = (
                f"error: {type(exc).__name__}: {str(exc)[:200]}")
        finally:
            guard.cancel()

    def _breakdown():
        """Attribute the non-MFU time of the SHIPPED (winning-policy)
        path: variant scans at the same shape isolate on-chip data
        generation and the SGD update; the rest is kernel residual
        (non-matmul work + matmul inefficiency — for the remat policy
        this includes its executed-but-not-model recompute matmul)."""
        from distributed_llm_code_samples_tpu.data import batch_from_seed
        from distributed_llm_code_samples_tpu.ops.ffn import (
            ffn_block, ffn_block_saved)
        from distributed_llm_code_samples_tpu.ops.stack import stack_grads

        block = ffn_block_saved if policy == "saved" else ffn_block
        t_full = TIMED_STEPS / ours_sps  # the shipped step, measured

        def grads_of(p, x, dy):
            return type(p)(*stack_grads(p.w1, p.w2, x, dy,
                                        block=block)[1])

        # (a) fwd+bwd only: near-fixed batch, grads accumulated, no
        # update. The inputs must depend on the scanned seed or XLA's
        # loop-invariant code motion hoists the whole fwd+bwd out of the
        # scan and times ONE step; a seed-scaled epsilon (one fused
        # multiply over [T, d], no RNG) keeps it loop-variant.
        x0, dy0 = batch_from_seed(jnp.int32(7), TOKENS, D_MODEL,
                                  jnp.float32)

        @jax.jit
        def run_base(p, seeds):
            def body(acc, s):
                x = x0 * (1.0 + 1e-12 * s.astype(jnp.float32))
                g = grads_of(p, x, dy0)
                return jax.tree_util.tree_map(jnp.add, acc, g), None
            return lax.scan(body, jax.tree_util.tree_map(
                jnp.zeros_like, p), seeds)[0]

        # (b) + per-step data generation (the shipped step's RNG)
        @jax.jit
        def run_data(p, seeds):
            def body(acc, s):
                x, dy = batch_from_seed(s, TOKENS, D_MODEL, jnp.float32)
                g = grads_of(p, x, dy)
                return jax.tree_util.tree_map(jnp.add, acc, g), None
            return lax.scan(body, jax.tree_util.tree_map(
                jnp.zeros_like, p), seeds)[0]

        def time_of(run_fn):
            out = run_fn(params, warm)
            _sync(out)
            best = None
            for _ in range(reps):
                t0 = time.perf_counter()
                out = run_fn(params, timed)
                _sync(out)
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            return best

        t_base = time_of(run_base)
        t_data = time_of(run_data)

        ideal = TIMED_STEPS * _MODEL_FLOPS / peak
        payload["gap_breakdown"] = {
            "policy": policy,
            "ideal_s": round(ideal, 4),
            "fwd_bwd_s": round(t_base, 4),
            "datagen_s": round(max(t_data - t_base, 0.0), 4),
            "update_s": round(max(t_full - t_data, 0.0), 4),
            "kernel_residual_s": round(max(t_base - ideal, 0.0), 4),
            "full_step_s": round(t_full, 4),
            "note": f"seconds per {TIMED_STEPS}-step program; "
                    "full ~= fwd_bwd + datagen + update; "
                    "kernel_residual = fwd_bwd - ideal",
        }

    _guarded_section("BENCH_BREAKDOWN", "BENCH_BREAKDOWN_TIMEOUT", 600,
                     "gap_breakdown", _breakdown)

    def _families():
        """Driver-run hardware numbers for the flagship families. FLOP
        accounting (per layer, per batch element): attention projections
        8Td^2, scores+AV 2T^2d — HALVED because the trained models are
        causal and only the lower triangle is useful work (the same 0.5
        causal factor bench_attention.py applies; one convention
        everywhere keeps the 'honest MFU' headline honest); FFN 16Td^2;
        LM head 2TdV; fwd 1x + bwd 2x model FLOPs. Note: when a
        recompute policy wins (flash attention re-derives score tiles,
        the fused head re-derives logit tiles in its backward), the
        EXECUTED FLOPs exceed this model-FLOP numerator — family mfu
        stays model-FLOPs-based (the honest-MFU convention), so it
        understates hardware utilization for those winners."""
        from distributed_llm_code_samples_tpu.models import (
            init_lm, init_transformer)
        from distributed_llm_code_samples_tpu.parallel import (
            train_lm_single, train_transformer_single)

        fam_d = int(os.environ.get("BENCH_FAM_D", 768))
        fam_L = int(os.environ.get("BENCH_FAM_LAYERS", 12))
        fam_H = int(os.environ.get("BENCH_FAM_HEADS", 12))
        fam_T = int(os.environ.get("BENCH_FAM_SEQ", 512))
        fam_B = int(os.environ.get("BENCH_FAM_BATCH", 16))
        fam_V = int(os.environ.get("BENCH_FAM_VOCAB", 50304))
        toks = fam_B * fam_T

        block_flops = 3 * fam_B * fam_L * (
            8 * fam_T * fam_d ** 2 + 2 * fam_T ** 2 * fam_d
            + 16 * fam_d ** 2 * fam_T)
        head_flops = 3 * 2 * toks * fam_d * fam_V

        # Attention policy measured, not taken on faith (same stance as the
        # headline's remat/saved/naive choice): the quadratic oracle
        # materializes B*H*T^2 scores in HBM (~200 MB/layer here) while
        # the flash kernels keep tiles in VMEM — at this shape the r04
        # chip said flash wins; whichever wins TODAY ships as the
        # family number, both are reported.
        fams = {}
        tf = init_transformer(jax.random.PRNGKey(3), fam_d, fam_L)
        by_attn = {}
        for impl in (None, "flash"):
            by_attn[impl or "oracle"] = measure(
                lambda p, s, _i=impl: train_transformer_single(
                    p, s, toks, fam_d, lr=LR, seq_len=fam_T,
                    n_heads=fam_H, attn_impl=_i), tf,
                label=f"transformer_{impl or 'oracle'}")
        attn_win = max(by_attn, key=by_attn.get)
        sps = by_attn[attn_win]
        # the transformer bf16 policy at the winning attn impl (the
        # same precision axis the LM family measures)
        tf_mixed_sps = measure(
            lambda p, s: train_transformer_single(
                p, s, toks, fam_d, lr=LR, seq_len=fam_T, n_heads=fam_H,
                attn_impl=None if attn_win == "oracle" else attn_win,
                mixed=True), tf, label="transformer_mixed")
        fams["transformer"] = {
            "steps_per_sec": round(sps, 4),
            "mfu": round(sps * block_flops / peak, 4),
            "model_tflops": round(block_flops / 1e12, 4),
            "attn": attn_win,
            "oracle_steps_per_sec": round(by_attn["oracle"], 4),
            "flash_steps_per_sec": round(by_attn["flash"], 4),
            "mixed_steps_per_sec": round(tf_mixed_sps, 4),
            "mixed_vs_f32": round(tf_mixed_sps / sps, 4),
            "shape": f"d{fam_d}_L{fam_L}_H{fam_H}_T{fam_T}_B{fam_B}",
        }
        if tf_mixed_sps > sps:
            fams["transformer"]["steps_per_sec"] = round(tf_mixed_sps, 4)
            fams["transformer"]["mfu"] = round(
                tf_mixed_sps * block_flops / peak, 4)
            fams["transformer"]["attn"] = attn_win + "+mixed"
        del tf

        # The LM adds a second measured policy axis: the tied head.
        # oracle = materialized [N, V] logits + saved-softmax xent
        # residual (~1.65 GB each at this shape); fused = the Pallas
        # head (ops/pallas_xent.py) that keeps logit tiles in VMEM and
        # recomputes them in the backward. 2x2 grid, winner ships.
        lm = init_lm(jax.random.PRNGKey(4), fam_V, fam_d, fam_L,
                     max_seq_len=fam_T)
        by_policy = {}
        for a_impl in (None, "flash"):
            for h_impl in (None, "fused"):
                key = f"{a_impl or 'oracle'}+{h_impl or 'oracle'}"
                by_policy[key] = measure(
                    lambda p, s, _a=a_impl, _h=h_impl: train_lm_single(
                        p, s, toks, fam_d, lr=LR, seq_len=fam_T,
                        n_heads=fam_H, attn_impl=_a, head_impl=_h), lm,
                    label=f"lm_{key}")
        win = max(by_policy, key=by_policy.get)
        sps = by_policy[win]
        # the LM bf16 policy (bf16 trunk/residuals, f32 head+master) at
        # the winning attn x head combo: one extra measurement, reported
        # as its own ratio (a separate axis from the 2x2 grid)
        win_a, win_h = win.split("+")
        mixed_sps = measure(
            lambda p, s: train_lm_single(
                p, s, toks, fam_d, lr=LR, seq_len=fam_T, n_heads=fam_H,
                attn_impl=None if win_a == "oracle" else win_a,
                head_impl=None if win_h == "oracle" else win_h,
                mixed=True), lm, label="lm_mixed")
        fams["lm"] = {
            "steps_per_sec": round(sps, 4),
            "mfu": round(sps * (block_flops + head_flops) / peak, 4),
            "model_tflops": round((block_flops + head_flops) / 1e12, 4),
            "policy": win,  # "<attn>+<head>"
            "by_policy": {k: round(v, 4) for k, v in by_policy.items()},
            "mixed_steps_per_sec": round(mixed_sps, 4),
            "mixed_mfu": round(
                mixed_sps * (block_flops + head_flops) / peak, 4),
            "mixed_vs_f32": round(mixed_sps / sps, 4),
            "shape": (f"d{fam_d}_L{fam_L}_H{fam_H}_T{fam_T}_B{fam_B}"
                      f"_V{fam_V}"),
        }
        if mixed_sps > sps:
            # the headline family number is the best measured policy —
            # including the precision axis
            fams["lm"]["steps_per_sec"] = round(mixed_sps, 4)
            fams["lm"]["mfu"] = fams["lm"]["mixed_mfu"]
            fams["lm"]["policy"] = win + "+mixed"
            sps = mixed_sps
        # Where the LM family's non-MFU time lives (VERDICT r4 #3): the
        # transformer family ran the SAME d/L/H/T/B shape AND measured
        # both attn policies, so the blocks reference is the
        # transformer step under the LM WINNER'S OWN attn policy, and
        # the decomposition uses the f32 by_policy winner (never the
        # bf16-trunk run — its trunk speedup would masquerade as
        # reduced head cost). flop_shares says where the model FLOPs
        # go (the T^2 score share is why flash matters more at long T).
        proj_f = 3 * fam_B * fam_L * 8 * fam_T * fam_d ** 2
        score_f = 3 * fam_B * fam_L * 2 * fam_T ** 2 * fam_d
        ffn_f = 3 * fam_B * fam_L * 16 * fam_d ** 2 * fam_T
        total_f = block_flops + head_flops
        f32_sps = by_policy[win]
        tf_sps = by_attn[win_a]
        blocks_s = 1.0 / tf_sps
        head_s = max(1.0 / f32_sps - blocks_s, 0.0)
        fams["lm"]["gap_breakdown"] = {
            "blocks_s": round(blocks_s, 5),
            "blocks_ideal_s": round(block_flops / peak, 5),
            "head_embed_s": round(head_s, 5),
            "head_ideal_s": round(head_flops / peak, 5),
            "note": (f"per-step seconds at f32 (lm {win}): blocks_s "
                     f"is the transformer family's measured step with "
                     f"attn={win_a} at the same shape; head_embed_s = "
                     "lm f32 step - blocks_s (head + embedding + "
                     "final LN + softmax xent)"),
        }
        fams["lm"]["flop_shares"] = {
            "attn_proj": round(proj_f / total_f, 3),
            "attn_scores": round(score_f / total_f, 3),
            "ffn": round(ffn_f / total_f, 3),
            "head": round(head_flops / total_f, 3),
        }
        payload["families"] = fams

    # 2700s: the section now runs 6 full measurements (2 transformer
    # attn policies + the 2x2 LM attn x head grid) vs the original 2
    _guarded_section("BENCH_FAMILIES", "BENCH_FAMILIES_TIMEOUT", 2700,
                     "families", _families)

    # bf16 mixed precision (VERDICT r3 #3): the TPU-first policy — bf16
    # matmul inputs on the MXU, f32 params/grads/accumulation, bf16
    # residuals (half the activation HBM traffic). Same model FLOPs, same
    # bf16-peak denominator, so bf16_mfu compares directly against the
    # headline mfu; bf16_vs_f32 > 1.0 means the policy pays off on chip.
    def _bf16():
        # Residual policy measured, like the f32 headline: remat stashes
        # only the bf16 block input (half the f32 remat policy's only
        # residual traffic — the one single-chip lever bf16 has when the
        # MXU is saturated, since default-precision f32 matmuls are
        # single bf16 passes already); saved keeps the bf16 post-ReLU.
        by_pol = {}
        for pol, flag in (("remat", True), ("saved", False)):
            by_pol[pol] = measure(
                lambda p, s, _r=flag: train_single(
                    p, s, TOKENS, D_MODEL, lr=LR, mixed=True, remat=_r),
                params, label=f"bf16_{pol}")
        pol = max(by_pol, key=by_pol.get)
        bf16_sps = by_pol[pol]
        payload["bf16_steps_per_sec"] = round(bf16_sps, 4)
        payload["bf16_mfu"] = round(bf16_sps * _MODEL_FLOPS / peak, 4)
        payload["bf16_vs_f32"] = round(bf16_sps / ours_sps, 4)
        payload["bf16_policy"] = pol
        payload["bf16_remat_steps_per_sec"] = round(by_pol["remat"], 4)
        payload["bf16_saved_steps_per_sec"] = round(by_pol["saved"], 4)

    _guarded_section("BENCH_BF16", "BENCH_BF16_TIMEOUT", 900,
                     "bf16_vs_f32", _bf16)

    # Pallas fused-FFN path vs the XLA path, same chip, same shape
    # (VERDICT r1 #3): vs the remat XLA path — both recompute, so the
    # ratio isolates hand-scheduling vs XLA at identical math. r5: the
    # kernels run the flash recipe (bf16 MXU operands); with
    # BENCH_PALLAS_SWEEP=1 a tile sweep runs on chip (jax.clear_caches
    # between points so the env-read tile defaults re-trace) and the
    # best combo ships as the ratio.
    def _pallas():
        def measure_pallas():
            return measure(
                lambda p, s: train_single(p, s, TOKENS, D_MODEL, lr=LR,
                                          use_pallas=True), params,
                label="pallas_ffn")

        if os.environ.get("BENCH_PALLAS_SWEEP", "0") == "1":
            combos = [(256, 512, 256), (512, 512, 256),
                      (512, 1024, 512), (1024, 512, 256),
                      (256, 1024, 512)]
            grid = {}
            # restore the caller's pre-sweep tile envs afterwards — an
            # operator pinning PALLAS_FFN_* for the whole bench run must
            # not have the sweep silently strip the pin
            sweep_envs = ("PALLAS_FFN_BT", "PALLAS_FFN_BF",
                          "PALLAS_FFN_DW_BF")
            saved_envs = {v: os.environ.get(v) for v in sweep_envs}
            for bt, bf, dw_bf in combos:
                os.environ["PALLAS_FFN_BT"] = str(bt)
                os.environ["PALLAS_FFN_BF"] = str(bf)
                os.environ["PALLAS_FFN_DW_BF"] = str(dw_bf)
                jax.clear_caches()
                try:
                    grid[f"bt{bt}_bf{bf}_dwbf{dw_bf}"] = round(
                        measure_pallas(), 4)
                except Exception as exc:  # noqa: BLE001
                    grid[f"bt{bt}_bf{bf}_dwbf{dw_bf}"] = (
                        f"error: {type(exc).__name__}: {str(exc)[:80]}")
            for v, old in saved_envs.items():
                if old is None:
                    os.environ.pop(v, None)
                else:
                    os.environ[v] = old
            jax.clear_caches()
            numeric = {k: v for k, v in grid.items()
                       if isinstance(v, float)}
            payload["pallas_tile_sweep"] = grid
            pallas_sps = max(numeric.values()) if numeric else 0.0
            if numeric:
                payload["pallas_best_tiles"] = max(numeric,
                                                   key=numeric.get)
        else:
            pallas_sps = measure_pallas()
        payload["pallas_vs_xla"] = round(pallas_sps / remat_sps, 4)
        payload["pallas_steps_per_sec"] = round(pallas_sps, 4)

    _guarded_section("BENCH_PALLAS", "BENCH_PALLAS_TIMEOUT", 600,
                     "pallas_vs_xla", _pallas)

    _emit_once(payload)


if __name__ == "__main__":
    main()
