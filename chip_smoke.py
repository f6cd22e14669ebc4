#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one chip: serve, hybrid, latent + experts, conv + experts, train
    python chip_smoke.py --chips 4  # four chips: the cross-chip paths only

One process. It imports JAX once and drives the program through the
entry point a user calls — ``train_ffns.py`` ->
``distributed_llm_code_samples_tpu.cli.main(argv)`` — in-process, so no
child ever needs the chip this process holds. It never picks a
platform, never fakes devices, never asks for interpret mode; it fails
(non-zero exit, no result line) unless JAX's first device is a TPU.

Every phase raises on failure. Each prints one JSON object (phase,
argv, wall and compile seconds, tokens or steps, peak device bytes);
the LAST line of stdout is the result:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The sizes below are the real ones (GPT-2 small at its published
widths, the paper's FFN stack at d=8192). ``tests/test_chip_smoke.py``
rehearses the same control flow on the CPU by shrinking them in the
test — that is the only way they change.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import shutil
import sys
import time

import jax

from distributed_llm_code_samples_tpu import cli
from distributed_llm_code_samples_tpu.parallel import launcher
from distributed_llm_code_samples_tpu.runtime import native, telemetry
from distributed_llm_code_samples_tpu.runtime.init import (
    describe_devices, enable_compile_cache)

HERE = os.path.dirname(os.path.abspath(__file__))

# GPT-2 small at its published widths; weights from the seed, f32.
SERVE = dict(model=["-d", "768", "-l", "12", "--heads", "12",
                    "--max_seq_len", "1024"],
             vocab=50257,
             # the vocab-parallel head wants V divisible by the model
             # axis: GPT-2's padded vocabulary, on both sides of the
             # --tp comparison
             vocab_tp=50304,
             prompt_lens="48,137,290,512", max_new=32)
# The paper's model at the paper's width (train_ffns.py's docstring
# shape; 2 GiB of f32 parameters), then BASELINE.json config 5's shape,
# the one with chip history. Both at the reference's own learning rate
# (1e-5, the default): gradients are SUMS over 8192 tokens, and at
# --lr 0.1 the d=8192 run was seen on the v5e to reach weights of 1e12
# in 8 steps and a gradient norm that overflows f32.
# ``moves``: whether 8 steps move layer 0's printed corner. They cannot
# at config 5's depth: 24 un-normalised layers at init scale 0.02 shrink
# the signal ~0.4x each, so every gradient is below f32 resolution of
# the weights (norm ~1.5e-4 over 113M parameters) — that shape is a
# step-rate workload, and is held to a finite, non-zero gradient only.
TRAIN = [dict(argv=["-d", "8192", "-l", "1", "-n", "1024", "-bs", "8",
                    "-s", "8"], moves=True),
         dict(argv=["-d", "768", "-l", "24", "-n", "1024", "-bs", "8",
                    "-s", "8"], moves=False)]
# the LM trainer across four chips (vocab-parallel Megatron TP)
TRAIN_LM = ["-d", "768", "-l", "12", "--heads", "12", "--vocab", "50304",
            "-n", "1024", "-bs", "8", "-s", "4", "--lr", "0.1"]

# A hybrid in small (``models/hybrid_lm.py``): Mamba-1 and attention
# layers in one stack, the shape of the benchmark's jamba2-3b-serve at
# toy widths, as a published-style config.json. A toy by intent: the
# published widths are the benchmark's cell; this phase proves the
# recurrent state beside the paged KV is right ON THE CHIP, against the
# plain reference, which the CPU's tests cannot.
HYBRID = dict(config=dict(
    model_type="jamba", hidden_size=64, intermediate_size=128,
    mamba_expand=2, mamba_d_state=4, mamba_d_conv=4, mamba_dt_rank=8,
    mamba_conv_bias=True, mamba_proj_bias=False, num_hidden_layers=6,
    attn_layer_period=3, attn_layer_offset=1, num_attention_heads=4,
    num_key_value_heads=1, vocab_size=96, rms_norm_eps=1e-6,
    max_position_embeddings=256, num_experts=1, hidden_act="silu",
    tie_word_embeddings=True, sliding_window=None, initializer_range=0.2),
    prompt_lens="5,19,40,64,11", max_new=24, max_slots=3,
    # a served token has to be the reference's first wherever its top
    # two logits lie further apart than this (float32 on both sides)
    tie=1e-3, phase="serve_hybrid", reference="jamba_lm_reference",
    driver="jamba_engine_driver")
# Latent attention and sparse experts in small (``models/mla_moe_lm.py``):
# the shape of the benchmark's glm47-flash-serve at toy widths — a latent
# cache row of 32 + 8 lanes, one dense layer, three layers of 16 experts
# with the top 4 and a shared one. The same proof, for the latent rows in
# the pool and the dropless expert layer.
LATENT = dict(config=dict(
    model_type="glm4_moe_lite", hidden_size=64, intermediate_size=160,
    moe_intermediate_size=48, num_attention_heads=4, num_key_value_heads=4,
    n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4,
    routed_scaling_factor=1.8, first_k_dense_replace=1,
    num_hidden_layers=4, q_lora_rank=24, kv_lora_rank=32,
    qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16, vocab_size=96,
    rms_norm_eps=1e-5, rope_theta=1000000, rope_scaling=None,
    partial_rotary_factor=1, tie_word_embeddings=False,
    topk_method="noaux_tc", n_group=1, topk_group=1, norm_topk_prob=True,
    hidden_act="silu", attention_bias=False, max_position_embeddings=256,
    initializer_range=0.2),
    prompt_lens="5,19,40,64,11", max_new=24, max_slots=3, tie=1e-3,
    phase="serve_latent_moe", reference="glm_moe_lm_reference",
    driver="glm_moe_engine_driver")

# Gated short convolutions, grouped-query attention with QK-norm and
# rotary, and sparse experts in small (``models/lfm2_moe_lm.py``): the
# shape of the benchmark's lfm2-24b-a2b-serve at toy widths — all three
# of the trunk's seams in one step program. d 128: the convolution runs
# at the model's width, and its kernel takes whole 128-lane tiles on the
# chip. The same proof, for the tails by slot with no scan state beside
# paged K/V and counted experts.
CONV_MOE = dict(config=dict(
    model_type="lfm2_moe", hidden_size=128, intermediate_size=160,
    moe_intermediate_size=48, num_attention_heads=4, num_key_value_heads=2,
    num_experts=16, num_experts_per_tok=4, num_dense_layers=1,
    num_hidden_layers=6,
    layer_types=["conv", "full_attention", "conv", "conv", "full_attention",
                 "conv"],
    conv_L_cache=3, conv_bias=False, norm_eps=1e-5, norm_topk_prob=True,
    use_expert_bias=True, routed_scaling_factor=1,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    vocab_size=96, max_position_embeddings=256, initializer_range=0.2),
    prompt_lens="5,19,40,64,11", max_new=24, max_slots=3, tie=1e-3,
    phase="serve_conv_moe", reference="lfm2_moe_lm_reference",
    driver="lfm2_moe_engine_driver")

def require_tpu() -> dict:
    """The device as JAX reports it — or an error where it is no TPU."""
    device = describe_devices()
    if device["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX's first device is {device['platform']!r} "
            f"({device['kind']}), not a TPU — nothing was run")
    return device


class _Compiles:
    """Compile seconds and persistent-cache traffic, from JAX's own
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


COMPILES = _Compiles()


def peak_bytes(device) -> int | None:
    """The device allocator's high-water mark (None where the backend
    keeps no statistics — the CPU)."""
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def run_cli(phase: str, argv: list[str], **extra) -> tuple[str, dict]:
    """One ``cli.main(argv)`` call: its return code must be 0. Returns
    what it printed and the phase record (the caller adds its findings
    and emits it). The CLI's own chatter goes to stderr so stdout stays
    the records."""
    s0, h0, m0 = COMPILES.snapshot()
    out = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(out):
        rc = cli.main(list(argv))
    wall = time.perf_counter() - t0
    text = out.getvalue()
    sys.stderr.write(text)
    if rc != 0:
        raise RuntimeError(f"{phase}: cli.main returned {rc} for {argv}")
    s1, h1, m1 = COMPILES.snapshot()
    rec = {"phase": phase, "argv": list(argv), "wall_s": round(wall, 3),
           "compile_s": round(s1 - s0, 3), "cache_hits": h1 - h0,
           "cache_misses": m1 - m0,
           "peak_bytes_in_use": peak_bytes(jax.devices()[0]), **extra}
    return text, rec


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def generate(phase: str, argv: list[str], vocab: int) -> tuple[dict, dict]:
    """A ``generate`` run and its checks: every request completed, every
    token id in range."""
    text, rec = run_cli(phase, ["generate", *argv])
    payload = json.loads(text.strip().splitlines()[-1])
    if payload["failed"]:
        raise RuntimeError(f"{phase}: failed requests {payload['failed']}")
    n_req = len(argv[argv.index("--prompt_lens") + 1].split(","))
    if len(payload["sequences"]) != n_req:
        raise RuntimeError(f"{phase}: {len(payload['sequences'])} of "
                           f"{n_req} requests completed")
    for seq in payload["sequences"]:
        if not all(0 <= t < vocab for t in seq["tokens"]):
            raise RuntimeError(f"{phase}: token id outside [0, {vocab})")
    rec["tokens"] = payload["tokens_generated"]
    rec["compiled_programs"] = payload["compiled_programs"]
    return payload, rec


def tokens_of(payload: dict) -> list:
    return [s["tokens"] for s in payload["sequences"]]


def serve_argv(vocab: int, *more: str) -> list[str]:
    return [*SERVE["model"], "--vocab", str(vocab), "-r", "7",
            "--prompt_lens", SERVE["prompt_lens"],
            "--max_new", str(SERVE["max_new"]), *more]


def phase_serve(out_dir: str) -> None:
    """Phase 1: the paged-KV engine on the gather path, bf16 KV."""
    mdir = os.path.join(out_dir, "serve_metrics")
    argv = serve_argv(SERVE["vocab"], "--kv_dtype", "bf16",
                      "--metrics_dir", mdir)
    first, rec = generate("serve_gather", argv, SERVE["vocab"])
    emit(rec)
    again, rec2 = generate("serve_gather_again", argv, SERVE["vocab"])
    emit(rec2)
    if tokens_of(first) != tokens_of(again):
        raise RuntimeError("serve: the same argv gave different tokens "
                           "the second time")
    if rec2["cache_misses"]:
        raise RuntimeError(f"serve: the second run compiled "
                           f"{rec2['cache_misses']} new program(s)")
    _, rec3 = run_cli("serve_report", ["report", mdir])
    emit(rec3)


def first_difference(a: list, b: list):
    for uid, (x, y) in enumerate(zip(a, b)):
        for pos, (t, u) in enumerate(zip(x, y)):
            if t != u:
                return {"uid": uid, "position": pos, "tokens": [t, u]}
    return None


def phase_model_config(out_dir: str) -> None:
    """Phase 3: the toy hybrid, the toy latent-attention expert model,
    then the toy gated-convolution expert model, each through
    ``generate --model_config``."""
    for toy in (HYBRID, LATENT, CONV_MOE):
        serve_model_config(out_dir, toy)


def serve_model_config(out_dir: str, toy: dict) -> None:
    """One toy family through ``generate --model_config`` at float32
    matmul precision (more requests than slots, so slots — rows of the
    recurrent state, blocks of the pool — are reused), against the plain
    reference teacher-forced on what was served: every served token is
    the reference's first wherever its top two logits are not tied."""
    import numpy as np
    config, name = toy["config"], toy["phase"]
    path = os.path.join(out_dir, name + "_config.json")
    with open(path, "w") as f:
        json.dump(config, f)
    argv = ["--model_config", path, "-r", "7", "--prompt_lens",
            toy["prompt_lens"], "--max_new", str(toy["max_new"]),
            "--max_slots", str(toy["max_slots"])]
    # the benchmark's plain reference and the weights its driver file
    # makes, found by name as the benchmark finds them
    from benchmark import harness
    ref = harness.reference_module(toy)
    driver = harness.driver_module(toy)
    with jax.default_matmul_precision("highest"):
        payload, rec = generate(name, argv, config["vocab_size"])
        w = driver.make_weights(config, 7)
        clear = total = 0
        for seq in payload["sequences"]:
            full, plen = seq["tokens"], seq["prompt_len"]
            rows = np.asarray(ref.logits(w, np.asarray(full), config))[
                plen - 1:len(full) - 1]
            served = np.asarray(full[plen:])
            top2 = np.sort(rows, -1)[:, -2:]
            sure = top2[:, 1] - top2[:, 0] > toy["tie"]
            wrong = np.flatnonzero(sure & (rows.argmax(-1) != served))
            if wrong.size:
                raise RuntimeError(
                    f"{name}: uid {seq['uid']} output token "
                    f"{int(wrong[0])} is {int(served[wrong[0]])}, the "
                    f"reference's first is "
                    f"{int(rows.argmax(-1)[wrong[0]])}")
            clear += int(sure.sum())
            total += len(served)
    rec["matmul_precision"] = "highest"
    rec["tokens_compared"] = clear
    rec["tokens_tied"] = total - clear
    emit(rec)
    if clear < 0.9 * total:
        raise RuntimeError(f"{name}: only {clear} of {total} tokens had "
                           "a clear first: the check compared too little")


def step_records(mdir: str) -> list[dict]:
    records, errors = telemetry.read_metrics(
        os.path.join(mdir, "metrics.jsonl"))
    if errors:
        raise RuntimeError(f"telemetry stream {mdir}: {errors[:3]}")
    return [r for r in records if r["kind"] == "step"]


def corners(text: str, tag: str) -> str:
    """The 5x5 parameter corners the CLI prints after ``tag`` (the
    reference's before/after printout) — the values line, not the
    shapes line."""
    block = text.split(tag)[2]
    end = block.find("\n\n")
    return block if end < 0 else block[:end]


def phase_train(out_dir: str) -> None:
    """Phase 3: the paper's FFN stack through method 1, at the paper's
    width and at the one shape with chip history."""
    for i, shape in enumerate(TRAIN):
        mdir = os.path.join(out_dir, f"train_metrics_{i}")
        argv = ["-m", "1", *shape["argv"], "-r", "7", "--strict",
                "--metrics_dir", mdir]
        text, rec = run_cli(f"train_single_{i}", argv)
        steps = step_records(mdir)
        before = corners(text, "initial layers_params[0]")
        after = corners(text, "final train_single layers_params[0]")
        rec["steps"] = steps[-1]["step"] if steps else 0
        rec["mfu"] = [r["mfu"] for r in steps]
        rec["grad_norm"] = [r["grad_norm"] for r in steps]
        rec["corner_moved"] = before != after
        emit(rec)
        want = int(shape["argv"][shape["argv"].index("-s") + 1])
        if rec["steps"] != want:
            raise RuntimeError(f"train {argv}: {rec['steps']} of {want} "
                               "steps recorded")
        if any(m is None or not m > 0 for m in rec["mfu"]):
            raise RuntimeError(
                f"train {argv}: null MFU — runtime/telemetry.py's peak "
                f"table does not know {jax.devices()[0].device_kind!r}")
        # finite: the probe's gradient norm over ALL parameters at their
        # final values (one NaN or inf weight poisons it), and the
        # corner the CLI prints. Changed: that corner, where the shape
        # lets 8 steps move it; everywhere, a non-zero gradient.
        if any(g is None or not math.isfinite(g) or not g > 0
               for g in rec["grad_norm"]):
            raise RuntimeError(f"train {argv}: gradient norm "
                               f"{rec['grad_norm']}")
        if "nan" in after or "inf" in after:
            raise RuntimeError(f"train {argv}: non-finite parameters")
        if shape["moves"] and not rec["corner_moved"]:
            raise RuntimeError(f"train {argv}: parameters did not change")


def phase_cross_chip(out_dir: str) -> None:
    """``--chips 4``: the cross-chip paths and what each is compared
    with — nothing else.

    (a) is ``-m 0 --strict`` as a user runs it: single, DDP, FSDP and
    TP with the CLI's own differential check (``cli.py::
    strategy_disagreement``; the CLI runs it at float32 matmul
    precision of its own accord), whose verdict ``--strict`` turns into
    the return code that ``run_cli`` holds to 0. (b) compares greedy
    tokens, at float32 matmul precision too: sharding a contraction
    over chips rounds it differently at the MXU's default (bf16
    passes)."""
    n = jax.device_count()
    if n != 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, JAX sees {n}")
    v = SERVE["vocab_tp"]
    common = serve_argv(v, "--kv_dtype", "f32")
    launcher.CAPTURE_COMPILED = captured = []
    try:
        text, rec = run_cli("train_all_strategies",
                            ["-m", "0", *TRAIN[0]["argv"], "-r", "7",
                             "--strict"])
    finally:
        launcher.CAPTURE_COMPILED = None
    # what the CLI's check said: one "compared" line per pair, plus the
    # ReLU flips it admitted. Both pairs must have been compared — a
    # return code of 0 from a check that never ran proves nothing.
    rec["cli_check"] = [ln for ln in text.splitlines() if ln.startswith(
        ("compared ", "relu flips", "SoftAssertionError"))]
    compared = [ln for ln in rec["cli_check"] if ln.startswith("compared ")]
    # every device ran a program: the launched DDP/FSDP/TP programs are
    # compiled for 4 partitions (a mesh that silently has one entry is
    # the failure to catch), and every device's allocator saw bytes
    spmd = [h for h in captured if f"num_partitions={n}" in h[:2000]]
    peaks = [peak_bytes(d) for d in jax.devices()]
    rec["programs_over_4_partitions"] = len(spmd)
    rec["peak_bytes_per_device"] = peaks
    emit(rec)
    if len(compared) != 2 or not all(": agree (" in ln for ln in compared):
        raise RuntimeError(f"cross-chip: the CLI's differential check "
                           f"did not report two agreeing pairs: "
                           f"{rec['cli_check']}")
    if len(spmd) < 3:
        raise RuntimeError(f"cross-chip: {len(spmd)} of the 3 sharded "
                           f"strategies compiled for {n} partitions")
    if not all(peaks):  # None (no allocator statistics) fails like 0
        raise RuntimeError(f"cross-chip: an idle device, peaks {peaks}")
    with jax.default_matmul_precision("highest"):
        # (b) Megatron decode over 4 chips against one device
        want, rec = generate("serve_tp1", [*common, "--tp", "1"], v)
        emit(rec)
        got, rec = generate("serve_tp4", [*common, "--tp", "4"], v)
    diff = first_difference(tokens_of(got), tokens_of(want))
    rec["first_difference_from_tp1"] = diff
    rec["note"] = (f"vocab {v} on both sides: the vocab-parallel head "
                   "needs V divisible by 4; float32 matmul precision")
    emit(rec)
    if got["tp"] != 4:
        raise RuntimeError(f"serve --tp 4 ran at tp={got['tp']}")
    if diff or tokens_of(got) != tokens_of(want):
        raise RuntimeError(f"serve --tp 4: tokens differ from --tp 1: "
                           f"{diff}")
    # (c) the LM trainer, vocab-parallel TP: loss finite and falling
    mdir = os.path.join(out_dir, "train_lm_metrics")
    _, rec = run_cli("train_lm_tp4",
                     ["-m", "11", "--tp", "4", *TRAIN_LM, "-r", "7",
                      "--metrics_dir", mdir, "--log_every", "1"])
    losses = [r["loss"] for r in step_records(mdir)]
    rec["steps"], rec["loss"] = len(losses), losses
    emit(rec)
    if len(losses) < 2 or any(x is None or not math.isfinite(x)
                              for x in losses):
        raise RuntimeError(f"train_lm_tp4: losses {losses}")
    # each loss is probed on the NEXT step's batch, so neighbours
    # jitter; falling means it ends below where it began
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"train_lm_tp4: loss not falling: {losses}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    device = require_tpu()
    cache_dir = enable_compile_cache()
    out_dir = os.path.join(HERE, "chiprun_out",
                           f"chip_smoke_{args.chips}chip")
    shutil.rmtree(out_dir, ignore_errors=True)  # metrics streams append
    os.makedirs(out_dir)
    phases = ([phase_cross_chip] if args.chips == 4
              else [phase_serve, phase_model_config, phase_train])
    for phase in phases:
        try:
            phase(out_dir)
        except BaseException as e:
            print(f"chip_smoke: {phase.__name__} failed: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            raise
    # built from what git holds: no phase may have needed the native
    # libraries (git-ignored .so files, built by make on first use)
    if native._LIB is not None or native._FFI_LIB is not None:
        raise RuntimeError("a phase loaded the native C++ libraries")
    compile_s, hits, misses = COMPILES.snapshot()
    emit({"phase": "total", "compile_cache_dir": cache_dir,
          "wall_s": round(time.perf_counter() - t0, 3),
          "compile_s": round(compile_s, 3), "cache_hits": hits,
          "cache_misses": misses})
    if args.chips == 4 and device["count"] != 4:
        raise RuntimeError(f"device count {device['count']} != 4")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
