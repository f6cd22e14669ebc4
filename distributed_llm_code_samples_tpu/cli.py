"""CLI driver — flag-for-flag parity with the reference's entrypoint.

Reference surface (``train_ffns.py:342-391``): seven flags, a method
dispatch table, per-method wall-clock timing, param-count/GB report,
before/after 5x5 param corners, and a soft cross-strategy ``allclose``
verification. Extensions beyond the reference: ``--method 5`` (hybrid
DDP x TP), mesh-shape flags for it (BASELINE config 4), ``--dtype``,
``--scan``, ``--strict`` (make verification hard-failing), and
``--fake_devices`` (run the multi-device methods on a virtual CPU mesh,
replacing the reference's hard multi-GPU dependency).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

# What the differential check (``-m 0`` / ``-m 9``) admits beyond its
# elementwise tolerance — see ``strategy_disagreement``.
FLIP_ROWS = 0.01    # at most this share of a weight's rows ...
FLIP_UPDATE = 0.05  # ... each within this share of its largest update


def strategy_disagreement(a, b, init, rtol, atol):
    """How the final weights ``a`` and ``b`` of two strategies, both
    trained from ``init``, disagree: ``(failed, text)``, text None where
    every element is inside ``rtol``/``atol``.

    Elementwise closeness is the bar, with one admitted exception.
    ReLU is not smooth: two programs that compute the same step in a
    different order (another sharding, another reduction) put a
    pre-activation that sits at zero on opposite sides of it, and that
    one (token, unit) then adds or withholds its whole term
    ``lr * da * x`` in row ``unit`` of the weight that feeds the ReLU —
    a jump the size of one token's share of the update, however small
    the rounding difference that caused it. The number of pre-activations
    grows with width x tokens, so at the paper's d=8192 (2.7e8 a step)
    some always flip. Such a disagreement is *row-sparse* and *small
    against the update*: it is admitted (and printed as a note) where it
    is confined to ``FLIP_ROWS`` of the weight's rows and stays inside
    ``FLIP_UPDATE`` of the largest update the weight received. A lost or
    doubled reduction, a wrong shard or a shard-boundary slip moves a
    quarter of the rows, or a row by the update itself.

    Measured on four v5e chips (``-m 0`` at d=8192, 8 steps, float32
    matmul precision): DDP vs FSDP differed in 7 of w1's 32768 rows, by
    at most 0.65% of the largest update; in each of those rows ONE
    token's vector carried over 99.97% of the difference and that
    token's pre-activation sat among the smallest 1% of the run's
    65536; every other row agreed to 7.5e-9, and w2 everywhere. The two
    bounds sit between that and the smallest fault planted on the same
    arrays (one row left at its initial value: that row's whole
    update), which fails."""
    import numpy as np

    bad = ~np.isclose(a, b, rtol=rtol, atol=atol)
    if not bad.any():
        return False, None
    diff = np.abs(a - b)
    text = f"max|diff|={diff.max()}"
    if a.ndim < 2:
        return True, text
    rows = bad.any(axis=-1)
    update = np.abs(b - init).max()
    text += (f", {bad.sum()} elements in {rows.sum()} of {rows.size} rows"
             f", largest update {update}")
    flips = (rows.sum() <= FLIP_ROWS * rows.size
             and diff.max() <= FLIP_UPDATE * update)
    return not flips, text


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="TPU-native distributed FFN-stack training "
                    "(reference-parity CLI, train_ffns.py:342-351)")
    # the reference's seven flags, same short names and defaults (:344-350)
    p.add_argument("-s", "--num_steps", type=int, default=1)
    p.add_argument("-bs", "--batch_size", type=int, default=8)
    p.add_argument("-n", "--seq_len", type=int, default=1024)
    p.add_argument("-l", "--layers", type=int, default=1)
    p.add_argument("-d", "--model_size", type=int, default=4)
    p.add_argument("-m", "--method", type=int, default=0,
                   choices=range(14),
                   help="0=all(1-4), 1=single, 2=DDP, 3=FSDP, 4=TP, "
                        "5=hybrid DDP x TP, 6=pipeline (ppermute send/recv), "
                        "7=MoE expert parallelism (all_to_all), "
                        "8=transformer blocks (Megatron TP; --heads), "
                        "9=all(1-8,10-13) with every strategy "
                        "cross-verified against its oracle, 10=MoE "
                        "transformer (GShard: data-parallel attention + "
                        "expert-parallel FFN), 11=language model on the "
                        "real cross-entropy objective (vocab-parallel "
                        "Megatron TP; --vocab --heads), 12=MoE language "
                        "model (GShard blocks + real loss + router aux; "
                        "--experts --vocab --heads), 13=long-context LM "
                        "(sequence dim sharded over the seq axis: ring "
                        "attention or Ulysses via --seq_impl; "
                        "--attn flash fuses the per-hop block compute)")
    p.add_argument("-r", "--random_seed", type=int, default=0,
                   help="!=0 makes runs reproducible (train_ffns.py:350)")
    # TPU-build extensions
    p.add_argument("--dp", type=int, default=0,
                   help="data-axis size for --method 5 (0 = devices//tp)")
    p.add_argument("--tp", type=int, default=2,
                   help="model-axis size for --method 5 and 8")
    p.add_argument("--microbatches", type=int, default=0,
                   help="pipeline microbatches for --method 6 (0 = n_stages)")
    p.add_argument("--pp_schedule",
                   choices=["gpipe", "1f1b", "interleaved"],
                   default="gpipe",
                   help="pipeline schedule for --method 6: gpipe (two "
                        "wavefronts, stash of M microbatches), 1f1b "
                        "(f/b interleave, stash bounded by stage depth), "
                        "or interleaved (Megatron virtual stages: "
                        "--pp_chunks non-contiguous layer chunks per "
                        "device, bubble cut by 1/chunks)")
    p.add_argument("--pp_chunks", type=int, default=0,
                   help="virtual-stage chunks per device for "
                        "--pp_schedule interleaved (0 = 2; stages x "
                        "chunks must divide --layers)")
    p.add_argument("--pp_family", choices=["ffn", "transformer", "lm"],
                   default="ffn",
                   help="model family for --method 6: the reference's FFN "
                        "stack, pre-LN transformer blocks, or the full "
                        "LM (embed/head staged, real loss; --vocab) "
                        "(--heads; microbatches split the batch dim)")
    p.add_argument("--experts", type=int, default=8,
                   help="expert count for --method 7/10/12 (MoE)")
    p.add_argument("--heads", type=int, default=4,
                   help="attention heads for --method 8/10/11/12 and "
                        "--method 6 with --pp_family transformer/lm")
    p.add_argument("--vocab", type=int, default=256,
                   help="vocabulary size for --method 11/12 and "
                        "--method 6 with --pp_family lm (method 11 needs "
                        "it divisible by the model-axis size)")
    p.add_argument("--kv_heads", type=int, default=0,
                   help="with --method 11, 9, or 6 + --pp_family lm: "
                        "grouped-query attention with this many KV heads "
                        "(0 = full MHA; wk/wv and the KV cache shrink by "
                        "heads/kv_heads; must divide --heads and the "
                        "model-axis size must divide it)")
    p.add_argument("--attn", choices=["oracle", "rope", "flash"],
                   default="oracle",
                   help="attention implementation for the transformer/LM "
                        "methods (8, 11, and 6 with --pp_family "
                        "transformer/lm): the quadratic hand-VJP oracle, "
                        "rotary positions, or the fused Pallas flash "
                        "kernels (interpret mode off-TPU)")
    p.add_argument("--head", choices=["oracle", "fused"],
                   default="oracle",
                   help="LM head+loss implementation for --method "
                        "11/12/13: the materialized-logits hand-VJP "
                        "xent, or the fused Pallas head "
                        "(ops/pallas_xent.py - no [N, V] logits in HBM; "
                        "vocab-parallel merge under method 11)")
    p.add_argument("--lr", type=float, default=None,
                   help="override LR (default 1e-5, train_ffns.py:29)")
    p.add_argument("--optimizer",
                   choices=["sgd", "momentum", "adam", "adamw"],
                   default="sgd",
                   help="update rule for --method 2 (DDP) or 3 (FSDP, "
                        "state sharded with the params): sgd is the "
                        "reference's stateless inline update; momentum/"
                        "adam/adamw carry hand-written optimizer state")
    p.add_argument("--clip_norm", type=float, default=0.0,
                   help="with --method 2 or 3: clip gradients to this "
                        "global L2 norm before the optimizer update "
                        "(0 = off)")
    p.add_argument("--seq_impl", choices=["ring", "ulysses"],
                   default="ring",
                   help="with --method 13: the cross-shard attention "
                        "scheme — ring (KV blocks rotating over "
                        "ppermute) or ulysses (two all_to_alls re-shard "
                        "heads<->sequence)")
    p.add_argument("--tp_sp", action="store_true",
                   help="with --method 4 or 8: Megatron sequence-parallel "
                        "TP (token-sharded activations; all_gather + "
                        "reduce_scatter instead of all_reduce)")
    p.add_argument("--comm", choices=["psum", "pallas_ring"],
                   default="psum",
                   help="with --method 2 (DDP) or 3 (FSDP): collective "
                        "transport — psum (XLA collectives, async-split "
                        "by the scheduler) or pallas_ring (the hand-"
                        "scheduled make_async_remote_copy ring kernels: "
                        "DDP grad all-reduce; FSDP param all-gathers + "
                        "grad reduce-scatters)")
    p.add_argument("--zero1", action="store_true",
                   help="with --method 2: shard the optimizer state "
                        "across the data axis (ZeRO-1; reduce_scatter + "
                        "all_gather instead of all_reduce)")
    p.add_argument("--dtype", choices=["float32", "bfloat16"],
                   default="float32")
    p.add_argument("--mixed", action="store_true",
                   help="bf16 mixed precision for the FFN methods "
                        "(1/2/3/4/5, incl. --zero1/--tp_sp): bf16 matmul "
                        "inputs on the MXU, f32 params/grads/accumulation; "
                        "FSDP additionally gathers its param shards in "
                        "bf16 (half the collective bytes). Distinct from "
                        "--dtype bfloat16, which stores the params "
                        "themselves in bf16")
    p.add_argument("--scan", action="store_true",
                   help="lax.scan over layers instead of unrolling")
    p.add_argument("--accum", type=int, default=1,
                   help="gradient-accumulation chunks per step for "
                        "--method 1/2 (exact: SUM semantics, ~1/accum "
                        "activation memory)")
    p.add_argument("--pallas", action="store_true",
                   help="use the fused Pallas FFN kernels for the "
                        "single-device method (interpret mode off-TPU)")
    p.add_argument("--strict", action="store_true",
                   help="make the cross-strategy verification hard-failing "
                        "(the reference only soft-asserts, :386-391)")
    p.add_argument("--fake_devices", type=int, default=0,
                   help="run on N virtual CPU devices "
                        "(xla_force_host_platform_device_count)")
    p.add_argument("--profile_dir", default=None,
                   help="profile each method's run into this directory "
                        "(Perfetto/TensorBoard trace, process 0 only — "
                        "the reference's torch_profile_rank_0 surface, "
                        "train_ffns.py:129-141, on by flag instead of by "
                        "commented-out decorator)")
    p.add_argument("--checkpoint_dir", default=None,
                   help="enable checkpoint/resume: save params + seed "
                        "schedule here (per-method subdirs); a re-run with "
                        "the same dir resumes from the latest checkpoint")
    p.add_argument("--checkpoint_backend",
                   choices=["npz", "orbax", "native"], default="npz",
                   help="checkpoint array I/O: npz (portable), orbax "
                        "(multi-host sharded), native (async C++ writer — "
                        "training overlaps the disk write)")
    p.add_argument("--checkpoint_every", type=int, default=0,
                   help="save every N steps (0 = final only); for methods "
                        "that shard the seed schedule (2, 3, 5, 7, 10) "
                        "pick N divisible by the sharding-axis size")
    p.add_argument("--no_resume", action="store_true",
                   help="ignore existing checkpoints (restart from step 0)")
    p.add_argument("--chaos", default=None, metavar="SPEC",
                   help="run the strategy under deterministic fault load "
                        "(runtime/chaos.py): comma-separated "
                        "KIND@STEP[:ARG] entries plus optional seed=N, "
                        "KIND in {nan_grad, inf_grad, hang, kill, "
                        "corrupt_ckpt}. The run goes through the failure "
                        "supervisor (restart + verified-checkpoint "
                        "recovery); requires --checkpoint_dir and a "
                        "single --method")
    p.add_argument("--max_restarts", type=int, default=3,
                   help="with --chaos or --spike_factor: the "
                        "supervisor's restart budget")
    p.add_argument("--guardrails", action="store_true",
                   help="compile the in-graph anomaly guardrail into the "
                        "training step (runtime/guardrails.py, methods "
                        "1/2/3/11): a non-finite update is jnp.where-"
                        "skipped inside the compiled chunk — params and "
                        "optimizer state untouched, zero restarts — and "
                        "per-chunk skip counters flow to --metrics_dir "
                        "as `anomaly` records. With --mixed (methods "
                        "2/3) adds dynamic loss scaling")
    p.add_argument("--loss_scale", type=float, default=0.0,
                   help="with --guardrails --mixed (methods 2/3): "
                        "initial dynamic loss scale (0 = auto 2^15; "
                        "grows 2x per 200 clean steps, halves on "
                        "overflow)")
    p.add_argument("--spike_factor", type=float, default=0.0,
                   help="with --checkpoint_dir: arm the loss-spike "
                        "guard — a segment whose param-update norm "
                        "exceeds this multiple of the previous "
                        "segment's raises for the supervisor's "
                        "in-process rollback rung instead of being "
                        "checkpointed (0 = off; the PaLM rewind-on-"
                        "spike practice)")
    p.add_argument("--max_rollbacks", type=int, default=2,
                   help="with --chaos or --spike_factor: budget for the "
                        "supervisor's "
                        "in-process rollback rung (rewind to the last "
                        "verified checkpoint without a restart) before "
                        "escalating to full restarts")
    p.add_argument("--metrics_dir", default=None,
                   help="write the unified telemetry stream here "
                        "(runtime/telemetry.py): one schema-versioned "
                        "JSONL record per logged step (loss/grad-norm "
                        "where the family defines them, tokens/s, step "
                        "wall-time, MFU from the hand FLOP count, "
                        "per-device HBM high-water) plus every "
                        "recovery/chaos event; fold it into a "
                        "human-readable report with the `report` "
                        "subcommand")
    p.add_argument("--log_every", type=int, default=0,
                   help="with --metrics_dir: emit one metrics record "
                        "every N steps by driving the run in N-step "
                        "programs (0 = one record for the whole run); "
                        "steps inside a chunk stay dispatch-only — "
                        "device readbacks batch at this cadence. With "
                        "--checkpoint_dir the records follow the "
                        "checkpoint segments instead (--checkpoint_every)")
    return p


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "report":
        # subcommand dispatch ahead of the flag parser: fold a
        # --metrics_dir run (+ supervise attempt log + optional profile
        # dir) into one human-readable run report
        from .report import report_main
        return report_main(argv[1:])
    if argv and argv[0] == "generate":
        # serving entrypoint: continuous-batching decode over the paged
        # KV engine (decode/engine.py), same dispatch pattern as report
        from .decode.generate_cli import generate_main
        return generate_main(argv[1:])
    if argv and argv[0] == "fleetstat":
        # live ops plane: render the router's atomic fleet status doc
        # (jax-free — the operator's terminal pays no backend import)
        from .fleetstat import fleetstat_main
        return fleetstat_main(argv[1:])
    p = build_parser()
    args = p.parse_args(argv)
    if args.mixed and args.pallas:
        # train_single would raise the same deep in the run; fail at the
        # flag surface instead (the Pallas block has its own precision
        # story inside the kernel)
        p.error("--mixed cannot combine with --pallas: the fused Pallas "
                "block carries its own residual/precision policy")

    if args.fake_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.fake_devices}").strip()

    import jax
    if args.fake_devices:
        jax.config.update("jax_platforms", "cpu")

    from .runtime.init import describe_devices, enable_compile_cache
    enable_compile_cache()
    describe_devices()

    if (args.method in (0, 9) and not (args.mixed or args.pallas)
            and jax.config.jax_default_matmul_precision is None):
        # The differential check compares float32 programs, as the
        # reference's does (torch multiplies f32 in f32). The MXU's
        # default rounds f32 operands to bf16: seen on four v5e chips
        # at d=8192, one device and TP then differ in EVERY row of w2,
        # by 1e-3 of the update — two strategies are then two bf16
        # roundings of the same math, and no f32 tolerance holds. At
        # float32 precision they agree to 1e-8 outside a handful of
        # ReLU flips (``strategy_disagreement``). An ambient setting
        # (JAX_DEFAULT_MATMUL_PRECISION, a caller's context) is kept.
        print("differential check: strategies run at float32 matmul "
              "precision; their timings below are not default-precision "
              "step times")
        with jax.default_matmul_precision("highest"):
            return _train(args, argv)
    return _train(args, argv)


def _train(args, argv) -> int:
    """Everything after start-up: the selected strategies, their
    telemetry, and for ``-m 0`` / ``-m 9`` the differential check."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from . import LR
    from .data import make_seed_schedule
    from .models import (init_ffn_stack, init_moe_stack, init_transformer,
                         params_size_gb)
    from .parallel import (make_mesh, guard_multi_device, STRATEGIES,
                           DATA_AXIS, MODEL_AXIS, PIPE_AXIS, EXPERT_AXIS,
                           SEQ_AXIS)

    chaos_plan = None
    if args.chaos:
        if not args.checkpoint_dir:
            print("error: --chaos requires --checkpoint_dir (recovery "
                  "resumes from published checkpoints)", file=sys.stderr)
            return 2
        if args.method in (0, 9):
            print("error: --chaos applies to a single --method (not 0/9):"
                  " restarts would desync the cross-strategy verification",
                  file=sys.stderr)
            return 2
        from .runtime.chaos import (FaultPlan, IN_SEGMENT_KINDS,
                                    PUBLISH_KINDS)
        try:
            chaos_plan = FaultPlan.parse(args.chaos)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        train_kinds = IN_SEGMENT_KINDS + PUBLISH_KINDS
        bad_kinds = [f.kind for f in chaos_plan.faults
                     if f.kind not in train_kinds]
        if bad_kinds:
            # a decode fault would silently never fire in a training
            # run — the same parse-rejection discipline as generate's
            # validate_decode_plan, pointed the other way
            print(f"error: --chaos kind(s) {bad_kinds} are decode "
                  f"faults; the train CLI accepts {train_kinds} (use "
                  "the generate subcommand for serving faults)",
                  file=sys.stderr)
            return 2
    if args.guardrails and args.method not in (0, 1, 2, 3, 9, 11):
        # 0/9 sweeps are allowed: the per-method loop arms the guard on
        # the strategies with the surface (1/2/3/11) and the guard is
        # value-transparent on clean runs, so the cross-strategy
        # differentials keep their power
        print("error: --guardrails applies to --method 1, 2, 3, or 11 "
              "(or the 0/9 sweeps, which guard those strategies)",
              file=sys.stderr)
        return 2
    if args.guardrails and args.zero1:
        print("error: --guardrails does not support --zero1: "
              "train_ddp_zero1 has no guard surface (its re-assembled "
              "params are typed shard-varying)", file=sys.stderr)
        return 2
    if args.loss_scale < 0:
        print(f"error: --loss_scale must be >= 0 (got {args.loss_scale})",
              file=sys.stderr)
        return 2
    if args.loss_scale > 0 and not (args.guardrails and args.mixed
                                    and args.method in (0, 2, 3, 9)):
        # 0/9 sweeps allowed like --guardrails itself: the per-method
        # loop applies the scale to the methods that scale (2/3)
        print("error: --loss_scale applies with --guardrails --mixed on "
              "--method 2 or 3 (or the 0/9 sweeps; dynamic scaling "
              "protects the bf16 backward)", file=sys.stderr)
        return 2
    if args.spike_factor < 0:
        print(f"error: --spike_factor must be >= 0 "
              f"(got {args.spike_factor})", file=sys.stderr)
        return 2
    if args.spike_factor and not args.checkpoint_dir:
        print("error: --spike_factor requires --checkpoint_dir (the "
              "spike guard compares checkpoint-segment deltas and the "
              "rollback rung rewinds to a published checkpoint)",
              file=sys.stderr)
        return 2
    if args.spike_factor and not args.checkpoint_every:
        # with the default (whole-run) segmentation there is only one
        # segment: no baseline ever forms and the guard NEVER fires —
        # refusing beats silently-unarmed spike protection
        print("error: --spike_factor requires --checkpoint_every > 0: "
              "the spike guard compares successive segment deltas, and "
              "one whole-run segment has nothing to compare",
              file=sys.stderr)
        return 2
    if args.max_rollbacks < 0:
        print(f"error: --max_rollbacks must be >= 0 "
              f"(got {args.max_rollbacks})", file=sys.stderr)
        return 2
    if args.comm != "psum" and args.zero1:
        print("error: --comm pallas_ring does not apply to --zero1 "
              "(ZeRO-1's reduce_scatter/all_gather pair keeps the XLA "
              "transport); drop one of the flags", file=sys.stderr)
        return 2
    if args.comm != "psum" and args.method not in (0, 2, 3, 9):
        print("error: --comm applies to --method 2 (DDP) or 3 (FSDP)",
              file=sys.stderr)
        return 2
    if args.head != "oracle" and args.method not in (9, 11, 12, 13):
        # same pattern as the --comm guard: inapplicable flags exit 2
        # instead of silently running the oracle head
        print("error: --head fused applies to --method 11 (LM TP), "
              "12 (MoE LM EP), 13 (sequence-parallel LM), or the "
              "--method 9 sweep (which verifies them)", file=sys.stderr)
        return 2
    if args.method == 13 and args.kv_heads:
        print("error: --method 13 (sequence-parallel LM) supports full "
              "MHA only (no --kv_heads): the ring vmaps equal q/kv "
              "heads", file=sys.stderr)
        return 2
    if args.method == 13 and args.attn == "rope":
        print("error: --attn rope is not supported by --method 13 "
              "(the ring's per-hop programs take oracle or flash)",
              file=sys.stderr)
        return 2

    if args.accum < 1:
        print(f"error: --accum must be >= 1 (got {args.accum})",
              file=sys.stderr)
        return 2
    if args.accum > 1 and args.method not in (1, 2):
        # methods 0/9 would cross-verify chunked-accumulation runs against
        # full-batch strategies at the tight tolerance (different f32
        # reduction order => spurious differential failures); other
        # methods would silently ignore the flag
        print("error: --accum applies to --method 1 or 2 only",
              file=sys.stderr)
        return 2
    if args.tp_sp and args.method not in (4, 8):
        print("error: --tp_sp applies to --method 4 or 8 only",
              file=sys.stderr)
        return 2
    if args.zero1 and args.method != 2:
        print("error: --zero1 applies to --method 2 only", file=sys.stderr)
        return 2
    if args.pp_chunks and not (args.method == 6
                               and args.pp_schedule == "interleaved"):
        print("error: --pp_chunks applies to --method 6 with "
              "--pp_schedule interleaved only", file=sys.stderr)
        return 2
    if args.pp_chunks < 0:
        print(f"error: --pp_chunks must be >= 0 (got {args.pp_chunks})",
              file=sys.stderr)
        return 2
    if args.method == 6 and args.pp_schedule == "interleaved":
        # mirror train_pp's chunking check up front: exit 2 with a clean
        # message instead of the trainer's ValueError traceback
        chunks = args.pp_chunks or 2
        stages = jax.device_count()
        if args.layers % (stages * chunks):
            print(f"error: --layers {args.layers} not divisible into "
                  f"{stages} stages x {chunks} chunks "
                  f"(--pp_schedule interleaved)", file=sys.stderr)
            return 2
    if args.pp_family != "ffn" and args.method != 6:
        # methods 0/9 verify PP against the FFN single-device oracle
        print("error: --pp_family applies to --method 6 only",
              file=sys.stderr)
        return 2
    if args.attn != "oracle" and not (
            args.method in (8, 11, 13)
            or (args.method == 6 and args.pp_family in ("transformer",
                                                        "lm"))):
        print("error: --attn applies to --method 8, 11, 13, or 6 with "
              "--pp_family transformer/lm", file=sys.stderr)
        return 2
    if args.optimizer != "sgd" and args.method not in (2, 3):
        # methods 0/9 cross-check against strategies that would still run
        # inline SGD — a guaranteed spurious differential failure
        print("error: --optimizer applies to --method 2 or 3 only",
              file=sys.stderr)
        return 2
    if args.clip_norm and args.method not in (2, 3):
        print("error: --clip_norm applies to --method 2 or 3 only",
              file=sys.stderr)
        return 2
    if args.clip_norm < 0:
        print(f"error: --clip_norm must be >= 0 (got {args.clip_norm})",
              file=sys.stderr)
        return 2
    if args.kv_heads < 0:
        print(f"error: --kv_heads must be >= 0 (got {args.kv_heads})",
              file=sys.stderr)
        return 2
    if args.kv_heads and not (
            args.method in (9, 11)
            or (args.method == 6 and args.pp_family == "lm")):
        print("error: --kv_heads applies to the LM family only "
              "(--method 11, 9, or 6 with --pp_family lm)",
              file=sys.stderr)
        return 2
    if args.kv_heads and args.heads % args.kv_heads:
        # mirrors init_lm's n_heads % n_kv_heads check — repeated here
        # only so an arg-only mistake exits 2 with a clean message
        # instead of that ValueError's traceback
        print(f"error: --heads {args.heads} not divisible by "
              f"--kv_heads {args.kv_heads}", file=sys.stderr)
        return 2
    if args.kv_heads and args.method in (9, 11):
        # the companion constraint the help text promises ("the model-axis
        # size must divide it"): mirrored up front so e.g. MQA
        # (--kv_heads 1) with the default --tp 2 exits 2 cleanly instead
        # of dying mid-run in _validate_tp's ValueError traceback
        tp_n = min(args.tp, jax.device_count())
        if tp_n > 1 and args.kv_heads % tp_n:
            print(f"error: --kv_heads {args.kv_heads} not divisible by "
                  f"the model-axis size {tp_n} (min(--tp, devices)) "
                  f"required by --method {args.method}", file=sys.stderr)
            return 2
    if (args.zero1 and args.optimizer != "sgd" and args.checkpoint_dir
            and args.checkpoint_every):
        # ZeRO-1's per-rank state shards have no opt_state surface yet;
        # segment boundaries would re-init them (train_ddp checkpoints
        # its optimizer state and has no such restriction)
        print("error: --checkpoint_every does not checkpoint ZeRO-1's "
              "sharded optimizer state; with --zero1 only whole-run "
              "checkpoints (0) are supported", file=sys.stderr)
        return 2

    lr = LR if args.lr is None else args.lr
    dtype = jnp.float32 if args.dtype == "float32" else jnp.bfloat16
    unroll = not args.scan

    # banner (train_ffns.py:353)
    print(f"ARGS:\n num_steps: {args.num_steps}\n BS: {args.batch_size}\n"
          f" N: {args.seq_len}\n D: {args.model_size}\n"
          f" FFN: {4 * args.model_size}\n")

    seeds = make_seed_schedule(args.num_steps, args.random_seed)
    key = jax.random.PRNGKey(args.random_seed)

    def family_of(method: int) -> str:
        if method == 6 and args.pp_family != "ffn":
            return args.pp_family  # transformer or lm
        return {7: "moe", 8: "transformer", 10: "moe_transformer",
                11: "lm", 12: "moe_lm", 13: "lm"}.get(method, "ffn")

    _family_params = {}

    def params_for(method: int):
        fam = family_of(method)
        if fam not in _family_params:
            if fam == "moe":
                _family_params[fam] = init_moe_stack(
                    key, args.model_size, args.layers, args.experts,
                    dtype=dtype)
            elif fam == "transformer":
                _family_params[fam] = init_transformer(
                    key, args.model_size, args.layers, dtype=dtype)
            elif fam == "moe_transformer":
                from .models import init_moe_transformer
                _family_params[fam] = init_moe_transformer(
                    key, args.model_size, args.layers, args.experts,
                    dtype=dtype)
            elif fam == "lm":
                from .models import init_lm
                _family_params[fam] = init_lm(
                    key, args.vocab, args.model_size, args.layers,
                    max_seq_len=args.seq_len, dtype=dtype,
                    n_heads=args.heads,
                    n_kv_heads=args.kv_heads or None)
            elif fam == "moe_lm":
                from .models import init_moe_lm
                _family_params[fam] = init_moe_lm(
                    key, args.vocab, args.model_size, args.layers,
                    args.experts, max_seq_len=args.seq_len, dtype=dtype)
            else:
                _family_params[fam] = init_ffn_stack(
                    key, args.model_size, args.layers, dtype=dtype)
        return _family_params[fam]

    params = params_for(args.method if args.method != 9 else 1)
    print(f"PARAMS: {params.num_params():_} "
          f"(size {params_size_gb(params)} GB)\n\n")
    corner = ((lambda w: w[0, 0]) if args.method in (7, 10, 12)
              else (lambda w: w[0]))
    print("initial layers_params[0]", params.w1[0].shape, params.w2[0].shape)
    print("initial layers_params[0]", corner(params.w1)[:5, :5],
          corner(params.w2)[:5, :5])

    n_dev = jax.device_count()
    tokens = args.batch_size * args.seq_len  # seq folded into batch (:379)

    def mesh_for(method: int):
        if method == 1:
            return None
        guard_multi_device()
        if method in (2, 3):
            return make_mesh({DATA_AXIS: n_dev})
        if method == 4:
            return make_mesh({MODEL_AXIS: n_dev})
        if method == 6:
            return make_mesh({PIPE_AXIS: n_dev})
        if method in (7, 10, 12):
            return make_mesh({EXPERT_AXIS: n_dev})
        if method in (8, 11):
            # model axis sized by --tp (like method 5): all-devices would
            # demand n_heads divisible by every possible device count
            return make_mesh({MODEL_AXIS: min(args.tp, n_dev)})
        if method == 13:
            # seq axis over the largest device count dividing seq_len
            # (and, for Ulysses, the head count it scatters)
            n = max(k for k in range(1, n_dev + 1)
                    if n_dev % k == 0 and args.seq_len % k == 0
                    and (args.seq_impl == "ring" or args.heads % k == 0))
            return make_mesh({SEQ_AXIS: n})
        return make_mesh({DATA_AXIS: hybrid_dp(), MODEL_AXIS: args.tp})

    def hybrid_dp() -> int:
        # one derivation for both the method-5 mesh and its method-9
        # verification oracle — they must never drift apart
        return args.dp or max(1, n_dev // args.tp)

    if args.log_every < 0:
        print(f"error: --log_every must be >= 0 (got {args.log_every})",
              file=sys.stderr)
        return 2
    if args.log_every and not args.metrics_dir:
        print("error: --log_every requires --metrics_dir",
              file=sys.stderr)
        return 2
    metrics = None
    peak = None
    if args.metrics_dir:
        from .runtime.telemetry import (TelemetryWriter,
                                        hand_flops_per_step,
                                        hbm_high_water, peak_flops)
        device_kind = jax.devices()[0].device_kind
        peak = peak_flops(device_kind)
        metrics = TelemetryWriter(args.metrics_dir, meta={
            "argv": list(argv),
            "num_steps": args.num_steps, "batch_size": args.batch_size,
            "seq_len": args.seq_len, "model_size": args.model_size,
            "layers": args.layers, "method": args.method,
            "tokens_per_step": tokens, "log_every": args.log_every,
            "device_kind": device_kind, "n_devices": n_dev,
            "chaos": args.chaos,
            "checkpoint_dir": args.checkpoint_dir})

    def make_probe(fam):
        """Logged-step loss/grad-norm probe: one extra jitted fwd(+bwd)
        at the LOGGING cadence only (never per step). Families without a
        scalar objective report null loss; families without a probe
        report both null."""
        import jax.numpy as jnp

        def gnorm_of(grads):
            return jnp.sqrt(sum(
                jnp.vdot(g, g).real
                for g in jax.tree_util.tree_leaves(grads)))

        if fam == "ffn":
            from .data import batch_from_seed
            from .parallel.ddp import grads_for_batch

            @jax.jit
            def probe(p, seed):
                x, dy = batch_from_seed(seed, tokens, args.model_size,
                                        p.w1.dtype)
                return None, gnorm_of(grads_for_batch(p, x, dy))

            return probe
        if fam == "lm":
            from .data import lm_batch_from_seed
            from .models.lm import lm_loss

            @jax.jit
            def probe(p, seed):
                tok, tgt = lm_batch_from_seed(seed, args.batch_size,
                                              args.seq_len, p.vocab)
                loss, grads = jax.value_and_grad(lm_loss)(
                    p, tok, tgt, args.heads)
                return loss, gnorm_of(grads)

            return probe
        return None

    if args.method == 0:
        selected = [1, 2, 3, 4]
    elif args.method == 9:
        selected = [1, 2, 3, 4, 5, 6, 7, 8, 10, 11, 12, 13]
    else:
        selected = [args.method]
    results = {}
    for m in selected:
        name, fn = STRATEGIES[m]
        params = params_for(m)
        mesh = mesh_for(m)
        kwargs = dict(lr=lr, unroll=unroll)
        if m in (1, 2, 3, 4, 5) and args.mixed:
            kwargs["mixed"] = True  # zero1/tp_sp swaps below keep it
        if m in (2, 3) and args.comm != "psum" and not args.zero1:
            kwargs["comm"] = args.comm
        if m in (1, 2) and args.accum > 1:
            kwargs["accum"] = args.accum  # train_ddp_zero1 accepts it too
        if m in (2, 3) and (args.optimizer != "sgd" or args.zero1
                            or args.clip_norm):
            from .optim import OPTIMIZERS, clipped
            opt = OPTIMIZERS[args.optimizer]()
            if args.clip_norm:
                # FSDP and ZeRO-1 run the update on gradient shards; the
                # true global norm needs a psum over the sharding axis
                sharded_update = m == 3 or args.zero1
                opt = clipped(opt, args.clip_norm,
                              axis=DATA_AXIS if sharded_update else None)
            kwargs["optimizer"] = opt
            if args.zero1:
                from .parallel import train_ddp_zero1
                name, fn = "train_ddp_zero1", train_ddp_zero1
        if m == 4 and args.tp_sp:
            from .parallel import train_tp_sp
            name, fn = "train_tp_sp", train_tp_sp
        if m == 6:
            kwargs = dict(lr=lr, schedule=args.pp_schedule)
            if args.pp_schedule == "interleaved":
                kwargs["interleave"] = args.pp_chunks or 2
            if args.microbatches:
                kwargs["n_microbatches"] = args.microbatches
            if args.pp_family == "transformer":
                from .parallel import train_transformer_pp
                name, fn = "train_transformer_pp", train_transformer_pp
                kwargs.update(seq_len=args.seq_len, n_heads=args.heads)
            elif args.pp_family == "lm":
                from .parallel import train_lm_pp
                name, fn = "train_lm_pp", train_lm_pp
                kwargs.update(seq_len=args.seq_len, n_heads=args.heads)
            if args.pp_family != "ffn" and args.attn != "oracle":
                kwargs["attn_impl"] = args.attn
        if m == 7:
            kwargs = dict(lr=lr)  # EP's expert loop has its own structure
        if m in (8, 10, 11, 12):
            kwargs = dict(lr=lr, seq_len=args.seq_len, n_heads=args.heads)
            if args.tp_sp and m == 8:
                kwargs["sequence_parallel"] = True
            if m in (8, 11) and args.attn != "oracle":
                kwargs["attn_impl"] = args.attn
            if m in (11, 12) and args.head != "oracle":
                kwargs["head_impl"] = args.head
        if m == 13:
            kwargs = dict(lr=lr, seq_len=args.seq_len,
                          n_heads=args.heads, seq_impl=args.seq_impl)
            if args.attn == "flash":
                kwargs["attn_impl"] = "flash"
            if args.head != "oracle":
                kwargs["head_impl"] = args.head
        if m == 1 and args.pallas:
            kwargs["use_pallas"] = True
            kwargs["interpret"] = jax.default_backend() != "tpu"
        if args.guardrails and m in (1, 2, 3, 11):
            from .runtime.guardrails import GuardrailConfig
            scale0 = 0.0
            if args.mixed and m in (2, 3):
                # dynamic loss scaling protects the bf16 backward; 2^15
                # is the conventional warm start (halves on overflow)
                scale0 = (args.loss_scale if args.loss_scale > 0
                          else 2.0 ** 15)
            kwargs["guard"] = GuardrailConfig(loss_scale=scale0)
        if mesh is not None:
            kwargs["mesh"] = mesh
        if args.profile_dir:
            # wrap fn itself so BOTH the direct and the checkpointing
            # branches profile (each checkpoint segment gets its own
            # timestamped trace run in the same directory)
            from .utils.profiling import profile_rank_0
            fn = profile_rank_0(os.path.join(args.profile_dir, name))(fn)
        probe = model_flops = None
        if metrics is not None:
            fam = family_of(m)
            model_flops = hand_flops_per_step(
                fam, tokens=tokens, model_size=args.model_size,
                n_layers=args.layers, seq_len=args.seq_len,
                vocab=args.vocab)
            attempt_log = None
            if chaos_plan is not None or args.spike_factor > 0:
                # supervise's per-attempt JSONL (failure.py default
                # path) — recorded ABSOLUTE so `report` folds it from
                # any working directory without being told
                attempt_log = os.path.abspath(os.path.join(
                    args.checkpoint_dir, name, "supervise.jsonl"))
            metrics.meta({"strategy": name, "family": fam,
                          "model_flops_per_step": model_flops,
                          "attempt_log": attempt_log,
                          "note": "first logged chunk includes compile"})
            probe = make_probe(fam)

        # strategies that split seeds strided across a data-ish axis
        # (data or expert; model/pipe axes replicate seeds) need every
        # chunk length divisible by it — ONE derivation shared by the
        # checkpoint segmenting and the metrics chunking below, so the
        # two can never drift
        seed_stride = 1
        if mesh is not None:
            seed_stride = (mesh.shape.get(DATA_AXIS, 1)
                           * mesh.shape.get(EXPERT_AXIS, 1))

        t0 = time.time()
        if args.checkpoint_dir:
            from .checkpoint import run_with_checkpointing
            ck_kwargs = dict(kwargs)
            opt = ck_kwargs.pop("optimizer", None)
            # guard threads per segment at the checkpoint layer (counter
            # continuity + anomaly events), not per trainer call
            guard_cfg = ck_kwargs.pop("guard", None)
            stateful_opt = opt is not None and not opt.stateless
            restore_shardings = None
            if m == 3 and stateful_opt and mesh is not None:
                # resume straight onto the 1/n FSDP layout — never
                # materialize full params + Adam moments on one device
                from .parallel.fsdp import checkpoint_shardings
                restore_shardings = checkpoint_shardings(params, opt, mesh)
            if metrics is not None:
                # bridge checkpoint/supervise events into the telemetry
                # stream AND synthesize one step record per published
                # segment (wall-time between publishes / segment length;
                # readbacks only at this cadence)
                last_pub = {"t": time.perf_counter()}

                def on_event(rec, _name=name, _flops=model_flops):
                    ev = rec.get("event")
                    if ev == "anomaly":
                        # schema v2 kinds get their own record stream
                        # (guardrail counters / ladder rungs), not the
                        # generic event envelope
                        metrics.anomaly(dict(rec, strategy=_name))
                        return
                    if ev == "rollback":
                        metrics.rollback(dict(rec, strategy=_name))
                        return
                    metrics.event(dict(rec, strategy=_name))
                    if ev != "published":
                        return
                    now = time.perf_counter()
                    a, b = rec.get("steps", (rec["step"], rec["step"]))
                    dt, last_pub["t"] = now - last_pub["t"], now
                    metrics.step(step=int(rec["step"]), strategy=_name,
                                 step_time_s=dt / max(1, b - a + 1),
                                 tokens=tokens, model_flops=_flops,
                                 peak=peak, hbm=hbm_high_water())

                ck_kwargs["on_event"] = on_event
            runner = run_with_checkpointing
            if chaos_plan is not None or args.spike_factor > 0:
                # fault load (and any armed spike guard — its remedy IS
                # the supervisor's rollback rung, so a real spike in a
                # chaos-free run must not escape as a raw traceback)
                # goes through the failure supervisor: a raised fault
                # rolls back in-process or costs one restart, and the
                # next attempt resumes from the last VERIFIED
                # checkpoint; kill@s takes the whole process, so its
                # recovery is the next invocation of this same command
                from .runtime.failure import supervise as runner
                ck_kwargs.update(max_restarts=args.max_restarts,
                                 max_rollbacks=args.max_rollbacks)
                if chaos_plan is not None:
                    ck_kwargs.update(chaos=chaos_plan, nonfinite="raise")
            out = runner(
                fn, params, seeds, tokens, args.model_size,
                ckpt_dir=os.path.join(args.checkpoint_dir, name),
                every=args.checkpoint_every, resume=not args.no_resume,
                seeds_divisor=seed_stride,
                backend=args.checkpoint_backend,
                optimizer=opt,
                # train_ddp threads (params, opt_state) through segments;
                # ZeRO-1's sharded state has no such surface yet
                thread_state=stateful_opt and not args.zero1,
                stateful=stateful_opt and args.zero1,
                guard=guard_cfg, spike_factor=args.spike_factor,
                # seed-poison injection only works where the data layer
                # carries it into a float gradient (the FFN family);
                # integer-token families keep the host-level poison so
                # the fault actually fires (rollback rung, not skip)
                in_graph_chaos=(guard_cfg is not None
                                and family_of(m) == "ffn"),
                restore_shardings=restore_shardings, **ck_kwargs)
        elif metrics is not None:
            # metrics-chunked driving: the schedule runs as log_every-step
            # compiled programs; steps inside a chunk stay dispatch-only
            # and every readback (wall-clock fence, probe, HBM stats)
            # batches at the chunk boundary — the logged step.
            chunk = args.log_every if args.log_every > 0 else len(seeds)
            opt = kwargs.get("optimizer")
            if opt is not None and not getattr(opt, "stateless", False):
                # stateful optimizers carry state INSIDE each trainer
                # call; chunked calls would re-init it and change the
                # math — fall back to one whole-run record
                print(f"metrics: --log_every ignored for {name} with a "
                      "stateful optimizer (state is per-call; chunked "
                      "driving would re-initialize it); logging one "
                      "whole-run record", file=sys.stderr)
                chunk = len(seeds)
            elif chunk % seed_stride or (len(seeds) % chunk) % seed_stride:
                # every chunk (including the final partial one) must
                # divide across the strided seed split, exactly like
                # --checkpoint_every (run_with_checkpointing validates
                # the same invariant)
                print(f"metrics: --log_every {chunk} does not tile "
                      f"{len(seeds)} steps across the {seed_stride}-way "
                      f"seed stride of {name}; logging one whole-run "
                      "record", file=sys.stderr)
                chunk = len(seeds)
            g_cfg = kwargs.get("guard")
            gstate = None
            g_prev = {"skipped": 0, "overflows": 0}
            out = params
            done = 0
            while done < len(seeds):
                n_chunk = int(min(chunk, len(seeds) - done))
                tc = time.perf_counter()
                if g_cfg is not None:
                    # thread the guard state across chunks (scale and
                    # counters persist) and surface per-chunk deltas
                    out, gstate = fn(out, seeds[done:done + n_chunk],
                                     tokens, args.model_size,
                                     guard_state=gstate,
                                     return_guard=True, **kwargs)
                else:
                    out = fn(out, seeds[done:done + n_chunk], tokens,
                             args.model_size, **kwargs)
                jax.block_until_ready(out)
                dt = time.perf_counter() - tc
                done += n_chunk
                if g_cfg is not None:
                    from .runtime.guardrails import (anomaly_delta,
                                                     summarize)
                    g_cur = summarize(gstate)
                    delta = anomaly_delta(g_prev, g_cur, done,
                                          [done - n_chunk + 1, done])
                    if delta is not None:
                        metrics.anomaly(dict(delta, strategy=name))
                    g_prev = g_cur
                loss = gnorm = None
                if probe is not None:
                    try:
                        loss, gnorm = probe(
                            out, seeds[min(done, len(seeds) - 1)])
                    except Exception as e:  # noqa: BLE001 — never kill the run
                        print(f"metrics: probe disabled for {name} "
                              f"({type(e).__name__}: {str(e)[:120]})",
                              file=sys.stderr)
                        probe = None
                metrics.step(step=done, strategy=name, loss=loss,
                             grad_norm=gnorm,
                             step_time_s=dt / n_chunk, tokens=tokens,
                             model_flops=model_flops, peak=peak,
                             hbm=hbm_high_water())
        else:
            out = fn(params, seeds, tokens, args.model_size, **kwargs)
        jax.block_until_ready(out)
        t1 = time.time()
        results[m] = out
        corner_m = ((lambda w: w[0, 0]) if m in (7, 10, 12)
                    else (lambda w: w[0]))
        print(f"\n{name} takes {t1 - t0} seconds")
        print(f"final {name} layers_params[0]", out.w1[0].shape,
              out.w2[0].shape)
        print(f"final {name} layers_params[0]", corner_m(out.w1)[:5, :5],
              corner_m(out.w2)[:5, :5])

    failed = False
    if args.method in (0, 9):
        # the reference compares DDP vs FSDP (:386-391); we also pin TP to
        # the single-device oracle (same data schedule). The Pallas kernels'
        # tiled f32 accumulation order differs from plain XLA, so loosen
        # the tolerance when they computed method 1; likewise --mixed,
        # where TP's bf16 contraction is split across shards (the psum
        # order composes with bf16 rounding).
        rtol, atol = ((1e-4, 1e-5) if args.pallas else
                      (2e-2, 1e-4) if args.mixed else (1e-5, 1e-7))
        checks = [("ddp", "fsdp", results[2], results[3], rtol, atol, 2),
                  ("1dev", "tp", results[1], results[4], rtol, atol, 1)]
        if args.method == 9:
            # every extension strategy against its oracle (the reference's
            # --method 0 idea extended to the full surface)
            from .parallel import (train_ddp, train_moe_dense,
                                   train_transformer_single)
            # hybrid(dp x tp) == DDP over a dp-sized mesh: TP is an exact
            # decomposition, so only the data axis affects the math
            dp = hybrid_dp()
            ddp_dp = train_ddp(params_for(2), seeds, tokens,
                               args.model_size,
                               make_mesh({DATA_AXIS: dp}), lr=lr,
                               unroll=unroll)
            checks.append(("hybrid", f"ddp({dp})", results[5], ddp_dp,
                           rtol, atol, 5))
            # PP replicates the data; microbatch grads sum to the
            # full-batch grad => equals the single-device run
            checks.append(("pp", "1dev", results[6], results[1],
                           rtol, atol, 1))
            # EP == the dense grouped-dispatch oracle, no mesh involved
            moe_dense = train_moe_dense(params_for(7), seeds, tokens,
                                        args.model_size, lr=lr,
                                        n_groups=n_dev)
            checks.append(("moe_ep", "moe_dense", results[7], moe_dense,
                           1e-4, 1e-5, 7))
            # transformer TP replicates the data => equals transformer
            # single-device
            t_single = train_transformer_single(
                params_for(8), seeds, tokens, args.model_size, lr=lr,
                seq_len=args.seq_len, n_heads=args.heads)
            checks.append(("ttp", "t1dev", results[8], t_single,
                           1e-4, 1e-5, 8))
            # GShard MoE transformer == its dense grouped oracle
            from .parallel import train_moe_transformer_dense
            mt_dense = train_moe_transformer_dense(
                params_for(10), seeds, tokens, args.model_size, lr=lr,
                seq_len=args.seq_len, n_heads=args.heads, n_groups=n_dev)
            checks.append(("moe_tf_ep", "moe_tf_dense", results[10],
                           mt_dense, 1e-4, 1e-5, 10))
            # vocab-parallel LM TP replicates the data => equals the LM
            # single-device oracle on the real objective
            from .parallel import train_lm_single
            lm_single = train_lm_single(
                params_for(11), seeds, tokens, args.model_size, lr=lr,
                seq_len=args.seq_len, n_heads=args.heads)
            checks.append(("lm_tp", "lm_1dev", results[11], lm_single,
                           1e-4, 1e-5, 11))
            # sequence-parallel LM replicates the data too (each shard
            # regenerates the batch and takes its token block) => equals
            # the same single-device oracle
            checks.append(("lm_seq", "lm_1dev", results[13], lm_single,
                           1e-4, 1e-5, 11))
            # GShard MoE-LM == its dense grouped oracle (real loss + aux)
            from .parallel import train_moe_lm_dense
            moe_lm_dense = train_moe_lm_dense(
                params_for(12), seeds, tokens, args.model_size, lr=lr,
                seq_len=args.seq_len, n_heads=args.heads, n_groups=n_dev)
            checks.append(("moe_lm_ep", "moe_lm_dense", results[12],
                           moe_lm_dense, 1e-4, 1e-5, 12))
        for la, lb, a, b, rt, at, fam in checks:
            # leaves-with-paths rather than _fields: the LM family's params
            # nest (blocks is a NamedTuple inside LMParams)
            flat_a = jax.tree_util.tree_flatten_with_path(a)[0]
            flat_b = jax.tree_util.tree_leaves(b)
            flat_0 = jax.tree_util.tree_leaves(params_for(fam))
            agree = True
            for (path, leaf_a), leaf_b, leaf_0 in zip(flat_a, flat_b,
                                                      flat_0):
                field = jax.tree_util.keystr(path)
                bad, text = strategy_disagreement(
                    np.asarray(leaf_a), np.asarray(leaf_b),
                    np.asarray(leaf_0), rt, at)
                if text:
                    print(f"{'SoftAssertionError' if bad else 'relu flips'}"
                          f": {la}{field} vs {lb}{field} {text}")
                agree &= not bad
            # one line per comparison, so a reader (and chip_smoke.py)
            # sees that it ran and what it was held to
            print(f"compared {la} vs {lb}: "
                  f"{'agree' if agree else 'DISAGREE'} "
                  f"(rtol={rt}, atol={at}, {len(flat_0)} arrays)")
            failed |= not agree
    if metrics is not None:
        metrics.close()  # drain the writer: records are on disk on exit
    return 1 if (failed and args.strict) else 0


if __name__ == "__main__":
    sys.exit(main())
