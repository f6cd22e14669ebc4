#!/usr/bin/env python
"""Scaling evidence for the BASELINE north star without multi-chip hardware.

The north star (BASELINE.md) is >=90% of ideal linear scaling for
DDP/FSDP/TP on a v5e-32 slice. One real chip can't measure that, so this
harness produces the strongest evidence available short of the slice:

1. **Real multi-chip codegen**: each strategy's step is AOT-compiled
   against genuine v5e topology descriptors (8 chips = ``v5e:2x4``,
   32 = ``v5e:4x8``) — the same XLA:TPU backend the slice would run —
   and the compiled HLO is checked for the expected collectives and for
   async start/done splits (XLA's latency-hiding scheduler CAN overlap
   them with compute).

2. **An analytic roofline**: per-chip collective bytes per step are known
   in closed form for each strategy (ring all-reduce moves
   ``2*(n-1)/n * bytes``, all-gather/reduce-scatter ``(n-1)/n * bytes``),
   and per-step compute time is anchored to the *measured* single-chip
   benchmark (BENCH r2: 0.92 MFU of the 197 Tflop/s bf16 peak). From
   those, the ICI bandwidth required to hit 90% scaling follows directly:
   with overlap, comm must fit inside compute/0.9; a fully-sequential
   bound (no overlap at all) needs comm <= compute/9.

Emits one JSON line per (strategy, chips) scenario with the HLO evidence
and the roofline numbers, then a summary line. Run on any host:
``JAX_PLATFORMS=cpu python bench_scaling.py`` (needs libtpu AOT support,
present in this image; no TPU attached).
"""

from __future__ import annotations

import json
import os
import sys

import jax

# AOT compilation needs no accelerator: this script compiles for
# described chips from the host backend, wherever it runs.
jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

# Measured anchor (round 2 on the real chip, before PR 1): the step runs
# at this fraction of the chip's bf16 peak at the BASELINE config-5 shape.
MEASURED_MFU = float(os.environ.get("SCALING_MFU", 0.92))
PEAK_FLOPS = 197e12  # v5e bf16 peak (public spec)
# v5e ICI: public spec quotes 1600 Gbps aggregate per chip = 200 GB/s.
# All "required_GBps" fields are gigaBYTES/s on the same scale.
V5E_ICI_GBPS = 200.0


def _mesh(axes: dict, n_chips: int) -> Mesh:
    from jax.experimental import topologies
    name = {8: "v5e:2x4", 32: "v5e:4x8"}[n_chips]
    topo = topologies.get_topology_desc(platform="tpu", topology_name=name)
    devs = np.array(topo.devices).reshape(tuple(axes.values()))
    return Mesh(devs, tuple(axes))


def _struct(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), tree)


def _compile_hlo(step, mesh, param_specs, params):
    f = jax.jit(jax.shard_map(step, mesh=mesh,
                              in_specs=(param_specs, P()),
                              out_specs=param_specs))
    return f.lower(_struct(params),
                   jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()


def _scenarios():
    """(name, chips, builder) for the BASELINE configs that scale.

    Each builder returns ``(step, mesh, param_specs, params,
    flops_per_step_per_chip, comm_bytes_per_chip)``.
    """
    from distributed_llm_code_samples_tpu.models import init_ffn_stack
    from distributed_llm_code_samples_tpu.parallel import (ddp, fsdp, tp)

    def ffn_flops(tokens, d, layers):  # recompute-policy matmul FLOPs
        return 14 * tokens * d * (4 * d) * layers

    def ddp_like(d, layers, tokens, chips, fsdp_mode, mixed=False):
        from distributed_llm_code_samples_tpu.parallel.mesh import DATA_AXIS
        params = init_ffn_stack(jax.random.PRNGKey(0), d, layers)
        pbytes = 4 * params.num_params()
        n = chips
        if fsdp_mode:
            step = fsdp.make_step(tokens, d, 0.1, mixed=mixed)
            specs = fsdp.PARAM_SPECS
            # fwd gather + bwd gather + grad reduce-scatter, (n-1)/n each;
            # under the bf16 policy both gathers ride the wire half-width
            # (the reduce-scatter stays f32 for master-grad exactness)
            gather_w = 0.5 if mixed else 1.0
            comm = (2 * gather_w + 1) * (n - 1) / n * pbytes
        else:
            step = ddp.make_step(tokens, d, 0.1)
            specs = P()  # DDP params replicate
            # ring all-reduce of the full grads
            comm = 2 * (n - 1) / n * pbytes
        mesh = _mesh({DATA_AXIS: chips}, chips)
        # DDP/FSDP shard the *steps* (strided seeds): per-chip compute is
        # the full per-step batch — scaling shows up as steps/sec * n
        return step, mesh, specs, params, ffn_flops(tokens, d, layers), comm

    def tp_case(d, layers, tokens, chips):
        from distributed_llm_code_samples_tpu.parallel.mesh import MODEL_AXIS
        params = init_ffn_stack(jax.random.PRNGKey(0), d, layers)
        step = tp.make_step(tokens, d, 0.1)
        mesh = _mesh({MODEL_AXIS: chips}, chips)
        n = chips
        # one activation all-reduce per layer per direction:
        # 2 dirs * 2(n-1)/n * tokens*d*4 bytes * layers
        comm = 2 * layers * 2 * (n - 1) / n * tokens * d * 4
        return (step, mesh, tp.PARAM_SPECS, params,
                ffn_flops(tokens, d, layers) / n, comm)

    def pp_case(d, layers, tokens, chips, m, v=1):
        # BASELINE config 3's literal ask: the send/recv + barrier path —
        # layers staged on the ppermute ring, activations streaming.
        # v > 1 selects the interleaved virtual-stage schedule: v
        # non-contiguous chunks per device, fill cost (S-1)/v.
        from distributed_llm_code_samples_tpu.parallel import pipeline
        from distributed_llm_code_samples_tpu.parallel.mesh import PIPE_AXIS
        params = init_ffn_stack(jax.random.PRNGKey(0), d, layers)
        if v > 1:
            step = pipeline.make_step(tokens, d, chips, m, 0.1,
                                      schedule="interleaved",
                                      interleave=v)
        else:
            step = pipeline.make_step(tokens, d, chips, m, 0.1)
        mesh = _mesh({PIPE_AXIS: chips}, chips)
        # per tick one activation hop each direction: 2 phases' worth
        # of ticks * microbatch activation bytes (fwd y + bwd dx)
        mb = tokens // m
        ticks = v * m + chips - 1  # v=1: the GPipe M + S - 1
        comm = 2 * ticks * mb * d * 4
        # per-chip compute: each stage runs layers/chips of every
        # microbatch. The schedule bubble — (S-1)/ticks idle slots per
        # stage — caps scaling regardless of ICI, so the pp row's
        # bandwidth headroom is comm-only evidence; the bubble fields
        # report the schedule-side ceiling. GPipe amortizes with more
        # microbatches; the interleaved schedule divides the fill by v
        # on top (bubble (S-1)/(vM+S-1) at the SAME M).
        extra = {
            "bubble_fraction": round((chips - 1) / ticks, 4),
            "max_scaling_from_bubble": round(v * m / ticks, 4),
            "note": "headroom is comm-only; the schedule bubble caps "
                    "scaling at max_scaling_from_bubble — raise "
                    "microbatches (or interleave chunks) to amortize",
        }
        if v > 1:
            extra["interleave"] = v
        return (step, mesh, pipeline.PARAM_SPECS, params,
                ffn_flops(tokens, d, layers) / chips, comm, extra)

    def hybrid_case(d, layers, tokens, dp_n, tp_n):
        # BASELINE config 4: hybrid DDP x MP on one 2-D mesh
        from distributed_llm_code_samples_tpu.parallel import hybrid
        from distributed_llm_code_samples_tpu.parallel.mesh import (
            DATA_AXIS, MODEL_AXIS)
        chips = dp_n * tp_n
        params = init_ffn_stack(jax.random.PRNGKey(0), d, layers)
        step = hybrid.make_step(tokens, d, 0.1)
        mesh = _mesh({DATA_AXIS: dp_n, MODEL_AXIS: tp_n}, chips)
        pbytes = 4 * params.num_params()
        # TP activation psums on the model axis + the DDP grad psum of
        # this shard's 1/tp params on the data axis
        comm = (2 * layers * 2 * (tp_n - 1) / tp_n * tokens * d * 4
                + 2 * (dp_n - 1) / dp_n * pbytes / tp_n)
        return (step, mesh, hybrid.PARAM_SPECS, params,
                ffn_flops(tokens, d, layers) / tp_n, comm)

    toks = 8 * 1024
    return [
        # BASELINE config 2: FSDP, 8-layer d=2048, 8 devices
        ("fsdp_d2048_L8", 8,
         lambda: ddp_like(2048, 8, toks, 8, fsdp_mode=True)),
        # the bf16 mixed-precision FSDP: param gathers at half width —
        # comm drops 3x->2x param bytes, headroom row shows the gain
        ("fsdp_d2048_L8_bf16gather", 8,
         lambda: ddp_like(2048, 8, toks, 8, fsdp_mode=True, mixed=True)),
        # BASELINE config 5 (north star): GPT-2-small-width FFN stack,
        # FSDP on v5e-32
        ("fsdp_d768_L24", 32,
         lambda: ddp_like(768, 24, toks, 32, fsdp_mode=True)),
        ("ddp_d768_L24", 8,
         lambda: ddp_like(768, 24, toks, 8, fsdp_mode=False)),
        ("ddp_d768_L24", 32,
         lambda: ddp_like(768, 24, toks, 32, fsdp_mode=False)),
        # BASELINE config 3, both readings: Megatron MP across chips and
        # the literal send/recv pipeline (8 layers, 8 stages; M=2 keeps
        # the unrolled-schedule AOT compile tractable — ~35s vs >15min at
        # M=8; the per-chip roofline uses the actual M)
        ("tp_d2048_L8", 8, lambda: tp_case(2048, 8, toks, 8)),
        ("pp_d2048_L8_M2", 8, lambda: pp_case(2048, 8, toks, 8, 2)),
        # the interleaved virtual-stage schedule at the same M: 2 chunks
        # per device (16 layers so each holds 2), fill cost halved —
        # the bubble row the gpipe line is compared against
        ("pp_d2048_L16_M2_interleaved", 8,
         lambda: pp_case(2048, 16, toks, 8, 2, v=2)),
        # BASELINE config 4: hybrid DDP(4) x MP(2), 12 layers
        ("hybrid_d2048_L12_dp4tp2", 8,
         lambda: hybrid_case(2048, 12, toks, 4, 2)),
    ]


def _count_hlo_collectives(hlo: str) -> dict:
    """Substring counts of each collective in optimized TPU HLO — the
    op list is utils.hlo's (hyphen-spelled here: backend HLO opcodes),
    substring-matched because TPU codegen wraps collectives in async
    fusions whose defining line spells the op inside a custom-call."""
    from distributed_llm_code_samples_tpu.utils.hlo import COLLECTIVE_OPS
    return {op.replace("_", "-"): hlo.count(op.replace("_", "-"))
            for op in COLLECTIVE_OPS}


class UnknownScenarios(ValueError):
    """A typo'd SCALING_SCENARIOS filter — never a silent empty run."""


def collect(wanted=None, emit=None):
    """Compile + score the scenarios; returns ``(rows, ok)``. Importable
    so the CI test can run it IN-PROCESS: libtpu's AOT lockfile is held
    for the life of a process that has compiled, so a pytest process
    that already ran its own AOT tests cannot delegate this to a
    subprocess. ``emit`` (e.g. print-a-json-line) streams progress."""
    from distributed_llm_code_samples_tpu.utils import count_async_pairs
    ok = True
    rows = []
    if wanted is not None:
        known = {name for name, _, _ in _scenarios()}
        unknown = set(wanted) - known
        if unknown:
            # fail loud: a typo'd filter must not produce an empty-but-
            # "ok" artifact
            raise UnknownScenarios(
                f"unknown SCALING_SCENARIOS {sorted(unknown)} "
                f"(known: {sorted(known)})")
    for name, chips, build in _scenarios():
        if wanted is not None and name not in wanted:
            continue
        try:
            built = build()
            step, mesh, specs, params, flops, comm_bytes = built[:6]
            extra = built[6] if len(built) > 6 else {}
            hlo = _compile_hlo(step, mesh, specs, params)
        except Exception as e:  # noqa: BLE001
            row = {"scenario": name, "chips": chips,
                   "error": str(e)[:300]}
            rows.append(row)
            if emit:
                emit(row)
            ok = False
            continue
        counts = {k: v for k, v in _count_hlo_collectives(hlo).items() if v}
        pairs = {k: v for k, v in dict(count_async_pairs(hlo)).items() if v}
        compute_s = flops / (MEASURED_MFU * PEAK_FLOPS)
        # >=90% scaling: overlapped comm must fit in compute/0.9;
        # a no-overlap schedule needs comm <= compute/9. GB/s = bytes/s
        # / 1e9 — gigaBYTES, compared against V5E_ICI_GBPS below (the
        # spec's 1600 Gbps aggregate = 200 GB/s).
        req_overlap = comm_bytes / (compute_s / 0.9) / 1e9
        req_seq = comm_bytes / (compute_s / 9.0) / 1e9
        row = {
            "scenario": name, "chips": chips,
            "collectives": counts,
            "async_pairs": pairs,
            "comm_gb_per_step_per_chip": round(comm_bytes / 1e9, 4),
            "compute_ms_per_step": round(compute_s * 1e3, 3),
            "required_GBps_90pct_overlapped": round(req_overlap, 2),
            "required_GBps_90pct_sequential": round(req_seq, 2),
            "headroom_x_overlapped": round(V5E_ICI_GBPS / req_overlap, 1),
            **extra,
        }
        rows.append(row)
        if emit:
            emit(row)
    return rows, ok


def main() -> int:
    from distributed_llm_code_samples_tpu.runtime.init import (
        describe_devices, enable_compile_cache)
    enable_compile_cache()
    describe_devices()
    only = os.environ.get("SCALING_SCENARIOS")  # comma-separated filter
    wanted = set(only.split(",")) if only else None
    try:
        rows, ok = collect(wanted, emit=lambda r: print(json.dumps(r)))
    except UnknownScenarios as e:
        print(json.dumps({"error": str(e)[:300]}))
        return 1
    summary = {"summary": "aot_v5e_codegen",
               "anchor_mfu": MEASURED_MFU,
               "v5e_ici_GBps": V5E_ICI_GBPS,
               "ok": ok}
    print(json.dumps(summary))
    artifact = os.environ.get("SCALING_ARTIFACT")
    if artifact:
        with open(artifact, "w") as f:
            json.dump({"rows": rows, **summary}, f, indent=1)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
