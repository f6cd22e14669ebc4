"""`generate` — the serving CLI: drive the decode engine end to end.

Mirrors the training CLI's stance (``cli.py``): the model is the LM
family at the flagged shape (``init_lm`` — random weights unless you
wire your own; the engine is the demonstration target, not the
checkpoint plumbing), prompts are either explicit token-id lists
(``--prompts "3,1,4;9,2"``) or deterministic random draws
(``--prompt_lens 5,9,13`` with ``--prompt_seed``), and the run prints
ONE JSON line with every sequence's tokens plus the engine's
throughput/occupancy/reliability stats. ``--metrics_dir`` streams the
schema-versioned ``decode`` / ``request`` / ``span`` (and, under
``--fleet``, ``router`` + ``fleet``) records through the unified
telemetry writer (``runtime/telemetry.py``) — ``report`` folds them
like any other run, and ``report --slo TTFT_S:ITL_S`` computes SLO
attainment over the completed requests (DESIGN.md section 21).

``--tp N`` runs the Megatron decode layout over an N-way model-axis
mesh (``--fake_devices`` makes that work on CPU, as everywhere else).

Reliability flags (round 10, DESIGN.md section 16):

- ``--snapshot_dir`` runs under the engine supervisor
  (``decode/supervise.py``): per-step atomic snapshots, in-process
  restart ladder, and automatic resume — re-running the same command
  after a crash continues from the snapshot, token-identically.
- ``--chaos SPEC`` injects the decode fault grammar
  (``nan_logits@STEP[:UID]``, ``hang_step@STEP[:SECS]``,
  ``corrupt_block@STEP:BLOCK``, ``kill@STEP``; ``runtime/chaos.py``).
  Requires ``--snapshot_dir`` — recovery resumes from snapshots, the
  train CLI's ``--chaos``/``--checkpoint_dir`` coupling.
- ``--max_retries`` / ``--deadline_steps`` / ``--queue_limit`` /
  ``--preempt_after`` set the engine's ``ServePolicy`` (quarantine
  retry budget, per-request TTL, reject-on-full admission,
  pool-pressure preemption). Bad values are rejected cleanly (rc 2),
  the train CLI's parse-rejection discipline.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def build_generate_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="generate",
        description="Continuous-batching decode over the paged KV engine "
                    "(decode/engine.py)")
    # model shape (the cli.py -m 11 family surface)
    p.add_argument("-d", "--model_size", type=int, default=64)
    p.add_argument("-l", "--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--kv_heads", type=int, default=0,
                   help="GQA KV heads (0 = full MHA); shrinks the KV "
                        "pool by heads/kv_heads")
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--max_seq_len", type=int, default=256)
    p.add_argument("--model_config", default=None, metavar="FILE",
                   help="a published-style config.json (model_type "
                        "jamba: Mamba and attention layers in one "
                        "stack, models/hybrid_lm.py; model_type "
                        "glm4_moe_lite: latent attention and sparse "
                        "experts, models/mla_moe_lm.py; or model_type "
                        "lfm2_moe: gated short convolutions, grouped-"
                        "query attention and sparse experts, "
                        "models/lfm2_moe_lm.py). The model comes "
                        "from its keys, in the type it states; "
                        "-d/-l/--heads/--kv_heads/--vocab/--max_seq_len "
                        "are then ignored, the weights come from -r or "
                        "--weights_from")
    p.add_argument("-r", "--random_seed", type=int, default=0,
                   help="model init seed (the cli.py convention)")
    p.add_argument("--use_rope", action="store_true",
                   help="rotary attention (must match training)")
    # requests — explicit prompts, random draws, or a workload trace
    # (round 19, DESIGN.md section 25): exactly one source
    p.add_argument("--trace", default=None, metavar="FILE",
                   help="replay a workload trace file "
                        "(runtime/workload.py TRACE_VERSION 1 JSONL): "
                        "arrivals paced on the virtual round clock "
                        "(--trace_pace wall for real seconds), tenants "
                        "and sessions tagged through the whole "
                        "telemetry plane; same (trace, seed) replays "
                        "byte-identically")
    p.add_argument("--trace_gen", default=None, metavar="SPEC",
                   help="generate a trace in-process and serve it "
                        "(grammar: n=INT,arrival=poisson:R|bursty:"
                        "R:ON:OFF|ramp:LO:HI,plen=fixed:N|uniform:"
                        "LO:HI|zipf:A:LO:HI,max_new=...,tenants="
                        "a:3;b:1,sessions=K[:GROW],seed=N); pair with "
                        "--trace_out to persist the trace for replay")
    p.add_argument("--trace_out", default=None, metavar="FILE",
                   help="write the --trace_gen trace to FILE "
                        "(atomic publish) so later runs can --trace "
                        "it — the falsifiability handle")
    p.add_argument("--trace_pace", choices=["virtual", "wall"],
                   default=None,
                   help="trace pacing: 'virtual' (default — offsets "
                        "map onto scheduling rounds, fully "
                        "deterministic, the CPU tier-1 mode) or "
                        "'wall' (offsets are real seconds — the chip "
                        "mode; token identity holds, admission order "
                        "may vary with service speed)")
    p.add_argument("--trace_steps_per_s", type=float, default=None,
                   help="virtual-clock rate: rounds per trace second "
                        "(default 8; higher = the same trace replayed "
                        "onto a denser round grid)")
    p.add_argument("--prompts", default=None,
                   help="semicolon-separated comma-lists of token ids, "
                        'e.g. "3,1,4;9,2,6,5"')
    p.add_argument("--prompt_lens", default=None,
                   help="comma-separated lengths of random prompts "
                        "(deterministic per --prompt_seed), e.g. 5,9,13")
    p.add_argument("--prompt_seed", type=int, default=0)
    p.add_argument("--max_new", type=int, default=16)
    # sampling (fused, in-graph; decode/sampling.py)
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy argmax")
    p.add_argument("--top_k", type=int, default=0)
    p.add_argument("--top_p", type=float, default=0.0)
    p.add_argument("--sample_seed", type=int, default=0)
    # engine layout
    p.add_argument("--kv_dtype", choices=["f32", "bf16", "int8"],
                   default="f32")
    p.add_argument("--block_size", type=int, default=16)
    p.add_argument("--n_blocks", type=int, default=0,
                   help="KV pool blocks incl. the scratch block "
                        "(0 = sized for max_slots full sequences)")
    p.add_argument("--max_slots", type=int, default=4)
    p.add_argument("--max_blocks_per_seq", type=int, default=0,
                   help="per-sequence table width (0 = cover "
                        "max_seq_len)")
    p.add_argument("--prefill_chunk", type=int, default=16)
    # raw-latency levers (round 12, DESIGN.md section 18)
    p.add_argument("--speculate", type=int, default=0,
                   help="speculative decoding: draft tokens per decode "
                        "step from the n-gram prompt-copy drafter "
                        "(greedy verification — requires temperature "
                        "0; a step emits 1 + accepted tokens; 0 = off)")
    # shared-prefix KV reuse (round 13, DESIGN.md section 19)
    p.add_argument("--prefix_cache", default=True,
                   action=argparse.BooleanOptionalAction,
                   help="shared-prefix KV reuse (decode/prefix.py): "
                        "requests sharing a prompt prefix map its "
                        "cached full blocks instead of re-prefilling "
                        "them, refcounted + copy-on-write; output "
                        "stays byte-identical (default on; "
                        "--no-prefix_cache restores the private-"
                        "blocks-only engine)")
    # KV memory hierarchy (round 23, DESIGN.md section 29)
    p.add_argument("--spill_blocks", type=int, default=0,
                   help="host-RAM KV spill tier capacity in blocks "
                        "(decode/spill.py): pool-pressure evictions of "
                        "cached prefix blocks demote their bytes to "
                        "host RAM instead of discarding, and a radix "
                        "hit on the spilled edge restores via the "
                        "compiled implant program instead of "
                        "re-prefilling (0 = tier off; requires "
                        "--prefix_cache)")
    p.add_argument("--spill_restore_per_step", type=int, default=2,
                   help="max spilled blocks promoted back per engine "
                        "step — the restore budget that keeps a "
                        "returning session's promotion from stalling "
                        "running decodes (admission defers past it)")
    p.add_argument("--prefix_partial", default=False,
                   action=argparse.BooleanOptionalAction,
                   help="sub-block prefix sharing: a partial-block "
                        "radix hit CoW-copies the shared leading rows "
                        "into a fresh block so short shared system "
                        "prompts save prefill too (f32/bf16 output "
                        "stays byte-identical; int8 rows reuse the "
                        "donor's frozen scale — deterministic, "
                        "documented in DESIGN.md section 29)")
    # parallel strategy
    p.add_argument("--tp", type=int, default=1,
                   help="model-axis size for the Megatron decode layout "
                        "(1 = single-device)")
    p.add_argument("--fake_devices", type=int, default=0)
    # reliability (decode/supervise.py + engine ServePolicy)
    p.add_argument("--snapshot_dir", default=None,
                   help="run under the engine supervisor: per-step "
                        "atomic snapshots + automatic crash-resume "
                        "(re-run the same command to continue)")
    p.add_argument("--chaos", default=None, metavar="SPEC",
                   help="deterministic decode fault injection "
                        "(runtime/chaos.py): comma-separated "
                        "KIND@STEP[:ARG] with KIND in nan_logits/"
                        "hang_step/corrupt_block/kill; requires "
                        "--snapshot_dir")
    p.add_argument("--max_retries", type=int, default=0,
                   help="per-request retry budget for quarantined "
                        "sequences (replay-resumed; 0 = fail on first "
                        "fault)")
    p.add_argument("--deadline_steps", type=int, default=0,
                   help="per-request TTL in engine steps from submit "
                        "(0 = none); expired requests are failed with "
                        "reason 'deadline'")
    p.add_argument("--queue_limit", type=int, default=0,
                   help="bounded waiting queue: submissions past it are "
                        "shed (rejected, not an error; 0 = unbounded)")
    p.add_argument("--preempt_after", type=int, default=0,
                   help="pool-pressure preemption: a head-of-line "
                        "request starved of blocks for N steps evicts "
                        "the youngest running sequence (0 = off)")
    p.add_argument("--snapshot_every", type=int, default=1,
                   help="engine-step cadence of the atomic snapshot "
                        "(1 = every step, maximum recoverability; "
                        "raise it to amortize the host-side "
                        "json+fsync on throughput-critical serving — "
                        "resume is equally correct from an older "
                        "snapshot, it just replays more)")
    p.add_argument("--watchdog_ms", type=int, default=0,
                   help="hung-step watchdog deadline (0 = off); latches "
                        "hung_step evidence in the attempt log")
    p.add_argument("--max_restarts", type=int, default=3,
                   help="in-process restart budget for the supervisor")
    # fleet-scale serving (round 14, DESIGN.md section 20)
    p.add_argument("--fleet", type=int, default=0,
                   help="serve through a multi-engine router "
                        "(decode/fleet.py): N single-device engine "
                        "replicas behind least-loaded + session + "
                        "prefix-affinity admission (N >= 2; 0 = the "
                        "single-engine path, byte-identical to a run "
                        "without fleet flags)")
    p.add_argument("--prefill_engines", type=int, default=0,
                   help="disaggregated prefill/decode: dedicate M of "
                        "the --fleet engines to chunked prefill; "
                        "finished prompts ship to the decode tier via "
                        "the single-sequence KV handoff (requires "
                        "--fleet, M < N)")
    p.add_argument("--fleet_kill", default=None, metavar="ENGINE@ROUND",
                   help="deterministic fleet chaos: kill engine id "
                        "ENGINE (e.g. e1) at the start of fleet round "
                        "ROUND; its in-flight requests migrate to the "
                        "survivors and complete token-identically "
                        "(requires --fleet; a real SIGKILL of the "
                        "worker process under --transport process)")
    # process-boundary fleet (round 16, DESIGN.md section 22)
    p.add_argument("--transport", choices=["inproc", "process", "tcp"],
                   default="inproc",
                   help="fleet transport: 'inproc' (replicas in the "
                        "router's process, the PR 10 fleet), "
                        "'process' (each engine in its OWN worker "
                        "process behind an AF_UNIX socket protocol, KV "
                        "handoffs as CRC-verified wire files — "
                        "decode/worker.py), or 'tcp' (the same worker "
                        "protocol over TCP loopback with reconnect + "
                        "sequence-numbered replay and handoffs "
                        "streamed over a framed side channel — the "
                        "multi-host shape; requires --fleet)")
    p.add_argument("--async_migration", action="store_true",
                   help="live migrations ship the KV snapshot WHILE "
                        "the source keeps decoding; the target "
                        "teacher-forces the ship-window delta at "
                        "commit (token-identical; requires --fleet)")
    p.add_argument("--fleet_chaos", default=None, metavar="SPEC",
                   help="fleet-transport fault injection "
                        "(runtime/chaos.py FLEET_KINDS): comma-"
                        "separated KIND@ROUND[:ARG] with KIND in "
                        "kill_worker (SIGKILL decode worker :IDX, "
                        "default e0) / hang_worker (first decode "
                        "worker goes silent :SECS) / corrupt_wire "
                        "(bit-flip the next wire handoff; CRC-"
                        "rejected) / partition_worker (drop the first "
                        "decode worker's link both ways for :SECS, "
                        "then heal — reconnect-and-replay; tcp only) / "
                        "slow_link (inject :MS latency per call on "
                        "the first decode link — must NOT page the "
                        "liveness ladder) / drop_conn (mid-message "
                        "RST on the first decode link; tcp only); "
                        "requires --fleet and --transport "
                        "process/tcp")
    # live weight hot-swap (round 17, DESIGN.md section 23)
    p.add_argument("--deploy_dir", default=None, metavar="CKPT_DIR",
                   help="weight-version ledger: a trainer checkpoint "
                        "dir (the existing atomic fsync+CRC publish "
                        "IS the deploy input); with --deploy_round "
                        "the fleet rolls the newest published step "
                        "through every engine mid-serve (requires "
                        "--fleet)")
    p.add_argument("--deploy_round", type=int, default=None,
                   metavar="ROUND",
                   help="fleet round to START the rolling deploy at "
                        "(drain-by-migration one engine at a time, "
                        "zero shed; requires --deploy_dir)")
    p.add_argument("--deploy_step", type=int, default=None,
                   help="explicit checkpoint step to deploy (default: "
                        "the newest published step at fire time — the "
                        "CRC ladder then accepts it or rolls back to "
                        "latest_verified_step)")
    p.add_argument("--deploy_watch", type=float, default=None,
                   metavar="SECS",
                   help="deploy-on-publish watcher: poll --deploy_dir's "
                        "latest VERIFIED step every SECS seconds "
                        "mid-serve and roll the fleet forward when it "
                        "advances — the trainer's atomic publish "
                        "becomes the deploy trigger (requires --fleet "
                        "and --deploy_dir; mutually exclusive with "
                        "--deploy_round)")
    p.add_argument("--weights_from", default=None, metavar="CKPT_DIR",
                   help="serve weights restored from a checkpoint dir "
                        "instead of the --random_seed init (the "
                        "pinned-version oracle surface: a single "
                        "engine serving exactly what a deploy "
                        "published; single-engine runs only)")
    p.add_argument("--weights_step", type=int, default=None,
                   help="checkpoint step for --weights_from (default: "
                        "newest verified)")
    # closed-loop autoscaling + tenant QoS (round 20, DESIGN.md
    # section 26)
    p.add_argument("--qos", default=None, metavar="SPEC",
                   help="per-tenant scheduling policy (runtime/"
                        "policy.py): discipline=fcfs|wfq,weights="
                        "a:3;b:1,budget=INT,predictive_shed=0|1 — "
                        "virtual-time weighted-fair admission over "
                        "served tokens, per-tenant resident token "
                        "budgets, and predictive deadline-miss shed "
                        "(host-side scheduling only: each request's "
                        "tokens are unchanged, only WHEN it admits)")
    p.add_argument("--autoscale", default=None, metavar="SPEC",
                   help="closed-loop decode-tier autoscaler "
                        "(decode/autoscale.py): min=,max=,up=,down=,"
                        "hysteresis=,cooldown= — spawns WARMED "
                        "engines under sustained queue pressure, "
                        "drains idle ones with zero shed; requires "
                        "--fleet and a trace source (the controller "
                        "ticks on the replay's round clock)")
    p.add_argument("--watch", default=None, metavar="SPEC",
                   help="fleet watchtower (runtime/watch.py): "
                        "deadline=ROUNDS,budget=F,burn=F,fast=N,"
                        "slow=N,queue=N,imbalance=F,collapse=N,"
                        "incidents=N — streaming detectors on the "
                        "replay's round clock emitting `alert` "
                        "records with a fired->resolved lifecycle "
                        "(burn-rate over the round-denominated "
                        "deadline, sustained queue depth/imbalance, "
                        "throughput collapse, incident rate); active "
                        "alerts ride fleet_status.json for fleetstat/"
                        "report --follow; requires --fleet and a "
                        "trace source")
    p.add_argument("--policy", default=None, metavar="LABEL",
                   help="policy label stamped into the run's meta "
                        "records and payload — `report --slo` folds "
                        "per-policy attainment by it (the offline "
                        "policy-search key over a committed trace)")
    # observability
    p.add_argument("--metrics_dir", default=None)
    p.add_argument("--log_every", type=int, default=4,
                   help="decode-record cadence in engine steps")
    p.add_argument("--engine_id", default=None,
                   help="engine label stamped in the run's meta records "
                        "(default: the metrics dir's basename); the "
                        "multi-stream `report A B ...` merge keys "
                        "per-engine percentiles on it")
    return p


def _fleet_main(args, prompts, cfg, policy, params, fleet_kill,
                fleet_chaos, argv, trace_doc=None, qos=None,
                autoscale=None, watch=None) -> int:
    """The ``--fleet N`` run: N engine replicas behind the router
    (``decode/fleet.py``), each with its own metrics stream under
    ``--metrics_dir/<engine_id>`` plus a ``router`` stream for the
    schema-v8 routing records — ``report m/router m/p0 m/e0 ...``
    merges them onto one timeline. Prints the same one-line JSON
    payload shape as the single-engine path, with a ``fleet`` block.

    ``--transport process`` (round 16) runs every replica in its OWN
    worker process (``decode/worker.py``): the same router, the same
    payload shape, but an engine kill is a real SIGKILL, handoffs are
    CRC-verified wire files, and the per-engine metrics streams are
    written by the workers themselves."""
    import json as _json
    import time as _time

    import jax

    from .engine import AdmissionError, DecodeEngine
    from .fleet import FleetRouter

    writers = []
    router_metrics = None

    def _writer(eid):
        from ..decode.fleet import PREFILL_PREFIX
        from ..runtime.telemetry import TelemetryWriter
        role = ("router" if eid == "router" else
                "prefill" if eid.startswith(PREFILL_PREFIX) else
                "decode")
        meta = {"argv": list(argv or []), "subcommand": "generate",
                "engine_id": eid, "role": role, "fleet": args.fleet,
                "prefill_engines": args.prefill_engines,
                "transport": args.transport,
                "kv_dtype": args.kv_dtype,
                "n_prompts": len(prompts), "max_new": args.max_new,
                "device_kind": jax.devices()[0].device_kind}
        if args.policy:
            meta["policy"] = args.policy
        if args.qos:
            meta["qos"] = args.qos
        w = TelemetryWriter(os.path.join(args.metrics_dir, eid),
                            meta=meta)
        writers.append(w)
        return w

    def make_engine(eid):
        return DecodeEngine(params, args.heads, cfg, policy=policy,
                            qos=qos,
                            metrics=(_writer(eid) if args.metrics_dir
                                     else None))

    router = None
    handles = None
    t0 = _time.perf_counter()
    try:
        if args.metrics_dir:
            router_metrics = _writer("router")
        if args.transport in ("process", "tcp"):
            import dataclasses as _dc
            import tempfile as _tempfile

            from .worker import spawn_fleet_handles
            family = "tcp" if args.transport == "tcp" else "unix"
            spool = (os.path.join(args.metrics_dir, "spool")
                     if args.metrics_dir
                     else _tempfile.mkdtemp(prefix="fleet_spool_"))
            model = {"vocab": args.vocab, "model_size": args.model_size,
                     "layers": args.layers, "heads": args.heads,
                     "kv_heads": args.kv_heads or None,
                     "max_seq_len": args.max_seq_len,
                     "random_seed": args.random_seed}
            worker_meta = {"argv": list(argv or []),
                           "subcommand": "generate",
                           "fleet": args.fleet,
                           "transport": args.transport,
                           "prefill_engines": args.prefill_engines,
                           "kv_dtype": args.kv_dtype,
                           "n_prompts": len(prompts),
                           "max_new": args.max_new}
            if args.policy:
                worker_meta["policy"] = args.policy
            if args.qos:
                worker_meta["qos"] = args.qos
            handles = spawn_fleet_handles(
                args.fleet, args.prefill_engines, spool,
                model=model, config=_dc.asdict(cfg),
                policy=_dc.asdict(policy),
                qos=(qos.as_dict() if qos is not None else None),
                metrics_root=args.metrics_dir or None,
                meta=worker_meta, family=family)
            router = FleetRouter(None, args.fleet,
                                 args.prefill_engines,
                                 metrics=router_metrics,
                                 handles=handles,
                                 fleet_chaos=fleet_chaos,
                                 async_migration=args.async_migration)
        else:
            router = FleetRouter(make_engine, args.fleet,
                                 args.prefill_engines,
                                 metrics=router_metrics,
                                 fleet_chaos=fleet_chaos,
                                 async_migration=args.async_migration)
        if fleet_kill is not None:
            router.schedule_kill(*fleet_kill)
        if args.deploy_round is not None:
            router.schedule_deploy(args.deploy_dir, args.deploy_round,
                                   step=args.deploy_step)
        if args.deploy_watch is not None:
            router.deploy_watch(args.deploy_dir, args.deploy_watch)
        controller = None
        if autoscale is not None:
            from .autoscale import AutoscaleController
            if args.transport in ("process", "tcp"):
                from .worker import spawn_worker

                def _spawn(eid):
                    mdir = (os.path.join(args.metrics_dir, eid)
                            if args.metrics_dir else None)
                    return spawn_worker(
                        eid, "decode", spool, model=model,
                        config=_dc.asdict(cfg),
                        policy=_dc.asdict(policy),
                        qos=(qos.as_dict() if qos is not None
                             else None),
                        metrics_dir=mdir,
                        meta={**worker_meta, "engine_id": eid,
                              "role": "decode"},
                        family=family)
            else:
                from .fleet import EngineHandle

                def _spawn(eid):
                    return EngineHandle(eid, make_engine(eid),
                                        "decode")
            controller = AutoscaleController(router, autoscale,
                                             _spawn,
                                             metrics=router_metrics)
        tower = None
        if watch is not None:
            from ..runtime.watch import Watchtower
            tower = Watchtower(router, watch, metrics=router_metrics)
        shed = 0
        workload = None
        if trace_doc is not None:
            from .workload_driver import replay_trace
            workload = replay_trace(
                router, *trace_doc, vocab=args.vocab,
                pace=args.trace_pace or "virtual",
                steps_per_s=(args.trace_steps_per_s
                             if args.trace_steps_per_s is not None
                             else 8.0),
                log_every=args.log_every, metrics=router_metrics,
                autoscale=controller, watch=tower)
            shed = workload["shed"]
        else:
            for pr in prompts:
                try:
                    router.submit(pr, args.max_new)
                except AdmissionError:
                    shed += 1       # the router recorded the shed
            router.run(log_every=args.log_every)
        # fetch outcomes BEFORE close: under the process transport
        # these are protocol calls the shut-down workers can't answer
        finished = router.results()
        failed = router.failed()
        stats = router.fleet_stats()
    except (ValueError, RuntimeError) as e:
        # RuntimeError covers the fleet's own liveness failures (last
        # decode engine killed, fleet stalled) — a clean rc-2 error,
        # not a traceback, with the buffered telemetry flushed and
        # every worker process reaped
        print(f"error: {e}", file=sys.stderr)
        return 2
    finally:
        if router is not None:
            router.close()      # workers flush their telemetry + exit
        elif handles is not None:
            # spawn succeeded but router construction raised (e.g. a
            # worker died before the fingerprint cross-check): the
            # detached workers must still be reaped — no orphans
            for h in handles:
                h.kill()
        for w in writers:
            w.close()
    wall = _time.perf_counter() - t0

    sequences = [{"uid": u, "tokens": toks,
                  "prompt_len": (len(router.requests[u]["prompt"])
                                 if u in router.requests else None)}
                 for u, toks in sorted(finished.items())]
    new_tokens = sum(len(s["tokens"]) - (s["prompt_len"] or 0)
                     for s in sequences)
    payload = {
        "sequences": sequences,
        "failed": {str(u): dict(info)
                   for u, info in sorted(failed.items())},
        "tokens_generated": new_tokens,
        "wall_s": round(wall, 4),
        "tokens_per_sec": round(new_tokens / wall, 2),
        "kv_dtype": args.kv_dtype,
        "transport": args.transport,
        "fleet": stats,
        "fleet_rounds": stats["rounds"],
        "shed": shed,
    }
    if workload is not None:
        payload["workload"] = workload
    if controller is not None:
        payload["autoscale"] = {
            "scale_ups": controller.scale_ups,
            "scale_downs": controller.scale_downs,
            "history": [{"round": r, "event": e, "reason": why}
                        for r, e, why in controller.history],
        }
    if tower is not None:
        payload["watch"] = {
            "fired": tower.fired,
            "resolved": tower.resolved,
            "history": [{"round": r, "event": e, "detector": d}
                        for r, e, d in tower.history],
        }
    if args.policy:
        payload["policy"] = args.policy
    if args.metrics_dir:
        # where the live ops plane lives: `fleetstat <this>` renders
        # the router's atomic status doc, mid-run or after
        payload["status_doc"] = os.path.join(args.metrics_dir,
                                             "router")
    print(_json.dumps(payload))
    return 0


def generate_main(argv=None) -> int:
    p = build_generate_parser()
    args = p.parse_args(argv)

    if args.fake_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.fake_devices}").strip()

    import jax
    if args.fake_devices:
        jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from ..runtime.init import describe_devices, enable_compile_cache
    enable_compile_cache()

    from ..models import init_lm
    from .engine import AdmissionError, DecodeEngine, EngineConfig, \
        ServePolicy
    from .model_config import engine_from_config, params_from_config

    model_config = None
    if args.model_config:
        try:
            with open(args.model_config) as f:
                model_config = json.load(f)
            # the prompts below draw their ids from the model's own
            # vocabulary, the reservation from its own context
            args.vocab = int(model_config["vocab_size"])
            args.max_seq_len = int(
                model_config["max_position_embeddings"])
        except (OSError, ValueError, KeyError) as e:
            print(f"error: --model_config: {e!r}", file=sys.stderr)
            return 2

    n_sources = sum(x is not None for x in
                    (args.prompts, args.prompt_lens, args.trace,
                     args.trace_gen))
    if n_sources != 1:
        print("error: pass exactly one of --prompts / --prompt_lens / "
              "--trace / --trace_gen", file=sys.stderr)
        return 2
    trace_mode = args.trace is not None or args.trace_gen is not None
    # trace-only knobs reject without a trace source (the fleet-flag
    # discipline: silently ignoring them would break a scripted run)
    if not trace_mode and (args.trace_out or args.trace_pace
                           or args.trace_steps_per_s is not None):
        print("error: --trace_out/--trace_pace/--trace_steps_per_s "
              "shape a trace replay: pass --trace FILE or "
              "--trace_gen SPEC", file=sys.stderr)
        return 2
    if args.trace_out and args.trace_gen is None:
        print("error: --trace_out persists a GENERATED trace: pass "
              "--trace_gen SPEC (a --trace file already exists)",
              file=sys.stderr)
        return 2
    if args.trace_steps_per_s is not None \
            and args.trace_steps_per_s <= 0:
        print(f"error: --trace_steps_per_s must be > 0, got "
              f"{args.trace_steps_per_s}", file=sys.stderr)
        return 2
    if trace_mode and (args.snapshot_dir or args.chaos
                       or args.watchdog_ms):
        print("error: --trace replay drives the engine directly "
              "(chaos composes at the FLEET level: --fleet_kill / "
              "--fleet_chaos); drop --snapshot_dir/--chaos/"
              "--watchdog_ms", file=sys.stderr)
        return 2
    trace_doc = None
    if trace_mode:
        from ..runtime.workload import (TraceError, generate_trace,
                                        materialize_prompt,
                                        read_trace, write_trace)
        try:
            if args.trace is not None:
                trace_doc = read_trace(args.trace)
            else:
                trace_doc = generate_trace(args.trace_gen)
                if args.trace_out:
                    write_trace(args.trace_out, *trace_doc)
            prompts = [materialize_prompt(trace_doc[0], e, args.vocab)
                       for e in trace_doc[1]]
        except (TraceError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    elif args.prompts is not None:
        try:
            prompts = [[int(t) for t in grp.split(",") if t.strip()]
                       for grp in args.prompts.split(";") if grp.strip()]
        except ValueError:
            print(f"error: unparseable --prompts {args.prompts!r}",
                  file=sys.stderr)
            return 2
    else:
        try:
            lens = [int(x) for x in args.prompt_lens.split(",")
                    if x.strip()]
        except ValueError:
            print(f"error: unparseable --prompt_lens "
                  f"{args.prompt_lens!r}", file=sys.stderr)
            return 2
        rng = np.random.default_rng(args.prompt_seed)
        prompts = [rng.integers(0, args.vocab, size=n).tolist()
                   for n in lens]
    if not prompts or any(not pr for pr in prompts):
        print("error: need at least one non-empty prompt",
              file=sys.stderr)
        return 2

    chaos_plan = None
    if args.chaos:
        if not args.snapshot_dir:
            print("error: --chaos requires --snapshot_dir (recovery "
                  "resumes from engine snapshots)", file=sys.stderr)
            return 2
        from ..runtime.chaos import FaultPlan, validate_decode_plan
        try:
            chaos_plan = FaultPlan.parse(args.chaos)
            validate_decode_plan(chaos_plan)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    if args.watchdog_ms and not args.snapshot_dir:
        print("error: --watchdog_ms runs inside the supervisor: pass "
              "--snapshot_dir", file=sys.stderr)
        return 2
    if args.snapshot_every < 1:
        print(f"error: --snapshot_every must be >= 1, got "
              f"{args.snapshot_every}", file=sys.stderr)
        return 2
    # the supervisor-only flags reject consistently instead of some
    # silently no-opping: a user who set them expects supervision
    if args.snapshot_every != 1 and not args.snapshot_dir:
        print("error: --snapshot_every is the supervisor's snapshot "
              "cadence: pass --snapshot_dir", file=sys.stderr)
        return 2
    if args.max_restarts != 3 and not args.snapshot_dir:
        print("error: --max_restarts is the supervisor's restart "
              "budget: pass --snapshot_dir", file=sys.stderr)
        return 2

    # fleet flags (round 14): reject cleanly up front — the train-CLI
    # parse-rejection discipline. No --fleet means the single-engine
    # code path below runs UNTOUCHED (byte-identical to a CLI without
    # these flags).
    if not args.fleet and (args.prefill_engines or args.fleet_kill
                           or args.transport != "inproc"
                           or args.async_migration
                           or args.fleet_chaos or args.deploy_dir
                           or args.deploy_round is not None
                           or args.deploy_step is not None
                           or args.deploy_watch is not None
                           or args.autoscale or args.watch):
        print("error: --prefill_engines/--fleet_kill/--transport/"
              "--async_migration/--fleet_chaos/--deploy_*/"
              "--autoscale/--watch are "
              "fleet flags: pass --fleet N (N >= 2)", file=sys.stderr)
        return 2
    if args.autoscale and not trace_mode:
        print("error: --autoscale drives the trace replay loop (the "
              "controller ticks on the round clock between arrivals): "
              "pass --trace FILE or --trace_gen SPEC", file=sys.stderr)
        return 2
    if args.watch and not trace_mode:
        print("error: --watch detectors fold the trace replay's round "
              "clock (that's what makes the alert history replayable): "
              "pass --trace FILE or --trace_gen SPEC", file=sys.stderr)
        return 2
    if args.policy is not None and not args.policy.strip():
        print("error: --policy needs a non-empty label",
              file=sys.stderr)
        return 2
    if args.weights_from is None and args.weights_step is not None:
        print("error: --weights_step names a step of --weights_from — "
              "pass both", file=sys.stderr)
        return 2
    if args.weights_from and args.fleet:
        print("error: --weights_from is the single-engine oracle "
              "surface; a fleet takes new weights through "
              "--deploy_dir/--deploy_round instead", file=sys.stderr)
        return 2
    fleet_kill = None
    fleet_chaos = None
    if args.fleet:
        if args.fleet < 2:
            print(f"error: --fleet needs >= 2 engines, got "
                  f"{args.fleet} (a fleet of one is the default "
                  "single-engine path — drop the flag)",
                  file=sys.stderr)
            return 2
        if not 0 <= args.prefill_engines < args.fleet:
            print(f"error: --prefill_engines must leave >= 1 decode "
                  f"engine: got {args.prefill_engines} of "
                  f"{args.fleet}", file=sys.stderr)
            return 2
        if args.tp > 1:
            print("error: --fleet runs single-device replicas (the KV "
                  "handoff has no TP path); drop --tp", file=sys.stderr)
            return 2
        if args.snapshot_dir or args.chaos or args.watchdog_ms:
            print("error: --snapshot_dir/--chaos/--watchdog_ms drive "
                  "the single-engine supervisor; the fleet owns "
                  "failover in-process (fleet chaos: --fleet_kill "
                  "ENGINE@ROUND)", file=sys.stderr)
            return 2
        if args.engine_id is not None:
            # the fleet names its own streams (p0../e0../router);
            # silently ignoring the flag would break a user scripting
            # per-host labels — same discipline as the flags above
            print("error: --engine_id names a single engine's stream; "
                  "the fleet stamps its replicas p0../e0../router "
                  "under --metrics_dir — drop the flag",
                  file=sys.stderr)
            return 2
        if args.fleet_kill:
            eng_id, sep, rnd = args.fleet_kill.partition("@")
            try:
                at_round = int(rnd)
            except ValueError:
                at_round = -1
            if not eng_id or not sep or at_round < 0:
                print(f"error: unparseable --fleet_kill "
                      f"{args.fleet_kill!r} (want ENGINE@ROUND, e.g. "
                      "e1@6)", file=sys.stderr)
                return 2
            if (args.fleet - args.prefill_engines == 1
                    and eng_id == "e0"):
                # knowable at parse time: killing the sole decode
                # engine leaves the fleet nowhere to migrate
                print("error: --fleet_kill e0 would kill the only "
                      "decode engine in this fleet (the survivors "
                      "have nowhere to migrate its requests) — add "
                      "decode engines or kill a prefill engine",
                      file=sys.stderr)
                return 2
            fleet_kill = (eng_id, at_round)
        if args.deploy_watch is not None:
            if args.deploy_watch <= 0:
                print(f"error: --deploy_watch must be > 0 seconds, "
                      f"got {args.deploy_watch}", file=sys.stderr)
                return 2
            if not args.deploy_dir:
                print("error: --deploy_watch polls --deploy_dir's "
                      "ledger — pass both", file=sys.stderr)
                return 2
            if args.deploy_round is not None:
                print("error: --deploy_watch and --deploy_round are "
                      "two triggers for one deploy: pick one (watch "
                      "polls the ledger; round fires at a fixed "
                      "round)", file=sys.stderr)
                return 2
            if args.deploy_step is not None:
                # the watcher deploys whatever latest_verified
                # advances to — silently dropping a pinned step would
                # be exactly the ignored-flag failure this block
                # exists to reject
                print("error: --deploy_watch tracks the ledger's "
                      "latest verified step; an explicit "
                      "--deploy_step needs --deploy_round",
                      file=sys.stderr)
                return 2
        elif (args.deploy_round is None) != (args.deploy_dir is None):
            print("error: a rolling deploy needs both --deploy_dir "
                  "(the version ledger) and --deploy_round (when to "
                  "roll; or --deploy_watch to poll for publishes)",
                  file=sys.stderr)
            return 2
        if args.deploy_step is not None and not args.deploy_dir:
            print("error: --deploy_step names a step of --deploy_dir "
                  "— pass both", file=sys.stderr)
            return 2
        if args.deploy_round is not None and args.deploy_round < 0:
            print(f"error: --deploy_round must be >= 0, got "
                  f"{args.deploy_round}", file=sys.stderr)
            return 2
        if args.fleet_chaos:
            from ..runtime.chaos import FaultPlan, validate_fleet_plan
            try:
                fleet_chaos = FaultPlan.parse(args.fleet_chaos)
                validate_fleet_plan(fleet_chaos)
            except ValueError as e:
                print(f"error: {e}", file=sys.stderr)
                return 2
            kinds = {f.kind for f in fleet_chaos.faults}
            if (kinds - {"corrupt_deploy"}
                    and args.transport not in ("process", "tcp")):
                # worker faults need a boundary that can actually
                # fail: a worker that can die/go silent, a wire file
                # that can tear — in-process has neither
                # (corrupt_deploy tears a CHECKPOINT file, a surface
                # both transports share)
                print("error: --fleet_chaos drills the process "
                      "boundary: pass --transport process or tcp "
                      "(corrupt_deploy alone runs on either)",
                      file=sys.stderr)
                return 2
            if (kinds & {"partition_worker", "drop_conn"}
                    and args.transport != "tcp"):
                # only the TCP transport carries a reconnect ladder
                # to drill — an AF_UNIX EOF is an honest death
                print("error: partition_worker/drop_conn drill the "
                      "reconnect ladder: pass --transport tcp",
                      file=sys.stderr)
                return 2
            if "corrupt_deploy" in kinds and args.deploy_round is None:
                print("error: corrupt_deploy tears a SCHEDULED "
                      "deploy's checkpoint: pass --deploy_dir/"
                      "--deploy_round", file=sys.stderr)
                return 2
            n_decode = args.fleet - args.prefill_engines
            for f in fleet_chaos.faults:
                if f.kind != "kill_worker":
                    continue
                idx = 0 if f.arg is None else int(f.arg)
                if idx >= n_decode:
                    print(f"error: kill_worker index {idx} names "
                          f"e{idx}, but this fleet has {n_decode} "
                          "decode engine(s)", file=sys.stderr)
                    return 2
                if n_decode == 1:
                    print("error: kill_worker would kill the only "
                          "decode engine in this fleet (the survivors "
                          "have nowhere to migrate its requests)",
                          file=sys.stderr)
                    return 2

    if trace_doc is not None:
        # per-entry max_new: the reservation must cover the LONGEST
        # (prompt + continuation) the trace asks for
        need_tokens = max(len(pr) + int(e["max_new"])
                          for pr, e in zip(prompts, trace_doc[1]))
    else:
        need_tokens = max(len(pr) for pr in prompts) + args.max_new
    mbps = args.max_blocks_per_seq or -(
        -min(args.max_seq_len, need_tokens) // args.block_size)
    n_blocks = args.n_blocks or 1 + args.max_slots * mbps
    try:
        cfg = EngineConfig(
            block_size=args.block_size, n_blocks=n_blocks,
            max_slots=args.max_slots, max_blocks_per_seq=mbps,
            prefill_chunk=args.prefill_chunk, kv_dtype=args.kv_dtype,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, seed=args.sample_seed,
            use_rope=args.use_rope, speculate=args.speculate,
            prefix_cache=args.prefix_cache,
            spill_blocks=args.spill_blocks,
            spill_restore_per_step=args.spill_restore_per_step,
            prefix_partial=args.prefix_partial)
        policy = ServePolicy(
            queue_limit=args.queue_limit,
            deadline_steps=args.deadline_steps,
            max_retries=args.max_retries,
            preempt_after_steps=args.preempt_after)
        # the serving-policy layer (round 20): both specs are
        # validated HERE so a malformed one rejects rc 2 with the
        # parser's one-line named offense, never mid-run
        qos = None
        if args.qos:
            from ..runtime.policy import parse_qos_spec
            qos = parse_qos_spec(args.qos)
        autoscale_policy = None
        if args.autoscale:
            from ..runtime.policy import parse_autoscale_spec
            autoscale_policy = parse_autoscale_spec(args.autoscale)
        watch_policy = None
        if args.watch:
            from ..runtime.watch import parse_watch_spec
            watch_policy = parse_watch_spec(args.watch)
        # the flags are sound: say where this runs before any device
        # work starts
        platform = describe_devices()["platform"]
        if platform == "tpu" and args.transport in ("process", "tcp"):
            # one chip, one process: this parent holds the chip the
            # moment it touched jax, so engine workers started from it
            # could only fail or hang waiting for the device
            raise ValueError(
                f"--transport {args.transport} starts each engine in "
                "its own worker process, and on a TPU the parent "
                "already holds the chip, so the workers can never get "
                "it; use --transport inproc here (process/tcp are "
                "verified on the CPU backend only)")
        # under the process transport the router never touches weights
        # — each worker rebuilds them from the recipe (same seed, same
        # bits) — so building them here would just double peak host
        # memory for nothing
        params = None
        if model_config is not None:
            params = params_from_config(model_config, args.random_seed)
            kinds = {k for k, _ in params.layers} - {"attn", "latent"}
            if kinds and (args.fleet or args.snapshot_dir):
                # both move a sequence by its ONE block table alone
                # (handoff, snapshot-resume): refused up front, by what
                # the model is, and not mid-serve
                why = ("it moves a sequence by one block table, and "
                       "theirs is a second one"
                       if kinds <= {"window", "chunked"}
                       else "they cannot carry their recurrent state yet")
                raise ValueError(
                    "--fleet / --snapshot_dir are not served for a "
                    f"model with {'/'.join(sorted(kinds))} layers: {why}")
        elif not (args.fleet and args.transport in ("process", "tcp")):
            params = init_lm(jax.random.PRNGKey(args.random_seed),
                             args.vocab, args.model_size, args.layers,
                             max_seq_len=args.max_seq_len,
                             n_heads=args.heads,
                             n_kv_heads=args.kv_heads or None)
        if args.weights_from:
            # serve FROM a published checkpoint (the deploy drill's
            # pinned-version oracle): the init above is the
            # architecture template the ledger restores into — a
            # mismatched shape rejects rc 2 like any other bad flag
            from ..runtime.weights import VersionLedger
            ledger = VersionLedger(args.weights_from)
            w_step = args.weights_step
            if w_step is None:
                w_step = ledger.latest_verified()
                if w_step is None:
                    raise ValueError("no verified checkpoint under "
                                     f"{args.weights_from}")
            try:
                params = ledger.load(w_step, params)
            except (OSError, RuntimeError) as e:
                raise ValueError(f"--weights_from: {e}") from None
        mesh = None
        tp = 1
        if args.tp > 1:
            from ..parallel import MODEL_AXIS, make_mesh
            # the payload/meta report the EFFECTIVE mesh size, never the
            # request — a clamped run must not masquerade as N-way TP
            tp = min(args.tp, jax.device_count())
            if tp < args.tp:
                print(f"generate: --tp {args.tp} clamped to {tp} "
                      f"({jax.device_count()} device(s) visible; use "
                      "--fake_devices on CPU)", file=sys.stderr)
            if tp > 1:
                mesh = make_mesh({MODEL_AXIS: tp})
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    if chaos_plan is not None:
        # the pool size is known here: a block id typo must reject rc 2
        # instead of burning the supervisor's whole restart ladder on a
        # deterministic ValueError at fire time
        for f in chaos_plan.faults:
            if f.kind == "corrupt_block" and int(f.arg) >= cfg.n_blocks:
                print(f"error: corrupt_block block {int(f.arg)} outside "
                      f"the pool ({cfg.n_blocks} block(s) incl. "
                      "scratch)", file=sys.stderr)
                return 2

    if args.fleet:
        return _fleet_main(args, prompts, cfg, policy, params,
                           fleet_kill, fleet_chaos, argv,
                           trace_doc=trace_doc, qos=qos,
                           autoscale=autoscale_policy,
                           watch=watch_policy)

    metrics = None
    engine_id = args.engine_id
    if args.metrics_dir:
        from ..runtime.telemetry import TelemetryWriter
        if engine_id is None:
            engine_id = os.path.basename(
                os.path.normpath(args.metrics_dir))
        meta = {
            "argv": list(argv or []), "subcommand": "generate",
            "engine_id": engine_id,
            "vocab": args.vocab, "model_size": args.model_size,
            "layers": args.layers, "heads": args.heads,
            "kv_dtype": args.kv_dtype, "max_slots": args.max_slots,
            "block_size": args.block_size, "tp": tp,
            "speculate": args.speculate,
            "prefix_cache": args.prefix_cache,
            "n_prompts": len(prompts), "max_new": args.max_new,
            "device_kind": jax.devices()[0].device_kind}
        if args.policy:
            # the offline policy-search key: `report --slo` folds
            # per-policy attainment by this meta label
            meta["policy"] = args.policy
        if args.qos:
            meta["qos"] = args.qos
        if args.snapshot_dir:
            meta["snapshot_dir"] = args.snapshot_dir
            meta["attempt_log"] = os.path.join(
                args.snapshot_dir, "serve_supervise.jsonl")
        metrics = TelemetryWriter(args.metrics_dir, meta=meta)

    mesh_kw = dict(mesh=mesh, policy=policy, qos=qos)

    def make_engine(**kw):
        if model_config is not None:
            return engine_from_config(model_config, params,
                                      engine_config=cfg, **mesh_kw, **kw)
        return DecodeEngine(params, args.heads, cfg, **mesh_kw, **kw)

    shed = 0
    workload = None
    prior_tokens = 0
    resumed_from = None
    t0 = time.perf_counter()
    try:
        if args.snapshot_dir:
            from .supervise import load_snapshot, supervise_decode
            snap = load_snapshot(args.snapshot_dir)
            if snap is not None:
                resumed_from = int(snap["step"])
                prior_tokens = int(
                    snap["counters"]["tokens_generated"])
                print(f"generate: resuming from snapshot step "
                      f"{resumed_from} in {args.snapshot_dir} (prompt "
                      "flags ignored — the snapshot is authoritative)",
                      file=sys.stderr)
            engine = supervise_decode(
                make_engine,
                [(pr, args.max_new) for pr in prompts],
                snapshot_dir=args.snapshot_dir, chaos=chaos_plan,
                watchdog_ms=args.watchdog_ms, metrics=metrics,
                log_every=args.log_every,
                snapshot_every=args.snapshot_every,
                max_restarts=args.max_restarts)
            shed = engine.rejected
        elif trace_doc is not None:
            from .workload_driver import replay_trace
            engine = make_engine(metrics=metrics)
            workload = replay_trace(
                engine, *trace_doc, vocab=args.vocab,
                pace=args.trace_pace or "virtual",
                steps_per_s=(args.trace_steps_per_s
                             if args.trace_steps_per_s is not None
                             else 8.0),
                log_every=args.log_every, metrics=metrics)
            shed = workload["shed"]
        else:
            engine = make_engine(metrics=metrics)
            for pr in prompts:
                try:
                    engine.submit(pr, args.max_new)
                except AdmissionError:
                    shed += 1       # recorded as a `rejected` event
            engine.run(metrics=metrics, log_every=args.log_every)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        if metrics is not None:
            metrics.close()
        return 2
    wall = time.perf_counter() - t0
    if metrics is not None:
        metrics.close()

    new_tokens = engine.tokens_generated - prior_tokens
    sequences = []
    for u, toks in sorted(engine.finished.items()):
        # prompt_len from the engine's own per-uid record (snapshot-
        # persisted): immune to shed submissions skewing uid/index
        # alignment and to a resume invoked with different flags
        sequences.append({"uid": u, "tokens": toks,
                          "prompt_len": engine.prompt_lens.get(u)})
    payload = {
        "sequences": sequences,
        "failed": {str(u): info
                   for u, info in sorted(engine.failed.items())},
        "tokens_generated": engine.tokens_generated,
        "wall_s": round(wall, 4),
        "tokens_per_sec": round(new_tokens / wall, 2),
        "engine_steps": engine.global_step,
        "mean_occupancy": round(engine.mean_occupancy(), 4),
        "compiled_programs": engine.compile_count,
        "dispatches": engine.dispatch_count,
        "kv_dtype": args.kv_dtype,
        "tp": tp,
        "speculate": args.speculate,
        "drafted_tokens": engine.drafted_tokens,
        "accepted_tokens": engine.accepted_tokens,
        "accept_rate": (round(engine.accepted_tokens
                              / engine.drafted_tokens, 4)
                        if engine.drafted_tokens else None),
        "prefix_cache": args.prefix_cache,
        "prefix_hit_blocks": engine.prefix_hit_blocks,
        "prefill_tokens_saved": engine.prefill_tokens_saved,
        "prefill_dispatches": engine.prefill_dispatches,
        "cow_copies": engine.cow_copies,
        "spill_blocks": args.spill_blocks,
        "spilled_blocks": engine.spilled_blocks,
        "restores": engine.restores,
        "restore_tokens_saved": engine.restore_tokens_saved,
        "partial_hits": engine.partial_hits,
        "quarantined": engine.quarantined,
        "retried": engine.retried,
        "preempted": engine.preempted,
        "rejected": engine.rejected,
        "expired": engine.expired,
        "shed": shed,
    }
    if workload is not None:
        payload["workload"] = workload
    if resumed_from is not None:
        payload["resumed_from_step"] = resumed_from
    if engine_id is not None:
        payload["engine_id"] = engine_id
    if args.policy:
        payload["policy"] = args.policy
    print(json.dumps(payload))
    return 0


if __name__ == "__main__":
    sys.exit(generate_main())
