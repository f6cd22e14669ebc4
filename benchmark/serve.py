"""Serving cells: the paged-KV engine under offline or open-loop load.

The system under test is the engine that the configuration's ``driver``
file builds (``configs/gpt2_engine_driver.py``: ``decode.DecodeEngine``
as ``generate_cli`` builds it), driven through ``submit()`` /
``step()``. Everything else here is the benchmark's: the weights, the
requests, the clocks, the check.

Kinds:

- ``serve-offline``: a backlog all due at t=0; pre-rolled until
  ``preroll.completed`` requests have finished, then the window. Judged
  by output tokens per second.
- ``serve-open``: an open loop on the host clock; each request is
  submitted when it is due (lateness recorded) and timed from when it
  was DUE. ``preroll.seconds`` of the same process precede the window;
  requests due inside the window are the measured ones, and arrivals
  go on through a drain of at most ``drain_s`` while they finish.
"""

from __future__ import annotations

import time
from typing import NamedTuple

import numpy as np

from . import harness, traffic


class Step(NamedTuple):
    """One ``engine.step()`` as the driver saw it."""
    start: float          # seconds on the driver's clock
    end: float
    active: int           # slots taken after the step
    tokens: int           # engine.tokens_generated after the step
    live: int             # cached positions the next decode will read
    n_prefill: int        # prefill-chunk dispatches in the step
    n_decode: int         # decode dispatches in the step
    traced: bool          # ran under the profiler


class _Collector:
    """An in-memory stand-in for the program's TelemetryWriter, used in
    traced runs only: keeps ``span`` records, swallows the rest."""

    def __init__(self):
        self.spans: list[dict] = []

    def span(self, rec: dict) -> None:
        self.spans.append(rec)

    def __getattr__(self, _name):
        return lambda *a, **k: None


class Driver:
    """One engine, one request list, one clock. ``pump`` is the loop
    both kinds share: submit what is due, step, stamp tokens."""

    def __init__(self, engine, requests: list[dict]):
        self.engine = engine
        self.requests = requests
        self.next = 0                      # next request to submit
        self.uid_of: dict[int, int] = {}   # uid -> request index
        self.t0 = None
        # per request
        n = len(requests)
        self.submit_t = np.full(n, np.nan)
        self.first_t = np.full(n, np.nan)
        self.done_t = np.full(n, np.nan)
        self.tok_t: list[list[float]] = [[] for _ in range(n)]
        self.seen: dict[int, int] = {}     # uid -> tokens stamped
        self.steps: list[Step] = []
        self.util: list[float] = []

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def start(self) -> None:
        self.t0 = time.perf_counter()

    def submit_due(self) -> None:
        reqs, eng = self.requests, self.engine
        t = self.now()
        while self.next < len(reqs) and reqs[self.next]["due_s"] <= t:
            r = reqs[self.next]
            with harness.span("submit"):
                uid = eng.submit(r["prompt"], r["max_new"])
            self.uid_of[uid] = self.next
            self.submit_t[self.next] = self.now()
            self.next += 1

    def _stamp(self, t: float) -> int:
        """Stamp the tokens the last step emitted; returns the cached
        positions the NEXT decode dispatch will read (live KV)."""
        eng = self.engine
        live = 0
        for slot, seq in enumerate(eng.slots):
            if seq is None:
                continue
            if seq.prompt_done:
                live += int(eng.lengths[slot])
            n, had = len(seq.out), self.seen.get(seq.uid, 0)
            if n > had:
                i = self.uid_of[seq.uid]
                if had == 0:
                    self.first_t[i] = t
                self.tok_t[i].extend([t] * (n - had))
                self.seen[seq.uid] = n
        if len(eng.finished) > self._n_finished:
            for uid in list(eng.finished)[self._n_finished:]:
                i = self.uid_of.get(uid)
                if i is None:
                    continue               # a warm-up request
                n, had = len(eng.finished[uid]) - len(
                    self.requests[i]["prompt"]), self.seen.pop(uid, 0)
                if had == 0:
                    self.first_t[i] = t
                self.tok_t[i].extend([t] * (n - had))
                self.done_t[i] = t
            self._n_finished = len(eng.finished)
        return live

    def pump(self, until, tracer=None, window_t0: float | None = None):
        """Run until ``until(self)`` is true. Returns when it is, or
        when there is neither work nor a request left to wait for."""
        eng = self.engine
        self._n_finished = len(eng.finished)
        while not until(self):
            self.submit_due()
            if tracer is not None:
                tracer.poll(self.now() - window_t0)
            if eng.active or eng.waiting:
                traced = tracer is not None and tracer.state == "tracing"
                n_disp, n_pre = eng.dispatch_count, eng.prefill_dispatches
                a = self.now()
                with harness.span("engine.step"):
                    eng.step()
                b = self.now()
                with harness.span("stamp"):
                    live = self._stamp(b)
                    n_pre = eng.prefill_dispatches - n_pre
                    self.steps.append(Step(
                        a, b, eng.active, eng.tokens_generated, live, n_pre,
                        eng.dispatch_count - n_disp - n_pre, traced))
                    if len(self.steps) % 16 == 0:
                        self.util.append(eng.kv_pool_utilization())
            elif self.next < len(self.requests):
                wait = self.requests[self.next]["due_s"] - self.now()
                if wait > 0:
                    with harness.span("wait_arrival"):
                        time.sleep(wait)
            else:
                return


def _spread(ms: list) -> dict | None:
    """Quantiles of the window's step times, and the seconds its slow
    steps (over 1.5 x the median) took beyond the median: what tells a
    stall from a run that was slower throughout."""
    if not ms:
        return None
    a = np.asarray(ms)
    med = float(np.median(a))
    slow = a[a > 1.5 * med]
    return {"p50": med, "p90": float(np.quantile(a, 0.9)),
            "p99": float(np.quantile(a, 0.99)), "max": float(a.max()),
            "slow_steps": int(slow.size),
            "slow_excess_s": float((slow - med).sum() / 1e3)}


def warm(engine) -> None:
    """Drive every program the traffic can reach once, so that it
    compiles (or is fetched) here and never in the window: a request per
    slot, each of 2*chunk-1 prompt tokens (chunks 16+8+4+2+1), prefilled
    one after another. Request j leaves just after the last one is
    ready, so the ready count climbs through every slot bucket from 1
    to the full batch and the whole thing is over in about
    ``slots * 5`` steps."""
    slots = engine.cfg.max_slots
    plen = 2 * engine.cfg.prefill_chunk - 1
    per_req = bin(plen).count("1")
    rng = np.random.default_rng(0)
    for j in range(slots):
        engine.submit(rng.integers(0, engine.params.vocab, plen).tolist(),
                      per_req * (slots - j - 1) + 3)
    engine.run()


def check(cell: dict, ref, w: dict, drv: Driver, finished: list[int],
          seed: int, alter=None, control: tuple = ()) -> dict:
    """Teacher-force the plain reference on a seeded sample of finished
    requests (the longest among them) and read, over every served
    token, how far its reference logit lies below that position's
    best. ``alter`` is the tests' hook for a token changed where it is
    produced. ``control`` names lower-precision modes of the reference
    to put in the program's place (``tests/control_on_chip.py``): at
    each position of the same prompts and tokens, the gap of the token
    that mode puts first."""
    import jax.numpy as jnp
    spec = cell["work"]["correct"]
    eng, reqs = drv.engine, drv.requests
    config = cell["config"]
    if not finished:
        return {"ok": False, "numbers": [], "why": "nothing finished"}
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 0xC0DE])
    longest = max(finished, key=lambda u: len(eng.finished[u]))
    rest = [u for u in finished if u != longest]
    take = min(spec["sample"] - 1, len(rest))
    sample = [longest] + [int(u) for u in rng.choice(rest, take,
                                                     replace=False)]
    pad = spec.get("pad_to", 256)
    gaps, n_tok = [], 0
    ctrl = {m: [] for m in control}
    for uid in sample:
        full = list(eng.finished[uid])
        plen = len(reqs[drv.uid_of[uid]]["prompt"])
        if alter is not None:
            full = alter(full, plen)
        t = len(full)
        t_pad = -(-t // pad) * pad
        toks = np.zeros(t_pad, np.int32)
        toks[:t] = full
        lg = ref.logits(w, toks, config)            # [t_pad, V], causal
        rows = lg[plen - 1:t - 1]                    # predict out tokens
        served = jnp.asarray(full[plen:], jnp.int32)
        gap = jnp.max(rows, -1) - jnp.take_along_axis(
            rows, served[:, None], -1)[:, 0]
        gaps.append(np.asarray(gap))
        n_tok += t - plen
        for mode in control:
            low = ref.logits(w, toks, config, mode)[plen - 1:t - 1]
            first = jnp.argmax(low, -1)
            ctrl[mode].append(np.asarray(jnp.max(rows, -1) - jnp.take_along_axis(
                rows, first[:, None], -1)[:, 0]))
    gaps = np.concatenate(gaps)
    worst = float(gaps.max())
    mean = float(gaps.mean())
    numbers = [
        {"name": "served_logit_gap_max", "value": worst,
         "limit": spec["max_logit_gap"]},
        {"name": "served_logit_gap_mean", "value": mean,
         "limit": spec["mean_logit_gap"]}]
    ok = all(np.isfinite(x["value"]) and x["value"] <= x["limit"]
             for x in numbers)
    for mode, vals in ctrl.items():
        vals = np.concatenate(vals)
        numbers.append({"name": f"control_{mode}_gap_max",
                        "value": float(vals.max()),
                        "limit": spec["max_logit_gap"]})
        numbers.append({"name": f"control_{mode}_gap_mean",
                        "value": float(vals.mean()),
                        "limit": spec["mean_logit_gap"]})
    return {"ok": ok, "numbers": numbers, "requests": len(sample),
            "tokens": n_tok}


def run(cell: dict, seed: int, seconds: float, trace_on: bool,
        device: dict, compiles, t_start: float, alter=None,
        control: tuple = ()) -> tuple:
    """One run of a serving cell. Returns ``(outcome, ctx)``."""
    import jax
    work, config = cell["work"], cell["config"]
    kind = work["kind"]
    tr = work["traffic"]
    sut = harness.driver_module(config)
    w = sut.make_weights(config, seed)
    jax.block_until_ready(w)
    harness.note("weights made")
    collector = _Collector() if trace_on else None
    engine = sut.build_engine(config, w, metrics=collector)
    harness.note("engine built")
    with harness.span("warm"):
        warm(engine)
    harness.note(f"warm: {engine.steps} steps, compile {compiles.seconds:.1f}s, "
                 f"{compiles.misses} cache misses")
    if collector is not None:
        collector.spans.clear()

    pre = work["preroll"]
    if kind == "serve-offline":
        n_req = int(tr["arrival"]["n"])
    else:
        horizon = pre["seconds"] + seconds + work["drain_s"]
        n_req = int(tr["arrival"]["rate"] * horizon * 1.25) + 64
    requests = traffic.generate(tr, n_req, seed,
                                config["vocab_size"])
    drv = Driver(engine, requests)
    harness.say(phase="traffic", requests=n_req, **traffic.describe(tr))

    # -- pre-roll (set-up: reaches the steady state the window measures)
    drv.start()
    with harness.span("preroll"):
        if kind == "serve-offline":
            need = pre["completed"]
            drv.pump(lambda d: int(np.isfinite(d.done_t).sum()) >= need)
        else:
            drv.pump(lambda d: d.now() >= pre["seconds"])
    jax.block_until_ready(engine.pool)
    setup_s = time.perf_counter() - t_start
    harness.note(f"pre-roll: {len(drv.steps)} steps in {drv.now():.1f}s")

    # -- the window
    tracer = harness.Tracer(trace_on, work["trace"]["after_s"],
                            work["trace"]["for_s"])
    programs0 = compiles.programs()
    w0 = drv.now()
    tok0, step0 = engine.tokens_generated, len(drv.steps)
    drv.pump(lambda d: d.now() - w0 >= seconds, tracer=tracer,
             window_t0=w0)
    jax.block_until_ready(engine.pool)
    w1 = drv.now()
    tracer.stop()
    tok1, step1 = engine.tokens_generated, len(drv.steps)
    programs_in_window = compiles.programs() - programs0
    mem_peak = harness.peak_bytes()
    backlog_end = len(engine.waiting)

    # -- drain (open loop): window requests finish under continuing load
    in_window = [i for i, r in enumerate(requests)
                 if w0 <= r["due_s"] < w1]
    if kind == "serve-open":
        deadline = w1 + work["drain_s"]
        drv.pump(lambda d: d.now() >= deadline or all(
            np.isfinite(d.done_t[i]) for i in in_window))

    # -- numbers
    window_s = w1 - w0
    steps = drv.steps[step0:step1]
    step_ms = [(st.end - st.start) * 1e3 for st in steps]
    values = {
        "memory_peak_bytes": mem_peak,
        "compile_s": compiles.seconds,
        "programs_in_window": programs_in_window,
        # the mean: steps with and without a prefill chunk make the
        # median jump between two modes
        "engine_step_ms": (float(np.mean(step_ms)) if step_ms else None),
        "slot_occupancy_pct": (100.0 * float(np.mean(
            [st.active for st in steps])) / engine.cfg.max_slots
            if steps else None),
        "kv_pool_util_pct": (100.0 * float(np.mean(drv.util))
                             if drv.util else None),
        "steps_per_s": len(steps) / window_s,
        "backlog_at_window_end": backlog_end,
    }
    # the window's output tokens by the driver's own stamps; the
    # engine's counter has to agree, or one of the two is miscounting
    out_tokens = int(sum(np.count_nonzero((np.asarray(ts) > w0)
                                          & (np.asarray(ts) <= w1))
                         for ts in drv.tok_t[:drv.next]))
    if out_tokens != tok1 - tok0:
        raise RuntimeError(
            f"the driver stamped {out_tokens} tokens in the window, the "
            f"engine's tokens_generated moved by {tok1 - tok0}")
    e2e = {"setup_s": setup_s}
    if kind == "serve-offline":
        attempted = int(np.sum([np.isfinite(drv.done_t[i]) and
                                w0 < drv.done_t[i] <= w1
                                for i in range(drv.next)]))
        failed = len(engine.failed)
        e2e["out_tokens_per_s"] = out_tokens / window_s
        measured = [i for i in range(drv.next)
                    if np.isfinite(drv.done_t[i])]
    else:
        attempted = len(in_window)
        done = [i for i in in_window if np.isfinite(drv.done_t[i])]
        failed = attempted - len(done)
        ttft = [(drv.first_t[i] - requests[i]["due_s"]) * 1e3
                for i in in_window if np.isfinite(drv.first_t[i])]
        itl = np.concatenate([np.diff(drv.tok_t[i]) * 1e3
                              for i in in_window if len(drv.tok_t[i]) > 1]
                             or [np.zeros(0)])
        late = [(drv.submit_t[i] - requests[i]["due_s"]) * 1e3
                for i in in_window if np.isfinite(drv.submit_t[i])]
        def q(v, p):
            return float(np.quantile(v, p)) if len(v) else float("nan")

        e2e["ttft_p90_ms"] = q(ttft, 0.9)
        e2e["itl_p99_ms"] = q(itl, 0.99)
        e2e["out_tokens_per_s"] = out_tokens / window_s
        harness.say(phase="latency", requests=attempted, gaps=int(len(itl)),
                    ttft_p50_ms=q(ttft, 0.5),
                    ttft_p90_ms=e2e["ttft_p90_ms"],
                    itl_p50_ms=q(itl, 0.5),
                    itl_p99_ms=e2e["itl_p99_ms"],
                    generator_late_p50_ms=q(late, 0.5),
                    generator_late_max_ms=max(late) if late else None,
                    offered_req_per_s=attempted / window_s)
        values["generator_late_max_ms"] = max(late) if late else None
        measured = done
    harness.say(phase="window", seconds=window_s, steps=len(steps),
                out_tokens=out_tokens, attempted=attempted, failed=failed,
                step_ms=_spread(step_ms),
                **{k: v for k, v in values.items()
                   if k != "memory_peak_bytes"})
    if programs_in_window:
        raise RuntimeError(f"{programs_in_window} program(s) were "
                           "compiled or fetched inside the measured window")

    # -- traced part
    red = tracer.reduce()
    tsteps = [st for st in steps if st.traced]
    if red is not None and tsteps:
        # what a decode dispatch read is what stood after the step before
        read = [drv.steps[step0 + i - 1].live if step0 + i else 0
                for i, st in enumerate(steps) if st.traced and st.n_decode]
        values.update(
            traced_steps=tsteps,
            traced_prefill_dispatches=sum(st.n_prefill for st in tsteps),
            traced_decode_dispatches=sum(st.n_decode for st in tsteps),
            traced_mean_live_tokens=float(np.mean(read)) if read else None)
    # bytes from the arrays' own dtypes, as they are at run time
    pool = engine.pool
    values["weight_bytes"] = sut.decode_weight_bytes(w)
    values["kv_bytes_per_token"] = (
        (pool.k.size * pool.k.dtype.itemsize
         + pool.v.size * pool.v.dtype.itemsize)
        / (pool.n_blocks * pool.block_size))

    # -- correct, outside the window and outside set-up
    measured = set(measured)
    finished_uids = [u for u, i in drv.uid_of.items() if i in measured]
    t_chk = time.perf_counter()
    res = check(cell, harness.reference_module(config), w, drv,
                finished_uids, seed, alter, control)
    for num in res["numbers"]:
        harness.say(phase="correct", **num)
    harness.say(phase="correct", ok=res["ok"],
                requests=res.get("requests"), tokens=res.get("tokens"),
                seconds=time.perf_counter() - t_chk)
    ctx = {"values": values, "trace": red, "cell": cell, "device": device,
           "spans": collector.spans if collector else []}
    outcome = {"correct": res["ok"] and failed == 0,
               "attempted": attempted, "failed": failed, "e2e": e2e}
    return outcome, ctx
