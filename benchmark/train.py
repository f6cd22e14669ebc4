"""Training cells: the trainer's own step, call after call.

This file knows no model, method, optimizer or leaf by name. The
configuration names two files beside it: its ``driver`` (how the
program's trainer is reached: ``make_weights``, ``build``,
``flops_per_token``; see ``configs/ffn_single_driver.py``) and its plain
``reference`` (``grads``, ``update``, ``grads_low`` and, where the
trainer has a scalar loss, ``loss``). What is compared and against
which limit is the workload file's ``correct`` group. Here is only what
every training cell shares: the call loop the CLI's ``--metrics_dir
--log_every K`` loop makes (``state = step(state, seeds[k : k + K])``
fenced by ``block_until_ready``), the window, and the comparison of
norms.

Set-up makes the weights from the seed, runs the first
``correct.steps`` calls (which compile, and which the plain reference
then follows), and hands the SAME state to the window. Weights and seed
schedule are the benchmark's; the rows a step trains on are drawn from
its integer seed inside the program's step, and the reference restates
that draw.
"""

from __future__ import annotations

import time

import numpy as np

from . import harness

GRAD_ROWS = 128     # rows of each leaf's first gradient kept as arrays


def _rows(a):
    """The first ``GRAD_ROWS`` rows of a leaf: along the axis before the
    last (``[layers, rows, cols]`` or ``[rows, cols]``)."""
    if a.ndim < 2:
        return a
    return a[(slice(None),) * (a.ndim - 2) + (slice(0, GRAD_ROWS),)]


def worst_leaf_gap(prog: dict, ref: dict) -> float:
    """Largest, over leaves, of |norm(program) - norm(reference)| held
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    med = float(np.median(list(ref.values())))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in ref)


def _norms(names, leaf) -> dict:
    """L2 norm of ``leaf(name)`` for every name, one leaf at a time so
    that only one leaf-sized temporary is alive."""
    import jax.numpy as jnp
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(
        leaf(k).astype(jnp.float32))))) for k in sorted(names)}


def run(cell: dict, seed: int, seconds: float, trace_on: bool,
        device: dict, compiles, t_start: float, alter=None,
        control: tuple = ()) -> tuple:
    import jax
    import jax.numpy as jnp
    work, config = cell["work"], cell["config"]
    drv = harness.driver_module(config)
    w = drv.make_weights(config, seed)
    jax.block_until_ready(w)
    job = drv.build(config, w)
    step, leaves = job["step"], job["leaves"]
    grad_of_step, tokens = job["grad_of_step"], job["tokens_per_step"]
    k_call = int(work["job"]["steps_per_call"])
    n_check = int(work["correct"]["steps"])
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    # distinct step seeds, so every step's rows differ
    seeds = rng.permutation(100_000)[:200_000 // max(k_call, 1)].astype(
        np.int32)
    losses = []

    def call(p, i):
        with harness.span("train.call"):
            out = step(p, seeds[i * k_call:(i + 1) * k_call])
            jax.block_until_ready(out)
        if job.get("returns_loss"):         # step -> (state, loss)
            out, loss = out
            if i < n_check:
                losses.append(float(jnp.mean(loss)))
        return alter(p, out) if alter is not None else out

    # -- set-up: the first calls compile, and are what the reference
    # follows. The launchers clone what they are given, so the initial
    # weights stay alive beside the state; they are dropped after the
    # first call and made again from the seed (bit-identical) when a
    # difference from them is needed, so that the window's peak memory
    # is the program's own: its state, its clone and its step.
    state = call(job.pop("state"), 0)
    after = leaves(state)
    # the first gradient as the optimizer got it: its norm leaf by
    # leaf, and its first GRAD_ROWS rows of every leaf as arrays
    first_norm = _norms(w, lambda k: grad_of_step(w[k], after[k]))
    first_rows = {k: grad_of_step(_rows(w[k]), _rows(after[k])) for k in w}
    del w, after
    for i in range(1, n_check):
        state = call(state, i)
    w = drv.make_weights(config, seed)
    now = leaves(state)
    moved = _norms(w, lambda k: now[k] - w[k])
    del w, now
    setup_s = time.perf_counter() - t_start

    # -- the window: the same callable, the same state
    tracer = harness.Tracer(trace_on, work["trace"]["after_s"],
                            work["trace"]["for_s"])
    programs0 = compiles.programs()
    calls, i = [], n_check
    w0 = time.perf_counter()
    while True:
        tracer.poll(time.perf_counter() - w0)
        a = time.perf_counter()
        state = call(state, i)
        b = time.perf_counter()
        calls.append((a, b, tracer.state == "tracing"))
        i += 1
        if b - w0 >= seconds:
            break
    w1 = time.perf_counter()
    tracer.stop()
    programs_in_window = compiles.programs() - programs0
    mem_peak = harness.peak_bytes()
    window_s = w1 - w0
    n_calls = len(calls)
    steps = n_calls * k_call
    call_ms = [(b - a) * 1e3 for a, b, _ in calls]
    tok_s = steps * tokens / window_s
    values = {"memory_peak_bytes": mem_peak, "compile_s": compiles.seconds,
              "programs_in_window": programs_in_window,
              "train_step_ms": float(np.median(call_ms)) / k_call,
              "train_tokens_per_s": tok_s,
              "model_flops_per_token": drv.flops_per_token(config),
              "chips": cell["chips"],
              "traced_calls": sum(1 for c in calls if c[2])}
    harness.say(phase="window", seconds=window_s, calls=n_calls,
                steps=steps, tokens_per_step=tokens, strategy=job["name"],
                call_ms_max=max(call_ms),
                **{k: v for k, v in values.items()
                   if k != "memory_peak_bytes"})
    if programs_in_window:
        raise RuntimeError(f"{programs_in_window} program(s) were "
                           "compiled or fetched inside the measured window")
    red = tracer.reduce()
    final = leaves(state)
    del state

    # -- correct: the plain reference follows the first calls
    t_chk = time.perf_counter()
    finite = all(bool(jnp.all(jnp.isfinite(x))) for x in final.values())
    del final
    ok, numbers = check(cell, drv.make_weights(config, seed), seeds,
                        k_call, n_check, job["lr"], first_norm, first_rows,
                        moved, losses, finite, control)
    for num in numbers:
        harness.say(phase="correct", **num)
    harness.say(phase="correct", ok=ok,
                seconds=time.perf_counter() - t_chk)
    ctx = {"values": values, "trace": red, "cell": cell, "device": device,
           "spans": []}
    outcome = {"correct": ok, "attempted": steps, "failed": 0,
               "e2e": {"setup_s": setup_s, "train_tokens_per_s": tok_s}}
    return outcome, ctx


def check(cell, w, seeds, k_call, n_check, lr, first_norm, first_rows,
          moved, losses, finite, control=()):
    """Follow the first ``n_check`` calls with the plain reference and
    compare, leaf by leaf, what the workload's ``correct`` group names:

    - ``grad_rel_diff``, ``{leaf: {"limit": x, "layer": i}}``: the norm
      of the DIFFERENCE between the program's first gradient and the
      reference's over the first ``GRAD_ROWS`` rows of the leaf (of its
      layer ``i`` where one is given), against the reference's norm
      there. The number a lower precision moves, where the leaf is
      chosen so that it does (the workload file says why);
    - ``grad_norm_gap`` and ``param_change_gap``: the gap between the
      program's norm and the reference's (whole leaves, the worst leaf).
      Precision hardly moves a norm; these are held against a part of
      the batch left out and a step that returns its state unchanged;
    - ``loss_gap``, where the trainer returns a scalar loss and the
      reference has ``loss``: the widest relative gap over the steps.

    The first-gradient numbers need the state after ONE step, so they
    are read where ``steps_per_call`` is 1. ``control`` names lower
    precisions of the reference to put in the program's place."""
    import jax.numpy as jnp
    config, spec = cell["config"], cell["work"]["correct"]
    ref = harness.reference_module(config)
    norm = lambda a: float(jnp.sqrt(jnp.sum(jnp.square(a))))  # noqa: E731

    def rel_diffs(rows: dict, want: dict, prefix: str) -> list[dict]:
        out = []
        for k, sel in sorted(spec["grad_rel_diff"].items()):
            a, b = rows[k], want[k]
            if "layer" in sel:
                a, b = a[sel["layer"]], b[sel["layer"]]
            out.append({"name": f"{prefix}first_grad_rel_diff.{k}",
                        "value": norm(a - b) / norm(b),
                        "limit": sel["limit"]})
        return out

    cur = dict(w)
    numbers, ref_losses = [], []
    for s in range(n_check * k_call):
        if losses and s % k_call == 0:
            ref_losses.append(float(ref.loss(cur, seeds[s], config)))
        g = ref.grads(cur, seeds[s], config)
        if s == 0:
            g_first = _norms(g, g.get)
            ref_rows = {k: _rows(g[k]) for k in g}
            if k_call == 1:
                numbers += rel_diffs(first_rows, ref_rows, "")
        cur = ref.update(cur, g, lr)
        del g
    ref_moved = _norms(w, lambda k: cur[k] - w[k])
    del cur
    if k_call == 1:
        numbers.append({"name": "first_grad_norm_gap",
                        "value": worst_leaf_gap(first_norm, g_first),
                        "limit": spec["grad_norm_gap"]})
    numbers.append({"name": "param_change_norm_gap",
                    "value": worst_leaf_gap(moved, ref_moved),
                    "limit": spec["param_change_gap"]})
    if losses:
        numbers.append({"name": "loss_gap", "value": max(
            abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)),
            "limit": spec["loss_gap"]})
    numbers.append({"name": "final_state_nonfinite", "value": 0.0 if finite
                    else 1.0, "limit": 0.0})
    ok = all(np.isfinite(n["value"]) and n["value"] <= n["limit"]
             for n in numbers)
    for mode in control:
        low = ref.grads_low(w, seeds[0], config, mode)
        numbers += rel_diffs({k: _rows(low[k]) for k in low}, ref_rows,
                             f"control_{mode}_")
        numbers.append({"name": f"control_{mode}_first_grad_norm_gap",
                        "value": worst_leaf_gap(_norms(low, low.get), g_first),
                        "limit": spec["grad_norm_gap"]})
        del low
    return ok, numbers
