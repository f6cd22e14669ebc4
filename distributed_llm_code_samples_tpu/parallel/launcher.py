"""Shared launcher tail for every multi-device strategy.

The reference's launchers all share the same skeleton — shard params and
seeds, spawn workers, join, re-assemble (``train_ffns.py:174-193, :262-287,
:315-338``). The SPMD analogue is one function: ``shard_map`` the per-shard
step loop over the mesh, jit with donation, run. Each strategy is then just
its specs + hooks.

Self-healing hooks (round 8):

- ``guard`` (a ``runtime.guardrails.GuardrailConfig``) compiles the
  in-graph anomaly guardrail into ANY strategy's scan: the step's carry
  is extended with a ``GuardState``, the finite check + ``jnp.where``
  skip-select wraps every step, and the final counters come back with
  the result (``return (out, GuardState)``). Because the wrap happens
  here — at the one place every strategy's scan is built — a new
  strategy gets skip-step protection for free.
- ``accum`` re-strides the seed schedule for topology-elastic resume
  (``data.shard_seeds_elastic``): each scan step consumes a VECTOR of
  ``accum`` seeds per rank, preserving the save-time global batch when
  a checkpoint resumes onto fewer devices. The step function must
  accept the vector (``seed_accum`` surface in ddp/fsdp).
"""

from __future__ import annotations

from typing import Callable

import jax
from jax import lax

from ..runtime.tracing import PhaseTimer

_PHASES = PhaseTimer("launch")  # never begun: annotations, no stamps

# Test/introspection hook: when a list is installed here, every launch
# also lowers+compiles its program AOT and appends the optimized HLO
# text (the named-scope presence contract is asserted against the REAL
# launched program, not a reconstruction — tests/test_telemetry.py).
# None (the default) costs nothing.
CAPTURE_COMPILED: list | None = None


def _maybe_capture(jitted, *args) -> None:
    if CAPTURE_COMPILED is not None:
        CAPTURE_COMPILED.append(
            jitted.lower(*args).compile().as_text())


def launch(step: Callable, params, seeds_arr, mesh, param_specs, seed_spec,
           select_local: Callable = lambda s: s,
           make_carry: Callable | None = None,
           check_vma: bool = True,
           state=None, state_specs=None, return_state: bool = False,
           guard=None, guard_state=None, guard_scale: bool = False):
    """Run ``lax.scan(step)`` over the seed schedule under ``shard_map``.

    ``select_local`` maps the shard's view of the seed array to its 1-D
    schedule (e.g. ``s[:, 0]`` for a strided column split). ``params`` must
    already be owned by the launcher (cloned/resharded) — they are donated.

    Stateful strategies (optimizer state, ZeRO shards) pass ``make_carry``:
    it builds the scan carry from the per-shard params *inside* the
    ``shard_map`` body (so per-shard state can be sliced from the shard's
    view), ``step`` then maps ``(carry, seed) -> carry``, and the carry's
    first element is returned as the final params.

    Alternatively, ``state``/``state_specs`` pass explicit optimizer
    state *through* the program boundary: the carry is ``(params,
    state)`` and with ``return_state=True`` the final state comes back
    out — what checkpoint/resume needs to continue an Adam run exactly.

    ``check_vma=False`` disables shard_map's varying-manual-axes typing for
    strategies whose replicated outputs the type system cannot prove —
    e.g. ZeRO-1's params re-assembled by ``all_gather`` from
    ``axis_index``-sliced shards (identical by construction on every
    rank, but typed varying; JAX offers no varying->invariant cast).

    ``guard`` arms the in-graph anomaly guardrail (module docstring):
    the return value becomes ``(normal_result, GuardState)``, with the
    guard state replicated (its finite flag is ``psum``-reduced over
    every mesh axis, so all shards skip — or keep — the same steps).
    ``guard_scale=True`` passes the live loss scale into the step as a
    third argument (the mixed-precision strategies' scaling hook).
    """
    from jax.sharding import PartitionSpec as P

    gstate = None
    if guard is not None:
        from ..runtime.guardrails import (guarded_scan_step, init_state,
                                          mesh_world)
        if guard.scaling and not guard_scale:
            # a scaling config on a strategy without the loss-scale hook
            # would never scale anything while GuardState.loss_scale
            # still ran its grow/shrink schedule — refuse the silent lie
            raise ValueError(
                "guard.loss_scale > 0 but this strategy has no "
                "loss-scale hook: dynamic scaling is a mixed-precision "
                "DDP/FSDP surface — pass loss_scale=0 here")
        axes, world = mesh_world(mesh)
        step = guarded_scan_step(step, guard, axis_names=axes, world=world,
                                 takes_scale=guard_scale)
        gstate = init_state(guard) if guard_state is None else guard_state

    # each branch names the per-shard program, its specs and operands;
    # the tail below builds and runs it
    if state is not None and guard is None:
        def run(params, state, seeds):
            local = select_local(seeds)
            out = lax.scan(lambda c, s: (step(c, s), None),
                           (params, state), local)[0]
            return out if return_state else out[0]

        in_specs = (param_specs, state_specs, seed_spec)
        out_specs = ((param_specs, state_specs) if return_state
                     else param_specs)
        args, donate = (params, state, seeds_arr), (0, 1)
    elif state is not None:
        def run(params, state, gstate, seeds):
            local = select_local(seeds)
            carry, g = lax.scan(lambda c, s: (step(c, s), None),
                                ((params, state), gstate), local)[0]
            return (carry if return_state else carry[0]), g

        in_specs = (param_specs, state_specs, P(), seed_spec)
        out_specs = (((param_specs, state_specs) if return_state
                      else param_specs), P())
        args, donate = (params, state, gstate, seeds_arr), (0, 1)
    elif guard is None:
        def run(params, seeds):
            local = select_local(seeds)
            carry = params if make_carry is None else make_carry(params)
            out = lax.scan(lambda c, s: (step(c, s), None), carry, local)[0]
            return out if make_carry is None else out[0]

        in_specs, out_specs = (param_specs, seed_spec), param_specs
        args, donate = (params, seeds_arr), 0
    else:
        def run(params, gstate, seeds):
            local = select_local(seeds)
            carry = params if make_carry is None else make_carry(params)
            out, g = lax.scan(lambda c, s: (step(c, s), None),
                              (carry, gstate), local)[0]
            return (out if make_carry is None else out[0]), g

        in_specs = (param_specs, P(), seed_spec)
        out_specs = (param_specs, P())
        args, donate = (params, gstate, seeds_arr), 0

    # host events launch:build / launch:run in a profiler trace: a new
    # jax.jit every call, then trace + lower + cache look-up + enqueue
    with _PHASES.phase("build"):
        jitted = jax.jit(
            jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=check_vma),
            donate_argnums=donate)
    _maybe_capture(jitted, *args)
    with _PHASES.phase("run"):
        return jitted(*args)


def launch_strided(step: Callable, params, seeds, mesh, axis: str,
                   param_specs, accum: int = 1, **kwargs):
    """``launch`` with the strided seed split every data-sharding strategy
    uses (``train_ffns.py:182`` semantics, ``data.shard_seeds_strided``):
    rank ``r``'s step ``t`` consumes global seed ``seeds[t*n + r]``. One
    helper so the convention — which silently breaks the DDP==FSDP
    differential tests if it drifts — lives in one place. The shard count
    is ``mesh.shape[axis]`` by construction: a caller-supplied count could
    silently mis-assign seeds if it drifted from the mesh.

    ``accum > 1`` switches to the elastic re-stride
    (``data.shard_seeds_elastic``): each scan step hands the strategy a
    ``[accum]`` seed vector per rank, preserving an ``accum * n``-seed
    global batch — the topology-elastic resume path (the step must have
    the ``seed_accum`` surface)."""
    from jax.sharding import PartitionSpec as P

    from ..data import shard_seeds_elastic, shard_seeds_strided
    n = dict(mesh.shape)[axis]
    if accum > 1:
        seed_cols = shard_seeds_elastic(seeds, n, accum)
        return launch(step, params, seed_cols, mesh,
                      param_specs=param_specs,
                      seed_spec=P(None, None, axis),
                      select_local=lambda s: s[:, :, 0], **kwargs)
    seed_cols = shard_seeds_strided(seeds, n)
    return launch(step, params, seed_cols, mesh, param_specs=param_specs,
                  seed_spec=P(None, axis), select_local=lambda s: s[:, 0],
                  **kwargs)
