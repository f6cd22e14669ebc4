"""Multi-host process bootstrap — the ``init_process`` analogue.

The reference's per-process rendezvous (``train_ffns.py:121-127``) sets
MASTER_ADDR/PORT and calls ``dist.init_process_group("nccl", rank,
world_size)``. In SPMD JAX the per-device process model collapses to one
process per *host*; this module wraps ``jax.distributed.initialize`` with
the same ergonomics, and exposes the runtime facts the reference's workers
read from their args.
"""

from __future__ import annotations

import os
import sys

import jax

DEFAULT_COORDINATOR = "127.0.0.1:29500"  # the reference's addr:port (:123-124)

# <checkout>/.jax_cache, resolved from this file: the directory is part
# of the cache key's environment, so it must not move between runs
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; every entry point
    calls this before its first compile. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set in code; otherwise the cache lives in
    ``<checkout>/.jax_cache`` (git-ignored). Returns the directory."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        # JAX skips programs that compiled in under a second; a decode
        # engine is dozens of those
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def describe_devices() -> dict:
    """Print (stderr, one line) and return what this process runs on —
    so a run that landed on the CPU by accident, with every Pallas
    kernel in the interpreter, says so at its start."""
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}
    print(f"devices: platform={info['platform']} kind={info['kind']!r} "
          f"count={info['count']}", file=sys.stderr)
    return info


def initialize(coordinator_address: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None) -> None:
    """Join the multi-host runtime. No-op on a single-process run.

    Arguments fall back to the standard env vars
    (``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID``)
    the way the reference fell back to MASTER_ADDR/PORT.
    """
    coordinator_address = (coordinator_address
                           or os.environ.get("JAX_COORDINATOR_ADDRESS"))
    if num_processes is None:
        num_processes = int(os.environ.get("JAX_NUM_PROCESSES", "1"))
    if process_id is None:
        process_id = int(os.environ.get("JAX_PROCESS_ID", "0"))
    if num_processes <= 1:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address or DEFAULT_COORDINATOR,
        num_processes=num_processes, process_id=process_id)


def runtime_info() -> dict:
    """The facts every reference worker carried in its args: rank/world."""
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": jax.device_count(),
    }
