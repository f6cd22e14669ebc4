"""Tracing / profiling — parity with ``torch_profile_rank_0``.

The reference wraps a worker in ``torch.profiler.profile`` and exports a
chrome trace on rank 0 (``train_ffns.py:129-141``), with a noted pickling
hack to survive ``mp.spawn``. The TPU equivalent is ``jax.profiler.trace``
(Perfetto/TensorBoard format) — and SPMD removes the pickling problem
entirely: the decorator below is an ordinary closure because there is no
per-GPU process spawn to serialize through.
"""

from __future__ import annotations

import functools
import os
import time
from contextlib import contextmanager

import jax


@contextmanager
def trace(log_dir: str):
    """Profile a region to ``log_dir`` (Perfetto/TensorBoard format)."""
    with jax.profiler.trace(log_dir):
        yield


def profile_rank_0(log_dir: str = "trace_profiler"):
    """Decorator: profile the wrapped call, exporting only on process 0 —
    the ``torch_profile_rank_0`` surface (``train_ffns.py:129-141``)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if jax.process_index() != 0:
                return fn(*args, **kwargs)
            os.makedirs(log_dir, exist_ok=True)
            with jax.profiler.trace(log_dir):
                out = fn(*args, **kwargs)
                jax.block_until_ready(out)
            return out

        return wrapper

    return deco


def timed(fn, *args, **kwargs):
    """``(result, seconds)`` with completion fenced by
    ``jax.block_until_ready``; per-method wall-clock is the reference's
    timing surface (``train_ffns.py:378-382``)."""
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0
