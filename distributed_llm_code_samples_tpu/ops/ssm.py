"""The selective scan of a Mamba-1 mixer, in the two forms serving needs.

A recurrent layer carries, per sequence, a state that does not grow with
the sequence: the scan state ``s [N, D]`` and the last ``K-1`` inputs of
the depthwise causal convolution ``tail [K-1, D]`` (``D`` the mixer's
inner width, ``N`` its state size, ``K`` the convolution's kernel). The
inner width is the minor axis of both: ``D`` is a multiple of 128 lanes
at every published width, so the chip keeps a ``[N, D]`` state unpadded,
where the published ``[D, N]`` would pad ``N = 16`` up to 128.

The recurrence, per channel ``d`` and state index ``n``::

    s_t = exp(dt_t * A) * s_{t-1} + (dt_t * x_t) * B_t
    y_t = sum_n s_t * C_t + D * x_t

Two forms, one mathematics (a chunk of one token is a step of one row):

- ``*_chunk``: ``c`` consecutive tokens of ONE sequence, state in and
  state out — the prefill program. A ``lax.scan`` over time; ``y_t`` is
  reduced inside it, so no ``[c, N, D]`` history is ever held.
- ``*_step``: one token for each of ``b`` sequences — the decode program.

Everything here is float32: the state, the recurrence and the
convolution. Plain ``jax.numpy`` / ``lax``; forward only (serving). A
chunked-scan kernel and the hand-written backward the trainers would
need are ROADMAP M4.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def conv_chunk(x: jax.Array, tail: jax.Array, w: jax.Array,
               bias: jax.Array):
    """Depthwise causal convolution over a chunk: ``x [c, D]`` the new
    inputs, ``tail [K-1, D]`` the inputs just before them (zeros at the
    start of a sequence), ``w [K, D]`` with tap ``K-1`` on the current
    token, ``bias [D]``. Returns ``(y [c, D], new tail [K-1, D])``.
    The taps are summed oldest first, whatever the chunk size."""
    k = w.shape[0]
    c = x.shape[0]
    full = jnp.concatenate([tail, x], axis=0)           # [K-1+c, D]
    y = bias + sum(w[j] * full[j:j + c] for j in range(k))
    return y, full[c:]


def conv_step(x: jax.Array, tail: jax.Array, w: jax.Array,
              bias: jax.Array):
    """``conv_chunk`` for one token of each of ``b`` sequences:
    ``x [b, D]``, ``tail [b, K-1, D]``."""
    k = w.shape[0]
    full = jnp.concatenate([tail, x[:, None, :]], axis=1)  # [b, K, D]
    y = bias + sum(w[j] * full[:, j] for j in range(k))
    return y, full[:, 1:]


def scan_chunk(x, dt, a, b, c, d, s0):
    """The recurrence over a chunk of one sequence. ``x, dt [c, D]``,
    ``a [N, D]`` (negative), ``b, c [c, N]``, ``d [D]``, ``s0 [N, D]``.
    Returns ``(y [c, D], s [N, D])``."""

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t * a) * s + (dt_t * x_t) * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], axis=0) + d * x_t

    s, y = lax.scan(step, s0, (x, dt, b, c))
    return y, s


def scan_step(x, dt, a, b, c, d, s):
    """The recurrence for one token of each of ``n`` sequences.
    ``x, dt [n, D]``, ``b, c [n, N]``, ``s [n, N, D]``. Returns
    ``(y [n, D], s [n, N, D])``."""
    s = (jnp.exp(dt[:, None, :] * a) * s
         + (dt * x)[:, None, :] * b[:, :, None])
    return jnp.sum(s * c[:, :, None], axis=1) + d * x, s
