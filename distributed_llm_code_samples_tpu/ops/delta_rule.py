"""The gated delta rule of a linear-attention mixer, in the forms
serving needs (arXiv:2412.06464, as ``model_type: qwen3_next`` runs it;
``models/qwen3_next_lm.py``). This file is the recurrence's ONE home;
its depthwise causal convolution is ``ops/ssm.py``'s, as it is.

A delta-rule layer carries, per sequence and per VALUE head, a matrix
``S [d_k, d_v]`` that does not grow with the sequence. The heads' matrices
lie side by side as ONE state ``s [N, D]``, ``N = d_k`` the key lanes
and ``D = H_v * d_v`` the heads' value lanes (``decode/paged.py::
RecurrentState.ssm``; ``models/face.py::StateRow``): head ``j`` is the
lanes ``[j * d_v, (j + 1) * d_v)``, whole 128-lane tiles at the
published ``d_v`` 128, so the chip keeps the store unpadded. Value head
``j`` reads key head ``j // (H_v / H_k)``. A token, per value head::

    S <- exp(g_t) S                     (g_t <= 0: the decay)
    u  = beta_t (v_t - S^T k_t)         (the delta: what S gets wrong)
    S <- S + k_t u^T                    (a rank-one write)
    o_t = S^T q_t

Unlike ``ops/ssm.py``'s Mamba-1 scan, which is elementwise over ``[N,
D]``, the state is CONTRACTED twice a token (with ``k``, then with
``q``). One mathematics, in the four forms its callers need:

- ``delta_chunk``: ``c`` consecutive tokens of ONE sequence, state in
  and state out: the prefill program. A ``lax.scan`` over time; a
  chunked (WY) form is ROADMAP M4.
- ``delta_step_in_place``: one token for each of ``b`` sequences, the
  state advanced WHERE IT IS STORED: the decode program's one path. One
  Pallas kernel a layer, ``ssm.scan_step_in_place``'s form: the store
  taken whole and given back aliased, the batch's ``rows``
  scalar-prefetched, a row's ``[N, D]`` read once and written once,
  both contractions and the rank-one write on the block while it is in
  fast memory; no ``[b, N, D]`` copy on either side.
- ``delta_mixed``: a decode batch's ``b`` rows through the kernel, then
  ONE sequence's chunk: the mixed program, where the mixer's weight
  products run once over both kinds of row.
- ``delta_step``: the same token for ``b`` sequences on GATHERED copies
  of their rows: the oracle of the tests and of the plain reference, on
  no program's path.

Every form takes ``q, k [n, H_k, d_k]`` (normalised and scaled by the
caller), ``v [n, H_v, d_v]`` and ``g, beta [n, H_v]``, and answers ``y
[n, H_v * d_v]``. Everything is float32 and exact on the vector unit:
the contractions are products and sums over the key lanes, never a
matrix product at the device's default precision. Forward only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ssm


def _per_value_head(x: jax.Array, h_v: int) -> jax.Array:
    """``[n, H_k, d_k] -> [n, H_v, d_k]``: value head ``j`` reads key
    head ``j // (H_v / H_k)``."""
    return jnp.repeat(x, h_v // x.shape[1], axis=1)


def _token(s, q, k, v, g, beta):
    """One token of one sequence on ``s [N, H_v, d_v]``: ``q, k [H_v,
    N]`` (a value head's own), ``v [H_v, d_v]``, ``g, beta [H_v]``.
    Returns ``(s, o [H_v, d_v])``."""
    kc, qc = k.T[:, :, None], q.T[:, :, None]           # [N, H_v, 1]
    s = jnp.exp(g)[None, :, None] * s
    u = beta[:, None] * (v - jnp.sum(s * kc, axis=0))
    s = s + kc * u[None]
    return s, jnp.sum(s * qc, axis=0)


def delta_chunk(q, k, v, g, beta, s0):
    """The recurrence over a chunk of one sequence: ``q, k [c, H_k,
    d_k]``, ``v [c, H_v, d_v]``, ``g, beta [c, H_v]``, ``s0 [N, D]``.
    Returns ``(y [c, D], s [N, D])``."""
    h_v, d_v = v.shape[1:]

    def step(s, inp):
        s, o = _token(s, *inp)
        return s, o.reshape(-1)

    s, y = lax.scan(step, s0.reshape(-1, h_v, d_v),
                    (_per_value_head(q, h_v), _per_value_head(k, h_v), v, g,
                     beta))
    return y, s.reshape(s0.shape)


def delta_step(q, k, v, g, beta, s):
    """The recurrence for one token of each of ``b`` sequences, on
    copies of their states: ``s [b, N, D]``. Returns ``(y [b, D], s [b,
    N, D])``."""
    h_v, d_v = v.shape[1:]
    s1, o = jax.vmap(_token)(
        s.reshape(s.shape[0], -1, h_v, d_v), _per_value_head(q, h_v),
        _per_value_head(k, h_v), v, g, beta)
    return o.reshape(o.shape[0], -1), s1.reshape(s.shape)


def _delta_kernel(d_v: int, rows_ref, veb_ref, kq_ref, s_ref, y_ref,
                  new_ref):
    del rows_ref                    # it placed the blocks; nothing more
    heads = kq_ref.shape[-1] // 2   # the value heads of this tile
    for h in range(heads):
        lanes = slice(h * d_v, (h + 1) * d_v)
        # the head's key and query as COLUMNS over the state's rows
        kc, qc = kq_ref[:, h:h + 1], kq_ref[:, heads + h:heads + h + 1]
        # ``_token``, term for term, on the head's ``[N, d_v]`` block
        s = veb_ref[1:2, lanes] * s_ref[:, lanes]
        u = veb_ref[2:3, lanes] * (
            veb_ref[0:1, lanes] - jnp.sum(s * kc, axis=0, keepdims=True))
        s = s + kc * u
        new_ref[:, lanes] = s
        y_ref[:, lanes] = jnp.sum(s * qc, axis=0, keepdims=True)


def delta_step_in_place(q, k, v, g, beta, store, *, layer: int, rows):
    """``delta_step`` on the states where they are stored: rows ``rows
    [b]`` of layer ``layer`` of ``store [L, S, N, D]``
    (``RecurrentState.ssm``) are read once and written once, in place.
    Operands as ``delta_step``'s; returns ``(y [b, D], store)``, every
    other row of the store with the bits it had.

    A Pallas kernel: ``store`` goes in and comes out as ONE aliased
    buffer, ``rows`` is scalar-prefetched, and grid step ``(r, j)``
    holds tile ``j`` of row ``rows[r]`` as the block ``[N, tile]``: a
    tile is whole value heads, so a head's two contractions and its
    rank-one write happen on the block while it is in fast memory and
    no ``[b, N, D]`` copy exists on either side. What a row brings
    beside its state is small and laid out by the caller's side here:
    ``v``, ``exp(g)`` and ``beta`` as three rows over the ``D`` lanes
    (a head's scalar repeated over its lanes), and each value head's
    key and query as columns over the state's rows. Rows that repeat
    (the padded rows of a bucket all name the scratch row) are read and
    written by several grid steps: what such a row then holds is one of
    those writes, which nothing reads."""
    # ``ssm``'s rule, read through the module (its one place to steer)
    interpret = ssm._interpreted()
    n_b, h_v, d_v = v.shape
    n, width = store.shape[2:]
    if not interpret and d_v % ssm._LANES:
        raise ValueError(ssm._UNTILED.format(d=d_v))
    # a grid step's blocks, in 8-row tiles: the three rows and y one
    # each, the columns' ``[N, 2 * heads]`` (under a tile of lanes),
    # the state in and out; the interpreter takes any width whole
    t = width if interpret else ssm._tile(width, 2 * 8 + 3 * n)
    tiles, heads = width // t, t // d_v

    def lanes(x):           # [b, H_v] -> [b, D]: a head's scalar a lane
        return jnp.repeat(x, d_v, axis=1)

    veb = jnp.stack([v.reshape(n_b, width), lanes(jnp.exp(g)), lanes(beta)],
                    axis=1)                             # [b, 3, D]

    def columns(x):         # [b, H_k, N] -> [b, tiles, N, heads]
        return _per_value_head(x, h_v).reshape(
            n_b, tiles, heads, n).swapaxes(2, 3)

    kq = jnp.concatenate([columns(k), columns(q)], axis=-1)

    def row(r_blk):         # one block a batch row, of ``[b, r_blk, D]``
        return pl.BlockSpec((None, r_blk, t), lambda r, j, rows: (r, 0, j))

    state = pl.BlockSpec((None, None, n, t),
                         lambda r, j, rows: (layer, rows[r], 0, j))
    y, store = pl.pallas_call(
        functools.partial(_delta_kernel, d_v),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_b, tiles),
            in_specs=[row(3),
                      pl.BlockSpec((None, None, n, 2 * heads),
                                   lambda r, j, rows: (r, j, 0, 0)),
                      state],
            out_specs=[row(1), state]),
        out_shape=[jax.ShapeDtypeStruct((n_b, 1, width), jnp.float32),
                   jax.ShapeDtypeStruct(store.shape, store.dtype)],
        # operands count from the prefetched ``rows``: the store is 3rd
        input_output_aliases={3: 1},
        interpret=interpret,
        name="delta_step",
    )(rows, veb, kq, store)
    return y[:, 0], store


def delta_mixed(q, k, v, g, beta, carried, *, layer: int, rows):
    """The recurrence over a decode batch's rows and then ONE
    sequence's chunk: the first ``len(rows)`` rows of every operand one
    token each of as many sequences, on the store where it lies; the
    rest a chunk of one sequence from its state. ``carried = (store,
    s)``; returns ``(y [n, D], (store, s))``."""
    b = rows.shape[0]
    store, s = carried
    batch, chunk = zip(*((x[:b], x[b:]) for x in (q, k, v, g, beta)))
    yb, store = delta_step_in_place(*batch, store, layer=layer, rows=rows)
    yc, s = delta_chunk(*chunk, s)
    return jnp.concatenate([yb, yc]), (store, s)
