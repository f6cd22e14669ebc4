"""The selective scan of a Mamba-1 mixer, in the forms serving needs;
its depthwise causal convolution alone is also the core of a gated
short-convolution layer (``models/lfm2_moe_lm.py``: ``K = 3``, no bias,
no scan state), which runs the same ``conv_chunk`` and
``conv_step_in_place``; so does a gated delta-rule mixer
(``models/qwen3_next_lm.py``: ``K = 4`` over the lanes of ``q``, ``k``
and ``v`` side by side), whose recurrence, a matrix a head that is
contracted twice a token, has its one home in ``ops/delta_rule.py``
(the same four forms, this file's tiling and interpreter rule).

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

One mathematics (a chunk of one token is a step of one row), in the
forms its callers need:

- ``*_chunk``: ``c`` consecutive tokens of ONE sequence, state in and
  state out — the prefill program. A ``lax.scan`` over time; ``y_t`` is
  reduced inside it, so no ``[c, N, D]`` history is ever held.
- ``conv_step_in_place`` + ``scan_step_in_place``: one token for each
  of ``b`` sequences, the state advanced WHERE IT IS STORED
  (``decode/paged.py::RecurrentState``) — the decode program's one path
  (PR 32). Two Pallas kernels a layer (the convolution's output passes
  through two weight products before the scan needs it): each takes its
  store whole and gives it back, aliased; the batch's ``rows`` are
  scalar-prefetched and place one block a row, so a row is read once
  and written once and no ``[b, N, D]`` or ``[b, K-1, D]`` copy exists
  on either side. The bytes follow the batch, not the slots. On the
  chip the parent's gather, recurrence and scatter over copies took
  10.2 ms a program of 26 layers and 64 rows, these 2.7 (PERF.md §6).
- ``conv_mixed`` / ``scan_mixed``: a decode batch's ``b`` rows and then
  ONE sequence's chunk as rows of one array — the mixed program
  (PR 36), where a mixer's weight products run once over both kinds of
  row: the first ``b`` rows go through the ``*_step_in_place`` kernel,
  the rest through the ``*_chunk`` form.
- ``conv_step`` / ``scan_step``: the same token for ``b`` sequences on
  GATHERED copies of their rows — the oracle of the tests and of the
  plain references, on no program's path.

Everything here is float32: the state, the recurrence and the
convolution. Forward only (serving). A chunked-scan kernel for the
prefill program and the hand-written backward the trainers would need
are ROADMAP M4.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def conv_chunk(x: jax.Array, tail: jax.Array, w: jax.Array,
               bias: jax.Array | None):
    """Depthwise causal convolution over a chunk: ``x [c, D]`` the new
    inputs, ``tail [K-1, D]`` the inputs just before them (zeros at the
    start of a sequence), ``w [K, D]`` with tap ``K-1`` on the current
    token, ``bias [D]`` (None for a convolution without one). Returns
    ``(y [c, D], new tail [K-1, D])``. The taps are summed oldest
    first, whatever the chunk size."""
    k = w.shape[0]
    c = x.shape[0]
    full = jnp.concatenate([tail, x], axis=0)           # [K-1+c, D]
    y = sum(w[j] * full[j:j + c] for j in range(k))
    return (y if bias is None else bias + y), full[c:]


def conv_step(x: jax.Array, tail: jax.Array, w: jax.Array,
              bias: jax.Array | None):
    """``conv_chunk`` for one token of each of ``b`` sequences:
    ``x [b, D]``, ``tail [b, K-1, D]``."""
    k = w.shape[0]
    full = jnp.concatenate([tail, x[:, None, :]], axis=1)  # [b, K, D]
    y = sum(w[j] * full[:, j] for j in range(k))
    return (y if bias is None else bias + y), full[:, 1:]


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


# -- the decode program's forms: the state advanced where it is stored ---

# what one grid step of the kernel may hold of the chip's fast memory,
# buffers doubled: half of the 16 MiB a kernel gets of a v5e's VMEM by
# default
_VMEM_BUDGET = 8 * 2 ** 20
_LANES = 128
_UNTILED = ("inner width {d} is no multiple of 128 lanes: the chip "
            "cannot tile the state's rows")


def _interpreted() -> bool:
    """The ONE place that decides how both kernels run: in the Pallas
    interpreter wherever the process's default backend is no TPU
    (``cli.py``'s rule for the older kernels): correct, slow, never a
    timing — the toy widths of the CPU tests are no multiple of 128
    lanes. No caller passes an ``interpret`` of its own. The rule reads
    the process, not what is lowered for: a compile for a described
    chip from a host without one patches this function
    (``tests/test_chip_compile.py::kernels_for_the_chip``)."""
    return jax.default_backend() != "tpu"


def _tile(d: int, rows: int) -> int:
    """The tile of the inner width ``D`` one grid step works on, on the
    chip: the largest whole-lane divisor of ``D`` of which ``rows``
    float32 rows (every block in and out, padded to 8-row tiles), each
    buffered twice, fit ``_VMEM_BUDGET``."""
    if d % _LANES:
        raise ValueError(_UNTILED.format(d=d))
    return max(t for t in range(_LANES, d + 1, _LANES)
               if d % t == 0 and (t == _LANES
                                  or 2 * rows * t * 4 <= _VMEM_BUDGET))


def _conv_kernel(rows_ref, x_ref, w_ref, *refs):
    del rows_ref                    # it placed the blocks; nothing more
    # ``refs``: the bias where the convolution has one, then the tail
    # in, ``y`` out and the tail out
    *bias_ref, tail_ref, y_ref, new_ref = refs
    d = x_ref.shape[-1]
    k1 = tail_ref.shape[-1] // d
    # ``conv_step``'s sum, term for term: the taps oldest first, each
    # ``D`` whole lanes of the row as stored
    taps = [tail_ref[:, j * d:(j + 1) * d] for j in range(k1)]
    taps.append(x_ref[...])                             # [1, D]
    y = sum(w_ref[j:j + 1, :] * tap for j, tap in enumerate(taps))
    y_ref[...] = bias_ref[0][...] + y if bias_ref else y
    for j in range(k1):
        new_ref[:, j * d:(j + 1) * d] = taps[j + 1]


def conv_step_in_place(x, store, w, bias, *, layer: int, rows):
    """``conv_step`` on the tails where they are stored: rows ``rows
    [b]`` of layer ``layer`` of ``store [L, S, 1, (K-1)*D]``
    (``RecurrentState.conv``: a row's taps oldest first, end to end)
    are read once and written once, in place. ``x [b, D]``, ``w [K,
    D]``, ``bias [D]`` (or None: the kernel then takes no operand in
    its place) as ``conv_step``'s; returns ``(y [b, D], store)``, every
    other row of the store with the bits it had.

    The same kernel form as ``scan_step_in_place`` below: the store
    aliased in to out, ``rows`` scalar-prefetched, grid step ``r``
    holding row ``rows[r]`` WHOLE as the block ``[1, (K-1)*D]`` (61 KB
    at the hybrid cell's widths, where a grid step's share of the fast
    memory is megabytes; a flat row has no tile of ``D`` that is one
    block). A tap is ``D`` whole lanes of that block, so nothing is
    split or re-laid-out."""
    interpret = _interpreted()
    n_b, d = x.shape
    if not interpret and d % _LANES:
        raise ValueError(_UNTILED.format(d=d))
    row = pl.BlockSpec((None, 1, d), lambda r, rows: (r, 0, 0))
    tail = pl.BlockSpec((None, None, 1, store.shape[-1]),
                        lambda r, rows: (layer, rows[r], 0, 0))
    x = x[:, None, :]
    small = [(w, pl.BlockSpec(w.shape, lambda r, rows: (0, 0)))]
    if bias is not None:
        small.append((bias[None, :],
                      pl.BlockSpec((1, d), lambda r, rows: (0, 0))))
    y, store = pl.pallas_call(
        _conv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_b,),
            in_specs=[row, *(spec for _, spec in small), tail],
            out_specs=[row, tail]),
        out_shape=[jax.ShapeDtypeStruct((n_b, 1, d), jnp.float32),
                   jax.ShapeDtypeStruct(store.shape, store.dtype)],
        # operands count from the prefetched ``rows``: the store is
        # last, after ``x`` and the small ones
        input_output_aliases={2 + len(small): 1},
        interpret=interpret,
    )(rows, x, *(a for a, _ in small), store)
    return y[:, 0], store


def _scan_kernel(rows_ref, x_ref, dt_ref, bc_ref, a_ref, d_ref, s_ref,
                 y_ref, new_ref):
    del rows_ref                    # it placed the blocks; nothing more
    x, dt = x_ref[...], dt_ref[...]                     # [1, tile]
    b, c = bc_ref[:, 0:1], bc_ref[:, 1:2]               # [N, 1]
    s = jnp.exp(dt * a_ref[...]) * s_ref[...] + (dt * x) * b
    new_ref[...] = s
    y_ref[...] = jnp.sum(s * c, axis=0, keepdims=True) + d_ref[...] * x


def scan_step_in_place(x, dt, a, b, c, d, store, *, layer: int, rows):
    """``scan_step`` on the scan states where they are stored: rows
    ``rows [b]`` of layer ``layer`` of ``store [L, S, N, D]``
    (``RecurrentState.ssm``) are read once and written once, in place.
    Operands as ``scan_step``'s; returns ``(y [b, D], store)``, every
    other row of the store with the bits it had.

    A Pallas kernel: ``store`` goes in and comes out as ONE aliased
    buffer, ``rows`` is scalar-prefetched, and grid step ``(j, r)``
    holds tile ``j`` of row ``rows[r]`` as the block ``[N, tile]`` — the
    blocks' addresses follow ``rows``, so no ``[b, N, D]`` copy exists
    on either side of the recurrence. The row is the inner grid axis:
    the layer's ``a`` and ``d`` stay in fast memory over a tile's rows.
    The recurrence and the sum over ``N`` are ``scan_step``'s, in
    float32. Rows that repeat (the padded rows of a bucket all name the
    scratch row) are read and written by several grid steps: what such
    a row then holds is one of those writes, which nothing reads."""
    interpret = _interpreted()
    n_b, width = x.shape
    n = store.shape[2]
    # a grid step's blocks, in 8-row tiles: x, dt, y, d one each, b/c
    # and a the state's rows, the state in and out; the interpreter
    # takes any width whole
    t = width if interpret else _tile(width, 4 * 8 + 4 * (-(-n // 8) * 8))

    def row(r_blk):         # one block a batch row, of ``[b, r_blk, D]``
        return pl.BlockSpec((None, r_blk, t), lambda j, r, rows: (r, 0, j))

    def whole(r_blk):       # the layer's own ``[r_blk, D]``
        return pl.BlockSpec((r_blk, t), lambda j, r, rows: (0, j))

    state = pl.BlockSpec((None, None, n, t),
                         lambda j, r, rows: (layer, rows[r], 0, j))
    y, store = pl.pallas_call(
        _scan_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(width // t, n_b),
            in_specs=[row(1), row(1),
                      pl.BlockSpec((None, n, 2),
                                   lambda j, r, rows: (r, 0, 0)),
                      whole(n), whole(1), state],
            out_specs=[row(1), state]),
        out_shape=[jax.ShapeDtypeStruct((n_b, 1, width), jnp.float32),
                   jax.ShapeDtypeStruct(store.shape, store.dtype)],
        # operands count from the prefetched ``rows``: the store is 6th
        input_output_aliases={6: 1},
        interpret=interpret,
    )(rows, x[:, None, :], dt[:, None, :], jnp.stack([b, c], axis=-1), a,
      d[None, :], store)
    return y[:, 0], store


def conv_mixed(x, carried, w, bias, *, layer: int, rows):
    """The convolution over a decode batch's rows and then ONE
    sequence's chunk: ``x [b + c, D]``, ``carried = (store, tail)`` the
    WHOLE store (of which rows ``rows [b]`` advance in place) and the
    chunk's sequence's tail. Returns ``(y [b + c, D], (store, tail))``."""
    b = rows.shape[0]
    store, tail = carried
    yb, store = conv_step_in_place(x[:b], store, w, bias, layer=layer,
                                   rows=rows)
    yc, tail = conv_chunk(x[b:], tail, w, bias)
    return jnp.concatenate([yb, yc]), (store, tail)


def scan_mixed(x, dt, a, b, c, d, carried, *, layer: int, rows):
    """The recurrence likewise: the first ``len(rows)`` rows of ``x, dt
    [n, D]`` and ``b, c [n, N]`` one token each of as many sequences, on
    the store where it lies; the rest a chunk of ONE sequence from its
    state. ``carried = (store, s)``; returns ``(y [n, D], (store,
    s))``."""
    n = rows.shape[0]
    store, s = carried
    yb, store = scan_step_in_place(x[:n], dt[:n], a, b[:n], c[:n], d, store,
                                   layer=layer, rows=rows)
    yc, s = scan_chunk(x[n:], dt[n:], a, b[n:], c[n:], d, s)
    return jnp.concatenate([yb, yc]), (store, s)
