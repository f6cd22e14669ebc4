"""Hand-scheduled ICI collectives on Pallas remote DMA — the explicit-
control escape hatch (SURVEY.md §2.7, last ledger row).

Everywhere else this framework lets XLA schedule communication: the
strategies emit ``psum``/``all_gather``/``ppermute`` and the compiler's
latency-hiding scheduler splits them into async pairs (proven in
``tests/test_observability.py``). That recovers what the reference
hand-builds with ``async_op=True`` + ``handle.wait()``
(``train_ffns.py:164-172``) — but it is trust-the-compiler control. This
module is the OTHER answer, the one the reference's stream experiment
(``test_torch_cuda_stream.py:31-37``) was reaching for: communication as
explicitly issued, explicitly awaited inter-chip DMA, scheduled by us.

The COMPLETE collective family — every op in SURVEY §2.7's ledger — as
hand-scheduled kernels, each pinned against its XLA counterpart and
AOT-compiled for v5e-8:

- ``ppermute_dma``: one ring hop — each device RDMAs its block to its
  right neighbor (``pltpu.make_async_remote_copy``), with the neighbor
  barrier that makes a raw remote write safe. Equality-pinned against
  ``lax.ppermute``.
- ``ring_all_reduce``: the full classic 2(n-1)-step ring — reduce-
  scatter phase then all-gather phase — inside ONE kernel launch:
  double-buffered communication slots, DMA-completion semaphores,
  explicit capacity handshaking (a receiver frees a slot back to its
  sender), and a pairwise phase handoff. Each step's accumulate overlaps
  the next chunk's DMA — the comm/compute overlap the reference wanted,
  hand-scheduled. Equality-pinned against ``lax.psum`` (identical
  summation order per chunk: partials accumulate in ring order on both
  paths only if n is the ring size — values agree to f32 reduction-order
  tolerance).
- ``ring_reduce_scatter`` / ``ring_all_gather``: the two phases as
  standalone kernels in the ``psum_scatter``/``all_gather`` conventions
  — the exact pattern FSDP consumes (``train_fsdp(comm="pallas_ring")``
  runs its whole comm schedule through them).
- ``all_to_all_dma``: the dense peer fan-out (EP-dispatch / Ulysses
  transport) — every (src, dst) block pair is a direct RDMA with
  per-peer semaphore slots; all n-1 transfers in flight at once, no
  slot reuse, no backpressure needed.

Algorithm notes (device ``r`` of ``n``, chunks = leading-dim n-split):

- reduce-scatter step ``s``: send chunk ``(r - s) % n`` right, receive
  chunk ``(r - s - 1) % n`` from the left into comm slot ``s % 2``, add
  it to the local copy. After ``n-1`` steps device ``r`` owns the fully
  reduced chunk ``(r + 1) % n``.
- all-gather step ``s``: send chunk ``(r + 1 - s) % n`` right, directly
  into the receiver's output at the SAME global chunk index (all-gather
  writes chunk c to slot c everywhere); receive chunk ``(r - s) % n``.
  Every received chunk is immediately the next step's send — the ring
  dependency is the only synchronization needed.
- hazards handled explicitly: slot-reuse backpressure (capacity
  semaphore, signaled sender-ward on consumption), phase handoff (a
  device may only write a neighbor's output region after that neighbor
  left the reduce-scatter phase — pairwise REGULAR semaphore, no global
  barrier), and kernel-entry (neighbor barrier semaphore: no DMA may
  target a chip that has not entered the kernel).

Off-TPU the kernels run under the Mosaic TPU *interpreter*
(``pltpu.InterpretParams`` — NOT the generic ``interpret=True``, which
has no remote-DMA model), so the 8-device CPU mesh exercises the real
semaphore/DMA semantics. On-chip compilation is pinned by the v5e-8 AOT
codegen test (the Mosaic custom call replaces the XLA collective in the
lowered module).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret_arg(interpret: bool | None):
    # the TPU interpreter models semaphores + remote DMA; the generic
    # pallas interpreter does not. None = auto: interpreter off-TPU,
    # Mosaic on chip (AOT codegen callers pass False explicitly).
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return pltpu.InterpretParams() if interpret else False


_LANES = 128


def _legalize_2d(x2, n: int):
    """Mosaic slices a 2-D VMEM ref along dim 0 only if dim 1 is
    lane-aligned (128). A narrow operand (e.g. FSDP's per-layer
    ``[rows, 64]`` shards) is re-flattened so each ring CHUNK becomes
    ``[elems/128, 128]`` — pure reshape, chunk boundaries preserved
    (chunks are contiguous in row-major), values untouched. Returns the
    legalized array; the caller reshapes the result back."""
    rows, cols = x2.shape
    if cols % _LANES == 0:
        return x2
    elems = (rows // n) * cols  # per chunk
    if elems % _LANES == 0:
        return x2.reshape(n * (elems // _LANES), _LANES)
    return x2  # narrow fallback: fine in interpret; Mosaic may reject


def _drain_capacity(capacity, n: int):
    """Zero the capacity semaphore's never-waited leftovers (the last
    two steps' consumption signals have no reusing step). SAFETY-
    CRITICAL ledger: a stale count satisfies a later backpressure wait
    without any real consumption and re-opens the ≥2-step-skew DMA/
    semaphore aliasing race (the n=8 corruption bug) — one accounting,
    shared by every phase of every ring kernel."""
    for slot_id in (0, 1):
        sig = len([s for s in range(n - 1) if s % 2 == slot_id])
        wai = len([s for s in range(2, n - 1) if s % 2 == slot_id])
        if sig - wai:
            pltpu.semaphore_wait(capacity.at[slot_id], sig - wai)


def _neighbor_barrier(axis_name: str, n: int):
    """No remote write may target a chip still outside the kernel."""
    r = lax.axis_index(axis_name)
    barrier = pltpu.get_barrier_semaphore()
    left = lax.rem(r - 1 + n, n)
    right = lax.rem(r + 1, n)
    pltpu.semaphore_signal(barrier, inc=1, device_id=left,
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_signal(barrier, inc=1, device_id=right,
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(barrier, 2)


def ppermute_dma(x: jax.Array, axis_name: str, *,
                 interpret: bool | None = None) -> jax.Array:
    """One ring hop by explicit RDMA: device r's block lands on device
    ``(r+1) % n`` — ``lax.ppermute(x, perm=[(i, (i+1)%n)])`` with the
    transport hand-issued. Call inside ``shard_map``."""
    n = lax.psum(1, axis_name)
    if n == 1:
        return x
    shape = x.shape
    x2 = x.reshape(shape[0], -1) if x.ndim != 2 else x

    def kernel(x_ref, o_ref, send_sem, recv_sem):
        _neighbor_barrier(axis_name, n)
        r = lax.axis_index(axis_name)
        rdma = pltpu.make_async_remote_copy(
            src_ref=x_ref, dst_ref=o_ref,
            send_sem=send_sem, recv_sem=recv_sem,
            device_id=lax.rem(r + 1, n),
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        rdma.start()
        rdma.wait()

    out = pl.pallas_call(
        kernel,
        # vma: the landed blocks differ per device (shard-varying under
        # shard_map's vma typing — DESIGN.md §4)
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype,
                                       vma=frozenset({axis_name})),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(()),
                        pltpu.SemaphoreType.DMA(())],
        compiler_params=pltpu.CompilerParams(has_side_effects=True,
                                             collective_id=7),
        interpret=_interpret_arg(interpret),
    )(x2)
    return out.reshape(shape)


def ring_all_reduce(x: jax.Array, axis_name: str, *,
                    interpret: bool | None = None) -> jax.Array:
    """``lax.psum(x, axis_name)`` as a hand-scheduled 2-phase ring of
    ``pltpu.make_async_remote_copy`` hops. Call inside ``shard_map``;
    ``x.shape[0]`` must divide by the axis size (the chunk unit)."""
    n = lax.psum(1, axis_name)
    if n == 1:
        return x
    shape = x.shape
    if shape[0] % n:
        raise ValueError(f"leading dim {shape[0]} not divisible by ring "
                         f"size {n} (chunk unit of the ring)")
    x2 = x.reshape(shape[0], -1) if x.ndim != 2 else x
    x2 = _legalize_2d(x2, n)
    rows, cols = x2.shape
    rc = rows // n  # rows per chunk

    def chunk(ref, idx):
        return ref.at[pl.ds(idx * rc, rc), :]

    def kernel(x_ref, o_ref, comm_buf, send_sem, recv_sem, capacity,
               phase_sem):
        _neighbor_barrier(axis_name, n)
        r = lax.axis_index(axis_name)
        left = lax.rem(r - 1 + n, n)
        right = lax.rem(r + 1, n)
        o_ref[...] = x_ref[...]

        # ---- phase 1: reduce-scatter (n-1 steps) --------------------
        def rs_step(s, _):
            slot = lax.rem(s, 2)
            send_idx = lax.rem(r - s + n, n)
            recv_idx = lax.rem(r - s - 1 + n, n)
            # backpressure: slot reused every 2 steps — wait until the
            # right neighbor freed it (it signals on consumption)
            @pl.when(s >= 2)
            def _():
                pltpu.semaphore_wait(capacity.at[slot], 1)
            rdma = pltpu.make_async_remote_copy(
                src_ref=chunk(o_ref, send_idx),
                dst_ref=comm_buf.at[slot],
                send_sem=send_sem.at[slot], recv_sem=recv_sem.at[slot],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            rdma.start()
            rdma.wait_recv()  # left's chunk for this step has landed
            o_ref[pl.ds(recv_idx * rc, rc), :] += comm_buf[slot]
            # slot consumed: hand it back to its writer (left neighbor)
            pltpu.semaphore_signal(
                capacity.at[slot], inc=1, device_id=left,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            rdma.wait_send()
            return 0

        lax.fori_loop(0, n - 1, rs_step, 0)

        # ---- drain phase 1's capacity leftovers ---------------------
        # The last two steps' consumption signals are never waited (no
        # step n/n+1 reuses those slots): +1 leftover per slot. Phase 2
        # REUSES the capacity semaphore — a stale count would satisfy
        # its first backpressure wait without any real consumption,
        # re-opening the ≥2-step-skew DMA/semaphore aliasing race (this
        # exact bug corrupted chunks at n=8). Drain to zero here, so
        # phase 2's waits can only be satisfied by phase-2 signals.
        # (Also the ledger discipline: leftover counts would poison the
        # next kernel sharing the physical semaphores.)
        _drain_capacity(capacity, n)

        # ---- phase handoff ------------------------------------------
        # Phase 2 writes straight into the RIGHT neighbor's output; that
        # is only safe once the neighbor is out of phase 1. Pairwise
        # signal leftward ("I am done reading what you may overwrite"),
        # wait for the right neighbor's.
        pltpu.semaphore_signal(phase_sem, inc=1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(phase_sem, 1)

        # ---- phase 2: all-gather (n-1 steps) ------------------------
        # The same ≤2-step skew bound phase 1 gets from its capacity
        # handshake is REQUIRED here too: without backpressure a sender
        # can run ≥2 steps ahead of its receiver, two of its DMAs alias
        # the same mod-2 semaphore slot, and DMA completion order is not
        # guaranteed — the receiver's wait can be satisfied by the LATER
        # chunk's arrival (observed as corrupted chunks at n=8 in the
        # Mosaic interpreter). Signal-after-wait_recv bounds the skew.
        def ag_step(s, _):
            slot = lax.rem(s, 2)
            send_idx = lax.rem(r + 1 - s + n, n)  # global chunk id; the
            # receiver stores chunk c at slot c, so src and dst slices
            # coincide — every received chunk is the next step's send
            @pl.when(s >= 2)
            def _():
                pltpu.semaphore_wait(capacity.at[slot], 1)
            rdma = pltpu.make_async_remote_copy(
                src_ref=chunk(o_ref, send_idx),
                dst_ref=chunk(o_ref, send_idx),
                send_sem=send_sem.at[slot], recv_sem=recv_sem.at[slot],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            rdma.start()
            rdma.wait_recv()  # chunk (r - s) % n landed in place
            pltpu.semaphore_signal(
                capacity.at[slot], inc=1, device_id=left,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            rdma.wait_send()
            return 0

        lax.fori_loop(0, n - 1, ag_step, 0)

        # ---- drain phase 2's leftovers (same accounting) ------------
        _drain_capacity(capacity, n)

    out = pl.pallas_call(
        kernel,
        # typed shard-varying: the SUM is value-replicated but produced
        # independently per device; callers needing invariant typing
        # pcast (same situation as zero1's re-assembled params)
        out_shape=jax.ShapeDtypeStruct((rows, cols), x2.dtype,
                                       vma=frozenset({axis_name})),
        # VMEM: the kernel reads/accumulates the operand directly (ANY/
        # HBM refs are DMA-only), and resident operands are what lets
        # each step's accumulate overlap the next chunk's DMA
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, rc, cols), x2.dtype),   # double-buffered slots
            pltpu.SemaphoreType.DMA((2,)),         # send completion
            pltpu.SemaphoreType.DMA((2,)),         # recv completion
            pltpu.SemaphoreType.REGULAR((2,)),     # slot backpressure
            pltpu.SemaphoreType.REGULAR,           # phase handoff
        ],
        compiler_params=pltpu.CompilerParams(has_side_effects=True,
                                             collective_id=8),
        interpret=_interpret_arg(interpret),
    )(x2)
    return out.reshape(shape)


def ring_reduce_scatter(x: jax.Array, axis_name: str, *,
                        interpret: bool | None = None) -> jax.Array:
    """``collectives.reduce_scatter(x, axis, dim=0)`` hand-scheduled:
    the reduce-scatter phase of the ring alone. ``x [n*rc, ...]`` per
    device; device ``r`` returns the summed chunk ``r`` (``[rc, ...]``).

    Same protocol as ``ring_all_reduce``'s phase 1 with the ring pattern
    shifted one hop (virtual rank ``r-1``), so the finally-owned chunk is
    ``r`` — the ``lax.psum_scatter(tiled=True)`` convention the XLA path
    implements. Accumulation happens on a scratch copy of the input;
    only the owned chunk is written out."""
    n = lax.psum(1, axis_name)
    shape = x.shape
    if shape[0] % n:
        raise ValueError(f"leading dim {shape[0]} not divisible by ring "
                         f"size {n} (chunk unit of the ring)")
    if n == 1:
        return x
    x2 = x.reshape(shape[0], -1) if x.ndim != 2 else x
    x2 = _legalize_2d(x2, n)
    rc = x2.shape[0] // n
    cols = x2.shape[1]

    def kernel(x_ref, o_ref, acc, comm_buf, send_sem, recv_sem, capacity):
        _neighbor_barrier(axis_name, n)
        r = lax.axis_index(axis_name)
        left = lax.rem(r - 1 + n, n)
        right = lax.rem(r + 1, n)
        acc[...] = x_ref[...]
        rv = lax.rem(r - 1 + n, n)  # virtual rank: owned chunk = rv+1 = r

        def rs_step(s, _):
            slot = lax.rem(s, 2)
            send_idx = lax.rem(rv - s + n, n)
            recv_idx = lax.rem(rv - s - 1 + n, n)
            @pl.when(s >= 2)
            def _():
                pltpu.semaphore_wait(capacity.at[slot], 1)
            rdma = pltpu.make_async_remote_copy(
                src_ref=acc.at[pl.ds(send_idx * rc, rc), :],
                dst_ref=comm_buf.at[slot],
                send_sem=send_sem.at[slot], recv_sem=recv_sem.at[slot],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            rdma.start()
            rdma.wait_recv()
            acc[pl.ds(recv_idx * rc, rc), :] += comm_buf[slot]
            pltpu.semaphore_signal(
                capacity.at[slot], inc=1, device_id=left,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            rdma.wait_send()
            return 0

        lax.fori_loop(0, n - 1, rs_step, 0)
        o_ref[...] = acc[pl.ds(lax.rem(rv + 1, n) * rc, rc), :]
        # drain the never-waited capacity leftovers (ledger discipline)
        _drain_capacity(capacity, n)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rc, cols), x2.dtype,
                                       vma=frozenset({axis_name})),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((n * rc, cols), x2.dtype),  # accumulator copy
            pltpu.VMEM((2, rc, cols), x2.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR((2,)),
        ],
        compiler_params=pltpu.CompilerParams(has_side_effects=True,
                                             collective_id=9),
        interpret=_interpret_arg(interpret),
    )(x2)
    return out.reshape((shape[0] // n,) + shape[1:])


def ring_all_gather(x: jax.Array, axis_name: str, *,
                    interpret: bool | None = None) -> jax.Array:
    """``collectives.all_gather(x, axis, dim=0)`` hand-scheduled: the
    all-gather phase of the ring alone. ``x [rows, ...]`` per device;
    returns ``[n*rows, ...]`` with chunk ``i`` = device ``i``'s block —
    ``ring_all_reduce``'s phase 2 with the output seeded from the local
    block instead of reduced chunks (owner of chunk ``r`` is ``r``, so
    the send pattern starts one hop later: ``send_idx = (r - s) % n``)."""
    n = lax.psum(1, axis_name)
    if n == 1:
        return x
    shape = x.shape
    x2 = x.reshape(shape[0], -1) if x.ndim != 2 else x
    x2 = _legalize_2d(x2, 1)  # the chunk unit is the WHOLE local block
    rc, cols = x2.shape

    def kernel(x_ref, o_ref, send_sem, recv_sem, capacity):
        _neighbor_barrier(axis_name, n)
        r = lax.axis_index(axis_name)
        left = lax.rem(r - 1 + n, n)
        right = lax.rem(r + 1, n)
        o_ref[pl.ds(r * rc, rc), :] = x_ref[...]

        def ag_step(s, _):
            slot = lax.rem(s, 2)
            send_idx = lax.rem(r - s + n, n)  # own block at s=0, then
            # each received chunk is the next step's send (the ring
            # dependency); receiver stores chunk c at slot c
            @pl.when(s >= 2)
            def _():
                pltpu.semaphore_wait(capacity.at[slot], 1)
            rdma = pltpu.make_async_remote_copy(
                src_ref=o_ref.at[pl.ds(send_idx * rc, rc), :],
                dst_ref=o_ref.at[pl.ds(send_idx * rc, rc), :],
                send_sem=send_sem.at[slot], recv_sem=recv_sem.at[slot],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            rdma.start()
            rdma.wait_recv()  # chunk (r - s - 1) % n landed in place
            pltpu.semaphore_signal(
                capacity.at[slot], inc=1, device_id=left,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            rdma.wait_send()
            return 0

        lax.fori_loop(0, n - 1, ag_step, 0)
        _drain_capacity(capacity, n)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n * rc, cols), x2.dtype,
                                       vma=frozenset({axis_name})),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR((2,)),
        ],
        compiler_params=pltpu.CompilerParams(has_side_effects=True,
                                             collective_id=10),
        interpret=_interpret_arg(interpret),
    )(x2)
    return out.reshape((n * shape[0],) + shape[1:])


def _all_peer_barrier(axis_name: str, n: int):
    """All-to-all targets every peer, so kernel-entry safety needs the
    FULL barrier (the neighbor form only covers ring topologies)."""
    r = lax.axis_index(axis_name)
    barrier = pltpu.get_barrier_semaphore()

    def signal(j, _):
        @pl.when(j != r)
        def _():
            pltpu.semaphore_signal(
                barrier, inc=1, device_id=j,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
        return 0

    lax.fori_loop(0, n, signal, 0)
    pltpu.semaphore_wait(barrier, n - 1)


def all_to_all_dma(x: jax.Array, axis_name: str, *,
                   interpret: bool | None = None) -> jax.Array:
    """``collectives.all_to_all(x, axis, split_dim=0, concat_dim=0)``
    hand-scheduled: chunk ``j`` of every device's block RDMAs DIRECTLY to
    device ``j`` (no ring — the dense peer fan-out the EP dispatch and
    Ulysses re-shards ride), landing at chunk position ``r`` of the
    receiver. All ``n-1`` outgoing transfers start before any wait (full
    overlap); per-peer semaphore slots make completion order irrelevant
    (each (src, dst) pair is unique — no slot reuse, no backpressure
    needed, unlike the ring kernels)."""
    n = lax.psum(1, axis_name)
    if n == 1:
        return x
    shape = x.shape
    if shape[0] % n:
        raise ValueError(f"leading dim {shape[0]} not divisible by "
                         f"{n} peers (the split unit of all_to_all)")
    x2 = x.reshape(shape[0], -1) if x.ndim != 2 else x
    x2 = _legalize_2d(x2, n)
    rows, cols = x2.shape
    rc = rows // n

    def kernel(x_ref, o_ref, send_sem, recv_sem):
        _all_peer_barrier(axis_name, n)
        r = lax.axis_index(axis_name)
        o_ref[pl.ds(r * rc, rc), :] = x_ref[pl.ds(r * rc, rc), :]

        def out_desc(j):
            # outgoing r->j: my chunk j lands at the receiver's chunk r;
            # the remote signal slot is MY index (so the receiver can
            # tell sources apart), my send slot is the peer index
            return pltpu.make_async_remote_copy(
                src_ref=x_ref.at[pl.ds(j * rc, rc), :],
                dst_ref=o_ref.at[pl.ds(r * rc, rc), :],
                send_sem=send_sem.at[j], recv_sem=recv_sem.at[r],
                device_id=j,
                device_id_type=pltpu.DeviceIdType.LOGICAL)

        def start(j, _):
            @pl.when(j != r)
            def _():
                out_desc(j).start()
            return 0

        lax.fori_loop(0, n, start, 0)

        def wait(j, _):
            @pl.when(j != r)
            def _():
                # incoming from peer j: wrote my chunk j, signals MY
                # recv slot j — a descriptor with the matching refs
                # (same transfer size) and slot performs the wait
                pltpu.make_async_remote_copy(
                    src_ref=x_ref.at[pl.ds(r * rc, rc), :],
                    dst_ref=o_ref.at[pl.ds(j * rc, rc), :],
                    send_sem=send_sem.at[j], recv_sem=recv_sem.at[j],
                    device_id=j,
                    device_id_type=pltpu.DeviceIdType.LOGICAL).wait_recv()
                out_desc(j).wait_send()
            return 0

        lax.fori_loop(0, n, wait, 0)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, cols), x2.dtype,
                                       vma=frozenset({axis_name})),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((n,)),   # per-peer send completion
            pltpu.SemaphoreType.DMA((n,)),   # per-source recv completion
        ],
        compiler_params=pltpu.CompilerParams(has_side_effects=True,
                                             collective_id=11),
        interpret=_interpret_arg(interpret),
    )(x2)
    return out.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def all_to_all_dma_dims(x: jax.Array, axis_name: str, split_dim: int,
                        concat_dim: int,
                        interpret: bool | None = None) -> jax.Array:
    """``collectives.all_to_all(x, axis, split_dim=s, concat_dim=c)``
    (tiled) over the ``all_to_all_dma`` kernel: the split dim moves to
    the front for the dim-0 exchange, and the received blocks
    concatenate back along ``concat_dim`` — the Ulysses re-shard shapes
    (``[H, T, dh]``, 0<->1) ride this form. Differentiable: the VJP of a
    tiled all_to_all is the all_to_all with the dims swapped (the
    exchange is a linear permutation of blocks), so autodiff through a
    strategy's a2a transport runs the transport kernel both ways."""
    return _a2a_dims_fwd(x, axis_name, split_dim, concat_dim,
                         interpret)[0]


def _a2a_dims_fwd(x, axis_name, split_dim, concat_dim, interpret):
    n = lax.psum(1, axis_name)
    if n == 1:
        return x, None
    xm = jnp.moveaxis(x, split_dim, 0)
    k = all_to_all_dma(xm, axis_name, interpret=interpret)
    kb = k.reshape(n, xm.shape[0] // n, *xm.shape[1:])
    blocks = [jnp.moveaxis(kb[j], 0, split_dim) for j in range(n)]
    return jnp.concatenate(blocks, axis=concat_dim), None


def _a2a_dims_bwd(axis_name, split_dim, concat_dim, interpret, _, dy):
    return (all_to_all_dma_dims(dy, axis_name, concat_dim, split_dim,
                                interpret),)


all_to_all_dma_dims.defvjp(_a2a_dims_fwd, _a2a_dims_bwd)


def ring_all_reduce_spmd(x: jax.Array, mesh, axis_name: str, *,
                         interpret: bool = False) -> jax.Array:
    """Convenience launcher: shard a global ``[n*rows, cols]`` array over
    the axis, ring-all-reduce the per-device blocks, return the stacked
    per-device results (each block is the full sum — the differential-
    test harness shape, comparable leaf-for-leaf against the same
    ``shard_map`` wrapping ``lax.psum``)."""
    from jax.sharding import PartitionSpec as P
    f = jax.shard_map(
        functools.partial(ring_all_reduce, axis_name=axis_name,
                          interpret=interpret),
        mesh=mesh, in_specs=P(axis_name, None), out_specs=P(axis_name, None))
    return f(x)
