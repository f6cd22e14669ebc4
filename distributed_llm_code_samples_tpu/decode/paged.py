"""Block/paged KV cache: the decode engine's memory layout.

The lockstep decoder (``models.lm.generate``) allocates one contiguous
``[T_max]`` cache lane per sequence, so a batch of mixed-length
sequences pays for its longest member and freeing a finished sequence
means rebuilding the batch (a recompile). This module is the
PagedAttention-style answer in the repo's first-principles idiom: the
cache is a static-shape **pool of fixed-size blocks**
(``k/v [L, n_blocks, block, H_kv*dh]``; where a value head is not as
wide as a key head, ``v [.., H_kv*dv]``: the two sides are arrays of
their own and ``models/face.py::KVRow`` says both widths) and each
sequence names its
blocks through a per-slot int32 **block table** — the KV read goes
through the table, the write is a scatter, and freeing a sequence is a
host-side table edit. Shapes never depend on sequence length, so one
compiled decode step serves every occupancy.

Which read each kind of row takes, decided from the pool and the row
kind alone (no flag, field or environment variable chooses):

- a decode-side row (``decode``, ``mixed``, ``verify``) calls
  ``stored_decode_attn``, which attends over the rows as stored. For
  the FULL kind, for a LATENT pool and for a window layer's RING that
  is a WALK over the row's live blocks where they lie
  (``ops/kv_walk.py``: one kernel a layer, nothing of a gathered
  view's size exists) wherever ``walks`` says the pool takes it: a
  float pool whose rows are, on the chip, two or more whole 128-lane
  tiles. Every other pool keeps the PLAIN form,
  ``gathered_decode_attn``: a gather of each row's whole table and two
  products over the copy — an int8 pool (per-block scales), a
  one-tile or ragged row on the chip;
- a prefill chunk's rows call ``gathered_chunk_attn``: one slot's f32
  head-split view (``models.attention.gather_paged_kv``), which is also
  the tests' oracle for both decode-side forms.

The stored form is the one the chip keeps as it is. A token's row holds
all its KV heads side by side (``H_kv*dh`` lanes, head ``h`` at
``[h*dh, (h+1)*dh)``), so the two minor axes are ``(block, H_kv*dh)``:
the TPU tiles those row-major, and unpadded wherever ``H_kv*dh`` is a
multiple of 128 lanes (a narrower row, as a toy model's or a TP shard's
320, pads up to the next 128 — correct, and counted in ``PERF.md``). A
jitted program therefore takes and returns the donated pool without
converting it. The old head-major ``[L, n_blocks, H_kv, block, dh]``
left ``(block, dh) = (16, 64)`` minor: the chip stored that with the
BLOCK index innermost, padded, and every program re-laid the whole
pool out on the way in and on the way out. The layer rides inside the
indices of every step-path read and write (``k[layer, phys, off]``,
``k[full_like(table, layer), table]``): no ``[n_blocks, ...]`` slab of
one layer is ever sliced out. Host-side block documents (handoff,
spill, snapshots) keep the head-major ``[L, n, H_kv, block, dh]``;
``extract_blocks`` / ``implant_block`` convert at that edge, off the
step path.

Physical block 0 is reserved as the **scratch block**: unassigned table
slots and padded bucket rows point at it, so padded writes land
somewhere harmless instead of needing a masked scatter, and gathers of
short sequences read bytes the causal mask then hides (the walk does
not fetch a row's dead blocks at all; a padded row walks the scratch
block alone). Nothing is ever read from it unmasked.

Quantization (``kv_dtype``):

- ``"f32"`` — exact; the bit-for-bit baseline.
- ``"bf16"`` — cast on write; the decode read multiplies the rows as
  bf16 operands under f32 accumulation, the oracle view upcasts them
  (2x fewer KV bytes).
- ``"int8"`` — symmetric per-(layer, block, kv-head) scales
  (``k_scale/v_scale [L, n_blocks, H_kv]`` f32, ``scale = amax/127``).
  A write re-quantizes the touched block over its *valid* rows only
  (stale rows from a freed sequence never inflate the scale), which is
  lossy but deterministic: a block's stored bytes depend only on its own
  sequence's write history, so continuous batching stays token-identical
  to sequential decode at any dtype (tests/test_decode_engine.py).

A **latent** pool (``latent_rank > 0``; ``models/face.py::LATENT``)
keeps ONE row a token a layer with no head axis and no K/V pair: ``k
[L, n_blocks, block, m]`` holds the rows (multi-head latent attention's
``[c_t | k_rope_t]``, ``m = rank + rope`` lanes) and ``v`` is the same
shape with a minor axis of 0 — no bytes, so everything that moves a
block by its id (the scatters, copy-on-write, the prefix cache's
sharing, scrub, the chaos block) runs on it unchanged. The two reads
take every head's query against the row as ONE KV head: scores over the
whole row, values over its first ``latent_rank`` lanes, so ONE fetch of
a row serves both products: the walk copies each live block once into
one buffer (a V side of no lanes is no operand of the kernel), the
plain form gathers once. Both products run over the whole row and the
result's lanes beyond ``latent_rank`` are dropped after them. int8 has
per-head scales and a latent row no heads: refused.

A **window** pool (``models/face.py::WINDOW``: the third paged kind) is
a second ``PagedKV`` beside the first, for the layers that attend over
the last ``window`` positions only: ``k/v [L_w, n_blocks_w, block,
H_kv*dh]`` in the same stored form, with a scratch block and a free
list of its own. A sequence names its window blocks through a second,
short table (``window / block + 2`` entries at most: the window, the
block being written, one of slack) that it uses as a RING: the block of
positions ``[j*block, (j+1)*block)`` lies in entry ``j mod entries``, so
a block is overwritten exactly when every position in it is behind the
window of every row still to come (``ring_positions`` says which
position each stored row holds; ``models/attention.py::window_mask``
hides the rest, stale rows of an overwritten block among them; the walk
is told where a row's window starts, ``ring_start``, and fetches the
blocks from there to the row's last). The
writes and the two reads are the full kind's, told the window
(``write_chunk(ring=True)``, ``stored_decode_attn(window=)``,
``gathered_chunk_attn(window=)``): a window layer's gather is its short
table, never the sequence's whole capacity. Its row is its own
(``CacheSpec.window_row``: the window layers may have other KV head
counts and widths than the full ones), and every read takes an optional
per-head SINK, one more term of the softmax's denominator that has no
value row (``models/attention.py::softmax_stats``). What moves a sequence by
ONE block table (the prefix cache, spill, handoff, snapshots,
speculation, int8's write history, the head-sharded mesh) refuses a
model with window layers in one line (``decode/engine.py``).

A **chunked** layer (``models/face.py::CHUNKED``: the fourth paged
kind, chunk-summarised attention) owns TWO stores under its one index
and adds no pool of its own: the window kind's ring, read under the
ALIGNED rule (``models/attention.py::aligned_mask``: a query sees the
keys of its own multiple-of-``window`` window up to itself; the windows
do not slide), and one ROW of the full kind's pool for every finished
chunk of ``block`` positions (a pool block IS a chunk): row ``j`` of
the sequence's table holds ``(ktilde_j, vtilde_j)``, the model's
``chunk_summary`` of ring block ``j`` AS STORED, written by whichever
program writes the chunk's last position (``write_summaries``; the
scratch block on every other step). A row attends over its ring and
over the summaries of every earlier window, ``(window / block) *
(position // window)`` rows, under ONE softmax: each read hands back
its softmax statistics beside its result (``stats=True``) and
``join_reads`` puts them together. A full-kind table of ``MB`` blocks
so stands for ``MB * block * block`` positions.

The pool's layer axis counts the layers that own a KV cache index: all
of an ``LMParams``' layers, the attention layers only of a hybrid
(``models/hybrid_lm.py``: 2 of 28). What such a model's other layers
carry is the second kind of per-sequence state, ``RecurrentState``: it
does not grow with the sequence, so it is indexed by slot and not
through a block table, and lives beside the pool.

All functions are pure jnp with static shapes; the layer index is a
Python int (the engine unrolls layers at trace time, like
``models.lm.decode_step``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ..models.face import KVRow, StateRow

KV_DTYPES = ("f32", "bf16", "int8")

# physical block 0 is the scratch block (see module docstring)
SCRATCH_BLOCK = 0


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["k", "v", "k_scale", "v_scale"],
                   meta_fields=["head_dim", "latent_rank", "v_head_dim"])
@dataclasses.dataclass(frozen=True)
class PagedKV:
    """The block pool. ``k/v [L, n_blocks, block, H_kv*dh]`` in the
    storage dtype (a token's row holds its heads side by side: the
    module docstring says why); ``k_scale/v_scale [L, n_blocks, H_kv]``
    f32 per-block dequantization scales (``None`` unless
    ``kv_dtype="int8"``). ``head_dim`` is static (pytree metadata, not
    a leaf): it is what splits a row back into heads; ``v_head_dim`` is
    the value side's (``v [.., H_kv*dv]``; given as 0 it is
    ``head_dim``, and kept resolved), and ``row`` the whole
    description. ``latent_rank``
    > 0 marks a pool of latent rows (the module docstring): ``k`` holds
    them whole (``head_dim`` their lanes), ``v`` is zero lanes wide,
    and a row's first ``latent_rank`` lanes are its values."""
    k: jax.Array
    v: jax.Array
    k_scale: jax.Array | None
    v_scale: jax.Array | None
    head_dim: int
    latent_rank: int = 0
    v_head_dim: int = 0

    def __post_init__(self):
        if not self.v_head_dim:
            object.__setattr__(self, "v_head_dim", self.head_dim)

    def _replace(self, **fields) -> "PagedKV":
        return dataclasses.replace(self, **fields)

    @property
    def n_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def block_size(self) -> int:
        return self.k.shape[2]

    @property
    def kv_heads(self) -> int:
        """KV heads in a row — the LOCAL count inside a TP shard."""
        return self.k.shape[3] // self.head_dim

    @property
    def row(self) -> KVRow:
        """The store's row: its (local) KV heads, a key head's lanes
        and a value head's."""
        return KVRow(self.kv_heads, self.head_dim, self.v_head_dim)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["conv", "ssm"], meta_fields=[])
@dataclasses.dataclass(frozen=True)
class RecurrentState:
    """The second kind of per-sequence state: what a recurrent
    (state-space) layer carries, which does not grow with the sequence.
    Indexed by SLOT, not through a block table: ``conv [L_r, slots+1,
    1, (K-1)*C]`` the convolution's last ``K-1`` inputs, oldest first,
    and ``ssm [L_r, slots+1, N, D]`` the state (``ops/ssm.py``: Mamba's
    scan state, or a gated delta rule's matrix a value head, ``N`` its
    key lanes and ``D`` the heads' value lanes side by side), both
    float32, ``L_r`` the model's recurrent layers; ``C, K, N, D`` are
    the model's ``StateRow`` (``init_state`` builds both from it: the
    convolution's lanes ``C`` and the state's ``D`` are equal for a
    Mamba mixer and differ for a delta-rule one). A layer kind
    that carries the convolution's tail and NO state (a gated
    short convolution, ``models/lfm2_moe_lm.py``: ``rows`` 0) has
    ``ssm`` None: no leaf, no operand, no bytes — never an array of no
    elements handed to a program or a kernel. The wide axis
    is the minor one of both, so the chip keeps them unpadded (a ``[D,
    N]`` state would pad ``N = 16`` up to 128 lanes; a delta rule's
    ``[128, H_v * 128]`` is a head's ``[128, 128]`` block in whole
    128-lane tiles). Row ``slots`` is
    the scratch row — the pool's idiom: padded bucket rows read and
    write it, nothing else does. A slot's row is never cleared: the
    prefill program takes zeros in its place at position 0. Donated
    into the step programs beside the pool and updated in place: the
    decode program advances a batch's rows WHERE THEY LIE (PR 32,
    ``ops/ssm.py::conv_step_in_place`` / ``scan_step_in_place``: no
    gathered copy, no scatter), the prefill chunk slices its one row.

    The tail is FLAT, ``(K-1)*D`` lanes a row, taps end to end, under
    an axis of one: a tap is then ``D`` whole lanes of the row, which
    an op slices without a re-layout, and a row is a legal block of a
    kernel (``[1, (K-1)*D]``), which the chip keeps row-major in
    one-row tiles with no padding (without the axis of one its 8-row
    tiles pad 65 rows to 72). ``[slots+1, K-1, D]`` the chip keeps
    tap-major, and every program re-lays the whole store out on the way
    in and on the way out (static, this compiler: PERF.md §6, PR 32)."""
    conv: jax.Array
    ssm: jax.Array | None

    def _replace(self, **fields) -> "RecurrentState":
        return dataclasses.replace(self, **fields)

    @property
    def scratch_row(self) -> int:
        return self.conv.shape[1] - 1

    @property
    def bytes_per_slot(self) -> int:
        """Bytes one sequence's state holds over all recurrent layers
        (what a decode dispatch reads, and writes, for each ready
        slot)."""
        held = self.conv.nbytes + (0 if self.ssm is None
                                   else self.ssm.nbytes)
        return int(held // self.conv.shape[1])


def init_state(n_layers: int, slots: int, row: StateRow) -> RecurrentState:
    """Zero-filled recurrent state for ``slots`` sequences (+ the
    scratch row) over ``n_layers`` recurrent layers, each sequence's
    row of a layer as ``row`` says (``models/face.py::StateRow``); a
    row of no state rows is a layer kind that carries its tail alone
    (``ssm`` None)."""
    return RecurrentState(
        conv=jnp.zeros((n_layers, slots + 1, 1, row.tail_lanes),
                       jnp.float32),
        ssm=(jnp.zeros((n_layers, slots + 1, row.rows, row.lanes),
                       jnp.float32) if row.rows else None))


def _heads_major(x, head_dim: int):
    """``[..., block, H_kv*dh] -> [..., H_kv, block, dh]``: stored rows
    to the head-major block the int8 quantizer and the host documents
    speak. Works on numpy and jax arrays alike."""
    *lead, blk, m = x.shape
    return x.reshape(*lead, blk, m // head_dim, head_dim).swapaxes(-3, -2)


def _rows_major(x):
    """``[..., H_kv, block, dh] -> [..., block, H_kv*dh]``: the inverse
    of ``_heads_major``."""
    *lead, hkv, blk, dh = x.shape
    return x.swapaxes(-3, -2).reshape(*lead, blk, hkv * dh)


def storage_dtype(kv_dtype: str):
    if kv_dtype not in KV_DTYPES:
        raise ValueError(f"kv_dtype {kv_dtype!r} not in {KV_DTYPES}")
    return {"f32": jnp.float32, "bf16": jnp.bfloat16,
            "int8": jnp.int8}[kv_dtype]


def kv_bytes_per_token(kv_dtype: str, n_layers: int, kv_heads: int,
                       head_dim: int, latent: bool = False,
                       v_head_dim: int = 0) -> float:
    """Stored KV bytes per cached token position — the roofline's
    ``kv_bytes`` knob. int8 adds the amortized per-block scale pair
    (negligible; counted as 0 here, the bench reports block overheads
    separately). A ``latent`` row is one vector of ``head_dim`` lanes,
    not a K/V pair; ``v_head_dim`` > 0 is a value head's lanes where
    they are not a key head's."""
    per_elt = {"f32": 4, "bf16": 2, "int8": 1}[kv_dtype]
    lanes = head_dim if latent else head_dim + (v_head_dim or head_dim)
    return n_layers * kv_heads * lanes * per_elt


def pool_bytes(pool: PagedKV) -> tuple[int, int]:
    """``(kv_bytes, scale_bytes)`` actually held by the pool arrays —
    the device-side truth ``decode_static_report`` cross-checks against
    the roofline's hand prediction (``kv_bytes_per_token * n_blocks *
    block_size``; the two MUST agree exactly, or the roofline prices a
    layout the engine doesn't run)."""
    kv = int(pool.k.nbytes) + int(pool.v.nbytes)
    sc = (0 if pool.k_scale is None
          else int(pool.k_scale.nbytes) + int(pool.v_scale.nbytes))
    return kv, sc


def init_pool(n_layers: int, n_blocks: int, kv_heads: int,
              block_size: int, head_dim: int, kv_dtype: str = "f32",
              latent_rank: int = 0, v_head_dim: int = 0) -> PagedKV:
    """Zero-filled pool. ``n_blocks`` includes the reserved scratch
    block, so at least 2 are required for any real sequence.
    ``v_head_dim`` > 0: the V side's row is ``kv_heads * v_head_dim``
    lanes (``KVRow``)."""
    if n_blocks < 2:
        raise ValueError(f"n_blocks must be >= 2 (block {SCRATCH_BLOCK} "
                         f"is the reserved scratch block), got {n_blocks}")
    shape = (n_layers, n_blocks, block_size, kv_heads * head_dim)
    dt = storage_dtype(kv_dtype)
    if latent_rank:
        if kv_dtype == "int8":
            raise ValueError("kv_dtype int8 is not served for a latent "
                             "cache: its scales are per KV head, and a "
                             "latent row has none")
        return PagedKV(k=jnp.zeros(shape, dt),
                       v=jnp.zeros(shape[:3] + (0,), dt), k_scale=None,
                       v_scale=None, head_dim=head_dim,
                       latent_rank=latent_rank)

    def scale():
        # distinct arrays per field: the engine donates the whole pool
        # into its compiled steps, and XLA rejects donating one buffer
        # through two arguments
        return (jnp.zeros((n_layers, n_blocks, kv_heads), jnp.float32)
                if kv_dtype == "int8" else None)

    v_shape = shape[:3] + (kv_heads * (v_head_dim or head_dim),)
    return PagedKV(k=jnp.zeros(shape, dt), v=jnp.zeros(v_shape, dt),
                   k_scale=scale(), v_scale=scale(), head_dim=head_dim,
                   v_head_dim=v_head_dim)


def _quantize(x: jax.Array, valid: jax.Array):
    """Symmetric int8 quantization of one (or a batch of) blocks.
    ``x [..., block, dh]`` f32, ``valid [..., block]`` bool row mask.
    Returns ``(q int8, scale [...])`` with ``scale = amax/127`` over the
    valid rows; an all-invalid (or all-zero) block gets scale 0 and
    zero codes."""
    masked = jnp.where(valid[..., None], jnp.abs(x), 0.0)
    amax = jnp.max(masked, axis=(-2, -1))
    scale = amax / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)[..., None, None]
    q = jnp.clip(jnp.round(x / safe), -127, 127).astype(jnp.int8)
    q = jnp.where((scale > 0)[..., None, None], q, jnp.int8(0))
    return q, scale


def _dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    """``x_hat = q * scale``; ``q [..., block, dh]``, ``scale [...]``."""
    return q.astype(jnp.float32) * scale[..., None, None]


def write_rows(pool: PagedKV, layer: int, phys: jax.Array,
               off: jax.Array, k_new: jax.Array, v_new: jax.Array,
               kv_dtype: str) -> PagedKV:
    """Scatter ``N`` new KV rows into the pool: row ``i`` lands at
    ``(layer, phys[i], off[i], :)``. ``k_new [N, H_kv, dh]``, ``v_new
    [N, H_kv, dv]`` f32 (each side's lanes a head are its operand's).
    For f32/bf16 this is one masked-free scatter; for int8 each touched
    block is read back, dequantized, re-quantized over its valid
    rows ``0..off[i]`` (blocks fill in order, so everything at or below
    the newest offset is live) and written whole. Duplicate ``phys``
    entries are only ever the scratch block (padded bucket rows) — last
    writer wins there, and nothing reads it unmasked."""
    n = off.shape[0]
    # "requant" tags the KV write in traces/HLO (utils/trace_analysis
    # SCOPES: decode/requant, prefill/requant). At f32/bf16 the region
    # is the plain scatter; the name stays "requant" because the int8
    # read-modify-requantize is the cost the attribution exists to
    # separate — the cheap dtypes show the region near zero.
    if kv_dtype != "int8":
        dt = pool.k.dtype
        idx = (layer, phys, off)        # the layer rides in the indices
        with jax.named_scope("requant"):
            return pool._replace(
                k=pool.k.at[idx].set(k_new.reshape(n, -1).astype(dt)),
                v=pool.v.at[idx].set(v_new.reshape(n, -1).astype(dt)))
    # int8: read-modify-requantize the touched blocks, head-major (the
    # quantizer's scales are per (block, head))
    blk, hkv = pool.block_size, pool.kv_heads
    rows = jnp.arange(blk)
    valid = rows[None, :] <= off[:, None]               # [N, block]
    valid = jnp.broadcast_to(valid[:, None, :], (n, hkv, blk))

    def requant(pool_side, scale_side, new):
        old = _dequantize(                              # [N, Hkv, blk, dh]
            _heads_major(pool_side[layer, phys], new.shape[-1]),
            scale_side[layer, phys])
        ins = rows[None, None, :, None] == off[:, None, None, None]
        cur = jnp.where(ins, new[:, :, None, :], old)
        q, scale = _quantize(cur, valid)
        return (pool_side.at[layer, phys].set(_rows_major(q)),
                scale_side.at[layer, phys].set(scale))

    with jax.named_scope("requant"):
        k, ks = requant(pool.k, pool.k_scale, k_new)
        v, vs = requant(pool.v, pool.v_scale, v_new)
    return pool._replace(k=k, v=v, k_scale=ks, v_scale=vs)


def ring_positions(last, entries: int, block: int) -> jax.Array:
    """The global position each stored row of a window table holds once
    the row at position ``last`` is written: ``last [...] -> [...,
    entries * block]``. Entry ``e`` holds the block ``j = jc - ((jc -
    e) mod entries)``, ``jc = last // block`` the block being written
    (the newest block congruent to ``e``), so its row ``o`` is position
    ``j * block + o``: negative where the entry was never written,
    beyond ``last`` where the row is still a stale one of the block's
    last use (``models/attention.py::window_mask`` hides both)."""
    jc = jnp.asarray(last)[..., None] // block
    j = jc - (jc - jnp.arange(entries)) % entries           # [..., entries]
    pos = j[..., None] * block + jnp.arange(block)
    return pos.reshape(*pos.shape[:-2], entries * block)


def ring_start(last, window: int, aligned: bool):
    """The first position a row at position ``last`` attends over in a
    window layer: ``last - window + 1`` under the sliding rule
    (``models/attention.py::window_mask``), the start of ``last``'s own
    multiple-of-``window`` window under the aligned one
    (``aligned_mask``), never below 0. Both rules give ONE range
    ``[start, last]``, which is what the walk and the engine's count of
    the blocks it fetches take; numpy or jax arrays alike."""
    return ((last - last % window) if aligned
            else (last - window + 1)).clip(0)


def write_chunk(pool: PagedKV, layer: int, table: jax.Array, pos0,
                k_new: jax.Array, v_new: jax.Array,
                kv_dtype: str, ring: bool = False) -> PagedKV:
    """Write one sequence's prefill chunk: ``k_new/v_new [C, H_kv, dh]``
    f32 at global positions ``pos0 .. pos0+C-1`` through ``table
    [max_blocks]``. The engine's power-of-two chunk buckets never
    straddle a block boundary (chunk starts are multiples of the chunk
    size and ``block_size`` is a power of two >= or <= every bucket), so
    a chunk either part-fills exactly one block (``C < block``) or
    covers ``C/block`` whole blocks — the two static cases below.
    ``ring``: ``table`` is a window layer's short table, used as a ring
    (block ``j`` lies in entry ``j mod len(table)``)."""
    c = k_new.shape[0]
    blk = pool.block_size
    positions = pos0 + jnp.arange(c)
    entry = positions // blk
    phys = table[entry % table.shape[0] if ring else entry]
    off = positions % blk
    if kv_dtype != "int8" or c < blk:
        # int8 c<blk touches ONE block; write_rows' per-row requant
        # converges because every row shares (phys, valid-hi) — requant
        # once with all rows inserted
        if kv_dtype == "int8":
            return _int8_partial_chunk(pool, layer, phys[0], off, k_new,
                                       v_new)
        return write_rows(pool, layer, phys, off, k_new, v_new, kv_dtype)
    # int8, whole blocks: quantize each block outright (no old content)
    if c % blk:
        raise ValueError(f"chunk {c} > block {blk} must be a whole "
                         "multiple (power-of-two buckets guarantee it)")
    nb = c // blk
    hkv = pool.kv_heads
    blocks = table[pos0 // blk + jnp.arange(nb)]        # [nb]
    valid = jnp.ones((nb, hkv, blk), bool)

    def quant_whole(pool_side, scale_side, new):
        shaped = new.reshape(nb, blk, hkv, -1).transpose(0, 2, 1, 3)
        q, scale = _quantize(shaped, valid)
        return (pool_side.at[layer, blocks].set(_rows_major(q)),
                scale_side.at[layer, blocks].set(scale))

    with jax.named_scope("requant"):
        k, ks = quant_whole(pool.k, pool.k_scale, k_new)
        v, vs = quant_whole(pool.v, pool.v_scale, v_new)
    return pool._replace(k=k, v=v, k_scale=ks, v_scale=vs)


def _int8_partial_chunk(pool: PagedKV, layer: int, phys, off: jax.Array,
                        k_new: jax.Array, v_new: jax.Array) -> PagedKV:
    """int8 chunk write confined to ONE block (``C < block``): read the
    block, dequantize, insert the ``C`` rows at ``off``, re-quantize
    over rows ``0..max(off)``."""
    blk = pool.block_size
    hkv = pool.kv_heads
    rows = jnp.arange(blk)
    valid_hi = off[-1]                                  # fills in order
    valid = jnp.broadcast_to((rows <= valid_hi)[None, :], (hkv, blk))
    hit = jnp.zeros((blk,), bool).at[off].set(True)

    def requant(pool_side, scale_side, new):
        dh = new.shape[-1]
        old = _dequantize(                              # [Hkv, blk, dh]
            _heads_major(pool_side[layer, phys], dh),
            scale_side[layer, phys])
        # insert row c at offset off[c] (offsets are distinct)
        upd = jnp.zeros((blk, hkv, dh), new.dtype).at[off].set(new)
        cur = jnp.where(hit[None, :, None], upd.transpose(1, 0, 2), old)
        q, scale = _quantize(cur, valid)
        return (pool_side.at[layer, phys].set(_rows_major(q)),
                scale_side.at[layer, phys].set(scale))

    with jax.named_scope("requant"):
        k, ks = requant(pool.k, pool.k_scale, k_new)
        v, vs = requant(pool.v, pool.v_scale, v_new)
    return pool._replace(k=k, v=v, k_scale=ks, v_scale=vs)


def scrub_blocks(pool: PagedKV, blocks) -> PagedKV:
    """Zero the named physical blocks (values AND int8 scales) —
    factory-fresh state, as if never written. The engine runs this when
    a QUARANTINED sequence releases its blocks: a poisoned cache may
    hold NaN/Inf, and a non-finite stale byte is the one thing the
    length/causal mask cannot neutralize (``0.0 * nan == nan`` inside
    the attention ``p @ v`` reduction — finite stale bytes contribute
    exact zeros, non-finite ones poison the whole row). Scrubbing also
    restores the int8 invariant that a block's bytes are a pure
    function of its own sequence's write history, so a retried request
    re-quantizes against the same zero state an uninterrupted run saw.
    Normal releases (finished/preempted sequences) skip the scrub —
    their stale bytes are finite and masked-exact — except for blocks
    the chaos layer marked corrupted, which the engine scrubs on ANY
    release (an eviction can precede the dispatch that would have
    flagged the NaN)."""
    blocks = jnp.asarray(blocks, jnp.int32)
    z = jnp.zeros((), pool.k.dtype)
    out = pool._replace(k=pool.k.at[:, blocks].set(z),
                        v=pool.v.at[:, blocks].set(z))
    if pool.k_scale is not None:
        out = out._replace(k_scale=pool.k_scale.at[:, blocks].set(0.0),
                          v_scale=pool.v_scale.at[:, blocks].set(0.0))
    return out


def copy_block(pool: PagedKV, src, dst) -> PagedKV:
    """Copy one physical block's bytes (values AND int8 scales) from
    ``src`` to ``dst`` across every layer — the device half of
    copy-on-write (``decode/engine.py``): a sequence about to write
    into a block it shares takes a private bit-identical copy first,
    so the write history every sharer observes stays exactly the
    unshared engine's. ``src``/``dst`` may be traced scalars (one
    compiled copy program serves every block pair)."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    out = pool._replace(k=pool.k.at[:, dst].set(pool.k[:, src]),
                        v=pool.v.at[:, dst].set(pool.v[:, src]))
    if pool.k_scale is not None:
        out = out._replace(
            k_scale=pool.k_scale.at[:, dst].set(pool.k_scale[:, src]),
            v_scale=pool.v_scale.at[:, dst].set(pool.v_scale[:, src]))
    return out


def copy_block_rows(pool: PagedKV, src, dst, n_rows) -> PagedKV:
    """Row-masked ``copy_block``: copy only the first ``n_rows`` token
    rows of ``src`` into ``dst`` (rows past the mask are zeroed, the
    scrubbed-free-block state a fresh prefill expects) — the device
    half of SUB-BLOCK prefix sharing. A partial radix hit clones just
    the shared prefix rows into a private block and the borrower's
    prefill resumes past them, so sharing no longer quantizes to whole
    blocks. The int8 per-block SCALES copy whole: they freeze at share
    time exactly as whole-block sharing froze them (a per-row slice of
    a per-block scale does not exist), which is why the borrowed rows
    stay bit-identical to the donor's bytes rather than to an unshared
    re-prefill. All three operands may be traced scalars — one
    compiled program serves every (src, dst, rows) triple."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    n = jnp.asarray(n_rows, jnp.int32)
    mask = (jnp.arange(pool.block_size) < n)[None, :, None]
    z = jnp.zeros((), pool.k.dtype)
    out = pool._replace(
        k=pool.k.at[:, dst].set(jnp.where(mask, pool.k[:, src], z)),
        v=pool.v.at[:, dst].set(jnp.where(mask, pool.v[:, src], z)))
    if pool.k_scale is not None:
        out = out._replace(
            k_scale=pool.k_scale.at[:, dst].set(pool.k_scale[:, src]),
            v_scale=pool.v_scale.at[:, dst].set(pool.v_scale[:, src]))
    return out


def extract_blocks(pool: PagedKV, blocks) -> dict:
    """Host-side copy of the named physical blocks' bytes — the export
    half of the single-sequence KV handoff (``decode/fleet.py``):
    ``k``/``v`` come back ``[L, n, H_kv, block, dh]`` (``v``: the
    row's ``v_dim`` lanes a head) numpy arrays AT
    THE STORAGE DTYPE (int8 codes stay int8 — the import must not
    round-trip through f32, or the bit-exactness contract dies at the
    requantization boundary), ``k_scale``/``v_scale`` ``[L, n, H_kv]``
    f32 (None unless int8). A plain eager gather + device->host
    readback: export rides the host, never the compiled program set —
    and the stored rows become the document's head-major blocks here, on
    the host (a byte shuffle; no wire or snapshot format knows the
    stored form)."""
    import numpy as np
    idx = np.asarray(blocks, np.int32)

    def doc(side, lanes):
        return np.ascontiguousarray(
            _heads_major(np.asarray(side[:, idx]), lanes))

    row = pool.row
    out = {"k": doc(pool.k, row.k_dim), "v": doc(pool.v, row.v_dim),
           "k_scale": None, "v_scale": None}
    if pool.k_scale is not None:
        out["k_scale"] = np.asarray(pool.k_scale[:, idx])
        out["v_scale"] = np.asarray(pool.v_scale[:, idx])
    return out


def implant_block(pool: PagedKV, dst, k_blk, v_blk,
                  k_scale=None, v_scale=None) -> PagedKV:
    """Write one imported block's bytes (values AND int8 scales) at
    physical block ``dst`` across every layer — the import half of the
    KV handoff. ``k_blk``/``v_blk`` are ``[L, H_kv, block, dh]`` (the
    document's form) in the pool's storage dtype, re-formed into stored
    rows here; ``dst`` may be a traced scalar, so ONE compiled implant
    program (donated, like the step programs) serves every destination
    block — importing never recompiles."""
    dst = jnp.asarray(dst, jnp.int32)
    out = pool._replace(k=pool.k.at[:, dst].set(_rows_major(k_blk)),
                        v=pool.v.at[:, dst].set(_rows_major(v_blk)))
    if pool.k_scale is not None:
        out = out._replace(k_scale=pool.k_scale.at[:, dst].set(k_scale),
                           v_scale=pool.v_scale.at[:, dst].set(v_scale))
    return out


def corrupt_block(pool: PagedKV, block: int) -> PagedKV:
    """Chaos injection (``corrupt_block@s:block``): poison one physical
    block the way a flipped HBM page would — NaN values for the float
    dtypes; NaN per-block SCALES under int8 (int8 codes have no NaN, so
    corruption surfaces through the dequantize multiply). Any sequence
    whose table names the block reads NaN through its gather and fails
    the per-row logits guardrail at its next step — masked positions
    offer no shelter (``0.0 * nan == nan`` in the attention ``p @ v``
    reduction, the same arithmetic ``scrub_blocks`` exists for). A
    corrupted FREE block is caught by the next request that reserves
    it: quarantined once, scrubbed on release, clean on retry."""
    if not 0 <= block < pool.n_blocks:
        raise ValueError(f"block {block} outside pool "
                         f"[0, {pool.n_blocks})")
    if pool.k_scale is not None:
        bad = jnp.asarray(jnp.nan, jnp.float32)
        return pool._replace(k_scale=pool.k_scale.at[:, block].set(bad),
                             v_scale=pool.v_scale.at[:, block].set(bad))
    bad = jnp.asarray(jnp.nan, pool.k.dtype)
    return pool._replace(k=pool.k.at[:, block].set(bad),
                         v=pool.v.at[:, block].set(bad))


def gathered_decode_attn(pool: PagedKV, layer: int, q: jax.Array,
                         tables: jax.Array, lengths: jax.Array,
                         window: int = 0, aligned: bool = False,
                         stats: bool = False, sink=None):
    """The PLAIN decode-side read: single-query attention for one layer
    over a gather of every row's whole table, the rows AS STORED — what
    ``stored_decode_attn`` runs for the pools that do not take the walk
    (``walks``), and what the tests hold the walk to. ``q [B, H,
    dh]`` f32, ``tables [B, MB]`` int32, ``lengths [B]`` attendable
    positions; returns ``[B, H, dv]`` f32 (``dv`` the row's ``v_dim``:
    ``dh`` unless the pool says otherwise; a latent pool:
    ``_latent_decode_attn``, the same gather with the one row on both
    sides of it). ``sink [H]`` f32: each
    head's sink, one more term of its softmax's denominator with no
    value row (``models/attention.py::softmax_stats``; the statistics
    count it). ``window`` > 0: ``tables``
    are window layers' short tables, used as rings, and a row attends
    over its last ``window`` positions (``ring_positions``), or, where
    ``aligned``, over the positions of its own multiple-of-``window``
    window up to itself. ``stats``: ``(y, m [B, H], l [B, H])``, the
    result beside each head's score maximum and its sum of ``exp(s -
    m)`` (``join_reads``). The same
    mathematics as the oracle ``decode_attn(q,
    *vmap(gather_layer), lengths)`` — same mask, scale and f32 softmax —
    as two matrix products over ``[B, T_cap, H_kv*dh]`` in the pool's
    dtype, accumulated in f32:

    - the query is scattered into its KV head's lane group, ``qbd[b,
      k*dh+d, (k,g)] = q[b, (k,g), d]`` and zero elsewhere, so
      ``scores[b,h,t] = sum_j K[b,t,j] * qbd[b,j,h]`` runs over the
      whole stored row (one KV head: ``qbd`` is just ``q^T``);
    - ``full[b,h,j] = sum_t p[b,h,t] * V[b,t,j]``, of which head ``h``
      keeps its own KV head's ``dv`` lanes.

    The small operands (``qbd``, the probabilities) are brought to the
    rows' dtype — bf16 operands under f32 accumulation for a bf16 pool,
    f32 for an f32 one; int8 codes are exact in bf16 and their
    per-(block, KV-head) scales multiply the small side (the scores
    after the product, the probabilities before it). The oracle splits
    each row into heads in f32: on the chip a ``[B, T_cap, H_kv, dh]``
    f32 copy of the view, padded where ``dh`` < 128 lanes, and a
    matrix-vector product over it on the vector unit. Here nothing of
    the gathered view's size is written after the gather — no cast, no
    head split, no transpose of the cache, no dequantized copy
    (``tests/test_chip_compile.py`` pins the compiled program). Against
    the oracle: f32 pools to reduction order; otherwise to two bf16
    roundings of the small operands (``tests/test_paged_layout.py``
    states the bound). Stale bytes beyond ``lengths`` meet a
    probability that is exactly 0, as in the oracle (and a NaN there
    still poisons the row: ``corrupt_block``)."""
    if pool.latent_rank:
        return _latent_decode_attn(pool, layer, q, tables, lengths)
    b, h, dh = q.shape
    hkv, blk, dv = pool.kv_heads, pool.block_size, pool.row.v_dim
    g = h // hkv
    with jax.named_scope("gather"):
        layers = jnp.full_like(tables, layer)   # the layer rides in the indices
        k = pool.k[layers, tables].reshape(b, -1, hkv * dh)
        v = pool.v[layers, tables].reshape(b, -1, hkv * dv)
        if pool.k_scale is not None:
            # per-block scales -> per (query head, position): [B, H, T]
            def per_head(scale):
                s = scale[layers, tables].transpose(0, 2, 1)   # [B, Hkv, MB]
                return jnp.repeat(jnp.repeat(s, g, axis=1), blk, axis=2)
            ks, vs = per_head(pool.k_scale), per_head(pool.v_scale)
    dt = k.dtype if jnp.issubdtype(k.dtype, jnp.floating) else jnp.bfloat16
    with jax.named_scope("attn"):
        qt = q.reshape(b, hkv, g, dh).transpose(0, 1, 3, 2)
        qbd = jnp.where(jnp.eye(hkv, dtype=bool)[:, None, :, None],
                        qt[:, :, :, None, :], 0)        # [B, k, d, K, g]
        s = jnp.einsum("btj,bjh->bht", k.astype(dt),
                       qbd.reshape(b, hkv * dh, h).astype(dt),
                       preferred_element_type=jnp.float32)
        if pool.k_scale is not None:
            s = s * ks
        s = s / jnp.sqrt(jnp.asarray(dh, jnp.float32))
        from ..models.attention import (aligned_mask, softmax_stats,
                                        window_mask)
        if window:
            last = lengths - 1
            mask = (aligned_mask if aligned else window_mask)(
                last[:, None, None],
                ring_positions(last, tables.shape[1], blk)[:, None, :],
                window)
        else:
            mask = jnp.arange(k.shape[1]) < lengths[:, None, None]
        s = jnp.where(mask, s, jnp.float32(-1e30))
        if stats or sink is not None:
            p, m, l = softmax_stats(s, sink)    # [H] against [B, H]
        else:
            p = jax.nn.softmax(s, axis=-1)
        if pool.k_scale is not None:
            p = p * vs
        full = jnp.einsum("bht,btj->bhj", p.astype(dt), v.astype(dt),
                          preferred_element_type=jnp.float32)
        y = jnp.einsum("bkgkd->bkgd", full.reshape(b, hkv, g, hkv, dv))
    y = y.reshape(b, h, dv)
    return (y, m, l) if stats else y


def walks(pool: PagedKV, shards: int = 1) -> bool:
    """Whether the decode-side read of ``pool`` is the walk over each
    row's live blocks (``ops/kv_walk.py``) or the plain gather and two
    products (``gathered_decode_attn``). Decided from the pool's own kind,
    dtype and shape and from nothing else — no flag, field or
    environment variable, and no model's name; a window layer's ring is
    admitted under the same rule as the full kind's pool (the walk reads
    any table as a ring):

    - a float pool only: an int8 pool (``k_scale``) has per-block scales
      on the small side, which the kernel does not take;
    - on the chip, rows of whole 128-lane tiles in blocks of whole
      sublane tiles only (a ``--tp 4`` shard of GPT-2's row is 320
      lanes), and of MORE than one tile: a block of one-tile rows is 4
      KB, every block is a copy of its own, and a copy costs ~33 ns to
      issue whatever it moves, so the walk read 122 GB/s of live rows
      there where the plain read's one gather reads 188 (0.60 against
      0.39 ms a program of 64 rows x 2 layers at ONE KV head of 128
      lanes; at 512 lanes 0.71 against 2.95, at 1,024 2.07 against
      11.5, at 1,280 1.75 against 4.32: ``PERF.md`` section 6, PR 40).
      Where the two sides' rows differ (``KVRow``) each side's has to
      be such a row; a side of NO lanes (a latent pool's ``v``) is no
      operand of the kernel and has no say. The interpreter, off the
      chip, takes any width.

    ``shards``: the ways ``pool``'s rows are sharded over a mesh where
    the caller holds the whole pool (the engine, for its counters); a
    step program asks of the shard it is handed."""
    if pool.k_scale is not None:
        return False
    from ..ops import ssm
    if ssm._interpreted():
        return True
    sublanes = 32 // pool.k.dtype.itemsize
    return (all(lanes > ssm._LANES and lanes % ssm._LANES == 0
                for lanes in (pool.k.shape[-1] // shards,
                              pool.v.shape[-1] // shards) if lanes)
            and pool.block_size % sublanes == 0)


def stored_decode_attn(pool: PagedKV, layer: int, q: jax.Array,
                       tables: jax.Array, lengths: jax.Array,
                       window: int = 0, aligned: bool = False,
                       stats: bool = False, sink=None):
    """The decode-side programs' cache read (``decode``, ``mixed`` and
    ``verify``): single-query attention for one layer over the rows AS
    STORED. ``q [B, H, dh]`` f32, ``tables [B, MB]`` int32, ``lengths
    [B]`` attendable positions; returns ``[B, H, dv]`` f32, ``dv`` the
    row's ``v_dim``. A latent pool: ``q [B, H, m]`` is each head's query
    FOR the stored row, scaled by the model
    (``models/face.py::latent_qrow``), and the result is ``[B, H,
    latent_rank]``.
    ``sink [H]`` f32: each head's sink, a term of the softmax's
    denominator with no value row. One contract, met by the walk
    over each row's live blocks where the pool takes it (``walks``) and
    by the plain form, ``gathered_decode_attn``, where it does not.
    ``window`` > 0: ``tables`` are window layers' short tables, used as
    rings, and a row attends over its last ``window`` positions or,
    where ``aligned``, over those of its own multiple-of-``window``
    window up to itself. ``stats``: ``(y, m [B, H], l [B, H])`` from
    either form, for ``join_reads``.

    The walk (``ops/kv_walk.py::walk_attn``) runs the same two products
    over the rows as stored, block by block where they lie, under an
    online float32 softmax: the query laid out for the stored row (zero
    outside its KV head's lanes) and the probabilities in the pool's
    dtype, sums in float32, head ``h`` keeping its KV head's ``dv``
    lanes of the result (the V side's row; a sink is where the running
    maximum and sum START, no column and no copy). It is handed each
    row's range of positions,
    ``[ring_start, lengths)`` for a ring and ``[0, lengths)`` otherwise
    (this is the one place on the path that knows the two window rules),
    and reads any table as a ring. Against the oracle: an f32 pool to
    reduction order, a bf16 pool to the two roundings of the small
    operands, as the plain form. What differs: only the blocks that
    hold a position of the range are read, so a NaN in a DEAD block of
    a row's table no longer reaches it; one inside a live block, before
    the range's start or beyond ``lengths`` (a ring's stale rows of that
    entry's last use), still does (``corrupt_block``).

    A latent pool's walk is ONE-SIDED: the row is key and value both
    (``v`` has no lanes and is no operand of the kernel), so each live
    block is fetched once and both products run over it. The query is
    already laid out for the row, one KV head, and scaled; the kernel
    hands back the heads' sums over the WHOLE stored row, ``f32[B, H,
    m]``, as for every other kind, and the lanes beyond ``latent_rank``
    (the rotary lanes' sums, the filling's zeros) are dropped here."""
    if not walks(pool):
        return gathered_decode_attn(pool, layer, q, tables, lengths, window,
                                    aligned, stats, sink)
    from ..ops.kv_walk import walk_attn
    if pool.latent_rank:
        with jax.named_scope("attn"):
            full = walk_attn(pool.k, pool.v, layer, q.astype(pool.k.dtype),
                             tables, jnp.zeros_like(lengths), lengths, 1.0)
        return full[..., :pool.latent_rank]
    b, h, dh = q.shape
    hkv, dv = pool.kv_heads, pool.row.v_dim
    g = h // hkv
    starts = (ring_start(lengths - 1, window, aligned) if window
              else jnp.zeros_like(lengths))
    with jax.named_scope("attn"):
        # ``rows[b, (K,g), (k,d)] = q[b, (K,g), d]`` where ``k == K``
        rows = jnp.where(jnp.eye(hkv, dtype=bool)[:, None, :, None],
                         q.reshape(b, hkv, g, 1, dh), 0)
        full = walk_attn(pool.k, pool.v, layer,
                         rows.reshape(b, h, hkv * dh).astype(pool.k.dtype),
                         tables, starts, lengths, dh ** -0.5, stats, sink)
        if stats:
            full, m, l = full
        y = jnp.einsum("bkgkd->bkgd", full.reshape(b, hkv, g, hkv, dv))
    y = y.reshape(b, h, dv)
    return (y, m, l) if stats else y


def _latent_decode_attn(pool: PagedKV, layer: int, q: jax.Array,
                        tables: jax.Array, lengths: jax.Array) -> jax.Array:
    """The PLAIN form of ``stored_decode_attn`` over latent rows (what
    a pool that does not take the walk runs, a one-tile row on the chip,
    and what the tests hold the one-sided walk to): ``q [B, H, m]`` is
    each head's query FOR the stored row, scaled by the model
    (``models/face.py::latent_qrow``); returns ``[B, H, latent_rank]``.
    ONE gather of every row's whole table, and both products run over
    the whole row as stored —
    ``s[b,h,t] = rows[b,t,:] . q[b,h,:]`` and ``full[b,h,:] = sum_t
    p[b,h,t] rows[b,t,:]``, of which the first ``latent_rank`` lanes are
    the result (the rotary lanes ride along: an eighth more MXU work,
    and no slice of the gathered view is ever written). The small
    operands take the rows' dtype, sums are float32."""
    b, h, m = q.shape
    with jax.named_scope("gather"):
        layers = jnp.full_like(tables, layer)
        rows = pool.k[layers, tables].reshape(b, -1, m)
    dt = rows.dtype
    with jax.named_scope("attn"):
        s = jnp.einsum("btj,bhj->bht", rows, q.astype(dt),
                       preferred_element_type=jnp.float32)
        mask = jnp.arange(rows.shape[1]) < lengths[:, None, None]
        p = jax.nn.softmax(jnp.where(mask, s, jnp.float32(-1e30)), axis=-1)
        full = jnp.einsum("bht,btj->bhj", p.astype(dt), rows,
                          preferred_element_type=jnp.float32)
    return full[..., :pool.latent_rank]


def _latent_chunk_attn(pool: PagedKV, layer: int, q: jax.Array,
                       table: jax.Array, pos0) -> jax.Array:
    """``gathered_chunk_attn`` over latent rows: ``q [C, H, m]`` at
    positions ``pos0 .. pos0+C-1`` of ONE sequence, causal over its
    rows, the same two products as the decode read over the slot's
    view in float32 (one slot's view is small, as in
    ``gathered_chunk_attn``). Returns ``[C, H, latent_rank]``."""
    from ..models.attention import causal_mask
    c, h, m = q.shape
    with jax.named_scope("gather"):
        rows = pool.k[jnp.full_like(table, layer), table].reshape(
            -1, m).astype(jnp.float32)
    with jax.named_scope("attn"):
        s = jnp.einsum("tj,chj->hct", rows, q)
        mask = causal_mask(c, rows.shape[0], q_offset=pos0)
        p = jax.nn.softmax(jnp.where(mask, s, jnp.float32(-1e30)), axis=-1)
        full = jnp.einsum("hct,tj->chj", p, rows)
    return full[..., :pool.latent_rank]


def gather_layer(pool: PagedKV, layer: int, table: jax.Array):
    """One sequence's dequantized contiguous KV view for one layer:
    ``table [max_blocks]`` -> ``(k [H_kv, T_cap, dh], v [H_kv, T_cap,
    dv])`` f32 (``T_cap = max_blocks * block``). The gather itself is
    ``models.attention.gather_paged_kv`` — the attention read against a
    block table; this wrapper only adds the dtype story. With
    ``decode_attn`` this is the ORACLE the tests hold
    ``stored_decode_attn`` to, in both its forms; in the engine only
    the prefill chunk reads through it (one slot's view), the decode
    side attends over the rows as stored."""
    from ..models.attention import gather_paged_kv
    # "gather" tags the block-table read + dequant in traces/HLO
    # (utils/trace_analysis SCOPES: decode/gather, prefill/gather) —
    # the paged-KV traffic term the DECODE roofline prices
    with jax.named_scope("gather"):
        k, v = gather_paged_kv(pool.k, pool.v, layer, table,
                               pool.head_dim, pool.v_head_dim)
        if pool.k_scale is None:
            if k.dtype != jnp.float32:
                k = k.astype(jnp.float32)
                v = v.astype(jnp.float32)
            return k, v
        blk = pool.block_size
        # per-block scales -> per-position: [MB, Hkv] -> [Hkv, MB*blk]
        ks = jnp.repeat(pool.k_scale[layer, table].T, blk, axis=1)
        vs = jnp.repeat(pool.v_scale[layer, table].T, blk, axis=1)
        return (k.astype(jnp.float32) * ks[..., None],
                v.astype(jnp.float32) * vs[..., None])


def gathered_chunk_attn(pool: PagedKV, layer: int, q: jax.Array,
                        table: jax.Array, pos0, window: int = 0,
                        aligned: bool = False, rows=None,
                        stats: bool = False, sink=None):
    """A prefill chunk's read: ``q [C, H, dh]`` at positions ``pos0 ..
    pos0+C-1`` of ONE sequence attends causally over its gathered view
    (``gather_layer`` + ``models.attention.chunk_attn``, the oracle's
    arithmetic: one slot's f32 head-split view is small). Returns
    ``[C, H, dv]`` (``dv`` the row's ``v_dim``). ``sink [H]``: each
    head's sink, as in the decode-side read. ``window`` > 0: ``table``
    is a window layer's short table, a ring the chunk's rows were just written into, and each row
    sees the last ``window`` positions up to its own (the rows of one
    chunk have different window starts), or, where ``aligned``, the
    positions of its own multiple-of-``window`` window up to its own.
    ``rows`` (a scalar): the view is no sequence of positions but the
    first ``rows`` stored rows, every query seeing them all (a chunked
    layer's summaries). ``stats``: ``(y, m [C, H], l [C, H])``
    (``join_reads``)."""
    from ..models.attention import aligned_mask, chunk_attn, window_mask
    if pool.latent_rank:
        return _latent_chunk_attn(pool, layer, q, table, pos0)
    ck, cv = gather_layer(pool, layer, table)
    mask = None
    if window:
        c = q.shape[0]
        mask = (aligned_mask if aligned else window_mask)(
            (pos0 + jnp.arange(c))[:, None],
            ring_positions(pos0 + c - 1, table.shape[0],
                           pool.block_size)[None, :], window)
    elif rows is not None:
        mask = jnp.broadcast_to(jnp.arange(ck.shape[1]) < rows,
                                (q.shape[0], ck.shape[1]))
    with jax.named_scope("attn"):
        y = chunk_attn(q.transpose(1, 0, 2), ck, cv, pos0, mask, stats,
                       sink)
    if stats:
        y, m, l = y
        return y.transpose(1, 0, 2), m.T, l.T
    return y.transpose(1, 0, 2)


def join_reads(*reads):
    """Two (or more) reads of the same queries over disjoint sets of
    keys as ONE softmax over their union: each read is ``(y [..., H,
    dh], m [..., H], l [..., H])``, its own normalised result beside its
    score maximum and its sum of ``exp(s - m)`` (``stats=True`` of the
    reads above). With ``M = max_i m_i`` and ``w_i = l_i exp(m_i - M)``
    the joint result is ``sum_i w_i y_i / sum_i w_i``. A read over NO
    key (its mask hid every row: ``m`` is the mask's value, ``-1e30``)
    gets the weight ``exp(-1e30 - M) = 0`` exactly, and whatever it
    returned in ``y``'s place, a NaN among it, is dropped."""
    with jax.named_scope("attn.join"):
        top = functools.reduce(jnp.maximum, [m for _, m, _ in reads])
        ws = [l * jnp.exp(m - top) for _, m, l in reads]
        total = functools.reduce(jnp.add, ws)
        out = sum(jnp.where(w[..., None] > 0, w[..., None] * y, 0.0)
                  for (y, _, _), w in zip(reads, ws))
        return out / total[..., None]


def write_summaries(pool: PagedKV, wpool: PagedKV, layer: int, summarise,
                    ring_phys, tables, last, kv_dtype: str) -> PagedKV:
    """A chunked layer's summary rows (the module docstring), for ``N``
    writes that end at positions ``last [N]``: the ring block each ends
    in, ``ring_phys [N]``, is read AS STORED and summarised
    (``summarise(k_blk, v_blk) -> (ktilde, vtilde) [N, H_kv, dh]``, the
    model's), and row ``j = last // block`` of the sequence
    (``tables [N, MB]``: entry ``j // block``, offset ``j % block``)
    takes the pair where ``last`` is the LAST position of its chunk;
    every other write's lands in the scratch block."""
    blk = wpool.block_size
    with jax.named_scope("attn.summary"):
        kt, vt = summarise(wpool.k[layer, ring_phys],
                           wpool.v[layer, ring_phys])
        j = last // blk
        done = last % blk == blk - 1
        phys = jnp.where(done, tables[jnp.arange(j.shape[0]), j // blk],
                         SCRATCH_BLOCK)
        return write_rows(pool, layer, phys, j % blk, kt, vt, kv_dtype)
