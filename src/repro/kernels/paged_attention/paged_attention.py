"""Ragged fused-KV paged-attention Pallas TPU kernels — the FPR hot path.

Queries attend to a KV cache that lives in *physical blocks* of the FPR
pool addressed through per-sequence block tables (repro.core.block_table).
This is the TPU-native adaptation of the paper's translation layer: the
block table is the "page table", and the kernels walk it with **scalar
prefetch** — the table rows are SMEM scalars available to the BlockSpec
index maps, so each grid step DMAs exactly the physical block it needs
from HBM into VMEM.  Holes (non-resident / swapped blocks, table entry
< 0) are clamped in the index map and masked in the kernel, never
touched.  Four kernels share that walk:

  * ``paged_attention_fwd`` — the legacy split-KV decode kernel (separate
    ``(N, KV, bs, hd)`` K and V pools, two DMA descriptors per logical
    block).  Kept as the *naive* baseline the microbench sweep compares
    against.
  * ``paged_attention_fused_fwd`` — the fused-KV decode kernel.  The pool
    is ``(N, 2, KV, bs, hd)``: K and V of one block sit side by side on
    an untiled axis, so one logical block is ONE contiguous DMA — one
    translation covers twice the reach, the serving analogue of the
    large-reach TLBs in PAPERS.md — and the kernel reads K and V by a
    static index (``kv_ref[0, 0]`` / ``kv_ref[0, 1]``), never by a
    strided slice the TPU cannot lower.  Bit-identical to the split
    kernel: both hand the same ``(KV, bs, hd)`` tiles to one flash step.
  * ``paged_attention_fused_pipelined_fwd`` — the fused kernel with
    *manual multi-depth VMEM buffering*: the fused pool stays in
    ``pl.ANY`` memory and the kernel issues its own
    ``pltpu.make_async_copy`` per block into a revolving ``(depth, 2, KV,
    bs, hd)`` VMEM buffer, so block ``m + depth``'s copy overlaps block
    ``m``'s flash step.  ``buffer_depth`` (2/4) and the pool block size
    are the autotune knobs (see ``autotune.py``).
  * ``ragged_fused_fwd`` — ragged batching over the fused pool: mixed
    chunked-prefill rows and decode rows are packed into query tiles of
    ``QT`` tokens (tiles never span sequences) and served by ONE kernel
    call per step.  Each tile reaches the kernel as a ``(KV, G*QT, hd)``
    slab (row ``g * QT + t`` is query head ``g`` of token ``t``), so
    every score and value product is the same head-batched 3-D matmul
    as the decode kernel's.  The descriptor — derived from
    scalar-prefetched ``cu_q_lens`` / ``cu_kv_lens`` by
    ``ops.build_ragged_descriptor`` — maps each query tile to its batch
    slot and global position; causality, sequence length, sliding
    window and holes are all masked per (query, key) element.

**Shard-native tables.**  All kernels consume the block table in the
device's *sharded* layout: a ``(W, Bs, M)`` int32 stack of per-worker
shards, where batch slot ``b`` lives at shard ``b % W``, local row
``b // W`` (the interleaved slot layout of
``repro.core.block_table.BlockTableStore``).  The page walk indexes the
flattened stack directly — ``(b % W) * Bs * M + (b // W) * M + m`` — so
the serving cache hands its shard arrays straight to the kernel and a
scoped fence or an elastic reshard never pays an O(full-table) host-side
assemble.  The pre-sharding monolithic ``(B, M)`` layout is exactly the
``W = 1`` case (the index arithmetic degenerates to ``b * M + m``),
which is how the classic entry points in ``ops.py`` still work, bit for
bit.

Grids: ``(B, M)`` (decode) / ``(T // QT, M)`` (ragged) with the block
walk innermost and sequential; online-softmax state (m, l, acc) lives in
VMEM scratch across the walk.  Fully-invalid blocks (beyond the kv
length or outside the sliding window) are skipped with ``pl.when``, so
cost is proportional to the *resident* cache, not the table capacity.

Each ``pallas_call`` is named after its entry point in ``ops.py``
(``paged_attention``, ``paged_attention_split``,
``ragged_paged_attention``): the name a TPU profile gives the kernel's
operation, which the benchmark's roofline readers look up.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: query-tile height of the ragged kernel — packed rows are padded so a
#: tile never spans two sequences
QT = 8

#: the f32 score and value matmuls run at full f32 precision: at the
#: default, Mosaic rounds q and the probabilities to bf16 for one MXU
#: pass, a relative error of about 2e-3 per layer on a TPU v5e
_PRECISION = jax.lax.Precision.HIGHEST


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _table_index(b, m, *, W: int, Bs: int, M: int):
    """Flattened index of (slot b, logical block m) in the (W, Bs, M)
    shard stack: shard b % W, local row b // W.  W == 1 ⇒ b * M + m."""
    if W == 1:
        return b * M + m
    return (b % W) * (Bs * M) + (b // W) * M + m


def _check_fused(kv_pool: jax.Array, KV: int, hd: int) -> int:
    """Validate a ``(N, 2, KV, bs, hd)`` fused pool; return its ``bs``."""
    if (kv_pool.ndim != 5 or kv_pool.shape[1] != 2
            or kv_pool.shape[2] != KV or kv_pool.shape[4] != hd):
        raise ValueError(f"fused pool must be (N, 2, {KV}, bs, {hd}), "
                         f"got {kv_pool.shape}")
    return kv_pool.shape[3]


def _init_state(m_sc, l_sc, acc_sc):
    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)


def _finalize(o_ref, l_sc, acc_sc):
    out = acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
    o_ref[0] = out.astype(o_ref.dtype)


def _online_softmax(s, v, m_sc, l_sc, acc_sc):
    """Fold one masked ``(KV, R, bs)`` score block and its ``(KV, bs,
    hd)`` values into the running (m, l, acc) state."""
    m_prev = m_sc[...]                                # (KV, R, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                            # (KV, R, bs)
    scale = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * scale + p.sum(axis=-1, keepdims=True)
    acc_sc[...] = acc_sc[...] * scale + jnp.einsum(
        "krs,ksd->krd", p, v, precision=_PRECISION,
        preferred_element_type=jnp.float32)
    m_sc[...] = m_new


def _scores(q, k):
    """Head-batched ``(KV, R, hd) x (KV, bs, hd) -> (KV, R, bs)``."""
    hd = q.shape[-1]
    return jnp.einsum("krd,ksd->krs", q, k, precision=_PRECISION,
                      preferred_element_type=jnp.float32) * (hd ** -0.5)


def _flash_block_step(q, k, v, blk_start, length, window,
                      m_sc, l_sc, acc_sc):
    """One decode online-softmax step over a ``(KV, bs, hd)`` block.

    Shared verbatim by the split, fused and pipelined decode kernels —
    same tiles, same float ops in the same order, which is what makes
    the fused and pipelined paths *bit-identical* to the naive baseline.
    """
    s = _scores(q, k)                                 # (KV, G, bs)
    pos = blk_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    mask = pos < length
    if window is not None:
        mask = jnp.logical_and(mask, pos > length - 1 - window)
    _online_softmax(jnp.where(mask, s, NEG_INF), v, m_sc, l_sc, acc_sc)


def _block_visible(blk_start, length, bs, window):
    visible = blk_start < length
    if window is not None:
        visible = jnp.logical_and(visible, blk_start + bs > length - window)
    return visible


def _decode_scratch(KV, G, hd):
    return [pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32)]


# ---------------------------------------------------------------------------
# legacy split-KV decode kernel (the naive baseline: 2 DMAs per block)
# ---------------------------------------------------------------------------


def _pa_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
               m_sc, l_sc, acc_sc, *, bs: int, window: int | None,
               W: int, Bs: int, M: int):
    b = pl.program_id(0)
    mi = pl.program_id(1)
    length = lengths_ref[b]

    @pl.when(mi == 0)
    def _init():
        _init_state(m_sc, l_sc, acc_sc)

    blk_start = mi * bs
    resident = tables_ref[_table_index(b, mi, W=W, Bs=Bs, M=M)] >= 0

    @pl.when(jnp.logical_and(resident,
                             _block_visible(blk_start, length, bs, window)))
    def _step():
        q = q_ref[0].astype(jnp.float32)              # (KV, G, hd)
        k = k_ref[0].astype(jnp.float32)              # (KV, bs, hd)
        v = v_ref[0].astype(jnp.float32)              # (KV, bs, hd)
        _flash_block_step(q, k, v, blk_start, length, window,
                          m_sc, l_sc, acc_sc)

    @pl.when(mi == pl.num_programs(1) - 1)
    def _done():
        _finalize(o_ref, l_sc, acc_sc)


def paged_attention_fwd(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        shard_tables: jax.Array, lengths: jax.Array, *,
                        window: int | None = None,
                        interpret: bool = False) -> jax.Array:
    """q: (B, KV, G, hd); pools: (N, KV, bs, hd) — the K and V halves of
    a fused pool; shard_tables: (W, Bs, M) int32 interleaved shard stack
    (W*Bs >= B); lengths: (B,) int32 → (B, KV, G, hd)."""
    B, KV, G, hd = q.shape
    N, _, bs, _ = k_pool.shape
    W, Bs, M = shard_tables.shape
    if W * Bs < B:
        raise ValueError(f"shard stack covers {W * Bs} slots < batch {B}")

    def q_map(b, m, tables_ref, lengths_ref):
        return (b, 0, 0, 0)

    def kv_map(b, m, tables_ref, lengths_ref):
        # the page walk: physical block for logical block m of slot b,
        # read straight out of the interleaved shard stack
        idx = _table_index(b, m, W=W, Bs=Bs, M=M)
        return (jnp.maximum(tables_ref[idx], 0), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, M),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), q_map),
            pl.BlockSpec((1, KV, bs, hd), kv_map),
            pl.BlockSpec((1, KV, bs, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd), q_map),
        scratch_shapes=_decode_scratch(KV, G, hd),
    )
    kern = functools.partial(_pa_kernel, bs=bs, window=window,
                             W=W, Bs=Bs, M=M)
    return pl.pallas_call(
        kern,
        name="paged_attention_split",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(shard_tables.reshape(-1), lengths, q, k_pool, v_pool)


# ---------------------------------------------------------------------------
# fused-KV decode kernel: one (2, KV, bs, hd) block, ONE DMA per block
# ---------------------------------------------------------------------------


def _fused_kernel(tables_ref, lengths_ref, q_ref, kv_ref, o_ref,
                  m_sc, l_sc, acc_sc, *, bs: int, window: int | None,
                  W: int, Bs: int, M: int):
    b = pl.program_id(0)
    mi = pl.program_id(1)
    length = lengths_ref[b]

    @pl.when(mi == 0)
    def _init():
        _init_state(m_sc, l_sc, acc_sc)

    blk_start = mi * bs
    resident = tables_ref[_table_index(b, mi, W=W, Bs=Bs, M=M)] >= 0

    @pl.when(jnp.logical_and(resident,
                             _block_visible(blk_start, length, bs, window)))
    def _step():
        q = q_ref[0].astype(jnp.float32)              # (KV, G, hd)
        k = kv_ref[0, 0].astype(jnp.float32)          # (KV, bs, hd)
        v = kv_ref[0, 1].astype(jnp.float32)
        _flash_block_step(q, k, v, blk_start, length, window,
                          m_sc, l_sc, acc_sc)

    @pl.when(mi == pl.num_programs(1) - 1)
    def _done():
        _finalize(o_ref, l_sc, acc_sc)


def paged_attention_fused_fwd(q: jax.Array, kv_pool: jax.Array,
                              shard_tables: jax.Array, lengths: jax.Array, *,
                              window: int | None = None,
                              interpret: bool = False) -> jax.Array:
    """q: (B, KV, G, hd); fused pool: (N, 2, KV, bs, hd) (K at index 0,
    V at 1 of axis 1); shard_tables: (W, Bs, M); lengths: (B,) →
    (B, KV, G, hd).  Bit-identical to :func:`paged_attention_fwd` on
    ``kv_pool[:, 0]`` / ``kv_pool[:, 1]``."""
    B, KV, G, hd = q.shape
    bs = _check_fused(kv_pool, KV, hd)
    W, Bs, M = shard_tables.shape
    if W * Bs < B:
        raise ValueError(f"shard stack covers {W * Bs} slots < batch {B}")

    def q_map(b, m, tables_ref, lengths_ref):
        return (b, 0, 0, 0)

    def kv_map(b, m, tables_ref, lengths_ref):
        idx = _table_index(b, m, W=W, Bs=Bs, M=M)
        return (jnp.maximum(tables_ref[idx], 0), 0, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, M),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), q_map),
            pl.BlockSpec((1, 2, KV, bs, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd), q_map),
        scratch_shapes=_decode_scratch(KV, G, hd),
    )
    kern = functools.partial(_fused_kernel, bs=bs, window=window,
                             W=W, Bs=Bs, M=M)
    return pl.pallas_call(
        kern,
        name="paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(shard_tables.reshape(-1), lengths, q, kv_pool)


# ---------------------------------------------------------------------------
# fused-KV decode kernel with manual multi-depth DMA pipelining
# ---------------------------------------------------------------------------


def _fused_pipelined_kernel(tables_ref, lengths_ref, q_ref, kv_hbm_ref,
                            o_ref, m_sc, l_sc, acc_sc, buf, sem, *,
                            bs: int, window: int | None,
                            W: int, Bs: int, M: int, depth: int):
    """The fused kernel with the block walk's DMA issued by hand.

    The fused pool stays in ``ANY`` (HBM) memory; a revolving ``(depth,
    2, KV, bs, hd)`` VMEM buffer holds the next ``depth`` blocks in
    flight, so block ``mi + depth``'s copy overlaps block ``mi``'s flash
    step.  Copy starts/waits are balanced per sequence row: ``min(depth,
    nm)`` warm-up starts at ``mi == 0``, one wait + (if another block
    remains) one start per step.
    """
    b = pl.program_id(0)
    mi = pl.program_id(1)
    nm = pl.num_programs(1)
    length = lengths_ref[b]

    def copy(m, slot):
        phys = jnp.maximum(
            tables_ref[_table_index(b, m, W=W, Bs=Bs, M=M)], 0)
        return pltpu.make_async_copy(kv_hbm_ref.at[phys], buf.at[slot],
                                     sem.at[slot])

    @pl.when(mi == 0)
    def _init():
        _init_state(m_sc, l_sc, acc_sc)
        for j in range(min(depth, M)):                # warm-up fills
            copy(j, j).start()

    slot = jax.lax.rem(mi, depth)
    copy(mi, slot).wait()

    blk_start = mi * bs
    resident = tables_ref[_table_index(b, mi, W=W, Bs=Bs, M=M)] >= 0

    @pl.when(jnp.logical_and(resident,
                             _block_visible(blk_start, length, bs, window)))
    def _step():
        q = q_ref[0].astype(jnp.float32)              # (KV, G, hd)
        k = buf[slot, 0].astype(jnp.float32)          # (KV, bs, hd)
        v = buf[slot, 1].astype(jnp.float32)
        _flash_block_step(q, k, v, blk_start, length, window,
                          m_sc, l_sc, acc_sc)

    @pl.when(mi + depth < nm)
    def _prefetch_next():
        copy(mi + depth, slot).start()

    @pl.when(mi == nm - 1)
    def _done():
        _finalize(o_ref, l_sc, acc_sc)


def paged_attention_fused_pipelined_fwd(
        q: jax.Array, kv_pool: jax.Array, shard_tables: jax.Array,
        lengths: jax.Array, *, window: int | None = None,
        buffer_depth: int = 2, interpret: bool = False) -> jax.Array:
    """:func:`paged_attention_fused_fwd` with ``buffer_depth`` blocks of
    manual DMA pipelining.  Bit-identical output — pipelining only moves
    *when* bytes arrive in VMEM, never what the flash step computes."""
    B, KV, G, hd = q.shape
    bs = _check_fused(kv_pool, KV, hd)
    if buffer_depth < 1:
        raise ValueError(f"buffer_depth must be >= 1, got {buffer_depth}")
    W, Bs, M = shard_tables.shape
    if W * Bs < B:
        raise ValueError(f"shard stack covers {W * Bs} slots < batch {B}")

    def q_map(b, m, tables_ref, lengths_ref):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, M),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), q_map),
            pl.BlockSpec(memory_space=pl.ANY),        # whole fused pool
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd), q_map),
        scratch_shapes=_decode_scratch(KV, G, hd) + [
            pltpu.VMEM((buffer_depth, 2, KV, bs, hd), kv_pool.dtype),
            pltpu.SemaphoreType.DMA((buffer_depth,)),
        ],
    )
    kern = functools.partial(_fused_pipelined_kernel, bs=bs, window=window,
                             W=W, Bs=Bs, M=M, depth=buffer_depth)
    return pl.pallas_call(
        kern,
        name="paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(shard_tables.reshape(-1), lengths, q, kv_pool)


# ---------------------------------------------------------------------------
# ragged fused-KV kernel: mixed prefill + decode rows, one call per step
# ---------------------------------------------------------------------------


def _ragged_kernel(tables_ref, tile_row_ref, tile_pos_ref, kv_lens_ref,
                   q_ref, kv_ref, o_ref, m_sc, l_sc, acc_sc, *,
                   bs: int, window: int | None, W: int, Bs: int, M: int):
    t = pl.program_id(0)
    mi = pl.program_id(1)
    row = tile_row_ref[t]                             # batch slot, -1 = pad
    qpos0 = tile_pos_ref[t]                           # tile's first q pos
    slot = jnp.maximum(row, 0)
    kv_len = kv_lens_ref[slot]

    @pl.when(mi == 0)
    def _init():
        _init_state(m_sc, l_sc, acc_sc)

    blk_start = mi * bs
    resident = tables_ref[_table_index(slot, mi, W=W, Bs=Bs, M=M)] >= 0
    # causal upper bound: no query in this tile sees keys >= kv_len
    visible = jnp.logical_and(row >= 0, blk_start < kv_len)
    if window is not None:
        # lowest query of the tile reaches back to qpos0 - window + 1
        visible = jnp.logical_and(visible, blk_start + bs > qpos0 - window)

    @pl.when(jnp.logical_and(resident, visible))
    def _step():
        q = q_ref[0].astype(jnp.float32)              # (KV, G*QT, hd)
        k = kv_ref[0, 0].astype(jnp.float32)          # (KV, bs, hd)
        v = kv_ref[0, 1].astype(jnp.float32)
        s = _scores(q, k)                             # (KV, G*QT, bs)
        # row r holds query token r % QT of the tile (head group r // QT)
        qpos = qpos0 + jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 1), QT)
        kpos = blk_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        mask = jnp.logical_and(kpos <= qpos, kpos < kv_len)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        _online_softmax(jnp.where(mask, s, NEG_INF), v, m_sc, l_sc, acc_sc)

    @pl.when(mi == pl.num_programs(1) - 1)
    def _done():
        _finalize(o_ref, l_sc, acc_sc)


def ragged_fused_fwd(q: jax.Array, kv_pool: jax.Array,
                     shard_tables: jax.Array, tile_row: jax.Array,
                     tile_pos: jax.Array, kv_lens: jax.Array, *,
                     window: int | None = None,
                     interpret: bool = False) -> jax.Array:
    """Ragged fused-KV attention over packed query tiles.

    q: (T // QT, KV, G * QT, hd) — the packed queries, one ``(KV, G*QT,
    hd)`` slab per tile of :data:`QT` tokens, row ``g * QT + t`` holding
    query head ``g`` (of its KV group) of the tile's token ``t``; each
    row's segment is padded so tiles never span rows.  fused pool: (N,
    2, KV, bs, hd); shard_tables: (W, Bs, M); tile_row: (T // QT,) batch
    slot per tile (-1 = padding tile, skipped); tile_pos: (T // QT,)
    global position of each tile's first query; kv_lens: (W * Bs,) kv
    length per batch slot → same layout as ``q``.  Padded rows produce
    finite garbage (``NEG_INF`` is finite) and are dropped by the caller.
    """
    tiles, KV, R, hd = q.shape
    if R % QT:
        raise ValueError(f"tile rows {R} not a multiple of QT={QT}")
    bs = _check_fused(kv_pool, KV, hd)
    W, Bs, M = shard_tables.shape

    def q_map(t, m, tables_ref, tile_row_ref, tile_pos_ref, kv_lens_ref):
        return (t, 0, 0, 0)

    def kv_map(t, m, tables_ref, tile_row_ref, tile_pos_ref, kv_lens_ref):
        slot = jnp.maximum(tile_row_ref[t], 0)
        idx = _table_index(slot, m, W=W, Bs=Bs, M=M)
        return (jnp.maximum(tables_ref[idx], 0), 0, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(tiles, M),
        in_specs=[
            pl.BlockSpec((1, KV, R, hd), q_map),
            pl.BlockSpec((1, 2, KV, bs, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, KV, R, hd), q_map),
        scratch_shapes=_decode_scratch(KV, R, hd),
    )
    kern = functools.partial(_ragged_kernel, bs=bs, window=window,
                             W=W, Bs=Bs, M=M)
    return pl.pallas_call(
        kern,
        name="ragged_paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(shard_tables.reshape(-1), tile_row, tile_pos, kv_lens,
      q, kv_pool)
