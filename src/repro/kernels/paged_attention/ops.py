"""Public paged-attention ops (decode + ragged over the FPR block tables).

Two table layouts, one descriptor helper, three entry points:

  * ``tables.ndim == 2`` — the classic monolithic ``(B, M)`` table,
    treated as a single-shard ``(1, B, M)`` stack; the kernel's index
    arithmetic degenerates to ``b * M + m``, reproducing the
    pre-sharding behaviour bit for bit.
  * ``tables.ndim == 3`` — the device-native ``(W, Bs, M)`` per-worker
    shard stack (slot ``b`` at shard ``b % W``, row ``b // W``).  This
    is what :class:`~repro.serving.kv_cache.PagedKVCache` maintains; the
    kernels walk it directly, so no caller ever assembles a monolithic
    tensor on the host.

:func:`shard_descriptor` is the ONE place that dispatch lives — the
classic, sharded, pipelined and ragged entry points all normalize their
table argument through it (it used to be copy-pasted ndim branching in
each call site).

Entry points: :func:`paged_attention` (fused pool, optionally
pipelined), :func:`paged_attention_split` (the legacy split-K/V shim —
kept as the naive baseline the microbench compares against), and
:func:`ragged_paged_attention` + :func:`build_ragged_descriptor` (mixed
prefill + decode rows in one call; the descriptor is built host-side
from the scheduler batch's ``cu_q_lens`` / ``cu_kv_lens``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention.paged_attention import (
    QT, paged_attention_fused_fwd, paged_attention_fused_pipelined_fwd,
    paged_attention_fwd, ragged_fused_fwd)


def shard_descriptor(tables: jax.Array) -> tuple[jax.Array, int, int, int]:
    """Normalize a block table to ``(stack (W, Bs, M) int32, W, Bs, M)``.

    The single dispatch point for the W=1 / W>1 layouts (the branch used
    to be duplicated across the classic, MLA and sharded call sites).
    ``(B, M)`` becomes the degenerate single-shard stack ``(1, B, M)``.
    """
    if tables.ndim == 2:
        B, M = tables.shape
        return tables.astype(jnp.int32).reshape(1, B, M), 1, B, M
    if tables.ndim != 3:
        raise ValueError(f"block table must be (B, M) or (W, Bs, M), "
                         f"got shape {tables.shape}")
    W, Bs, M = tables.shape
    return tables.astype(jnp.int32), W, Bs, M


@functools.partial(jax.jit,
                   static_argnames=("window", "buffer_depth", "interpret"))
def paged_attention(q: jax.Array, kv_pool: jax.Array, tables: jax.Array,
                    lengths: jax.Array, *, window: int | None = None,
                    buffer_depth: int = 1,
                    interpret: bool = False) -> jax.Array:
    """Fused-KV paged decode.  q: (B, H, hd); kv_pool: (N, 2, KV, bs, hd)
    (K at index 0 and V at 1 of axis 1); tables: (B, M) or (W, Bs, M);
    lengths: (B,) → (B, H, hd).  ``buffer_depth >= 2`` takes the manual
    multi-depth DMA pipeline; output is bit-identical either way.
    Matches attention.paged_decode_attention_ref on the split views."""
    B, H, hd = q.shape
    KV = kv_pool.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    stack, _, _, _ = shard_descriptor(tables)
    if buffer_depth >= 2:
        o = paged_attention_fused_pipelined_fwd(
            qg, kv_pool, stack, lengths.astype(jnp.int32), window=window,
            buffer_depth=buffer_depth, interpret=interpret)
    else:
        o = paged_attention_fused_fwd(
            qg, kv_pool, stack, lengths.astype(jnp.int32), window=window,
            interpret=interpret)
    return o.reshape(B, H, hd)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention_split(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                          tables: jax.Array, lengths: jax.Array, *,
                          window: int | None = None,
                          interpret: bool = False) -> jax.Array:
    """Legacy split-K/V decode shim (two DMA descriptors per block).

    Kept as the *naive* baseline for the DMA-vs-compute sweep and the
    fused-vs-split differential tests; new callers should store the pool
    fused and use :func:`paged_attention`.  k_pool/v_pool: (N, KV, bs,
    hd), the two halves ``kv_pool[:, 0]`` / ``kv_pool[:, 1]`` of a fused
    pool."""
    B, H, hd = q.shape
    KV = k_pool.shape[1]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    stack, _, _, _ = shard_descriptor(tables)
    o = paged_attention_fwd(qg, k_pool, v_pool, stack,
                            lengths.astype(jnp.int32),
                            window=window, interpret=interpret)
    return o.reshape(B, H, hd)


def build_ragged_descriptor(slot_ids, q_lens, q_starts, kv_lens, *,
                            num_slots: int, t_cap: int) -> dict:
    """Host-side (NumPy) ragged descriptor for one mixed engine step.

    ``slot_ids[i]`` is the batch slot of active row i, contributing
    ``q_lens[i]`` query tokens starting at global position
    ``q_starts[i]`` with ``kv_lens[i]`` total kv tokens visible after
    its writes land (decode rows: q_len 1, q_start length-1, kv_len
    length).  Rows are packed in order into a ``t_cap``-token buffer,
    each segment padded to a multiple of :data:`QT` so query tiles never
    span rows.

    Returns int32 NumPy arrays: ``cu_q_lens``/``cu_kv_lens`` (rows+1,)
    exclusive prefix sums of the *real* token counts, ``tile_row``/
    ``tile_pos`` (t_cap // QT,) per-tile batch slot (-1 = padding tile)
    and first-query position, ``token_row``/``token_pos`` (t_cap,)
    per-packed-token batch slot (-1 = padding) and global position,
    ``token_src`` (t_cap,) index into the concatenated real-token stream
    (-1 = padding), ``kv_lens`` (num_slots,) per-slot kv lengths and
    ``last_index`` (num_slots,) packed index of each slot's final real
    token (-1 = inactive slot).
    """
    if t_cap % QT:
        raise ValueError(f"t_cap {t_cap} not a multiple of QT={QT}")
    tiles_cap = t_cap // QT
    tile_row = np.full(tiles_cap, -1, np.int32)
    tile_pos = np.zeros(tiles_cap, np.int32)
    token_row = np.full(t_cap, -1, np.int32)
    token_pos = np.zeros(t_cap, np.int32)
    token_src = np.full(t_cap, -1, np.int32)
    kv = np.ones(num_slots, np.int32)        # >=1 keeps padded rows finite
    last_index = np.full(num_slots, -1, np.int32)
    cu_q = [0]
    cu_kv = [0]
    off = 0
    src = 0
    for slot, qn, start, kvn in zip(slot_ids, q_lens, q_starts, kv_lens):
        qn, start, kvn = int(qn), int(start), int(kvn)
        if qn <= 0:
            continue
        padded = -(-qn // QT) * QT
        if off + padded > t_cap:
            raise ValueError(
                f"ragged batch overflows t_cap={t_cap} "
                f"(need {off + padded})")
        for j in range(padded // QT):
            tile_row[off // QT + j] = slot
            tile_pos[off // QT + j] = start + j * QT
        token_row[off:off + qn] = slot
        token_pos[off:off + padded] = start + np.arange(padded)
        token_src[off:off + qn] = src + np.arange(qn)
        kv[slot] = kvn
        last_index[slot] = off + qn - 1
        cu_q.append(cu_q[-1] + qn)
        cu_kv.append(cu_kv[-1] + kvn)
        off += padded
        src += qn
    return {
        "cu_q_lens": np.asarray(cu_q, np.int32),
        "cu_kv_lens": np.asarray(cu_kv, np.int32),
        "tile_row": tile_row,
        "tile_pos": tile_pos,
        "token_row": token_row,
        "token_pos": token_pos,
        "token_src": token_src,
        "kv_lens": kv,
        "last_index": last_index,
    }


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def ragged_paged_attention(q: jax.Array, kv_pool: jax.Array,
                           tables: jax.Array, tile_row: jax.Array,
                           tile_pos: jax.Array, kv_lens: jax.Array, *,
                           window: int | None = None,
                           interpret: bool = False) -> jax.Array:
    """Ragged fused-KV attention over one packed mixed batch.

    q: (T, H, hd) packed queries (T a multiple of :data:`QT`); kv_pool:
    (N, 2, KV, bs, hd); tables: (B, M) or (W, Bs, M); tile_row/tile_pos:
    (T // QT,); kv_lens: per-slot kv lengths → (T, H, hd).  One call
    serves every chunked-prefill AND decode row of an engine step."""
    T, H, hd = q.shape
    KV = kv_pool.shape[2]
    G = H // KV
    if T % QT:
        raise ValueError(f"packed length {T} not a multiple of QT={QT}")
    tiles = T // QT
    # (T, H, hd) → one (KV, G*QT, hd) slab per query tile, row g*QT + t
    qg = q.reshape(tiles, QT, KV, G, hd).transpose(0, 2, 3, 1, 4).reshape(
        tiles, KV, G * QT, hd)
    stack, W, Bs, _ = shard_descriptor(tables)
    kv_lens = kv_lens.astype(jnp.int32)
    if kv_lens.shape[0] < W * Bs:
        kv_lens = jnp.pad(kv_lens, (0, W * Bs - kv_lens.shape[0]),
                          constant_values=1)
    o = ragged_fused_fwd(qg, kv_pool, stack,
                         tile_row.astype(jnp.int32),
                         tile_pos.astype(jnp.int32), kv_lens,
                         window=window, interpret=interpret)
    return o.reshape(tiles, KV, G, QT, hd).transpose(0, 3, 1, 2, 4).reshape(
        T, H, hd)


__all__ = ["paged_attention", "paged_attention_split",
           "ragged_paged_attention", "build_ragged_descriptor",
           "shard_descriptor", "QT"]
