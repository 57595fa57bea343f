"""Pure-jnp oracles for paged decode + ragged fused attention.

``paged_decode_attention_ref`` (re-exported from models) is the
monolithic split-pool oracle; ``paged_decode_attention_sharded_ref``
consumes the device-native ``(W, Bs, M)`` interleaved shard stack by
assembling the monolithic view *inside the traced graph* and deferring
to it; ``paged_decode_attention_fused_ref`` does the same for the
fused ``(N, 2, KV, bs, hd)`` pool by splitting it into token-major K and
V; and ``ragged_fused_ref`` is the oracle for the ragged kernel —
packed mixed prefill + decode query rows, per-element causal / length /
window / hole masking, any table layout.  The Pallas kernels must match
all of them on the same inputs (and the fused/pipelined kernels must
match the split kernel *bit for bit* — fusing is a pure permutation).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import (NEG_INF, assemble_shard_tables,
                                    paged_decode_attention_ref,
                                    split_fused_kv)


def paged_decode_attention_sharded_ref(
        q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
        shard_tables: jax.Array, lengths: jax.Array,
        window: int | None = None) -> jax.Array:
    """Oracle for the shard-native split-pool kernel path."""
    B = q.shape[0]
    tables = assemble_shard_tables(shard_tables)[:B]
    return paged_decode_attention_ref(q, k_pool, v_pool, tables, lengths,
                                      window=window)


def paged_decode_attention_fused_ref(
        q: jax.Array, kv_pool: jax.Array, shard_tables: jax.Array,
        lengths: jax.Array, window: int | None = None) -> jax.Array:
    """Oracle for the fused-pool kernel path: split the fused pool and
    defer to the split oracle."""
    k_pool, v_pool = split_fused_kv(kv_pool)
    return paged_decode_attention_sharded_ref(q, k_pool, v_pool,
                                              shard_tables, lengths,
                                              window=window)


def ragged_fused_ref(q: jax.Array, kv_pool: jax.Array, tables: jax.Array,
                     token_row: jax.Array, token_pos: jax.Array,
                     kv_lens: jax.Array,
                     window: int | None = None) -> jax.Array:
    """Oracle for the ragged fused kernel.

    q:          (T, H, hd)   packed query rows (padding rows included)
    kv_pool:    (N, 2, KV, bs, hd) fused pool
    tables:     (B, M) or (W, Bs, M)
    token_row:  (T,) batch slot per packed token (-1 = padding)
    token_pos:  (T,) global position per packed token
    kv_lens:    per-slot kv lengths (>= 1)
    → (T, H, hd); padding rows are zeroed (the kernel leaves finite
    garbage there — callers drop them either way).
    """
    T, H, hd = q.shape
    k_pool, v_pool = split_fused_kv(kv_pool)
    N, bs, KV, _ = k_pool.shape
    G = H // KV
    mono = assemble_shard_tables(tables)                   # (slots, M)
    M = mono.shape[1]
    slot = jnp.maximum(token_row, 0)
    tab = mono[slot]                                       # (T, M)
    phys = jnp.maximum(tab, 0)
    k = jnp.take(k_pool, phys, axis=0).reshape(
        T, M * bs, KV, hd).astype(jnp.float32)
    v = jnp.take(v_pool, phys, axis=0).reshape(
        T, M * bs, KV, hd).astype(jnp.float32)
    qf = q.reshape(T, KV, G, hd).astype(jnp.float32) / jnp.sqrt(hd)
    s = jnp.einsum("tkgd,tskd->tkgs", qf, k)               # (T,KV,G,S)
    kpos = jnp.arange(M * bs)[None, :]
    qpos = token_pos[:, None]
    valid = (kpos <= qpos) & (kpos < kv_lens[slot][:, None]) & (
        jnp.repeat(tab, bs, axis=1) >= 0)
    if window is not None:
        valid &= kpos > qpos - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("tkgs,tskd->tkgd", p, v).reshape(T, H, hd)
    out = jnp.where((token_row >= 0)[:, None, None], out, 0.0)
    return out.astype(q.dtype)


__all__ = ["paged_decode_attention_ref", "paged_decode_attention_sharded_ref",
           "paged_decode_attention_fused_ref", "ragged_fused_ref",
           "assemble_shard_tables"]
