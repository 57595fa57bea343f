"""Absorbed-MLA paged decode Pallas TPU kernel (DeepSeek-V2).

The absorbed form turns 128-head MLA decode into dense latent matmuls:
queries are pre-folded through W_UK (ops.py), so the kernel scores every
head directly against the shared rank-512 latent pages

    s[h, t] = q_lat[h] · c_t  +  q_rope[h] · k_rope_t
    ctx[h]  = softmax_t(s)[h] · c_t               (still in latent space)

and the value up-projection W_UV is applied after the kernel.  Per grid
step the kernel holds one latent page (bs, rank) + its rope keys in VMEM;
with bs = 128 and rank = 512 the score matmul is (H,512)·(512,128) — pure
MXU work, and the page is ~9× smaller than the equivalent GQA page (the
reason MLA pages recycle fastest; DESIGN.md §4).

Grid: (B, M) — same scalar-prefetch page walk as paged_attention,
including its shard-native ``_table_index`` arithmetic: the serving
cache's ``(W, Bs, M)`` interleaved shard stack is walked directly (slot
``b`` at shard ``b % W``, row ``b // W``), with the classic monolithic
``(B, M)`` table as the bit-identical ``W = 1`` degenerate case — no
caller materializes a traced transpose of the stack anymore.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention.paged_attention import _table_index

NEG_INF = -1e30


def _mla_kernel(tables_ref, lengths_ref, ql_ref, qr_ref, c_ref, r_ref,
                o_ref, m_sc, l_sc, acc_sc, *, bs: int, scale: float,
                W: int, Bs: int, M: int):
    b = pl.program_id(0)
    mi = pl.program_id(1)
    nm = pl.num_programs(1)
    length = lengths_ref[b]

    @pl.when(mi == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    blk_start = mi * bs
    resident = tables_ref[_table_index(b, mi, W=W, Bs=Bs, M=M)] >= 0

    @pl.when(jnp.logical_and(resident, blk_start < length))
    def _step():
        ql = ql_ref[0].astype(jnp.float32)            # (H, rank)
        qr = qr_ref[0].astype(jnp.float32)            # (H, rope_hd)
        c = c_ref[0].astype(jnp.float32)              # (bs, rank)
        kr = r_ref[0].astype(jnp.float32)             # (bs, rope_hd)
        s = (jax.lax.dot_general(ql, c, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
             ) * scale                                # (H, bs)
        pos = blk_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        sc = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * sc + p.sum(axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * sc + jax.lax.dot_general(
            p, c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # (H, rank)
        m_sc[...] = m_new

    @pl.when(mi == nm - 1)
    def _finalize():
        o_ref[0] = (acc_sc[...] /
                    jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


def mla_paged_ctx_fwd(q_lat: jax.Array, q_rope: jax.Array, c_pool: jax.Array,
                      rope_pool: jax.Array, tables: jax.Array,
                      lengths: jax.Array, *, scale: float,
                      interpret: bool = False) -> jax.Array:
    """q_lat: (B, H, rank); q_rope: (B, H, rope_hd); c_pool: (N, bs, rank);
    rope_pool: (N, bs, rope_hd); tables: (B, M) monolithic or (W, Bs, M)
    interleaved shard stack → latent context (B, H, rank) f32."""
    from repro.kernels.paged_attention.ops import shard_descriptor

    B, H, rank = q_lat.shape
    rope_hd = q_rope.shape[-1]
    N, bs, _ = c_pool.shape
    stack, W, Bs, M = shard_descriptor(tables)
    if W * Bs < B:
        raise ValueError(f"shard stack covers {W * Bs} slots < batch {B}")

    def q_map(b, m, t, l):
        return (b, 0, 0)

    def pool_map(b, m, t, l):
        return (jnp.maximum(t[_table_index(b, m, W=W, Bs=Bs, M=M)], 0),
                0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, M),
        in_specs=[
            pl.BlockSpec((1, H, rank), q_map),
            pl.BlockSpec((1, H, rope_hd), q_map),
            pl.BlockSpec((1, bs, rank), pool_map),
            pl.BlockSpec((1, bs, rope_hd), pool_map),
        ],
        out_specs=pl.BlockSpec((1, H, rank), q_map),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, rank), jnp.float32),
        ],
    )
    kern = functools.partial(_mla_kernel, bs=bs, scale=scale,
                             W=W, Bs=Bs, M=M)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(stack.reshape(-1), lengths, q_lat, q_rope, c_pool, rope_pool)
