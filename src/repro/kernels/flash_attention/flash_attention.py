"""Flash attention Pallas TPU kernel — prefill/train hot path.

Tiling: the grid is (B, H, Sq/bq, Sk/bk) with the KV dimension innermost and
*arbitrary* (sequential) semantics so the online-softmax state lives in VMEM
scratch across KV steps.  Per step the kernel holds

    q tile (bq, hd)  ·  k tile (bk, hd)  ·  v tile (bk, hd)

in VMEM — with bq = bk = 128 and hd = 128 the s = q·kᵀ matmul is exactly one
MXU-shaped (128,128)·(128,128) contraction.  GQA never materialises repeated
KV heads: the k/v BlockSpec index map sends query head h to KV head h//G.

Causal/sliding-window tiles that are fully masked are skipped with pl.when
(the dominant saving for long sequences: the causal lower triangle costs
half the tiles, a window of W keeps only ceil(W/bk)+1 diagonals).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
               bq: int, bk: int, sk: int, causal: bool, window: int | None,
               q_offset: int):
    """One (q-tile, k-tile) step of online-softmax attention."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # ---- tile visibility: skip fully-masked tiles --------------------------
    q_start = qi * bq + q_offset          # global position of first query row
    k_start = ki * bk
    run = True
    if causal:
        # tile is visible iff its first k pos <= last q pos
        run = k_start <= q_start + bq - 1
    if window is not None:
        # and its last k pos is within the window of the last q row
        run = jnp.logical_and(run, k_start + bk - 1
                              > q_start - window) if causal else run

    @pl.when(run if (causal or window is not None) else True)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (q.shape[-1] ** -0.5)                # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < sk                              # kv padding
        if causal:
            mask = jnp.logical_and(mask, qpos >= kpos)
        if window is not None:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_sc[...]                            # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                        # (bq, bk)
        scale = jnp.exp(m_prev - m_new)               # (bq, 1)
        l_sc[...] = l_sc[...] * scale + p.sum(axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * scale + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_sc[...]
        o_ref[0, 0] = (acc_sc[...] /
                       jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int | None = None,
                        q_offset: int = 0, bq: int = 128, bk: int = 128,
                        sk_valid: int | None = None,
                        interpret: bool = False) -> jax.Array:
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd) → (B, H, Sq, hd).

    Sq must be a multiple of bq and Sk of bk (ops.py pads — ``sk_valid`` is
    the unpadded KV length); hd should be a multiple of 128 for full MXU
    utilisation (smaller works, under-utilised).
    """
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    grid = (B, H, Sq // bq, Sk // bk)

    q_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, hd),
                           lambda b, h, qi, ki: (b, h // G, ki, 0))
    o_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0))

    kern = functools.partial(_fa_kernel, bq=bq, bk=bk,
                             sk=sk_valid if sk_valid is not None else Sk,
                             causal=causal, window=window, q_offset=q_offset)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),      # running max
            pltpu.VMEM((bq, 1), jnp.float32),      # running denom
            pltpu.VMEM((bq, hd), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
