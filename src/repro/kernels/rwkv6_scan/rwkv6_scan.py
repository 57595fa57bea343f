"""RWKV-6 "Finch" WKV Pallas TPU kernel — data-dependent per-channel decay.

Recurrence per head (state S is a (hd × hd) outer-product accumulator):

    S_t = diag(w_t)·S_{t-1} + k_tᵀ v_t
    y_t = r_t·(S_{t-1} + diag(u)·k_tᵀ v_t)

The kernel walks the sequence in chunks of 32 on the innermost (sequential)
grid axis, carrying S in VMEM scratch, and evaluates each chunk in *direct
form* — three (chunk,hd)-shaped MXU matmuls instead of ``chunk`` sequential
rank-1 updates:

    y  = (r·Wexcl) @ S  +  mask∘[(r·Wexcl) @ (k/Wincl)ᵀ] @ v  +  diag-term
    S' = diag(Wincl_last)·S + (tail·k)ᵀ @ v

where Wincl/Wexcl are inclusive/exclusive cumulative decay products.  The
chunk length (32) bounds the dynamic range of the cumulated decays so the
(k / Wincl) division stays in f32 range (w ∈ (0,1), log w ≥ −e^{0.5}·e).

Grid: (B, nH, S/chunk) = (parallel, parallel, arbitrary).  Padded tail
positions use w = 1, r = k = 0: they contribute nothing and leave S intact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _rwkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sl_ref,
                 s_sc, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        s_sc[...] = s0_ref[0, 0]

    r = r_ref[0, 0]                                   # (c, hd) f32
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    w = w_ref[0, 0]
    u = u_ref[0]                                      # (hd,)
    S = s_sc[...]                                     # (hd, hd)

    logw = jnp.log(w)
    cum = jnp.cumsum(logw, axis=0)                    # inclusive
    Wincl = jnp.exp(cum)
    Wexcl = jnp.exp(cum - logw)

    rW = r * Wexcl                                    # (c, hd)
    y_state = jax.lax.dot_general(rW, S, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    att = jax.lax.dot_general(rW, k / Wincl, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (c, c)
    t_i = jax.lax.broadcasted_iota(jnp.int32, att.shape, 0)
    s_i = jax.lax.broadcasted_iota(jnp.int32, att.shape, 1)
    att = jnp.where(t_i > s_i, att, 0.0)              # strictly past
    diag = (r * u[None, :] * k).sum(axis=-1)          # (c,)
    y_intra = jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_ref[0, 0] = y_state + y_intra + diag[:, None] * v

    tail = Wincl[-1:] / Wincl                         # (c, hd)
    s_sc[...] = (Wincl[-1][:, None] * S
                 + jax.lax.dot_general(tail * k, v, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32))

    @pl.when(ci == nc - 1)
    def _fin():
        sl_ref[0, 0] = s_sc[...]


def rwkv6_scan_fwd(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                   u: jax.Array, S0: jax.Array, *, chunk: int = 32,
                   interpret: bool = False):
    """r, k, v, w: (B, nH, S, hd) f32; u: (nH, hd); S0: (B, nH, hd, hd),
    S divisible by chunk → (y (B, nH, S, hd), S_last (B, nH, hd, hd))."""
    B, nH, S, hd = r.shape
    assert S % chunk == 0
    grid = (B, nH, S // chunk)

    seq_map = lambda b, h, ci: (b, h, ci, 0)
    u_map = lambda b, h, ci: (h, 0)
    s_map = lambda b, h, ci: (b, h, 0, 0)

    y, s_last = pl.pallas_call(
        functools.partial(_rwkv_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, hd), seq_map),
            pl.BlockSpec((1, 1, chunk, hd), seq_map),
            pl.BlockSpec((1, 1, chunk, hd), seq_map),
            pl.BlockSpec((1, 1, chunk, hd), seq_map),
            pl.BlockSpec((1, hd), u_map),
            pl.BlockSpec((1, 1, hd, hd), s_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, hd), seq_map),
            pl.BlockSpec((1, 1, hd, hd), s_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nH, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, nH, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u, S0)
    return y, s_last
