"""Attention: GQA with optional sliding window / QKV bias.

Three implementations of the same math (and tests assert they agree):

  * ``direct_attention``   — O(S²) softmax oracle (small shapes only)
  * ``chunked_attention``  — online-softmax over KV chunks in pure jnp:
                             memory-bounded; used for CPU runs and dry-run
                             lowering (cost_analysis sees the true FLOPs)
  * kernels/flash_attention — the Pallas TPU kernel (same math, VMEM tiles)

Decode over the FPR paged KV cache has a jnp reference here
(``paged_decode_attention_ref``) and a Pallas kernel in kernels/paged_attention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, init_dense, rms_norm

NEG_INF = -1e30


def init_attn(key, cfg, dtype=jnp.bfloat16) -> dict:
    D, H, KV, HD = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 5)
    p = {"norm": jnp.ones((D,), dtype),
         "wq": init_dense(ks[0], D, H * HD, dtype),
         "wk": init_dense(ks[1], D, KV * HD, dtype),
         "wv": init_dense(ks[2], D, KV * HD, dtype),
         "wo": init_dense(ks[3], H * HD, D, dtype)}
    if cfg.attn.qkv_bias:
        p["bq"] = jnp.zeros((H * HD,), dtype)
        p["bk"] = jnp.zeros((KV * HD,), dtype)
        p["bv"] = jnp.zeros((KV * HD,), dtype)
    return p


def qkv_proj(params: dict, x: jax.Array, cfg, positions: jax.Array | None
             ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B,S,D) → q (B,S,H,hd), k/v (B,S,KV,hd), rope applied."""
    B, S, _ = x.shape
    H, KV, HD = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.attn.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, S, H, HD)
    k = k.reshape(B, S, KV, HD)
    v = v.reshape(B, S, KV, HD)
    if cfg.attn.rope and positions is not None:
        q = apply_rope(q, positions, cfg.attn.rope_theta)
        k = apply_rope(k, positions, cfg.attn.rope_theta)
    return q, k, v


# ----------------------------------------------------------------- oracle ----
def direct_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool = True, window: int | None = None,
                     q_offset: int = 0) -> jax.Array:
    """q: (B,Sq,H,hd); k,v: (B,Sk,KV,hd). GQA by head grouping."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, Sq, KV, G, hd).astype(jnp.float32)
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) / jnp.sqrt(hd)
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(k.shape[1])
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        mask &= qpos[:, None] - kpos[None, :] < window
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, H, hd)


# ----------------------------------------------------- chunked (flash-jnp) ----
def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: int | None = None,
                      q_offset: int = 0, chunk: int = 256) -> jax.Array:
    """Online-softmax attention over KV chunks with a flash-style custom
    backward: the VJP recomputes per-chunk scores from (q, k, v, out, lse)
    instead of letting scan stack every chunk's probability tensor —
    O(S·chunk) live memory in both directions (the naive scan backward
    materialises O(S²/chunk · chunk) = O(S²) residuals; see EXPERIMENTS.md
    §Perf iteration 1)."""
    return _chunked_attention_vjp(q, k, v, causal, window, q_offset, chunk)


def chunked_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                          causal: bool = True, window: int | None = None,
                          q_offset=0, chunk: int = 256) -> jax.Array:
    """Inference-only chunked attention: same online softmax as
    :func:`chunked_attention`, but without the custom VJP wrapper — whose
    ``nondiff_argnums`` pin ``q_offset`` as a static (trace-time) value.
    Here ``q_offset`` may be a traced scalar, which is what lets a
    fixed-shape prefill *chunk* compile once and slide along the sequence
    (see ``transformer.prefill_chunk``)."""
    out, _ = _chunked_fwd(q, k, v, causal, window, q_offset, chunk)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _chunked_attention_vjp(q, k, v, causal, window, q_offset, chunk):
    out, _ = _chunked_fwd(q, k, v, causal, window, q_offset, chunk)
    return out


def _chunked_fwd_res(q, k, v, causal, window, q_offset, chunk):
    out, lse = _chunked_fwd(q, k, v, causal, window, q_offset, chunk)
    return out, (q, k, v, out, lse)


#: queries are processed in blocks of this many rows so the live
#: (B, KV, G, q_block, chunk) score tile stays bounded — 128-head MLA at
#: 32k tokens would otherwise materialise ~8 GB score tensors per chunk
Q_BLOCK = 2048


def _q_blocks(Sq: int) -> int:
    return Q_BLOCK if (Sq > Q_BLOCK and Sq % Q_BLOCK == 0) else Sq


def _chunk_mask(qpos, kpos, Sk, causal, window):
    mask = kpos[None, :] < Sk
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        mask &= qpos[:, None] - kpos[None, :] < window
    return mask


def _chunked_bwd(causal, window, q_offset, chunk, res, dout):
    q, k, v, out, lse = res
    B, Sq, H, hd = q.shape
    qc = _q_blocks(Sq)
    if qc != Sq:
        nq = Sq // qc
        KVh = lse.shape[1]
        G = lse.shape[2]
        qb = q.reshape(B, nq, qc, H, hd).transpose(1, 0, 2, 3, 4)
        ob = out.reshape(B, nq, qc, H, hd).transpose(1, 0, 2, 3, 4)
        dob = dout.reshape(B, nq, qc, H, hd).transpose(1, 0, 2, 3, 4)
        lseb = lse.reshape(B, KVh, G, nq, qc).transpose(3, 0, 1, 2, 4)

        def step(carry, inp):
            dka, dva = carry
            i, (qi, oi, li, doi) = inp
            dqi, dki, dvi = _chunked_bwd_body(
                causal, window, q_offset, chunk, (qi, k, v, oi, li), doi,
                q_base=i * qc)
            return (dka + dki.astype(jnp.float32),
                    dva + dvi.astype(jnp.float32)), dqi

        zk = jnp.zeros(k.shape, jnp.float32)
        zv = jnp.zeros(v.shape, jnp.float32)
        (dk, dv), dqs = jax.lax.scan(
            step, (zk, zv), (jnp.arange(nq), (qb, ob, lseb, dob)))
        dq = dqs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)
        return dq, dk.astype(k.dtype), dv.astype(v.dtype)
    return _chunked_bwd_body(causal, window, q_offset, chunk, res, dout)


def _chunked_bwd_body(causal, window, q_offset, chunk, res, dout,
                      q_base=0):
    q, k, v, out, lse = res
    B, Sq, H, hd = q.shape
    q_offset = q_offset + q_base
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = hd ** -0.5
    pad = (-Sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = k.shape[1] // chunk
    qf = q.reshape(B, Sq, KV, G, hd).astype(jnp.float32)
    dof = dout.reshape(B, Sq, KV, G, hd).astype(jnp.float32)
    of = out.reshape(B, Sq, KV, G, hd).astype(jnp.float32)
    kc = k.reshape(B, n_chunks, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    qpos = jnp.arange(Sq) + q_offset
    # D_i = Σ_d dO_i·O_i  — the softmax-backward diagonal term
    delta = (dof * of).sum(-1)                         # (B,Sq,KV,G)

    def step(dq, inp):
        ci, (kb, vb) = inp
        kb = kb.astype(jnp.float32)
        vb = vb.astype(jnp.float32)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qf, kb) * scale
        kpos = ci * chunk + jnp.arange(chunk)
        mask = _chunk_mask(qpos, kpos, Sk, causal, window)
        # p from saved lse (no renormalisation pass needed)
        p = jnp.where(mask, jnp.exp(s - lse[..., None]), 0.0)
        dp = jnp.einsum("bqkgd,bskd->bkgqs", dof, vb)
        ds = p * (dp - delta.transpose(0, 2, 3, 1)[..., None]) * scale
        dq = dq + jnp.einsum("bkgqs,bskd->bqkgd", ds, kb)
        dk = jnp.einsum("bkgqs,bqkgd->bskd", ds, qf)
        dv = jnp.einsum("bkgqs,bqkgd->bskd", p, dof)
        return dq, (dk, dv)

    dq0 = jnp.zeros((B, Sq, KV, G, hd), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(step, dq0,
                                  (jnp.arange(n_chunks), (kc, vc)))
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, n_chunks * chunk, KV, hd)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, n_chunks * chunk, KV, hd)
    return (dq.reshape(B, Sq, H, hd).astype(q.dtype),
            dk[:, :Sk].astype(k.dtype), dv[:, :Sk].astype(v.dtype))


_chunked_attention_vjp.defvjp(_chunked_fwd_res, _chunked_bwd)


def _chunked_fwd(q: jax.Array, k: jax.Array, v: jax.Array, causal,
                 window, q_offset, chunk):
    """Forward online-softmax over KV chunks → (out, lse); queries are
    processed in Q_BLOCK-row blocks (bounded score tiles)."""
    B, Sq, H, hd = q.shape
    qc = _q_blocks(Sq)
    if qc != Sq:
        nq = Sq // qc
        qb = q.reshape(B, nq, qc, H, hd).transpose(1, 0, 2, 3, 4)

        def one(inp):
            i, qi = inp
            return _chunked_fwd_body(qi, k, v, causal, window,
                                     q_offset + i * qc, chunk)

        outs, lses = jax.lax.map(one, (jnp.arange(nq), qb))
        out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)
        KVh, G = lses.shape[2], lses.shape[3]
        lse = lses.transpose(1, 2, 3, 0, 4).reshape(B, KVh, G, Sq)
        return out, lse
    return _chunked_fwd_body(q, k, v, causal, window, q_offset, chunk)


def _chunked_fwd_body(q: jax.Array, k: jax.Array, v: jax.Array, causal,
                      window, q_offset, chunk):
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    if Sk % chunk:
        pad = chunk - Sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = k.shape[1] // chunk
    qf = q.reshape(B, Sq, KV, G, hd).astype(jnp.float32) / jnp.sqrt(hd)
    kc = k.reshape(B, n_chunks, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    qpos = jnp.arange(Sq) + q_offset

    def step(carry, inp):
        m, l, acc = carry
        ci, (kb, vb) = inp
        kb = kb.astype(jnp.float32)
        vb = vb.astype(jnp.float32)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qf, kb)       # (B,KV,G,Sq,chunk)
        kpos = ci * chunk + jnp.arange(chunk)
        mask = kpos[None, :] < Sk
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window is not None:
            mask &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        scale = jnp.exp(m - m_new)
        l_new = l * scale + p.sum(axis=-1)
        acc_new = acc * scale[..., None] + jnp.einsum("bkgqs,bskd->bkgqd", p, vb)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.arange(n_chunks), (kc, vc)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))           # (B,KV,G,Sq)
    return out.astype(q.dtype), lse


# --------------------------------------------------------- paged decode ref ----
def assemble_shard_tables(tables: jax.Array) -> jax.Array:
    """Monolithic view of a ``(W, Bs, M)`` interleaved shard stack.

    Slot ``b`` lives at shard ``b % W``, local row ``b // W``, so the
    monolithic ``(W*Bs, M)`` table is a pure transpose+reshape — cheap
    inside a traced graph, and the identity for a 2-D table.  Only
    non-shard-native consumers (the jnp reference, sequence-parallel
    collectives, MLA decode) call this; the Pallas kernel indexes the
    stack directly.
    """
    if tables.ndim == 2:
        return tables
    W, Bs, M = tables.shape
    return tables.transpose(1, 0, 2).reshape(W * Bs, M)


def lookup_slot_blocks(tables: jax.Array, slots: jax.Array,
                       blk_idx: jax.Array) -> jax.Array:
    """Physical block of logical block ``blk_idx[i]`` for slot
    ``slots[i]``, for either table layout (monolithic ``(B, M)`` or the
    ``(W, Bs, M)`` shard stack)."""
    if tables.ndim == 2:
        return tables[slots, blk_idx]
    W = tables.shape[0]
    return tables[slots % W, slots // W, blk_idx]


def fuse_kv(k: jax.Array, v: jax.Array) -> jax.Array:
    """Fuse token-major K and V into the paged-attention pool layout.

    ``(..., S, KV, hd) × 2 → (..., 2, KV, S, hd)``: K at index 0 and V at
    index 1 of an untiled axis, each head's ``(S, hd)`` tile contiguous.
    With ``S`` the block size this is one fused pool block — ONE
    contiguous DMA instead of two, and the kernel picks K and V by a
    static index.  Pure permutation: ``split_fused_kv`` inverts it bit
    for bit.
    """
    return jnp.stack([k, v], axis=-4).swapaxes(-3, -2)


def split_fused_kv(kv: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Token-major ``(..., S, KV, hd)`` K and V of a fused ``(..., 2, KV,
    S, hd)`` array (inverse of :func:`fuse_kv`)."""
    kv = kv.swapaxes(-3, -2)
    return kv[..., 0, :, :, :], kv[..., 1, :, :, :]


def paged_decode_attention_ref(q: jax.Array, k_pool: jax.Array,
                               v_pool: jax.Array, block_tables: jax.Array,
                               lengths: jax.Array,
                               window: int | None = None) -> jax.Array:
    """Decode attention over the FPR paged cache (jnp reference).

    q:            (B, H, hd)        one new token per sequence
    k_pool/v_pool:(N, bs, KV, hd)   physical block pools
    block_tables: (B, M) int32      logical→physical (−1/−2 = non-resident)
    lengths:      (B,) int32        tokens in cache (incl. the new one)
    window:       sliding-window size (danube SWA); None = full causal
    """
    B, H, hd = q.shape
    N, bs, KV, _ = k_pool.shape
    M = block_tables.shape[1]
    G = H // KV
    tables = jnp.maximum(block_tables, 0)                  # clamp holes
    k = jnp.take(k_pool, tables, axis=0)                   # (B,M,bs,KV,hd)
    v = jnp.take(v_pool, tables, axis=0)
    k = k.reshape(B, M * bs, KV, hd).astype(jnp.float32)
    v = v.reshape(B, M * bs, KV, hd).astype(jnp.float32)
    qf = q.reshape(B, KV, G, hd).astype(jnp.float32) / jnp.sqrt(hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qf, k)               # (B,KV,G,S)
    pos = jnp.arange(M * bs)[None, :]
    valid = (pos < lengths[:, None]) & (
        jnp.repeat(block_tables, bs, axis=1) >= 0)
    if window is not None:
        valid &= pos > lengths[:, None] - 1 - window       # SWA
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v)
    return out.reshape(B, H, hd).astype(q.dtype)


# ------------------------------------------------------------ full layers ----
def attn_layer(params: dict, x: jax.Array, positions: jax.Array, cfg, *,
               impl: str = "chunked") -> jax.Array:
    """Pre-norm residual attention block for train/prefill."""
    h = rms_norm(x, params["norm"], cfg.norm_eps)
    q, k, v = qkv_proj(params, h, cfg, positions)
    w = cfg.attn.window
    if impl == "direct":
        o = direct_attention(q, k, v, causal=True, window=w)
    elif impl == "chunked":
        o = chunked_attention(q, k, v, causal=True, window=w)
    elif impl == "pallas" or impl == "pallas_interpret":
        from repro.kernels.flash_attention import ops as fa_ops
        o = fa_ops.flash_attention(q, k, v, causal=True, window=w,
                                   interpret=(impl == "pallas_interpret"))
    else:
        raise ValueError(impl)
    B, S, H, hd = o.shape
    return x + o.reshape(B, S, H * hd) @ params["wo"]


def cross_attn_layer(params: dict, x: jax.Array, enc_kv: tuple, cfg
                     ) -> jax.Array:
    """Encoder-decoder cross attention (whisper); enc_kv precomputed."""
    h = rms_norm(x, params["norm"], cfg.norm_eps)
    B, S, _ = h.shape
    H, HD = cfg.n_heads, cfg.head_dim
    q = (h @ params["wq"]).reshape(B, S, H, HD)
    k, v = enc_kv
    o = chunked_attention(q, k, v, causal=False)
    return x + o.reshape(B, S, H * HD) @ params["wo"]


def encode_cross_kv(params: dict, enc_out: jax.Array, cfg) -> tuple:
    B, Se, _ = enc_out.shape
    KV, HD = cfg.n_kv_heads, cfg.head_dim
    k = (enc_out @ params["wk"]).reshape(B, Se, KV, HD)
    v = (enc_out @ params["wv"]).reshape(B, Se, KV, HD)
    return k, v
