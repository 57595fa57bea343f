"""Model assembly: init, train/prefill forward, chunked loss, decode step.

Layer layout (see ModelConfig.segmentation): an irregular *prefix* is
unrolled; the periodic *body* is scanned with ``jax.lax.scan`` over stacked
parameters (HLO stays small at 512 devices).  Each layer = mixer + ffn
(+ cross-attention for enc-dec).

Decode state is a dict pytree of per-kind cache pools:

  kv:    fused k/v (L_attn, N, 2, KV, bs, hd) paged GQA cache, K and V
         side by side on axis 2 so one logical block is ONE contiguous
         DMA (writes past the pool scatter-drop)
  mla:   c/rope  (L, N+1, bs, rank|rope_hd)  paged latent cache
  mamba: conv/ssm (L_m, B, K-1, DI) / (L_m, B, DI, dstate)
  rwkv:  last_x/wkv (L, B, D) / (L, B, nH, 64, 64)
  cross: k/v     (L, B, enc_len, KV, hd)     whisper cross-attn (immutable)
  tables (B, M) int32 — FPR block tables; lengths (B,) int32

The decode step is unrolled over layers (small graphs; per-layer pool
indexing is static); train/prefill scan.  Paged attention is pluggable:
``page_impl`` ∈ {'ref' (jnp), 'sp' (shard_map sequence-parallel),
'pallas'/'pallas_interpret' (kernels/paged_attention)}.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.config import ModelConfig
from repro.models.frontends import init_frontend
from repro.models.layers import (embed, init_embed,
                                 init_swiglu, rms_norm, unembed)

BLOCK_SIZE = 128   # tokens per physical KV block (MXU-aligned)


# ============================================================ initialisation
def _init_mixer(key, cfg: ModelConfig, kind: str, dtype):
    if kind == "attn":
        return attn_mod.init_attn(key, cfg, dtype)
    if kind == "mla":
        return mla_mod.init_mla(key, cfg, dtype)
    if kind == "mamba":
        return mamba_mod.init_mamba(key, cfg, dtype)
    if kind == "rwkv6":
        return rwkv_mod.init_rwkv6(key, cfg, dtype)
    raise ValueError(kind)


def _init_ffn(key, cfg: ModelConfig, kind: str, dtype):
    if kind == "dense":
        dff = cfg.dense_d_ff or cfg.d_ff
        p = init_swiglu(key, cfg.d_model, dff, dtype)
        p["norm"] = jnp.ones((cfg.d_model,), dtype)
        return p
    if kind == "moe":
        return moe_mod.init_moe(key, cfg, dtype)
    raise ValueError(kind)


def _init_cross(key, cfg: ModelConfig, dtype):
    D, H, KV, HD = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    from repro.models.layers import init_dense
    return {"norm": jnp.ones((D,), dtype),
            "wq": init_dense(ks[0], D, H * HD, dtype),
            "wk": init_dense(ks[1], D, KV * HD, dtype),
            "wv": init_dense(ks[2], D, KV * HD, dtype),
            "wo": init_dense(ks[3], H * HD, D, dtype)}


def _init_layer(key, cfg: ModelConfig, i: int, dtype):
    mix, ffn = cfg.layer_sig(i)
    k1, k2, k3 = jax.random.split(key, 3)
    lp = {"mix": _init_mixer(k1, cfg, mix, dtype),
          "ffn": _init_ffn(k2, cfg, ffn, dtype)}
    if cfg.enc_dec:
        lp["cross"] = _init_cross(k3, cfg, dtype)
    return lp


def init_params(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    prefix, period = cfg.segmentation()
    keys = jax.random.split(key, cfg.n_layers + 8)
    params: dict[str, Any] = {
        "embed": init_embed(keys[-1], cfg.vocab, cfg.d_model, dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = init_embed(keys[-2], cfg.vocab, cfg.d_model, dtype)
    params.update(init_frontend(keys[-3], cfg, dtype))

    params["prefix"] = tuple(
        _init_layer(keys[i], cfg, i, dtype) for i in range(prefix))
    if period:
        n_blocks = (cfg.n_layers - prefix) // period
        body = []
        for j in range(period):
            # vmap over the blocks' keys builds the stacked body directly
            # (same values as stacking per-layer inits, without holding
            # the unstacked copy alongside it)
            ids = [prefix + b * period + j for b in range(n_blocks)]
            body.append(jax.vmap(
                lambda k, i=ids[0]: _init_layer(k, cfg, i, dtype))(
                    keys[jnp.asarray(ids)]))
        params["body"] = tuple(body)
    else:
        params["body"] = ()

    if cfg.enc_dec:
        ek = jax.random.split(keys[-4], cfg.enc_layers)
        params["encoder"] = tuple(
            {"mix": attn_mod.init_attn(ek[i], cfg, dtype),
             "ffn": _init_ffn(jax.random.fold_in(ek[i], 7), cfg, "dense",
                              dtype)}
            for i in range(cfg.enc_layers))
        params["enc_pos"] = (jax.random.normal(
            keys[-5], (cfg.enc_len, cfg.d_model), jnp.float32) * 0.02
            ).astype(dtype)
        params["dec_pos"] = (jax.random.normal(
            keys[-6], (4096, cfg.d_model), jnp.float32) * 0.02).astype(dtype)
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ================================================================== forward
def _apply_layer(lp, x, positions, cfg: ModelConfig, sig, *, impl,
                 enc_out=None, moe_groups=1, moe_axes=(None, None)):
    """One layer on (B,S,D). Returns (x, aux, cache) — cache only the parts
    a later decode needs (collected by prefill)."""
    mix, ffn = sig
    cache = {}
    if mix == "attn":
        h = rms_norm(x, lp["mix"]["norm"], cfg.norm_eps)
        q, k, v = attn_mod.qkv_proj(lp["mix"], h, cfg, positions)
        o = attn_mod.chunked_attention(q, k, v, causal=True,
                                       window=cfg.attn.window) \
            if impl == "chunked" else attn_mod.direct_attention(
                q, k, v, causal=True, window=cfg.attn.window)
        B, S, H, hd = o.shape
        x = x + o.reshape(B, S, H * hd) @ lp["mix"]["wo"]
        cache["kv"] = (k, v)
    elif mix == "mla":
        x = mla_mod.mla_layer(lp["mix"], x, positions, cfg, impl=impl)
        h = rms_norm(x, lp["mix"]["norm"], cfg.norm_eps)  # for cache only
        # NOTE: cache must reflect the *input* latents; recompute from pre-x.
        cache["mla"] = None   # filled by the dedicated prefill path below
    elif mix == "mamba":
        h = rms_norm(x, lp["mix"]["norm"], cfg.norm_eps)
        y, (cs, ss) = mamba_mod.mamba_mix(lp["mix"], h, cfg, impl=impl)
        x = x + y
        cache["mamba"] = (cs, ss)
    elif mix == "rwkv6":
        h = rms_norm(x, lp["mix"]["norm"], cfg.norm_eps)
        y, (lx, st) = rwkv_mod.rwkv6_mix(lp["mix"], h, cfg, impl=impl)
        x = x + y
        cache["rwkv"] = (lx, st)
    else:
        raise ValueError(mix)

    if cfg.enc_dec and enc_out is not None:
        kc, vc = attn_mod.encode_cross_kv(lp["cross"], enc_out, cfg)
        x = attn_mod.cross_attn_layer(lp["cross"], x, (kc, vc), cfg)
        cache["cross"] = (kc, vc)

    if ffn == "dense":
        from repro.models.layers import dense_ffn
        x = dense_ffn(lp["ffn"], x, cfg.norm_eps)
        aux = jnp.zeros((), jnp.float32)
    else:
        x, aux = moe_mod.moe_ffn(lp["ffn"], x, cfg, num_groups=moe_groups,
                                 ep_axis=moe_axes[0], dp_axis=moe_axes[1])
    return x, aux, cache


def _mla_layer_with_cache(lp, x, positions, cfg):
    """Expanded MLA for prefill that also returns the latent cache content."""
    h = rms_norm(x, lp["norm"], cfg.norm_eps)
    c_kv, k_rope = mla_mod.latent_kv(lp, h, cfg, positions)
    x = mla_mod.mla_layer(lp, x, positions, cfg, impl="chunked")
    return x, (c_kv, k_rope[:, :, 0, :])


def encode(params, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """Whisper encoder: frames (B, enc_len, D) → enc_out (B, enc_len, D)."""
    from repro.models.frontends import audio_frames_to_embeddings
    x = audio_frames_to_embeddings(params, frames)
    x = x + params["enc_pos"][None, : x.shape[1]]
    for lp in params["encoder"]:
        h = rms_norm(x, lp["mix"]["norm"], cfg.norm_eps)
        q, k, v = attn_mod.qkv_proj(lp["mix"], h, cfg, None)
        o = attn_mod.chunked_attention(q, k, v, causal=False)
        B, S, H, hd = o.shape
        x = x + o.reshape(B, S, H * hd) @ lp["mix"]["wo"]
        from repro.models.layers import dense_ffn
        x = dense_ffn(lp["ffn"], x, cfg.norm_eps)
    return rms_norm(x, params["final_norm"], cfg.norm_eps)


def embed_inputs(params, cfg: ModelConfig, tokens: jax.Array,
                 patches: jax.Array | None = None, *, mesh=None,
                 act_spec=None) -> jax.Array:
    """tokens (B,S_text) [+ patches (B,P,D)] → x (B,S,D)."""
    if mesh is not None and "model" in mesh.axis_names:
        from repro.distributed.collectives import vocab_parallel_embed
        dp = act_spec[0] if act_spec is not None else None
        x = vocab_parallel_embed(tokens, params["embed"], mesh=mesh,
                                 dp_spec=dp)
    else:
        x = embed(tokens, params["embed"])
    if cfg.frontend == "vision" and patches is not None:
        from repro.models.frontends import vision_patches_to_embeddings
        vis = vision_patches_to_embeddings(params, patches)
        x = jnp.concatenate([vis.astype(x.dtype), x], axis=1)
    if cfg.enc_dec:
        pos_idx = jnp.minimum(jnp.arange(x.shape[1]), 4095)
        x = x + params["dec_pos"][pos_idx][None]
    return x


def _constrain(x, act_spec):
    """Pin activation sharding (batch over data axes, D replicated across
    TP) — without this GSPMD is free to pick feature-sharded activations
    and re-reduce them at every matmul (observed: 16× redundant compute)."""
    if act_spec is None:
        return x
    spec = act_spec if x.ndim == 3 else jax.sharding.PartitionSpec(
        *act_spec[:x.ndim - 1], None)
    return jax.lax.with_sharding_constraint(x, spec)


def forward_hidden(params, cfg: ModelConfig, x: jax.Array, *,
                   impl: str = "chunked", enc_out=None, remat: bool = True,
                   moe_groups: int = 1, remat_policy=None, act_spec=None):
    """x: (B,S,D) embedded inputs → (hidden (B,S,D), aux_loss)."""
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    prefix, period = cfg.segmentation()
    aux = jnp.zeros((), jnp.float32)
    x = _constrain(x, act_spec)
    moe_axes = (("model", act_spec[0]) if act_spec is not None
                else (None, None))

    for i, lp in enumerate(params["prefix"]):
        x, a, _ = _apply_layer(lp, x, positions, cfg, cfg.layer_sig(i),
                               impl=impl, enc_out=enc_out,
                               moe_groups=moe_groups, moe_axes=moe_axes)
        x = _constrain(x, act_spec)
        aux = aux + a

    if period and params["body"]:
        sigs = [cfg.layer_sig(prefix + j) for j in range(period)]

        def blk(carry, xs):
            x, aux = carry
            for j in range(period):
                x, a, _ = _apply_layer(xs[j], x, positions, cfg, sigs[j],
                                       impl=impl, enc_out=enc_out,
                                       moe_groups=moe_groups,
                                       moe_axes=moe_axes)
                x = _constrain(x, act_spec)
                aux = aux + a
            return (x, aux), None

        if remat:
            blk = jax.checkpoint(blk, policy=remat_policy,
                                 prevent_cse=False)
        (x, aux), _ = jax.lax.scan(blk, (x, aux), params["body"])
    return x, aux


def chunked_loss(params, cfg: ModelConfig, hidden: jax.Array,
                 labels: jax.Array, chunk: int = 512) -> jax.Array:
    """Cross-entropy without materialising (B,S,V) logits at once."""
    B, S, D = hidden.shape
    h = rms_norm(hidden, params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    while S % chunk:
        chunk //= 2
    n = S // chunk
    hc = h.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def step(acc, inp):
        # checkpointed: the backward recomputes the (B,chunk,V) logits per
        # chunk instead of scan stacking all of them (≈3.4 GB/chip saved)
        hk, lk = inp
        logits = unembed(hk, table)                  # (B,chunk,V) f32
        mask = lk != -100
        lab = jnp.where(mask, lk, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        nll, cnt = acc
        return (nll + ((logz - gold) * mask).sum(), cnt + mask.sum()), None

    (nll, cnt), _ = jax.lax.scan(
        step, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (hc, lc))
    return nll / jnp.maximum(cnt, 1)


def loss_fn(params, cfg: ModelConfig, batch: dict, *, impl: str = "chunked",
            moe_groups: int = 1, remat_policy=None, act_spec=None,
            mesh=None) -> jax.Array:
    """batch: tokens (B,S), labels (B,S) [, patches (B,P,D), frames]."""
    enc_out = None
    if cfg.enc_dec:
        enc_out = encode(params, cfg, batch["frames"])
    x = embed_inputs(params, cfg, batch["tokens"], batch.get("patches"),
                     mesh=mesh, act_spec=act_spec)
    hidden, aux = forward_hidden(params, cfg, x, impl=impl, enc_out=enc_out,
                                 moe_groups=moe_groups,
                                 remat_policy=remat_policy,
                                 act_spec=act_spec)
    labels = batch["labels"]
    if cfg.frontend == "vision" and "patches" in batch:
        # prefix positions carry no LM loss
        P = batch["patches"].shape[1]
        pad = jnp.full((labels.shape[0], P), -100, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    return chunked_loss(params, cfg, hidden, labels) + aux


# ============================================================= decode state
def attn_layer_ids(cfg: ModelConfig) -> list[int]:
    return [i for i in range(cfg.n_layers) if cfg.mixers[i] == "attn"]


def mamba_layer_ids(cfg: ModelConfig) -> list[int]:
    return [i for i in range(cfg.n_layers) if cfg.mixers[i] == "mamba"]


def cache_spec(cfg: ModelConfig, batch: int, max_len: int,
               num_blocks: int | None = None,
               dtype=jnp.bfloat16, round_to: int = 1) -> dict:
    """Shapes/dtypes of the decode-state pytree (used for ShapeDtypeStruct
    dry-runs and real allocation alike).  num_blocks defaults to exactly
    enough blocks for batch×max_len tokens; ``round_to`` rounds the pool up
    so it divides evenly across (batch × sequence) shards."""
    bs = BLOCK_SIZE
    M = (max_len + bs - 1) // bs
    N = num_blocks if num_blocks is not None else batch * M
    N = ((N + round_to - 1) // round_to) * round_to
    spec: dict[str, Any] = {
        "tables": ((batch, M), jnp.int32),
        "lengths": ((batch,), jnp.int32),
    }
    n_attn = len(attn_layer_ids(cfg))
    n_mamba = len(mamba_layer_ids(cfg))
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    if cfg.mixers[0] == "mla" or "mla" in cfg.mixers:
        m = cfg.mla
        L = cfg.n_layers
        spec["mla_c"] = ((L, N, bs, m.kv_lora_rank), dtype)
        spec["mla_rope"] = ((L, N, bs, m.rope_head_dim), dtype)
    if n_attn:
        # fused K/V pool: each block holds K then V, head-major — one
        # block, one DMA (see kernels/paged_attention)
        spec["kv"] = ((n_attn, N, 2, KV, bs, hd), dtype)
    if n_mamba:
        mm = cfg.mamba
        spec["conv"] = ((n_mamba, batch, mm.d_conv - 1, cfg.d_inner), dtype)
        spec["ssm"] = ((n_mamba, batch, cfg.d_inner, mm.d_state), jnp.float32)
    if "rwkv6" in cfg.mixers:
        L = cfg.n_layers
        nH = cfg.d_model // rwkv_mod.HEAD_SIZE
        spec["rwkv_x"] = ((L, batch, cfg.d_model), dtype)
        spec["rwkv_s"] = ((L, batch, nH, rwkv_mod.HEAD_SIZE,
                           rwkv_mod.HEAD_SIZE), jnp.float32)
    if cfg.enc_dec:
        L = cfg.n_layers
        spec["cross_k"] = ((L, batch, cfg.enc_len, KV, hd), dtype)
        spec["cross_v"] = ((L, batch, cfg.enc_len, KV, hd), dtype)
    return spec


def sp_identity_tables(batch: int, M: int, N: int, batch_shards: int = 1,
                       seq_shards: int = 1):
    """Global block-table layout consistent with an (batch × seq)-sharded
    pool: data shard ``di`` owns pool partitions ``di*seq + s`` (each
    ``Nl = N/(batch_shards*seq_shards)`` rows); block column ``m`` of local
    sequence ``bl`` lives on seq shard ``m // M_loc`` at local row
    ``bl*M_loc + m%M_loc``.  With (1,1) this is the identity ``b*M + m``."""
    import numpy as np
    Bl = batch // batch_shards
    M_loc = (M + seq_shards - 1) // seq_shards
    Nl = N // (batch_shards * seq_shards)
    assert Bl * M_loc <= Nl, (
        f"pool too small: need {Bl}x{M_loc} rows per shard, have {Nl}")
    b = np.arange(batch)[:, None]
    m = np.arange(M)[None, :]
    di, bl = b // Bl, b % Bl
    s, ml = m // M_loc, m % M_loc
    g = (di * seq_shards + s) * Nl + bl * M_loc + ml
    return jnp.asarray(g, jnp.int32)


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      num_blocks: int | None = None, dtype=jnp.bfloat16,
                      tables: jax.Array | None = None,
                      lengths: jax.Array | None = None,
                      batch_shards: int = 1, seq_shards: int = 1) -> dict:
    spec = cache_spec(cfg, batch, max_len, num_blocks, dtype,
                      round_to=batch_shards * seq_shards)
    st = {k: jnp.zeros(sh, dt) for k, (sh, dt) in spec.items()}
    if tables is not None:
        st["tables"] = tables
    else:
        (B_, M), _ = spec["tables"]
        N = spec["kv"][0][1] if "kv" in spec else (
            spec["mla_c"][0][1] if "mla_c" in spec else batch * M)
        st["tables"] = sp_identity_tables(batch, M, N, batch_shards,
                                          seq_shards)
    st["lengths"] = (lengths if lengths is not None
                     else jnp.zeros((batch,), jnp.int32))
    return st


# ================================================================ decode step
def _paged_attn(q, kv_pool, tables, lengths, *, page_impl, window,
                mesh=None, batch_axes=(), seq_axes=()):
    """Dispatch one decode-attention step over either table layout.

    ``kv_pool`` is the fused ``(N, 2, KV, bs, hd)`` pool;
    ``tables`` is the monolithic ``(B, M)`` table or the device-native
    ``(W, Bs, M)`` shard stack.  The Pallas kernel consumes both
    directly (shard-native page walk over one-DMA fused blocks, with the
    autotuned multi-depth pipeline); the jnp reference and the
    sequence-parallel collectives see the split K/V *views* of the fused
    pool and the monolithic table through traced slices/transposes
    (never a host-side rebuild).
    """
    B = q.shape[0]
    if page_impl in ("pallas", "pallas_interpret"):
        from repro.kernels.paged_attention import autotune as pa_autotune
        from repro.kernels.paged_attention import ops as pa_ops
        _, _, KV, bs, hd = kv_pool.shape
        tuned = pa_autotune.get_tuning(KV, hd, bs)
        return pa_ops.paged_attention(
            q, kv_pool, tables, lengths, window=window,
            buffer_depth=tuned.buffer_depth,
            interpret=(page_impl == "pallas_interpret"))
    k_pool, v_pool = attn_mod.split_fused_kv(kv_pool)
    if page_impl in ("sp", "sp_opt"):
        from repro.distributed.collectives import paged_decode_attention_sp
        return paged_decode_attention_sp(
            q, k_pool, v_pool,
            attn_mod.assemble_shard_tables(tables)[:B], lengths, mesh=mesh,
            batch_axes=batch_axes, seq_axes=seq_axes, window=window,
            table_cols_sharded=(page_impl == "sp_opt"))
    return attn_mod.paged_decode_attention_ref(
        q, k_pool, v_pool, attn_mod.assemble_shard_tables(tables)[:B],
        lengths, window=window)


def _token_blocks(tables, lengths, bs, n_blocks):
    """Physical block and in-block offset of each slot's incoming token
    (0-based position ``lengths``).  Non-resident (<0) table entries map
    to ``n_blocks``, past the pool end, so a ``mode="drop"`` scatter
    drops them (negative indices would *wrap*, not drop)."""
    blk_idx = lengths // bs                          # (B,)
    phys = attn_mod.lookup_slot_blocks(
        tables, jnp.arange(lengths.shape[0]),
        jnp.minimum(blk_idx, tables.shape[-1] - 1))
    return jnp.where(phys >= 0, phys, n_blocks), lengths % bs


def _write_fused_kv(kvp, phys, off, rows):
    """Scatter token rows (T, 2, KV, hd) into the fused (N, 2, KV, bs, hd)
    layer pool: row t lands in block ``phys[t]`` at in-block offset
    ``off[t]``; ``phys[t] >= N`` drops it."""
    return kvp.at[phys, :, :, off].set(rows.astype(kvp.dtype), mode="drop")


def _write_token_kv(pool, tables, lengths, new, bs):
    """Scatter one token's row new (B, ...) into a latent (N, bs, ...)
    paged pool."""
    phys, off = _token_blocks(tables, lengths, bs, pool.shape[0])
    return pool.at[phys, off].set(new.astype(pool.dtype), mode="drop")


def _write_token_kv_stacked(pool, layer, tables, lengths, new, bs):
    """Per-layer-slice scatter into the stacked (L, N, bs, …) pool.

    The scatter runs on the (N, bs, …) layer slice, not the full stack:
    XLA:CPU lowers bf16 scatter via an f32 round-trip of the *operand*, so
    a full-stack scatter would materialise two pool-sized f32 temps per
    write (60× per decode step).  The slice is re-inserted with an in-place
    dynamic-update-slice.  (On TPU both forms scatter in place.)"""
    sl = _write_token_kv(
        jax.lax.index_in_dim(pool, layer, keepdims=False),
        tables, lengths, new, bs)
    return pool.at[layer].set(sl)


def decode_step(params, cfg: ModelConfig, state: dict, tokens: jax.Array, *,
                page_impl: str = "ref", mesh=None, batch_axes=(),
                seq_axes=(), moe_groups: int = 1):
    """One decode step: tokens (B,) int32 → (logits (B,V) f32, new state).

    ``state['lengths']`` counts tokens already in the cache; the incoming
    token is written at position ``lengths`` and attends to ``lengths+1``
    tokens (itself included).  Unrolled over layers.
    """
    B = tokens.shape[0]
    bs = BLOCK_SIZE
    st = dict(state)
    pos = st["lengths"]                              # (B,) position of token
    if mesh is not None and "model" in mesh.axis_names:
        from repro.distributed.collectives import vocab_parallel_embed
        ba = tuple(batch_axes)
        bspec = ba if len(ba) != 1 else (ba[0] if ba else None)
        act_spec = jax.sharding.PartitionSpec(bspec, None)
        x = vocab_parallel_embed(tokens, params["embed"], mesh=mesh,
                                 dp_spec=bspec)
    else:
        act_spec = None
        x = embed(tokens, params["embed"])           # (B, D)
    if cfg.enc_dec:
        x = x + params["dec_pos"][jnp.minimum(pos, 4095)]
    positions = pos[:, None]

    prefix, period = cfg.segmentation()
    aidx = midx = 0          # per-kind pool cursors
    attn_ids = attn_layer_ids(cfg)
    mamba_ids = mamba_layer_ids(cfg)

    def layer_params(i):
        if i < prefix:
            return params["prefix"][i]
        j = (i - prefix) % period
        b = (i - prefix) // period
        return jax.tree.map(lambda t: t[b], params["body"][j])

    for i in range(cfg.n_layers):
        lp = layer_params(i)
        mix, ffn = cfg.layer_sig(i)
        if mix == "attn":
            a = attn_ids.index(i)
            h = rms_norm(x[:, None], lp["mix"]["norm"], cfg.norm_eps)
            q, k, v = attn_mod.qkv_proj(lp["mix"], h, cfg, positions)
            # scatter into the layer slice, then re-insert it (see
            # _write_token_kv_stacked)
            kvp = _write_fused_kv(
                st["kv"][a],
                *_token_blocks(st["tables"], pos, bs, st["kv"].shape[1]),
                jnp.stack([k[:, 0], v[:, 0]], axis=1))
            st["kv"] = st["kv"].at[a].set(kvp)
            o = _paged_attn(q[:, 0], kvp, st["tables"],
                            pos + 1, page_impl=page_impl,
                            window=cfg.attn.window, mesh=mesh,
                            batch_axes=batch_axes, seq_axes=seq_axes)
            x = x + o.reshape(B, -1) @ lp["mix"]["wo"]
        elif mix == "mla":
            h = rms_norm(x[:, None], lp["mix"]["norm"], cfg.norm_eps)
            c_kv, k_rope = mla_mod.latent_kv(lp["mix"], h, cfg, positions)
            st["mla_c"] = _write_token_kv_stacked(
                st["mla_c"], i, st["tables"], pos, c_kv[:, 0], bs)
            st["mla_rope"] = _write_token_kv_stacked(
                st["mla_rope"], i, st["tables"], pos, k_rope[:, 0, 0], bs)
            x = _mla_paged_decode(lp["mix"], x, pos, st, i, cfg,
                                  page_impl=page_impl, mesh=mesh,
                                  batch_axes=batch_axes, seq_axes=seq_axes)
        elif mix == "mamba":
            m = mamba_ids.index(i)
            y, (cs, ss) = mamba_mod.mamba_decode_step(
                lp["mix"], x, cfg, st["conv"][m], st["ssm"][m])
            x = y
            st["conv"] = st["conv"].at[m].set(cs)
            st["ssm"] = st["ssm"].at[m].set(ss)
        elif mix == "rwkv6":
            y, (lx, s_new) = rwkv_mod.rwkv6_decode_step(
                lp["mix"], x, cfg, st["rwkv_x"][i], st["rwkv_s"][i])
            x = y
            st["rwkv_x"] = st["rwkv_x"].at[i].set(lx.astype(st["rwkv_x"].dtype))
            st["rwkv_s"] = st["rwkv_s"].at[i].set(s_new)

        if cfg.enc_dec:
            x = attn_mod.cross_attn_layer(
                lp["cross"], x[:, None],
                (st["cross_k"][i], st["cross_v"][i]), cfg)[:, 0]

        if ffn == "dense":
            from repro.models.layers import dense_ffn
            x = dense_ffn(lp["ffn"], x[:, None], cfg.norm_eps)[:, 0]
        else:
            out, _ = moe_mod.moe_ffn(
                lp["ffn"], x[:, None], cfg, num_groups=moe_groups,
                ep_axis="model" if act_spec is not None else None)
            x = out[:, 0]
        x = _constrain(x, act_spec)

    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(h[:, None], table)[:, 0]
    st["lengths"] = pos + 1
    return logits, st


def _mla_paged_decode(lp, x, positions, st, layer, cfg, *, page_impl, mesh,
                      batch_axes, seq_axes):
    if page_impl in ("pallas", "pallas_interpret"):
        # shard-native: the MLA kernel walks the (W, Bs, M) stack through
        # the same _table_index arithmetic as paged_attention — no traced
        # transpose is materialized on this path
        from repro.kernels.mla_attention import ops as mla_ops
        return mla_ops.mla_paged_decode(
            lp, x, positions, st["mla_c"][layer], st["mla_rope"][layer],
            st["tables"], st["lengths"] + 1, cfg,
            interpret=(page_impl == "pallas_interpret"))
    # the jnp reference and sp collectives view the stack monolithically
    # through a traced transpose (never a host-side rebuild)
    tables = attn_mod.assemble_shard_tables(st["tables"])[:x.shape[0]]
    if page_impl in ("sp", "sp_opt"):
        from repro.distributed.collectives import mla_decode_sp
        return mla_decode_sp(lp, x, positions, st["mla_c"][layer],
                             st["mla_rope"][layer], tables,
                             st["lengths"] + 1, cfg, mesh=mesh,
                             batch_axes=batch_axes, seq_axes=seq_axes,
                             table_cols_sharded=(page_impl == "sp_opt"))
    return mla_mod.mla_decode_ref(lp, x, positions, st["mla_c"][layer],
                                  st["mla_rope"][layer], tables,
                                  st["lengths"] + 1, cfg)


# ================================================================== prefill
def prefill(params, cfg: ModelConfig, tokens: jax.Array, state: dict, *,
            impl: str = "chunked", enc_frames=None, patches=None,
            moe_groups: int = 1, remat: bool = True, mesh=None,
            batch_axes=(), seq_axes=("model",)):
    """Process a full prompt, write every cache, return (last_logits, state).

    tokens: (B, S).  The caches land exactly where decode_step expects them
    (token t of sequence b → pool[tables[b, t//bs], t%bs]).
    """
    B, S = tokens.shape
    bs = BLOCK_SIZE
    st = dict(state)
    if mesh is not None and "model" in mesh.axis_names:
        ba = tuple(batch_axes)
        bspec = ba if len(ba) != 1 else (ba[0] if ba else None)
        act_spec = jax.sharding.PartitionSpec(bspec, None, None)
    else:
        act_spec = None
    enc_out = encode(params, cfg, enc_frames) if cfg.enc_dec else None
    x = embed_inputs(params, cfg, tokens, patches, mesh=mesh,
                     act_spec=act_spec)
    S_tot = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S_tot)[None], (B, S_tot))
    prefix, period = cfg.segmentation()
    attn_ids = attn_layer_ids(cfg)
    mamba_ids = mamba_layer_ids(cfg)

    def run_layer(lp, x, i):
        """Returns (x, cache-dict for this layer)."""
        mix, ffn = cfg.layer_sig(i)
        cache = {}
        if mix == "attn":
            h = rms_norm(x, lp["mix"]["norm"], cfg.norm_eps)
            q, k, v = attn_mod.qkv_proj(lp["mix"], h, cfg, positions)
            o = attn_mod.chunked_attention(q, k, v, causal=True,
                                           window=cfg.attn.window)
            B_, S_, H, hd = o.shape
            x = x + o.reshape(B_, S_, H * hd) @ lp["mix"]["wo"]
            cache["kv"] = (k, v)
        elif mix == "mla":
            x, (c_kv, k_rope) = _mla_layer_with_cache(lp["mix"], x,
                                                      positions, cfg)
            cache["mla"] = (c_kv, k_rope)
        elif mix == "mamba":
            h = rms_norm(x, lp["mix"]["norm"], cfg.norm_eps)
            y, (cs, ss) = mamba_mod.mamba_mix(lp["mix"], h, cfg, impl=impl)
            x = x + y
            cache["mamba"] = (cs, ss)
        elif mix == "rwkv6":
            h = rms_norm(x, lp["mix"]["norm"], cfg.norm_eps)
            y, (lx, s_new) = rwkv_mod.rwkv6_mix(lp["mix"], h, cfg, impl=impl)
            x = x + y
            cache["rwkv"] = (lx, s_new)
        if cfg.enc_dec:
            kc, vc = attn_mod.encode_cross_kv(lp["cross"], enc_out, cfg)
            x = attn_mod.cross_attn_layer(lp["cross"], x, (kc, vc), cfg)
            cache["cross"] = (kc, vc)
        if ffn == "dense":
            from repro.models.layers import dense_ffn
            x = dense_ffn(lp["ffn"], x, cfg.norm_eps)
        else:
            x, _ = moe_mod.moe_ffn(
                lp["ffn"], x, cfg, num_groups=moe_groups,
                ep_axis="model" if act_spec is not None else None,
                dp_axis=act_spec[0] if act_spec is not None else None)
        return _constrain(x, act_spec), cache

    # ---- streaming cache writes (inside the layer scan) --------------------
    # Stacking every layer's (B, S, KV, hd) cache out of the scan and
    # scattering afterwards would materialise the entire KV cache a second
    # time (tens of GB/chip for prefill_32k); instead each layer scatters
    # its rows into the pools as it runs, and the pools ride the scan carry.
    tables_const = attn_mod.assemble_shard_tables(st["tables"])[:B]

    def to_blocks(seq):
        """seq: (B, S_tot, ...) → per-block rows (B * M_used, bs, ...)."""
        pad = (-S_tot) % bs
        if pad:
            seq = jnp.pad(seq, ((0, 0), (0, pad)) + ((0, 0),) * (seq.ndim - 2))
        return seq.reshape((-1, bs) + seq.shape[2:])

    def scatter_seq(pool, seq):
        """seq: (B * M_used, ...) pool blocks → paged pool (N, ...); <0
        entries drop."""
        M_used = seq.shape[0] // B
        tab = tables_const[:, :M_used].reshape(-1)
        if mesh is not None and "model" in mesh.axis_names:
            from repro.distributed.collectives import scatter_seq_sp
            return scatter_seq_sp(pool, seq, tab, mesh=mesh,
                                  batch_axes=batch_axes,
                                  seq_axes=seq_axes)
        neg = jnp.where(tab >= 0, tab, pool.shape[0])
        return pool.at[neg].set(seq.astype(pool.dtype), mode="drop")

    def _dyn_write(pool, idx, value):
        """pool[idx] = value with a (possibly traced) leading index."""
        return jax.lax.dynamic_update_index_in_dim(
            pool, value, idx, 0)

    def _dyn_scatter(pool, idx, seq):
        cur = jax.lax.dynamic_index_in_dim(pool, idx, 0, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(
            pool, scatter_seq(cur, seq), idx, 0)

    def write_caches(stt, i_dyn, a_dyn, m_dyn, c):
        if "kv" in c:
            k, v = c["kv"]
            stt["kv"] = _dyn_scatter(
                stt["kv"], a_dyn, attn_mod.fuse_kv(to_blocks(k),
                                                   to_blocks(v)))
        if "mla" in c and c["mla"] is not None:
            ckv, krope = c["mla"]
            stt["mla_c"] = _dyn_scatter(stt["mla_c"], i_dyn, to_blocks(ckv))
            stt["mla_rope"] = _dyn_scatter(stt["mla_rope"], i_dyn,
                                           to_blocks(krope))
        if "mamba" in c:
            cs, ss = c["mamba"]
            stt["conv"] = _dyn_write(stt["conv"], m_dyn,
                                     cs.astype(stt["conv"].dtype))
            stt["ssm"] = _dyn_write(stt["ssm"], m_dyn, ss)
        if "rwkv" in c:
            lx, s_new = c["rwkv"]
            stt["rwkv_x"] = _dyn_write(stt["rwkv_x"], i_dyn,
                                       lx.astype(stt["rwkv_x"].dtype))
            stt["rwkv_s"] = _dyn_write(stt["rwkv_s"], i_dyn, s_new)
        if "cross" in c:
            kc, vc = c["cross"]
            stt["cross_k"] = _dyn_write(stt["cross_k"], i_dyn,
                                        kc.astype(stt["cross_k"].dtype))
            stt["cross_v"] = _dyn_write(stt["cross_v"], i_dyn,
                                        vc.astype(stt["cross_v"].dtype))
        return stt

    pool_keys = [k for k in st if k not in ("tables", "lengths")]
    pools = {k: st[k] for k in pool_keys}

    for i in range(prefix):
        x, c = run_layer(params["prefix"][i], x, i)
        a = attn_ids.index(i) if cfg.mixers[i] == "attn" else 0
        m = mamba_ids.index(i) if cfg.mixers[i] == "mamba" else 0
        pools = write_caches(pools, i, a, m, c)

    if period and params["body"]:
        sigs = [cfg.layer_sig(prefix + j) for j in range(period)]
        attn_js = [j for j in range(period) if sigs[j][0] == "attn"]
        mamba_js = [j for j in range(period) if sigs[j][0] == "mamba"]
        attn_base = sum(1 for i in range(prefix) if cfg.mixers[i] == "attn")
        mamba_base = sum(1 for i in range(prefix)
                         if cfg.mixers[i] == "mamba")
        n_blocks = (cfg.n_layers - prefix) // period

        def blk(carry, inp):
            x, pl = carry
            lps, b = inp
            for j in range(period):
                x, c = run_layer(lps[j], x, prefix + j)   # sig via static j
                i_dyn = prefix + b * period + j
                a_dyn = (attn_base + b * len(attn_js)
                         + (attn_js.index(j) if j in attn_js else 0))
                m_dyn = (mamba_base + b * len(mamba_js)
                         + (mamba_js.index(j) if j in mamba_js else 0))
                pl = write_caches(pl, i_dyn, a_dyn, m_dyn, c)
            return (x, pl), None

        (x, pools), _ = jax.lax.scan(
            blk, (x, pools), (params["body"], jnp.arange(n_blocks)))

    st.update(pools)
    h = rms_norm(x[:, -1], params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(h[:, None], table)[:, 0]
    st["lengths"] = jnp.full((B,), S_tot, jnp.int32)
    return logits, st


def prefill_chunk(params, cfg: ModelConfig, tokens: jax.Array, state: dict,
                  start, *, moe_groups: int = 1) -> dict:
    """One fixed-shape prefill chunk: tokens (B, C) at positions
    ``start .. start+C-1`` (C a BLOCK_SIZE multiple; ``start``
    block-aligned and *traced*, so the whole function compiles once per
    (B, C) shape — no per-prompt-length retrace).

    Attention-only decoder models (the engine gates on that): each layer
    scatters the chunk's K/V rows into the paged pools at the chunk's
    table columns, then attends the *full* gathered window with
    ``q_offset=start``.  Keys past the causal frontier are garbage pool
    rows, but the online softmax masks them to ``NEG_INF`` and
    ``exp(NEG_INF - m)`` underflows to exactly ``0.0`` — so for every real
    query row the result matches monolithic :func:`prefill` bit-for-bit
    whenever the pool dtype round-trips K/V exactly (float32 caches; the
    engine's bit-identity tests pin it).  Chunk-pad rows past the prompt
    write deterministic garbage that decode rewrites position-by-position
    before ever attending it.  Returns the new state dict; logits are not
    computed — the engine's first decode step rewrites position S−1 and
    produces them, identically in both prefill paths.
    """
    if any(m != "attn" for m in cfg.mixers) or cfg.enc_dec:
        raise NotImplementedError(
            "prefill_chunk supports attention-only decoder models; "
            f"got mixers={cfg.mixers} enc_dec={cfg.enc_dec}")
    B, C = tokens.shape
    bs = BLOCK_SIZE
    if C % bs:
        raise ValueError(f"chunk length {C} must be a multiple of "
                         f"BLOCK_SIZE={bs}")
    st = dict(state)
    x = embed_inputs(params, cfg, tokens)
    positions = jnp.broadcast_to(start + jnp.arange(C)[None], (B, C))
    prefix, period = cfg.segmentation()
    tables_const = attn_mod.assemble_shard_tables(st["tables"])[:B]
    M = tables_const.shape[1]
    Cb = C // bs
    # the chunk's table columns: a traced window of Cb columns starting at
    # start//bs; columns past the window width map to -1 (writes drop)
    cols = start // bs + jnp.arange(Cb)                       # (Cb,)
    chunk_tab = jnp.where(cols[None, :] < M,
                          jnp.take(tables_const,
                                   jnp.minimum(cols, M - 1), axis=1),
                          -1)                                 # (B, Cb)

    def scatter_chunk(pool, k, v):
        """k/v (B, C, KV, hd) → the chunk's fused pool blocks; <0 entries
        drop."""
        blocks = attn_mod.fuse_kv(k.reshape((B * Cb, bs) + k.shape[2:]),
                                  v.reshape((B * Cb, bs) + v.shape[2:]))
        tab = chunk_tab.reshape(-1)
        neg = jnp.where(tab >= 0, tab, pool.shape[0])
        return pool.at[neg].set(blocks.astype(pool.dtype), mode="drop")

    def gather_window(pool):
        """Full-window keys and values, (B, M*bs, KV, hd) each —
        unallocated (-1) columns gather arbitrary resident rows; they sit
        past the causal frontier and the attention mask zeroes them
        exactly."""
        rows = jnp.take(pool, jnp.maximum(tables_const, 0).reshape(-1),
                        axis=0)
        k, v = attn_mod.split_fused_kv(rows)         # (B*M, bs, KV, hd)
        return (k.reshape((B, M * bs) + k.shape[2:]),
                v.reshape((B, M * bs) + v.shape[2:]))

    def run_layer(lp, x, pools, a_dyn, sig):
        _, ffn = sig
        h = rms_norm(x, lp["mix"]["norm"], cfg.norm_eps)
        q, k, v = attn_mod.qkv_proj(lp["mix"], h, cfg, positions)
        kvp = jax.lax.dynamic_index_in_dim(pools["kv"], a_dyn, 0,
                                           keepdims=False)
        kvp = scatter_chunk(kvp, k, v)
        pools = dict(pools)
        pools["kv"] = jax.lax.dynamic_update_index_in_dim(pools["kv"], kvp,
                                                          a_dyn, 0)
        kw, vw = gather_window(kvp)
        o = attn_mod.chunked_attention_fwd(
            q, kw, vw, causal=True,
            window=cfg.attn.window, q_offset=start)
        B_, C_, H, hd = o.shape
        x = x + o.reshape(B_, C_, H * hd) @ lp["mix"]["wo"]
        if ffn == "dense":
            from repro.models.layers import dense_ffn
            x = dense_ffn(lp["ffn"], x, cfg.norm_eps)
        else:
            x, _ = moe_mod.moe_ffn(lp["ffn"], x, cfg,
                                   num_groups=moe_groups)
        return x, pools

    pools = {k: st[k] for k in st if k not in ("tables", "lengths")}
    for i in range(prefix):
        x, pools = run_layer(params["prefix"][i], x, pools, i,
                             cfg.layer_sig(i))
    if period and params["body"]:
        sigs = [cfg.layer_sig(prefix + j) for j in range(period)]
        n_blocks = (cfg.n_layers - prefix) // period

        def blk(carry, inp):
            x, pl = carry
            lps, b = inp
            for j in range(period):
                # all-attn model: attn pool index of layer i is i itself
                x, pl = run_layer(lps[j], x, pl, prefix + b * period + j,
                                  sigs[j])
            return (x, pl), None

        (x, pools), _ = jax.lax.scan(
            blk, (x, pools), (params["body"], jnp.arange(n_blocks)))
    st.update(pools)
    return st


def ragged_step(params, cfg: ModelConfig, state: dict, tokens: jax.Array,
                token_row: jax.Array, token_pos: jax.Array,
                tile_row: jax.Array, tile_pos: jax.Array,
                kv_lens: jax.Array, last_index: jax.Array, *,
                page_impl: str = "ref",
                moe_groups: int = 1) -> tuple[jax.Array, dict]:
    """One *ragged* engine step: every chunked-prefill AND decode row of
    the scheduler batch packed into one fixed-shape token stream and one
    attention kernel call per layer.

    tokens/token_row/token_pos: (T,) packed incoming tokens, their batch
    slots (-1 = padding, writes drop) and global positions; tile_row/
    tile_pos: (T // QT,) per-query-tile descriptor for the kernel;
    kv_lens: (num_slots,) kv length of each slot *after* this step's
    writes land; last_index: (num_slots,) packed index of each slot's
    final real token (-1 = inactive slot — its logits row is garbage and
    its length is left untouched).  Every shape but ``T`` is fixed by
    the cache, so one program per ``T`` serves any blend of prefill
    chunks and single-token decodes that fits it.  Returns (logits
    (num_slots, V) gathered at each slot's last token, new state).

    Per layer the incoming fused K/V rows are scattered *before* the
    attention call (so a chunk attends its own tokens, matching
    :func:`prefill_chunk`), and the ragged fused kernel masks causality,
    length, window and holes per (query, key) element — decode rows are
    simply q_len-1 chunks, so the numerics match :func:`decode_step` and
    :func:`prefill_chunk` exactly.
    """
    if any(m != "attn" for m in cfg.mixers) or cfg.enc_dec:
        raise NotImplementedError(
            "ragged_step supports attention-only decoder models; "
            f"got mixers={cfg.mixers} enc_dec={cfg.enc_dec}")
    T = tokens.shape[0]
    bs = BLOCK_SIZE
    st = dict(state)
    M = st["tables"].shape[-1]
    x = embed(tokens, params["embed"])                   # (T, D)
    positions = token_pos[None]                          # (1, T)
    prefix, period = cfg.segmentation()

    # per-token scatter targets: padding rows, non-resident blocks and
    # out-of-window positions all map past the pool end (mode="drop")
    valid = token_row >= 0
    slot = jnp.maximum(token_row, 0)
    blk_idx = token_pos // bs
    off = token_pos % bs
    phys = attn_mod.lookup_slot_blocks(
        st["tables"], slot, jnp.minimum(blk_idx, M - 1))
    drop = valid & (phys >= 0) & (blk_idx < M)

    def ragged_attn(q, kvp):
        """q: (T, H, hd) over the fused (N, 2, KV, bs, hd) layer pool."""
        if page_impl in ("pallas", "pallas_interpret"):
            from repro.kernels.paged_attention import ops as pa_ops
            return pa_ops.ragged_paged_attention(
                q, kvp, st["tables"], tile_row, tile_pos, kv_lens,
                window=cfg.attn.window,
                interpret=(page_impl == "pallas_interpret"))
        from repro.kernels.paged_attention.ref import ragged_fused_ref
        return ragged_fused_ref(q, kvp, st["tables"], token_row,
                                token_pos, kv_lens,
                                window=cfg.attn.window)

    def run_layer(lp, x, pools, a_dyn, sig):
        _, ffn = sig
        h = rms_norm(x[None], lp["mix"]["norm"], cfg.norm_eps)
        q, k, v = attn_mod.qkv_proj(lp["mix"], h, cfg, positions)
        rows = jnp.stack([k[0], v[0]], axis=1)           # (T, 2, KV, hd)
        kvp = jax.lax.dynamic_index_in_dim(pools["kv"], a_dyn, 0,
                                           keepdims=False)
        tgt = jnp.where(drop, jnp.maximum(phys, 0), kvp.shape[0])
        kvp = _write_fused_kv(kvp, tgt, off, rows)
        pools = dict(pools)
        pools["kv"] = jax.lax.dynamic_update_index_in_dim(
            pools["kv"], kvp, a_dyn, 0)
        o = ragged_attn(q[0], kvp)                       # (T, H, hd)
        x = x + o.reshape(T, -1) @ lp["mix"]["wo"]
        if ffn == "dense":
            from repro.models.layers import dense_ffn
            x = dense_ffn(lp["ffn"], x[None], cfg.norm_eps)[0]
        else:
            out, _ = moe_mod.moe_ffn(lp["ffn"], x[None], cfg,
                                     num_groups=moe_groups)
            x = out[0]
        return x, pools

    pools = {k: st[k] for k in st if k not in ("tables", "lengths")}
    for i in range(prefix):
        x, pools = run_layer(params["prefix"][i], x, pools, i,
                             cfg.layer_sig(i))
    if period and params["body"]:
        sigs = [cfg.layer_sig(prefix + j) for j in range(period)]
        n_blocks = (cfg.n_layers - prefix) // period

        def blk(carry, inp):
            x, pl = carry
            lps, b = inp
            for j in range(period):
                x, pl = run_layer(lps[j], x, pl, prefix + b * period + j,
                                  sigs[j])
            return (x, pl), None

        (x, pools), _ = jax.lax.scan(
            blk, (x, pools), (params["body"], jnp.arange(n_blocks)))
    st.update(pools)

    h = rms_norm(x, params["final_norm"], cfg.norm_eps)  # (T, D)
    h_last = h[jnp.maximum(last_index, 0)]               # (slots, D)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(h_last[:, None], table)[:, 0]
    st["lengths"] = jnp.where(last_index >= 0,
                              kv_lens[:st["lengths"].shape[0]].astype(
                                  jnp.int32),
                              st["lengths"])
    return logits, st
