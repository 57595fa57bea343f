"""SP collectives: distributed flash-decode over the sharded FPR pool.

Decode shards the **physical block pool** (the N dimension) over mesh axes
rather than sharding KV heads — uniform across all ten archs (no KV-head /
mesh divisibility constraints) and exactly the flash-decode design:

    pool partition p = (batch_shard · n_seq + seq_shard)     (row-major)
    data shard owns its batch rows' blocks; model shards split each
    sequence; per-shard online-softmax partials merge with the LSE combine

        m = pmax(m_s)   l = Σ l_s·e^{m_s−m}   acc = Σ acc_s·e^{m_s−m}

— one f32 (B, H) pmax + two psums per layer instead of all-gathering the
pool (GSPMD's default for a global gather through the block table, which
for decode_32k would move the entire multi-TB cache every step).

Block tables hold *global* physical indices; each shard subtracts its pool
offset and masks rows outside its window, so the FPR translation layer
(core/block_table) is untouched.  Projections outside the softmax core
stay in global pjit semantics.

Layout contract (matches transformer.sp_identity_tables and
sharding.decode_state_specs):
    pool:            P(batch_axes + seq_axes) on N
    q/tables/lengths P(batch_axes) on B
    combine over     seq_axes (empty ⇒ pure batch-local, no collective)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

NEG_INF = -1e30


def _axis_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _shard_offset(mesh, batch_axes, seq_axes, Nl):
    """Pool-row offset of this shard inside the global pool."""
    bidx = jnp.zeros((), jnp.int32)
    for a in batch_axes:
        bidx = bidx * mesh.shape[a] + jax.lax.axis_index(a)
    sidx = jnp.zeros((), jnp.int32)
    for a in seq_axes:
        sidx = sidx * mesh.shape[a] + jax.lax.axis_index(a)
    n_seq = _axis_size(mesh, seq_axes)
    return (bidx * n_seq + sidx) * Nl


def _localize(tables, offset, Nl):
    local = tables - offset
    return jnp.where((tables >= 0) & (local >= 0) & (local < Nl), local, -1)


def _pvary(x, axes):
    """Mark a shard-invariant init as varying over ``axes`` (scan inside
    shard_map requires carry in/out varying-axis types to match)."""
    if not axes:
        return x
    return jax.lax.pcast(x, tuple(axes), to="varying")


def _lse_combine(m, l, acc, axes):
    if not axes:
        return acc / jnp.maximum(l, 1e-30)[..., None]
    m_g = jax.lax.pmax(m, axes)
    scale = jnp.exp(m - m_g)
    l_g = jax.lax.psum(l * scale, axes)
    acc_g = jax.lax.psum(acc * scale[..., None], axes)
    return acc_g / jnp.maximum(l_g, 1e-30)[..., None]


def _bspec(batch_axes):
    ba = tuple(batch_axes)
    return ba if len(ba) != 1 else ba[0]


# ----------------------------------------------------------- local partials
def _paged_partials(q, k_pool, v_pool, tables, lengths, *,
                    window: int | None, chunk_bytes: int = 1 << 27,
                    vary_axes=(), pos_base=0):
    """Un-normalised attention over one pool shard, chunked over the block
    table so the gathered KV copy never exceeds ~``chunk_bytes`` live
    (the naive full-table gather for decode_32k is 2 GB × 2 pools × per
    layer — the difference between fitting HBM and not).

    q: (B, KV, G, hd) f32; pools: (Nl, bs, KV, hd); tables: (B, M) *local*
    physical indices (<0 ⇒ not this shard / hole).  Returns m, l (B, KV, G),
    acc (B, KV, G, hd).
    """
    B, KV, G, hd = q.shape
    Nl, bs, _, _ = k_pool.shape
    M = tables.shape[1]
    row_bytes = B * bs * KV * hd * k_pool.dtype.itemsize
    bpc = max(1, min(M, chunk_bytes // max(1, row_bytes)))
    padM = (-M) % bpc
    if padM:
        tables = jnp.pad(tables, ((0, 0), (0, padM)), constant_values=-1)
    nch = tables.shape[1] // bpc
    tc = tables.reshape(B, nch, bpc).transpose(1, 0, 2)    # (nch, B, bpc)

    def step(carry, inp):
        m, l, acc = carry
        ci, tb = inp                                       # tb: (B, bpc)
        tclamp = jnp.clip(tb, 0, Nl - 1)
        k = jnp.take(k_pool, tclamp, axis=0).reshape(B, bpc * bs, KV, hd)
        v = jnp.take(v_pool, tclamp, axis=0).reshape(B, bpc * bs, KV, hd)
        s = jnp.einsum("bkgd,bskd->bkgs", q,
                       k.astype(jnp.float32)) * (hd ** -0.5)
        pos = (pos_base + ci * bpc * bs + jnp.arange(bpc * bs))[None, :]
        valid = (pos < lengths[:, None]) & jnp.repeat(tb >= 0, bs, axis=1)
        if window is not None:
            valid &= pos > lengths[:, None] - 1 - window
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None]) * valid[:, None, None, :]
        scale = jnp.exp(m - m_new)
        l = l * scale + p.sum(axis=-1)
        acc = acc * scale[..., None] + jnp.einsum(
            "bkgs,bskd->bkgd", p, v.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = _pvary(jnp.full((B, KV, G), NEG_INF, jnp.float32), vary_axes)
    l0 = _pvary(jnp.zeros((B, KV, G), jnp.float32), vary_axes)
    a0 = _pvary(jnp.zeros((B, KV, G, hd), jnp.float32), vary_axes)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (jnp.arange(nch), tc))
    return m, l, acc


# --------------------------------------------------- vocab-parallel embed
def vocab_parallel_embed(tokens, table, *, mesh, dp_spec=None,
                         axis: str = "model"):
    """Embedding lookup with the table V-sharded over ``axis``.

    GSPMD's handling of a gather from a vocab-sharded table is fragile
    (involuntary full rematerialisation, and an outright partitioner
    mis-compile when the output sharding is constrained — see dryrun
    notes); the explicit form is one masked local gather + one psum:

        x = psum_axis( mask·table_local[tokens − offset] )

    tokens: (B, S) or (B,) int32; table: (V, D) with spec P(axis, None).
    """
    V, D = table.shape
    n = mesh.shape[axis]
    Vp = -(-V // n) * n
    if Vp != V:
        table = jnp.pad(table, ((0, Vp - V), (0, 0)))
    Vl = Vp // n
    tspec = P(dp_spec) if tokens.ndim == 1 else P(dp_spec, None)
    ospec = P(*tspec, None)

    def body(tab, tok):
        i = jax.lax.axis_index(axis)
        loc = tok - i * Vl
        ok = (loc >= 0) & (loc < Vl)
        x = jnp.take(tab, jnp.clip(loc, 0, Vl - 1), axis=0)
        x = jnp.where(ok[..., None], x, 0)
        return jax.lax.psum(x, axis)

    return shard_map(body, mesh=mesh,
                         in_specs=(P(axis, None), tspec),
                         out_specs=ospec)(table, tokens)


# --------------------------------------------------- SP prefill cache write
def scatter_seq_sp(pool, seq, tab, *, mesh, batch_axes=("data",),
                   seq_axes=("model",)):
    """Write prefill cache rows into the sharded pool without GSPMD's
    involuntary full-pool replication (a global scatter with arbitrary row
    indices replicates the pool on every chip — for prefill_32k that is
    the entire multi-TB cache).  Each shard localises the row indices to
    its own pool window and drops the rest.

    pool: (N, bs, …) P(ba+sa); seq: (R, bs, …) rows, R = B·M_used sharded
    over ba; tab: (R,) global physical rows, sharded over ba.
    """
    ba, sa = tuple(batch_axes), tuple(seq_axes)
    N = pool.shape[0]
    Nl = N // (_axis_size(mesh, ba) * _axis_size(mesh, sa))
    bspec = _bspec(ba)
    pool_spec = ba + sa if (ba or sa) else None
    nd_pool = pool.ndim
    nd_seq = seq.ndim

    def body(pl, sq, tb):
        off = _shard_offset(mesh, ba, sa, Nl)
        loc = tb - off
        loc = jnp.where((tb >= 0) & (loc >= 0) & (loc < Nl), loc, Nl)
        return pl.at[loc].set(sq.astype(pl.dtype), mode="drop")

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(pool_spec, *([None] * (nd_pool - 1))),
                  P(bspec, *([None] * (nd_seq - 1))), P(bspec)),
        out_specs=P(pool_spec, *([None] * (nd_pool - 1))),
    )(pool, seq, tab)


# ------------------------------------------------------------- GQA SP decode
def paged_decode_attention_sp(q, k_pool, v_pool, tables, lengths, *, mesh,
                              batch_axes=("data",), seq_axes=("model",),
                              window: int | None = None,
                              table_cols_sharded: bool = False):
    """SP decode attention; same contract as
    models.attention.paged_decode_attention_ref.

    ``table_cols_sharded`` — §Perf optimisation: with the identity block
    layout (column m lives on seq shard m // M_loc), each shard walks only
    its own M/n_seq table columns instead of masking through all of them —
    an n_seq× cut in gather/score work for the jnp path.
    """
    B, H, hd = q.shape
    N, bs, KV, _ = k_pool.shape
    G = H // KV
    M = tables.shape[1]
    ba, sa = tuple(batch_axes), tuple(seq_axes)
    n_seq = _axis_size(mesh, sa)
    Nl = N // (_axis_size(mesh, ba) * n_seq)
    bspec = _bspec(ba)
    pool_spec = ba + sa if (ba or sa) else None
    tspec = P(bspec, sa if table_cols_sharded else None)
    M_loc = M // n_seq if table_cols_sharded else M

    def body(qg, kp, vp, tb, ln):
        off = _shard_offset(mesh, ba, sa, Nl)
        pos_base = 0
        if table_cols_sharded:
            sidx = jnp.zeros((), jnp.int32)
            for a in sa:
                sidx = sidx * mesh.shape[a] + jax.lax.axis_index(a)
            pos_base = sidx * M_loc * bs
        m, l, acc = _paged_partials(qg.astype(jnp.float32), kp, vp,
                                    _localize(tb, off, Nl), ln,
                                    window=window, vary_axes=ba + sa,
                                    pos_base=pos_base)
        return _lse_combine(m, l, acc, sa)

    out = shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, None, None), P(pool_spec, None, None, None),
                  P(pool_spec, None, None, None), tspec, P(bspec)),
        out_specs=P(bspec, None, None, None),
    )(q.reshape(B, KV, G, hd), k_pool, v_pool, tables, lengths)
    return out.reshape(B, H, hd).astype(q.dtype)


# ------------------------------------------------------------- MLA SP decode
def mla_decode_sp(params, x, positions, c_pool, rope_pool, tables, lengths,
                  cfg, *, mesh, batch_axes=("data",), seq_axes=("model",),
                  table_cols_sharded: bool = False):
    """SP absorbed-MLA decode; same contract as models.mla.mla_decode_ref."""
    from repro.models.layers import rms_norm
    from repro.models.mla import _project_q, absorbed_weights

    m_ = cfg.mla
    B, D = x.shape
    h = rms_norm(x[:, None, :], params["norm"], cfg.norm_eps)
    q_nope, q_rope = _project_q(params, h, cfg, positions[:, None])
    q_nope, q_rope = q_nope[:, 0], q_rope[:, 0]
    w_uk, w_uv = absorbed_weights(params, cfg)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    scale = (m_.nope_head_dim + m_.rope_head_dim) ** -0.5

    ba, sa = tuple(batch_axes), tuple(seq_axes)
    N, bs, rank = c_pool.shape
    n_seq = _axis_size(mesh, sa)
    Nl = N // (_axis_size(mesh, ba) * n_seq)
    bspec = _bspec(ba)
    pool_spec = ba + sa if (ba or sa) else None
    tspec = P(bspec, sa if table_cols_sharded else None)
    M_glob = tables.shape[1]
    M_loc_cols = M_glob // n_seq if table_cols_sharded else M_glob

    def body(ql, qr, cp, rp, tb, ln):
        off = _shard_offset(mesh, ba, sa, Nl)
        pos_base = 0
        if table_cols_sharded:
            sidx = jnp.zeros((), jnp.int32)
            for a in sa:
                sidx = sidx * mesh.shape[a] + jax.lax.axis_index(a)
            pos_base = sidx * M_loc_cols * bs
        local = _localize(tb, off, Nl)
        Bl, M = local.shape
        H = ql.shape[1]
        row_bytes = Bl * bs * rank * cp.dtype.itemsize
        bpc = max(1, min(M, (1 << 27) // max(1, row_bytes)))
        padM = (-M) % bpc
        if padM:
            local = jnp.pad(local, ((0, 0), (0, padM)), constant_values=-1)
        nch = local.shape[1] // bpc
        tc = local.reshape(Bl, nch, bpc).transpose(1, 0, 2)

        def step(carry, inp):
            mx, l, acc = carry
            ci, tbk = inp
            tclamp = jnp.clip(tbk, 0, Nl - 1)
            c = jnp.take(cp, tclamp, axis=0).reshape(Bl, bpc * bs, rank)
            kr = jnp.take(rp, tclamp, axis=0).reshape(Bl, bpc * bs, -1)
            s = (jnp.einsum("bhr,bsr->bhs", ql, c.astype(jnp.float32))
                 + jnp.einsum("bhr,bsr->bhs", qr.astype(jnp.float32),
                              kr.astype(jnp.float32))) * scale
            pos = (pos_base + ci * bpc * bs
                   + jnp.arange(bpc * bs))[None, :]
            valid = (pos < ln[:, None]) & jnp.repeat(tbk >= 0, bs, axis=1)
            s = jnp.where(valid[:, None, :], s, NEG_INF)
            m_new = jnp.maximum(mx, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None]) * valid[:, None, :]
            sc = jnp.exp(mx - m_new)
            l = l * sc + p.sum(axis=-1)
            acc = acc * sc[..., None] + jnp.einsum(
                "bhs,bsr->bhr", p, c.astype(jnp.float32))
            return (m_new, l, acc), None

        m0 = _pvary(jnp.full((Bl, H), NEG_INF, jnp.float32), ba + sa)
        l0 = _pvary(jnp.zeros((Bl, H), jnp.float32), ba + sa)
        a0 = _pvary(jnp.zeros((Bl, H, rank), jnp.float32), ba + sa)
        (mx, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                       (jnp.arange(nch), tc))
        return _lse_combine(mx, l, acc, sa)

    ctx = shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, None), P(bspec, None, None),
                  P(pool_spec, None, None), P(pool_spec, None, None),
                  tspec, P(bspec)),
        out_specs=P(bspec, None, None),
    )(q_lat, q_rope, c_pool, rope_pool, tables, lengths)
    o = jnp.einsum("bhr,rhv->bhv", ctx, w_uv.astype(jnp.float32))
    o = o.reshape(B, -1).astype(x.dtype)
    return x + o @ params["wo"]
