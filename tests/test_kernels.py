"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes and dtypes (+hypothesis randomised shapes)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.mamba_scan.ops import mamba_scan
from repro.kernels.mamba_scan.ref import mamba_scan_ref
from repro.kernels.paged_attention.ops import (build_ragged_descriptor,
                                               paged_attention,
                                               paged_attention_split,
                                               ragged_paged_attention,
                                               shard_descriptor)
from repro.kernels.paged_attention.ref import (paged_decode_attention_ref,
                                               ragged_fused_ref)
from repro.models.attention import fuse_kv, split_fused_kv
from repro.kernels.rwkv6_scan.ops import rwkv6_scan
from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------ flash attn
@pytest.mark.parametrize("B,Sq,Sk,H,KV,hd,causal,win", [
    (2, 64, 64, 4, 2, 32, True, None),
    (1, 100, 100, 4, 4, 16, True, None),
    (2, 128, 128, 8, 2, 64, True, 32),
    (1, 33, 77, 2, 1, 16, False, None),
    (2, 16, 144, 4, 2, 32, True, None),
])
def test_flash_attention(B, Sq, Sk, H, KV, hd, causal, win):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, Sk, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, Sk, KV, hd), jnp.float32)
    off = Sk - Sq if causal else 0
    got = flash_attention(q, k, v, causal=causal, window=win,
                          q_offset=off, interpret=True)
    want = flash_attention_ref(q, k, v, causal=causal, window=win,
                               q_offset=off)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 3e-2),
                                       (jnp.float32, 2e-5)])
def test_flash_attention_dtypes(dtype, tol):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 64), dtype)
    k = jax.random.normal(ks[1], (2, 64, 2, 64), dtype)
    v = jax.random.normal(ks[2], (2, 64, 2, 64), dtype)
    got = flash_attention(q, k, v, interpret=True).astype(jnp.float32)
    want = flash_attention_ref(q, k, v).astype(jnp.float32)
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@settings(max_examples=10, deadline=None)
@given(sq=st.integers(1, 96), sk=st.integers(8, 96),
       g=st.sampled_from([1, 2, 4]), causal=st.booleans())
def test_flash_attention_hypothesis(sq, sk, g, causal):
    KV, hd = 2, 16
    ks = jax.random.split(jax.random.PRNGKey(sq * 100 + sk), 3)
    q = jax.random.normal(ks[0], (1, sq, KV * g, hd), jnp.float32)
    k = jax.random.normal(ks[1], (1, sk, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (1, sk, KV, hd), jnp.float32)
    off = max(0, sk - sq) if causal else 0
    got = flash_attention(q, k, v, causal=causal, q_offset=off,
                          interpret=True)
    want = flash_attention_ref(q, k, v, causal=causal, q_offset=off)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


# ------------------------------------------------------------ paged attn
def _paged_case(B, H, KV, hd, bs, M, N, W, seed=0):
    """Pools, fused pool, monolithic table (+hole) and (W, Bs, M) stack."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (N, bs, KV, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (N, bs, KV, hd), jnp.float32)
    perm = np.random.RandomState(seed).permutation(N)[:B * M]
    mono = perm.reshape(B, M).astype(np.int32)
    mono[0, M - 1] = -1                             # hole
    lengths = jnp.asarray(
        np.random.RandomState(seed + 1).randint(1, M * bs + 1, (B,)),
        jnp.int32)
    if W == 1:
        tables = jnp.asarray(mono)
    else:
        Bs = -(-B // W)
        stack = np.full((W, Bs, M), -1, np.int32)
        for b in range(B):
            stack[b % W, b // W] = mono[b]          # interleaved slot layout
        tables = jnp.asarray(stack)
    return q, kp, vp, fuse_kv(kp, vp), tables, jnp.asarray(mono), lengths


@pytest.mark.parametrize("B,H,KV,hd,bs,M,N,win", [
    (2, 4, 2, 32, 16, 4, 16, None),
    (3, 8, 8, 64, 32, 3, 12, None),
    (2, 4, 1, 16, 8, 6, 32, 20),
])
def test_paged_attention(B, H, KV, hd, bs, M, N, win):
    """Fused kernel vs the jnp oracle AND bit-identical to the legacy
    split-KV baseline: the split kernel gets the K and V halves of the
    fused pool, and both kernels hand the same (KV, bs, hd) tiles to one
    shared flash step, so every float op runs in the same order."""
    q, kp, vp, kv, tables, _, lengths = _paged_case(B, H, KV, hd, bs, M, N, 1)
    got = paged_attention(q, kv, tables, lengths, window=win,
                          interpret=True)
    want = paged_decode_attention_ref(q, kp, vp, tables, lengths,
                                      window=win)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    split = paged_attention_split(q, kv[:, 0], kv[:, 1], tables, lengths,
                                  window=win, interpret=True)
    assert jnp.array_equal(got, split), "fused kernel drifted from split"


def test_microbench_kernel_identity():
    """The microbench's kernel-mode identity gate (fused == split ==
    every pipelined depth) runs and holds."""
    from benchmarks.microbench import kernel_identity_case
    out = kernel_identity_case()
    assert out and all(out.values()), out


@pytest.mark.parametrize("B,H,KV,hd,bs,M,N,win,W", [
    (2, 4, 2, 32, 16, 4, 16, None, 2),
    (3, 8, 8, 64, 32, 3, 12, None, 2),     # ragged: Bs = ceil(3/2)
    (2, 4, 1, 16, 8, 6, 32, 20, 2),
    (4, 4, 2, 32, 16, 4, 24, None, 4),
])
def test_paged_attention_sharded_layout(B, H, KV, hd, bs, M, N, win, W):
    """The shard-native page walk: the fused kernel consumes the
    (W, Bs, M) interleaved shard stack directly and must match both the
    fused oracle and the monolithic run on the equivalent 2-D table."""
    from repro.kernels.paged_attention.ref import (
        paged_decode_attention_fused_ref)
    q, kp, vp, kv, stack, mono, lengths = _paged_case(
        B, H, KV, hd, bs, M, N, W)
    got = paged_attention(q, kv, stack, lengths, window=win,
                          interpret=True)
    want = paged_decode_attention_fused_ref(q, kv, stack, lengths,
                                            window=win)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    mono_run = paged_attention(q, kv, mono, lengths,
                               window=win, interpret=True)
    np.testing.assert_allclose(got, mono_run, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("depth", [2, 4])
@pytest.mark.parametrize("W,win", [(1, None), (2, 20), (4, None)])
def test_paged_attention_pipelined(W, win, depth):
    """Multi-depth manual DMA buffering is bit-identical to the
    unpipelined fused walk — pipelining only moves *when* bytes arrive
    in VMEM, never what the flash step computes."""
    B, H, KV, hd, bs, M, N = 4, 4, 2, 16, 8, 5, 24
    q, _, _, kv, tables, _, lengths = _paged_case(B, H, KV, hd, bs, M, N, W)
    base = paged_attention(q, kv, tables, lengths, window=win,
                           interpret=True)
    piped = paged_attention(q, kv, tables, lengths, window=win,
                            buffer_depth=depth, interpret=True)
    assert jnp.array_equal(base, piped), f"depth={depth} drifted"


def test_shard_descriptor_collapses_layout_dispatch():
    t2 = jnp.zeros((3, 4), jnp.int32)
    flat, W, Bs, M = shard_descriptor(t2)
    assert (W, Bs, M) == (1, 3, 4) and flat.shape == (1, 3, 4)
    t3 = jnp.zeros((2, 2, 4), jnp.int32)
    flat, W, Bs, M = shard_descriptor(t3)
    assert (W, Bs, M) == (2, 2, 4)
    with pytest.raises(ValueError):
        shard_descriptor(jnp.zeros((4,), jnp.int32))


# ----------------------------------------------------------- ragged fused
@pytest.mark.parametrize("W", [1, 2, 4])
@pytest.mark.parametrize("win", [None, 12])
def test_ragged_fused(W, win):
    """Mixed chunked-prefill + decode rows in ONE kernel call, swept over
    shard layouts, holes and SWA windows, vs the pure-jnp oracle."""
    B, H, KV, hd, bs, M, N = 5, 4, 2, 16, 8, 5, 40
    q0, kp, vp, kv, tables, mono, _ = _paged_case(B, H, KV, hd, bs, M, N, W,
                                                  seed=3 + W)
    # slot 0: mid-prompt chunk; slot 2: decode; slot 3: prompt head chunk
    slot_ids, q_lens, q_starts, kv_lens = [0, 2, 3], [11, 1, 5], [3, 19, 0], \
        [14, 20, 5]
    num_slots = B if W == 1 else tables.shape[0] * tables.shape[1]
    d = build_ragged_descriptor(slot_ids, q_lens, q_starts, kv_lens,
                                num_slots=num_slots, t_cap=48)
    assert list(d["cu_q_lens"]) == [0, 11, 12, 17]
    assert list(d["cu_kv_lens"]) == [0, 14, 34, 39]
    rng = np.random.RandomState(0)
    qp = np.zeros((48, H, hd), np.float32)
    real = rng.randn(17, H, hd).astype(np.float32)
    m = d["token_src"] >= 0
    qp[m] = real[d["token_src"][m]]
    qp = jnp.asarray(qp)
    got = ragged_paged_attention(
        qp, kv, tables, jnp.asarray(d["tile_row"]),
        jnp.asarray(d["tile_pos"]), jnp.asarray(d["kv_lens"]),
        window=win, interpret=True)
    want = ragged_fused_ref(
        qp, kv, tables, jnp.asarray(d["token_row"]),
        jnp.asarray(d["token_pos"]), jnp.asarray(d["kv_lens"]), window=win)
    np.testing.assert_allclose(np.asarray(got)[m], np.asarray(want)[m],
                               rtol=2e-5, atol=2e-5)


def test_ragged_decode_rows_match_decode_kernel():
    """A ragged batch of pure decode rows reproduces the decode kernel's
    output for every row (same masks: causal ≡ length cut at q = last)."""
    B, H, KV, hd, bs, M, N = 3, 4, 2, 16, 8, 4, 24
    q, _, _, kv, tables, _, lengths = _paged_case(B, H, KV, hd, bs, M, N, 1)
    lengths = jnp.asarray([5, 17, 26], jnp.int32)
    d = build_ragged_descriptor(
        list(range(B)), [1] * B, [int(x) - 1 for x in lengths],
        [int(x) for x in lengths], num_slots=B, t_cap=B * 8)
    qp = np.zeros((B * 8, H, hd), np.float32)
    qp[d["token_src"] >= 0] = np.asarray(q)
    got = ragged_paged_attention(
        jnp.asarray(qp), kv, tables, jnp.asarray(d["tile_row"]),
        jnp.asarray(d["tile_pos"]), jnp.asarray(d["kv_lens"]),
        interpret=True)
    want = paged_attention(q, kv, tables, lengths, interpret=True)
    got_rows = np.asarray(got)[np.asarray(d["last_index"])]
    np.testing.assert_allclose(got_rows, np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------- autotune
def test_autotune_deterministic_and_prefers_pipeline():
    from repro.kernels.paged_attention import autotune as at
    at.clear()
    try:
        d0 = at.get_tuning(2, 64, 128)
        assert d0 == at.get_tuning(2, 64, 128)          # deterministic
        assert d0.buffer_depth == at.DEFAULT_BUFFER_DEPTH
        model = at.KernelCostModel()
        # compute-heavy shape: overlap is worth a deeper buffer
        block_bytes = 128 * 4 * 128 * 4          # bs * KV*2 * hd * f32
        tuned = at.autotune(32, 128, 128, n_blocks=8,
                            block_bytes=block_bytes)
        assert at.get_tuning(32, 128, 128) == tuned      # persisted
        assert tuned.buffer_depth >= 2                   # pipelined wins
        naive = model.step_s(8, block_bytes, 128, 32, 128, fused=False,
                             buffer_depth=1)
        best = model.step_s(8, block_bytes, 128, 32, 128, fused=True,
                            buffer_depth=tuned.buffer_depth)
        assert best < naive                              # tuned <= naive
    finally:
        at.clear()


# -------------------------------------------------------------- MLA decode
def test_mla_paged_decode():
    from repro.kernels.mla_attention.ops import mla_paged_decode
    from repro.kernels.mla_attention.ref import mla_decode_ref
    from repro.models.config import MLAConfig, ModelConfig
    from repro.models.mla import init_mla
    B, H, rank, rope, bs, M, N = 2, 4, 32, 16, 16, 3, 8
    cfg = ModelConfig(name="t", n_layers=1, d_model=64, n_heads=H,
                      n_kv_heads=H, d_ff=64, vocab=64, head_dim=32,
                      mixers=("mla",),
                      mla=MLAConfig(kv_lora_rank=rank, q_lora_rank=48,
                                    rope_head_dim=rope, nope_head_dim=16,
                                    v_head_dim=16))
    ks = jax.random.split(KEY, 5)
    p = init_mla(ks[0], cfg, jnp.float32)
    x = jax.random.normal(ks[1], (B, 64), jnp.float32)
    cp = jax.random.normal(ks[2], (N, bs, rank), jnp.float32)
    rp = jax.random.normal(ks[3], (N, bs, rope), jnp.float32)
    tables = jnp.asarray(np.random.RandomState(0).permutation(N)[
        :B * M].reshape(B, M).astype(np.int32))
    lengths = jnp.asarray([M * bs - 5, bs + 3], jnp.int32)
    got = mla_paged_decode(p, x, lengths - 1, cp, rp, tables, lengths,
                           cfg, interpret=True)
    want = mla_decode_ref(p, x, lengths - 1, cp, rp, tables, lengths, cfg)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)
    # shard-native: the kernel walks the (W, Bs, M) stack directly and
    # must be bit-identical to the monolithic run (no traced transpose)
    W = 2
    Bs = -(-B // W)
    stack = np.full((W, Bs, M), -1, np.int32)
    mono = np.asarray(tables)
    for b in range(B):
        stack[b % W, b // W] = mono[b]
    sharded = mla_paged_decode(p, x, lengths - 1, cp, rp,
                               jnp.asarray(stack), lengths, cfg,
                               interpret=True)
    np.testing.assert_allclose(sharded, got, rtol=1e-6, atol=1e-6)


# -------------------------------------------------------------- mamba scan
@pytest.mark.parametrize("B,S,DI,N,chunk", [
    (2, 32, 16, 8, 16), (1, 100, 64, 16, 64), (2, 64, 24, 4, 32)])
def test_mamba_scan(B, S, DI, N, chunk):
    ks = jax.random.split(KEY, 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, DI)))
    A = -jnp.exp(jax.random.normal(ks[1], (DI, N)) * 0.2)
    Bc = jax.random.normal(ks[2], (B, S, N))
    Cc = jax.random.normal(ks[3], (B, S, N))
    x = jax.random.normal(ks[4], (B, S, DI))
    h0 = jax.random.normal(jax.random.fold_in(KEY, 9), (B, DI, N))
    gy, gh = mamba_scan(dt, A, Bc, Cc, x, h0, chunk=chunk, interpret=True)
    wy, wh = mamba_scan_ref(dt, A, Bc, Cc, x, h0)
    np.testing.assert_allclose(gy, wy, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gh, wh, rtol=1e-5, atol=1e-5)


# -------------------------------------------------------------- rwkv6 scan
@pytest.mark.parametrize("B,S,nH,hd,chunk", [
    (2, 32, 2, 16, 16), (1, 100, 4, 64, 32)])
def test_rwkv6_scan(B, S, nH, hd, chunk):
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, S, nH, hd))
    k = jax.random.normal(ks[1], (B, S, nH, hd))
    v = jax.random.normal(ks[2], (B, S, nH, hd))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, S, nH, hd)) * .5 - .5))
    u = jax.random.normal(ks[4], (nH, hd)) * 0.1
    S0 = jax.random.normal(jax.random.fold_in(KEY, 7),
                           (B, nH, hd, hd)) * 0.1
    gy, gs = rwkv6_scan(r, k, v, w, u, S0, chunk=chunk, interpret=True)
    wy, ws = rwkv6_scan_ref(r, k, v, w, u, S0)
    np.testing.assert_allclose(gy, wy, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(gs, ws, rtol=2e-4, atol=2e-4)


# ----------------------------------------------- flash custom-vjp backward
def test_chunked_attention_flash_backward():
    from repro.models.attention import chunked_attention, direct_attention
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 24, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (2, 40, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (2, 40, 2, 16), jnp.float32)
    f1 = lambda *a: (chunked_attention(*a, causal=True, q_offset=16,
                                       chunk=16) ** 2).sum()
    f2 = lambda *a: (direct_attention(*a, causal=True,
                                      q_offset=16) ** 2).sum()
    g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
