"""Prefix-sharing invariants (the sharing-set extension of §IV).

Three properties anchor the soundness argument (see core/shootdown.py):

  (a) a block's refcount is never negative and always equals the number
      of live mappings inside its sharing set;
  (b) no fence is ever issued for a block while it stays inside one
      sharing set — witnessed by ``fpr.prefix.in_set_violations == 0``
      (no refcounted block ever reaches the allocator) plus the
      detach-only munmap keeping the fence counter flat;
  (c) after a cross-tenant sharing exit, a fence precedes the first
      foreign reuse — the ordinary context-exit check, scoped to the
      union of every former sharer's worker-presence bit.

The engine differential at the bottom asserts sharing never changes
tokens, only how many unique blocks back them.
"""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import ContextScope, FprMemoryManager, derive_context
from repro.core.config import FprConfig
from repro.core.events import BlocksShared, SharingExit
from repro.core.prefix import PrefixIndex, block_hashes
from repro.core.tracking import FLAG_WAS_SHARED


def ctx(gid=1, scope=ContextScope.PER_GROUP, **kw):
    return derive_context(scope, group_id=gid, **kw)


def make_mgr(n=128, workers=1, **kw):
    return FprMemoryManager(
        config=FprConfig(num_blocks=n, num_workers=workers,
                         fpr_enabled=True, max_order=6, **kw))


# ======================================================== hashing & index
class TestHashesAndIndex:
    def test_block_hashes_full_blocks_only(self):
        toks = np.arange(70)
        hs = block_hashes(toks, 32)
        assert len(hs) == 2                       # 70 // 32, tail dropped
        assert hs == block_hashes(np.arange(64), 32)

    def test_block_hashes_chain_is_prefix_sensitive(self):
        a = block_hashes(np.arange(64), 32)
        b = block_hashes(np.concatenate([np.arange(32), np.arange(32)]), 32)
        assert a[0] == b[0]                       # same first block
        assert a[1] != b[1]                       # chain diverges
        # same *content* in a different leading block ⇒ different hash
        assert a[1] != block_hashes(np.arange(32, 96), 32)[0]

    def test_match_walks_longest_indexed_prefix(self):
        ix = PrefixIndex()
        ix.insert(1, 10, mapping_id=1)
        ix.insert(2, 11, mapping_id=1)
        assert ix.match((1, 2, 3)) == [10, 11]
        assert ix.match((9, 1)) == []             # unknown head stops the walk
        assert ix.match((1, 9, 2)) == [10]        # ...wherever it happens
        assert ix.match(()) == []

    def test_detach_orphans_then_exits(self):
        ix = PrefixIndex()
        ix.insert(5, 7, mapping_id=1)
        ix.attach(7, mapping_id=2)
        res = ix.detach(7, 1)                     # owner leaves first
        assert not res.exited and res.newly_orphaned
        assert ix.orphaned_live == 1
        res = ix.detach(7, 2)                     # last sharer
        assert res.exited and res.was_orphan
        assert len(ix) == 0 and ix.live_blocks == 0


# ========================================================== shared mmap
class TestSharedMmap:
    def test_attach_reuses_blocks_without_alloc_or_fence(self):
        mgr = make_mgr()
        h = (11, 12)
        m1 = mgr.mmap(3, ctx(1), prefix_hashes=h)
        allocs_before = mgr.stats.allocs
        m2 = mgr.mmap(3, ctx(1), prefix_hashes=h)
        assert m2.physical[:2] == m1.physical[:2]   # same physical prefix
        assert m2.physical[2] != m1.physical[2]     # private tail
        assert m2.prefix_hits == 2
        assert mgr.stats.allocs == allocs_before + 1   # only the tail
        assert mgr.fences.stats.fences == 0
        for b in m1.physical[:2]:
            assert mgr.tracker.refcount(b) == 2
        c = mgr.prefix_stats.counters(mgr.prefix)
        assert c["hit_blocks"] == 2 and c["miss_blocks"] == 2
        assert c["in_set_violations"] == 0

    def test_sharing_disabled_never_matches(self):
        mgr = make_mgr(prefix_sharing=False)
        m1 = mgr.mmap(2, ctx(1), prefix_hashes=(1,))
        m2 = mgr.mmap(2, ctx(1), prefix_hashes=(1,))
        assert m2.prefix_hits == 0
        assert set(m1.physical).isdisjoint(m2.physical)
        assert mgr.prefix.live_blocks == 0

    def test_non_fpr_mapping_never_shares(self):
        mgr = make_mgr()
        mgr.mmap(2, ctx(1), prefix_hashes=(3,))
        m2 = mgr.mmap(2, None, prefix_hashes=(3,))   # ctx_id 0
        assert m2.prefix_hits == 0 and not m2.shared_idx

    def test_shared_lease_cannot_bypass_manager(self):
        mgr = make_mgr()
        m1 = mgr.mmap(2, ctx(1), prefix_hashes=(9,))
        assert m1.lease.manager is mgr
        with pytest.raises(ValueError):
            mgr.alloc.release(m1.lease)
        # raw refcounted blocks are refused too
        with pytest.raises(ValueError):
            mgr.alloc.release([m1.physical[0]], worker_id=0)

    def test_sharing_events_published(self):
        mgr = make_mgr()
        seen = []
        mgr.bus.subscribe(BlocksShared, seen.append)
        mgr.bus.subscribe(SharingExit, seen.append)
        h = (21,)
        m1 = mgr.mmap(2, ctx(1), prefix_hashes=h)
        m2 = mgr.mmap(2, ctx(2), prefix_hashes=h)
        assert isinstance(seen[0], BlocksShared)
        assert seen[0].n_blocks == 1 and seen[0].mapping_id == m2.mapping_id
        mgr.munmap(m1.mapping_id)                 # owner leaves → orphan
        mgr.munmap(m2.mapping_id)                 # last sharer → exit
        exits = [e for e in seen if isinstance(e, SharingExit)]
        assert exits[0].reason == "munmap" and exits[0].newly_orphaned == 1
        assert exits[1].n_blocks == 1 and exits[1].orphaned == 1


# ===================================================== invariants (b)+(c)
class TestSharingExitFences:
    def test_detach_only_munmap_is_fence_free(self):
        """(b): leaving a sharing set that stays alive fences nothing."""
        mgr = make_mgr()
        h = (31, 32)
        m1 = mgr.mmap(2, ctx(1), prefix_hashes=h)
        m2 = mgr.mmap(2, ctx(2), prefix_hashes=h)
        mgr.munmap(m2.mapping_id)                 # pure detach
        assert mgr.fences.stats.fences == 0
        assert mgr.prefix_stats.sharing_exits == 0
        assert mgr.prefix_stats.shared_detaches == 2
        for b in m1.physical:                     # still resident for m1
            assert mgr.tracker.refcount(b) == 1

    def test_cross_tenant_exit_fence_precedes_first_foreign_use(self):
        """(c): the context-exit fence covers every former sharer."""
        mgr = make_mgr(workers=2)
        h = (41, 42)
        m1 = mgr.mmap(2, ctx(1), worker=0, prefix_hashes=h)
        mgr.mmap(2, ctx(2), worker=1, prefix_hashes=h)
        blocks = list(m1.physical)
        for mid in list(mgr.tables.mappings):
            mgr.munmap(mid, worker=0)
        # both sharers gone: blocks exited their set, recycled fence-free
        assert mgr.fences.stats.fences == 0
        assert mgr.prefix_stats.sharing_exits == 2
        for b in blocks:
            assert mgr.tracker.flags(b) & FLAG_WAS_SHARED
            # presence mask still remembers BOTH former sharers' workers
            assert mgr.tracker.worker_mask(b) == 0b11
        m3 = mgr.mmap(2, ctx(3), worker=0)        # first foreign reuse
        assert set(m3.physical) == set(blocks)
        assert mgr.fences.stats.fences == 1       # one merged exit fence
        assert mgr.fences.stats.workers_covered >= 2
        assert mgr.prefix_stats.exit_fenced == 2
        assert mgr.prefix_stats.in_set_violations == 0

    def test_same_context_reuse_after_exit_stays_fence_free(self):
        mgr = make_mgr()
        h = (51,)
        m1 = mgr.mmap(1, ctx(1), prefix_hashes=h)
        blocks = list(m1.physical)
        mgr.munmap(m1.mapping_id)
        m2 = mgr.mmap(1, ctx(1))                  # back to the same tenant
        assert m2.physical == blocks
        assert mgr.fences.stats.fences == 0

    def test_global_fence_after_exit_elides_the_exit_fence(self):
        mgr = make_mgr()
        h = (61,)
        m1 = mgr.mmap(1, ctx(1), prefix_hashes=h)
        mgr.munmap(m1.mapping_id)
        mgr.fences.fence("unrelated_global")
        before = mgr.fences.stats.fences
        mgr.mmap(1, ctx(2))
        assert mgr.fences.stats.fences == before  # elided (§IV-C5)
        assert mgr.prefix_stats.exit_elided == 1


# ============================================================ copy-on-write
class TestCow:
    def _pair(self, mgr, h=(71,)):
        m1 = mgr.mmap(2, ctx(1), prefix_hashes=h)
        m2 = mgr.mmap(2, ctx(2), prefix_hashes=h)
        return m1, m2

    def test_cow_copies_only_when_actually_shared(self):
        mgr = make_mgr()
        m1 = mgr.mmap(2, ctx(1), prefix_hashes=(81,))
        assert mgr.cow(m1.mapping_id, 0) is None  # sole sharer: no copy
        assert mgr.cow(m1.mapping_id, 1) is None  # not a hashed block
        assert mgr.prefix_stats.cow_copies == 0

    def test_cow_diverges_without_fence(self):
        mgr = make_mgr()
        m1, m2 = self._pair(mgr)
        old = m2.physical[0]
        assert old == m1.physical[0]
        old_b, new_b = mgr.cow(m2.mapping_id, 0)
        assert (old_b, m2.physical[0]) == (old, new_b)
        assert m1.physical[0] == old              # sharer keeps the set
        assert mgr.prefix.is_indexed(old)
        assert mgr.tracker.refcount(old) == 1
        assert mgr.fences.stats.fences == 0
        assert mgr.prefix_stats.cow_copies == 1
        # the diverged mapping is private now — a second cow is a no-op
        assert mgr.cow(m2.mapping_id, 0) is None

    def test_owner_cow_orphans_the_entry(self):
        mgr = make_mgr()
        m1, m2 = self._pair(mgr)
        mgr.cow(m1.mapping_id, 0)                 # the *owner* diverges
        assert mgr.prefix.orphaned_live == 1
        # the orphan still serves: a third request attaches to it
        m3 = mgr.mmap(2, ctx(3), prefix_hashes=(71,))
        assert m3.physical[0] == m2.physical[0]
        assert m3.prefix_hits == 1


# =============================================================== eviction
class TestEvictionPinning:
    def test_shared_blocks_are_pinned(self):
        mgr = make_mgr()
        m1 = mgr.mmap(1, ctx(1), prefix_hashes=(91,))
        mgr.mmap(1, ctx(2), prefix_hashes=(91,))
        b = m1.physical[0]
        assert mgr.evict([(m1.mapping_id, 0)], fpr_batch=True) == 0
        assert mgr.prefix_stats.evict_pinned == 1
        assert m1.physical[0] == b                # untouched, still mapped
        assert mgr.prefix.is_indexed(b)

    def test_sole_sharer_eviction_exits_then_swaps(self):
        mgr = make_mgr()
        m1 = mgr.mmap(1, ctx(1), prefix_hashes=(92,))
        b = m1.physical[0]
        assert mgr.evict([(m1.mapping_id, 0)], fpr_batch=True) == 1
        assert not mgr.prefix.is_indexed(b)
        assert mgr.prefix_stats.sharing_exits == 1
        assert m1.physical[0] < 0                 # swapped out
        assert mgr.tracker.refcount(b) == 0


# ===================================================== property-based sweep
HASH_CHAINS = [(1,), (1, 2), (1, 2, 3), (7,), (7, 8)]

OP = st.one_of(
    st.tuples(st.just("mmap"), st.integers(1, 3),
              st.integers(0, len(HASH_CHAINS) - 1), st.integers(0, 2),
              st.integers(0, 7)),
    st.tuples(st.just("munmap"), st.integers(0, 50)),
    st.tuples(st.just("cow"), st.integers(0, 50), st.integers(0, 4)),
    st.tuples(st.just("evict"), st.integers(0, 50), st.integers(0, 4)),
    st.tuples(st.just("reshard"), st.integers(1, 3)),
)


@given(st.lists(OP, min_size=1, max_size=60))
@settings(max_examples=40, deadline=None)
def test_sharing_set_invariants_under_interleaving(ops):
    _run_sweep(ops)


def test_sharing_set_invariants_seeded():
    """The same sweep, deterministic — runs even without hypothesis."""
    rng = np.random.RandomState(17)
    for _ in range(25):
        ops = []
        for _ in range(rng.randint(5, 60)):
            kind = rng.choice(["mmap", "mmap", "munmap", "cow",
                               "evict", "reshard"])
            if kind == "mmap":
                ops.append(("mmap", rng.randint(1, 4),
                            rng.randint(0, len(HASH_CHAINS)),
                            rng.randint(0, 3), rng.randint(0, 8)))
            elif kind == "reshard":
                ops.append(("reshard", rng.randint(1, 4)))
            else:
                ops.append((kind, rng.randint(0, 51), rng.randint(0, 5)))
        _run_sweep(ops)


def _run_sweep(ops):
    """(a): refcounts mirror live sharer counts; (b): no refcounted block
    ever reaches the allocator; block conservation holds throughout."""
    mgr = make_mgr(64, workers=2)
    live: dict[int, object] = {}
    for op in ops:
        kind = op[0]
        try:
            if kind == "mmap":
                _, gid, hi, extra, w = op
                h = HASH_CHAINS[hi]
                m = mgr.mmap(len(h) + extra, ctx(gid),
                             worker=w % mgr.num_workers, prefix_hashes=h)
                live[m.mapping_id] = m
            elif kind == "munmap" and live:
                mid = list(live)[op[1] % len(live)]
                mgr.munmap(mid)
                del live[mid]
            elif kind == "cow" and live:
                mid = list(live)[op[1] % len(live)]
                mgr.cow(mid, op[2] % len(live[mid].physical))
            elif kind == "evict" and live:
                mid = list(live)[op[1] % len(live)]
                mgr.evict([(mid, op[2] % len(live[mid].physical))],
                          fpr_batch=True)
            elif kind == "reshard":
                mgr.reshard(op[1])
        except Exception as e:
            if "OutOfBlocks" in type(e).__name__:
                continue
            raise

        # (a) refcount == live sharer count, for every block
        expected: dict[int, int] = {}
        for m in live.values():
            for idx in m.shared_idx:
                b = m.physical[idx]
                assert b >= 0 and mgr.prefix.is_indexed(b)
                expected[b] = expected.get(b, 0) + 1
        rc = mgr.tracker.refcounts(np.arange(mgr.num_blocks))
        assert (rc >= 0).all()
        for b in range(mgr.num_blocks):
            assert rc[b] == expected.get(b, 0), (b, ops)
        assert mgr.prefix.live_blocks == len(expected)
        # (b) witness: no refcounted block ever reached allocation
        assert mgr.prefix_stats.in_set_violations == 0
        # conservation: every block is free, mapped, or swapped out
        mapped = {b for m in live.values() for b in m.physical if b >= 0}
        assert mgr.free_blocks + len(mapped) == mgr.num_blocks


# ===================================================== engine differential
@pytest.mark.slow
class TestEngineSharing:
    def _engine(self, sharing, num_blocks=64, admission=None, max_batch=4):
        import jax
        import jax.numpy as jnp
        from repro.models import transformer as tfm
        from repro.models.config import ModelConfig
        from repro.serving.config import EngineConfig
        from repro.serving.engine import Engine

        cfg = ModelConfig(name="t", n_layers=1, d_model=32, n_heads=2,
                          n_kv_heads=1, d_ff=64, vocab=128, head_dim=16)
        params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
        return Engine(cfg, params, config=EngineConfig(
            num_blocks=num_blocks, max_batch=max_batch, max_seq_len=256,
            prefix_sharing=sharing, admission=admission))

    def _run(self, prompts, sharing, num_blocks=64, max_new=10,
             admission=None, max_batch=4):
        eng = self._engine(sharing, num_blocks, admission, max_batch)
        for p in prompts:
            eng.submit(p, max_new_tokens=max_new)
        peak = 0
        while not eng.sched.idle and eng.steps < 500:
            eng.step()
            peak = max(peak, len(eng.sched.running))
        toks = [r.generated for r in sorted(eng.sched.done,
                                            key=lambda r: r.rid)]
        return eng, toks, peak

    def test_shared_prefix_tokens_bit_identical(self):
        """Sharing moves storage, never tokens — including through COW
        divergence of a fully-shared block-aligned prompt."""
        rng = np.random.RandomState(3)
        system = rng.randint(1, 128, size=128)     # exactly one full block
        prompts = [np.concatenate([system,
                                   rng.randint(1, 128,
                                               size=rng.randint(3, 20))])
                   for _ in range(4)]
        prompts += [system.copy(), system.copy()]  # block-aligned → COW
        e1, t1, _ = self._run(prompts, sharing=True)
        e0, t0, _ = self._run(prompts, sharing=False)
        assert t1 == t0
        s1 = e1.metrics.snapshot()
        assert s1["fpr.prefix.hit_blocks"] >= 4    # followers attached (the
        # whole first wave can complete at once, de-indexing its block
        # before the aligned pair is admitted — ≥4, not 5, is structural)
        assert s1["fpr.prefix.cow_copies"] >= 1    # aligned pair diverged
        assert s1["fpr.prefix.in_set_violations"] == 0
        assert s1["fpr.allocs"] < e0.metrics.snapshot()["fpr.allocs"]
        assert e1.metrics.snapshot()["fpr.prefix.hit_rate"] > 0

    def test_prefill_leaves_attached_blocks_alone(self):
        """A sharer's monolithic prefill never rewrites the prefix blocks
        it attached: their owner wrote them and running sharers read
        them (on TPU a prompt of another length computes the same prefix
        in different low bits)."""
        rng = np.random.RandomState(7)
        system = rng.randint(1, 128, size=128)     # one full block
        owner, sharer = (np.concatenate([system, rng.randint(1, 128,
                                                             size=n)])
                         for n in (5, 9))
        eng = self._engine(sharing=True)
        eng.submit(owner, max_new_tokens=4)
        eng.step()                                 # owner prefilled
        blk = eng.sched.running[0].mapping.physical[0]
        kv = eng.cache.state["kv"]
        eng.cache.state["kv"] = kv.at[:, blk].set(7.0)
        eng.submit(sharer, max_new_tokens=4)
        eng.step()                                 # sharer attaches
        m = next(r.mapping for r in eng.sched.running.values()
                 if r.mapping.prefix_hits)
        assert m.physical[0] == blk
        assert bool((eng.cache.state["kv"][:, blk] == 7.0).all())

    def test_monolithic_sharing_warns_on_tpu(self, monkeypatch):
        """On TPU, per-slot (monolithic-prefill) sharing is not
        token-identical to unshared serving, so the engine warns; it
        stays silent off TPU and with sharing off."""
        import warnings

        import jax
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.warns(UserWarning, match="not token-identical"):
            self._engine(sharing=True)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            self._engine(sharing=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            self._engine(sharing=True)

    def test_ledger_admits_more_concurrent_shared_requests(self):
        """Admission commits *unique* blocks: at a fixed pool size the
        governed engine runs strictly more shared-prefix requests
        concurrently than it can unshared ones."""
        rng = np.random.RandomState(5)
        system = rng.randint(1, 128, size=128)
        prompts = [np.concatenate([system,
                                   rng.randint(1, 128, size=5 + i)])
                   for i in range(4)]
        kw = dict(num_blocks=5, max_new=8, admission="fcfs")
        e1, t1, peak_shared = self._run(prompts, sharing=True, **kw)
        e0, t0, peak_plain = self._run(prompts, sharing=False, **kw)
        assert t1 == t0                            # same tokens regardless
        assert peak_plain == 2                     # 2-block windows, pool 5
        assert peak_shared > peak_plain            # sharing fits them all
        s = e1.metrics.snapshot()
        assert s["admission.ledger.peak_committed"] <= 5
        assert s["fpr.prefix.in_set_violations"] == 0
