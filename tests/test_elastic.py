"""Elastic worker topology: live resharding of block tables + engine.

Three layers of coverage:

  * **unit** — ``BlockTableStore.reshard`` / ``BlockTracker.remap_workers``
    / ``FenceEngine.reshard_workers`` carry each structure in its sound
    direction (max-merge shard epochs, min-merge worker epochs, bit-OR
    masks through the translation), and the manager-level ``reshard``
    fences exactly the surviving old owners of moved live rows.
  * **property** — random traces interleaving alloc/free/touch/evict/
    **reshard** and **island join/leave** uphold the scoped-fence
    soundness invariant (*no worker reads a block version newer than its
    last covering fence, at either level*), the two-level epoch-merge
    invariant (*a merged island is exactly as stale as its stalest
    constituent* — ``island_epochs[i] == min(worker_epochs[w] for w in
    island i)`` after every operation) and the scoped/global
    differential (identical observable reads); the deep hypothesis sweep
    is slow-marked for nightly, a seeded slice runs in the fast lane.
  * **engine** — a live engine resized 1→4→2 mid-trace decodes tokens
    bit-identical to the fixed-topology run, with reshard refresh traffic
    strictly below one full-table re-upload (the elastic acceptance
    criterion; the bench twin is ``benchmarks/engine_trace.py``).
"""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import ContextScope, FprMemoryManager, derive_context
from repro.core.block_table import BlockTableStore
from repro.core.config import FprConfig
from repro.core.shootdown import FenceEngine
from repro.core.tracking import BlockTracker, worker_bit


def ctx(gid):
    return derive_context(ContextScope.PER_GROUP, group_id=gid)


def make_mgr(n=64, workers=2, scoped=True, **kw):
    return FprMemoryManager(
        config=FprConfig(num_blocks=n, num_workers=workers,
                         fpr_enabled=True, scoped_fences=scoped,
                         max_order=5, **kw),
        fence_engine=FenceEngine(measure=False))


# ================================================================ unit layer
class TestStoreReshard:
    def test_grow_keeps_rows_and_translates_shards(self):
        s = BlockTableStore(8, 4, num_shards=1)
        maps = [s.create_mapping([i], worker=0) for i in range(3)]
        table_before = s.table.copy()
        plan = s.reshard(4, translation=(0,))
        np.testing.assert_array_equal(s.table, table_before)  # rows stay put
        assert s.num_shards == 4
        for m in maps:
            assert (s.shard_of_mapping(m.mapping_id)
                    == s.slot_of[m.mapping_id] % 4)
        # every slot whose new owner isn't worker 0 moved
        assert set(plan["moved_slots"]) == {x for x in range(8) if x % 4}

    def test_modulo_shrink_moves_nothing(self):
        s = BlockTableStore(8, 4, num_shards=4)
        for w in range(4):
            s.create_mapping([w], worker=w)
        plan = s.reshard(2, translation=(0, 1, 0, 1))
        assert plan["moved_slots"] == []
        assert plan["fence_workers"] == []

    def test_shard_epochs_carry_max_of_contributors(self):
        s = BlockTableStore(8, 4, num_shards=4)
        s.bump_epoch(shards=[2])                # epochs [1, 1, 2, 1]
        s.bump_epoch(shards=[1])                # epochs [1, 3, 2, 1]
        s.reshard(2, translation=(0, 1, 0, 1))
        # new shard 0 ← old {0, 2} → max(1, 2); new shard 1 ← old {1, 3}
        assert list(s.shard_epochs) == [2, 3]

    def test_free_lists_repartition_by_new_modulo(self):
        s = BlockTableStore(8, 4, num_shards=2)
        m = s.create_mapping([5], worker=1)     # occupies slot 1
        s.reshard(4, translation=(0, 1))
        live = s.slot_of[m.mapping_id]
        free = {sh: list(lst) for sh, lst in enumerate(s._free_slots)}
        for sh, lst in free.items():
            assert all(x % 4 == sh for x in lst)
        assert sorted(x for lst in free.values() for x in lst) \
            == [x for x in range(8) if x != live]

    def test_overflow_residue_spreads_conservatively(self):
        s = BlockTableStore(2, 2, num_shards=2)
        s.create_mapping([1], worker=0)
        m_over = s.create_mapping([2], worker=0)     # overflow → shard 1
        s.destroy_mapping(m_over.mapping_id)         # dead residue (0, 1)
        s.reshard(1, translation=(0, 0))
        # old shard 1's slot folds into the single new shard — the residue
        # must survive the reshard so the next covering fence retires it
        assert (0, 0) in s._overflow_dead

    def test_live_overflow_records_recomputed(self):
        s = BlockTableStore(2, 2, num_shards=2)
        s.create_mapping([1], worker=0)
        m_over = s.create_mapping([2], worker=0)     # live overflow (0, 1)
        assert s._overflow_live == {(0, 1): 1}
        s.reshard(2, translation=(0, 1))             # same topology
        assert s._overflow_live == {(0, 1): 1}
        assert m_over.mapping_id in s.worker_of_mapping


class TestEpochAndMaskCarry:
    def test_worker_epochs_min_merge_on_shrink(self):
        eng = FenceEngine(measure=False, num_workers=4)
        eng.fence_scoped("x", worker_mask=int(worker_bit(2)))   # w2 → seq 2
        eng.fence_scoped("x", worker_mask=int(worker_bit(1)))   # w1 → seq 3
        eng.reshard_workers(2, translation=(0, 1, 0, 1))
        # w0 ← min(w0=1, w2=2) = 1; w1 ← min(w1=3, w3=1) = 1
        assert list(eng.worker_epochs) == [1, 1]

    def test_fresh_workers_start_at_current_seq(self):
        eng = FenceEngine(measure=False, num_workers=1)
        eng.fence("x")                                          # seq 2
        eng.reshard_workers(3, translation=(0,))
        assert list(eng.worker_epochs) == [2, 2, 2]

    def test_mask_bits_fold_through_translation(self):
        tr = BlockTracker(4)
        tr.add_worker(0, 3)
        tr.add_worker(1, 0)
        tr.remap_workers((0, 1, 0, 1), 4, 2)
        assert tr.worker_mask(0) == int(worker_bit(1))   # w3 → w1
        assert tr.worker_mask(1) == int(worker_bit(0))

    def test_aliased_top_bit_expands_to_all_new_workers(self):
        tr = BlockTracker(2)
        tr.add_worker(0, 70)                  # aliases bit 63
        tr.remap_workers(tuple(w % 4 for w in range(70)), 70, 4)
        assert tr.worker_mask(0) == 0b1111    # conservative: everyone

    def test_reshard_fences_only_surviving_old_owners(self):
        m = make_mgr(n=64, workers=1, max_seqs=8)
        mp = m.mmap(4, ctx(1), worker=0)      # slot 0 — stays on worker 0
        mp1 = m.mmap(4, ctx(1), worker=0)     # slot 1 — moves on grow
        st = m.fences.stats
        plan = m.reshard(4)
        assert plan["fence_workers"] == [0]   # old owner; 1..3 are fresh
        assert st.fences_by_reason["reshard"] == 1
        assert st.fences_scoped == 1          # scoped, not a broadcast
        m.munmap(mp.mapping_id, worker=0)
        m.munmap(mp1.mapping_id, worker=0)

    def test_modulo_shrink_is_fence_free(self):
        m = make_mgr(n=64, workers=4, max_seqs=8)
        maps = [m.mmap(2, ctx(1), worker=w) for w in range(4)]
        before = m.fences.stats.fences
        plan = m.reshard(2)
        assert plan["fence_workers"] == []
        assert m.fences.stats.fences == before
        for mp in maps:
            m.munmap(mp.mapping_id, worker=0)

    def test_soundness_across_shrink_merge(self):
        """A block freed on a worker that later merges away must still
        fence before a foreign context reuses it: the merged worker
        inherits the stale constituent's (lower) epoch and the block's
        remapped mask names it."""
        m = make_mgr(n=8, workers=4, max_seqs=8)
        mp = m.mmap(8, ctx(1), worker=3)      # whole pool on worker 3
        m.munmap(mp.mapping_id, worker=3)     # stale on w3, fence skipped
        m.reshard(2)                          # w3 folds into w1
        st = m.fences.stats
        fences_before = st.fences
        m.mmap(8, ctx(2), worker=0)           # foreign context exit
        assert st.fences == fences_before + 1
        # the fence covered translated holder w1, not a full broadcast
        assert st.fences_scoped >= 1


# ============================================================ property layer
# Random traces over alloc/free/touch/evict/fence/RESHARD/ISLAND.  The
# model mirrors the kernel bookkeeping: per-block holder sets (remapped
# through every reshard's translation) and free-time records; at
# re-allocation to a foreign context every recorded holder must have a
# covering fence.  The "island" op installs or dissolves a two-island
# partition of the current workers mid-trace; after EVERY op the driver
# asserts the two-level merge invariant (island summary epochs are the
# exact min over their constituents' worker epochs, tracker island
# summary bits cover every present worker's island).
_OPS = ["map", "map", "map", "unmap", "touch", "evict", "gfence",
        "sfence", "reshard", "island"]

_TRACE_OPS = st.lists(
    st.tuples(st.sampled_from(_OPS),
              st.integers(0, 2),          # ctx / live-mapping pick
              st.integers(1, 4),          # size / touch index / new workers
              st.integers(0, 7)),         # worker (mod num_workers)
    min_size=4, max_size=60)


def _drive_elastic_trace(trace, workers, *, scoped, check_soundness):
    eng = FenceEngine(measure=False, num_workers=workers)
    mgr = FprMemoryManager(
        config=FprConfig(num_blocks=48, num_workers=workers,
                         fpr_enabled=True, scoped_fences=scoped,
                         max_order=5),
        fence_engine=eng)
    live: list = []
    holders: dict[int, set] = {}    # block → workers holding a translation
    freed: dict[int, tuple] = {}    # block → (ctx, version, holders@free)
    reads: list = []

    def check_reuse(blocks, ctx_id):
        for b in blocks:
            fctx, fver, fholders = freed.pop(b, (None, None, set()))
            if fctx is not None and fctx != ctx_id:
                topo = eng.topology
                for hw in fholders:
                    assert int(eng.worker_epochs[hw]) > fver, (
                        f"worker {hw} reads block {b} (freed at v{fver}) "
                        f"without a covering fence "
                        f"(epoch {int(eng.worker_epochs[hw])})")
                    if topo is not None:
                        # island-level soundness: the summary epoch is a
                        # min, so it may lag the member — but it must
                        # never *lead* it (an island-level claim the
                        # member worker did not receive)
                        isl = topo.island_of(hw)
                        assert (int(eng.island_epochs[isl])
                                <= int(eng.worker_epochs[hw])), (
                            f"island {isl} summary epoch leads member "
                            f"worker {hw}")
                holders[b] = set()     # staleness covered: fresh start

    def check_two_level():
        """The two-level merge invariant, asserted after every op."""
        topo = eng.topology
        tr = mgr.tracker
        if topo is None:
            assert tr._island_mask is None
            assert eng.island_stats is None
            return
        # merged island exactly as stale as its stalest constituent
        expect = [min(int(eng.worker_epochs[w])
                      for w in range(len(eng.worker_epochs))
                      if topo.island_of(w) == i)
                  for i in range(topo.num_islands)]
        assert list(int(e) for e in eng.island_epochs) == expect, (
            f"island epochs {list(eng.island_epochs)} != min-merge "
            f"{expect} over workers {list(eng.worker_epochs)}")
        # tracker summary bits cover (at least) every present worker's
        # island — conservative supersets (buddy merges OR summaries)
        # are sound, a missing bit would let a scoped fence skip a
        # stale holder's island
        derived = tr._islands_from_masks(tr._worker_mask)
        assert np.all(tr._island_mask & derived == derived), (
            "island summary bits miss a present worker's island")

    for op, sel, size, w in trace:
        nw = mgr.config.num_workers
        w %= nw
        if op == "map":
            c = ctx(sel + 1)
            try:
                m = mgr.mmap(size, c, worker=w)
            except Exception:
                reads.append(("oom",))
                continue
            if check_soundness:
                check_reuse(m.physical, c.ctx_id)
                for b in m.physical:
                    holders.setdefault(b, set()).add(w)
            live.append(m)
            reads.append(("map", tuple(m.physical)))
        elif op == "unmap":
            if not live:
                continue
            m = live.pop(sel % len(live))
            if check_soundness:
                for b in m.physical:
                    if b >= 0:
                        freed[b] = (m.ctx_id, eng.seq,
                                    frozenset(holders.get(b, set())))
            mgr.munmap(m.mapping_id, worker=w)
            reads.append(("unmap", m.mapping_id))
        elif op == "touch":
            if not live:
                continue
            m = live[sel % len(live)]
            idx = size % m.num_blocks
            b, faulted = mgr.touch(m.mapping_id, idx, worker=w)
            if check_soundness:
                if faulted:
                    # a fault re-allocates: a foreign context's freed
                    # block must be covered exactly as at map
                    check_reuse([b], m.ctx_id)
                holders.setdefault(b, set()).add(w)
            reads.append(("touch", b, faulted))
        elif op == "evict":
            if not live:
                continue
            m = live[sel % len(live)]
            victims = [(m.mapping_id, i) for i in range(m.num_blocks)
                       if m.physical[i] >= 0]
            if not victims:
                continue
            blocks = [m.physical[i] for _, i in victims]
            fver = eng.seq          # versions stamp the pre-fence seq
            n = mgr.evict(victims, fpr_batch=True, worker=w)
            if check_soundness:
                # the §IV-B merged fence fires AT evict and must cover
                # every holder right now — afterwards the blocks carry no
                # stale holders (their masks were flushed by the fence),
                # which is what lets a later reshard min-merge epochs
                # without reviving them
                for b in blocks:
                    for hw in holders.get(b, set()):
                        assert int(eng.worker_epochs[hw]) > fver, (
                            f"evict fence missed holder {hw} of block {b}")
                    freed[b] = (m.ctx_id or 1, fver, frozenset())
                    holders[b] = set()
            reads.append(("evict", m.mapping_id, n))
        elif op == "gfence":
            eng.fence("external")
            reads.append(("gfence",))
        elif op == "sfence":
            mask = int(worker_bit(w)) | int(worker_bit(sel % nw))
            eng.fence_scoped("external", worker_mask=mask)
            reads.append(("sfence",))
        elif op == "reshard":
            new_workers = size                    # 1..4
            trans = mgr.default_translation(new_workers)
            topo = None
            if sel == 2 and new_workers >= 2:
                # island join riding the reshard: the new partition is
                # installed atomically with the worker remap
                topo = (tuple(range(new_workers - 1)), (new_workers - 1,))
            mgr.reshard(new_workers, trans, topology=topo)
            if check_soundness:
                tr = [int(trans[i]) for i in range(len(trans))]

                def remap(ws):
                    return frozenset(tr[x] if x < len(tr)
                                     else x % new_workers for x in ws)

                holders.update({b: set(remap(hs))
                                for b, hs in holders.items()})
                freed.update({b: (fc, fv, remap(fh))
                              for b, (fc, fv, fh) in freed.items()})
            reads.append(("reshard", new_workers, topo))
        elif op == "island":
            if sel == 0 or nw < 2:
                mgr.set_topology(None)            # leave: back to flat
            else:
                cut = 1 + (size % (nw - 1)) if nw > 2 else 1
                mgr.set_topology((tuple(range(cut)),
                                  tuple(range(cut, nw))))
            topo = mgr.topology
            reads.append(("island",
                          None if topo is None else topo.spec))
        if check_soundness:
            check_two_level()
    return reads


def _check_elastic_trace(trace, workers):
    scoped_reads = _drive_elastic_trace(trace, workers, scoped=True,
                                        check_soundness=True)
    global_reads = _drive_elastic_trace(trace, workers, scoped=False,
                                        check_soundness=True)
    assert scoped_reads == global_reads


class TestElasticSoundnessProperty:
    @given(trace=_TRACE_OPS, workers=st.integers(2, 4))
    @settings(max_examples=50, deadline=None)
    def test_soundness_and_differential(self, trace, workers):
        _check_elastic_trace(trace, workers)

    @pytest.mark.slow
    @given(trace=_TRACE_OPS, workers=st.integers(2, 8))
    @settings(max_examples=200, deadline=None)
    def test_soundness_and_differential_8worker_sweep(self, trace, workers):
        """The heavy sweep (up to 8 workers, more examples) — nightly."""
        _check_elastic_trace(trace, workers)

    def test_soundness_and_differential_seeded(self):
        """Deterministic seeded slice — runs even without hypothesis, so
        the fast lane always exercises reshard interleavings."""
        import random
        rng = random.Random(20240814)
        for workers in (2, 4):
            for _ in range(8):
                trace = [(rng.choice(_OPS), rng.randrange(3),
                          rng.randrange(1, 5), rng.randrange(8))
                         for _ in range(30)]
                _check_elastic_trace(trace, workers)


# ============================================================== engine layer
class TestEngineElastic:
    """The fast-lane twin of the bench's elastic replay."""

    def _setup(self):
        jax = pytest.importorskip("jax")
        import jax.numpy as jnp
        from repro.models import transformer as tfm
        from repro.models.config import ModelConfig
        tiny = ModelConfig(name="t", n_layers=1, d_model=32, n_heads=2,
                           n_kv_heads=1, d_ff=64, vocab=64, head_dim=16)
        params = tfm.init_params(jax.random.PRNGKey(0), tiny, jnp.float32)
        rng = np.random.RandomState(11)
        reqs = [(rng.randint(1, 64, size=rng.randint(4, 40)), f"s{i % 3}",
                 (i % 3) + 1, 4 + (i % 3)) for i in range(8)]
        return tiny, params, reqs

    def _drive(self, tiny, params, reqs, workers, schedule=None):
        from repro.serving.config import EngineConfig
        from repro.serving.engine import Engine
        eng = Engine(tiny, params, config=EngineConfig(
            num_blocks=6, max_batch=4, max_seq_len=256, fpr_enabled=True,
            num_workers=workers, scoped_fences=True, admission="fcfs"))
        for p, s, g, mnt in reqs:
            eng.submit(p, max_new_tokens=mnt, stream=s, group_id=g)
        steps = 0
        while not eng.sched.idle and eng.steps < 500:
            eng.step()
            steps += 1
            if schedule and steps in schedule:
                eng.resize_workers(schedule[steps])
        return eng, [list(map(int, r.generated))
                     for r in sorted(eng.sched.done, key=lambda r: r.rid)]

    def test_elastic_tokens_bit_identical_and_cheap(self):
        tiny, params, reqs = self._setup()
        _, t_fixed = self._drive(tiny, params, reqs, 1)
        eng, t_el = self._drive(tiny, params, reqs, 1,
                                schedule={2: 4, 5: 2})
        assert t_el == t_fixed                     # differential identity
        snap = eng.metrics.snapshot()
        assert snap["device.reshards"] == 2
        assert snap["table.reshards"] == 2
        assert snap["engine.num_workers"] == 2
        full = (eng.cache.max_batch * eng.cache.max_blocks_per_seq * 4)
        assert 0 < snap["device.reshard_refreshed_bytes"] < full
        assert snap["table.stale_lookups_detected"] == 0

    def test_resize_remaps_governor_ledger(self):
        tiny, params, reqs = self._setup()
        from repro.serving.config import EngineConfig
        from repro.serving.engine import Engine
        eng = Engine(tiny, params, config=EngineConfig(
            num_blocks=8, max_batch=4, max_seq_len=256,
            num_workers=4, admission="fcfs"))
        for p, s, g, mnt in reqs[:4]:
            eng.submit(p, max_new_tokens=mnt, stream=s, group_id=g)
        eng.step()
        led = eng.governor.ledger
        committed = led.committed
        assert committed > 0
        eng.resize_workers(2)
        led.check()                                 # invariants hold
        assert led.committed == committed           # capacity untouched
        assert len(led.per_worker) == 2
        eng.run()
        assert led.committed == 0

    def test_resize_noop_same_count(self):
        tiny, params, reqs = self._setup()
        eng, _ = self._drive(tiny, params, reqs[:2], 2)
        plan = eng.resize_workers(2)
        assert plan["moved_slots"] == []


class TestSimReshardCost:
    def test_sim_models_moved_fraction_refresh(self):
        """SimConfig.reshard_iters: the virtual-time model charges the
        moved row fraction of the device table, never a cold re-upload."""
        from repro.serving.sim import FenceImpactSim, SimConfig
        cfg = SimConfig(io_workers=2, iters=50, num_blocks=512,
                        reshard_iters=((10, 4), (30, 2)))
        res = FenceImpactSim(cfg).run()
        assert res.reshards == 2
        # 2→4 moves the slots whose owner changed; 4→2 (modulo) moves none
        assert res.reshard_moved_rows > 0
        assert res.device_refreshed_bytes > 0
        assert res.refresh_time > 0

    def test_sim_reshard_free_for_modulo_shrink(self):
        from repro.serving.sim import FenceImpactSim, SimConfig
        base = SimConfig(io_workers=4, iters=20, num_blocks=512, fpr=True,
                         shared_context=True)
        shrunk = SimConfig(io_workers=4, iters=20, num_blocks=512, fpr=True,
                           shared_context=True, reshard_iters=((10, 2),))
        r0 = FenceImpactSim(base).run()
        r1 = FenceImpactSim(shrunk).run()
        assert r1.reshards == 1
        assert r1.reshard_moved_rows == 0         # modulo shrink: free
        assert r1.io_ops == r0.io_ops


class TestTranslationValidation:
    """A malformed translation must be rejected BEFORE any per-worker
    structure mutates — reshard applies fully or not at all."""

    def test_manager_rejects_bad_translation_untouched(self):
        m = make_mgr(n=64, workers=2)
        mp = m.mmap(4, ctx(1), worker=0)
        masks_before = m.tracker._worker_mask.copy()
        epochs_before = m.fences.worker_epochs.copy()
        with pytest.raises(ValueError, match="translation"):
            m.reshard(2, translation=(5, 1))      # 5 outside new topology
        with pytest.raises(ValueError, match="translation"):
            m.reshard(4, translation=(0,))        # missing entry for w1
        np.testing.assert_array_equal(m.tracker._worker_mask, masks_before)
        np.testing.assert_array_equal(m.fences.worker_epochs, epochs_before)
        assert m.config.num_workers == 2
        m.munmap(mp.mapping_id, worker=0)

    def test_engine_rejects_bad_translation_before_ledger_remap(self):
        jax = pytest.importorskip("jax")
        import jax.numpy as jnp
        from repro.models import transformer as tfm
        from repro.models.config import ModelConfig
        from repro.serving.config import EngineConfig
        from repro.serving.engine import Engine
        tiny = ModelConfig(name="t", n_layers=1, d_model=32, n_heads=2,
                           n_kv_heads=1, d_ff=64, vocab=64, head_dim=16)
        params = tfm.init_params(jax.random.PRNGKey(0), tiny, jnp.float32)
        eng = Engine(tiny, params, config=EngineConfig(
            num_blocks=8, max_batch=4, max_seq_len=256,
            num_workers=2, admission="fcfs"))
        eng.submit(np.arange(1, 12), max_new_tokens=4, stream="s0")
        eng.step()
        per_worker_before = list(eng.governor.ledger.per_worker)
        with pytest.raises(ValueError, match="translation"):
            eng.resize_workers(2, translation=(5, 1))
        assert eng.governor.ledger.per_worker == per_worker_before
        assert eng.cache.num_workers == 2
        eng.run()

    def test_shared_fence_engine_with_extra_workers_reshards(self):
        """Review regression: a FenceEngine grown past the manager's
        topology (observer workers, like the sim's compute workers) must
        reshard through the default fold instead of indexing the
        translation out of range mid-reshard."""
        from repro.serving.sim import FenceImpactSim, SimConfig
        res = FenceImpactSim(SimConfig(io_workers=2, compute_workers=4,
                                       iters=8,
                                       reshard_iters=((3, 4),))).run()
        assert res.reshards == 1

    def test_numpy_int_worker_counts_accepted(self):
        m = make_mgr(n=64, workers=2)
        plan = m.reshard(np.int64(4))           # numpy ints are integers
        assert m.config.num_workers == 4
        assert isinstance(plan["moved_slots"], list)
