"""munmap microbenchmarks — paper Fig. 6–11 (cases 1–5), plus the
framework's two hot-path extensions:

Five thread mixes over a shared fast-device mapping pool:
  case1  N I/O workers                       (Fig. 7, vm-scalability-like)
  case2  1 I/O + N compute                   (Fig. 8)
  case3  N I/O + 1 compute                   (Fig. 9)
  case4  N I/O + N compute                   (Fig. 10)
  case5  N mixed workers                     (Fig. 11)
Reported: I/O + compute throughput and fence counts, FPR vs baseline.

Extensions (``--mode scoped`` runs only these):
  scoped_fences  global vs worker-scoped fences on an identical
                 context-rotation trace — modeled fence cost and
                 replicas spared (the numaPTE shootdown-filter analogue)
  engine_trace   the same comparison through the *actual* serving Engine
                 with sharded device block-tables — refreshed bytes and
                 fence counts, decoded tokens bit-identical
  alloc_batch    looped per-block allocation vs the batched
                 ``acquire``/``release`` lease hot path — wall time
                 (kept out of ``microbench_scoped.json``, which contains
                 only deterministic, seeded, diffable sections)

Kernel sweep (``--mode kernel``): the fused paged-attention DMA-vs-
compute sweep over (block_size × buffer_depth) — modeled latencies from
``KernelCostModel`` (deterministic; interpret-mode wall clocks are
noise), the autotune winner per shape, and a bitwise identity check of
the real pipelined/fused/split kernels at a small shape.  Artifact:
``microbench_kernel.json``.
"""

from __future__ import annotations

import time

from benchmarks.common import (ALLOC_COST, COMPUTE_Q, FENCE_COST,
                               improvement, save)
from repro.core.allocator import BlockAllocator
from repro.core.config import FprConfig
from repro.core.contexts import ContextScope, derive_context
from repro.core.fpr import FprMemoryManager
from repro.core.shootdown import FenceEngine
from repro.core.tracking import BlockTracker
from repro.serving.sim import FenceImpactSim, SimConfig


def _run(io, cp, mx, *, fpr, iters=1500, storage=0.0,
         in_kernel_frac=0.0):
    cfg = SimConfig(io_workers=io, compute_workers=cp, mixed_workers=mx,
                    iters=iters, fpr=fpr, alloc_cost=ALLOC_COST,
                    fence_cost=FENCE_COST, compute_quantum=COMPUTE_Q,
                    storage_latency=storage,
                    in_kernel_frac=in_kernel_frac)
    return FenceImpactSim(cfg).run()


def case(name: str, grid, mk):
    rows = []
    for n in grid:
        io, cp, mx = mk(n)
        base = _run(io, cp, mx, fpr=False)
        fpr = _run(io, cp, mx, fpr=True)
        rows.append({
            "n": n,
            "io_thr_base": base.throughput(),
            "io_thr_fpr": fpr.throughput(),
            "io_improvement_pct": improvement(fpr.throughput(),
                                              base.throughput()),
            "cp_thr_base": base.compute_throughput(),
            "cp_thr_fpr": fpr.compute_throughput(),
            "cp_improvement_pct": improvement(fpr.compute_throughput(),
                                              base.compute_throughput()),
            "fences_base": base.fences,
            "fences_fpr": fpr.fences,
            "fences_eliminated_pct": improvement(-fpr.fences, -base.fences)
            if base.fences else 0.0,
        })
    return {"case": name, "rows": rows}


def scoped_fence_case(workers: int = 8, iters: int = 1500,
                      contexts: int = 4, blocks_per_map: int = 8) -> dict:
    """Global vs worker-scoped fences on an *identical* trace.

    One I/O worker rotates through ``contexts`` recycling contexts — every
    mmap is a context exit, so a fence fires each cycle.  All staleness
    lives on worker 0, so the scoped path covers 1 of ``workers`` table
    replica groups while the global path rebroadcasts to all of them.
    """
    out: dict = {"workers": workers, "iters": iters, "contexts": contexts}
    for mode in ("global", "scoped"):
        eng = FenceEngine(measure=False)
        mgr = FprMemoryManager(
            config=FprConfig(num_blocks=2048, num_workers=workers,
                             fpr_enabled=True,
                             scoped_fences=(mode == "scoped")),
            fence_engine=eng)
        for i in range(iters):
            ctx = derive_context(ContextScope.PER_GROUP,
                                 group_id=(i % contexts) + 1)
            m = mgr.mmap(blocks_per_map, ctx, worker=0)
            mgr.munmap(m.mapping_id, worker=0)
        t = eng.totals()
        out[mode] = {k: t[k] for k in
                     ("fences", "fences_scoped", "modeled_s",
                      "replicas_spared", "elided_by_version",
                      "elided_by_scope", "workers_covered")}
    g, s = out["global"]["modeled_s"], out["scoped"]["modeled_s"]
    out["modeled_saving_pct"] = round((1 - s / g) * 100.0, 2) if g else 0.0
    return out


def alloc_batch_case(n: int = 64, iters: int = 300,
                     pool: int = 4096) -> dict:
    """Looped per-block alloc/free vs the batched hot path, wall time."""
    def drive(batched: bool) -> float:
        tr = BlockTracker(pool)
        alloc = BlockAllocator(pool, tr, num_workers=1)
        t0 = time.perf_counter()
        for _ in range(iters):
            if batched:
                alloc.release(alloc.acquire(n, worker_id=0))
            else:
                got = [alloc.acquire(1, worker_id=0) for _ in range(n)]
                for lease in got:
                    alloc.release(lease)
        return time.perf_counter() - t0

    looped_s = drive(batched=False)
    batched_s = drive(batched=True)
    return {"n": n, "iters": iters, "looped_s": round(looped_s, 6),
            "batched_s": round(batched_s, 6),
            "speedup": round(looped_s / batched_s, 2) if batched_s else None}


def _extension_sections(smoke: bool) -> dict:
    return {
        "scoped_fences": scoped_fence_case(iters=200 if smoke else 1500),
        "alloc_batch": alloc_batch_case(iters=30 if smoke else 300),
    }


def _print_scoped_fences(sf: dict) -> None:
    print(f"  scoped fences:   modeled {sf['global']['modeled_s']:.3f}s → "
          f"{sf['scoped']['modeled_s']:.3f}s "
          f"(-{sf['modeled_saving_pct']:.0f}%), "
          f"replicas spared {sf['scoped']['replicas_spared']}")


def _print_extensions(out: dict) -> None:
    _print_scoped_fences(out["scoped_fences"])
    ab = out["alloc_batch"]
    print(f"  batched alloc:   {ab['looped_s']*1e3:.1f}ms → "
          f"{ab['batched_s']*1e3:.1f}ms ({ab['speedup']}x)")


def run_scoped(smoke: bool = False) -> dict:
    """The scoped-fence extension benchmarks (deterministic artifact).

    ``microbench_scoped.json`` holds only seeded, deterministic sections
    (fence counts, modeled costs, refreshed bytes) so CI bench-smoke
    artifacts are diffable run-to-run; the wall-clock ``alloc_batch``
    timing lives in ``microbench.json`` instead.
    """
    from benchmarks import engine_trace
    out = {
        "seed": engine_trace.SEED,
        "scoped_fences": scoped_fence_case(iters=200 if smoke else 1500),
        "engine_trace": engine_trace.case(smoke=smoke),
    }
    save("microbench_scoped", out)
    _print_scoped_fences(out["scoped_fences"])
    engine_trace.report(out["engine_trace"])
    return out


def kernel_sweep_case(smoke: bool = False) -> dict:
    """(block_size × buffer_depth) sweep of the fused kernel's knobs.

    For every pool block size the sweep prices one decode-row page walk
    under the deterministic :class:`KernelCostModel`: the **naive**
    configuration (split K/V pools — two DMA descriptors per block — and
    no pipelining) against every fused buffer depth, records the
    :func:`repro.kernels.paged_attention.autotune.autotune` winner, and
    reports the tuned-vs-naive delta.  Larger blocks amortize descriptor
    cost (the paper's "one translation, more reach"); deeper buffers
    amortize the per-wait sync stall once compute can hide the copy.
    """
    from repro.kernels.paged_attention import autotune as at

    model = at.KernelCostModel()
    heads, head_dim = 8, 128
    n_blocks = 4 if smoke else 16
    block_sizes = (64, 128) if smoke else (32, 64, 128, 256)
    at.clear()
    rows = []
    for bs in block_sizes:
        block_bytes = bs * heads * 2 * head_dim * 4      # fused f32 block
        naive = model.step_s(n_blocks, block_bytes, bs, heads, head_dim,
                             fused=False, buffer_depth=1)
        by_depth = {d: model.step_s(n_blocks, block_bytes, bs, heads,
                                    head_dim, fused=True, buffer_depth=d)
                    for d in at.BUFFER_DEPTHS}
        tuned = at.autotune(heads, head_dim, bs, n_blocks, block_bytes)
        best = by_depth[tuned.buffer_depth]
        rows.append({
            "block_size": bs, "block_bytes": block_bytes,
            "naive_split_s": naive,
            "fused_by_depth_s": {str(d): v for d, v in by_depth.items()},
            "tuned_depth": tuned.buffer_depth,
            "tuned_s": best,
            # latency saved vs naive (positive = tuned faster)
            "tuned_vs_naive_pct": round((1 - best / naive) * 100.0, 2),
        })
    at.clear()           # sweeps are advisory here; leave engines on the
    #                      deterministic default unless they sweep too
    return {"heads": heads, "head_dim": head_dim, "n_blocks": n_blocks,
            "rows": rows}


def kernel_identity_case() -> dict:
    """Bitwise identity of the real kernels at one small shape: the split
    walk reads the K and V halves of the fused pool as two DMAs and hands
    the flash step the same tiles the fused walk reads as one, and
    pipelining only moves *when* bytes reach VMEM — so fused == split
    and every buffer depth == the unpipelined fused walk, exactly."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_attention.ops import (paged_attention,
                                                   paged_attention_split)
    from repro.models.attention import fuse_kv

    B, H, KV, hd, bs, M, N = 3, 4, 2, 16, 8, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (N, bs, KV, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (N, bs, KV, hd), jnp.float32)
    tables = jnp.asarray(np.random.RandomState(0).permutation(N)[
        :B * M].reshape(B, M).astype(np.int32))
    lengths = jnp.asarray([bs + 3, M * bs, 2 * bs - 1], jnp.int32)
    kv = fuse_kv(kp, vp)
    split = paged_attention_split(q, kv[:, 0], kv[:, 1], tables, lengths,
                                  interpret=True)
    fused = paged_attention(q, kv, tables, lengths, interpret=True)
    out = {"fused_eq_split": bool(jnp.array_equal(fused, split))}
    for d in (2, 4):
        piped = paged_attention(q, kv, tables, lengths, buffer_depth=d,
                                interpret=True)
        out[f"depth{d}_eq_fused"] = bool(jnp.array_equal(piped, fused))
    return out


def run_kernel(smoke: bool = False) -> dict:
    """The fused-kernel DMA sweep (deterministic artifact)."""
    out = {
        "sweep": kernel_sweep_case(smoke=smoke),
        "identity": kernel_identity_case(),
    }
    save("microbench_kernel", out)
    rows = out["sweep"]["rows"]
    best = max(rows, key=lambda r: r["tuned_vs_naive_pct"])
    print(f"  kernel sweep:    tuned depth {best['tuned_depth']} at "
          f"bs={best['block_size']} beats naive split by "
          f"{best['tuned_vs_naive_pct']:.0f}% (modeled); identity "
          f"{out['identity']}")
    if not all(out["identity"].values()):
        raise AssertionError(f"kernel identity broken: {out['identity']}")
    if any(r["tuned_s"] > r["naive_split_s"] for r in rows):
        raise AssertionError("autotuned fused config lost to the naive "
                             "split walk under its own cost model")
    return out


def run(smoke: bool = False) -> dict:
    grids = {
        "case1": [1, 2, 4, 8, 16, 32],
        "case2": [1, 2, 4, 8, 16, 32, 48],
        "case3": [1, 2, 4, 8, 16],
        "case4": [1, 2, 4, 8],
        "case5": [1, 2, 4, 8, 16],
    }
    if smoke:                      # CI smoke lane: smallest useful grid
        grids = {k: v[:3] for k, v in grids.items()}
    out = {
        "case1": case("case1", grids["case1"], lambda n: (n, 0, 0)),
        "case2": case("case2", grids["case2"], lambda n: (1, n, 0)),
        "case3": case("case3", grids["case3"], lambda n: (n, 1, 0)),
        "case4": case("case4", grids["case4"], lambda n: (n, n, 0)),
        "case5": case("case5", grids["case5"], lambda n: (0, 0, n)),
    }
    out.update(_extension_sections(smoke))
    save("microbench", out)
    _print_extensions(out)
    c2 = out["case2"]["rows"][-1]
    c1 = out["case1"]["rows"][min(2, len(out["case1"]["rows"]) - 1)]
    print(f"  case1 (4 I/O):   io +{c1['io_improvement_pct']:.0f}% "
          f"(paper: up to 30–92%)  fences {c1['fences_base']}→"
          f"{c1['fences_fpr']}")
    print(f"  case2 (48 cp):   compute +{c2['cp_improvement_pct']:.0f}% "
          f"(paper: up to 21%)  io +{c2['io_improvement_pct']:.0f}%")
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["all", "scoped", "kernel"],
                    default="all",
                    help="'scoped' runs only the scoped-fence + "
                         "batched-alloc extension benchmarks; 'kernel' "
                         "the fused paged-attention DMA sweep")
    ap.add_argument("--smoke", action="store_true")
    a = ap.parse_args()
    {"scoped": run_scoped, "kernel": run_kernel}.get(a.mode, run)(
        smoke=a.smoke)
