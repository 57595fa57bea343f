"""Multi-worker serving traces through the *actual* Engine (not the sim).

A seeded request trace — several streams with distinct recycling contexts
over a deliberately tight block pool, so completions recycle blocks across
contexts and context-exit fences actually fire — is replayed through
``repro.serving.Engine`` with ``num_workers`` workers:

  * ``global``  — ``scoped_fences=False``: every fence re-uploads the whole
                  device block-table (the paper's broadcast pessimism);
  * ``sharded`` — ``scoped_fences=True``: each fence re-uploads only the
                  table shards of the workers in its mask.

Reported per path: fence counts, device-refreshed table entries/bytes, and
the decoded tokens, which must be **bit-identical** — scoping only moves
*when* device table copies are refreshed, never what they contain.  All
counters are read from the unified ``MetricsRegistry`` flat snapshot, so
the artifact keys are exactly the schema CI validates.

**Elastic replay.**  The same trace runs once more through an engine whose
worker topology changes *mid-trace* — grow 1→4 after two steps, shrink
4→2 a few steps later (``Engine.resize_workers``, drain-free, governed by
the admission ledger).  Acceptance: tokens stay bit-identical to the
fixed-topology run, and the reshard's device refresh traffic
(``device.reshard_refreshed_bytes`` — only the rows whose shard owner
moved) is strictly below ONE full-table re-upload, i.e. a topology change
costs the moved fraction, never a cold start.

**Shared-prefix replay** (``run_prefix`` → ``BENCH_prefix.json``, the
first perf-trajectory artifact).  The same engine replays a trace whose
requests all carry one full-block system prompt, with prefix sharing on
vs off.  Acceptance, enforced by :func:`prefix_report` and re-checked by
``benchmarks/validate.py`` in the push lane: tokens bit-identical, ≥40%
fewer unique blocks allocated, zero fences while blocks remain inside a
sharing set (``fpr.prefix.in_set_violations == 0`` — and on this
single-tenant trace, zero fences at all), and the admission ledger —
committing *unique* blocks — running strictly more requests concurrently
at the same pool size.

**Chunked-prefill replay** (``run_chunked`` → ``BENCH_chunked.json``).
One trace of mixed non-block-aligned prompt lengths through the engine
with ``chunked_prefill`` off vs on: tokens bit-identical, the chunk path
traced exactly once across all lengths (the monolithic baseline retraces
per padded prompt shape), plus the open-loop mice-and-elephants
``admission_sim`` section where chunk-grown elephants must strictly
improve the mice ``queue_wait_p99``.  Enforced by :func:`chunked_report`
and re-checked by ``benchmarks/validate.py`` in the push lane.

The whole trace is deterministic (seeded prompts, greedy decode), so the
JSON artifact is diffable run-to-run.
"""

from __future__ import annotations

import zlib

import numpy as np

from benchmarks.common import save

SEED = 20240814

_CFG_KW = dict(name="trace", n_layers=1, d_model=32, n_heads=2,
               n_kv_heads=1, d_ff=64, vocab=64, head_dim=16)

#: the elastic schedule: after step k, resize to v workers (grow → shrink)
ELASTIC_SCHEDULE = {2: 4, 6: 2}

#: flat MetricsRegistry keys reported per trace mode
_REPORT_KEYS = (
    "fence.fences",
    "fence.fences_scoped",
    "fence.replicas_spared",
    "device.full_refreshes",
    "device.shard_refreshes",
    "device.refreshed_entries",
    "device.refreshed_bytes",
    "admission.admitted",
    "admission.rejected_overcommit",
    "admission.preemptions_recompute",
    "admission.preemptions_swap",
    "admission.affinity_hit_rate",
)

_ELASTIC_KEYS = _REPORT_KEYS + (
    "device.reshards",
    "device.reshard_moved_entries",
    "device.reshard_refreshed_bytes",
    "table.reshards",
    "table.num_shards",
    "engine.num_workers",
)


def _trace(n_requests: int, n_streams: int, seed: int = SEED):
    """Seeded (prompt, stream, group, max_new) tuples."""
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n_requests):
        s = i % n_streams
        reqs.append((rng.randint(1, _CFG_KW["vocab"], size=rng.randint(4, 40)),
                     f"stream{s}", s + 1, 4 + (i % 3)))
    return reqs


def _make_engine(params, *, num_workers: int, scoped: bool,
                 num_blocks: int, max_batch: int):
    from repro.models.config import ModelConfig
    from repro.serving.config import EngineConfig
    from repro.serving.engine import Engine

    # fcfs governor ≡ the legacy fill-every-slot order on this trace (all
    # windows fit), but the replay output gains the admission counters
    return Engine(ModelConfig(**_CFG_KW), params,
                  config=EngineConfig(num_blocks=num_blocks,
                                      max_batch=max_batch, max_seq_len=256,
                                      fpr_enabled=True,
                                      num_workers=num_workers,
                                      scoped_fences=scoped,
                                      admission="fcfs"))


def _replay(eng, reqs, resize_schedule: dict | None = None):
    """Drive the trace; optionally resize the worker topology mid-trace."""
    for prompt, stream, gid, mnt in reqs:
        eng.submit(prompt, max_new_tokens=mnt, stream=stream, group_id=gid)
    steps = 0
    while not eng.sched.idle and eng.steps < 10_000:
        eng.step()
        steps += 1
        if resize_schedule and steps in resize_schedule:
            eng.resize_workers(resize_schedule[steps])
    toks = [list(map(int, r.generated))
            for r in sorted(eng.sched.done, key=lambda r: r.rid)]
    return eng.metrics.snapshot(), toks


def _drive(params, reqs, *, num_workers: int, scoped: bool,
           num_blocks: int, max_batch: int,
           resize_schedule: dict | None = None):
    eng = _make_engine(params, num_workers=num_workers, scoped=scoped,
                       num_blocks=num_blocks, max_batch=max_batch)
    return _replay(eng, reqs, resize_schedule)


def case(smoke: bool = False, num_workers: int = 4) -> dict:
    """Global vs sharded refresh + elastic resharding, one identical trace."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tfm
    from repro.models.config import ModelConfig

    params = tfm.init_params(jax.random.PRNGKey(0), ModelConfig(**_CFG_KW),
                             jnp.float32)
    reqs = _trace(n_requests=8 if smoke else 16, n_streams=3)
    kw = dict(num_blocks=6, max_batch=4)
    out: dict = {"seed": SEED, "num_workers": num_workers,
                 "requests": len(reqs), **kw}
    toks = {}
    snaps = {}
    for mode, scoped in (("global", False), ("sharded", True)):
        snaps[mode], toks[mode] = _drive(params, reqs,
                                         num_workers=num_workers,
                                         scoped=scoped, **kw)
        out[mode] = {k: snaps[mode].get(k) for k in _REPORT_KEYS}
    out["tokens_identical"] = toks["global"] == toks["sharded"]
    g = out["global"]["device.refreshed_bytes"]
    s = out["sharded"]["device.refreshed_bytes"]
    out["refreshed_bytes_saving_pct"] = (round((1 - s / g) * 100.0, 2)
                                         if g else 0.0)

    # elastic replay: start on 1 worker, grow 1→4 mid-trace, shrink 4→2 —
    # tokens must match the fixed-topology runs bit for bit, and the
    # reshard refresh must stay below one full-table re-upload
    el_eng = _make_engine(params, num_workers=1, scoped=True, **kw)
    el_snap, el_toks = _replay(el_eng, reqs,
                               resize_schedule=dict(ELASTIC_SCHEDULE))
    full_table_bytes = (el_eng.cache.max_batch
                        * el_eng.cache.max_blocks_per_seq * 4)
    out["elastic"] = {
        "schedule": {str(k): v for k, v in ELASTIC_SCHEDULE.items()},
        "tokens_identical": el_toks == toks["sharded"],
        "full_table_bytes": full_table_bytes,
        **{k: el_snap.get(k) for k in _ELASTIC_KEYS},
    }
    return out


def report(out: dict) -> None:
    """Print the global-vs-sharded + elastic summary; fail loud on drift."""
    g, s = out["global"], out["sharded"]
    print(f"  engine trace:    refreshed bytes {g['device.refreshed_bytes']}"
          f" → {s['device.refreshed_bytes']} "
          f"(-{out['refreshed_bytes_saving_pct']:.0f}%), "
          f"fences {g['fence.fences']} → {s['fence.fences']} "
          f"({s['fence.fences_scoped']} scoped), "
          f"tokens identical: {out['tokens_identical']}")
    el = out["elastic"]
    print(f"  elastic 1→4→2:   reshards {el['device.reshards']}, moved "
          f"rows refreshed {el['device.reshard_refreshed_bytes']}B vs "
          f"full-table {el['full_table_bytes']}B, tokens identical: "
          f"{el['tokens_identical']}")
    if not out["tokens_identical"]:
        raise AssertionError("sharded path changed decoded tokens")
    if not el["tokens_identical"]:
        raise AssertionError("elastic resharding changed decoded tokens")
    if el["device.reshards"] < 2:
        raise AssertionError("elastic replay applied fewer than 2 reshards")
    if not el["device.reshard_refreshed_bytes"] < el["full_table_bytes"]:
        raise AssertionError(
            "reshard refreshed "
            f"{el['device.reshard_refreshed_bytes']}B — not below one "
            f"full-table re-upload ({el['full_table_bytes']}B)")


#: flat MetricsRegistry keys reported per shared-prefix mode
_PREFIX_KEYS = (
    "fpr.allocs",
    "fpr.prefix.lookups",
    "fpr.prefix.hit_blocks",
    "fpr.prefix.miss_blocks",
    "fpr.prefix.hit_rate",
    "fpr.prefix.cow_copies",
    "fpr.prefix.sharing_exits",
    "fpr.prefix.in_set_violations",
    "fence.fences",
    "admission.admitted",
    "admission.ledger.peak_committed",
)


def prefix_case(smoke: bool = False) -> dict:
    """Shared-system-prompt trace, prefix sharing on vs off."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tfm
    from repro.models.config import ModelConfig
    from repro.serving.config import EngineConfig
    from repro.serving.engine import Engine

    cfg = ModelConfig(**_CFG_KW)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    rng = np.random.RandomState(SEED + 1)
    system = rng.randint(1, _CFG_KW["vocab"], size=tfm.BLOCK_SIZE)
    n_requests = 6 if smoke else 12
    reqs = [(np.concatenate([system,
                             rng.randint(1, _CFG_KW["vocab"],
                                         size=rng.randint(4, 20))]),
             f"user{i}", 1, 4 + (i % 3))
            for i in range(n_requests)]
    # a deliberately tight pool: every window is 2 blocks, so unshared
    # admission caps out at 2 concurrent requests — sharing must beat it
    kw = dict(num_blocks=5, max_batch=4)
    out: dict = {"seed": SEED + 1, "requests": n_requests,
                 "system_prompt_blocks": 1, "window_blocks": 2, **kw}
    toks = {}
    for mode, sharing in (("shared", True), ("unshared", False)):
        eng = Engine(cfg, params, config=EngineConfig(
            max_seq_len=256, fpr_enabled=True, admission="fcfs",
            prefix_sharing=sharing, **kw))
        for prompt, stream, gid, mnt in reqs:
            eng.submit(prompt, max_new_tokens=mnt, stream=stream,
                       group_id=gid)
        peak = 0
        while not eng.sched.idle and eng.steps < 10_000:
            eng.step()
            peak = max(peak, len(eng.sched.running))
        toks[mode] = [list(map(int, r.generated))
                      for r in sorted(eng.sched.done, key=lambda r: r.rid)]
        snap = eng.metrics.snapshot()
        out[mode] = {"peak_running": peak,
                     **{k: snap.get(k) for k in _PREFIX_KEYS}}
    out["tokens_identical"] = toks["shared"] == toks["unshared"]
    u, s = out["unshared"]["fpr.allocs"], out["shared"]["fpr.allocs"]
    out["unique_blocks_saving_pct"] = (round((1 - s / u) * 100.0, 2)
                                       if u else 0.0)
    return out


def prefix_report(out: dict) -> None:
    """Print the sharing summary; fail loud on any acceptance regression."""
    s, u = out["shared"], out["unshared"]
    print(f"  shared prefix:   unique blocks {u['fpr.allocs']} → "
          f"{s['fpr.allocs']} (-{out['unique_blocks_saving_pct']:.0f}%), "
          f"hit rate {s['fpr.prefix.hit_rate']}, "
          f"cow {s['fpr.prefix.cow_copies']}, concurrency "
          f"{u['peak_running']} → {s['peak_running']}, "
          f"tokens identical: {out['tokens_identical']}")
    if not out["tokens_identical"]:
        raise AssertionError("prefix sharing changed decoded tokens")
    if out["unique_blocks_saving_pct"] < 40.0:
        raise AssertionError(
            f"shared-prefix trace saved only "
            f"{out['unique_blocks_saving_pct']}% unique blocks (< 40%)")
    if s["fpr.prefix.in_set_violations"]:
        raise AssertionError("a refcounted block reached the allocator "
                             "(fence inside a sharing set)")
    if s["fence.fences"]:
        raise AssertionError("single-tenant shared trace issued fences")
    if not s["peak_running"] > u["peak_running"]:
        raise AssertionError(
            f"unique-block admission ran {s['peak_running']} concurrent "
            f"requests — not above the unshared {u['peak_running']}")


#: flat MetricsRegistry keys reported per chunked-prefill mode
_CHUNK_KEYS = (
    "engine.prefill_traces",
    "engine.prefill_chunk_traces",
    "engine.prefill_chunks",
    "engine.completed",
    "admission.chunk_grows",
    "admission.admitted",
    "admission.holds",
)

#: the open-loop mice-and-elephants regime for the chunked sim section —
#: admission_bench.SLA_SIM_KW's workload, deadline policy (FCFS first-fit
#: simply starves the elephants monolithically, which zeroes the mice tail
#: by never seating an elephant at all — not a comparison worth winning)
_CHUNK_SIM_KW = dict(pool_blocks=8, max_batch=8, window_lo=1, window_hi=8,
                     arrival_every=1.5, large_frac=0.12, steps_per_block=4,
                     sla_steps=32, seed=23, policy="deadline")


def chunked_case(smoke: bool = False) -> dict:
    """Chunked vs monolithic prefill: bit-identical tokens, one trace.

    Two sections:

    * ``monolithic`` / ``chunked`` — the *real* Engine replays one trace of
      deliberately mixed, non-block-aligned prompt lengths.  Decoded tokens
      must be **bit-identical** (chunking only changes *when* prompt blocks
      commit, never what attention computes — the chunk kernel's extra
      causally-masked keys contribute exact zeros).  The fixed chunk shape
      must also kill the per-prompt-length ``jax.jit`` retrace:
      ``engine.prefill_chunk_traces == 1`` across all lengths, while the
      monolithic run retraces ``engine.prefill_traces`` once per distinct
      padded prompt shape.
    * ``sim`` — the open-loop mice-and-elephants ``admission_sim`` regime:
      with ``chunk_blocks`` set, an elephant is admitted on its first
      chunk and grows per written block, releasing the pool to mice for
      most of its service — ``queue_wait_p99_mice`` must be strictly
      better chunked than monolithic.
    """
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tfm
    from repro.models.config import ModelConfig
    from repro.serving.config import EngineConfig
    from repro.serving.engine import Engine
    from repro.serving.sim import AdmissionSimConfig, admission_sim

    cfg = ModelConfig(**_CFG_KW)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    rng = np.random.RandomState(SEED + 2)
    lengths = ((40, 200, 170, 300) if smoke
               else (40, 200, 170, 300, 90, 260, 410, 130))
    reqs = [(rng.randint(1, _CFG_KW["vocab"], size=n), f"s{i % 2}",
             (i % 2) + 1, 6 + (i % 3)) for i, n in enumerate(lengths)]
    kw = dict(num_blocks=64, max_batch=4)
    out: dict = {"seed": SEED + 2, "requests": len(reqs),
                 "prompt_lengths": list(lengths), "prefill_chunk": 1, **kw}
    toks = {}
    for mode, chunked in (("monolithic", False), ("chunked", True)):
        eng = Engine(cfg, params, config=EngineConfig(
            max_seq_len=1024, fpr_enabled=True, admission="fcfs",
            chunked_prefill=chunked, prefill_chunk=1, **kw))
        for prompt, stream, gid, mnt in reqs:
            eng.submit(prompt, max_new_tokens=mnt, stream=stream,
                       group_id=gid)
        while not eng.sched.idle and eng.steps < 10_000:
            eng.step()
        toks[mode] = [list(map(int, r.generated))
                      for r in sorted(eng.sched.done, key=lambda r: r.rid)]
        snap = eng.metrics.snapshot()
        out[mode] = {k: snap.get(k) for k in _CHUNK_KEYS}
    out["tokens_identical"] = toks["monolithic"] == toks["chunked"]

    n = 48 if smoke else 96
    sim: dict = {"config": {**_CHUNK_SIM_KW, "n_requests": n}}
    for label, cb in (("monolithic", 0), ("chunked", 1)):
        sim[label] = admission_sim(AdmissionSimConfig(
            chunk_blocks=cb, n_requests=n, **_CHUNK_SIM_KW))
    out["sim"] = sim
    return out


def chunked_report(out: dict) -> None:
    """Print the chunked summary; fail loud on any acceptance regression."""
    m, c = out["monolithic"], out["chunked"]
    sm = out["sim"]["monolithic"]
    sc = out["sim"]["chunked"]
    print(f"  chunked prefill: traces monolithic "
          f"{m['engine.prefill_traces']} → chunked "
          f"{c['engine.prefill_chunk_traces']} "
          f"({c['engine.prefill_chunks']} chunks, "
          f"{c['admission.chunk_grows']} grows), tokens identical: "
          f"{out['tokens_identical']}")
    print(f"  mice & elephants: queue-wait p99 (mice) monolithic "
          f"{sm['queue_wait_p99_mice']} → chunked "
          f"{sc['queue_wait_p99_mice']} "
          f"(makespan {sm['makespan']} → {sc['makespan']})")
    if not out["tokens_identical"]:
        raise AssertionError("chunked prefill changed decoded tokens")
    if c["engine.prefill_chunk_traces"] != 1 or c["engine.prefill_traces"]:
        raise AssertionError(
            f"chunked prefill must trace exactly once (got "
            f"{c['engine.prefill_chunk_traces']} chunk traces, "
            f"{c['engine.prefill_traces']} monolithic traces)")
    if m["engine.prefill_traces"] < 2:
        raise AssertionError(
            "monolithic baseline no longer retraces per prompt shape — "
            "the trace lost its mixed lengths")
    if not sc["queue_wait_p99_mice"] < sm["queue_wait_p99_mice"]:
        raise AssertionError(
            f"chunked admission must beat monolithic on mice p99 "
            f"queue-wait (got {sc['queue_wait_p99_mice']} vs "
            f"{sm['queue_wait_p99_mice']})")


#: snapshot keys the ragged-kernel comparison records per engine mode
_KERNEL_KEYS = _CHUNK_KEYS + (
    "engine.kernel.dma_bytes",
    "engine.kernel.kernel_calls",
    "engine.kernel.pipeline_depth",
    "engine.kernel.ragged_steps",
    "engine.steps",
)


def kernel_case(smoke: bool = False) -> dict:
    """Ragged fused-KV serving: the whole mixed prefill+decode batch —
    chunk rows and decode rows alike — through ONE ragged kernel call
    per attention layer per engine step.

    Three engines replay one trace of mixed, non-block-aligned prompts:

    * ``chunked_ref`` — the per-slot chunked path (jnp reference
      attention), the token oracle;
    * ``ragged_ref`` — the ragged pass over the reference ragged
      attention (isolates the batching rewrite from the kernel);
    * ``ragged_kernel`` — the ragged pass over the pallas fused-KV
      kernel under interpret mode (the real scalar-prefetched ragged
      page walk).

    Decoded tokens must be **bit-identical** across all three (the
    ragged pack only changes *which call* serves a row, never what its
    attention computes), every ragged engine must trace one program per
    token capacity (``prefill_chunk_traces == programs``), and the kernel
    counters must show exactly one ragged kernel call per attention layer
    per step — ``kernel_calls == n_layers * ragged_steps`` — whatever the
    step's prefill/decode blend.  The tuned-vs-naive delta is modeled
    (``KernelCostModel``, like ``FenceCostModel``): interpret-mode wall
    clocks on CPU are noise.
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_attention import autotune as pa_at
    from repro.models import transformer as tfm
    from repro.models.config import ModelConfig
    from repro.serving.config import EngineConfig
    from repro.serving.engine import Engine

    cfg = ModelConfig(**_CFG_KW)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    rng = np.random.RandomState(SEED + 4)
    lengths = ((40, 150, 90, 200) if smoke
               else (40, 200, 170, 300, 90, 260))
    reqs = [(rng.randint(1, _CFG_KW["vocab"], size=n), f"s{i % 2}",
             (i % 2) + 1, 6 + (i % 3)) for i, n in enumerate(lengths)]
    kw = dict(num_blocks=64, max_batch=4)
    out: dict = {"seed": SEED + 4, "requests": len(reqs),
                 "prompt_lengths": list(lengths), "prefill_chunk": 1, **kw}
    modes = (("chunked_ref", False, "ref"),
             ("ragged_ref", True, "ref"),
             ("ragged_kernel", True, "pallas_interpret"))
    toks = {}
    for mode, ragged, impl in modes:
        eng = Engine(cfg, params, config=EngineConfig(
            max_seq_len=1024, fpr_enabled=True, admission="fcfs",
            chunked_prefill=True, prefill_chunk=1, page_impl=impl,
            ragged_kernel=ragged, **kw))
        for prompt, stream, gid, mnt in reqs:
            eng.submit(prompt, max_new_tokens=mnt, stream=stream,
                       group_id=gid)
        while not eng.sched.idle and eng.steps < 10_000:
            eng.step()
        toks[mode] = [list(map(int, r.generated))
                      for r in sorted(eng.sched.done, key=lambda r: r.rid)]
        snap = eng.metrics.snapshot()
        out[mode] = {k: snap.get(k) for k in _KERNEL_KEYS}
        out[mode]["programs"] = len(eng.ragged_capacities)
    out["tokens_identical"] = (toks["chunked_ref"] == toks["ragged_ref"]
                               == toks["ragged_kernel"])
    # fixed-seed fingerprint of the decoded stream — a run-to-run drift
    # in kernel numerics shows up here before anything else does
    flat = np.concatenate([np.asarray(t, np.int32)
                           for t in toks["ragged_kernel"]] or
                          [np.zeros(0, np.int32)])
    out["token_crc"] = zlib.crc32(flat.tobytes())
    out["n_layers"] = _CFG_KW["n_layers"]

    # modeled tuned-vs-naive at the engine's own kernel shape
    model = pa_at.KernelCostModel()
    bs = tfm.BLOCK_SIZE
    heads, hd = _CFG_KW["n_kv_heads"], _CFG_KW["head_dim"]
    block_bytes = bs * heads * 2 * hd * 4
    n_blocks = max(-(-n // bs) for n in lengths)
    depth = pa_at.get_tuning(heads, hd, bs).buffer_depth
    naive = model.step_s(n_blocks, block_bytes, bs, heads, hd,
                         fused=False, buffer_depth=1)
    tuned = model.step_s(n_blocks, block_bytes, bs, heads, hd,
                         fused=True, buffer_depth=depth)
    out["modeled"] = {
        "block_bytes": block_bytes, "n_blocks": n_blocks,
        "pipeline_depth": depth,
        "naive_split_s": naive, "tuned_fused_s": tuned,
        "tuned_vs_naive_pct": round((1 - tuned / naive) * 100.0, 2),
    }
    return out


def kernel_report(out: dict) -> None:
    """Print the ragged-kernel summary; fail loud on any regression."""
    rk = out["ragged_kernel"]
    md = out["modeled"]
    print(f"  ragged kernel:   {rk['engine.kernel.ragged_steps']} steps, "
          f"{rk['engine.kernel.kernel_calls']} kernel calls "
          f"({out['n_layers']} layer(s)), "
          f"{rk['engine.kernel.dma_bytes']} fused DMA bytes, "
          f"depth {rk['engine.kernel.pipeline_depth']}; tokens identical: "
          f"{out['tokens_identical']} (crc {out['token_crc']:#010x})")
    print(f"  tuned vs naive:  {md['tuned_fused_s']:.3e}s vs "
          f"{md['naive_split_s']:.3e}s modeled "
          f"({md['tuned_vs_naive_pct']:.0f}% saved)")
    if not out["tokens_identical"]:
        raise AssertionError(
            "ragged serving changed decoded tokens vs the chunked oracle")
    for mode in ("ragged_ref", "ragged_kernel"):
        m = out[mode]
        if (m["engine.prefill_chunk_traces"] != m["programs"]
                or m["engine.prefill_traces"]):
            raise AssertionError(
                f"{mode} must trace once per capacity, {m['programs']} "
                f"(got {m['engine.prefill_chunk_traces']} chunk traces, "
                f"{m['engine.prefill_traces']} monolithic traces)")
        calls, steps = (m["engine.kernel.kernel_calls"],
                        m["engine.kernel.ragged_steps"])
        if calls != out["n_layers"] * steps:
            raise AssertionError(
                f"{mode}: mixed prefill+decode batches must be served by "
                f"one kernel call per layer per step — got {calls} calls "
                f"over {steps} steps")
    if md["tuned_fused_s"] > md["naive_split_s"]:
        raise AssertionError(
            "tuned fused pipeline lost to the naive split walk under the "
            "kernel cost model")


#: island partition of the hierarchical replay: 2 islands × 2 workers
ISLANDS = ((0, 1), (2, 3))

#: stream names whose crc32 routing pins them to workers 0/1/2/3 — the
#: trace needs streams on *specific* workers so sharing sets span a known
#: pair of islands (zlib.crc32("stream4") % 4 == 1, etc.)
_STREAM_OF_WORKER = {0: "stream0", 1: "stream4", 2: "stream1", 3: "stream5"}


def _topology_trace(n_requests: int, seed: int = SEED):
    """Two interleaved sharing groups over pinned workers.

    Group 1 shares system prompt A between workers 0 and 1 — both inside
    island 0, so its sharing-exit/recycle fences are **intra**-island.
    Group 2 shares system prompt B between workers 0 and 2 — islands 0
    and 1, so its fences must **cross**.  Shared blocks carry multi-worker
    presence masks (each attach touches them from that stream's worker),
    which is what widens the fence scope past one worker in the first
    place.
    """
    from repro.models import transformer as tfm

    rng = np.random.RandomState(seed)
    vocab = _CFG_KW["vocab"]
    sys_a = rng.randint(1, vocab, size=tfm.BLOCK_SIZE)
    sys_b = rng.randint(1, vocab, size=tfm.BLOCK_SIZE)
    reqs = []
    for i in range(n_requests):
        if i % 2 == 0:
            system, gid, w = sys_a, 1, (0, 1)[(i // 2) % 2]
        else:
            system, gid, w = sys_b, 2, (0, 2)[(i // 2) % 2]
        prompt = np.concatenate(
            [system, rng.randint(1, vocab, size=rng.randint(4, 16))])
        reqs.append((prompt, _STREAM_OF_WORKER[w], gid, 4 + (i % 3)))
    return reqs

#: island counter keys reported for the multi-island replay (the
#: ``ISLAND_SCHEMA`` groups materialized only under a hierarchy)
_ISLAND_KEYS = (
    "fence.island.num_islands",
    "fence.island.fences_intra",
    "fence.island.fences_cross",
    "fence.island.deltas_propagated",
    "fence.island.modeled_intra_s",
    "fence.island.modeled_cross_s",
    "table.island.fences_intra",
    "table.island.fences_cross",
    "table.island.shard_bumps_intra",
    "table.island.shard_bumps_remote",
    "device.island.intra_refreshes",
    "device.island.remote_deltas",
    "device.island.delta_entries",
    "device.island.delta_bytes",
)


def topology_case(smoke: bool = False) -> dict:
    """Hierarchical 2×2-island replay vs flat 4-worker scoped fencing.

    The same seeded trace runs twice through a 4-worker engine under
    ``worker_routing="stream"`` (so slot rows land outside their worker's
    modulo shard — the foreign bindings a scoped fence must pull in):

      * ``flat``    — single island: every covered shard is re-uploaded
                      in full (pre-island scoped fencing, bit for bit);
      * ``islands`` — ``((0,1),(2,3))``: shards inside the covered
                      islands still re-upload in full, but foreign shards
                      on *remote* islands receive the compact
                      delta-propagated update instead (billed to
                      ``device.island.delta_bytes``).

    Acceptance (re-checked by ``benchmarks/validate.py``): decoded tokens
    bit-identical, total ``device.refreshed_bytes`` strictly lower under
    islands, and per-fence modeled cost strictly cheaper intra-island
    than cross-island (the ``cross_island_cost`` multiplier).  A third
    replay reshapes a *live* flat engine to the island partition and back
    (``Engine.reshape`` — islands join/leave mid-trace) and must also
    stay bit-identical.
    """
    import jax
    import jax.numpy as jnp
    from repro.core.topology import Topology
    from repro.models import transformer as tfm
    from repro.models.config import ModelConfig
    from repro.serving.config import EngineConfig
    from repro.serving.engine import Engine

    params = tfm.init_params(jax.random.PRNGKey(0), ModelConfig(**_CFG_KW),
                             jnp.float32)
    reqs = _topology_trace(n_requests=12 if smoke else 20)
    kw = dict(num_blocks=6, max_batch=4)

    def build(islands):
        return Engine(ModelConfig(**_CFG_KW), params,
                      config=EngineConfig(max_seq_len=256, fpr_enabled=True,
                                          num_workers=4, scoped_fences=True,
                                          worker_routing="stream",
                                          admission="fcfs",
                                          islands=islands, **kw))

    out: dict = {"seed": SEED, "islands": [list(i) for i in ISLANDS],
                 "requests": len(reqs), "num_workers": 4, **kw}
    toks = {}
    for mode, islands in (("flat", None), ("islands", ISLANDS)):
        snap, toks[mode] = _replay(build(islands), reqs)
        keys = _REPORT_KEYS + (_ISLAND_KEYS if islands else ())
        out[mode] = {k: snap.get(k) for k in keys}
    out["tokens_identical"] = toks["flat"] == toks["islands"]
    f = out["flat"]["device.refreshed_bytes"]
    i = out["islands"]["device.refreshed_bytes"]
    out["cross_island_bytes_saving_pct"] = (round((1 - i / f) * 100.0, 2)
                                            if f else 0.0)
    isl = out["islands"]
    fi = isl["fence.island.fences_intra"]
    fx = isl["fence.island.fences_cross"]
    out["modeled_intra_per_fence_s"] = (
        round(isl["fence.island.modeled_intra_s"] / fi, 9) if fi else None)
    out["modeled_cross_per_fence_s"] = (
        round(isl["fence.island.modeled_cross_s"] / fx, 9) if fx else None)

    # live reshape: the flat engine joins the island partition after two
    # steps and dissolves it back to flat a few steps later — tokens must
    # stay bit-identical to the fixed-flat run (reshape moves replica
    # groups, never rows' contents)
    eng = build(None)
    for prompt, stream, gid, mnt in reqs:
        eng.submit(prompt, max_new_tokens=mnt, stream=stream, group_id=gid)
    schedule = {2: Topology.of(ISLANDS), 6: Topology.flat(4)}
    steps = 0
    while not eng.sched.idle and eng.steps < 10_000:
        eng.step()
        steps += 1
        if steps in schedule:
            eng.reshape(schedule[steps])
    r_toks = [list(map(int, r.generated))
              for r in sorted(eng.sched.done, key=lambda r: r.rid)]
    r_snap = eng.metrics.snapshot()
    out["reshape"] = {
        "schedule": {"2": [list(i) for i in ISLANDS], "6": "flat"},
        "tokens_identical": r_toks == toks["flat"],
        "ended_flat": eng.cache.topology is None,
        **{k: r_snap.get(k) for k in ("table.reshards",
                                      "engine.num_workers")},
    }
    return out


def topology_report(out: dict) -> None:
    """Print the two-level summary; fail loud on any acceptance miss."""
    f, i = out["flat"], out["islands"]
    print(f"  2×2 islands:     refreshed bytes "
          f"{f['device.refreshed_bytes']} → {i['device.refreshed_bytes']} "
          f"(-{out['cross_island_bytes_saving_pct']:.0f}%), fences "
          f"{i['fence.island.fences_intra']} intra / "
          f"{i['fence.island.fences_cross']} cross "
          f"({i['device.island.delta_bytes']}B deltas), tokens identical: "
          f"{out['tokens_identical']}")
    print(f"  live reshape:    flat→islands→flat, reshards "
          f"{out['reshape']['table.reshards']}, tokens identical: "
          f"{out['reshape']['tokens_identical']}")
    if not out["tokens_identical"]:
        raise AssertionError("island topology changed decoded tokens")
    if not out["reshape"]["tokens_identical"]:
        raise AssertionError("live reshape changed decoded tokens")
    if not (i["device.refreshed_bytes"] < f["device.refreshed_bytes"]):
        raise AssertionError(
            f"island replay refreshed {i['device.refreshed_bytes']}B — "
            f"not strictly below flat {f['device.refreshed_bytes']}B")
    fi = i["fence.island.fences_intra"]
    fx = i["fence.island.fences_cross"]
    if not fi or not fx:
        raise AssertionError(f"trace must exercise both fence levels "
                             f"(got {fi} intra, {fx} cross)")
    ci = out["modeled_intra_per_fence_s"]
    cx = out["modeled_cross_per_fence_s"]
    if not ci < cx:
        raise AssertionError(
            f"intra-island fences must be strictly cheaper per fence "
            f"than cross-island (got {ci} vs {cx})")


def run(smoke: bool = False) -> dict:
    out = case(smoke=smoke)
    save("engine_trace", out)
    report(out)
    return out


def run_topology(smoke: bool = False) -> dict:
    out = topology_case(smoke=smoke)
    save("BENCH_topology", out)
    topology_report(out)
    return out


def run_prefix(smoke: bool = False) -> dict:
    out = prefix_case(smoke=smoke)
    save("BENCH_prefix", out)
    prefix_report(out)
    return out


def run_chunked(smoke: bool = False) -> dict:
    out = chunked_case(smoke=smoke)
    save("BENCH_chunked", out)
    chunked_report(out)
    return out


def run_kernel(smoke: bool = False) -> dict:
    out = kernel_case(smoke=smoke)
    save("BENCH_kernel", out)
    kernel_report(out)
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    run(smoke=args.smoke)
    run_prefix(smoke=args.smoke)
    run_chunked(smoke=args.smoke)
    run_kernel(smoke=args.smoke)
    run_topology(smoke=args.smoke)
