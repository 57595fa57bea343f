"""Validate benchmark smoke artifacts against the MetricsRegistry schema.

    PYTHONPATH=src python -m benchmarks.validate [files...]

Every *namespaced* key in the JSON artifacts (a dotted key whose first
segment is one of the registry namespaces — ``fpr.`` / ``fence.`` /
``table.`` / ``device.`` / ``admission.`` / ``engine.``) must be known to
:mod:`repro.core.metrics` — either a :data:`~repro.core.metrics.
STABLE_SCHEMA` / :data:`~repro.core.metrics.ADMISSION_SCHEMA` key or a
declared wildcard group.  Artifact-local fields (``seed``,
``tokens_identical``, sim rows, …) are ignored.

Beyond schema membership, required *sections* are enforced per artifact:
``microbench_scoped.json`` must carry the engine-trace **elastic** replay
(reshards applied, tokens bit-identical, reshard refresh below one
full-table re-upload) — losing the section would silently retire the
elastic acceptance criterion — ``BENCH_prefix.json`` (the
shared-prefix perf trajectory) must keep tokens identical, the ≥40%
unique-block saving, zero in-set fence violations and the concurrency
win — and ``BENCH_chunked.json`` (chunked prefill) must keep tokens
bit-identical to monolithic, the chunk path compiled exactly once
across prompt lengths, and the mice-and-elephants ``queue_wait_p99``
strictly better chunked than monolithic — ``BENCH_kernel.json`` (ragged
fused-KV serving) must keep tokens bit-identical to the chunked oracle,
exactly one ragged kernel call per attention layer per step under mixed
prefill+decode batches, one compile per token capacity, and the
autotuned fused pipeline at or below the naive split walk — and
``BENCH_load.json`` (the open-loop load harness) must carry every
workload with a present queue-wait/step-latency p99, finite
fences/token and refreshed bytes/token, tokens bit-identical to the
fixed-seed replay, and a trace summary with at least one root span and
zero left-open spans.  The
``BENCH_topology.json`` (hierarchical islands) must keep the
multi-island replay token-identical to flat scoped fencing, the strict
cross-island device-bytes win, both fence levels exercised, and
intra-island fences strictly cheaper per fence than cross-island.  The
schema itself must know the ``fpr.eviction.``,
``fpr.prefix.`` and topology (``table.reshards`` / ``device.reshard_*``)
counter groups, the two-level island groups (``fence.island.*`` /
``table.island.*`` / ``device.island.*``), plus the pinned
observability histograms and the subscriber-error counter, so retiring
them fails here too.

This runs in the CI push lane right after ``benchmarks.run --smoke``:
counter drift (a renamed, retired or misspelled key) fails the push
instead of surfacing as a silent nightly artifact diff.
"""

from __future__ import annotations

import json
import os
import sys

from repro.core.metrics import schema_violations

#: the deterministic smoke artifacts the push lane publishes
DEFAULT_ARTIFACTS = ("microbench_scoped.json", "admission_smoke.json",
                     "BENCH_prefix.json", "BENCH_chunked.json",
                     "BENCH_kernel.json", "BENCH_load.json",
                     "BENCH_topology.json")

#: workloads the load harness must always exercise
LOAD_WORKLOADS = ("poisson", "diurnal", "multi_tenant")

#: counter groups that must stay in the flat schema (satellite coverage:
#: eviction-pass counters + elastic-topology counters + prefix sharing)
REQUIRED_SCHEMA_KEYS = (
    "fpr.eviction.wakeups",
    "fpr.eviction.pages_scanned",
    "fpr.eviction.pages_dropped",
    "fpr.eviction.swap_outs",
    "fpr.prefix.hit_rate",
    "fpr.prefix.hit_blocks",
    "fpr.prefix.cow_copies",
    "fpr.prefix.sharing_exits",
    "fpr.prefix.exit_fenced",
    "fpr.prefix.exit_elided",
    "fpr.prefix.in_set_violations",
    "table.num_shards",
    "table.reshards",
    "device.reshards",
    "device.reshard_moved_entries",
    "device.reshard_refreshed_bytes",
    "engine.num_workers",
    "engine.prefill_chunks",
    "engine.prefill_chunk_traces",
    "engine.prefill_traces",
    "admission.chunk_grows",
    # observability loop: pinned latency histograms + isolation counter
    "engine.obs.subscriber_errors",
    "engine.obs.step_latency_s",
    "engine.obs.queue_wait_steps",
    "admission.obs.queue_depth",
    "fence.obs.scope_workers",
    "device.obs.refresh_bytes",
    # ragged fused-KV kernel serving counters (KERNEL_SCHEMA)
    "engine.kernel.dma_bytes",
    "engine.kernel.kernel_calls",
    "engine.kernel.pipeline_depth",
    "engine.kernel.ragged_steps",
    # hierarchical island topology: two-level fence + replica-group +
    # delta-propagation counters (ISLAND_SCHEMA)
    "fence.island.num_islands",
    "fence.island.fences_intra",
    "fence.island.fences_cross",
    "fence.island.deltas_propagated",
    "fence.island.modeled_intra_s",
    "fence.island.modeled_cross_s",
    "table.island.shard_bumps_intra",
    "table.island.shard_bumps_remote",
    "device.island.delta_entries",
    "device.island.delta_bytes",
    "admission.ledger.per_island_committed",
)

RESULTS = os.path.join(os.path.dirname(__file__), "results")


def _walk_keys(node, found: set) -> None:
    """Collect every dict key at every depth (artifacts nest snapshots)."""
    if isinstance(node, dict):
        for key, value in node.items():
            if isinstance(key, str):
                found.add(key)
            _walk_keys(value, found)
    elif isinstance(node, list):
        for item in node:
            _walk_keys(item, found)


def validate_file(path: str) -> list[str]:
    """Schema violations in one artifact (empty list = clean)."""
    with open(path) as f:
        payload = json.load(f)
    keys: set = set()
    _walk_keys(payload, keys)
    return schema_violations(keys)


def elastic_violations(path: str) -> list[str]:
    """Required-section check: the engine-trace elastic replay.

    Applies to ``microbench_scoped.json`` (which embeds the engine trace);
    returns human-readable problems, empty when the section is sound.
    """
    with open(path) as f:
        payload = json.load(f)
    trace = payload.get("engine_trace", payload)
    elastic = trace.get("elastic")
    if elastic is None:
        return ["missing engine_trace elastic section"]
    bad = []
    if not elastic.get("tokens_identical"):
        bad.append("elastic replay tokens diverged from fixed topology")
    if not elastic.get("device.reshards"):
        bad.append("elastic replay applied no reshards")
    refreshed = elastic.get("device.reshard_refreshed_bytes")
    full = elastic.get("full_table_bytes")
    if refreshed is None or full is None or not refreshed < full:
        bad.append(f"reshard refresh {refreshed}B not below one "
                   f"full-table re-upload ({full}B)")
    return bad


def prefix_violations(path: str) -> list[str]:
    """Required-section check: the shared-prefix perf trajectory.

    Applies to ``BENCH_prefix.json``; a regression in any acceptance
    number (token divergence, unique-block saving below 40%, a fence
    inside a sharing set, no concurrency win) fails the push lane.
    """
    with open(path) as f:
        payload = json.load(f)
    shared = payload.get("shared")
    unshared = payload.get("unshared")
    if shared is None or unshared is None:
        return ["missing shared/unshared prefix sections"]
    bad = []
    if not payload.get("tokens_identical"):
        bad.append("shared-prefix tokens diverged from the unshared run")
    saving = payload.get("unique_blocks_saving_pct")
    if saving is None or saving < 40.0:
        bad.append(f"unique-block saving {saving}% below the 40% floor")
    if shared.get("fpr.prefix.in_set_violations"):
        bad.append("fpr.prefix.in_set_violations != 0 "
                   "(fence inside a sharing set)")
    if not (shared.get("peak_running") or 0) > (unshared.get("peak_running")
                                                or 0):
        bad.append("unique-block admission shows no concurrency win")
    return bad


def chunked_violations(path: str) -> list[str]:
    """Required-section check: the chunked-prefill trajectory.

    Applies to ``BENCH_chunked.json``; fails the push lane when chunking
    stops being bit-identical, the fixed-shape chunk path starts
    retracing, or the mice-and-elephants sim loses the strict
    ``queue_wait_p99`` (mice) win over monolithic admission.
    """
    with open(path) as f:
        payload = json.load(f)
    chunked = payload.get("chunked")
    mono = payload.get("monolithic")
    if chunked is None or mono is None:
        return ["missing chunked/monolithic sections"]
    bad = []
    if not payload.get("tokens_identical"):
        bad.append("chunked tokens diverged from the monolithic run")
    if chunked.get("engine.prefill_chunk_traces") != 1:
        bad.append(f"chunk path traced "
                   f"{chunked.get('engine.prefill_chunk_traces')} times "
                   f"(fixed chunk shape must compile exactly once)")
    if chunked.get("engine.prefill_traces"):
        bad.append("chunked run fell back to the monolithic prefill path")
    sim = payload.get("sim") or {}
    sc = sim.get("chunked") or {}
    sm = sim.get("monolithic") or {}
    p99c = sc.get("queue_wait_p99_mice")
    p99m = sm.get("queue_wait_p99_mice")
    if p99c is None or p99m is None or not p99c < p99m:
        bad.append(f"mice queue-wait p99 chunked {p99c} not strictly "
                   f"below monolithic {p99m}")
    return bad


def kernel_violations(path: str) -> list[str]:
    """Required-section check: the ragged fused-KV kernel trajectory.

    Applies to ``BENCH_kernel.json``; fails the push lane when the
    ragged mixed prefill+decode batch stops being served by exactly one
    kernel call per attention layer per step, decoded tokens stop being
    bit-identical to the per-slot chunked oracle, the fixed-shape ragged
    step starts retracing, or the autotuned fused pipeline loses to the
    naive (split-KV, unpipelined) walk under the kernel cost model.
    """
    with open(path) as f:
        payload = json.load(f)
    rk = payload.get("ragged_kernel")
    if rk is None or payload.get("chunked_ref") is None:
        return ["missing ragged_kernel/chunked_ref sections"]
    bad = []
    if not payload.get("tokens_identical"):
        bad.append("ragged tokens diverged from the chunked oracle")
    n_layers = payload.get("n_layers") or 0
    for mode in ("ragged_ref", "ragged_kernel"):
        m = payload.get(mode) or {}
        calls = m.get("engine.kernel.kernel_calls")
        steps = m.get("engine.kernel.ragged_steps")
        if calls is None or steps is None or calls != n_layers * steps:
            bad.append(f"{mode}: {calls} kernel calls over {steps} steps "
                       f"— a mixed batch must cost one call per layer "
                       f"per step ({n_layers} layer(s))")
        if m.get("engine.prefill_chunk_traces") != m.get("programs"):
            bad.append(f"{mode}: ragged step traced "
                       f"{m.get('engine.prefill_chunk_traces')} times "
                       f"(one program per token capacity: "
                       f"{m.get('programs')})")
    md = payload.get("modeled") or {}
    tuned, naive = md.get("tuned_fused_s"), md.get("naive_split_s")
    if tuned is None or naive is None or tuned > naive:
        bad.append(f"tuned fused pipeline {tuned}s not at or below the "
                   f"naive split walk {naive}s (modeled)")
    if payload.get("token_crc") is None:
        bad.append("missing fixed-seed token_crc fingerprint")
    return bad


def load_violations(path: str) -> list[str]:
    """Required-section check: the open-loop load harness trajectory.

    Applies to ``BENCH_load.json``; fails the push lane when a workload
    disappears, a percentile goes absent (empty histogram), the
    per-token coherence ratios stop being finite numbers, the fixed-seed
    replay stops being bit-identical, or the Chrome trace leaks spans
    (root spans missing / spans left open at drain).
    """
    import math

    with open(path) as f:
        payload = json.load(f)
    workloads = payload.get("workloads") or {}
    bad = []
    for name in LOAD_WORKLOADS:
        wl = workloads.get(name)
        if wl is None:
            bad.append(f"missing workload section {name!r}")
            continue
        for hist in ("queue_wait_steps", "step_latency_s"):
            p99 = (wl.get(hist) or {}).get("p99")
            if not isinstance(p99, (int, float)) or not math.isfinite(p99):
                bad.append(f"{name}: {hist} p99 absent "
                           f"(empty histogram?) — got {p99!r}")
        for ratio in ("fences_per_token", "refreshed_bytes_per_token"):
            val = wl.get(ratio)
            if not isinstance(val, (int, float)) or not math.isfinite(val):
                bad.append(f"{name}: {ratio} not finite — got {val!r}")
        if not wl.get("tokens_identical"):
            bad.append(f"{name}: tokens diverged from fixed-seed replay")
    if not payload.get("tokens_identical"):
        bad.append("tokens_identical is not true across workloads")
    trace = payload.get("trace")
    if not trace:
        bad.append("missing trace summary section")
    else:
        if not trace.get("root_spans"):
            bad.append("trace has no root spans")
        if trace.get("open_spans") != 0:
            bad.append(f"trace left {trace.get('open_spans')} spans open")
        if not trace.get("root_spans_match_completed"):
            bad.append("trace root spans != completed requests")
    return bad


def topology_violations(path: str) -> list[str]:
    """Required-section check: the hierarchical-island replay.

    Applies to ``BENCH_topology.json``; fails the push lane when the
    multi-island replay stops being token-identical to flat scoped
    fencing, loses the strict cross-island device-bytes win (remote
    replicas must receive deltas, not full re-uploads), stops exercising
    both fence levels, or intra-island fences stop being strictly
    cheaper per fence than cross-island ones in modeled cost.
    """
    with open(path) as f:
        payload = json.load(f)
    flat = payload.get("flat")
    isl = payload.get("islands")
    if flat is None or isl is None:
        return ["missing flat/islands sections"]
    bad = []
    if not payload.get("tokens_identical"):
        bad.append("island replay tokens diverged from the flat run")
    fb = flat.get("device.refreshed_bytes")
    ib = isl.get("device.refreshed_bytes")
    if fb is None or ib is None or not ib < fb:
        bad.append(f"island refreshed bytes {ib} not strictly below "
                   f"flat {fb}")
    fi = isl.get("fence.island.fences_intra") or 0
    fx = isl.get("fence.island.fences_cross") or 0
    if not fi or not fx:
        bad.append(f"replay must exercise both fence levels "
                   f"(got {fi} intra, {fx} cross)")
    ci = payload.get("modeled_intra_per_fence_s")
    cx = payload.get("modeled_cross_per_fence_s")
    if ci is None or cx is None or not ci < cx:
        bad.append(f"intra-island per-fence modeled cost {ci} not "
                   f"strictly below cross-island {cx}")
    reshape = payload.get("reshape")
    if not reshape:
        bad.append("missing live-reshape section")
    elif not reshape.get("tokens_identical"):
        bad.append("live reshape (flat→islands→flat) changed tokens")
    return bad


def main(argv: list[str]) -> int:
    paths = argv or [os.path.join(RESULTS, name)
                     for name in DEFAULT_ARTIFACTS]
    failed = False
    missing = schema_violations(REQUIRED_SCHEMA_KEYS)
    if missing:
        failed = True
        print("SCHEMA REGRESSION — required counter groups left the "
              "MetricsRegistry schema:")
        for key in missing:
            print(f"  {key}")
    for path in paths:
        if not os.path.exists(path):
            print(f"MISSING artifact: {path}")
            failed = True
            continue
        bad = validate_file(path)
        name = os.path.basename(path)
        if name == "microbench_scoped.json":
            bad = bad + [f"elastic: {b}" for b in elastic_violations(path)]
        if name == "BENCH_prefix.json":
            bad = bad + [f"prefix: {b}" for b in prefix_violations(path)]
        if name == "BENCH_chunked.json":
            bad = bad + [f"chunked: {b}" for b in chunked_violations(path)]
        if name == "BENCH_kernel.json":
            bad = bad + [f"kernel: {b}" for b in kernel_violations(path)]
        if name == "BENCH_load.json":
            bad = bad + [f"load: {b}" for b in load_violations(path)]
        if name == "BENCH_topology.json":
            bad = bad + [f"topology: {b}"
                         for b in topology_violations(path)]
        if bad:
            failed = True
            print(f"SCHEMA DRIFT in {name} — keys not in "
                  f"the MetricsRegistry schema / required sections:")
            for key in bad:
                print(f"  {key}")
        else:
            print(f"ok: {name}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
