"""Open-loop load harness → the ``BENCH_load.json`` perf trajectory.

    PYTHONPATH=src python -m benchmarks.loadgen [--smoke | --sustained]

Arrival-process generators — **open loop**: arrivals are scheduled by the
process, never gated on completions, so saturation shows up as queue
wait instead of being hidden by closed-loop self-throttling — drive the
*real* :class:`repro.serving.Engine` (chunked prefill + FCFS governor):

  * ``poisson``      — Poisson arrivals, 90/10 mice-and-elephants size mix;
  * ``diurnal``      — the same mix under a square-wave rate (quiet/burst
                       windows), the bursty-traffic shape that stresses
                       admission;
  * ``multi_tenant`` — three tenants (mice-heavy / elephant-heavy /
                       mixed) with per-tenant streams, so recycling
                       affinity and the per-stream worker routing see a
                       realistic interleaving.

Virtual time is the engine step.  Per workload the artifact records the
paper-relevant per-PR trajectory numbers: p50/p99 **queue-wait** (steps,
deterministic) and **step latency** (wall seconds, machine-dependent)
from the registry's pinned histograms, plus **fences/token** and
**refreshed bytes/token** — the shootdown-cost-per-useful-work ratios
every future optimisation (ragged kernel, extent coalescing, hierarchical
fences) must move.  Each workload is replayed with the same seed on a
fresh engine and the decoded tokens must be **bit-identical**
(``tokens_identical`` — checked by ``benchmarks/validate.py`` in CI);
latency numbers vary run-to-run, the tokens and counter trajectory may
not.

The ``poisson`` workload additionally runs under a
:class:`~repro.core.tracing.TraceCollector` and ships the Chrome-trace
JSON (``trace_load.json``, openable in Perfetto / ``chrome://tracing``)
with one closed root span per completed request — also CI-checked.
"""

from __future__ import annotations

import argparse
import os
import sys
import zlib

import numpy as np

from benchmarks.common import RESULTS, save

SEED = 20250809

#: engine shape shared by every workload (tiny attention model — the
#: harness measures the serving/coherence plane, not the matmuls)
_CFG_KW = dict(name="load", n_layers=1, d_model=32, n_heads=2,
               n_kv_heads=1, d_ff=64, vocab=64, head_dim=16)
_ENGINE_KW = dict(num_blocks=24, max_batch=4, max_seq_len=256,
                  num_workers=2, fpr_enabled=True, scoped_fences=True,
                  admission="fcfs", chunked_prefill=True, prefill_chunk=1)

#: hard step bound per workload run (a drain that exceeds it is a bug)
MAX_STEPS = 5000


# ------------------------------------------------------------------ arrivals
def _size_mix(rng, kind: str) -> tuple:
    """(prompt_len, max_new) for a mouse or an elephant."""
    if kind == "mouse":
        return int(rng.randint(8, 33)), int(rng.randint(4, 9))
    return int(rng.randint(160, 225)), int(rng.randint(8, 17))


def poisson_arrivals(seed: int, horizon: int, rate: float,
                     elephant_frac: float = 0.1) -> list:
    """Poisson(rate) arrivals per step with a mice-and-elephants mix."""
    rng = np.random.RandomState(seed)
    out = []
    for step in range(horizon):
        for _ in range(int(rng.poisson(rate))):
            kind = "elephant" if rng.rand() < elephant_frac else "mouse"
            plen, mnew = _size_mix(rng, kind)
            # distinct per-class contexts → cross-context recycling fences
            out.append({"step": step, "prompt_len": plen, "max_new": mnew,
                        "stream": f"{kind}s", "kind": kind,
                        "group": 1 if kind == "mouse" else 2})
    return out


def diurnal_arrivals(seed: int, horizon: int, base_rate: float,
                     burst_factor: float = 4.0, period: int = 20) -> list:
    """Square-wave diurnal rate: half of each period quiet, half burst."""
    rng = np.random.RandomState(seed)
    out = []
    for step in range(horizon):
        rate = base_rate * (burst_factor if (step % period) >= period // 2
                            else 1.0)
        for _ in range(int(rng.poisson(rate))):
            kind = "elephant" if rng.rand() < 0.1 else "mouse"
            plen, mnew = _size_mix(rng, kind)
            out.append({"step": step, "prompt_len": plen, "max_new": mnew,
                        "stream": "diurnal", "kind": kind,
                        "group": 1 if kind == "mouse" else 2})
    return out


def multi_tenant_arrivals(seed: int, horizon: int, scale: float = 1.0) -> list:
    """Three tenants with distinct rates and size profiles (tenant =
    request stream = quota key)."""
    tenants = (
        ("tenant_mice", 0.5 * scale, 0.0),       # all mice
        ("tenant_heavy", 0.12 * scale, 1.0),     # all elephants
        ("tenant_mixed", 0.25 * scale, 0.3),     # 30% elephants
    )
    rng = np.random.RandomState(seed)
    out = []
    for step in range(horizon):
        for gid, (name, rate, efrac) in enumerate(tenants, start=1):
            for _ in range(int(rng.poisson(rate))):
                kind = "elephant" if rng.rand() < efrac else "mouse"
                plen, mnew = _size_mix(rng, kind)
                out.append({"step": step, "prompt_len": plen,
                            "max_new": mnew, "stream": name, "group": gid,
                            "kind": kind})
    return out


def _workloads(smoke: bool) -> dict:
    """name → arrival list.  The sustained variant runs the same shapes
    ~4x longer at a higher rate (the nightly lane)."""
    h, r = (40, 0.7) if smoke else (160, 0.9)
    return {
        "poisson": poisson_arrivals(SEED, horizon=h, rate=r),
        "diurnal": diurnal_arrivals(SEED + 1, horizon=h,
                                    base_rate=r / 2.5),
        "multi_tenant": multi_tenant_arrivals(SEED + 2, horizon=h,
                                              scale=1.0 if smoke else 1.5),
    }


# -------------------------------------------------------------------- driver
def _make_engine():
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tfm
    from repro.models.config import ModelConfig
    from repro.serving.config import EngineConfig
    from repro.serving.engine import Engine

    cfg = ModelConfig(**_CFG_KW)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    return Engine(cfg, params, config=EngineConfig(**_ENGINE_KW))


def _drive(eng, arrivals: list, seed: int) -> dict:
    """Open-loop replay: submit every arrival at its step, run to drain.

    Returns the run's raw outcome (token digest + counts); prompts are
    derived from the workload seed so a replay regenerates them
    bit-identically.
    """
    from repro.serving.admission import CapacityError

    rng = np.random.RandomState(seed ^ 0x5EED)
    prompts = [rng.randint(1, _CFG_KW["vocab"],
                           size=a["prompt_len"]).astype(np.int32)
               for a in arrivals]
    now = 0
    i = 0
    never_fit = 0
    step_errors = 0
    while i < len(arrivals) or not eng.sched.idle:
        while i < len(arrivals) and arrivals[i]["step"] <= now:
            a = arrivals[i]
            try:
                eng.submit(prompts[i], a["max_new"], stream=a["stream"],
                           group_id=a["group"])
            except CapacityError:
                never_fit += 1          # window can never fit — open loop
            i += 1                      # drops it and moves on
        try:
            eng.step()
        except CapacityError:
            step_errors += 1
            if step_errors > 16:
                raise
        now += 1
        if eng.steps > MAX_STEPS or now > MAX_STEPS + len(arrivals):
            raise RuntimeError(
                f"loadgen did not drain within {MAX_STEPS} steps "
                f"({len(eng.sched.queue)} queued, "
                f"{len(eng.sched.running)} running)")
    digest = 0
    for r in sorted(eng.sched.done, key=lambda r: r.rid):
        blob = np.asarray([r.rid] + list(r.generated), np.int64).tobytes()
        digest = zlib.crc32(blob, digest)
    return {"digest": digest, "completed": len(eng.sched.done),
            "never_fit": never_fit, "step_errors": step_errors}


def _hist_stats(snap: dict, name: str) -> dict:
    return {"p50": snap[f"{name}.p50"], "p99": snap[f"{name}.p99"],
            "count": snap[f"{name}.count"]}


def _report(eng, outcome: dict, arrivals: list) -> dict:
    snap = eng.metrics.snapshot()
    tokens = max(1, snap["engine.tokens"])
    return {
        "arrivals": len(arrivals),
        "completed": outcome["completed"],
        "rejected_never_fit": outcome["never_fit"],
        "queue_wait_steps": _hist_stats(snap, "engine.obs.queue_wait_steps"),
        "step_latency_s": _hist_stats(snap, "engine.obs.step_latency_s"),
        "fences_per_token": round(snap["fence.fences"] / tokens, 6),
        "refreshed_bytes_per_token": round(
            snap["device.refreshed_bytes"] / tokens, 3),
        "snapshot": snap,
    }


def run(smoke: bool = False) -> dict:
    """Run every workload (plus a fixed-seed replay and the traced
    variant), write ``BENCH_load.json`` + ``trace_load.json``."""
    from repro.core.tracing import TraceCollector

    workloads = _workloads(smoke)
    mode = "smoke" if smoke else "sustained"
    payload: dict = {"seed": SEED, "mode": mode, "workloads": {}}
    identical = True
    trace_summary = None
    for name, arrivals in workloads.items():
        eng = _make_engine()
        collector = (TraceCollector(eng) if name == "poisson"
                     else None)
        outcome = _drive(eng, arrivals, SEED)
        report = _report(eng, outcome, arrivals)
        # fixed-seed replay on a fresh engine: tokens must be bit-identical
        replay = _drive(_make_engine(), arrivals, SEED)
        report["tokens_identical"] = (outcome["digest"] == replay["digest"]
                                      and outcome["completed"]
                                      == replay["completed"])
        identical &= report["tokens_identical"]
        payload["workloads"][name] = report
        qw = report["queue_wait_steps"]
        print(f"  {name}: {report['completed']}/{len(arrivals)} done, "
              f"queue-wait p50/p99 {qw['p50']}/{qw['p99']} steps, "
              f"fences/token {report['fences_per_token']}, "
              f"identical={report['tokens_identical']}")
        if collector is not None:
            collector.detach()
            os.makedirs(RESULTS, exist_ok=True)
            collector.save(os.path.join(RESULTS, "trace_load.json"))
            trace_summary = collector.summary()
            # list-of-pairs: category names must not masquerade as
            # namespaced snapshot keys to benchmarks.validate
            trace_summary["by_cat"] = sorted(trace_summary["by_cat"].items())
            trace_summary["file"] = "trace_load.json"
            trace_summary["root_spans_match_completed"] = (
                trace_summary["root_spans"] == report["completed"])
    payload["tokens_identical"] = identical
    payload["trace"] = trace_summary
    path = save("BENCH_load", payload)
    print(f"  wrote {path}")
    return payload


def main() -> int:
    ap = argparse.ArgumentParser()
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--smoke", action="store_true",
                      help="CI push-lane variant (short horizon)")
    mode.add_argument("--sustained", action="store_true",
                      help="nightly sustained-load variant (default)")
    args = ap.parse_args()
    run(smoke=args.smoke)
    return 0


if __name__ == "__main__":
    sys.exit(main())
