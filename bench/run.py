"""Run one benchmark cell once on the served path and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with the chips the cell
asks for.  Everything is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``bench/configs/<config>.json`` (with its plain
reference in ``bench/references/``), its traffic in
``bench/traffic/<mix>.json`` (generated by
``bench/traffic/kinds/<kind>.py``), and each per-layer metric in
``bench/metrics/<metric>.py``.

The run makes bf16 weights from the seed on the device, builds the
serving engine (``Engine.submit`` / ``Engine.step``), serves warm-up
traffic from a separate seed stream until every program has run, then
serves the window's traffic for ``--seconds`` from an open- or
closed-loop client (``drive.py``).  With ``--trace 1`` it records a
profiler trace of part of the window and prints the per-layer metrics;
with ``--trace 0`` it prints the end-to-end metrics.  Either way it then
frees the engine and checks a seeded sample of the served tokens against
the plain reference (``check.py``).  The last line of standard output is
one JSON object; the numbers compared and their limits are the last
lines of standard error.  With no TPU, or fewer chips than the cell
asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

_T_IMPORT = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import check  # noqa: E402
import counts  # noqa: E402
import drive  # noqa: E402
import host  # noqa: E402
import trace_reduce  # noqa: E402

#: the traced part of a ``--trace 1`` window: from this share of the
#: window on, for this many seconds
TRACE_FROM_S = 0.25
TRACE_SECONDS = 12.0
#: where a run keeps its trace while it reads it (removed afterwards)
TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")


def process_start() -> float:
    """Wall time at which this process started (Linux), else the import
    of this module."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(l.split()[1]) for l in f if l.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return _T_IMPORT


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def load_cell(workload: str, smoke: bool = False) -> dict:
    """The cell's entry, configuration, traffic and metrics, by name."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"unknown workload {workload!r}")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if smoke:
        cfg = _merge(cfg, cfg["smoke"])
        traffic = _merge(traffic, traffic["smoke"])

    def reports(m):
        return workload in m.get("workloads", [workload])

    return {"bench": bench, "cell": cell, "cfg": cfg, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
            "per_layer": [m for m in bench["per_layer"] if reports(m)]}


class CompileClock:
    """Count and seconds of JAX's compile events (traces, lowerings,
    backend compiles and cache reads)."""

    def __init__(self):
        import jax
        self.total = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.total += duration
            self.events += 1


def configure_cache() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set (JAX reads it itself), else
    ``.jax_cache/`` at the checkout's root; every program is cached."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


#: configuration-file key → where the program's ModelConfig holds it
#: (``sub.field`` for its attention, MLA and expert configs); the one
#: table both checks a file against the program and builds the smoke
#: config from it
_MODEL_KEYS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
               "num_key_value_heads": "n_kv_heads",
               "intermediate_size": "d_ff", "vocab_size": "vocab",
               "head_dim": "head_dim", "num_hidden_layers": "n_layers",
               "rms_norm_eps": "norm_eps",
               "tie_word_embeddings": "tie_embeddings",
               "rope_theta": "attn.rope_theta",
               "kv_lora_rank": "mla.kv_lora_rank",
               "q_lora_rank": "mla.q_lora_rank",
               "qk_rope_head_dim": "mla.rope_head_dim",
               "qk_nope_head_dim": "mla.nope_head_dim",
               "v_head_dim": "mla.v_head_dim",
               "n_routed_experts": "moe.num_experts",
               "num_experts_per_tok": "moe.top_k",
               "n_shared_experts": "moe.num_shared",
               "moe_intermediate_size": "moe.d_expert"}


def _fields(cfg: dict) -> dict:
    """The file's keys that the program holds, by where it holds them:
    ``intermediate_size`` is the dense layers' ``dense_d_ff`` where the
    model has expert layers, and a null ``q_lora_rank`` (no query
    compression) is None."""
    moe = any(f == "moe" for _, f in counts.layer_kinds(cfg))
    out = {}
    for k, f in _MODEL_KEYS.items():
        if k in cfg:
            if k == "intermediate_size" and moe:
                f = "dense_d_ff"
            out[f] = (cfg[k] or None) if k == "q_lora_rank" else cfg[k]
    return out


def _get(mc, path: str):
    obj = mc
    for part in path.split("."):
        obj = getattr(obj, part, None) if obj is not None else None
    return (obj or None) if path == "mla.q_lora_rank" else obj


def program_config(cfg: dict, smoke: bool):
    """The program's ModelConfig for this configuration file; a file that
    disagrees with the program's own config is an error that names the
    file's key."""
    from dataclasses import replace

    from repro.configs import get_config
    from repro.models.config import MLAConfig, MoEConfig

    mc = get_config(cfg["arch"], n_layers=cfg["num_hidden_layers"])
    kinds = counts.layer_kinds(cfg)
    mixers, ffns = tuple(m for m, _ in kinds), tuple(f for _, f in kinds)
    fields = _fields(cfg)
    if smoke:
        top = {f: v for f, v in fields.items() if "." not in f}
        subs = {"attn": mc.attn, "mla": mc.mla or MLAConfig(),
                "moe": mc.moe or MoEConfig(num_experts=0, top_k=0)}
        for f, v in fields.items():
            if "." in f:
                sub, name = f.split(".")
                subs[sub] = replace(subs[sub], **{name: v})
        top["attn"] = subs["attn"]
        top["mla"] = subs["mla"] if "mla" in mixers else None
        top["moe"] = subs["moe"] if "moe" in ffns else None
        return replace(mc, **top, mixers=mixers, ffns=ffns)
    keys = {f: k for k, f in _MODEL_KEYS.items()}
    keys["dense_d_ff"] = "intermediate_size"
    wrong = {keys[f]: (v, _get(mc, f)) for f, v in fields.items()
             if v != _get(mc, f)}
    if mixers != mc.mixers:
        wrong["kv_lora_rank (mixers)"] = (mixers, mc.mixers)
    if ffns != mc.ffns:
        wrong["n_routed_experts, first_k_dense_replace, moe_layer_freq "
              "(ffns)"] = (ffns, mc.ffns)
    if wrong:
        raise SystemExit(f"configuration file disagrees with the program's "
                         f"{cfg['arch']}: {wrong}")
    return mc


def _pct(values, q):
    return float(np.percentile(values, q)) if values else None


def end_to_end(rec: dict, setup_s: float) -> tuple[dict, dict]:
    """The client-side numbers of the window, and the sample of each."""
    reqs = rec["requests"]
    drain = drive.DRAIN_LIMIT_S
    ttft = [(r["stamps"][0] - r["due"]) * 1e3 if r["stamps"] else drain * 1e3
            for r in reqs]
    itl = [(b - a) * 1e3 for r in reqs
           for a, b in zip(r["stamps"], r["stamps"][1:])]
    return {
        "ttft_p50_ms": {"value": _pct(ttft, 50), "unit": "ms"},
        "ttft_p75_ms": {"value": _pct(ttft, 75), "unit": "ms"},
        "itl_p50_ms": {"value": _pct(itl, 50), "unit": "ms"},
        "itl_p95_ms": {"value": _pct(itl, 95), "unit": "ms"},
        "output_tok_s": {"value": rec["tokens_in_window"] / rec["window_s"],
                         "unit": "tokens/s"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }, {"ttft": ttft, "itl": itl}


def record(cell: dict, raw: dict, trace: dict | None) -> dict:
    """The run's record, which every per-layer metric reads."""
    cfg = cell["cfg"]
    t0, end = raw["t0"], raw["end"]
    reqs = [{"due": t.due, "seated": t.seated, "stamps": t.stamps}
            for t in raw["tracked"]]
    tokens = sum(1 for t in raw["tracked"] for s in t.stamps if t0 <= s <= end)
    return {"dims": counts.dims(cfg), "ragged": cfg["engine"]["ragged_kernel"],
            "window_s": raw["seconds"], "requests": reqs,
            "steps": raw["steps"], "steps_in_window": raw["steps_in_window"],
            "tokens_in_window": tokens, "counters": raw["counters"],
            "trace": trace, "notes": {}}


def run(args, *, allow_cpu: bool = False, smoke: bool = False,
        patch_engine=None, control: bool = False) -> tuple[int, dict | None]:
    """One run of one cell; returns (exit code, result)."""
    import jax

    cell = load_cell(args.workload, smoke)
    chips = cell["cell"]["chips"]
    devices = jax.devices()
    dev = devices[0]
    if not allow_cpu and (dev.platform != "tpu" or len(devices) < chips):
        print(f"bench: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 2, None
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench: the system under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2, None
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax.numpy as jnp

    from repro.serving.config import EngineConfig
    from repro.serving.engine import Engine

    cfg, tp = cell["cfg"], cell["traffic"]
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if not allow_cpu and dev.device_kind not in peaks:
        raise SystemExit(f"no peaks for device kind {dev.device_kind!r} in "
                         f"bench/peaks.json")
    peak = peaks.get(dev.device_kind, next(iter(peaks.values())))
    cache_dir = configure_cache()
    clock = CompileClock()
    print(f"device: {dev.device_kind} x{len(devices)} ({dev.platform}); "
          f"compile cache {cache_dir}")

    mc = program_config(cfg, smoke)
    ref = load_module(os.path.join(HERE, "references",
                                   cfg["reference"] + ".py"), "reference")
    kind = load_module(os.path.join(HERE, "traffic", "kinds",
                                    tp["kind"] + ".py"), "traffic_kind")
    params = ref.make_params(args.seed, cfg)
    jax.block_until_ready(params)
    eng = Engine(mc, params, config=EngineConfig(
        dtype=jnp.dtype(cfg["dtype"]).type, **cfg["engine"]))
    if patch_engine is not None:
        patch_engine(eng)
    below = cfg["token_ids_below"]
    probe0 = host.probe_ms()
    for r in kind.warmup(tp, args.seed, below):
        eng.submit(np.asarray(r["prompt"], np.int32),
                   max_new_tokens=r["max_new_tokens"], stream=r["stream"],
                   group_id=r["group_id"])
    eng.run()
    window = kind.window(tp, args.seed, args.seconds, below)
    tracer = drive.Tracer(TRACE_DIR if args.trace else None,
                          TRACE_FROM_S * args.seconds,
                          TRACE_FROM_S * args.seconds + min(
                              TRACE_SECONDS, 0.5 * args.seconds))
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    compiles0 = clock.events
    window_start = time.time()
    setup_s = window_start - process_start()
    print(f"setup: {setup_s} s, compile {clock.total} s")
    with host.GcLog() as gc_log:
        raw = drive.run_window(eng, kind, window, args.seconds,
                               ragged=cfg["engine"]["ragged_kernel"],
                               tracer=tracer)
    compiles = clock.events - compiles0
    probe1 = host.probe_ms()
    stats = dev.memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")
    trace = None
    if args.trace and tracer.state == "done":
        table = trace_reduce.extract(TRACE_DIR)
        print(f"trace lines: {table['lines']}")
        if args.keep_trace:
            os.makedirs(os.path.dirname(args.keep_trace) or ".",
                        exist_ok=True)
            with open(args.keep_trace, "w") as f:
                json.dump(table, f)
        trace = trace_reduce.reduce(table)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    rec = record(cell, raw, trace)
    rec["peak"] = peak
    host_ms = load_module(os.path.join(HERE, "metrics",
                                       "engine.host_ms_p50.py"),
                          "host_ms").compute(dict(rec, notes={}))
    host_line = (f"host: probe ms before warm-up {probe0}, after "
                 f"the window {probe1}; collections in the window "
                 f"{gc_log.summary(raw['t0'], raw['end'])}; "
                 f"engine.iteration - engine.fetch ms p50 {host_ms}")
    finished = [{"prompt": np.asarray(t.req.prompt),
                 "generated": list(t.req.generated)}
                for t in raw["tracked"] if t.done]
    attempted = len(raw["tracked"])
    failed = attempted - len(finished)
    late = [x * 1e3 for x in raw["late_s"]]
    del eng, raw
    gc.collect()

    e2e, samples = end_to_end(rec, setup_s)
    print(f"requests due in window {attempted}, finished {len(finished)}, "
          f"failed {failed}; steps in window {rec['steps_in_window']}, "
          f"tokens in window {rec['tokens_in_window']}")
    for k, v in samples.items():
        beyond = [sum(x > _pct(v, q) for x in v) for q in (50, 75, 95)]
        print(f"samples: {k} {len(v)}, beyond p50/p75/p95 "
              + "/".join(map(str, beyond)))
    print("end to end, listed or not: "
          + ", ".join(f"{k} {v['value']}" for k, v in e2e.items()))
    print(f"submission lateness ms: p50 {_pct(late, 50)}, "
          f"p95 {_pct(late, 95)}, max {max(late, default=None)}")
    print(f"compile events inside the window: {compiles}")
    print(host_line)
    print(f"counters over the window: {rec['counters']}")
    if args.trace:
        metrics = {}
        for m in cell["per_layer"]:
            mod = load_module(os.path.join(HERE, "metrics",
                                           m["name"] + ".py"), "metric")
            v = mod.compute(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        for k, note in rec["notes"].items():
            print(f"{k}: {note}")
    else:
        names = [m["name"] for m in cell["end_to_end"]]
        metrics = {k: v for k, v in e2e.items() if k in names}
    for k, v in metrics.items():
        print(f"metric {k} = {v['value']} {v['unit']}")

    picked = check.sample(finished, args.seed, cfg["check"]["sample_tokens"],
                          cfg["check"]["sample_max_requests"])
    t_ref = time.time()
    got = check.gaps(ref, params, cfg, picked, control=control)
    print(f"reference: {got['requests']} requests, {got['tokens']} served "
          f"tokens, {time.time() - t_ref} s")
    limit = cfg["check"]["max_gap"]
    checks = {"served_gap_max": {"value": got["served_gap_max"],
                                 "limit": limit},
              "failed_requests": {"value": failed, "limit": 0},
              "sampled_tokens": {"value": got["tokens"], "limit": 1}}
    if control:
        checks["program_gap_max"] = {"value": got["program_gap_max"],
                                     "limit": limit}
    correct = (got["served_gap_max"] <= limit and failed == 0
               and got["tokens"] >= 1)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": mem_peak}}
    if trace:
        result["device"]["busy_s"] = trace["busy_s"]
        result["device"]["window_s"] = trace["window_s"]
        result["breakdown"] = trace["breakdown"]
    result["checks"] = checks
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    return 0, result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the trace's event table to this file")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    code, result = run(parse(argv))
    if result is not None:
        print(json.dumps(result))
    return code


if __name__ == "__main__":
    sys.exit(main())
