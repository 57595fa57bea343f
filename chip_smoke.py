"""Serve granite-3-8b at full width on one TPU through the Pallas
paged-attention kernels, and check what comes out.

    python chip_smoke.py [--seed 0]

Run from the repository root on a machine with a TPU.  The model is
granite-3-8b at its published widths (d_model 4096, 32 query / 8 KV
heads, head_dim 128, d_ff 12800, vocab 49408) cut to 16 of its 40 layers
so its bf16 weights fit one 16 GB chip; weights are random, made from
``--seed``.  The script drives the real serving engine
(``Engine.submit`` / ``Engine.run``) with ``page_impl="pallas"``:

* **Phase A** — the per-slot path: monolithic prefill, then decode
  through the fused paged-attention kernel at its tuned buffer depth.
  Run twice, with fast page recycling (FPR) on and off; the generated
  tokens must be identical.  Prefix sharing stays off here: monolithic
  prefill compiles one program per padded prompt length, and on TPU
  those programs compute a shared prefix's KV in different low bits,
  so a sharer would read other bits than it computes alone.
* **Phase B** — the ragged path (``chunked_prefill=True,
  ragged_kernel=True``): one ragged kernel call per attention layer per
  step, each step in the smallest of the engine's token capacities that
  holds its rows, one program per capacity compiled with the engine.
  Run with FPR and prefix sharing on, and with FPR off; the tokens must
  be identical: a shared prefix computed in one capacity's program is
  read by sharers whose own rows run in another, so the programs must
  give a prefix the same bits whichever computed it.  The steps each
  capacity served are printed.
* **Reference** — on identical state, one decode step and one ragged
  step through the Pallas kernels must give the logits of the jnp
  reference (``page_impl="ref"``) within :data:`LOGIT_REL_TOL`, and the
  engine's compiled steps must contain the kernel (``tpu_custom_call``).  On
  the same cache, one layer's attention through each kernel must match
  the reference at full f32 precision within :data:`ATTN_REL_TOL`, a
  bound the reference at one-bf16-pass precision must miss.

It exits nonzero when JAX finds no TPU.  The compile cache goes to
``$JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise to
``.jax_cache/`` beside this file.  The last line of standard output is
one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "granite-3-8b"
N_LAYERS = 16
MAX_BATCH = 8
MAX_SEQ_LEN = 4096
NUM_BLOCKS = 256
N_REQUESTS = 12
#: padded prompt lengths; monolithic prefill traces once per length
PROMPT_LENGTHS = (512, 1024, 2048)
NEW_TOKENS = 32
#: query tokens of the ragged reference step — small enough that the jnp
#: oracle's full-window gather fits beside the weights
RAGGED_CHECK_TOKENS = 64
#: Pallas-vs-reference bound on ||pallas - ref||_2 / ||ref||_2 of the
#: logits through all 16 layers: 8 bf16 ulps (2**-8 each).  Both steps
#: are compiled with every XLA matmul at precision HIGHEST, so they
#: differ only in each layer's attention (kernel vs jnp, in other
#: summation orders); a difference can flip the bf16 rounding of an
#: attention output, and 16 random-weight layers amplify the flips.
#: With the reference at XLA's default TPU precision (one bf16 pass) a
#: TPU v5e measured 0.0256 (decode) and 0.0258 (ragged) at seed 0.
#: The kernel's own precision is held by the per-layer bound below.
LOGIT_REL_TOL = 2.0 ** -5
#: bound on one layer's attention output, f32 queries over the engine's
#: bf16 cache: the kernel vs the jnp reference at precision HIGHEST.  A
#: control, the reference at default precision (one bf16 pass, what a
#: kernel doing its matmuls in bf16 computes), must miss it.
ATTN_REL_TOL = 2.0 ** -12


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or reading
    the persistent cache), summed from its monitoring events."""

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.total += duration


def make_requests(vocab: int, block_size: int, seed: int) -> list:
    """``N_REQUESTS`` prompts: one shared full-block system prompt plus a
    per-user tail, each padding to one of :data:`PROMPT_LENGTHS`."""
    rng = np.random.RandomState(seed)
    system = rng.randint(1, vocab, size=block_size)
    prompts = []
    for i in range(N_REQUESTS):
        padded = PROMPT_LENGTHS[i % len(PROMPT_LENGTHS)]
        total = rng.randint(padded - block_size + 1, padded + 1)
        tail = rng.randint(1, vocab, size=total - block_size)
        prompts.append(np.concatenate([system, tail]).astype(np.int32))
    return prompts


def serve(cfg, params, prompts, clock: CompileClock, **engine_kw):
    """Submit every prompt to a fresh engine, run it dry, and return
    (engine, tokens per request in submit order, phase stats)."""
    from repro.serving.config import EngineConfig
    from repro.serving.engine import Engine

    eng = Engine(cfg, params, config=EngineConfig(**engine_kw))
    c0 = clock.total
    t0 = time.perf_counter()
    for p in prompts:
        eng.submit(p, max_new_tokens=NEW_TOKENS)
    eng.run()
    wall = time.perf_counter() - t0
    done = sorted(eng.sched.done, key=lambda r: r.rid)
    tokens = [list(r.generated) for r in done]
    if len(tokens) != len(prompts) or any(len(t) != NEW_TOKENS
                                          for t in tokens):
        raise RuntimeError(f"engine finished {len(tokens)} of "
                           f"{len(prompts)} requests")
    return eng, tokens, {"compile_s": round(clock.total - c0, 3),
                         "wall_s": round(wall, 3),
                         "tokens": sum(len(t) for t in tokens)}


def counters(snapshot: dict) -> dict:
    """The fence, recycle and prefix counters of a metrics snapshot."""
    keep = ("fence.fences", "fence.skipped_at_free", "fence.fences_averted",
            "fpr.recycled_hits", "fpr.prefix.hit_blocks",
            "fpr.prefix.miss_blocks", "fpr.prefix.hit_rate",
            "fpr.prefix.in_set_violations")
    return {k: snapshot[k] for k in keep}


def capacity_steps(eng) -> dict:
    """Ragged steps of ``eng`` by the token capacity each ran in."""
    from repro.core import tracing

    out = {t: 0 for t in eng.ragged_capacities}
    for st in tracing.RECORDER.steps:
        if st.engine == eng.trace_id:
            for p in st.phases or ():
                if p.name == "engine.dispatch":
                    out[p.args["tokens"]] += 1
    return out


def synthetic_state(state: dict, rng) -> dict:
    """The engine's cache with every slot's table filled from a random
    permutation of the pool — each slot then reads blocks that real
    requests wrote."""
    st = dict(state)
    W, Bs, M = st["tables"].shape
    n = st["kv"].shape[1]
    phys = np.full(W * Bs * M, -1, np.int32)
    take = min(n, W * Bs * M)
    phys[:take] = rng.permutation(n)[:take]
    st["tables"] = jnp.asarray(phys.reshape(W, Bs, M))
    return st


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check_kernel(compiled, what: str) -> None:
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{what}: no Pallas kernel in the compiled "
                             f"program")


def attention_check(st: dict, desc: dict, cfg, depth: int, rng) -> dict:
    """Layer 0's attention over the cache ``st``, with random f32 queries:
    the fused decode kernel (buffer depth ``depth``) and the ragged kernel
    (descriptor ``desc``) vs the jnp reference at precision HIGHEST, and
    the reference at default precision as the control."""
    from repro.kernels.paged_attention import ops
    from repro.kernels.paged_attention.ref import (
        paged_decode_attention_fused_ref, ragged_fused_ref)

    kv, tables = st["kv"][0], st["tables"]
    B = st["lengths"].shape[0]
    shape = (cfg.n_heads, cfg.head_dim)
    q = jnp.asarray(rng.standard_normal((B,) + shape), jnp.float32)
    qr = jnp.asarray(rng.standard_normal((RAGGED_CHECK_TOKENS,) + shape),
                     jnp.float32)
    real = np.asarray(desc["token_row"]) >= 0
    dargs = (q, kv, tables, st["lengths"])
    rargs = (qr, kv, tables, jnp.asarray(desc["token_row"]),
             jnp.asarray(desc["token_pos"]), jnp.asarray(desc["kv_lens"]))
    kernel = {
        "decode": ops.paged_attention(*dargs, buffer_depth=depth),
        "ragged": ops.ragged_paged_attention(
            qr, kv, tables, jnp.asarray(desc["tile_row"]),
            jnp.asarray(desc["tile_pos"]), jnp.asarray(desc["kv_lens"]))}
    refs = {}
    for precision in ("highest", "default"):
        with jax.default_matmul_precision(precision):
            refs[precision] = {
                "decode": jax.jit(paged_decode_attention_fused_ref)(*dargs),
                "ragged": jax.jit(ragged_fused_ref)(*rargs)}
    out = {}
    for step in ("decode", "ragged"):
        rows = real if step == "ragged" else slice(None)
        want = np.asarray(refs["highest"][step])[rows]
        out[f"{step}_attn_rel_err"] = rel_err(
            np.asarray(kernel[step])[rows], want)
        out[f"{step}_attn_control_rel_err"] = rel_err(
            np.asarray(refs["default"][step])[rows], want)
    return out


def reference_check(eng, cfg, clock: CompileClock, seed: int,
                    depth: int) -> dict:
    """One decode step and one ragged step, Pallas vs the jnp reference,
    on identical state.  Returns the measured errors."""
    from repro.kernels.paged_attention.ops import build_ragged_descriptor
    from repro.models import transformer as tfm

    rng = np.random.RandomState(seed + 1)
    bs = eng.cache.block_size
    st = synthetic_state(eng.cache.state, rng)
    M = st["tables"].shape[-1]
    B = eng.cache.max_batch
    c0 = clock.total
    out = {}

    def run_both(step, *args):
        """Logits of ``step(impl)`` for the Pallas and the reference
        attention, both compiled with every XLA matmul at HIGHEST, so
        the two programs differ only in how attention is computed."""
        logits = []
        for impl in (eng.page_impl, "ref"):
            with jax.default_matmul_precision("highest"):
                compiled = step(impl).lower(eng.params, st, *args).compile()
            logits.append(np.asarray(compiled(eng.params, st, *args)[0]))
        return logits

    # -- decode: the engine's own step carries the kernel; its logits
    # against the reference's on the same state
    st["lengths"] = jnp.asarray(rng.randint(1, M * bs, size=B), jnp.int32)
    toks = jnp.asarray(rng.randint(1, cfg.vocab, size=B), jnp.int32)
    check_kernel(eng._decode.lower(eng.params, st, toks).compile(),
                 "engine decode step")
    got, want = run_both(lambda impl: jax.jit(
        lambda p, s, t: tfm.decode_step(p, cfg, s, t, page_impl=impl)),
        toks)
    out["decode_rel_err"] = rel_err(got, want)
    out["decode_argmax_agree"] = float(np.mean(got.argmax(-1)
                                               == want.argmax(-1)))

    # -- ragged: a prefill chunk, a decode row and a prompt head in one
    # packed step, through the kernel and through the reference
    slots, q_lens, q_starts = [0, 3, 5], [40, 1, 16], [1000, 2999, 0]
    kv_lens = [s + q for s, q in zip(q_starts, q_lens)]
    d = build_ragged_descriptor(slots, q_lens, q_starts, kv_lens,
                                num_slots=B, t_cap=RAGGED_CHECK_TOKENS)
    real = d["token_src"] >= 0
    stream = np.zeros(RAGGED_CHECK_TOKENS, np.int32)
    stream[real] = rng.randint(1, cfg.vocab, size=int(real.sum()))
    args = [jnp.asarray(stream)] + [jnp.asarray(d[k]) for k in (
        "token_row", "token_pos", "tile_row", "tile_pos", "kv_lens",
        "last_index")]
    logits = [x[slots] for x in run_both(lambda impl: jax.jit(
        lambda p, s, *a: tfm.ragged_step(p, cfg, s, *a, page_impl=impl)),
        *args)]
    out["ragged_rel_err"] = rel_err(*logits)
    out["ragged_argmax_agree"] = float(np.mean(
        logits[0].argmax(-1) == logits[1].argmax(-1)))
    # the engine's own ragged programs carry the kernel too
    for (t_cap, _), compiled in eng._ragged_programs.items():
        check_kernel(compiled, f"engine ragged step T={t_cap}")
    # -- one layer's attention, kernel vs a full-precision reference
    out.update(attention_check(st, d, cfg, depth, rng))
    out["compile_s"] = round(clock.total - c0, 3)
    print(f"reference (Pallas vs ref, rel L2; logits tolerance "
          f"{LOGIT_REL_TOL}, attention tolerance {ATTN_REL_TOL}): {out}")
    for step in ("decode", "ragged"):
        err = out[f"{step}_rel_err"]
        if not err <= LOGIT_REL_TOL:
            raise AssertionError(f"{step} logits: rel err {err} exceeds "
                                 f"{LOGIT_REL_TOL}")
        err = out[f"{step}_attn_rel_err"]
        if not err <= ATTN_REL_TOL:
            raise AssertionError(f"{step} attention: rel err {err} "
                                 f"exceeds {ATTN_REL_TOL}")
        control = out[f"{step}_attn_control_rel_err"]
        if not control > ATTN_REL_TOL:
            raise AssertionError(
                f"{step} attention: the bf16-pass control ({control}) "
                f"passes {ATTN_REL_TOL}, so the bound cannot tell it "
                f"from the kernel")
    return out


def configure_cache() -> str:
    """Use ``$JAX_COMPILATION_CACHE_DIR`` if set (JAX reads it itself),
    else one fixed directory in the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config
    from repro.kernels.paged_attention import autotune
    from repro.models import transformer as tfm

    cache_dir = configure_cache()
    clock = CompileClock()
    print(f"device: {dev.device_kind} x{len(devices)} ({dev.platform})")
    print(f"compile cache: {cache_dir}")

    full = get_config(ARCH)
    cfg = get_config(ARCH, n_layers=N_LAYERS)
    print(f"reduced: n_layers {full.n_layers}→{cfg.n_layers} (bf16 weights "
          f"{full.param_count() * 2 / 1e9:.1f} GB > one chip)")
    c0 = clock.total
    t0 = time.perf_counter()
    params = jax.jit(tfm.init_params, static_argnums=(1, 2))(
        jax.random.PRNGKey(args.seed), cfg, jnp.bfloat16)
    jax.block_until_ready(params)
    print(f"init: {cfg.param_count() * 2 / 1e9:.2f} GB bf16 weights, "
          f"compile {clock.total - c0:.3f} s, "
          f"wall {time.perf_counter() - t0:.3f} s")

    base = dict(page_impl="pallas", dtype=jnp.bfloat16, max_batch=MAX_BATCH,
                max_seq_len=MAX_SEQ_LEN, num_blocks=NUM_BLOCKS,
                admission="fcfs")
    prompts = make_requests(cfg.vocab, tfm.BLOCK_SIZE, args.seed)
    depth = autotune.get_tuning(cfg.n_kv_heads, cfg.head_dim,
                                tfm.BLOCK_SIZE).buffer_depth
    print(f"requests: {len(prompts)}, prompt lengths "
          f"{sorted(len(p) for p in prompts)}, {NEW_TOKENS} new tokens each; "
          f"fused decode kernel buffer depth {depth}")

    tokens_a = {}
    for fpr in (True, False):
        eng, tokens_a[fpr], stats = serve(cfg, params, prompts, clock,
                                          fpr_enabled=fpr,
                                          prefix_sharing=False, **base)
        snap = eng.metrics.snapshot()
        print(f"phase A (per-slot, fpr={'on' if fpr else 'off'}): {stats} "
              f"prefill_traces={snap['engine.prefill_traces']} "
              f"{counters(snap)}")
        del eng
        gc.collect()             # the engine's jit closures form a cycle
    if tokens_a[True] != tokens_a[False]:
        raise AssertionError("phase A: tokens differ with FPR on and off")
    print("phase A: tokens identical with FPR on and off")

    tokens_b = {}
    for fpr in (False, True):    # keep the FPR-on engine for the reference
        eng, tokens_b[fpr], stats = serve(cfg, params, prompts, clock,
                                          fpr_enabled=fpr,
                                          chunked_prefill=True,
                                          ragged_kernel=True, **base)
        snap = eng.metrics.snapshot()
        print(f"phase B (ragged, fpr={'on' if fpr else 'off'}): {stats} "
              f"prefill_chunk_traces={snap['engine.prefill_chunk_traces']} "
              f"kernel_calls={snap['engine.kernel.kernel_calls']} "
              f"ragged_steps={snap['engine.kernel.ragged_steps']} "
              f"{counters(snap)}")
        print(f"phase B (ragged, fpr={'on' if fpr else 'off'}): steps by "
              f"capacity {capacity_steps(eng)}")
        if snap["engine.prefill_chunk_traces"] != len(eng.ragged_capacities):
            raise AssertionError(
                "phase B: the ragged step traced "
                f"{snap['engine.prefill_chunk_traces']} times for "
                f"{len(eng.ragged_capacities)} capacities")
        if (snap["engine.kernel.kernel_calls"]
                != cfg.n_layers * snap["engine.kernel.ragged_steps"]):
            raise AssertionError("phase B: not one kernel call per layer "
                                 "per step")
        if not fpr:
            del eng
            gc.collect()
    if tokens_b[True] != tokens_b[False]:
        raise AssertionError("phase B: tokens differ with FPR on and off")
    print("phase B: tokens identical with FPR (and prefix sharing) on and "
          "off")
    flat_a = np.concatenate(tokens_a[True])
    flat_b = np.concatenate(tokens_b[True])
    print(f"greedy tokens agreeing between phase A and phase B: "
          f"{np.mean(flat_a == flat_b):.4f}")

    reference_check(eng, cfg, clock, args.seed, depth)
    del eng

    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(f"compile seconds, all phases: {clock.total:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
