"""Ragged fused-KV serving: mixed prefill+decode batches through ONE
ragged kernel call per attention layer per engine step.

The fast lane pins the batching rewrite (ragged pass vs the per-slot
chunked path, reference attention on both sides): bit-identical tokens,
one program per token capacity, all traced at construction, the choice of
capacity, and the one-call-per-layer-per-step counter invariant.  The
slow lane re-runs the comparison over the interpreted pallas kernel (the
real scalar-prefetched ragged page walk) and sweeps heavier mixes for the
nightly lane.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tracing
from repro.kernels.paged_attention.ops import QT, build_ragged_descriptor
from repro.models import transformer as tfm
from repro.models.config import ModelConfig
from repro.serving.config import EngineConfig
from repro.serving.engine import Engine, pick_capacity, ragged_capacities

_CFG_KW = dict(name="t", n_layers=1, d_model=32, n_heads=2, n_kv_heads=1,
               d_ff=64, vocab=64, head_dim=16)


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig(**_CFG_KW)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    return cfg, params


def _drive(model, *, ragged: bool, page_impl: str, lengths, seed: int = 7,
           max_batch: int = 4):
    """Serve ``lengths`` to the end; returns (tokens per request, the
    final snapshot, the engine, its trace counter once constructed)."""
    cfg, params = model
    eng = Engine(cfg, params, config=EngineConfig(
        num_blocks=64, max_batch=max_batch, max_seq_len=1024,
        fpr_enabled=True, admission="fcfs", chunked_prefill=True,
        prefill_chunk=1, page_impl=page_impl, ragged_kernel=ragged))
    built = eng.metrics.snapshot()["engine.prefill_chunk_traces"]
    rng = np.random.RandomState(seed)
    for i, n in enumerate(lengths):
        eng.submit(rng.randint(1, _CFG_KW["vocab"], size=n),
                   max_new_tokens=6 + (i % 3), stream=f"s{i % 2}",
                   group_id=(i % 2) + 1)
    while not eng.sched.idle and eng.steps < 10_000:
        eng.step()
    toks = [list(map(int, r.generated))
            for r in sorted(eng.sched.done, key=lambda r: r.rid)]
    return toks, eng.metrics.snapshot(), eng, built


def _dispatches(eng) -> list:
    """(capacity, real tokens) of each of the engine's ragged steps."""
    return [(p.args["tokens"], p.args["real"])
            for st in tracing.RECORDER.steps if st.engine == eng.trace_id
            for p in st.phases if p.name == "engine.dispatch"]


def test_ragged_tokens_match_chunked(model):
    """The ragged pass only changes *which call* serves a row — decoded
    tokens are bit-identical to the per-slot chunked engine, the mixed
    step traces once per token capacity, and every step costs one kernel
    call per attention layer whatever its prefill/decode blend."""
    lengths = (40, 200, 170, 300)
    ref, *_ = _drive(model, ragged=False, page_impl="ref", lengths=lengths)
    got, snap, eng, _ = _drive(model, ragged=True, page_impl="ref",
                               lengths=lengths)
    assert got == ref
    assert snap["engine.prefill_chunk_traces"] == len(eng.ragged_capacities)
    assert not snap["engine.prefill_traces"]
    assert (snap["engine.kernel.kernel_calls"]
            == _CFG_KW["n_layers"] * snap["engine.kernel.ragged_steps"])
    assert snap["engine.kernel.dma_bytes"] > 0


def test_ragged_kernel_keys_absent_on_default_engines(model):
    """KERNEL_SCHEMA is an optional group: engines not serving through
    the ragged kernel must not grow new snapshot keys (the golden schema
    tests pin exact equality for the default stack)."""
    _, snap, *_ = _drive(model, ragged=False, page_impl="ref",
                         lengths=(40,))
    assert not [k for k in snap if k.startswith("engine.kernel.")]


def test_ragged_capacities_and_the_choice_of_one():
    """Capacities hold k = 0, 1, 2 chunk rows beside a decode row in every
    slot, then a chunk in every slot; a step takes the smallest that
    holds its packed rows, and the full one once three chunks are in."""
    caps = ragged_capacities(8, 256)
    assert caps == (64, 320, 576, 2048)
    assert pick_capacity(caps, 8) == pick_capacity(caps, 64) == 64
    assert pick_capacity(caps, 65) == pick_capacity(caps, 256 + 7 * QT) == 320
    assert pick_capacity(caps, 2 * 256 + 6 * QT) == 576
    assert pick_capacity(caps, 3 * 256) == 2048           # k >= 3
    assert pick_capacity(caps, 3 * 100 + 5 * QT) == 576   # short chunks
    # T_k reaching t_cap leaves one program for it
    assert ragged_capacities(2, 256) == (16, 272, 512)
    assert ragged_capacities(1, 256) == (8, 256)
    # a chunk that is no multiple of QT pads to one
    assert ragged_capacities(4, 100) == (32, 136, 240, 416)
    for max_batch, chunk in ((8, 256), (2, 256), (1, 256), (4, 100),
                             (3, 13), (16, 128)):
        caps = ragged_capacities(max_batch, chunk)
        assert all(c % QT == 0 for c in caps)
        assert list(caps) == sorted(set(caps))
        seg = -(-chunk // QT) * QT
        assert caps[-1] == max_batch * seg
        # every mix of k chunk rows and decode rows fits its pick
        for k in range(max_batch + 1):
            q_lens = [chunk] * k + [1] * (max_batch - k)
            packed = sum(-(-n // QT) * QT for n in q_lens)
            t = pick_capacity(caps, packed)
            assert t == min(c for c in caps if c >= packed)
            build_ragged_descriptor(
                list(range(max_batch)), q_lens, [0] * max_batch, q_lens,
                num_slots=max_batch, t_cap=t)


def test_ragged_every_capacity_matches_chunked(model):
    """A mix that reaches every capacity, three or more chunk rows
    included, decodes the per-slot chunked engine's tokens; each capacity
    is one program, traced at construction and never after."""
    lengths = (40, 200, 170, 300, 500, 420, 30)
    ref, *_ = _drive(model, ragged=False, page_impl="ref", lengths=lengths)
    got, snap, eng, built = _drive(model, ragged=True, page_impl="ref",
                                   lengths=lengths)
    assert got == ref
    caps = eng.ragged_capacities
    assert len(caps) == 4
    assert built == snap["engine.prefill_chunk_traces"] == len(caps)
    steps = _dispatches(eng)
    assert len(steps) == snap["engine.kernel.ragged_steps"]
    assert {t for t, _ in steps} == set(caps)
    for t, real in steps:
        assert real <= t
        if real <= 4:                 # at most one padded tile a slot
            assert t == caps[0]
    chunk = tfm.BLOCK_SIZE
    # the full program ran for steps with three or more chunk rows
    assert max(real for t, real in steps if t == caps[-1]) > 2 * chunk
    # each prompt token runs once, and a decode row for every token but
    # a request's first (its last chunk's logits give that one)
    assert sum(r for _, r in steps) == sum(lengths) + sum(
        len(t) - 1 for t in got)


def test_ragged_reshard_compiles_for_the_new_tables(model):
    """A live reshard changes the tables' shape: the ragged programs the
    steps after it use are compiled for the new shape, and the tokens are
    those of the engine that never resharded."""
    cfg, params = model
    toks = {}
    for resize in (False, True):
        eng = Engine(cfg, params, config=EngineConfig(
            num_blocks=64, max_batch=4, max_seq_len=1024,
            admission="fcfs", chunked_prefill=True, prefill_chunk=1,
            ragged_kernel=True))
        rng = np.random.RandomState(7)
        for i, n in enumerate((40, 200, 170, 300)):
            eng.submit(rng.randint(1, _CFG_KW["vocab"], size=n),
                       max_new_tokens=8, stream=f"s{i % 2}",
                       group_id=(i % 2) + 1)
        for _ in range(3):
            eng.step()
        if resize:
            eng.resize_workers(2)
        eng.run()
        toks[resize] = [list(map(int, r.generated))
                        for r in sorted(eng.sched.done, key=lambda r: r.rid)]
        shapes = {shape for _, shape in eng._ragged_programs}
        assert shapes == ({(1, 4, 8), (2, 2, 8)} if resize else {(1, 4, 8)})
    assert toks[True] == toks[False]


def test_ragged_requires_chunked_prefill():
    with pytest.raises(ValueError):
        EngineConfig(ragged_kernel=True, chunked_prefill=False)


@pytest.mark.slow
def test_ragged_pallas_tokens_match_chunked(model):
    """The interpreted pallas ragged kernel decodes the exact same
    tokens as both reference engines."""
    lengths = (40, 150, 90, 200)
    ref, *_ = _drive(model, ragged=False, page_impl="ref", lengths=lengths)
    got, snap, eng, _ = _drive(model, ragged=True,
                               page_impl="pallas_interpret", lengths=lengths)
    assert got == ref
    assert snap["engine.prefill_chunk_traces"] == len(eng.ragged_capacities)
    assert (snap["engine.kernel.kernel_calls"]
            == snap["engine.kernel.ragged_steps"])


@pytest.mark.slow
def test_ragged_heavy_mix_sweep(model):
    """Nightly sweep: more rows than slots, re-queued admissions, and a
    decode-heavy tail — ragged stays bit-identical to chunked."""
    for seed, lengths in ((11, (40, 200, 170, 300, 90, 260)),
                          (12, (310, 20, 150, 40, 90))):
        ref, *_ = _drive(model, ragged=False, page_impl="ref",
                         lengths=lengths, seed=seed)
        got, snap, eng, _ = _drive(model, ragged=True, page_impl="ref",
                                   lengths=lengths, seed=seed)
        assert got == ref, f"seed {seed} diverged"
        assert (snap["engine.prefill_chunk_traces"]
                == len(eng.ragged_capacities))
