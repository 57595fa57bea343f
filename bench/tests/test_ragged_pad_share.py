"""``engine.ragged_pad_share``: the share of the ragged steps' token rows
that padding filled, read from the args of each step's ``engine.dispatch``
span; on hand-made step records, on a small ragged engine's own records,
and on spans that carry no args."""

import os

import numpy as np
import pytest

import run
from conftest import BENCH

from repro.core import tracing

NAME = "engine.ragged_pad_share"


def _compute(rec):
    mod = run.load_module(os.path.join(BENCH, "metrics", NAME + ".py"),
                          "metric")
    return mod.compute(rec)


def _step(ring, i, t0, dispatch_args):
    """A step record whose phases, an ``engine.dispatch`` with
    ``dispatch_args`` and an ``engine.fetch``, sit in ``ring``."""
    first = ring.written
    for k, (name, args) in enumerate((("engine.dispatch", dispatch_args),
                                      ("engine.fetch", None))):
        span = tracing.Span(name, args)
        span.start_ns, span.dur_ns, span.step = t0 + 1 + k, 1, i
        span.rid, span.parent, span.engine = None, tracing.STEP_SPAN, 1
        ring._add(span)
    return tracing.StepRecord(ring, 1, i, t0, 10_000, first, 2, (), ())


def _window(monkeypatch, dispatch_args):
    """A record whose window holds one harness step per entry of
    ``dispatch_args``, after a warm-up step outside it."""
    ring = tracing.Recorder()
    monkeypatch.setattr(tracing, "RECORDER", ring)
    ms = 1_000_000
    ring.steps.append(_step(ring, 0, 1_000 * ms, {"tokens": 2048,
                                                  "real": 5}))
    steps = []
    for i, args in enumerate(dispatch_args):
        t0 = 2_000 * ms + i * 50 * ms
        ring.steps.append(_step(ring, i + 1, t0 + ms // 10, args))
        steps.append({"t0": t0 / 1e9, "t1": (t0 + ms) / 1e9,
                      "in_window": True, "traced": False})
    return {"steps": steps, "notes": {}, "requests": []}


def test_share_of_the_window_steps_rows(monkeypatch):
    rec = _window(monkeypatch, [{"tokens": 64, "real": 8},
                                {"tokens": 320, "real": 263},
                                {"tokens": 64, "real": 5}])
    assert _compute(rec) == pytest.approx(100.0 * (1 - 276 / 448))
    assert rec["notes"][NAME] == (
        "3 ragged steps of 3 recorded in the window, 276 real tokens in "
        "448 rows")


def test_spans_without_args_give_nothing(monkeypatch):
    """As on a program that sizes no step: dispatch spans carry no args."""
    rec = _window(monkeypatch, [None, None])
    assert _compute(rec) is None
    assert NAME not in rec["notes"]


def test_a_program_without_records_gives_nothing(monkeypatch):
    rec = _window(monkeypatch, [{"tokens": 64, "real": 8}])
    monkeypatch.delattr(tracing, "RECORDER")
    assert _compute(rec) is None


def test_a_ragged_engine_reads_its_own_padding():
    """A small ragged engine on the CPU: the metric over all its steps
    counts every prompt token and decode row as real, in the rows of the
    capacities its steps ran."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as tfm
    from repro.models.config import ModelConfig
    from repro.serving.config import EngineConfig
    from repro.serving.engine import Engine

    cfg = ModelConfig(name="t", n_layers=1, d_model=32, n_heads=2,
                      n_kv_heads=1, d_ff=64, vocab=64, head_dim=16)
    eng = Engine(cfg, tfm.init_params(jax.random.PRNGKey(0), cfg,
                                      jnp.float32),
                 config=EngineConfig(num_blocks=64, max_batch=4,
                                     max_seq_len=1024, admission="fcfs",
                                     chunked_prefill=True, prefill_chunk=1,
                                     ragged_kernel=True))
    rng = np.random.RandomState(3)
    lengths = (40, 200, 300)
    for n in lengths:
        eng.submit(rng.randint(1, 64, size=n), max_new_tokens=5)
    eng.run()
    mine = [s for s in tracing.RECORDER.steps if s.engine == eng.trace_id]
    rec = {"steps": [{"t0": s.start_ns / 1e9,
                      "t1": (s.start_ns + s.dur_ns) / 1e9,
                      "in_window": True} for s in mine],
           "notes": {}, "requests": []}
    rows = sum(p.args["tokens"] for s in mine for p in s.phases
               if p.name == "engine.dispatch")
    real = sum(lengths) + len(lengths) * (5 - 1)
    assert _compute(rec) == pytest.approx(100.0 * (1 - real / rows))
    assert rec["notes"][NAME].startswith(
        f"{len(mine)} ragged steps of {len(mine)} recorded")
