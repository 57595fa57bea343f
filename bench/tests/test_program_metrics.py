"""The per-layer metrics that read the program's own records
(``program_records.py``): on a small CPU run driven by the harness, on a
hand-made record whose window starts after warm-up steps in the ring, and
on a program that keeps no records."""

import os

import numpy as np
import pytest

import drive
import run
from conftest import BENCH

from repro.core import tracing

METRICS = ("engine.host_ms_p50", "admission.wait_ms_p75",
           "engine.prefill_ms_p50", "prefix.recomputed_share")


def _compute(name, rec):
    mod = run.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                          "metric")
    return mod.compute(rec)


# ============================================================ a CPU run
@pytest.fixture(scope="module")
def served():
    """The shared-prefix cell at smoke size on the CPU, served by the
    harness's own client after warm-up: two tenants at a rate that keeps
    requests of one tenant seated together, so prefixes are hit."""
    import jax.numpy as jnp

    from repro.serving.config import EngineConfig
    from repro.serving.engine import Engine

    cell = run.load_cell("granite-3-8b.rag-shared", smoke=True)
    cfg, tp = cell["cfg"], dict(cell["traffic"])
    tp.update(rate_per_s=6.0, tenants=2,
              output_tokens={"median": 20, "sigma": 0.3, "min": 12,
                             "max": 30})
    engine_cfg = dict(cfg["engine"], page_impl="ref")
    ref = run.load_module(os.path.join(BENCH, "references",
                                       cfg["reference"] + ".py"), "ref")
    kind = run.load_module(os.path.join(BENCH, "traffic", "kinds",
                                        tp["kind"] + ".py"), "kind")
    seed, seconds, below = 3_000_000_211, 3.0, cfg["token_ids_below"]
    eng = Engine(run.program_config(cfg, True), ref.make_params(seed, cfg),
                 config=EngineConfig(dtype=jnp.dtype(cfg["dtype"]).type,
                                     **engine_cfg))
    for r in kind.warmup(tp, seed, below):
        eng.submit(np.asarray(r["prompt"], np.int32),
                   max_new_tokens=r["max_new_tokens"], stream=r["stream"],
                   group_id=r["group_id"])
    eng.run()
    warm_steps = len(tracing.RECORDER.steps)
    raw = drive.run_window(eng, kind, kind.window(tp, seed, seconds, below),
                           seconds, ragged=True,
                           tracer=drive.Tracer(None, 0.0, 0.0))
    rec = run.record(cell, raw, None)
    del eng, raw                        # as the run does before its metrics
    return rec, warm_steps


def test_each_metric_reads_the_window(served):
    rec, warm_steps = served
    assert warm_steps > 0
    got = {m: _compute(m, rec) for m in METRICS}
    assert all(v is not None for v in got.values()), got
    assert set(METRICS) <= set(rec["notes"]), rec["notes"]
    tracked = len(rec["requests"])
    assert f"({tracked} tracked by the harness)" in rec["notes"][
        "admission.wait_ms_p75"]
    assert rec["notes"]["engine.host_ms_p50"].startswith(
        f"{sum(s['in_window'] for s in rec['steps'])} steps")
    assert 0.0 <= got["prefix.recomputed_share"] <= 100.0
    # host time lies inside the harness's step and seating precedes TTFT
    step_ms = np.median([(s["t1"] - s["t0"]) * 1e3 for s in rec["steps"]
                         if s["in_window"]])
    assert 0.0 < got["engine.host_ms_p50"] <= step_ms
    assert got["admission.wait_ms_p75"] >= 0.0
    assert got["engine.prefill_ms_p50"] > 0.0


def test_window_requests_are_the_harness_requests(served):
    import program_records

    rec, _ = served
    reqs = program_records.window_requests(rec)
    assert len(reqs) == len(rec["requests"])
    for q, t in zip(sorted(reqs, key=lambda q: q.submit), rec["requests"]):
        # the program admits no later than the harness sees it seated,
        # and emits no later than the harness stamps the first token
        assert t["due"] * 1e9 - 1e3 <= q.submit
        assert q.admit <= t["seated"] * 1e9 + 1e3
        assert q.first_token <= t["stamps"][0] * 1e9 + 1e3


# ================================================== a hand-made record
def _step(ring, engine, i, t0, dur, fetch):
    """A step record whose one phase, an ``engine.fetch`` of ``fetch`` ns,
    sits in ``ring``'s span ring."""
    span = tracing.Span("engine.fetch")
    span.start_ns, span.dur_ns, span.step = t0 + 1, fetch, i
    span.rid, span.parent, span.engine = None, tracing.STEP_SPAN, engine
    first = ring.written
    ring._add(span)
    return tracing.StepRecord(ring, engine, i, t0, dur, first, 1, (), ())


def _request(engine, rid, submit, admit, first, hit, again):
    q = tracing.RequestRecord(engine, rid, 500, submit)
    q.admit, q.first_token, q.done = admit, first, first + 10
    q.hit_tokens, q.recomputed_tokens = hit, again
    return q


@pytest.fixture
def synthetic(monkeypatch):
    """Warm-up steps and requests sit in the ring before the window; the
    window's harness steps each hold one program step."""
    ring = tracing.Recorder()
    monkeypatch.setattr(tracing, "RECORDER", ring)
    ms = 1_000_000
    base = 5_000 * ms
    for i in range(3):                          # warm-up: 100 ms steps
        ring.steps.append(_step(ring, 1, i, base + i * 200 * ms, 100 * ms,
                                0))
    ring.requests[(1, 1)] = _request(1, 1, base, base + ms, base + 5 * ms,
                                     128, 128)
    w0 = base + 2_000 * ms
    host = [4, 6, 9, 5]
    steps = []
    for i, h in enumerate(host):
        t0 = w0 + i * 50 * ms
        ring.steps.append(_step(ring, 1, 3 + i, t0 + ms // 10, 40 * ms,
                                (40 - h) * ms))
        steps.append({"t0": t0 / 1e9, "t1": (t0 + 41 * ms) / 1e9,
                      "in_window": i < 3, "traced": False})
    waits, prefills = [10, 20, 30, 40], [100, 300, 200, 400]
    hits, again = [0, 256, 128, 256], [0, 256, 128, 0]
    for k in range(4):
        s = w0 + k * ms
        ring.requests[(1, k + 2)] = _request(
            1, k + 2, s, s + waits[k] * ms, s + (waits[k] + prefills[k]) * ms,
            hits[k], again[k])
    rec = {"steps": steps, "window_s": 0.2, "notes": {},
           "requests": [{"due": (w0 + k * ms) / 1e9} for k in range(4)]}
    return rec


def test_hand_made_window(synthetic):
    rec = synthetic
    assert _compute("engine.host_ms_p50", rec) == pytest.approx(6.0)
    assert _compute("admission.wait_ms_p75", rec) == pytest.approx(
        np.percentile([10, 20, 30, 40], 75))
    assert _compute("engine.prefill_ms_p50", rec) == pytest.approx(250.0)
    assert _compute("prefix.recomputed_share",
                    rec) == pytest.approx(100.0 * 384 / 640)
    assert rec["notes"]["engine.host_ms_p50"] == "3 steps of 3 in the window"
    assert rec["notes"]["admission.wait_ms_p75"].startswith(
        "4 of 4 recorded requests (4 tracked by the harness)")
    assert rec["notes"]["prefix.recomputed_share"].startswith("3 of 4")


def test_a_traced_window_times_requests_before_the_profiler_stops(
        synthetic):
    """``stop_trace`` holds the loop after the last traced step: the
    request times read only requests submitted before it, and the notes
    count the times that span it."""
    rec = synthetic
    rec["steps"][0]["traced"] = True           # stop after 41 ms
    # submitted at 0, 1, 2, 3 ms into the window, seated 10–43 ms later
    assert _compute("admission.wait_ms_p75", rec) == pytest.approx(
        np.percentile([10, 20, 30, 40], 75))
    rec["steps"][0]["t1"] = rec["steps"][0]["t0"] + 0.0025   # at 2.5 ms
    assert _compute("admission.wait_ms_p75", rec) == pytest.approx(
        np.percentile([10, 20, 30], 75))
    assert _compute("engine.prefill_ms_p50", rec) == pytest.approx(200.0)
    assert rec["notes"]["admission.wait_ms_p75"] == (
        "3 of 3 recorded requests (4 tracked by the harness), 0 preempted; "
        "submitted before the profiler stopped, 3 spanning the stop")
    assert rec["notes"]["engine.prefill_ms_p50"].endswith(
        "0 spanning the stop")
    # the prefix share counts tokens, not times: every request
    assert _compute("prefix.recomputed_share",
                    rec) == pytest.approx(100.0 * 384 / 640)


def test_a_program_without_records_gives_nothing(synthetic, monkeypatch):
    monkeypatch.delattr(tracing, "RECORDER")
    for m in METRICS:
        assert _compute(m, synthetic) is None
