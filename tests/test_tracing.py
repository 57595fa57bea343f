"""The in-program span recorder (``core/tracing.py``): spans, marks and
step records on the recorder's clock, the bounded rings, the per-request
records of a live engine on each serving path, and the Chrome-trace view.
"""

import gc
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tracing
from repro.core.events import FenceIssued, ShardRefreshed, TopologyChanged
from repro.core.tracing import (STEP_SPAN, TID_ENGINE, TID_REQUEST_BASE,
                                Recorder, TraceCollector)
from repro.models import transformer as tfm
from repro.models.config import ModelConfig
from repro.serving.admission import CapacityError, GovernorConfig
from repro.serving.config import EngineConfig
from repro.serving.engine import Engine

#: wide enough that a CPU step takes tens of ms, so the recorder's own
#: few microseconds between phases stay far below 5% of a step
MODEL = ModelConfig(name="tracing", n_layers=2, d_model=512, n_heads=4,
                    n_kv_heads=2, d_ff=2048, vocab=16384, head_dim=128)
TINY = ModelConfig(name="tiny", n_layers=1, d_model=32, n_heads=2,
                   n_kv_heads=1, d_ff=64, vocab=64, head_dim=16)
BS = tfm.BLOCK_SIZE
PREFIX = 2 * BS                       # two full shared blocks
#: names the benchmark harness gives its own host spans
HARNESS_SPANS = {"submit", "engine.step", "record"}

#: blocks per prefill chunk: the first prompt fits one chunk, the longest
#: takes two
CHUNK = 3
PATHS = {
    "monolithic": dict(),
    "chunked": dict(chunked_prefill=True, prefill_chunk=CHUNK),
    "ragged": dict(chunked_prefill=True, prefill_chunk=CHUNK,
                   ragged_kernel=True),
}


@pytest.fixture
def recorder(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(tracing, "RECORDER", rec)
    return rec


class FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``."""
    log: list = []

    def __init__(self, name, **kw):
        self.name = name

    def __enter__(self):
        FakeAnnotation.log.append(("enter", self.name))

    def __exit__(self, *exc):
        FakeAnnotation.log.append(("exit", self.name))


# ================================================================= recorder
class TestRecorder:
    def test_nested_spans_record_parent_rid_and_times(self, recorder):
        with tracing.span("outer"):
            with tracing.span("inner", rid=7):
                pass
        inner, outer = recorder.spans
        assert (inner.name, inner.parent, inner.rid) == ("inner", "outer", 7)
        assert (outer.name, outer.parent, outer.rid) == ("outer", None, None)
        assert outer.start_ns <= inner.start_ns
        assert (inner.start_ns + inner.dur_ns
                <= outer.start_ns + outer.dur_ns)
        assert inner.step == outer.step == -1 and inner.engine == 0
        assert inner.args == {"rid": 7} and outer.args is None

    def test_span_enters_the_profiler_annotation(self, recorder,
                                                 monkeypatch):
        FakeAnnotation.log = []
        monkeypatch.setattr(tracing, "_annotation", FakeAnnotation)
        with tracing.step(3, 11, tuple):
            with tracing.span("engine.fetch"):
                pass
            tracing.mark("engine.preempt", rid=2)
        assert FakeAnnotation.log == [
            ("enter", STEP_SPAN), ("enter", "engine.fetch"),
            ("exit", "engine.fetch"), ("enter", "engine.preempt"),
            ("exit", "engine.preempt"), ("exit", STEP_SPAN)]

    def test_mark_is_an_instant_event(self, recorder):
        with tracing.span("engine.admit"):
            tracing.mark("engine.preempt", rid=4, strategy="recompute")
        mark = recorder.spans[0]
        assert (mark.name, mark.dur_ns, mark.rid) == ("engine.preempt", 0, 4)
        assert mark.parent == "engine.admit"
        assert mark.args == {"rid": 4, "strategy": "recompute"}

    def test_step_collects_its_phases_tokens_and_retraces(self, recorder):
        r = recorder.request(5, 1, prompt_tokens=10, submit=tracing.now_ns())
        traces = {"prefill": 0, "decode": 0}
        with tracing.step(5, 2, lambda: tuple(traces.items())):
            with tracing.span("engine.dispatch"):
                traces["decode"] += 1
            with tracing.span("engine.emit"):
                r.token()
        (st,) = recorder.steps
        assert (st.engine, st.step) == (5, 2)
        assert [p.name for p in st.phases] == ["engine.dispatch",
                                               "engine.emit"]
        assert all(p.engine == 5 and p.step == 2 and p.parent == STEP_SPAN
                   for p in st.phases)
        assert st.emitted == (1,) and st.retraced == ("decode",)
        # the step is its record, not a second copy in the span ring
        assert [s.name for s in recorder.spans] == ["engine.dispatch",
                                                    "engine.emit"]
        assert st.phases[0].start_ns >= st.start_ns
        assert (st.phases[-1].start_ns + st.phases[-1].dur_ns
                <= st.start_ns + st.dur_ns)
        # outside a step the context is clear again
        with tracing.span("x"):
            pass
        assert recorder.spans[-1].step == -1

    def test_a_raising_body_still_closes_its_span(self, recorder):
        with pytest.raises(ValueError):
            with tracing.step(1, 0, tuple):
                with tracing.span("engine.admit"):
                    raise ValueError
        assert [s.name for s in recorder.spans] == ["engine.admit"]
        assert [p.name for p in recorder.steps[0].phases] == ["engine.admit"]
        assert not recorder._open and recorder.step == -1

    def test_acting_attributes_spans_outside_a_step(self, recorder):
        with tracing.acting(4):
            tracing.mark("fpr.reshard", old=2, new=1)
            with tracing.span("fpr.fence"):
                pass
        tracing.mark("x")
        assert [(s.name, s.engine, s.step) for s in recorder.spans] == [
            ("fpr.reshard", 4, -1), ("fpr.fence", 4, -1), ("x", 0, -1)]

    def test_rings_stay_bounded(self, monkeypatch):
        rec = Recorder(span_capacity=16, step_capacity=4, request_capacity=3)
        monkeypatch.setattr(tracing, "RECORDER", rec)
        for i in range(10):
            with tracing.step(1, i, tuple):
                for name in ("engine.dispatch", "engine.fetch"):
                    with tracing.span(name):
                        pass
            rec.request(1, i, prompt_tokens=1, submit=i)
        assert len(rec.spans) == 16 and rec.written == 20
        assert [s.step for s in rec.spans] == [i // 2 for i in range(4, 20)]
        assert [s.step for s in rec.steps] == [6, 7, 8, 9]
        assert [len(s.phases) for s in rec.steps] == [2, 2, 2, 2]
        assert sorted(rid for _, rid in rec.requests) == [7, 8, 9]
        # a step whose phases the span ring dropped reads none
        assert rec.span_range(0, 2) is None
        # the process's recorder holds a long run's steps, and the spans of
        # a benchmark window and its drain: 171 s at 65 ms a step, at most
        # 33 spans a step
        assert tracing.STEP_CAPACITY >= 65536
        assert tracing.SPAN_CAPACITY >= 33 * 171_000 // 65


# ============================================================ a live engine
def _serve(path: str) -> dict:
    """A shared-prefix case: request 1 is seated alone, then requests 2
    and 3 arrive with the same two-block prefix and attach to it.

    Every step decodes, so each one waits for its logits (tens of ms on
    the CPU).  A step of chunk dispatches alone takes 3–4 ms there, where
    the interpreter's own pauses between two phases (a thread switch after
    a dispatch: 10–20 µs on an idle CPU, some hundreds under a loaded
    test run) are a share of the step that the machine's load sets.  For
    the same reason no collection of the test process's heap runs while
    it serves."""
    gc.collect()
    gc.disable()
    try:
        return _serve_shared_prefix(path)
    finally:
        gc.enable()


def _serve_shared_prefix(path: str) -> dict:
    params = tfm.init_params(jax.random.PRNGKey(0), MODEL, jnp.float32)
    eng = Engine(MODEL, params, config=EngineConfig(
        num_blocks=48, max_batch=4, max_seq_len=512, **PATHS[path]))
    collector = TraceCollector(eng)
    rng = np.random.RandomState(0)
    prefix = rng.randint(1, MODEL.vocab, size=PREFIX)
    prompts = [np.concatenate([prefix, rng.randint(1, MODEL.vocab, size=n)])
               for n in (20, 37, 150)]
    rids = [eng.submit(prompts[0], max_new_tokens=4)]
    eng.step()
    rids += [eng.submit(p, max_new_tokens=4) for p in prompts[1:]]
    eng.run()
    collector.detach()
    rec = tracing.RECORDER
    return {"eng": eng, "collector": collector, "prompts": prompts,
            "rids": rids,
            "steps": [s for s in rec.steps if s.engine == eng.trace_id],
            "requests": {rid: rec.requests[(eng.trace_id, rid)]
                         for rid in rids}}


@pytest.fixture(scope="module", params=list(PATHS))
def served(request):
    return request.param, _serve(request.param)


class TestEngineRecords:
    def test_phases_nest_inside_their_step_and_cover_it(self, served):
        path, s = served
        assert s["steps"] and all(st.emitted for st in s["steps"])
        expected = {"engine.admit", "engine.residency", "engine.cow",
                    "engine.tables", "engine.dispatch", "engine.fetch",
                    "engine.emit"}
        if path != "monolithic":
            expected.add("engine.grow")
        if path == "ragged":
            expected.add("engine.descriptor")
        seen = set()
        for st in s["steps"]:
            end = st.start_ns + st.dur_ns
            top = [p for p in st.phases if p.parent == STEP_SPAN]
            for p in st.phases:
                assert st.start_ns <= p.start_ns
                assert p.start_ns + p.dur_ns <= end
                assert p.step == st.step and p.engine == st.engine
                seen.add(p.name)
            if len(st.phases) > 1:          # a step that served something
                assert sum(p.dur_ns for p in top) >= 0.95 * st.dur_ns, (
                    path, st.step, [(p.name, p.dur_ns) for p in top],
                    st.dur_ns)
        assert expected <= seen

    def test_one_dispatch_per_jitted_call(self, served):
        path, s = served
        for st in s["steps"]:
            n = sum(p.name == "engine.dispatch" for p in st.phases)
            fetches = sum(p.name == "engine.fetch" for p in st.phases)
            assert fetches <= 1
            if path == "ragged":
                assert n == fetches
            else:                           # prefill programs + the decode
                assert n >= fetches

    def test_request_times_are_ordered(self, served):
        _, s = served
        for r in s["requests"].values():
            assert r.submit <= r.admit <= r.first_token <= r.done, r.as_dict()
            assert r.preemptions == 0

    def test_prefix_hits_and_prefilled_tokens(self, served):
        path, s = served
        reqs = [s["requests"][rid] for rid in s["rids"]]
        assert [r.prompt_tokens for r in reqs] == [len(p)
                                                   for p in s["prompts"]]
        # the first request found nothing cached; the others attached the
        # two prefix blocks at admission, and prefill computed them again
        assert [r.hit_tokens for r in reqs] == [0, PREFIX, PREFIX]
        assert [r.recomputed_tokens for r in reqs] == [0, PREFIX, PREFIX]
        assert [r.prefilled_tokens for r in reqs] == [len(p)
                                                      for p in s["prompts"]]
        chunks = [-(-len(p) // (CHUNK * BS)) if path != "monolithic" else 1
                  for p in s["prompts"]]
        assert [r.chunks for r in reqs] == chunks
        assert max(chunks) == (1 if path == "monolithic" else 2)

    def test_steps_name_the_requests_that_emitted(self, served):
        _, s = served
        emitted = [rid for st in s["steps"] for rid in st.emitted]
        assert sorted(emitted) == sorted(s["rids"] * 4)
        assert len(emitted) == s["eng"].metrics.snapshot()["engine.tokens"]

    def test_steps_name_the_programs_that_retraced(self, served):
        path, s = served
        retraced = [p for st in s["steps"] for p in st.retraced]
        snap = s["eng"].metrics.snapshot()
        want = {"monolithic": {"prefill", "decode"},
                "chunked": {"prefill_chunk", "decode"},
                "ragged": set()}[path]
        assert set(retraced) == want
        if path == "ragged":
            # each capacity's program is traced with the engine, no step
            assert snap["engine.prefill_chunk_traces"] == len(
                s["eng"].ragged_capacities)
        else:
            assert s["steps"][0].retraced        # the first step compiles
        # each step names a program whose trace counter rose in it
        for name in want:
            assert 1 <= retraced.count(name) <= snap[f"engine.{name}_traces"]
        if path != "ragged":
            assert snap["engine.decode_traces"] == 1
        if path == "monolithic":                 # padded to 384, then 512
            assert retraced.count("prefill") == snap[
                "engine.prefill_traces"] == 2

    def test_no_span_takes_a_harness_span_name(self, served):
        _, s = served
        names = {p.name for st in s["steps"] for p in st.phases}
        names |= {x.name for x in tracing.RECORDER.spans}
        assert not names & HARNESS_SPANS

    def test_chrome_export_has_one_closed_root_per_request(self, served,
                                                           tmp_path):
        _, s = served
        tc = s["collector"]
        summary = tc.summary()
        assert summary["root_spans"] == len(s["rids"]) == s[
            "eng"].metrics.snapshot()["engine.completed"]
        assert summary["open_spans"] == 0
        roots = tc.root_spans()
        assert [e["name"] for e in roots] == [f"request {r}"
                                              for r in s["rids"]]
        children = [e for e in tc.events if e["cat"] == "request.phase"]
        for root in roots:
            mine = [c for c in children if c["tid"] == root["tid"]]
            assert [c["name"] for c in mine] == ["queue", "prefill",
                                                 "decode"]
            for c in mine:
                assert root["ts"] <= c["ts"]
                assert c["ts"] + c["dur"] <= root["ts"] + root["dur"] + 1e-3
        steps = [e for e in tc.events if e["name"] == STEP_SPAN]
        assert len(steps) == len(s["steps"])
        assert all(e["tid"] == TID_ENGINE for e in steps)
        assert [e["args"]["step"] for e in steps] == [st.step
                                                      for st in s["steps"]]
        phases = [e for e in tc.events if e["name"].startswith("engine.")
                  and e["name"] != STEP_SPAN]
        assert len(phases) == sum(len(st.phases) for st in s["steps"])
        with open(tc.save(str(tmp_path / "trace.json"))) as f:
            payload = json.load(f)
        metas = [e for e in payload["traceEvents"] if e["ph"] == "M"]
        assert {m["args"]["name"] for m in metas
                if m.get("tid", -1) >= TID_REQUEST_BASE} == {
            f"request {r}" for r in s["rids"]}
        for e in payload["traceEvents"]:
            if e["ph"] == "X":
                assert e["dur"] >= 0 and e["ts"] >= 0


# ============================================================== the view
def _tiny_engine(**kw):
    params = tfm.init_params(jax.random.PRNGKey(0), TINY, jnp.float32)
    cfg = dict(num_blocks=16, max_batch=2, max_seq_len=256, num_workers=2)
    cfg.update(kw)
    return Engine(TINY, params, config=EngineConfig(**cfg))


def _submit(eng, n, seed=0):
    rng = np.random.RandomState(seed)
    for i in range(n):
        eng.submit(rng.randint(1, TINY.vocab, size=10), max_new_tokens=3,
                   stream=f"s{i % 2}", group_id=(i % 2) + 1)


class TestCollectorView:
    def test_view_is_one_engine_from_its_making_until_detach(self):
        eng, other = _tiny_engine(), _tiny_engine()
        _submit(eng, 1)
        eng.run()                                 # before the view
        tc = TraceCollector(eng)
        _submit(eng, 2, seed=1)
        _submit(other, 2, seed=2)
        other.run()
        eng.run()
        tc.detach()
        _submit(eng, 1, seed=3)
        eng.run()                                 # after the view
        assert tc.summary()["root_spans"] == 2
        assert {s.engine for s in tc.spans()} == {eng.trace_id}
        assert {r.rid for r in tc.requests()} == {2, 3}

    def test_open_requests_are_open_spans(self):
        eng = _tiny_engine(max_batch=1)
        tc = TraceCollector(eng)
        _submit(eng, 3)
        eng.step()
        assert tc.summary()["root_spans"] == 0
        assert sorted(tc.open_spans) == [1, 2, 3]
        eng.run()
        assert not tc.open_spans and tc.summary()["root_spans"] == 3

    def test_fences_nest_inside_the_step_that_paid_them(self):
        """Without FPR every free fences; each ``fpr.fence`` span sits
        inside the emit phase of the step that completed the request,
        carries the fence's reason, size, scope and workers, and holds the
        ``fpr.shard_refresh`` mark of the tables it re-uploaded."""
        eng = _tiny_engine(fpr_enabled=False)
        issued, refreshed = [], []
        eng.bus.subscribe(FenceIssued, issued.append)
        eng.bus.subscribe(ShardRefreshed, refreshed.append)
        tc = TraceCollector(eng)
        _submit(eng, 4)
        snap0 = eng.metrics.snapshot()["fence.fences"]
        eng.run()
        fences = [s for s in tc.spans() if s.name == "fpr.fence"]
        assert len(fences) == eng.metrics.snapshot()["fence.fences"] - snap0
        assert fences
        steps = tc.steps()
        for f in fences:
            assert f.parent == "engine.emit"
            assert any(s.step == f.step and s.start_ns <= f.start_ns
                       and f.start_ns + f.dur_ns <= s.start_ns + s.dur_ns
                       for s in steps)
        assert [f.args for f in fences] == [
            {"reason": e.reason, "n_blocks": e.n_blocks, "scoped": e.scoped,
             "workers": e.workers} for e in issued]
        assert {f.args["reason"] for f in fences} == {"munmap"}
        marks = [s for s in tc.spans() if s.name == "fpr.shard_refresh"]
        assert [m.args for m in marks] == [
            {"reason": e.reason, "shards": e.shards, "entries": e.entries,
             "nbytes": e.nbytes, "full": e.full} for e in refreshed]
        assert marks and all(m.parent == "fpr.fence" and m.dur_ns == 0
                             for m in marks)
        chrome = [e for e in tc.events if e["name"] == "fpr.fence"]
        assert len(chrome) == len(fences)
        assert all(e["cat"] == "fpr" and e["ph"] == "X" for e in chrome)
        assert [e["args"]["n_blocks"] for e in chrome] == [
            e.n_blocks for e in issued]

    def test_reshards_are_marked_on_the_engine_track(self):
        """A reshard between steps is the engine's: its ``fpr.reshard``
        mark (and any fence it pays) lands in the engine's view."""
        eng = _tiny_engine(num_workers=4, max_batch=4)
        changed = []
        eng.bus.subscribe(TopologyChanged, changed.append)
        tc = TraceCollector(eng)
        _submit(eng, 4)
        eng.step()
        eng.resize_workers(2)
        eng.run()
        marks = [s for s in tc.spans() if s.name == "fpr.reshard"]
        (e,) = changed
        assert [m.args for m in marks] == [
            {"old": 4, "new": 2, "moved_slots": e.moved_slots}]
        assert marks[0].step == -1 and marks[0].dur_ns == 0
        chrome = [c for c in tc.events if c["name"] == "fpr.reshard"]
        assert len(chrome) == 1 and chrome[0]["ph"] == "i"
        assert chrome[0]["tid"] == TID_ENGINE
        assert tc.summary()["root_spans"] == 4


# ========================================================== preemption
PREEMPT_BLOCKS = 8


def _preempting_engine(strategy: str):
    """Two low-priority requests run; a high-priority one whose window
    exceeds the free blocks then preempts one of them inside a step."""
    eng = _tiny_engine(num_blocks=PREEMPT_BLOCKS, max_batch=2,
                       max_seq_len=1024, num_workers=4,
                       admission=GovernorConfig(policy="priority",
                                                preempt=strategy))
    tc = TraceCollector(eng)
    rng = np.random.RandomState(5)
    low = [eng.submit(rng.randint(1, TINY.vocab, size=140), max_new_tokens=8,
                      stream=f"s{i}", group_id=i + 1) for i in range(2)]
    eng.step()
    eng.step()
    return eng, tc, low, rng


class TestPreemption:
    def _preempt(self, strategy):
        eng, tc, low, rng = _preempting_engine(strategy)
        reqs = {r.rid: r for r in eng.sched.running.values()}
        before = {rid: (r.trace.admit, r.trace.first_token,
                        r.trace.hit_tokens, r.trace.prefilled_tokens,
                        r.trace.recomputed_tokens, r.trace.chunks)
                  for rid, r in reqs.items()}
        assert sorted(reqs) == low
        assert all(b[1] is not None for b in before.values())
        eng.submit(rng.randint(1, TINY.vocab, size=700), max_new_tokens=8,
                   stream="s9", group_id=9, priority=9)
        eng.step()
        (victim,) = [r for r in reqs.values() if r.trace.preemptions]
        assert victim.trace.preemptions == 1
        marks = [s for s in tc.spans() if s.name == "engine.preempt"]
        assert [(m.rid, m.args, m.dur_ns, m.parent) for m in marks] == [
            (victim.rid, {"rid": victim.rid, "strategy": strategy}, 0,
             "engine.admit")]
        readmit_hits = None
        while not eng.sched.idle:
            eng.step()
            if readmit_hits is None and victim.mapping is not None:
                readmit_hits = victim.mapping.prefix_hits * BS
        r = victim.trace
        assert r.submit <= r.admit <= r.first_token <= r.done
        assert (r.admit, r.first_token) == before[victim.rid][:2]
        assert len(victim.generated) == 8
        assert tc.summary()["root_spans"] == 3
        return victim, before[victim.rid][2:], readmit_hits

    def test_recompute_preemption_keeps_first_times_and_adds_counts(self):
        victim, (hit, prefilled, again, chunks), readmit_hits = (
            self._preempt("recompute"))
        r = victim.trace
        # served again from scratch: a second admission and prefill
        assert r.hit_tokens == hit + readmit_hits
        assert r.prefilled_tokens == prefilled + len(victim.prompt)
        assert r.recomputed_tokens == again + readmit_hits
        assert r.chunks == chunks + 1

    def test_swap_preemption_keeps_first_times_and_counts(self):
        victim, counts, _ = self._preempt("swap")
        r = victim.trace
        # the mapping and tokens survived: no second admission or prefill
        assert (r.hit_tokens, r.prefilled_tokens, r.recomputed_tokens,
                r.chunks) == counts

    def test_rejected_submit_leaves_no_record(self):
        eng = _tiny_engine(num_blocks=4, max_seq_len=1024,
                           admission=GovernorConfig(policy="fcfs"))
        tc = TraceCollector(eng)
        mine = [k for k in tracing.RECORDER.requests if k[0] == eng.trace_id]
        with pytest.raises(CapacityError):
            eng.submit(np.arange(1, 2), max_new_tokens=4 * BS + 1)
        assert [k for k in tracing.RECORDER.requests
                if k[0] == eng.trace_id] == mine
        assert not tc.open_spans and not tc.events
        assert not tracing.RECORDER._open
