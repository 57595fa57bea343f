"""In-program spans and per-request records, on the profiler's clock.

One process-wide recorder (:data:`RECORDER`), like the profiler itself,
so its records outlive the engine that wrote them:

  * :func:`span` is a context manager and :func:`mark` an instant event.
    Each enters a ``jax.profiler.TraceAnnotation`` of its name and args —
    while a profiler trace runs, the span sits on the trace's host plane
    on the same clock as the device's operations; with none running it
    costs next to nothing — and lands, once closed, in a bounded ring of
    :class:`Span` records timed with ``time.perf_counter_ns`` (the clock a
    client of the engine stamps its own steps with).  A span's times
    include the recorder's own work at its entry and exit.
  * :func:`step` wraps one ``Engine.step`` as the span :data:`STEP_SPAN`
    and keeps a :class:`StepRecord`: its times, where its phases (the
    spans closed inside it) lie in the ring, the ``rid`` of every request
    that emitted a token in it, and the jitted programs whose trace
    counters rose in it.  :func:`acting` attributes spans written outside
    a step (a reshard's fences, say) to an engine.
  * :meth:`Recorder.request` keeps one :class:`RequestRecord` per request:
    submit → admit (seated in a slot) → first token → done, with its
    prompt, prefix-hit, prefilled and recomputed token counts, chunks and
    preemptions.

Recording is always on; nothing is configured.  Memory is bounded:
:data:`SPAN_CAPACITY` spans, :data:`STEP_CAPACITY` step records and
:data:`REQUEST_CAPACITY` request records, each dropping its oldest.  A
step record whose phases the span ring has dropped reads no phases.  The
engine is single-threaded, and so is the recorder.

:class:`TraceCollector` is a view over the recorder: the steps, spans and
request records one engine wrote after the collector was made, exported
as Chrome trace-event JSON (``chrome://tracing`` / Perfetto
``ui.perfetto.dev``) with one root span per request.
"""

from __future__ import annotations

import itertools
import json
import time
from collections import OrderedDict, deque
from typing import NamedTuple

#: ring sizes: spans (a benchmark window and its drain, ~2,600 steps of
#: at most ~33 spans, fit with room), whole steps, requests; full, they
#: hold about 90 MB
SPAN_CAPACITY = 1 << 17
STEP_CAPACITY = 1 << 16
REQUEST_CAPACITY = 1 << 14
#: the whole-step span's name
STEP_SPAN = "engine.iteration"

#: Chrome-trace track (tid) of the engine's spans; request root spans get
#: ``TID_REQUEST_BASE + rid`` so every request is its own row
TID_ENGINE = 0
TID_REQUEST_BASE = 1000

now_ns = time.perf_counter_ns

_annotation = None


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use so the
    control plane stays importable without JAX."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


class Span:
    """One span: a context manager while open, then its own record in the
    ring (``dur_ns == 0``: an instant :func:`mark`)."""

    __slots__ = ("name", "args", "start_ns", "dur_ns", "step", "rid",
                 "parent", "engine", "_ann")

    def __init__(self, name: str, args: dict | None = None):
        self.name, self.args = name, args or None
        self.dur_ns = 0

    def _place(self, rec: "Recorder") -> None:
        self.start_ns = now_ns()
        self.step, self.engine = rec.step, rec.engine
        self.rid = self.args.get("rid") if self.args else None
        self.parent = rec._open[-1] if rec._open else None

    def __enter__(self):
        rec = RECORDER
        self._place(rec)
        rec._open.append(self.name)
        ann = _annotation or _trace_annotation()
        self._ann = ann(self.name, **self.args) if self.args else ann(
            self.name)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        rec = RECORDER
        self._ann.__exit__(*exc)
        self._ann = None
        rec._open.pop()
        rec._add(self)
        self.dur_ns = now_ns() - self.start_ns
        return False


class StepRecord(NamedTuple):
    """One ``Engine.step`` call; its phases are the ``count`` spans the
    recorder wrote from its ``first`` on."""
    recorder: "Recorder"
    engine: int
    step: int
    start_ns: int
    dur_ns: int
    first: int
    count: int
    emitted: tuple         # rids that emitted a token in the step
    retraced: tuple        # jitted programs traced in the step

    @property
    def phases(self) -> list | None:
        """The spans closed inside the step, in order (None once the span
        ring has dropped them)."""
        return self.recorder.span_range(self.first, self.first + self.count)


class RequestRecord:
    """One request's lifecycle, in ``perf_counter_ns`` times (None: not
    reached yet).  A request preempted and served again keeps its first
    admit and first-token times and adds up its counts."""

    __slots__ = ("engine", "rid", "submit", "admit", "first_token", "done",
                 "prompt_tokens", "hit_tokens", "prefilled_tokens",
                 "recomputed_tokens", "chunks", "preemptions")

    def __init__(self, engine: int, rid: int, prompt_tokens: int,
                 submit: int):
        self.engine, self.rid = engine, rid
        self.submit, self.admit = submit, None
        self.first_token = self.done = None
        self.prompt_tokens = prompt_tokens
        self.hit_tokens = 0          # prefix-hit tokens at each admission
        self.prefilled_tokens = 0    # prompt tokens prefill computed
        self.recomputed_tokens = 0   # ... of them already held by a hit
        self.chunks = 0
        self.preemptions = 0

    def admitted(self, hit_tokens: int) -> None:
        """Seated with a fresh mapping that attached ``hit_tokens`` tokens
        of cached prefix."""
        if self.admit is None:
            self.admit = now_ns()
        self.hit_tokens += hit_tokens

    def prefilled(self, start: int, end: int, hit_end: int) -> None:
        """One prefill program computed prompt tokens ``[start, end)``;
        the mapping's prefix hits held ``[0, hit_end)`` already."""
        self.chunks += 1
        self.prefilled_tokens += end - start
        self.recomputed_tokens += max(0, min(end, hit_end) - start)

    def token(self) -> None:
        if self.first_token is None:
            self.first_token = now_ns()
        RECORDER.emitted(self.rid)

    def completed(self) -> None:
        self.done = now_ns()

    def preempted(self) -> None:
        self.preemptions += 1

    def as_dict(self) -> dict:
        return {k: getattr(self, k) for k in self.__slots__}


class _StepScope:
    __slots__ = ("engine", "step", "programs", "_ann", "_t0", "_first",
                 "_counts", "_saved")

    def __init__(self, engine: int, step: int, programs):
        self.engine, self.step, self.programs = engine, step, programs

    def __enter__(self):
        rec = RECORDER
        self._saved = (rec.engine, rec.step, rec._emitted)
        rec.engine, rec.step, rec._emitted = self.engine, self.step, []
        self._counts = self.programs()
        self._ann = (_annotation or _trace_annotation())(STEP_SPAN)
        self._ann.__enter__()
        rec._open.append(STEP_SPAN)
        self._first = rec.written
        self._t0 = now_ns()
        return self

    def __exit__(self, *exc):
        t1 = now_ns()
        rec = RECORDER
        rec._open.pop()
        retraced = tuple(name for (name, n), (_, n0)
                         in zip(self.programs(), self._counts) if n != n0)
        rec.steps.append(StepRecord(
            rec, self.engine, self.step, self._t0, t1 - self._t0,
            self._first, rec.written - self._first, tuple(rec._emitted),
            retraced))
        rec.engine, rec.step, rec._emitted = self._saved
        self._ann.__exit__(*exc)
        return False


class _Acting:
    __slots__ = ("engine", "_saved")

    def __init__(self, engine: int):
        self.engine = engine

    def __enter__(self):
        rec = RECORDER
        self._saved, rec.engine = rec.engine, self.engine
        return self

    def __exit__(self, *exc):
        RECORDER.engine = self._saved
        return False


class Recorder:
    """Bounded rings of spans, steps and request records."""

    def __init__(self, span_capacity: int = SPAN_CAPACITY,
                 step_capacity: int = STEP_CAPACITY,
                 request_capacity: int = REQUEST_CAPACITY):
        self._ring: list = [None] * span_capacity
        self.written = 0                    # spans ever closed
        self.steps: deque = deque(maxlen=step_capacity)
        self.requests: OrderedDict = OrderedDict()    # (engine, rid) → rec
        self.request_capacity = request_capacity
        self.engine, self.step = 0, -1
        self._open: list = []               # names of open spans
        self._emitted = None
        self._engine_ids = itertools.count(1)

    def _add(self, span: Span) -> None:
        self._ring[self.written % len(self._ring)] = span
        self.written += 1

    def span_range(self, first: int, stop: int) -> list | None:
        """The ``first``-th to ``stop``-th spans ever closed (None once
        the ring has dropped the first of them)."""
        cap = len(self._ring)
        if first < self.written - cap:
            return None
        return [self._ring[i % cap] for i in range(first, stop)]

    @property
    def spans(self) -> list:
        """Every span the ring holds, oldest first."""
        return self.span_range(max(0, self.written - len(self._ring)),
                               self.written)

    def new_engine(self) -> int:
        """A fresh id for an engine's records."""
        return next(self._engine_ids)

    def request(self, engine: int, rid: int, prompt_tokens: int,
                submit: int) -> RequestRecord:
        """Open the record of a request submitted at ``submit`` (ns)."""
        r = RequestRecord(engine, rid, prompt_tokens, submit)
        self.requests[(engine, rid)] = r
        if len(self.requests) > self.request_capacity:
            self.requests.popitem(last=False)
        return r

    def emitted(self, rid: int) -> None:
        if self._emitted is not None:
            self._emitted.append(rid)


#: the process's recorder
RECORDER = Recorder()


def span(name: str, **args) -> Span:
    """Context manager: one span named ``name``; ``rid=`` ties it to a
    request, other ``args`` ride along (also into the profiler)."""
    return Span(name, args)


def mark(name: str, **args) -> None:
    """An instant event (a span of zero duration)."""
    rec = RECORDER
    s = Span(name, args)
    s._place(rec)
    ann = _annotation or _trace_annotation()
    with (ann(name, **args) if args else ann(name)):
        rec._add(s)


def step(engine: int, index: int, programs) -> _StepScope:
    """Context manager around one engine step (see :class:`StepRecord`);
    ``programs()`` gives ``(name, times traced)`` of each jitted program."""
    return _StepScope(engine, index, programs)


def acting(engine: int) -> _Acting:
    """Context manager: spans written inside it, outside any step, belong
    to ``engine``."""
    return _Acting(engine)


class TraceCollector:
    """The records one engine writes from now on, exported as Chrome
    trace-event JSON.

    Steps and spans land on the engine track (``TID_ENGINE``), or a span
    on its request's track when it carries a ``rid``; each request is one
    root span from submit to done, with ``queue`` / ``prefill`` /
    ``decode`` children.  :meth:`detach` ends the view.
    """

    def __init__(self, engine, *, pid: int = 1):
        self.engine = engine.trace_id
        self.pid = pid
        self._t0 = now_ns()
        self._t1 = None

    def detach(self) -> None:
        """Stop the view here: later records are left out."""
        self._t1 = now_ns()

    def _mine(self, engine: int, t: int) -> bool:
        return (engine == self.engine and t >= self._t0
                and (self._t1 is None or t <= self._t1))

    def steps(self) -> list:
        return [s for s in RECORDER.steps if self._mine(s.engine, s.start_ns)]

    def spans(self) -> list:
        return [s for s in RECORDER.spans
                if self._mine(s.engine, s.start_ns)]

    def requests(self) -> list:
        return [r for r in RECORDER.requests.values()
                if self._mine(r.engine, r.submit)]

    def _us(self, t: int) -> float:
        return (t - self._t0) / 1e3

    @property
    def events(self) -> list:
        """Every trace event of the view (no metadata)."""
        out = [{"name": STEP_SPAN, "cat": "engine", "ph": "X",
                "ts": self._us(s.start_ns), "dur": s.dur_ns / 1e3,
                "pid": self.pid, "tid": TID_ENGINE,
                "args": {"step": s.step, "emitted": len(s.emitted),
                         "retraced": list(s.retraced)}}
               for s in self.steps()]
        for s in self.spans():
            e = {"name": s.name, "cat": s.name.split(".")[0],
                 "ts": self._us(s.start_ns), "pid": self.pid,
                 "tid": (TID_ENGINE if s.rid is None
                         else TID_REQUEST_BASE + s.rid),
                 "args": {"step": s.step, **(s.args or {})}}
            if s.dur_ns:
                e.update(ph="X", dur=s.dur_ns / 1e3)
            else:
                e.update(ph="i", s="t")
            out.append(e)
        for r in self.requests():
            if r.done is None:
                continue
            tid = TID_REQUEST_BASE + r.rid
            out.append({"name": f"request {r.rid}", "cat": "request",
                        "ph": "X", "ts": self._us(r.submit),
                        "dur": (r.done - r.submit) / 1e3, "pid": self.pid,
                        "tid": tid, "args": r.as_dict()})
            bounds = (("queue", r.submit, r.admit),
                      ("prefill", r.admit, r.first_token),
                      ("decode", r.first_token, r.done))
            for name, a, b in bounds:
                if a is not None and b is not None:
                    out.append({"name": name, "cat": "request.phase",
                                "ph": "X", "ts": self._us(a),
                                "dur": (b - a) / 1e3, "pid": self.pid,
                                "tid": tid, "args": {"rid": r.rid}})
        return out

    def root_spans(self) -> list:
        """The closed per-request root spans, in submission order."""
        return sorted((e for e in self.events if e["cat"] == "request"),
                      key=lambda e: e["ts"])

    @property
    def open_spans(self) -> dict:
        """rid → record of a request submitted but not done."""
        return {r.rid: r for r in self.requests() if r.done is None}

    def summary(self) -> dict:
        """Counts for an artifact: events, root and open spans, and
        events by category."""
        events = self.events
        by_cat: dict = {}
        for e in events:
            by_cat[e["cat"]] = by_cat.get(e["cat"], 0) + 1
        return {"events": len(events),
                "root_spans": by_cat.get("request", 0),
                "open_spans": len(self.open_spans), "by_cat": by_cat}

    def chrome_trace(self) -> dict:
        """The Chrome trace-event JSON payload (metadata + events)."""
        events = sorted(self.events, key=lambda e: e["ts"])
        meta = [{"name": "process_name", "ph": "M", "pid": self.pid,
                 "args": {"name": "repro-fpr engine"}},
                {"name": "thread_name", "ph": "M", "pid": self.pid,
                 "tid": TID_ENGINE, "args": {"name": "engine"}}]
        tids = sorted({e["tid"] for e in events} - {TID_ENGINE})
        meta += [{"name": "thread_name", "ph": "M", "pid": self.pid,
                  "tid": tid,
                  "args": {"name": f"request {tid - TID_REQUEST_BASE}"}}
                 for tid in tids]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f, indent=1)
        return path


__all__ = ["RECORDER", "REQUEST_CAPACITY", "Recorder", "RequestRecord",
           "SPAN_CAPACITY", "STEP_CAPACITY", "STEP_SPAN", "Span",
           "StepRecord", "TID_ENGINE", "TID_REQUEST_BASE", "TraceCollector",
           "acting", "mark", "now_ns", "span", "step"]
