"""The program's own records of a run (``repro.core.tracing``), picked to
the run's window by the harness's own times.

The engine keeps, process-wide, one record per ``Engine.step`` call (its
phase spans, on ``time.perf_counter_ns``) and one per request (submit,
admit, first token, done, and its prefill counts).  The harness stamps
its steps with ``time.perf_counter``, the same clock, so each of its
window's steps finds the program's record of the step it drove.  Where
the program keeps no such records, every reader returns None.
"""

from __future__ import annotations

import bisect

#: slack when matching the harness's float seconds to the program's ns
_SLACK_NS = 1000


def recorder():
    """The program's recorder, or None where the program has none."""
    try:
        from repro.core import tracing
    except ImportError:
        return None
    rec = getattr(tracing, "RECORDER", None)
    if rec is None or not hasattr(rec, "steps"):
        return None
    return rec


def window_steps(rec: dict) -> list | None:
    """The program's record of each of the harness's steps in the window
    (``in_window``): the first program step that starts inside the
    harness's ``[t0, t1]``."""
    r = recorder()
    if r is None:
        return None
    steps = sorted(r.steps, key=lambda s: s.start_ns)
    starts = [s.start_ns for s in steps]
    out = []
    for h in rec["steps"]:
        if not h["in_window"]:
            continue
        a, b = h["t0"] * 1e9 - _SLACK_NS, h["t1"] * 1e9 + _SLACK_NS
        i = bisect.bisect_left(starts, a)
        if i < len(steps) and steps[i].start_ns <= b:
            out.append(steps[i])
    return out


def window_requests(rec: dict) -> list | None:
    """The program's records of the requests submitted from the window's
    start on (the earliest due time or step of the harness), by the
    engine that served the window."""
    r = recorder()
    steps = window_steps(rec)
    if r is None or not steps:
        return None
    engine = steps[0].engine
    start = min([q["due"] for q in rec["requests"]]
                + [s["t0"] for s in rec["steps"]]) * 1e9 - _SLACK_NS
    return [q for q in r.requests.values()
            if q.engine == engine and q.submit >= start]


def trace_stop_ns(rec: dict) -> float | None:
    """When the harness stopped the profiler: the end of its last traced
    step (None where no step was traced).  ``stop_trace`` then holds the
    loop for seconds, so a host-clock time that spans it, or follows the
    backlog it leaves, measures the harness and not the program."""
    ends = [s["t1"] for s in rec["steps"] if s.get("traced")]
    return max(ends) * 1e9 if ends else None


def timed_requests(rec: dict) -> list | None:
    """The window's requests whose host-clock times a metric may read:
    those submitted before the profiler stopped (all of them in a run
    with no trace)."""
    reqs = window_requests(rec)
    stop = trace_stop_ns(rec)
    if reqs is None or stop is None:
        return reqs
    return [q for q in reqs if q.submit < stop]


def request_note(rec: dict, reqs: list, used: int) -> str:
    """Sample size of a request metric, for ``rec["notes"]``."""
    return (f"{used} of {len(reqs)} recorded requests "
            f"({len(rec['requests'])} tracked by the harness), "
            f"{sum(q.preemptions > 0 for q in reqs)} preempted")


def timed_note(rec: dict, reqs: list, used: int, since: str,
               until: str) -> str:
    """:func:`request_note` for a metric over :func:`timed_requests`,
    with how many of the times it read span the profiler's stop."""
    stop = trace_stop_ns(rec)
    note = request_note(rec, reqs, used)
    if stop is None:
        return note
    spanning = sum(1 for q in reqs
                   if getattr(q, since) is not None
                   and getattr(q, since) < stop
                   and (getattr(q, until) is None
                        or getattr(q, until) > stop))
    return (f"{note}; submitted before the profiler stopped, "
            f"{spanning} spanning the stop")
