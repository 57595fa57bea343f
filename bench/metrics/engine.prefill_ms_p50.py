"""Engine: median, over the requests submitted in the window (before the
profiler stopped, in a traced run), of the program's own admit → first
token time: the prefill of a seated request, in ms."""

import numpy as np

import program_records


def compute(rec):
    reqs = program_records.timed_requests(rec)
    if not reqs:
        return None
    ms = [(q.first_token - q.admit) / 1e6 for q in reqs
          if q.admit is not None and q.first_token is not None]
    rec["notes"]["engine.prefill_ms_p50"] = program_records.timed_note(
        rec, reqs, len(ms), "admit", "first_token")
    return float(np.median(ms)) if ms else None
