"""Admission: 75th percentile, over the requests submitted in the window
(before the profiler stopped, in a traced run), of the program's own
submit → admit (seated in a slot) time, in ms."""

import numpy as np

import program_records


def compute(rec):
    reqs = program_records.timed_requests(rec)
    if not reqs:
        return None
    ms = [(q.admit - q.submit) / 1e6 for q in reqs if q.admit is not None]
    rec["notes"]["admission.wait_ms_p75"] = program_records.timed_note(
        rec, reqs, len(ms), "submit", "admit")
    return float(np.percentile(ms, 75)) if ms else None
