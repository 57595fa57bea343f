"""Engine: median, over the window's steps, of the program's whole-step
span minus its ``engine.fetch`` span(s): the host time of a step outside
its wait on the device, in ms."""

import numpy as np

import program_records


def compute(rec):
    steps = program_records.window_steps(rec)
    if not steps:
        return None
    ms = []
    for s in steps:
        phases = s.phases              # None once the span ring dropped them
        if phases is not None:
            ms.append((s.dur_ns - sum(p.dur_ns for p in phases
                                      if p.name == "engine.fetch")) / 1e6)
    rec["notes"]["engine.host_ms_p50"] = (
        f"{len(ms)} steps of {sum(h['in_window'] for h in rec['steps'])} "
        f"in the window")
    return float(np.median(ms)) if ms else None
