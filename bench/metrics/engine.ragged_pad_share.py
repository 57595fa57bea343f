"""Model step: of the token rows that the window's ragged steps ran (the
``tokens`` arg of each step's ``engine.dispatch`` span: the capacity of
the program the step ran), the share that no real token filled (its
``real`` arg), in %.  None where the spans carry no such args."""

import program_records


def compute(rec):
    steps = program_records.window_steps(rec)
    if not steps:
        return None
    rows = real = ragged = 0
    for s in steps:
        for p in s.phases or ():
            args = getattr(p, "args", None) or {}
            if p.name == "engine.dispatch" and "tokens" in args:
                rows += args["tokens"]
                real += args["real"]
                ragged += 1
    if not rows:
        return None
    rec["notes"]["engine.ragged_pad_share"] = (
        f"{ragged} ragged steps of {len(steps)} recorded in the window, "
        f"{real} real tokens in {rows} rows")
    return 100.0 * (1.0 - real / rows)
