"""Prefix cache: of the prompt tokens that a prefix hit already held when
a request of the window was admitted (``hit_tokens``), the share that its
prefill programs computed again (``recomputed_tokens``), in %."""

import program_records


def compute(rec):
    reqs = program_records.window_requests(rec)
    if not reqs:
        return None
    hit = [q for q in reqs if q.hit_tokens]
    held = sum(q.hit_tokens for q in hit)
    again = sum(q.recomputed_tokens for q in hit)
    rec["notes"]["prefix.recomputed_share"] = (
        program_records.request_note(rec, reqs, len(hit))
        + f"; {again} of {held} hit tokens computed again, "
        f"{sum(q.prompt_tokens for q in reqs)} prompt tokens")
    return 100.0 * again / held if held else None
