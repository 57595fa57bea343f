"""The host side of a run, for its ``host:`` line: how fast the host's CPU
ran, and what Python's garbage collector did inside the window.

The served path waits on the host between device programs, so a run on a
slower or busier host reads slower.  :func:`probe_ms` times a fixed piece
of pure Python work before warm-up and after the window; :class:`GcLog`
counts the collections of the window and their pauses.
"""

from __future__ import annotations

import gc
import statistics
import time

#: iterations of the probe's loop (about 15 ms of CPython on the chip's host)
PROBE_LOOP = 200_000
PROBE_REPEATS = 5


def probe_ms() -> float:
    """Median milliseconds of a fixed pure-Python loop."""
    times = []
    for _ in range(PROBE_REPEATS):
        t = time.perf_counter()
        x = 0
        for i in range(PROBE_LOOP):
            x ^= i * i
        times.append((time.perf_counter() - t) * 1e3)
    return statistics.median(times)


class GcLog:
    """Python's collections while installed: (start, generation, seconds)
    on ``time.perf_counter``, the harness's clock."""

    def __init__(self):
        self.events: list = []
        self._start = None

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)

    def _on(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._start = now
        elif self._start is not None:
            self.events.append((self._start, info["generation"],
                                now - self._start))
            self._start = None

    def summary(self, t0: float, t1: float) -> str:
        """Collections per generation that started in ``[t0, t1]``, with
        their summed pause."""
        parts = []
        for g in range(3):
            ev = [d for s, gen, d in self.events if gen == g and t0 <= s <= t1]
            parts.append(f"gen{g} {len(ev)} ({sum(ev) * 1e3:.3f} ms)")
        return ", ".join(parts)
