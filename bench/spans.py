"""The program's own host spans, as ``bench/trace.py`` reduces them.

``ServeSession`` (``src/repro/serve/session.py``) wraps each phase of a
step's host work in a ``jax.profiler.TraceAnnotation``; the benchmark wraps
the whole step in ``session.step`` (``trace.STEP``). The names are matched
as literal strings: a span renamed in the program reads as missing in the
tests, not as a metric that silently reads nothing.
"""

from __future__ import annotations

from bench import trace as tr

SCHEDULE = "session.schedule"
PREFILL = "session.prefill"
INSTALL = "session.install"
MERGE = "session.merge_demands"
DISPATCH = "session.wave_dispatch"
SYNC = "session.wave_sync"
EMIT = "session.emit"
PHASES = (SCHEDULE, PREFILL, INSTALL, MERGE, DISPATCH, SYNC, EMIT)
WAIT = "wait_arrivals"


def named(r: tr.Reduced, name: str) -> list:
    """The host spans of that name inside the traced window, by start."""
    return sorted(tr.in_window(r, [h for h in r.host if h[0] == name]),
                  key=lambda h: h[1])


def starting_in(spans: list, start: float, end: float) -> list:
    """Of ``spans``, those that start in [start, end)."""
    return [h for h in spans if start <= h[1] < end]
