"""Readers' helpers over the program's own spans in a traced run.

A traced run records the program's spans (``repro.obs``) on the tracer
that opens the benchmark's ``bench:window`` span.  ``Run`` does not carry
that tracer, but it is still alive while the readers run, so
:func:`window_spans` finds it among the live objects: the tracer whose
``bench:window`` span holds the run's requests.

A pattern that ends in ``:`` matches every span whose name starts with it
(``"gfjs:emit:"`` matches ``gfjs:emit:0``, ``gfjs:emit:1``...); any other
pattern matches one name exactly.
"""

from __future__ import annotations

import gc
import statistics
from typing import List, Optional, Sequence, Tuple

from bench.trace_reduce import union

WINDOW = "bench:window"


def window_spans(run) -> List[Tuple[str, float, float]]:
    """``(name, t0, t1)`` of the program's spans inside the traced window.

    Empty when the run was not traced or no live tracer holds a
    ``bench:window`` span around the run's requests.
    """
    if run.trace is None or not run.records:
        return []
    from repro.obs.trace import Tracer
    lo, hi = run.records[0]["t0"], run.records[-1]["t1"]
    for obj in gc.get_objects():
        if not isinstance(obj, Tracer):
            continue
        spans = obj.spans
        for w in spans:
            if w.name == WINDOW and w.t0 <= lo and hi <= w.t1:
                return [(s.name, s.t0, s.t1) for s in spans
                        if w.t0 <= s.t0 and s.t1 <= w.t1]
    return []


def _matches(name: str, patterns: Sequence[str]) -> bool:
    return any(name.startswith(p) if p.endswith(":") else name == p
               for p in patterns)


def matching(run, *patterns: str) -> List[Tuple[float, float]]:
    """``(t0, t1)`` of the window's spans that match any of ``patterns``."""
    return [(t0, t1) for name, t0, t1 in window_spans(run)
            if _matches(name, patterns)]


def seconds_per_request(run, *patterns: str) -> Optional[float]:
    """Seconds covered by the matching spans over the window's requests.

    Nested matches (``algebra:mean`` around ``algebra:sum``) count once:
    the time is that of the union of their intervals.  None when no span
    matches.
    """
    found = matching(run, *patterns)
    if not found:
        return None
    return sum(b - a for a, b in union(found)) / len(run.records)


def median_seconds(run, *patterns: str) -> Optional[float]:
    """Median length of the matching spans; None when none matches."""
    found = matching(run, *patterns)
    return statistics.median(b - a for a, b in found) if found else None
