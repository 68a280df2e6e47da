#!/usr/bin/env python3
"""A traced run's device-idle time, split by the program span open.

    python3 bench/run.py --workload lastfm_a1.build --seed 7 --seconds 51 \\
        --trace 1 --trace-dir <dir>
    python3 bench/idle_split.py <dir>

Every span the program opens with ``device=True`` (``repro.obs``) is also a
``TraceAnnotation`` on the profiler's host plane, so the trace alone holds
the spans and the device's programs on one clock.  Over the
``bench:window`` annotation, each instant at which the first chip runs no
program is charged to the innermost (latest-opened) program span open
then, or to "host" when none is; a trailing ``:<digits>`` is stripped from
span names.  The seconds add up to the window's idle time.

Prints one JSON object: ``window_s``, ``idle_s``, ``by_span`` (seconds,
largest first) and ``named_percent``, the share of the idle time under a
leaf span: one that names the work, not a container in ``CONTAINERS``.
"""

from __future__ import annotations

import heapq
import json
import os
import re
import sys
from typing import Dict, List, Sequence, Tuple

if __package__ in (None, ""):       # run as a script: bench/ is a package
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench.trace_reduce import _events, find_xplane, idle_gaps, union  # noqa: E402

WINDOW = "bench:window"
#: spans that hold other spans and name no work of their own
CONTAINERS = ("bench:", "phase:", "server:request", "server:build",
              "service:frame", "gfjs:level", "host")
_INDEX = re.compile(r":\d+$")
# the program's span names (``gfjs:emit:2``, ``server:plan``), not the
# runtime's own host events (``tpu::System::Execute``, ``H2D Dispatch``)
_PROGRAM = re.compile(r"^[a-z]+:[\w.-]+(:[\w.-]+)*$")


def is_program_span(name: str) -> bool:
    return bool(_PROGRAM.match(name))


def is_container(name: str) -> bool:
    return any(name.startswith(c) if c.endswith(":") else name == c
               for c in CONTAINERS)


def split_idle(idle: Sequence[Tuple[float, float]],
               spans: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Seconds of the sorted, disjoint ``idle`` intervals (ns) by span.

    ``spans`` are ``(name, t0, t1)`` in ns on the same clock.  Each idle
    instant goes to the latest-opened span open then, else to "host".
    """
    spans = sorted((a, b, _INDEX.sub("", n)) for n, a, b in spans)
    points = sorted({p for iv in idle for p in iv}
                    | {p for a, b, _ in spans for p in (a, b)})
    out: Dict[str, float] = {}
    open_: List[Tuple[float, float, str]] = []     # (-start, end, name)
    i = j = 0
    for p, q in zip(points, points[1:]):
        while i < len(spans) and spans[i][0] <= p:
            a, b, name = spans[i]
            heapq.heappush(open_, (-a, b, name))
            i += 1
        while open_ and open_[0][1] <= p:
            heapq.heappop(open_)
        while j < len(idle) and idle[j][1] <= p:
            j += 1
        if j < len(idle) and idle[j][0] <= p:
            name = open_[0][2] if open_ else "host"
            out[name] = out.get(name, 0.0) + (q - p) / 1e9
    return out


def idle_by_span(path: str, anchor: str = WINDOW) -> Tuple[float, Dict]:
    """(window seconds, idle seconds by span) of the trace at ``path``."""
    from jax.profiler import ProfileData
    devices, host = _events(ProfileData.from_file(path))
    marks = [(a, b) for n, a, b in host if n == anchor]
    if not devices or not marks:
        raise ValueError(f"{path}: no device plane or no {anchor!r} event")
    lo, hi = marks[0]
    busy = union((max(a, lo), min(b, hi)) for _, a, b in devices[0]
                 if b > lo and a < hi)
    spans = [(n, a, b) for n, a, b in host if is_program_span(n)]
    return (hi - lo) / 1e9, split_idle(idle_gaps(busy, lo, hi), spans)


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = args[0] if args[0].endswith(".pb") else find_xplane(args[0])
    window, split = idle_by_span(path)
    idle = sum(split.values())
    named = sum(s for n, s in split.items() if not is_container(n))
    print(json.dumps({
        "window_s": window, "idle_s": idle,
        "named_percent": 100.0 * named / idle if idle else None,
        "by_span": dict(sorted(split.items(), key=lambda kv: -kv[1]))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
