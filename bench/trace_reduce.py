"""From a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

The device planes (``/device:TPU:<n>``) carry an ``XLA Modules`` line whose
events are executions of jitted programs, named ``jit_<fn>(<hash>)``.  Over
the traced window, marked by the benchmark's ``bench:window`` annotation on
a host line:

* busy time: the union of those events, clipped to the window, per chip,
  averaged over chips; idle is the rest of the window;
* device time per program, hash suffix stripped, averaged over chips;
* the longest idle gaps (on the first chip), each labelled by the innermost
  host span open at its midpoint.  Host spans come from the program's
  tracer (``repro.obs``) on its own clock; the ``bench:window`` span is on
  both clocks and maps one onto the other.

Roofline shares (:func:`roofline_share`) divide the least time the chip
could take for the work by the device time that it took.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

_HASH = re.compile(r"\(\d+\)$")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE = "XLA Modules"


def strip_hash(name: str) -> str:
    return _HASH.sub("", name)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    programs: Dict[str, float]          # stripped name -> device seconds
    gaps: List[List] = field(default_factory=list)   # [label, seconds]
    chips: int = 0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0

    def program_seconds(self, names: Sequence[str]) -> float:
        return sum(self.programs.get(n, 0.0) for n in names)

    def top_programs(self, k: int) -> List[List]:
        top = sorted(self.programs.items(), key=lambda kv: -kv[1])[:k]
        return [[n, s] for n, s in top]


def _events(profile):
    """(device planes' module events, host events) as (name, start, end) ns."""
    devices, host = [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    evs += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
            devices.append(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events]
    return devices, host


def reduce_trace(path: str, *, anchor: str = "bench:window",
                 anchor_t0: Optional[float] = None,
                 spans: Sequence[Tuple[str, float, float]] = (),
                 top: int = 10) -> TraceSummary:
    """Reduce the trace at ``path`` over the ``anchor`` host event.

    ``spans`` are host spans ``(name, t0, t1)`` in seconds on the clock on
    which the anchor span began at ``anchor_t0``; they label idle gaps.
    Without the anchor event the window is the device events' extent.
    """
    from jax.profiler import ProfileData
    devices, host = _events(ProfileData.from_file(path))
    marks = [(a, b) for n, a, b in host if n == anchor]
    if marks:
        lo, hi = marks[0]
    else:
        ends = [(a, b) for evs in devices for _, a, b in evs]
        lo = min((a for a, _ in ends), default=0.0)
        hi = max((b for _, b in ends), default=0.0)
    window = hi - lo
    programs: Dict[str, float] = {}
    busy = []
    merged0: List[Tuple[float, float]] = []
    for i, evs in enumerate(devices):
        clipped = [(n, max(a, lo), min(b, hi)) for n, a, b in evs
                   if b > lo and a < hi]
        for n, a, b in clipped:
            key = strip_hash(n)
            programs[key] = programs.get(key, 0.0) + (b - a) / 1e9
        merged = union((a, b) for _, a, b in clipped)
        busy.append(sum(b - a for a, b in merged))
        if i == 0:
            merged0 = merged
    chips = len(devices)
    if chips:
        programs = {k: v / chips for k, v in programs.items()}
    gaps = idle_gaps(merged0, lo, hi)
    offset = (lo - anchor_t0 * 1e9) if anchor_t0 is not None else None
    labelled = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        labelled.append([label_at((a + b) / 2, spans, offset), (b - a) / 1e9])
    return TraceSummary(window_s=window / 1e9,
                        busy_s=(sum(busy) / chips / 1e9) if chips else 0.0,
                        programs=programs, gaps=labelled, chips=chips)


def idle_gaps(merged: Sequence[Tuple[float, float]], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] that no merged busy interval covers."""
    out, t = [], lo
    for a, b in merged:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label_at(t_ns: float, spans: Sequence[Tuple[str, float, float]],
             offset: Optional[float]) -> str:
    """The innermost (latest-opened) span open at ``t_ns``, else "host"."""
    if offset is None:
        return "host"
    best, best_t0 = "host", None
    for name, t0, t1 in spans:
        a, b = t0 * 1e9 + offset, t1 * 1e9 + offset
        if a <= t_ns <= b and (best_t0 is None or a > best_t0):
            best, best_t0 = name, a
    return best


def roofline_share(seconds: float, peaks: dict, *, nbytes: float = 0.0,
                   flops: float = 0.0) -> Optional[float]:
    """Percent of the chip's roofline: least possible time over ``seconds``.

    None when nothing ran (no device time to divide by).
    """
    if seconds <= 0:
        return None
    least = max(nbytes / peaks["hbm_bytes_per_s"],
                flops / peaks["bf16_flops_per_s"])
    return 100.0 * least / seconds
