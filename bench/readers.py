"""Small helpers the metric readers share (``bench/metrics/<name>.py``)."""

from __future__ import annotations

import statistics
from typing import List, Optional


def span_s(records: List[dict]) -> Optional[float]:
    """First request's start to last request's end."""
    if not records:
        return None
    return records[-1]["t1"] - records[0]["t0"]


def mean_phase(records: List[dict], phase: str) -> Optional[float]:
    """Mean of one phase's wall time over the replies that report it."""
    vals = [r["timings"][phase] for r in records
            if phase in r.get("timings", {})]
    return statistics.fmean(vals) if vals else None


def median_of(records: List[dict], key: str, scale: float = 1.0,
              where=lambda r: True) -> Optional[float]:
    vals = [r[key] * scale for r in records if key in r and where(r)]
    return statistics.median(vals) if vals else None


def idle_percent(run) -> Optional[float]:
    """Share of the traced window with no program on the device."""
    if run.trace is None or not run.trace.chips or not run.trace.window_s:
        return None
    return 100.0 * run.trace.idle_share


def level_of(run, var: str) -> dict:
    return next(l for l in run.summary_levels if var in l["vars"])
