"""Median ``service:wrap`` span, in ms: ``SummaryFrame.of`` over the
summary the service hands back (a cache hit for every frame here)."""

from bench.spans import median_seconds


def read(run):
    s = median_seconds(run, "service:wrap")
    return None if s is None else s * 1e3
