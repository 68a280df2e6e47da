"""Milliseconds per request in the program's ``filter:propagate`` spans,
one per upper level: the ancestor expansion and the segment sum of the
filtered weights (its device wait, ``segsum:wait``, included)."""

from bench.spans import seconds_per_request


def read(run):
    s = seconds_per_request(run, "filter:propagate")
    return None if s is None else s * 1e3
