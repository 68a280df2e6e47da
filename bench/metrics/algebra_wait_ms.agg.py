"""Milliseconds per request the summary algebra waits on the device: the
``segsum:wait`` and ``sort:wait`` spans around its blocking copies."""

from bench.spans import seconds_per_request


def read(run):
    s = seconds_per_request(run, "segsum:wait", "sort:wait")
    return None if s is None else s * 1e3
