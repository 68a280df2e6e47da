"""Seconds per build the host waits on ``gfjs:sync``: the per-psi read of
the new frontier size in device generation."""

from bench.spans import seconds_per_request


def read(run):
    return seconds_per_request(run, "gfjs:sync")
