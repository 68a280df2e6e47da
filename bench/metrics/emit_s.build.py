"""Seconds per build in the ``gfjs:emit:<d>`` spans: each generated
level's slice, device-to-host copy and widening into a LevelSummary (the
wait for the level's device programs is the ``gfjs:wait:<d>`` before it)."""

from bench.spans import seconds_per_request


def read(run):
    return seconds_per_request(run, "gfjs:emit:")
