"""Seconds per build in the program's ``plan:stats`` spans: the plan
phase's pass over the base rows (potentials and, for a searched plan, the
degree vectors), every pass of the build summed (the served path plans the
query, then the build's own plan phase builds the potentials again)."""

from bench.spans import seconds_per_request


def read(run):
    return seconds_per_request(run, "plan:stats")
