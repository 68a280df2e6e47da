"""Milliseconds per request in the program's ``filter:mask`` span: the
filter's predicates on the decoded values, the mask's gather to the
deepest level and the masked deepest weights, all on the host."""

from bench.spans import seconds_per_request


def read(run):
    s = seconds_per_request(run, "filter:mask")
    return None if s is None else s * 1e3
