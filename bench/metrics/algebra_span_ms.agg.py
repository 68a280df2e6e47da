"""Milliseconds per request in the program's ``algebra:<op>`` spans: the
in-program twin of ``algebra_ms.agg``, and a mean for the same reason."""

from bench.spans import seconds_per_request


def read(run):
    s = seconds_per_request(run, "algebra:")
    return None if s is None else s * 1e3
