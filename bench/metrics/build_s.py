"""Seconds per cold build: the window's span over its request count.

Every request started before the deadline runs to its end and counts."""

from bench.readers import span_s


def read(run):
    span = span_s(run.records)
    return None if span is None else span / len(run.records)
