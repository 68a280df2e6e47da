"""Percent of the traced window in which no program ran on the device."""

from bench.readers import idle_percent


def read(run):
    return idle_percent(run)
