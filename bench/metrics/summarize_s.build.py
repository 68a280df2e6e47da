"""GFJS generation on the device (the ``summarize`` phase), mean per
build."""

from bench.readers import mean_phase


def read(run):
    return mean_phase(run.records, "summarize")
