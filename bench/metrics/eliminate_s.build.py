"""Host elimination (the ``build_generator`` phase), mean per build."""

from bench.readers import mean_phase


def read(run):
    return mean_phase(run.records, "build_generator")
