"""Relational encode and potentials (the ``build_model`` phase), mean per
build, from each reply's own phase timings."""

from bench.readers import mean_phase


def read(run):
    return mean_phase(run.records, "build_model")
