"""Tiny sizes of the configurations that ``bench_tiny.TINY`` does not
list, registered before any test here runs: the sizes the TPC-H generator
is tested at in ``test_bench_data.py``."""

from bench.tests.bench_tiny import TINY

TINY.setdefault("tpch_sf1_q10", dict(customers=60, orders=200, parts=90))
