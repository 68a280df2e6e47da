"""The check refuses the control and faults planted under the timed path.

Each run skips the look for a chip and drives the rest of a run at a tiny
size on the CPU, with the program broken underneath (``monkeypatch``):

* a step that returns its state unchanged (a cold build that never builds);
* half of a batch left out (half the groups, half a summary level);
* an answer altered where it is produced (a summary code, a sum, a count).

The control (the reference in the next lower precision) is refused too: at
a tiny size through a whole run where the fingerprint shows it, and at the
full Last.FM size, reference against reference, for the aggregates, whose
tiny sums stay exact in float32.
"""

import numpy as np
import pytest

from bench import harness
from bench.check import compare
from bench.reference import JoinTree
from bench.tests.bench_tiny import tiny_run


def _wrong(out):
    assert out["correct"] is False
    return {k for k, c in out["checks"].items() if c["value"] > c["limit"]}


def test_build_that_never_builds(monkeypatch):
    from repro.summary.service import JoinService
    monkeypatch.setattr(JoinService, "invalidate", lambda self, table: 0)
    assert "builds_not_computed" in _wrong(tiny_run("lastfm_a1.build"))


@pytest.mark.parametrize("cell", ["lastfm_a1.build"])
def test_summary_code_altered(monkeypatch, cell):
    from repro.plan.executor import Executor
    summarize = Executor.summarize

    def altered(self):
        g = summarize(self)
        lvl = g.levels[-1]
        v = lvl.vars[0]
        col = lvl.key_cols[v].copy()
        col[0] = (col[0] + 1) % g.domains[v].size
        lvl.key_cols[v] = col
        return g

    monkeypatch.setattr(Executor, "summarize", altered)
    assert "fingerprint_mismatch" in _wrong(tiny_run(cell))


def test_half_the_summary_left_out(monkeypatch):
    from repro.plan.executor import Executor
    summarize = Executor.summarize

    def half(self):
        g = summarize(self)
        lvl = g.levels[-1]
        keep = len(lvl.freq) // 2
        lvl.freq = lvl.freq[:keep]
        for v in lvl.vars:
            lvl.key_cols[v] = lvl.key_cols[v][:keep]
        return g

    monkeypatch.setattr(Executor, "summarize", half)
    assert {"colsum_gap", "fingerprint_mismatch"} <= _wrong(
        tiny_run("lastfm_a1.build"))


def test_count_halved(monkeypatch):
    from repro.summary.algebra import SummaryFrame
    count = SummaryFrame.count
    monkeypatch.setattr(SummaryFrame, "count", lambda self: count(self) // 2)
    assert "count_gap" in _wrong(tiny_run("lastfm_a1.build"))


def test_sum_altered(monkeypatch):
    from repro.summary.algebra import SummaryFrame
    total = SummaryFrame.sum
    monkeypatch.setattr(SummaryFrame, "sum", lambda self, v: total(self, v) + 1)
    assert "sum_gap" in _wrong(tiny_run("lastfm_a1.agg"))


def test_half_the_groups_left_out(monkeypatch):
    from repro.summary.algebra import SummaryFrame
    group_by = SummaryFrame.group_by

    def half(self, keys, **aggs):
        out = group_by(self, keys, **aggs)
        return {k: v[: len(v) // 2] for k, v in out.items()}

    monkeypatch.setattr(SummaryFrame, "group_by", half)
    assert "group_by_gap" in _wrong(tiny_run("lastfm_a1.agg"))


@pytest.mark.parametrize("cell", ["lastfm_a1.build"])
def test_control_is_refused(cell):
    assert "fingerprint_mismatch" in _wrong(tiny_run(cell, control=True))


def test_control_is_refused_on_full_size_aggregates():
    cfg, gen = harness.load_config("lastfm_hetrec")
    tables = gen.generate(cfg, 2**31 + 29)
    join = JoinTree(tables, cfg["query"])
    key, value = cfg["roles"]["key"], cfg["roles"]["value"]
    asked = [("count", {}), ("sum", {"var": value}),
             ("group_by", {"key": key, "value": value})]
    ops = {op: harness.load_op(op) for op, _ in asked}
    records = [{"answers": [(op, p, ops[op].reference(join, p, np.int64))]}
               for op, p in asked]
    assert all(v == 0 for v in compare(records, ops, join, 1, {}).values())
    gaps = compare(records, ops, join, 1, {}, control=True)
    assert gaps["sum_gap"] > 0 and gaps["group_by_gap"] > 0
    assert np.isfinite(list(gaps.values())).all()
