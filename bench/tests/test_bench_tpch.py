"""TPC-H Q10's join (``tpch_sf1_q10``) through the served path at a tiny
size on the CPU, against the plain reference: the join size, the sum of the
join variable C (``o_custkey`` = ``c_custkey``) and the row fingerprint of
the built summary; and the check refusing the control and a planted fault."""

import numpy as np
import pytest

from bench import harness
from bench.reference import (JoinTree, quantity_gaps, reference_quantities,
                             row_quantities)
from bench.tests.bench_tiny import TINY, tiny_run


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_served_build_agrees_with_the_reference(seed):
    from repro.relational.query import JoinQuery
    from repro.relational.table import Catalog, Table
    from repro.serve.server import JoinServer
    from repro.summary.service import JoinService

    cfg, gen = harness.load_config("tpch_sf1_q10", TINY["tpch_sf1_q10"])
    tables = gen.generate(cfg, seed)
    cat = Catalog.of(*(Table(n, c) for n, c in tables.items()))
    query = JoinQuery.of(cfg["query"]["name"], cfg["query"]["tables"])
    reply = JoinServer(JoinService(cat, incremental=False)).frame(query)
    assert reply.source == "computed"
    join = JoinTree(tables, cfg["query"])

    frame = reply.frame
    rows = int(join.total(join.ones(), np.int64))
    assert rows == len(tables["lineitem"]["l_orderkey"])  # a FK chain
    assert frame.count() == rows
    assert frame.sum("C") == int(join.total(join.value_weights("C"),
                                            np.int64))
    # no redundancy: the deepest level holds a run per distinct lineitem
    # (order, part) pair, and a pair repeats only where a part does
    li = tables["lineitem"]
    pairs = np.unique(np.stack([li["l_orderkey"], li["l_partkey"]]), axis=1)
    assert frame.gfjs.levels[-1].num_runs == pairs.shape[1]

    expand = harness.load_op("frame").expand_summary
    got = row_quantities(expand(frame.gfjs), join.occs, salt=seed)
    want = reference_quantities(join, salt=seed)
    assert quantity_gaps(got, want) == {
        "rows_gap": 0, "colsum_gap": 0, "fingerprint_mismatch": 0}


def test_control_is_refused():
    out = tiny_run("tpch_q10.build", control=True)
    assert out["correct"] is False
    assert out["checks"]["fingerprint_mismatch"]["value"] == 1


def test_summary_code_altered_is_refused(monkeypatch):
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
    out = tiny_run("tpch_q10.build")
    assert out["checks"]["fingerprint_mismatch"]["value"] == 1
