"""The reference beyond paths, and the checks found by name.

The reference joins any acyclic query by message passing over a join tree;
a cyclic one is refused unless its configuration gives its own reference.
Operations carry their own checks, so ``bench/check.py`` compares an
operation it has never seen.
"""

import types

import numpy as np
import pytest

from bench import harness
from bench.check import compare, limits
from bench.reference import (JoinTree, join_tree, occurrences,
                             quantity_gaps, reference_quantities,
                             row_quantities)

SHAPES = {
    "star": [("r", {"a": "A", "b": "B"}), ("s", {"a": "A", "c": "C"}),
             ("t", {"a": "A", "d": "D"})],
    "two_variable_link": [("r", {"a": "A", "b": "B", "c": "C"}),
                          ("s", {"a": "A", "b": "B", "d": "D"}),
                          ("t", {"d": "D", "e": "E"})],
    "branching": [("r", {"a": "A", "b": "B"}), ("s", {"b": "B", "c": "C"}),
                  ("t", {"b": "B", "d": "D"}), ("u", {"d": "D", "e": "E"})],
}


def _tables(shape, seed):
    rng = np.random.default_rng(seed)
    out = {}
    for table, cols in SHAPES[shape]:
        rows = np.unique(rng.integers(0, 4, (40, len(cols))), axis=0)
        out[table] = {c: rows[:, i] for i, c in enumerate(cols)}
    return out


def _oracle_rows(tables, query):
    from repro.core.oracle import oracle_join
    from repro.relational.encoding import encode_query
    from repro.relational.query import JoinQuery
    from repro.relational.table import Catalog, Table

    cat = Catalog.of(*(Table(n, c) for n, c in tables.items()))
    enc = encode_query(cat, JoinQuery.of(query["name"], query["tables"]))
    return {v: enc.domains[v].decode(c) for v, c in oracle_join(enc).items()}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_join_tree_equals_the_oracle_join(shape):
    query = {"name": shape, "tables": SHAPES[shape]}
    tables = _tables(shape, 5)
    rows = _oracle_rows(tables, query)
    join = JoinTree(tables, query)
    assert reference_quantities(join, 7) == row_quantities(rows, join.occs, 7)
    grouped = harness.load_op("group_by")
    key, value = sorted(rows)[1], sorted(rows)[-1]
    keys, inv = np.unique(rows[key], return_inverse=True)
    want = {key: keys, "n": np.bincount(inv),
            "s": np.bincount(inv, weights=rows[value]).astype(np.int64)}
    assert grouped.gap(grouped.reference(
        join, {"key": key, "value": value}, np.int64), want) == 0


def test_join_tree_links_the_variables_it_shares():
    occs = occurrences({"tables": SHAPES["branching"]})
    assert sorted(join_tree(occs)) == [(0, 1), (0, 2), (2, 3)]


@pytest.mark.parametrize("tables, why", [
    ([("r", {"a": "A", "b": "B"}), ("s", {"b": "B", "c": "C"}),
      ("t", {"c": "C", "a": "A"})], "cyclic"),
    ([("r", {"a": "A"}), ("s", {"b": "B"})], "not connected"),
    ([("r", {"a": "A", "b": "A"})], "twice"),
])
def test_queries_without_a_join_tree_are_refused(tables, why):
    with pytest.raises(ValueError, match=why):
        JoinTree({}, {"tables": tables})


def test_a_configuration_can_give_its_own_reference():
    own = object()
    gen = types.SimpleNamespace(reference=lambda tables, cfg: own)
    assert harness.reference_for({}, gen, {}) is own
    plain = harness.reference_for({"query": {"tables": SHAPES["star"]}},
                                  types.SimpleNamespace(),
                                  _tables("star", 1))
    assert isinstance(plain, JoinTree)


def test_an_operation_carries_its_own_check():
    """An operation ``check.py`` never names is compared by its own file."""
    op = types.SimpleNamespace(
        LIMITS={"median_gap": 0, "tail_gap": 3},
        reference=lambda join, params, dtype: dtype(params["x"]),
        gap=lambda got, want: abs(int(got) - int(want)),
        check=lambda join, records, kept, salt, control: {"tail_gap": kept})
    records = [{"answers": [("median", {"x": 5}, 7)]},
               {"answers": [("median", {"x": 2}, 2)]}, {"error": "boom"}]
    ops = {"median": op}
    gaps = compare(records, ops, None, 0, {"median": 4})
    assert gaps == {"failed_requests": 1, "median_gap": 2, "tail_gap": 4}
    assert compare(records, ops, None, 0, {"median": 1},
                   control=True)["median_gap"] == 0
    assert limits(ops) == {"failed_requests": 0, "median_gap": 0,
                           "tail_gap": 3}


def test_a_ragged_result_reads_as_wrong():
    query = {"name": "star", "tables": SHAPES["star"]}
    join = JoinTree(_tables("star", 3), query)
    rows = {v: np.arange(6) for v in "ABCD"}
    rows["D"] = rows["D"][:3]
    got = row_quantities(rows, join.occs, 1)
    assert got.rows == 6 and got.fingerprint == -1
    assert quantity_gaps(got, reference_quantities(join, 1))[
        "fingerprint_mismatch"] == 1
