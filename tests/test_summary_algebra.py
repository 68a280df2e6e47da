"""Summary-side algebra vs the oracle join — the subsystem's ground truth.

Every aggregate the SummaryFrame computes in O(runs) must equal the same
aggregate over the fully materialized (oracle) join result, on randomized
acyclic AND cyclic queries.  Randomization uses plain numpy RNG so these
run in minimal environments (no hypothesis dependency).
"""

import collections

import numpy as np
import pytest

from repro.core.api import GraphicalJoin
from repro.core.gfjs import GFJS, LevelSummary, desummarize
from repro.core.oracle import oracle_join
from repro.relational.query import JoinQuery
from repro.relational.synth import figure1, lastfm_like
from repro.relational.table import Catalog, Table
from repro.summary.algebra import SummaryFrame

SHAPES = {
    "chain3": [("t0", {"x0": "A", "x1": "B"}), ("t1", {"x0": "B", "x1": "C"}),
               ("t2", {"x0": "C", "x1": "D"})],
    "star3": [("t0", {"x0": "M", "x1": "A"}), ("t1", {"x0": "M", "x1": "B"}),
              ("t2", {"x0": "M", "x1": "C"})],
    "selfjoin": [("t0", {"x0": "A", "x1": "B"}), ("t0", {"x0": "B", "x1": "C"})],
    "triangle": [("t0", {"x0": "A", "x1": "B"}), ("t1", {"x0": "B", "x1": "C"}),
                 ("t2", {"x0": "C", "x1": "A"})],
    "cycle4": [("t0", {"x0": "A", "x1": "B"}), ("t1", {"x0": "B", "x1": "C"}),
               ("t2", {"x0": "C", "x1": "D"}), ("t3", {"x0": "D", "x1": "A"})],
}


def random_instance(shape: str, seed: int):
    spec = SHAPES[shape]
    rng = np.random.default_rng(seed)
    domain = int(rng.integers(1, 6))
    cat = Catalog()
    for tname, vm in spec:
        if tname in cat:
            continue
        nrows = int(rng.integers(0, 25))
        cols = {c: rng.integers(0, domain, nrows).astype(np.int64)
                for c in vm.keys()}
        cat.add(Table(tname, cols))
    return cat, JoinQuery.of(shape, spec)


def oracle_raw(gj: GraphicalJoin):
    oc = oracle_join(gj.enc)
    return {v: gj.enc.domains[v].decode(c) for v, c in oc.items()}


CASES = [(s, seed) for s in SHAPES for seed in range(6)]


@pytest.mark.parametrize("shape,seed", CASES)
def test_scalar_aggregates_match_oracle(shape, seed):
    cat, query = random_instance(shape, seed)
    gj = GraphicalJoin(cat, query)
    frame = SummaryFrame.of(gj.run())
    raw = oracle_raw(gj)
    some_var = gj.enc.query.variables[0]
    n = len(raw[some_var])

    assert frame.count() == n
    for v in frame.gfjs.column_order:
        if n == 0:
            assert frame.sum(v) == 0
            assert frame.mean(v) is None
            assert frame.min(v) is None and frame.max(v) is None
            assert frame.count_distinct(v) == 0
        else:
            assert frame.sum(v) == int(raw[v].sum())
            assert frame.mean(v) == pytest.approx(raw[v].mean())
            assert frame.min(v) == raw[v].min()
            assert frame.max(v) == raw[v].max()
            assert frame.count_distinct(v) == len(np.unique(raw[v]))
            assert np.array_equal(frame.distinct(v), np.unique(raw[v]))


@pytest.mark.parametrize("shape,seed", CASES)
def test_group_by_matches_oracle(shape, seed):
    cat, query = random_instance(shape, seed)
    gj = GraphicalJoin(cat, query)
    frame = SummaryFrame.of(gj.run())
    raw = oracle_raw(gj)
    cols = frame.gfjs.column_order
    key, val = cols[0], cols[-1]

    got = frame.group_by(key, n="count", total=("sum", val),
                         lo=("min", val), hi=("max", val), avg=("mean", val))
    cnts = collections.Counter(raw[key])
    sums = collections.defaultdict(int)
    los, his = {}, {}
    for k, x in zip(raw[key], raw[val]):
        sums[k] += x
        los[k] = min(los.get(k, x), x)
        his[k] = max(his.get(k, x), x)
    ks = sorted(cnts)
    assert list(got[key]) == ks
    assert [int(x) for x in got["n"]] == [cnts[k] for k in ks]
    assert [int(x) for x in got["total"]] == [sums[k] for k in ks]
    assert [int(x) for x in got["lo"]] == [los[k] for k in ks]
    assert [int(x) for x in got["hi"]] == [his[k] for k in ks]
    assert np.allclose(got["avg"], [sums[k] / cnts[k] for k in ks])


@pytest.mark.parametrize("shape,seed", CASES)
def test_multi_key_group_by_matches_oracle(shape, seed):
    cat, query = random_instance(shape, seed)
    gj = GraphicalJoin(cat, query)
    frame = SummaryFrame.of(gj.run())
    raw = oracle_raw(gj)
    cols = frame.gfjs.column_order
    if len(cols) < 2:
        pytest.skip("needs two variables")
    k1, k2 = cols[0], cols[1]
    got = frame.group_by([k1, k2], n="count")
    want = collections.Counter(zip(raw[k1], raw[k2]))
    pairs = list(zip(got[k1], got[k2]))
    assert pairs == sorted(want)
    assert {p: int(c) for p, c in zip(pairs, got["n"])} == dict(want)


@pytest.mark.parametrize("shape,seed", CASES)
def test_filter_pushdown_matches_oracle(shape, seed):
    cat, query = random_instance(shape, seed)
    gj = GraphicalJoin(cat, query)
    frame = SummaryFrame.of(gj.run())
    raw = oracle_raw(gj)
    cols = frame.gfjs.column_order
    some_var = cols[0]
    n = len(raw[some_var])

    # equality predicate on the deepest variable, range on the shallowest
    deep_var = cols[-1]
    rng = np.random.default_rng(seed + 1000)
    pivot = int(rng.integers(0, 5))
    filtered = frame.filter({deep_var: pivot}, **{some_var: lambda v: v >= 1})
    mask = np.ones(n, dtype=bool)
    mask &= raw[deep_var] == pivot
    mask &= raw[some_var] >= 1

    assert filtered.count() == int(mask.sum())
    if mask.any():
        mid = cols[len(cols) // 2]
        assert filtered.sum(mid) == int(raw[mid][mask].sum())
        g = filtered.group_by(mid, n="count")
        want = collections.Counter(raw[mid][mask])
        assert {k: int(c) for k, c in zip(g[mid], g["n"])} == dict(want)

    # filters compose: two-step == one-step
    two_step = frame.filter({deep_var: pivot}).filter(
        **{some_var: lambda v: v >= 1})
    assert two_step.count() == filtered.count()

    # the filtered frame re-materializes to exactly the filtered multiset
    flat = desummarize(filtered.to_gfjs())
    assert len(flat[some_var]) == int(mask.sum())
    got_rows = sorted(zip(*(flat[v] for v in cols)))
    want_rows = sorted(zip(*(raw[v][mask] for v in cols)))
    assert got_rows == want_rows


@pytest.mark.parametrize("shape,seed", CASES)
def test_filtered_group_by_matches_oracle(shape, seed):
    """group_by + predicate pushdown COMBINED, against the oracle.

    The separate paths were covered; this closes the gap: every aggregate
    op (count/sum/min/max/mean), multi-key grouping, and a mixed predicate
    set (equality + range callable + membership) applied together.
    """
    cat, query = random_instance(shape, seed)
    gj = GraphicalJoin(cat, query)
    frame = SummaryFrame.of(gj.run())
    raw = oracle_raw(gj)
    cols = frame.gfjs.column_order
    if len(cols) < 3:
        pytest.skip("needs three variables")
    n = len(raw[cols[0]])

    rng = np.random.default_rng(seed + 2000)
    k1, k2 = cols[0], cols[1]
    fvar, val = cols[-1], cols[len(cols) // 2]
    pivot = int(rng.integers(0, 4))
    members = sorted({int(rng.integers(0, 5)) for _ in range(3)})
    preds = {fvar: lambda v: v >= pivot, k2: members}

    got = frame.filter(preds).group_by(
        [k1, k2], n="count", total=("sum", val), lo=("min", val),
        hi=("max", val), avg=("mean", val))

    mask = np.ones(n, dtype=bool)
    mask &= raw[fvar] >= pivot
    mask &= np.isin(raw[k2], members)
    want = collections.defaultdict(lambda: [0, 0, None, None])
    for a, b, x in zip(raw[k1][mask], raw[k2][mask], raw[val][mask]):
        w = want[(a, b)]
        w[0] += 1
        w[1] += x
        w[2] = x if w[2] is None else min(w[2], x)
        w[3] = x if w[3] is None else max(w[3], x)
    ks = sorted(want)
    assert list(zip(got[k1], got[k2])) == ks
    assert [int(x) for x in got["n"]] == [want[k][0] for k in ks]
    assert [int(x) for x in got["total"]] == [want[k][1] for k in ks]
    assert [int(x) for x in got["lo"]] == [want[k][2] for k in ks]
    assert [int(x) for x in got["hi"]] == [want[k][3] for k in ks]
    assert np.allclose(got["avg"],
                       [want[k][1] / want[k][0] for k in ks])

    # the same question asked through aggregate-then-filter composition:
    # grouping over the unfiltered frame restricted by the filter must
    # agree wherever groups survive
    full = frame.group_by([k1, k2], n="count")
    surviving = dict(zip(zip(full[k1], full[k2]),
                         (int(x) for x in full["n"])))
    for k in ks:
        assert want[k][0] <= surviving[k]


@pytest.mark.parametrize("shape,seed", CASES)
def test_filtered_scalar_aggregates_match_oracle(shape, seed):
    """Scalar aggregates under pushed-down predicates, against the oracle."""
    cat, query = random_instance(shape, seed)
    gj = GraphicalJoin(cat, query)
    frame = SummaryFrame.of(gj.run())
    raw = oracle_raw(gj)
    cols = frame.gfjs.column_order
    some, deep = cols[0], cols[-1]
    rng = np.random.default_rng(seed + 3000)
    pivot = int(rng.integers(0, 4))
    filtered = frame.filter({some: lambda v: v != pivot})
    mask = raw[some] != pivot
    assert filtered.count() == int(mask.sum())
    if mask.any():
        assert filtered.sum(deep) == int(raw[deep][mask].sum())
        assert filtered.min(deep) == raw[deep][mask].min()
        assert filtered.max(deep) == raw[deep][mask].max()
        assert filtered.count_distinct(deep) == \
            len(np.unique(raw[deep][mask]))
    else:
        assert filtered.min(deep) is None
        assert filtered.count_distinct(deep) == 0


def test_weights_stay_level_consistent_after_filter():
    cat, qs = lastfm_like(n_users=50, n_artists=40, artists_per_user=4,
                          friends_per_user=3)
    gj = GraphicalJoin(cat, qs["lastfm_A1"])
    frame = SummaryFrame.of(gj.run()).filter(U2=lambda u: u % 3 == 0)
    # every level's weights must sum to the same filtered count
    totals = {int(w.sum()) for w in frame.weights}
    assert totals == {frame.count()}


def _lastfm_summary(kind: str):
    cat, qs = lastfm_like(n_users=50, n_artists=40, artists_per_user=4,
                          friends_per_user=3)
    parts = 3 if kind == "sharded" else 1
    return GraphicalJoin(cat, qs["lastfm_A1"], partitions=parts).run()


def _shard_frames(frame):
    """(frame, summary) per shard; a monolithic frame is its own shard."""
    return [(f, f.gfjs) for f in getattr(frame, "frames", [frame])]


KINDS = ["monolithic", "sharded"]


@pytest.mark.parametrize("kind", KINDS)
def test_unfiltered_weights_share_run_lengths(kind):
    frame = SummaryFrame.of(_lastfm_summary(kind))
    for f, g in _shard_frames(frame):
        for w, lvl in zip(f.weights, g.levels):
            assert w.dtype == np.int64
            assert np.shares_memory(w, lvl.freq)


@pytest.mark.parametrize("kind", KINDS)
def test_unfiltered_weights_are_read_only(kind):
    frame = SummaryFrame.of(_lastfm_summary(kind))
    for f, _ in _shard_frames(frame):
        for w in f.weights:
            with pytest.raises(ValueError):
                w[...] = 0


@pytest.mark.parametrize("kind", KINDS)
def test_filter_leaves_run_lengths_unchanged(kind):
    g = _lastfm_summary(kind)
    shards = getattr(g, "shards", [g])
    before = [lvl.freq.copy() for s in shards for lvl in s.levels]
    filtered = SummaryFrame.of(g).filter(U2=lambda u: u % 3 == 0)
    assert filtered.count() < g.join_size
    after = [lvl.freq for s in shards for lvl in s.levels]
    assert all(np.array_equal(a, b) for a, b in zip(before, after))
    assert SummaryFrame.of(g).count() == g.join_size


def test_narrow_run_lengths_are_widened_to_a_copy():
    g = _lastfm_summary("monolithic")
    narrow = GFJS([LevelSummary(lvl.vars, lvl.key_cols,
                                lvl.freq.astype(np.int32))
                   for lvl in g.levels],
                  list(g.column_order), g.join_size, g.domains)
    frame = SummaryFrame.of(narrow)
    for w, lvl in zip(frame.weights, narrow.levels):
        assert w.dtype == np.int64
        assert not np.shares_memory(w, lvl.freq)
        assert np.array_equal(w, lvl.freq)
    assert frame.count() == g.join_size


def test_string_domains_reject_numeric_aggregates():
    cat, query = figure1()
    gj = GraphicalJoin(cat, query)
    frame = SummaryFrame.of(gj.run())
    with pytest.raises(TypeError):
        frame.sum("A")
    # but counting and membership filters work on strings
    assert frame.count() == 32
    assert frame.filter(A=["a3"]).count() == frame.group_by("A")["count"][-1]


def test_unknown_variable_raises():
    cat, query = figure1()
    frame = SummaryFrame.of(GraphicalJoin(cat, query).run())
    with pytest.raises(KeyError):
        frame.count_distinct("Z")
