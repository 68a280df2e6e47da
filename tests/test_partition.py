"""Hash-partitioned execution: ShardedGFJS vs the monolithic numpy oracle.

The contract (DESIGN.md §15): a plan with ``partitions=k`` produces a
:class:`ShardedGFJS` whose row count, desummarized row *multiset*, and
every SummaryFrame aggregate (including filtered group_by) exactly equal
the monolithic summary's — for every shard-shape edge case the hash can
produce: empty shards, all-rows-one-shard skew, more partitions than
distinct keys.  Device-parallel variants (forced virtual devices) live in
tests/test_dist.py; everything here is the host path.
"""

import itertools
import os
import tempfile

import numpy as np
import pytest

from test_plan import SHAPES, _random_instance, _row_multiset

from repro.core.api import GraphicalJoin
from repro.core.gfjs import ShardedGFJS, desummarize
from repro.core.storage import load_gfjs, save_gfjs
from repro.dist.partition import (PartitionScheme, choose_partition_var,
                                  hash_partition, parallel_desummarize,
                                  partition_counts, partition_encoded)
from repro.relational.encoding import encode_query
from repro.relational.query import JoinQuery
from repro.relational.synth import figure1, lastfm_like
from repro.relational.table import Catalog, Table
from repro.summary.algebra import ShardedSummaryFrame, SummaryFrame
from repro.summary.service import JoinService


def _assert_equal_summaries(gj_mono, g_mono, gj_part, g_part, variables):
    assert isinstance(g_part, ShardedGFJS)
    assert g_part.join_size == g_mono.join_size
    assert sum(g_part.shard_sizes()) == g_part.join_size
    assert list(g_part.column_order) == list(g_mono.column_order)
    all_vars = sorted(variables)
    assert np.array_equal(_row_multiset(gj_part, g_part, all_vars),
                          _row_multiset(gj_mono, g_mono, all_vars))


def _assert_equal_aggregates(g_mono, g_part, var, key):
    """Every frame aggregate, plus a filtered group_by, must match exactly."""
    f0, f1 = SummaryFrame.of(g_mono), SummaryFrame.of(g_part)
    assert isinstance(f1, ShardedSummaryFrame)
    assert f1.count() == f0.count()
    assert f1.sum(var) == f0.sum(var)
    assert f1.mean(var) == f0.mean(var)
    assert f1.min(var) == f0.min(var)
    assert f1.max(var) == f0.max(var)
    assert np.array_equal(f1.distinct(var), f0.distinct(var))
    assert f1.count_distinct(var) == f0.count_distinct(var)
    t0 = f0.group_by(key, n="count", s=("sum", var), avg=("mean", var),
                     lo=("min", var), hi=("max", var))
    t1 = f1.group_by(key, n="count", s=("sum", var), avg=("mean", var),
                     lo=("min", var), hi=("max", var))
    assert set(t0) == set(t1)
    for k in t0:
        assert np.array_equal(np.asarray(t0[k]), np.asarray(t1[k])), k
    # filtered: push a predicate through both frames, re-check
    dom = g_mono.domains[var].values
    if len(dom):
        pred = {var: lambda v: v <= dom[len(dom) // 2]}
        ff0, ff1 = f0.filter(pred), f1.filter(pred)
        assert ff1.count() == ff0.count()
        ft0 = ff0.group_by(key, n="count", s=("sum", var))
        ft1 = ff1.group_by(key, n="count", s=("sum", var))
        for k in ft0:
            assert np.array_equal(np.asarray(ft0[k]), np.asarray(ft1[k])), k


# ---------------------------------------------------------------------------
# partitioned == monolithic on test_plan's random acyclic + cyclic instances
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", ["chain3", "star3", "triangle", "cycle4"])
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("partitions", [2, 4])
def test_partitioned_equals_monolithic_random(shape, seed, partitions):
    cat, query = _random_instance(shape, seed)
    mono = GraphicalJoin(cat, query)
    g0 = mono.run()
    part = GraphicalJoin(cat, query, partitions=partitions)
    g1 = part.run()
    assert part.plan().partitions == partitions
    _assert_equal_summaries(mono, g0, part, g1, query.variables)
    var = sorted(query.variables)[0]
    key = sorted(query.variables)[-1]
    _assert_equal_aggregates(g0, g1, var, key)


@pytest.mark.parametrize("seed", [3, 4])
def test_partitioned_projected_queries(seed):
    """Early projection: partitioning still exact, even when the partition
    variable itself is projected out of the result."""
    cat, query = _random_instance("chain3", seed, output=["A", "D"])
    mono = GraphicalJoin(cat, query)
    g0 = mono.run()
    for pvar in [None, "B", "C"]:          # B, C are projected out
        part = GraphicalJoin(cat, query, partitions=3, partition_var=pvar)
        g1 = part.run()
        if pvar is not None:
            assert part.plan().partition_var == pvar
        assert g1.join_size == g0.join_size
        assert np.array_equal(_row_multiset(part, g1, ["A", "D"]),
                              _row_multiset(mono, g0, ["A", "D"]))


# ---------------------------------------------------------------------------
# shard-merge edge cases
# ---------------------------------------------------------------------------

def _single_key_catalog():
    """Every row joins through one key value: all rows hash to ONE shard."""
    n = 40
    rng = np.random.default_rng(0)
    cat = Catalog.of(
        Table("l", {"k": np.zeros(n, np.int64),
                    "a": rng.integers(0, 5, n).astype(np.int64)}),
        Table("r", {"k": np.zeros(n, np.int64),
                    "b": rng.integers(0, 5, n).astype(np.int64)}),
    )
    q = JoinQuery.of("sk", [("l", {"k": "K", "a": "A"}),
                            ("r", {"k": "K", "b": "B"})])
    return cat, q


def test_all_rows_one_shard_skew():
    cat, q = _single_key_catalog()
    mono = GraphicalJoin(cat, q)
    g0 = mono.run()
    part = GraphicalJoin(cat, q, partitions=4, partition_var="K")
    g1 = part.run()
    sizes = g1.shard_sizes()
    assert sorted(sizes)[:-1] == [0, 0, 0]      # three empty shards
    assert max(sizes) == g0.join_size
    _assert_equal_summaries(mono, g0, part, g1, q.variables)
    _assert_equal_aggregates(g0, g1, "A", "B")


def test_partitions_exceed_distinct_keys():
    cat, query = _random_instance("chain3", 1)   # domains are 2..5 values
    mono = GraphicalJoin(cat, query)
    g0 = mono.run()
    part = GraphicalJoin(cat, query, partitions=8)
    g1 = part.run()
    assert g1.num_partitions == 8
    pvar = part.plan().partition_var
    assert sum(1 for s in g1.shard_sizes() if s == 0) >= \
        8 - g0.domains[pvar].size
    _assert_equal_summaries(mono, g0, part, g1, query.variables)


def test_empty_shard_frames_are_benign():
    """Aggregates over a frame with empty shards never raise or skew."""
    cat, q = _single_key_catalog()
    g1 = GraphicalJoin(cat, q, partitions=4, partition_var="K").run()
    f = SummaryFrame.of(g1)
    assert f.count() == g1.join_size
    empty = f.filter(A=lambda v: v < 0)          # kills every shard
    assert empty.count() == 0
    assert empty.min("A") is None and empty.max("A") is None
    assert len(empty.distinct("A")) == 0
    tab = empty.group_by("B", n="count", s=("sum", "A"), avg=("mean", "A"))
    assert all(len(np.asarray(v)) == 0 for v in tab.values())


def test_empty_join_partitioned():
    """Zero-row base tables: every shard is empty, everything still merges."""
    cat = Catalog.of(
        Table("l", {"k": np.zeros(0, np.int64), "a": np.zeros(0, np.int64)}),
        Table("r", {"k": np.zeros(0, np.int64), "b": np.zeros(0, np.int64)}))
    q = JoinQuery.of("e", [("l", {"k": "K", "a": "A"}),
                           ("r", {"k": "K", "b": "B"})])
    g = GraphicalJoin(cat, q, partitions=3).run()
    assert g.join_size == 0 and g.shard_sizes() == [0, 0, 0]
    assert SummaryFrame.of(g).count() == 0
    out = desummarize(g, decode=False)
    assert all(len(v) == 0 for v in out.values())


# ---------------------------------------------------------------------------
# partition layer unit behavior
# ---------------------------------------------------------------------------

def test_hash_partition_covers_and_is_deterministic():
    codes = np.arange(10_000, dtype=np.int64)
    for k in (2, 3, 7):
        p = hash_partition(codes, k)
        assert p.min() >= 0 and p.max() < k
        assert np.array_equal(p, hash_partition(codes, k))
        # rough balance on a dense code range (multiplicative hash)
        counts = np.bincount(p, minlength=k)
        assert counts.min() > len(codes) // (4 * k)
    assert not np.array_equal(hash_partition(codes, 4),
                              hash_partition(codes, 4, salt=1))
    with pytest.raises(ValueError):
        hash_partition(codes, 0)


def test_partition_encoded_replicates_by_reference():
    cat, q = figure1()
    enc = encode_query(cat, q)
    scheme = PartitionScheme("B", 3)
    shards = partition_encoded(enc, scheme)
    assert len(shards) == 3
    total = partition_counts(enc, scheme)
    assert int(total.sum()) == sum(
        len(c["B"]) for c in enc.encoded_tables if "B" in c)
    for s, enc_s in enumerate(shards):
        for occ, occ_s in zip(enc.encoded_tables, enc_s.encoded_tables):
            if "B" in occ:
                assert np.all(scheme.shard_of(occ_s["B"]) == s)
            else:
                assert occ_s is occ             # replication is by reference
    with pytest.raises(ValueError):
        partition_encoded(enc, PartitionScheme("nope", 2))


def test_choose_partition_var_picks_costliest_step():
    cat, q = figure1()
    enc = encode_query(cat, q)
    from repro.plan.search import plan_query
    logical, plan = plan_query(enc)
    pvar = choose_partition_var(plan.steps, plan.order)
    costliest = max(plan.steps, key=lambda s: s.product_entries)
    assert pvar == costliest.var
    # empty steps: falls back to the root
    assert choose_partition_var((), ("A", "B")) == "B"
    with pytest.raises(ValueError):
        choose_partition_var((), ())


def test_sharded_range_and_row_access():
    """desummarize_range / row_at resolve through the shard-concatenated
    row order (the same order desummarize emits)."""
    from repro.core.gfjs import desummarize_range, row_at
    cat, query = _random_instance("chain3", 6)
    gj = GraphicalJoin(cat, query, partitions=3)
    g = gj.run()
    if g.join_size == 0:
        pytest.skip("degenerate instance")
    full = desummarize(g, decode=False)
    n = g.join_size
    for lo, hi in [(0, n), (0, min(5, n)), (n // 3, 2 * n // 3),
                   (n - 1, n), (2, 2), (n, n + 9)]:
        part = desummarize_range(g, lo, hi, decode=False)
        for v in g.column_order:
            np.testing.assert_array_equal(
                part[v], full[v][max(lo, 0):min(hi, n)])
    for t in {0, n // 2, n - 1}:
        row = row_at(g, t, decode=False)
        assert all(row[v] == int(full[v][t]) for v in g.column_order)
    with pytest.raises(IndexError):
        row_at(g, n)


def test_partition_layer_imports_without_jax():
    """Planning a partitioned query on the CPU must never force the jax
    import (repro.dist resolves its jax-dependent submodules lazily, and
    the platform probe reads JAX_PLATFORMS=cpu without importing jax)."""
    import subprocess
    import sys as _sys
    code = (
        "import sys\n"
        "from repro.relational.synth import figure1\n"
        "from repro.relational.encoding import encode_query\n"
        "from repro.plan.search import plan_query\n"
        "from repro.core.api import GraphicalJoin\n"
        "cat, q = figure1()\n"
        "plan_query(encode_query(cat, q), partitions=4)\n"
        "GraphicalJoin(cat, q, partitions=4).run()\n"
        "assert 'jax' not in sys.modules, 'jax import leaked'\n"
        "print('ok')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    out = subprocess.run([_sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0 and "ok" in out.stdout, out.stderr


def test_parallel_desummarize_matches_serial():
    cat, qs = lastfm_like(n_users=60, n_artists=50, artists_per_user=4,
                          friends_per_user=3)
    q = qs["lastfm_A1"]
    mono = GraphicalJoin(cat, q)
    g0 = mono.run()
    full = mono.desummarize(g0, decode=False)
    par = parallel_desummarize(g0, 5)            # range-sharded GFJS path
    for v in g0.column_order:
        np.testing.assert_array_equal(full[v], par[v])
    g1 = GraphicalJoin(cat, q, partitions=3).run()
    ref = desummarize(g1, decode=False)          # shard-concatenated order
    par2 = parallel_desummarize(g1, 3)
    for v in g1.column_order:
        np.testing.assert_array_equal(ref[v], par2[v])


# ---------------------------------------------------------------------------
# plan identity, explain, and the plan-feedback actuals
# ---------------------------------------------------------------------------

def test_partitions_flow_into_signature_and_explain():
    cat, q = figure1()
    p1 = GraphicalJoin(cat, q).plan()
    p2 = GraphicalJoin(cat, q, partitions=4).plan()
    p3 = GraphicalJoin(cat, q, partitions=2).plan()
    assert p1.partitions == 1 and p1.partition_var is None
    assert p2.partitions == 4 and p2.partition_var in q.variables
    assert len({p1.signature(), p2.signature(), p3.signature()}) == 3
    gj = GraphicalJoin(cat, q, partitions=4)
    gj.run()
    text = gj.explain()
    assert f"partitions        : 4 by hash({gj.plan().partition_var})" in text
    assert "x est)" in text                     # estimate-vs-actual drift
    with pytest.raises(ValueError):
        GraphicalJoin(cat, q, partitions=0).plan()
    with pytest.raises(ValueError):
        GraphicalJoin(cat, q, partitions=2, partition_var="Z").plan()
    # partition_var without partitions would be silently monolithic: refuse
    with pytest.raises(ValueError):
        GraphicalJoin(cat, q, partition_var="B").plan()
    # record_trace (incremental splicing) cannot follow shard structure:
    # refuse up front rather than erroring at capture_state much later
    with pytest.raises(ValueError):
        GraphicalJoin(cat, q, partitions=2, record_trace=True)
    with pytest.raises(ValueError):
        GraphicalJoin(cat, q, plan=GraphicalJoin(cat, q, partitions=2).plan(),
                      record_trace=True)


def test_partitioned_summary_is_memoized():
    """run()/join_size()/aggregate() after a partitioned build reuse the
    merged summary instead of paying the k-shard build again."""
    cat, q = figure1()
    gj = GraphicalJoin(cat, q, partitions=3)
    g1 = gj.run()
    assert gj.run() is g1                     # memoized, not rebuilt
    assert gj.join_size() == g1.join_size
    assert gj.aggregate("count", gfjs=g1) == g1.join_size
    gj.build_model()                          # re-entry clears the memo
    g2 = gj.run()
    assert g2 is not g1 and g2.join_size == g1.join_size


def test_step_actuals_partition_exactly():
    """Summed shard products == monolithic products: the hash split loses
    and duplicates nothing on partitioned steps (replicated steps excepted
    when the partition variable does not reach them)."""
    cat, query = _random_instance("chain3", 2)
    mono = GraphicalJoin(cat, query)
    mono.run()
    part = GraphicalJoin(cat, query, partitions=4)
    part.run()
    pvar = part.plan().partition_var
    mono_act = mono._executor.step_actuals
    part_act = part._executor.step_actuals
    assert set(mono_act) == set(part_act)
    # the partitioned step itself always splits exactly
    assert part_act[pvar] == mono_act[pvar]


def test_monolithic_signature_unchanged_by_partition_fields():
    """partitions=1 plans hash identically to pre-partitioning plans (the
    fields only enter the canon when > 1) — spilled caches stay valid."""
    cat, q = figure1()
    plan = GraphicalJoin(cat, q, elimination_order=["D", "C", "B", "A"]).plan()
    canon_wo = {
        "order": list(plan.order),
        "early_projection": bool(plan.early_projection),
        "backends": dict(sorted(plan.backends.items())),
        "materialize": plan.materialize,
    }
    import hashlib, json
    expect = hashlib.sha256(
        json.dumps(canon_wo, separators=(",", ":")).encode()).hexdigest()[:16]
    assert plan.signature() == expect


# ---------------------------------------------------------------------------
# storage + cache + service
# ---------------------------------------------------------------------------

def test_sharded_storage_roundtrip():
    cat, query = _random_instance("cycle4", 0)
    g = GraphicalJoin(cat, query, partitions=3).run()
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "s.gfjs")
        save_gfjs(g, path)
        back = load_gfjs(path)
    assert isinstance(back, ShardedGFJS)
    assert back.join_size == g.join_size
    assert back.partition_var == g.partition_var
    assert back.shard_sizes() == g.shard_sizes()
    a, b = desummarize(g, decode=False), desummarize(back, decode=False)
    for v in g.column_order:
        np.testing.assert_array_equal(a[v], b[v])


def test_service_partitioned_hits_and_spills_like_monolithic():
    cat, qs = lastfm_like(n_users=60, n_artists=50, artists_per_user=4,
                          friends_per_user=3)
    q1, q2 = qs["lastfm_A1"], qs["lastfm_tri"]
    with tempfile.TemporaryDirectory() as tmp:
        # budget of 1 byte: admitting the second summary evicts (and
        # spills) the first, so the next q1 request promotes from disk
        svc = JoinService(cat, partitions=3, spill_dir=tmp, byte_budget=1)
        r1 = svc.frame(q1)
        assert r1.source == "computed"
        assert isinstance(r1.frame.gfjs, ShardedGFJS)
        assert svc.frame(q1).source == "memory"
        svc.frame(q2)
        r3 = svc.frame(q1)
        assert r3.source == "disk"
        assert isinstance(r3.frame.gfjs, ShardedGFJS)
        assert r3.frame.count() == r1.frame.count()


def test_service_partitioned_append_falls_back_to_rebuild():
    """Appends on partitioned summaries rebuild (no splice-refresh) and
    the rebuilt answers track the live data exactly."""
    cat, qs = lastfm_like(n_users=50, n_artists=40, artists_per_user=3,
                          friends_per_user=2)
    q = qs["lastfm_A1"]
    svc = JoinService(cat, partitions=3)
    before = svc.count(q)
    name = sorted({qt.table for qt in q.tables})[0]
    rows = {c: cat[name][c][:5] for c in cat[name].columns}
    svc.append(name, rows)
    reply = svc.frame(q)
    assert reply.source == "computed"            # rebuilt, never "refreshed"
    assert svc.stats()["refreshed_requests"] == 0
    fresh = JoinService(cat, partitions=1)
    assert reply.frame.count() == fresh.count(q)
    assert svc.count(q) >= before                # appends only grow the join


def test_serve_provider_is_shape_oblivious():
    """RelationalFeatureProvider over a partitioned service == monolithic
    features, warm pulls are cache hits, appends keep it live (rebuild)."""
    from repro.serve.engine import RelationalFeatureProvider
    cat, qs = lastfm_like(n_users=50, n_artists=40, artists_per_user=4,
                          friends_per_user=3)
    q = qs["lastfm_A1"]
    svc_p = JoinService(cat, partitions=3)
    svc_m = JoinService(cat)
    keys = np.asarray([0, 1, 7, 10**9])
    aggs = {"n_rows": "count", "total": ("sum", "A1")}
    prov_p = RelationalFeatureProvider(svc_p, q, key_var="U1", aggs=aggs)
    prov_m = RelationalFeatureProvider(svc_m, q, key_var="U1", aggs=aggs)
    np.testing.assert_array_equal(prov_p.features(keys),
                                  prov_m.features(keys))
    before = svc_p.stats()["misses"]
    prov_p.refresh()
    prov_p.features(keys)
    assert svc_p.stats()["misses"] == before       # warm pull: cache hit
    name = sorted({qt.table for qt in q.tables})[0]
    svc_p.append(name, {c: cat[name][c][:4] for c in cat[name].columns})
    svc_m.append(name, {c: cat[name][c][:4] for c in cat[name].columns})
    np.testing.assert_array_equal(prov_p.features(keys),
                                  prov_m.features(keys))


def test_sharded_frame_to_gfjs_roundtrip():
    cat, query = _random_instance("triangle", 5)
    mono = GraphicalJoin(cat, query)
    g0 = mono.run()
    part = GraphicalJoin(cat, query, partitions=4)
    g1 = part.run()
    var = sorted(query.variables)[0]
    dom = g0.domains[var].values
    if len(dom) == 0:
        pytest.skip("empty instance")
    pred = {var: lambda v: v != dom[0]}
    filt0 = SummaryFrame.of(g0).filter(pred).to_gfjs()
    filt1 = SummaryFrame.of(g1).filter(pred).to_gfjs()
    assert isinstance(filt1, ShardedGFJS)
    assert filt1.join_size == filt0.join_size
    all_vars = sorted(query.variables)
    assert np.array_equal(_row_multiset(mono, filt0, all_vars),
                          _row_multiset(part, filt1, all_vars))


# ---------------------------------------------------------------------------
# Skew-aware partitioning (PR 7): top-key discount + over-partition/fold.
# ---------------------------------------------------------------------------

def test_fold_loads_lpt_balancing():
    from repro.dist.partition import fold_loads
    # fold=1 degenerates: one shard per worker, loads pass through
    np.testing.assert_allclose(sorted(fold_loads([3, 1, 2], 3)), [1, 2, 3])
    # greedy largest-first: 5->w0, 4->w1, 3->w1, 3->w0, 3->w1
    loads = fold_loads([5, 4, 3, 3, 3], 2)
    assert sorted(loads) == [8, 10]
    # more workers than shards: empties allowed
    loads = fold_loads([7], 3)
    assert sorted(loads) == [0, 0, 7]


def test_choose_partition_var_discounts_hot_keys():
    """A big step on a one-hot-key variable loses to a slightly smaller
    step whose key actually splits."""
    from dataclasses import dataclass as _dc

    @_dc
    class _Step:
        var: str
        product_entries: float

    from repro.plan.stats import FactorStats, QueryStats
    hot = np.zeros(16); hot[0] = 1000.0           # all mass on one code
    flat = np.full(16, 10.0)                      # perfectly spread
    stats = QueryStats(
        sizes={"H": 16, "F": 16},
        factors=[],
        factor_stats=[
            FactorStats(("H",), 1000.0, {"H": 1.0}, {"H": hot}),
            FactorStats(("F",), 160.0, {"F": 16.0}, {"F": flat}),
        ])
    steps = [_Step("H", 1000.0), _Step("F", 900.0)]
    # without stats: raw product wins
    assert choose_partition_var(steps, ("H", "F")) == "H"
    # with stats at k=4: H's shardable benefit is 0, F wins
    from repro.dist.partition import choose_partition_var as cpv
    assert cpv(steps, ("H", "F"), stats=stats, partitions=4) == "F"
    # balanced candidates degenerate to the raw-product rule
    stats_flat = QueryStats(
        sizes={"H": 16, "F": 16}, factors=[],
        factor_stats=[
            FactorStats(("H",), 160.0, {"H": 16.0}, {"H": flat.copy()}),
            FactorStats(("F",), 160.0, {"F": 16.0}, {"F": flat.copy()}),
        ])
    assert cpv(steps, ("H", "F"), stats=stats_flat, partitions=4) == "H"


def test_choose_partition_fold_balanced_stays_one():
    from repro.dist.partition import choose_partition_fold
    from repro.plan.stats import FactorStats, QueryStats
    flat = np.full(1024, 5.0)
    stats = QueryStats(
        sizes={"V": 1024}, factors=[],
        factor_stats=[FactorStats(("V",), 5120.0, {"V": 1024.0},
                                  {"V": flat})])
    assert choose_partition_fold(stats, "V", 1) == 1        # monolithic
    assert choose_partition_fold(None, "V", 4) == 1         # no stats
    assert choose_partition_fold(stats, "V", 4) == 1        # balanced
    # no degree vector for the var: unknowable, stay at 1
    assert choose_partition_fold(stats, "W", 4) == 1


def test_choose_partition_fold_smooths_zipf():
    """A Zipf-ish degree vector at k=4: over-partitioning must be chosen
    and must *predict* better folded balance than fold=1."""
    from repro.dist.partition import (choose_partition_fold, fold_loads,
                                      hash_partition)
    from repro.plan.stats import FactorStats, QueryStats
    rng = np.random.default_rng(0)
    deg = (1.0 / np.arange(1, 2049) ** 1.1) * 1e4
    rng.shuffle(deg)
    stats = QueryStats(
        sizes={"V": len(deg)}, factors=[],
        factor_stats=[FactorStats(("V",), float(deg.sum()),
                                  {"V": float(len(deg))}, {"V": deg})])
    k = 4
    f = choose_partition_fold(stats, "V", k)
    codes = np.arange(len(deg))

    def worker_skew(fold):
        pids = hash_partition(codes, k * fold)
        loads = np.bincount(pids, weights=deg, minlength=k * fold)
        w = fold_loads(loads, k)
        return float(w.max() / w.mean())

    assert f > 1
    assert worker_skew(f) <= worker_skew(1) + 1e-9


@pytest.mark.parametrize("shape,seed,fold", [
    ("chain3", 3, 2), ("triangle", 11, 4), ("cycle4", 2, 2),
])
def test_folded_partitions_equal_monolithic(shape, seed, fold):
    """k workers x f virtual shards is still exactly the monolithic
    answer (the fold only changes shard count, never membership)."""
    cat, query = _random_instance(shape, seed)
    all_vars = sorted({v for t in query.tables for _, v in t.var_map})
    mono = GraphicalJoin(cat, query)
    m0 = _row_multiset(mono, mono.run(), all_vars)
    gj = GraphicalJoin(cat, query, partitions=2, partition_fold=fold)
    sharded = gj.run()
    assert sharded.num_partitions == 2 * fold
    np.testing.assert_array_equal(
        m0, _row_multiset(gj, sharded, all_vars))
    rep = gj._executor.shard_report
    assert len(rep["sizes"]) == 2 * fold
    assert rep["workers"] == 2


def test_fold_reports_worker_skew_not_shard_skew():
    """With fold > 1 the reported skew is over folded per-worker loads —
    it can only improve on (never exceed) the raw virtual-shard skew."""
    from repro.dist.partition import fold_loads
    cat, qs = lastfm_like(n_users=60, n_artists=40, artists_per_user=4,
                          friends_per_user=3)
    q = qs["lastfm_tri"]
    gj = GraphicalJoin(cat, q, partitions=2, partition_fold=4)
    gj.run()
    rep = gj._executor.shard_report
    sizes = rep["sizes"]
    w = fold_loads(sizes, 2)
    raw_mean = sum(sizes) / len(sizes)
    raw_skew = max(sizes) / raw_mean if raw_mean > 0 else 1.0
    assert rep["skew"] == pytest.approx(float(w.max() / w.mean()))
    # folded worker skew is bounded by the raw per-shard skew
    assert rep["skew"] <= raw_skew + 1e-9


def test_explain_renders_fold_and_executor():
    cat, q = figure1()
    gj = GraphicalJoin(cat, q, partitions=4, partition_fold=2,
                       shard_executor="process")
    plan = gj.plan()
    text = plan.explain()
    pvar = plan.partition_var
    # the PR 5 substring is untouched (append-only changes to that line)
    assert f"partitions        : 4 by hash({pvar})" in text
    assert "x2 fold (8 virtual)" in text
    assert "executor=process" in text
