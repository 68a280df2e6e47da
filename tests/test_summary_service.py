"""JoinService + SummaryCache: compute-and-reuse as a service."""

import os

import numpy as np
import pytest

from repro.relational.query import JoinQuery
from repro.relational.synth import lastfm_like
from repro.relational.table import Catalog, Table
from repro.summary.cache import SummaryCache, cache_key
from repro.summary.service import JoinService


@pytest.fixture(scope="module")
def lastfm():
    return lastfm_like(n_users=60, n_artists=50, artists_per_user=4,
                       friends_per_user=3)


def test_cache_hit_skips_build_phases(lastfm):
    cat, qs = lastfm
    svc = JoinService(cat)
    first = svc.frame(qs["lastfm_A1"])
    assert first.source == "computed"
    # the miss ran the full pipeline
    assert {"build_model", "build_generator", "summarize"} <= set(first.timings)

    second = svc.frame(qs["lastfm_A1"])
    assert second.cache_hit and second.source == "memory"
    # the hit never touched GraphicalJoin: no build-phase timings at all
    assert "build_model" not in second.timings
    assert "build_generator" not in second.timings
    assert second.frame.count() == first.frame.count()
    st = svc.stats()
    assert st["hits"] == 1 and st["misses"] == 1 and st["puts"] == 1


def test_canonical_fingerprint_shares_cache_entries(lastfm):
    cat, qs = lastfm
    q = qs["lastfm_A1"]
    svc = JoinService(cat)
    svc.frame(q)
    # same join, different display name + table order + var_map insertion order
    permuted = JoinQuery(name="renamed", tables=tuple(reversed(q.tables)),
                         output=None)
    assert permuted.fingerprint() == q.fingerprint()
    assert svc.frame(permuted).cache_hit

    # a different projection is a different summary
    projected = JoinQuery(q.name, q.tables, output=("A1", "A2"))
    assert projected.fingerprint() != q.fingerprint()
    assert not svc.frame(projected).cache_hit


def test_table_version_invalidates(lastfm):
    cat, qs = lastfm
    q = qs["lastfm_A1"]
    cache = SummaryCache()
    JoinService(cat, cache=cache).frame(q)

    # same schema, one row dropped: new content version => cache miss
    ua = cat["user_artists"]
    cat2 = Catalog.of(
        Table("user_artists", {k: v[:-1] for k, v in ua.columns.items()}),
        cat["user_friends"])
    assert cache_key(q, cat2) != cache_key(q, cat)
    reply = JoinService(cat2, cache=cache).frame(q)
    assert reply.source == "computed"


def test_eviction_and_disk_spill(tmp_path, lastfm):
    cat, qs = lastfm
    spill = str(tmp_path / "spill")
    svc = JoinService(cat, byte_budget=1024, spill_dir=spill)
    svc.frame(qs["lastfm_A1"])
    svc.frame(qs["lastfm_B"])        # evicts A1 (budget is tiny)
    st = svc.stats()
    assert st["evictions"] >= 1 and st["spills"] >= 1
    assert len(os.listdir(spill)) >= 1

    reply = svc.frame(qs["lastfm_A1"])   # comes back from disk, not a re-join
    assert reply.source == "disk"
    assert "build_model" not in reply.timings


def test_service_aggregates_match_summary_frame(lastfm):
    cat, qs = lastfm
    q = qs["lastfm_A1"]
    svc = JoinService(cat)
    base = svc.frame(q).frame
    assert svc.count(q) == base.count()
    assert svc.sum(q, "A2") == base.sum("A2")
    assert svc.mean(q, "A2") == base.mean("A2")
    assert svc.min(q, "U1") == base.min("U1")
    assert svc.max(q, "U1") == base.max("U1")
    assert np.array_equal(svc.distinct(q, "A1"), base.distinct("A1"))

    got = svc.group_by(q, "U1", where={"U2": lambda u: u < 10},
                       total=("sum", "A2"))
    want = base.filter(U2=lambda u: u < 10).group_by("U1", total=("sum", "A2"))
    assert np.array_equal(got["U1"], want["U1"])
    assert np.array_equal(got["total"], want["total"])


def test_cache_hits_share_the_cached_run_lengths(lastfm):
    cat, qs = lastfm
    q = qs["lastfm_A1"]
    svc = JoinService(cat)
    svc.frame(q)
    a, b = svc.frame(q), svc.frame(q)
    assert a.source == b.source == "memory"
    assert a.frame.gfjs is b.frame.gfjs
    for wa, wb, lvl in zip(a.frame.weights, b.frame.weights,
                           a.frame.gfjs.levels):
        assert np.shares_memory(wa, wb)
        assert np.shares_memory(wa, lvl.freq)


def test_frame_before_append_keeps_its_total():
    cat, qs = lastfm_like(n_users=40, n_artists=30, artists_per_user=4,
                          friends_per_user=3)
    q = qs["lastfm_A1"]
    svc = JoinService(cat, incremental=True)
    old = svc.frame(q).frame
    old_total = old.gfjs.join_size
    rng = np.random.default_rng(7)
    svc.append("user_friends", {"userID": rng.integers(0, 40, 5),
                                "friendID": rng.integers(0, 40, 5)})
    reply = svc.frame(q)
    assert reply.source == "refreshed"
    assert reply.frame.count() > old_total
    # the old frame's first count and every level still read the old summary
    assert old.count() == old_total
    assert {int(w.sum()) for w in old.weights} == {old_total}


def test_lru_order_and_budget():
    rng = np.random.default_rng(0)
    cat = Catalog.of(
        Table("t0", {"x0": rng.integers(0, 5, 30), "x1": rng.integers(0, 5, 30)}),
        Table("t1", {"x0": rng.integers(0, 5, 30), "x1": rng.integers(0, 5, 30)}),
        Table("t2", {"x0": rng.integers(0, 5, 30), "x1": rng.integers(0, 5, 30)}))
    queries = [
        JoinQuery.of("q01", [("t0", {"x0": "A", "x1": "B"}),
                             ("t1", {"x0": "B", "x1": "C"})]),
        JoinQuery.of("q12", [("t1", {"x0": "A", "x1": "B"}),
                             ("t2", {"x0": "B", "x1": "C"})]),
        JoinQuery.of("q02", [("t0", {"x0": "A", "x1": "B"}),
                             ("t2", {"x0": "B", "x1": "C"})]),
    ]
    svc = JoinService(cat, byte_budget=1)  # at most one resident entry
    for q in queries:
        svc.frame(q)
    st = svc.stats()
    assert st["resident_entries"] == 1
    assert st["evictions"] == 2
    # no spill dir: evicted entries are recomputed on demand
    assert svc.frame(queries[0]).source == "computed"
    assert svc.frame(queries[0]).source == "memory"


def test_aggregate_convenience_on_graphical_join(lastfm):
    from repro.core.api import GraphicalJoin
    cat, qs = lastfm
    gj = GraphicalJoin(cat, qs["lastfm_A1"])
    gfjs = gj.run()
    flat = gj.desummarize(gfjs, decode=True)

    assert gj.aggregate("count", gfjs=gfjs) == len(flat["A1"])
    assert gj.aggregate("sum", "A2", gfjs=gfjs) == int(flat["A2"].sum())
    assert "aggregate" in gj.timings
    g = gj.aggregate("sum", "A2", by=["U1"], gfjs=gfjs)
    want_keys = np.unique(flat["U1"])
    assert np.array_equal(g["U1"], want_keys)
    mask0 = flat["U1"] == want_keys[0]
    assert int(g["sum"][0]) == int(flat["A2"][mask0].sum())
    n1 = gj.aggregate("count", where={"U2": lambda u: u < 10}, gfjs=gfjs)
    assert n1 == int((flat["U2"] < 10).sum())


# ---------------------------------------------------------------------------
# PR 10: message reuse + calibration sidecar at the service layer
# ---------------------------------------------------------------------------

def _chain_catalog(n_facts=2, seed=0):
    rng = np.random.default_rng(seed)
    cat = Catalog.of(
        Table("dim", {"id": np.arange(100),
                      "sub": rng.integers(0, 9, 100)}),
        Table("sub", {"id": np.arange(9), "val": rng.integers(0, 4, 9)}))
    for f in range(n_facts):
        cat.add(Table(f"fact{f}", {"u": rng.integers(0, 7, 400),
                                   "d": rng.integers(0, 100, 400)}))
    return cat


def _chain_query(f):
    return JoinQuery.of(f"cq{f}", [
        (f"fact{f}", {"u": "U", "d": "D"}),
        ("dim", {"id": "D", "sub": "S"}),
        ("sub", {"id": "S", "val": "V"})], output=["U"])


def test_service_shares_messages_across_queries():
    """Two cold queries over the same dimension chain: the second build
    hits the service's message cache (incremental off => untraced)."""
    cat = _chain_catalog()
    svc = JoinService(cat, incremental=False)
    svc.frame(_chain_query(0))
    st0 = svc.stats()
    svc.frame(_chain_query(1))
    st1 = svc.stats()
    assert st1["msgcache_hits"] > st0["msgcache_hits"]
    # truth: an isolated no-reuse service answers the same
    lone = JoinService(Catalog(dict(cat.tables)), incremental=False,
                       message_reuse=False)
    assert svc.count(_chain_query(1)) == lone.count(_chain_query(1))


def test_service_append_drops_dead_messages():
    cat = _chain_catalog()
    svc = JoinService(cat, incremental=False)
    svc.frame(_chain_query(0))
    assert len(svc.message_cache) > 0
    before = svc.stats()["msgcache_invalidations"]
    svc.append("dim", {"id": np.arange(100, 110),
                       "sub": np.zeros(10, np.int64)})
    assert svc.stats()["msgcache_invalidations"] > before
    # and the refreshed catalog still answers correctly
    lone = JoinService(Catalog(dict(svc.catalog.tables)),
                       incremental=False, message_reuse=False)
    assert svc.count(_chain_query(0)) == lone.count(_chain_query(0))


def test_calibration_sidecar_persists_across_services(tmp_path):
    """A computed build writes drift corrections to the spill-dir sidecar;
    a fresh service (new process stand-in) loads them and prices its
    plans with them (explain renders calib(loaded)=)."""
    from repro.core.api import GraphicalJoin
    cat = _chain_catalog()
    svc = JoinService(cat, spill_dir=str(tmp_path))
    assert svc.frame(_chain_query(0)).source == "computed"
    path = os.path.join(str(tmp_path), "calibration.json")
    assert os.path.exists(path)

    svc2 = JoinService(Catalog(dict(cat.tables)), spill_dir=str(tmp_path))
    corr = svc2._load_corrections()
    assert corr and "eliminate" in corr
    gj = GraphicalJoin(cat, _chain_query(0), corrections=corr)
    gj.plan()
    assert "calib(loaded)=" in gj.explain()
    # once this session measures its own drift, the loaded tag yields
    gj.run()
    assert "calib(loaded)=" not in gj.explain()


def test_corrupt_calibration_sidecar_is_ignored(tmp_path):
    path = os.path.join(str(tmp_path), "calibration.json")
    with open(path, "w") as f:
        f.write("{not json")
    cat = _chain_catalog()
    svc = JoinService(cat, spill_dir=str(tmp_path))
    assert svc._load_corrections() is None
    assert svc.frame(_chain_query(0)).source == "computed"
