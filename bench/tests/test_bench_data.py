"""The benchmark's generators and its plain reference, on the CPU."""

import numpy as np
import pytest

from bench.check import CONTROL_DTYPE, EXACT_DTYPE
from bench.harness import load_config, load_op
from bench.reference import JoinTree, reference_quantities, row_quantities

SMALL = {
    "lastfm_hetrec": dict(users=40, artists=50, user_artists_rows=180,
                          max_artists_per_user=6, friend_pairs=70),
    "tpch_sf1_q10": dict(customers=60, orders=200, parts=90),
}


@pytest.fixture(scope="module")
def lastfm():
    cfg, gen = load_config("lastfm_hetrec")
    return cfg, gen.generate(cfg, 2**31 + 3)


def test_lastfm_has_the_published_counts(lastfm):
    cfg, t = lastfm
    ua, uf = t["user_artists"], t["user_friends"]
    assert len(ua["userID"]) == 92834 and len(uf["userID"]) == 25434
    assert len(np.unique(ua["userID"])) == 1892
    assert len(np.unique(ua["artistID"])) == 17632
    pairs = ua["userID"] * 100_000 + ua["artistID"]
    assert len(np.unique(pairs)) == 92834          # distinct per user
    assert np.bincount(ua["userID"]).max() == 50
    fr = set(zip(uf["userID"].tolist(), uf["friendID"].tolist()))
    assert len(fr) == 25434 and all((b, a) in fr for a, b in fr)
    assert not np.any(uf["userID"] == uf["friendID"])


def test_lastfm_join_stays_under_the_bound(lastfm):
    cfg, t = lastfm
    rows = reference_quantities(JoinTree(t, cfg["query"]), 1).rows
    assert 55_000_000 < rows <= 25434 * 50 ** 2 == 63_585_000
    assert rows < 2 ** 26


def test_lastfm_sizes_do_not_depend_on_the_seed():
    cfg, gen = load_config("lastfm_hetrec", SMALL["lastfm_hetrec"])
    lens = [np.sort(np.bincount(gen.generate(cfg, s)["user_artists"]
                                ["userID"])) for s in (1, 2)]
    assert np.array_equal(*lens)


def test_tpch_follows_dbgen_rules():
    cfg, gen = load_config("tpch_sf1_q10")
    t = gen.generate(cfg, 5)
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    assert len(c["c_custkey"]) == 150_000
    assert len(o["o_orderkey"]) == 1_500_000
    assert len(np.unique(o["o_orderkey"])) == 1_500_000
    assert np.all((o["o_orderkey"] - 1) % 32 < 8)
    assert np.all(o["o_custkey"] % 3 != 0)
    per = np.bincount(np.searchsorted(o["o_orderkey"], li["l_orderkey"]),
                      minlength=1_500_000)
    assert per.min() == 1 and per.max() == 7
    assert np.all(np.isin(li["l_orderkey"], o["o_orderkey"]))
    assert li["l_partkey"].min() >= 1 and li["l_partkey"].max() <= 200_000
    assert len(t["nation"]["n_nationkey"]) == 25


@pytest.mark.parametrize("config", sorted(SMALL))
def test_reference_equals_the_oracle_join(config):
    """At a small scale: the reference's quantities and aggregates equal
    those of the program's brute-force oracle join."""
    from repro.core.oracle import oracle_join
    from repro.relational.encoding import encode_query
    from repro.relational.query import JoinQuery
    from repro.relational.table import Catalog, Table

    cfg, gen = load_config(config, SMALL[config])
    tables = gen.generate(cfg, 11)
    cat = Catalog.of(*(Table(n, c) for n, c in tables.items()))
    q = JoinQuery.of(cfg["query"]["name"], cfg["query"]["tables"])
    enc = encode_query(cat, q)
    rows = {v: enc.domains[v].decode(c) for v, c in oracle_join(enc).items()}
    join = JoinTree(tables, cfg["query"])
    assert row_quantities(rows, join.occs, 99) == reference_quantities(join,
                                                                       99)

    key, value = sorted(rows)[0], sorted(rows)[-1]
    count, total, group_by = (load_op(o) for o in ("count", "sum",
                                                   "group_by"))
    assert count.reference(join, {}, EXACT_DTYPE) == len(rows[key])
    assert total.reference(join, {"var": value}, EXACT_DTYPE) == \
        rows[value].sum()
    keys, inv = np.unique(rows[key], return_inverse=True)
    want = {key: keys, "n": np.bincount(inv),
            "s": np.bincount(inv, weights=rows[value]).astype(np.int64)}
    assert group_by.gap(group_by.reference(
        join, {"key": key, "value": value}, EXACT_DTYPE), want) == 0


def test_control_precision_reads_wrong_at_full_size(lastfm):
    cfg, t = lastfm
    join = JoinTree(t, cfg["query"])
    total, p = load_op("sum"), {"var": "A2"}
    assert total.gap(total.reference(join, p, CONTROL_DTYPE),
                     total.reference(join, p, EXACT_DTYPE)) > 0
    assert (reference_quantities(join, 3, control=True).fingerprint
            != reference_quantities(join, 3).fingerprint)
