"""JAX engine vs numpy engine equivalence (the engines-agree property)."""

import jax
import numpy as np
import pytest

from repro.core import engine_jax
from repro.core.api import GraphicalJoin
from repro.core.engine_jax import (build_factor_jax, desummarize_jax,
                                   maybe_dense_message, segment_weighted_sum,
                                   weighted_total)
from repro.kernels import ops
from repro.core.potentials import Factor
from repro.relational.synth import figure1, lastfm_like


def test_build_factor_jax_matches_numpy():
    rng = np.random.default_rng(0)
    cols = {"A": rng.integers(0, 40, 5000), "B": rng.integers(0, 60, 5000)}
    sizes = {"A": 40, "B": 60}
    a = build_factor_jax(cols, sizes, interpret=True)
    b = Factor.from_columns(cols, sizes)
    assert a.vars == b.vars
    np.testing.assert_array_equal(a.keys, b.keys)
    np.testing.assert_array_equal(a.bucket, b.bucket)


def test_desummarize_jax_matches_numpy():
    cat, query = figure1()
    gj = GraphicalJoin(cat, query)
    gfjs = gj.run()
    a = desummarize_jax(gfjs, interpret=True)
    b = gj.desummarize(gfjs, decode=False)
    for v in gfjs.column_order:
        np.testing.assert_array_equal(a[v], b[v])


def test_desummarize_jax_larger_query():
    cat, queries = lastfm_like(n_users=150, n_artists=120,
                               artists_per_user=5, friends_per_user=3)
    gj = GraphicalJoin(cat, queries["lastfm_A1"])
    gfjs = gj.run()
    a = desummarize_jax(gfjs, interpret=True)
    b = gj.desummarize(gfjs, decode=False)
    for v in gfjs.column_order:
        np.testing.assert_array_equal(a[v], b[v])


def test_dense_message_path_matches_coo():
    rng = np.random.default_rng(1)
    cols = {"P": rng.integers(0, 30, 2000), "V": rng.integers(0, 20, 2000)}
    sizes = {"P": 30, "V": 20}
    phi = Factor.from_columns(cols, sizes)
    msg = rng.integers(1, 50, 20).astype(np.int64)
    got = maybe_dense_message(phi, "V", msg, interpret=True)
    assert got is not None
    # reference: explicit per-parent contraction
    want = np.zeros(30, np.int64)
    for (p, v), c in zip(phi.keys, phi.bucket):
        want[p] += c * msg[v]
    np.testing.assert_array_equal(got, want)


def test_dense_message_declines_when_off_budget():
    keys = np.asarray([[0, 0]])
    phi = Factor(("P", "V"), keys, np.ones(1, np.int64), np.ones(1, np.int64),
                 (1 << 12, 1 << 12))
    assert maybe_dense_message(phi, "V", np.ones(1 << 12, np.int64),
                               interpret=True) is None


# ---------------------------------------------------------------------------
# exact integer segment sums (the sorted prefix-sum body of _segsum_padded)
# ---------------------------------------------------------------------------

def _sorted_ids(rng, n, num_segments, empty=()):
    """n sorted ids over 0..num_segments-1, every id but ``empty`` used."""
    live = np.setdiff1d(np.arange(num_segments), empty)
    ids = np.concatenate([live, rng.choice(live, n - len(live))])
    return np.sort(ids).astype(np.int32)


def _segsum_case(kind, rng):
    if kind == "empty_segments":
        ids = _sorted_ids(rng, 3000, 40, empty=(0, 1, 17, 38, 39))
        v = rng.integers(-1000, 1000, len(ids)).astype(np.int64)
        return ids, v, rng.integers(0, 50, len(ids)), 40
    if kind == "one_segment":
        v = rng.integers(-1000, 1000, 777).astype(np.int64)
        return np.zeros(777, np.int32), v, rng.integers(0, 9, 777), 1
    if kind in ("at_bucket", "past_bucket"):
        n = ops.next_bucket(2000) + (kind == "past_bucket")
        ids = _sorted_ids(rng, n, 300)
        return ids, rng.integers(-99, 99, n), rng.integers(0, 99, n), 300
    if kind in ("int8", "int32", "int64"):
        dt = np.dtype(kind)
        hi = int(np.iinfo(dt).max)
        ids = _sorted_ids(rng, 1500, 60, empty=(30,))
        v = rng.integers(-hi, hi, len(ids)).astype(dt)
        return ids, v, rng.integers(0, 3, len(ids)).astype(dt), 60
    if kind == "many_blocks":
        # past 512 * 512 entries the prefix sum's row totals are scanned in
        # rows themselves
        n = 300_000
        ids = _sorted_ids(rng, n, 3000, empty=(0, 1500, 2999))
        v = rng.integers(-(1 << 40), 1 << 40, n)
        return ids, v, rng.integers(0, 1 << 10, n), 3000
    if kind == "prefix_wraps":
        # each segment totals just under 2**63: the running prefix passes
        # int64's range twice, every per-segment total still fits
        big = np.int64(1 << 62)
        v = np.array([big, big - 1, big, big - 1, -5, 7, big, big - 1],
                     np.int64)
        ids = np.array([0, 0, 1, 1, 2, 2, 3, 3], np.int32)
        return ids, v, np.ones(len(v), np.int64), 4
    raise AssertionError(kind)


@pytest.mark.parametrize("kind", [
    "empty_segments", "one_segment", "at_bucket", "past_bucket",
    "int8", "int32", "int64", "many_blocks", "prefix_wraps"])
def test_integer_segment_sum_is_exact(kind):
    rng = np.random.default_rng(17)
    ids, v, w, ns = _segsum_case(kind, rng)
    want = np.zeros(ns, np.int64)
    np.add.at(want, ids, v.astype(np.int64) * w.astype(np.int64))
    got = segment_weighted_sum(ids, v, w, ns, interpret=True)
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, want)


def test_float_segment_sum_keeps_the_scatter():
    """Floats give what jax.ops.segment_sum gives on the same padded data,
    bit for bit: a float prefix difference would cancel."""
    rng = np.random.default_rng(3)
    ids = _sorted_ids(rng, 1000, 70, empty=(0, 69))
    v = rng.normal(0, 1e6, len(ids))
    w = rng.integers(0, 20, len(ids))
    n_pad, s_pad = ops.next_bucket(len(ids)), ops.next_bucket(71)
    seg_p = np.full(n_pad, s_pad - 1, np.int32)
    seg_p[:len(ids)] = ids
    prod = np.zeros(n_pad)
    prod[:len(ids)] = v * w
    want = np.asarray(jax.ops.segment_sum(prod, seg_p,
                                          num_segments=s_pad))[:70]
    got = segment_weighted_sum(ids, v, w, 70, interpret=True)
    assert got.dtype == np.float64
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("acc, scatters", [
    ("int64", False), ("float64", True)])
def test_segment_sum_program_name_and_body(acc, scatters):
    """The benchmark's roofline readers time the program by this name; the
    integer body sorts out segments without a scatter, the float body keeps
    one."""
    n = 1024
    seg = jax.ShapeDtypeStruct((n,), np.int32)
    x = jax.ShapeDtypeStruct((n,), np.dtype(acc))
    hlo = engine_jax._segsum_padded.lower(
        seg, x, x, num_segments=512, acc_dtype=np.dtype(acc)).as_text()
    assert "@jit__segsum_padded" in hlo
    assert ("scatter" in hlo) == scatters


def test_integer_segment_sum_runs_in_one_named_program(monkeypatch):
    calls = []
    real = engine_jax._segsum_padded

    def spy(*args, **kw):
        calls.append(kw)
        return real(*args, **kw)

    monkeypatch.setattr(engine_jax, "_segsum_padded", spy)
    ids = np.array([0, 0, 2, 2, 2], np.int32)
    got = segment_weighted_sum(ids, np.arange(5), np.ones(5, np.int64), 3,
                               interpret=True)
    np.testing.assert_array_equal(got, [1, 0, 9])
    assert len(calls) == 1 and calls[0]["acc_dtype"] == np.int64
    assert real.__wrapped__.__name__ == "_segsum_padded"


@pytest.mark.parametrize("n", [1, 513, 4096])
def test_weighted_total_is_exact(n):
    rng = np.random.default_rng(n)
    v = rng.integers(-(1 << 30), 1 << 30, n)
    w = rng.integers(0, 1 << 20, n)
    got = weighted_total(v, w, interpret=True)
    assert got == int((v * w).sum())
