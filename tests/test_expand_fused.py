"""Fused multi-payload expansion + device-resident GFJS generation.

Interpret-mode parity for `expand_gather_many` against the np.repeat oracle
(empty runs, single-run levels, padding-tail contract, K=1 degeneration,
x64 dtype pinning), level-for-level `generate_gfjs_jax` == `generate_gfjs`
on the random acyclic/cyclic query generator from test_plan, the
generation-backend plumbing, the memoized launch metadata, the on-device
group_by sort, and the O(1) kernel-pick guard of `segment_weighted_sum`.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine_jax  # noqa: F401  (flips jax_enable_x64 on)
from repro.core.api import GraphicalJoin
from repro.core.engine_jax import (_f32_exact_conclusive, desummarize_jax,
                                   generate_gfjs_jax, group_runs_device,
                                   segment_weighted_sum)
from repro.core.gfjs import desummarize, generate_gfjs
from repro.kernels import ops
from repro.kernels.expand import expand_gather
from repro.kernels.expand_fused import expand_gather_many
from repro.plan import Executor

from test_plan import SHAPES, _random_instance


# ---------------------------------------------------------------------------
# expand_gather_many vs the np.repeat oracle (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_runs", [1, 7, 500, 513, 1200])
@pytest.mark.parametrize("k", [1, 2, 5])
def test_expand_many_parity(n_runs, k):
    rng = np.random.default_rng(n_runs * 31 + k)
    freqs = rng.integers(1, 9, n_runs)
    bounds = np.cumsum(freqs).astype(np.int32)
    total = int(bounds[-1])
    payloads = rng.integers(0, 1 << 20, (k, n_runs)).astype(np.int32)
    got = ops.rle_expand_many(payloads, bounds, total, interpret=True)
    want = np.stack([np.repeat(payloads[q], freqs) for q in range(k)])
    np.testing.assert_array_equal(np.asarray(got), want)


def test_expand_many_empty_runs():
    """Zero-length runs (absent parent groups) contribute no output rows."""
    rng = np.random.default_rng(0)
    freqs = rng.integers(0, 4, 600)          # many zero-length runs
    freqs[::7] = 0
    bounds = np.cumsum(freqs).astype(np.int32)
    total = int(bounds[-1])
    payloads = rng.integers(0, 1 << 20, (3, 600)).astype(np.int32)
    got = ops.rle_expand_many(payloads, bounds, total, interpret=True)
    want = np.stack([np.repeat(payloads[q], freqs) for q in range(3)])
    np.testing.assert_array_equal(np.asarray(got), want)


def test_expand_many_single_run_level():
    got = ops.rle_expand_many(np.asarray([[9], [4]], np.int32),
                              np.asarray([6], np.int32), 6, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), [[9] * 6, [4] * 6])


def test_expand_many_padding_tail_contract():
    """Rows [total..t_pad) replicate whatever the saturated run index picks —
    exactly what the per-column kernel produces for the same bounds."""
    rng = np.random.default_rng(3)
    freqs = rng.integers(1, 5, 300)
    bounds = np.cumsum(freqs).astype(np.int32)
    total = int(bounds[-1])
    t_pad = ops.next_bucket(total)
    assert t_pad > total                      # the contract has a tail here
    payloads = rng.integers(0, 1 << 20, (2, 300)).astype(np.int32)
    got = expand_gather_many(jnp.asarray(payloads), jnp.asarray(bounds),
                             t_pad=t_pad, interpret=True)
    assert got.shape == (2, t_pad)
    for q in range(2):
        col = expand_gather(jnp.asarray(payloads[q]), jnp.asarray(bounds),
                            t_pad=t_pad, interpret=True)
        np.testing.assert_array_equal(np.asarray(got[q]), np.asarray(col))


def test_expand_many_k1_degenerates_to_expand_gather():
    rng = np.random.default_rng(4)
    freqs = rng.integers(1, 7, 777)
    bounds = np.cumsum(freqs).astype(np.int32)
    t_pad = ops.next_bucket(int(bounds[-1]))
    payload = rng.integers(0, 1 << 30, 777).astype(np.int32)
    one = expand_gather(jnp.asarray(payload), jnp.asarray(bounds),
                        t_pad=t_pad, interpret=True)
    many = expand_gather_many(jnp.asarray(payload[None]), jnp.asarray(bounds),
                              t_pad=t_pad, interpret=True)
    np.testing.assert_array_equal(np.asarray(many[0]), np.asarray(one))


def test_expand_many_x64_dtype_pinning():
    """Under jax_enable_x64 (flipped by the engine_jax import) the int32
    pins must hold: int64 inputs ride in, int32 comes out, no promotion."""
    freqs = np.asarray([2, 3, 1], np.int64)
    bounds = np.cumsum(freqs)                 # int64 on purpose
    payloads = np.asarray([[5, 6, 7], [1, 2, 3]], np.int64)
    got = ops.rle_expand_many(payloads, bounds, 6, interpret=True)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(got), np.stack([np.repeat(payloads[q], freqs)
                                   for q in range(2)]))


def test_gfjs_expand_meta_is_memoized():
    cat, query = _random_instance("chain3", 0)
    gj = GraphicalJoin(cat, query)
    gfjs = gj.run()
    if gfjs.join_size == 0:
        pytest.skip("degenerate empty instance")
    t_pad = ops.next_bucket(gfjs.join_size)
    m1 = ops.gfjs_expand_meta(gfjs, 0, t_pad)
    m2 = ops.gfjs_expand_meta(gfjs, 0, t_pad)
    assert m1 is m2                          # same tuple, no recompute
    assert 0 in gfjs._launch
    # bounded: a different t_pad replaces rather than accumulates, and the
    # byte-budget accounting sees the cached arrays
    ops.gfjs_expand_meta(gfjs, 0, t_pad * 2)
    assert len(gfjs._launch) == 1 and gfjs._launch[0][0] == t_pad * 2
    assert gfjs.resident_nbytes() == gfjs.nbytes() + gfjs.aux_nbytes()
    assert gfjs.aux_nbytes() > 0


# ---------------------------------------------------------------------------
# generate_gfjs_jax vs the numpy oracle, level for level
# ---------------------------------------------------------------------------

def _assert_gfjs_equal(a, b):
    assert a.join_size == b.join_size
    assert a.column_order == b.column_order
    assert len(a.levels) == len(b.levels)
    for la, lb in zip(a.levels, b.levels):
        assert la.vars == lb.vars
        np.testing.assert_array_equal(la.freq, lb.freq)
        for v in la.vars:
            np.testing.assert_array_equal(la.key_cols[v], lb.key_cols[v])


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_generate_gfjs_jax_parity(shape, seed):
    cat, query = _random_instance(shape, seed)
    gj = GraphicalJoin(cat, query)
    gfjs_np = gj.run()
    gfjs_jax = generate_gfjs_jax(gj.generator, gj.enc.domains,
                                 interpret=True)
    _assert_gfjs_equal(gfjs_np, gfjs_jax)


@pytest.mark.parametrize("seed", [3, 5])
def test_generate_gfjs_jax_parity_projected(seed):
    cat, query = _random_instance("chain3", seed, output=["A", "D"])
    gj = GraphicalJoin(cat, query)
    gfjs_np = gj.run()
    gfjs_jax = generate_gfjs_jax(gj.generator, gj.enc.domains,
                                 interpret=True)
    _assert_gfjs_equal(gfjs_np, gfjs_jax)


def test_generate_gfjs_jax_empty_join():
    """A join that dies mid-generation must emit empty levels, like numpy."""
    from repro.relational.table import Catalog, Table
    from repro.relational.query import JoinQuery
    cat = Catalog.of(
        Table("t0", {"x0": np.asarray([0, 1]), "x1": np.asarray([0, 1])}),
        Table("t1", {"x0": np.asarray([5, 6]), "x1": np.asarray([2, 3])}),
    )
    q = JoinQuery.of("dead", [("t0", {"x0": "A", "x1": "B"}),
                              ("t1", {"x0": "B", "x1": "C"})])
    gj = GraphicalJoin(cat, q)
    gfjs_np = gj.run()
    assert gfjs_np.join_size == 0
    gfjs_jax = generate_gfjs_jax(gj.generator, gj.enc.domains,
                                 interpret=True)
    _assert_gfjs_equal(gfjs_np, gfjs_jax)


def test_generate_gfjs_jax_fallback_is_oracle(monkeypatch):
    """Outside the int32/packing envelope the numpy oracle runs unchanged."""
    monkeypatch.setattr(engine_jax, "jax_generable", lambda gen: False)
    cat, query = _random_instance("triangle", 1)
    gj = GraphicalJoin(cat, query)
    gfjs_np = gj.run()
    gfjs_jax = generate_gfjs_jax(gj.generator, gj.enc.domains)
    _assert_gfjs_equal(gfjs_np, gfjs_jax)


def test_executor_generation_backend_knob():
    cat, query = _random_instance("cycle4", 2)
    ex_np = Executor(cat, query, generation_backend="numpy")
    gfjs_np = ex_np.run()
    ex_jax = Executor(cat, query, generation_backend="jax")
    gfjs_jax = ex_jax.run()
    _assert_gfjs_equal(gfjs_np, gfjs_jax)
    assert ex_jax.plan.backends["summarize"] == "jax"
    assert "summarize=jax" in ex_jax.explain()
    # the knob is execution-relevant, so it must flow into plan identity
    assert ex_np.plan.signature() != ex_jax.plan.signature()


def test_desummarize_jax_fused_matches_numpy():
    cat, query = _random_instance("star3", 1)
    gj = GraphicalJoin(cat, query)
    gfjs = gj.run()
    want = desummarize(gfjs, decode=False)
    got = desummarize_jax(gfjs, decode=False, interpret=True)
    for v in gfjs.column_order:
        np.testing.assert_array_equal(want[v], np.asarray(got[v]))
    assert gfjs._launch                       # meta memoized on the summary


# ---------------------------------------------------------------------------
# on-device group_by sort + O(1) exactness guard
# ---------------------------------------------------------------------------

def test_group_runs_device_matches_host():
    rng = np.random.default_rng(11)
    ranks = rng.integers(0, 400, 6000).astype(np.int64)
    order, seg, starts, ngroups = group_runs_device(ranks)
    horder = np.argsort(ranks, kind="stable")
    sranks = ranks[horder]
    new = np.ones(len(sranks), bool)
    new[1:] = sranks[1:] != sranks[:-1]
    np.testing.assert_array_equal(order, horder)
    np.testing.assert_array_equal(seg, np.cumsum(new) - 1)
    np.testing.assert_array_equal(starts, np.flatnonzero(new))
    assert ngroups == int(new.sum())


def test_group_by_device_path_parity(monkeypatch):
    from repro.summary.algebra import SummaryFrame
    cat, query = _random_instance("chain3", 6)
    gj = GraphicalJoin(cat, query)
    gfjs = gj.run()
    if gfjs.join_size == 0:
        pytest.skip("degenerate empty instance")
    frame = SummaryFrame.of(gfjs)
    host = frame.group_by(["A", "B"], n="count", lo=("min", "D"),
                          s=("sum", "C"))
    monkeypatch.setattr(engine_jax, "GROUP_DEVICE_MIN_RUNS", 0)
    monkeypatch.setattr(engine_jax, "group_device_enabled", lambda: True)
    dev = frame.group_by(["A", "B"], n="count", lo=("min", "D"),
                         s=("sum", "C"))
    assert host.keys() == dev.keys()
    for k in host:
        np.testing.assert_array_equal(host[k], dev[k])


def test_f32_exact_guard_dtype_ranges_are_o1():
    """Narrow dtypes decide without touching the data."""
    v = np.ones(1000, np.int8)
    w = np.ones(1000, np.int8)
    assert _f32_exact_conclusive(v, w, len(v), None)       # 1000*127*127 fits
    big = np.full(10, 2 ** 40, np.int64)
    # wide dtype + no hint -> falls back to the scan, which is conclusive
    assert not _f32_exact_conclusive(big, big, len(big), None)


def test_f32_exact_guard_bound_hint():
    big_dtype_small_values = np.ones(10, np.int64)
    w = np.ones(10, np.int64)
    assert _f32_exact_conclusive(big_dtype_small_values, w, 10, bound=10.0)
    assert not _f32_exact_conclusive(big_dtype_small_values, w, 10,
                                     bound=float(1 << 30))


def test_segment_weighted_sum_bound_does_not_change_results():
    rng = np.random.default_rng(5)
    seg = np.sort(rng.integers(0, 50, 2000)).astype(np.int32)
    _, seg = np.unique(seg, return_inverse=True)
    v = rng.integers(-100, 100, len(seg)).astype(np.int64)
    w = rng.integers(0, 100, len(seg)).astype(np.int64)
    ns = int(seg.max()) + 1
    base = segment_weighted_sum(seg, v, w, ns)
    hinted = segment_weighted_sum(seg, v, w, ns,
                                  bound=float(np.abs(v * w).sum()))
    loose = segment_weighted_sum(seg, v, w, ns, bound=float(1 << 40))
    np.testing.assert_array_equal(base, hinted)
    np.testing.assert_array_equal(base, loose)
