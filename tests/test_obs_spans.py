"""Spans and counters where the host holds the chip idle (DESIGN.md §16).

Under an active tracer: device generation records one ``gfjs:sync`` per
psi, and per emitted level a ``gfjs:wait:<d>`` on its device programs and
a ``gfjs:emit:<d>`` with the bytes it copied; the cache-hit frame records ``server:plan`` and ``service:wrap``;
every public frame call records an ``algebra:<op>`` span, with the
device waits of segment sums and grouped-run sorts beneath it, and a
filter its mask and each level's propagation; the plan phase's pass over
the base rows records ``plan:stats``; device desummarize and the admission
queue record their copy and their wait.
"""

import numpy as np
import pytest

from repro.core import engine_jax
from repro.core.api import GraphicalJoin
from repro.core.engine_jax import desummarize_jax, generate_gfjs_jax
from repro.core.gfjs import desummarize
from repro.obs.metrics import REGISTRY
from repro.obs.trace import Tracer
from repro.relational.synth import figure1, lastfm_like
from repro.serve.server import JoinServer
from repro.summary.algebra import SummaryFrame
from repro.summary.service import JoinService


def _lastfm():
    cat, qs = lastfm_like(n_users=60, n_artists=50, artists_per_user=4,
                          friends_per_user=3, alpha=1.3, seed=5)
    return cat, qs["lastfm_A1"]


def _parent(tracer, sp):
    return next(s for s in tracer.spans if s.span_id == sp.parent_id)


def test_device_generation_records_syncs_and_emits():
    cat, q = _lastfm()
    gj = GraphicalJoin(cat, q)
    want = gj.run()
    gen = gj.generator
    tracer = Tracer()
    with tracer.span("build"):
        got = generate_gfjs_jax(gen, gj.enc.domains, interpret=True)
    assert [lvl.num_runs for lvl in got.levels] == \
        [lvl.num_runs for lvl in want.levels]
    assert got.join_size > 0

    syncs = tracer.find("gfjs:sync")
    psis = [p for level in gen.levels for p in level]
    assert [s.args["child"] for s in syncs] == [p.child for p in psis]
    assert all(_parent(tracer, s).name.startswith("gfjs:level:")
               for s in syncs)

    # per level, the wait on its device programs, then the copy alone
    emits = tracer.find("gfjs:emit")
    waits = tracer.find("gfjs:wait")
    assert [s.name for s in emits] == [
        f"gfjs:emit:{d}" for d in range(len(gen.levels))]
    assert [s.name for s in waits] == [
        f"gfjs:wait:{d}" for d in range(len(gen.levels))]
    for d, (wait, sp) in enumerate(zip(waits, emits)):
        assert wait.t1 <= sp.t0
        assert _parent(tracer, wait).name == "build"
        lvl = got.levels[d + 1]           # level 0 is the root, not copied
        # int32 codes per variable and int64 run lengths, as copied
        nbytes = lvl.num_runs * (4 * len(lvl.vars) + 8)
        assert sp.args == {"runs": lvl.num_runs, "bytes": nbytes}


def test_device_desummarize_records_its_copy():
    cat, q = figure1()
    gfjs = GraphicalJoin(cat, q).run()
    tracer = Tracer()
    with tracer.span("export"):
        got = desummarize_jax(gfjs, interpret=True)
    want = desummarize(gfjs, decode=False)
    for v in gfjs.column_order:
        np.testing.assert_array_equal(got[v], want[v])
    copies = tracer.find("desummarize:d2h")
    assert len(copies) == len(gfjs.levels)
    assert all(_parent(tracer, s).name.startswith("desummarize:level:")
               for s in copies)
    # each level's columns come back as int32 over every row
    assert [s.args["bytes"] for s in copies] == [
        4 * len(lvl.vars) * gfjs.join_size for lvl in gfjs.levels]


def test_cache_hit_frame_records_plan_and_wrap():
    cat, q = _lastfm()
    server = JoinServer(JoinService(cat))
    server.frame(q)                                     # build and cache
    tracer = Tracer()
    with tracer.span("request"):
        reply = server.frame(q)
    assert reply.source == "memory"
    (plan,) = tracer.find("server:plan")
    assert _parent(tracer, plan).name == "server:request"
    (wrap,) = tracer.find("service:wrap")
    assert _parent(tracer, wrap).name == "service:frame"
    # the hit's weights alias the cached run lengths: nothing copied
    assert wrap.args == {"source": "memory",
                         "bytes": 8 * reply.frame.gfjs.num_runs(),
                         "copied": 0}


@pytest.fixture
def device_sort(monkeypatch):
    """Route every group-by through the on-device grouped-run sort."""
    monkeypatch.setattr(engine_jax, "GROUP_DEVICE_MIN_RUNS", 0)
    monkeypatch.setattr(engine_jax, "group_device_enabled", lambda: True)


def test_sum_and_group_by_record_algebra_spans_and_waits(device_sort):
    cat, q = _lastfm()
    gfjs = GraphicalJoin(cat, q).run()
    frame = SummaryFrame.of(gfjs)
    want_sum = frame.sum("A2")
    want_grouped = frame.group_by("U1", n="count", s=("sum", "A2"))

    tracer = Tracer()
    with tracer.span("request"):
        assert frame.sum("A2") == want_sum
        grouped = frame.group_by("U1", n="count", s=("sum", "A2"))
    for k in want_grouped:
        np.testing.assert_array_equal(grouped[k], want_grouped[k])

    (sum_sp,) = tracer.find("algebra:sum")
    (group_sp,) = tracer.find("algebra:group_by")
    assert _parent(tracer, sum_sp).name == "request"
    assert _parent(tracer, group_sp).name == "request"
    waits = {}
    for sp in tracer.find("segsum:wait") + tracer.find("sort:wait"):
        anc = _parent(tracer, sp)
        while not anc.name.startswith("algebra:"):
            anc = _parent(tracer, anc)
        waits.setdefault(anc.name, []).append(sp.name)
    assert waits["algebra:sum"] == ["segsum:wait"]
    assert sorted(waits["algebra:group_by"]) == [
        "segsum:wait", "segsum:wait", "sort:wait"]


def test_sharded_frame_calls_record_algebra_spans():
    cat, q = _lastfm()
    gfjs = GraphicalJoin(cat, q, partitions=2).run()
    frame = SummaryFrame.of(gfjs)
    tracer = Tracer()
    with tracer.span("request"):
        frame.count()
        frame.max("A2")
        frame.filter(U1=[0, 1]).count_distinct("A1")
    outer = [s for s in tracer.spans if s.name.startswith("algebra:")
             and _parent(tracer, s).name == "request"]
    assert sorted(s.name for s in outer) == [
        "algebra:count", "algebra:count_distinct", "algebra:filter",
        "algebra:max"]
    # the shards' own frame calls nest under the sharded frame's span
    (count,) = [s for s in outer if s.name == "algebra:count"]
    inner = [s for s in tracer.find("algebra:count")
             if s.parent_id == count.span_id]
    assert len(inner) == len(gfjs.shards)


def test_admission_queue_records_its_wait():
    cat, q = _lastfm()
    svc = JoinService(cat)
    plan = svc.compile(q)
    server = JoinServer(svc, cost_ceiling=plan.admission_cost() / 2,
                        admission="queue", max_expensive_builds=1)
    waits = REGISTRY.histogram("server.admit_wait_seconds", unit="s")
    n0 = waits.count
    tracer = Tracer()
    with tracer.span("request"):
        assert server.frame(q, plan=plan).source == "computed"
    (admit,) = tracer.find("server:admit")
    assert _parent(tracer, admit).name == "server:request"
    assert waits.count == n0 + 1



def test_statistics_pass_records_its_rows_on_both_plan_branches():
    cat, q = _lastfm()
    base = sum(cat[qt.table].num_rows for qt in q.tables)
    tracer = Tracer()
    with tracer.span("searched"):
        searched = GraphicalJoin(cat, q)
        plan = searched.plan()
    with tracer.span("pinned"):
        pinned = GraphicalJoin(cat, q, plan=plan)
        assert pinned.plan() is plan
    spans = tracer.find("plan:stats")
    assert len(spans) == 2
    for sp, gj, branch in zip(spans, (searched, pinned),
                              ("searched", "pinned")):
        chain, anc = [], sp
        while anc.parent_id is not None:
            anc = _parent(tracer, anc)
            chain.append(anc.name)
        # the search opens the pass inside its own span
        assert chain == (["plan:search"] if branch == "searched" else []) \
            + ["phase:plan", branch]
        factors = gj._executor.logical.stats.factors
        assert sp.args == {"rows": base, "entries": sum(
            f.num_entries for f in factors)}
    # the searched plan kept its degree vectors, the pinned one built none
    assert searched._executor.logical.stats.factor_stats
    assert pinned._executor.logical.stats.factor_stats == []


def test_filter_records_its_mask_and_each_levels_propagation():
    cat, q = _lastfm()
    gfjs = GraphicalJoin(cat, q).run()
    frame = SummaryFrame.of(gfjs)
    cut = int(np.median(gfjs.domains["U1"].values))
    want = frame.filter(U1=lambda v: v < cut).sum("A2")

    tracer = Tracer()
    with tracer.span("request"):
        assert frame.filter() is frame          # no predicate, no work
        assert frame.filter(U1=lambda v: v < cut).sum("A2") == want
    assert len(tracer.find("algebra:filter")) == 2
    (mask,) = tracer.find("filter:mask")
    assert _parent(tracer, mask).name == "algebra:filter"
    assert mask.args == {"runs": gfjs.levels[-1].num_runs}
    props = tracer.find("filter:propagate")
    assert [sp.args for sp in props] == [
        {"level": j, "runs": gfjs.levels[j].num_runs}
        for j in range(len(gfjs.levels) - 1)]
    assert all(_parent(tracer, sp).span_id == mask.parent_id
               for sp in props)
    # each level's segment sum waits on the device inside its own span
    waits = [_parent(tracer, sp) for sp in tracer.find("segsum:wait")]
    assert [sp.span_id for sp in props] == [
        w.span_id for w in waits if w.name == "filter:propagate"]
