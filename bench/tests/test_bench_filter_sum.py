"""The filtered sum (``bench/ops/filter_sum.py``): its reference against
the program's brute-force oracle join, and its check against the control
and faults planted under the timed path."""

import numpy as np
import pytest

from bench import harness
from bench.check import compare
from bench.reference import JoinTree
from bench.tests.bench_tiny import TINY, tiny_run

OP = harness.load_op("filter_sum")


def _wrong(out):
    assert out["correct"] is False
    return {k for k, c in out["checks"].items() if c["value"] > c["limit"]}


@pytest.fixture(scope="module")
def tiny():
    cfg, gen = harness.load_config("lastfm_hetrec", TINY["lastfm_hetrec"])
    return cfg, gen.generate(cfg, 2**31 + 5)


@pytest.mark.parametrize("share", [0.1, 0.5, 1.0])
@pytest.mark.parametrize("summed", ["value", "count", "U1", "U2", "A1"])
def test_reference_equals_the_oracle_join(tiny, share, summed):
    from repro.core.oracle import oracle_join
    from repro.relational.encoding import encode_query
    from repro.relational.query import JoinQuery
    from repro.relational.table import Catalog, Table

    cfg, tables = tiny
    key = cfg["roles"]["key"]
    value = {"value": cfg["roles"]["value"], "count": None}.get(summed,
                                                                summed)
    cat = Catalog.of(*(Table(n, c) for n, c in tables.items()))
    enc = encode_query(cat, JoinQuery.of(cfg["query"]["name"],
                                         cfg["query"]["tables"]))
    rows = oracle_join(enc)
    k = enc.domains[key].decode(rows[key])
    v = np.ones(len(k), np.int64) if value is None \
        else enc.domains[value].decode(rows[value])
    values = OP.key_values(cfg, tables, key)
    assert np.array_equal(values, enc.domains[key].values)
    width = max(1, int(share * len(values)))
    join = JoinTree(tables, cfg["query"])
    for lo in values[: len(values) - width + 1]:
        params = {"key": key, "value": value, "lo": int(lo),
                  "hi": int(lo) + width}
        band = (k >= params["lo"]) & (k < params["hi"])
        want = int(v[band].sum(dtype=np.int64))
        assert OP.reference(join, params, np.int64) == want


def test_mask_that_drops_one_user_is_refused(monkeypatch):
    from repro.summary import algebra
    evaluate = algebra._eval_predicate

    def drop_first(pred, values):
        mask = evaluate(pred, values).copy()
        mask[np.argmax(mask)] = False
        return mask

    monkeypatch.setattr(algebra, "_eval_predicate", drop_first)
    assert _wrong(tiny_run("lastfm_a1.filter")) == {"filter_sum_gap"}


def test_sum_with_one_run_altered_is_refused(monkeypatch):
    from repro.summary.algebra import SummaryFrame
    propagate = SummaryFrame._with_deep_weights

    def altered(self, deep_w):
        deep_w = deep_w.copy()
        deep_w[np.argmax(deep_w)] += 1
        return propagate(self, deep_w)

    monkeypatch.setattr(SummaryFrame, "_with_deep_weights", altered)
    assert _wrong(tiny_run("lastfm_a1.filter")) == {"filter_sum_gap"}


@pytest.mark.parametrize("level", [0, 1, 2])
@pytest.mark.parametrize("fault", ["add", "move"])
def test_propagated_level_with_one_segment_altered_is_refused(
        monkeypatch, level, fault):
    """A fault in one upper level's segment sum inside
    ``_with_deep_weights``: one more row in the heaviest segment, or one
    row moved from it to the next segment (the level's total kept)."""
    from repro.core import engine_jax
    from repro.summary.algebra import SummaryFrame
    segsum, propagate = (engine_jax.segment_weighted_sum,
                         SummaryFrame._with_deep_weights)
    calls = []

    def counted(self, deep_w):
        calls.clear()              # this filter's level sums, in order
        return propagate(self, deep_w)

    def altered(*args, **kw):
        out = segsum(*args, **kw)
        calls.append(len(out))
        if len(calls) == level + 1:
            out = np.array(out, copy=True)
            i = int(np.argmax(out))
            out[i] += 1
            if fault == "move":
                out[(i + 1) % len(out)] -= 1
        return out

    monkeypatch.setattr(SummaryFrame, "_with_deep_weights", counted)
    monkeypatch.setattr(engine_jax, "segment_weighted_sum", altered)
    assert _wrong(tiny_run("lastfm_a1.filter")) == {"filter_sum_gap"}
    assert len(calls) > level


def test_control_is_refused_on_full_size_filtered_sums():
    """Tiny sums stay exact in float32; at the full Last.FM size the
    reference one precision lower reads wrong, reference against
    reference."""
    cfg, gen = harness.load_config("lastfm_hetrec")
    tables = gen.generate(cfg, 2**31 + 31)
    join = JoinTree(tables, cfg["query"])
    key, value = cfg["roles"]["key"], cfg["roles"]["value"]
    values = OP.key_values(cfg, tables, key)
    width = int(0.1 * len(values))
    asked = [{"key": key, "value": v, "lo": int(lo), "hi": int(lo) + width}
             for lo in values[[0, 700, -width]]
             for v in (value, None, "U1", "U2", "A1")]
    records = [{"answers": [("filter_sum", p,
                             OP.reference(join, p, np.int64))]}
               for p in asked]
    ops = {"filter_sum": OP}
    assert compare(records, ops, join, 1, {}) == {
        "failed_requests": 0, "filter_sum_gap": 0}
    gaps = compare(records, ops, join, 1, {}, control=True)
    assert gaps["filter_sum_gap"] > 0
