"""The readers of the ``plan:stats``, ``filter:mask`` and
``filter:propagate`` spans, on hand-made runs and on tiny traced runs."""

import pytest

from bench import harness
from bench.tests.bench_tiny import tiny_run
from bench.trace_reduce import TraceSummary
from repro.obs.trace import Tracer

READERS = ["plan_stats_s.build", "filter_mask_ms.filter",
           "filter_propagate_ms.filter"]


def _read(metric, run):
    return harness.load_module(
        harness.HERE / "metrics" / f"{metric}.py").read(run)


def _traced(base, spans=()):
    """A tracer holding ``bench:window`` over [0, 10] s and ``spans``, and
    a traced run of two requests inside it."""
    tracer = Tracer()
    tracer.add("bench:window", base, base + 10.0)
    for name, t0, t1 in spans:
        tracer.add(name, base + t0, base + t1)
    records = [{"t0": base + 0.5, "t1": base + 5.0},
               {"t0": base + 5.0, "t1": base + 9.5}]
    run = harness.Run(cell={}, records=records, setup_s=1.0,
                      trace=TraceSummary(window_s=10.0, busy_s=5.0,
                                         programs={}))
    return tracer, run


@pytest.mark.parametrize("metric", READERS)
def test_reader_is_silent_without_its_spans(metric):
    untraced = harness.Run(cell={}, records=[{"t0": 0.0, "t1": 1.0}],
                           setup_s=1.0)
    assert _read(metric, untraced) is None
    tracer, run = _traced(50_000.0, spans=[("filter:maskless", 1.0, 2.0),
                                           ("plan:statistics", 1.0, 2.0)])
    assert _read(metric, run) is None


def test_readers_sum_their_spans_per_request():
    tracer, run = _traced(60_000.0, spans=[
        ("plan:stats", 1.0, 1.5), ("plan:stats", 2.0, 2.25),
        ("filter:mask", 3.0, 3.1), ("filter:mask", 6.0, 6.3),
        ("filter:propagate", 3.1, 3.6), ("filter:propagate", 3.6, 4.0),
        ("filter:propagate", 6.3, 6.4),
        ("plan:stats", 10.5, 11.0)])                  # after the window
    assert _read("plan_stats_s.build", run) == pytest.approx(0.375)
    assert _read("filter_mask_ms.filter", run) == pytest.approx(200.0)
    assert _read("filter_propagate_ms.filter", run) == pytest.approx(500.0)


@pytest.mark.parametrize("cell,metrics", [
    ("tpch_q10.build", ["plan_stats_s.build"]),
    ("lastfm_a1.build", ["plan_stats_s.build"]),
    ("lastfm_a1.filter", ["filter_mask_ms.filter",
                          "filter_propagate_ms.filter"])])
def test_tiny_traced_run_reports_the_new_spans(cell, metrics):
    out = tiny_run(cell, trace=True)
    assert out["correct"] is True
    for metric in metrics:
        assert out["metrics"][metric]["value"] > 0


def test_filter_roofline_counts_the_propagation_once_a_request():
    levels = [{"vars": ["U1"], "runs": 10}, {"vars": ["U2"], "runs": 100},
              {"vars": ["A2"], "runs": 1000}]

    def answers(lo):
        band = {"key": "U1", "lo": lo, "hi": lo + 3}
        return [("filter_sum", {**band, "value": v}, 0)
                for v in ("A2", None, "U1", "U2")]

    records = [{"answers": answers(0)}, {"answers": answers(5)},
               {"answers": [("count", {}, 7)]}]
    run = harness.Run(cell={}, records=records, setup_s=1.0,
                      summary_levels=levels,
                      trace=TraceSummary(window_s=10.0, busy_s=2.0, programs={
                          "jit__segsum_padded": 1.5, "jit_mul_segsum": 0.5,
                          "jit__sorted_runs": 4.0}))
    run.peaks = {"hbm_bytes_per_s": 1e6, "bf16_flops_per_s": 1.0}
    # per request: two level sums of the 1,000 deepest runs (20 B each,
    # 8 B a segment), then sum(A2), sum(U1), sum(U2); count reads no bytes
    per_request = (2 * 20 * 1000 + 8 * (10 + 100)) \
        + (20 * 1000 + 8) + (20 * 10 + 8) + (20 * 100 + 8)
    assert _read("segsum_roofline.filter", run) == pytest.approx(
        100.0 * 2 * per_request / 1e6 / 2.0)
    run.trace = None
    assert _read("segsum_roofline.filter", run) is None
