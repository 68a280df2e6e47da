"""The readers of the program's own spans (``bench/spans.py`` and the
metrics built on it), on hand-made runs and on a tiny traced run."""

import itertools

import pytest

from bench import harness
from bench.tests.bench_tiny import tiny_run
from bench.trace_reduce import TraceSummary
from repro.obs.trace import Tracer

READERS = ["sync_s.build", "emit_s.build", "wrap_ms.agg",
           "algebra_span_ms.agg", "algebra_wait_ms.agg"]
_BASES = itertools.count(1000.0, 1000.0)   # each run's spans their own time


def _read(metric, run):
    return harness.load_module(
        harness.HERE / "metrics" / f"{metric}.py").read(run)


def _traced(spans=(), requests=2, window=(0.0, 10.0)):
    """A tracer holding ``bench:window`` and ``spans``, and a traced run
    of ``requests`` requests inside [0.5, 9.5] s of it."""
    base = next(_BASES)
    tracer = Tracer()
    tracer.add("bench:window", base + window[0], base + window[1])
    for name, t0, t1 in spans:
        tracer.add(name, base + t0, base + t1)
    step = 9.0 / requests
    records = [{"t0": base + 0.5 + k * step, "t1": base + 0.5 + (k + 1) * step}
               for k in range(requests)]
    run = harness.Run(cell={}, records=records, setup_s=1.0,
                      trace=TraceSummary(window_s=10.0, busy_s=5.0,
                                         programs={}))
    return tracer, run


@pytest.mark.parametrize("metric", READERS)
def test_reader_is_silent_without_its_spans(metric):
    untraced = harness.Run(cell={}, records=[{"t0": 0.0, "t1": 1.0}],
                           setup_s=1.0)
    assert _read(metric, untraced) is None
    tracer, run = _traced()
    assert _read(metric, run) is None


def test_span_readers_sum_per_request_and_count_nesting_once():
    tracer, run = _traced(spans=[
        ("gfjs:sync", 1.0, 1.5), ("gfjs:sync", 2.0, 2.25),
        ("gfjs:emit:0", 3.0, 4.0), ("gfjs:emit:1", 5.0, 7.0),
        ("gfjs:emitter", 0.0, 9.0),                   # not an emit span
        ("service:wrap", 0.0, 0.1), ("service:wrap", 1.0, 1.3),
        ("service:wrap", 2.0, 2.2),
        ("algebra:mean", 3.0, 3.4), ("algebra:sum", 3.1, 3.3),
        ("algebra:group_by", 4.0, 4.6),
        ("segsum:wait", 3.15, 3.25), ("sort:wait", 4.1, 4.2),
        ("gfjs:sync", 10.5, 11.0)])                   # after the window
    assert _read("sync_s.build", run) == pytest.approx(0.375)
    assert _read("emit_s.build", run) == pytest.approx(1.5)
    assert _read("wrap_ms.agg", run) == pytest.approx(200.0)
    assert _read("algebra_span_ms.agg", run) == pytest.approx(500.0)
    assert _read("algebra_wait_ms.agg", run) == pytest.approx(100.0)


def test_only_the_tracer_around_the_requests_is_read():
    other, _ = _traced(spans=[("gfjs:sync", 1.0, 2.0)])
    tracer, run = _traced(spans=[("gfjs:sync", 1.0, 1.5)])
    assert _read("sync_s.build", run) == pytest.approx(0.25)
    # a window that ends before the last request does not hold the run
    short, run = _traced(spans=[("gfjs:sync", 1.0, 1.5)],
                         window=(0.0, 9.0))
    assert _read("sync_s.build", run) is None


def test_tiny_traced_agg_reports_the_program_spans():
    out = tiny_run("lastfm_a1.agg", trace=True)
    assert out["correct"] is True
    for metric in ("wrap_ms.agg", "algebra_span_ms.agg",
                   "algebra_wait_ms.agg"):
        assert out["metrics"][metric]["value"] > 0
