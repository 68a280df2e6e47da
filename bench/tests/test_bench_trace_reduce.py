"""The trace reduction on a small trace recorded on a TPU v5e chip.

``agg_filter.xplane.pb`` is ``--trace 1`` of one filtered-sum request of
lastFM A1 on one chip: four ``jit__segsum_padded`` programs on
``/device:TPU:0`` inside the ``bench:window`` annotation.  The expected
numbers were read off the file event by event and are checked here both
as literals and by an independent sweep over the raw events.
"""

from pathlib import Path

import pytest

from bench.trace_reduce import (idle_gaps, reduce_trace, roofline_share,
                                strip_hash, union)

TRACE = str(Path(__file__).resolve().parents[1] / "testdata"
            / "agg_filter.xplane.pb")


@pytest.fixture(scope="module")
def raw():
    """(device module events, anchor interval) straight from the file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(TRACE)
    dev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for p in pd.planes if p.name == "/device:TPU:0"
           for line in p.lines if line.name == "XLA Modules"
           for e in line.events]
    anchor = [(e.start_ns, e.start_ns + e.duration_ns)
              for p in pd.planes if p.name.startswith("/host:")
              for line in p.lines for e in line.events
              if e.name == "bench:window"]
    return dev, anchor[0]


def test_window_busy_and_programs(raw):
    dev, (lo, hi) = raw
    s = reduce_trace(TRACE)
    assert s.chips == 1
    assert s.window_s == pytest.approx(37.889362036, abs=1e-9)
    assert s.window_s == pytest.approx((hi - lo) / 1e9, abs=1e-9)
    assert s.busy_s == pytest.approx(18.337146763, abs=1e-9)
    assert list(s.programs) == ["jit__segsum_padded"]
    assert s.programs["jit__segsum_padded"] == pytest.approx(s.busy_s)
    assert len(dev) == 4
    # an independent sweep: the four programs do not overlap, all inside
    assert all(lo <= a < b <= hi for _, a, b in dev)
    spans = sorted((a, b) for _, a, b in dev)
    assert all(b1 <= a2 for (_, b1), (a2, _) in zip(spans, spans[1:]))
    assert s.busy_s == pytest.approx(sum(b - a for a, b in spans) / 1e9,
                                     abs=1e-9)
    assert s.idle_share == pytest.approx(1 - 18.337146763 / 37.889362036)


def test_gaps_are_the_window_minus_the_programs(raw):
    dev, (lo, hi) = raw
    s = reduce_trace(TRACE)
    assert [round(g[1], 9) for g in s.gaps] == [
        9.671853094, 4.873519449, 4.690493417, 0.271273088, 0.045076225]
    assert sum(g[1] for g in s.gaps) + s.busy_s == pytest.approx(
        s.window_s, abs=1e-8)
    assert all(g[0] == "host" for g in s.gaps)   # no spans given


def test_gaps_take_the_innermost_open_span(raw):
    dev, (lo, hi) = raw
    gaps = idle_gaps(union((a, b) for _, a, b in dev), lo, hi)
    longest = max(gaps, key=lambda g: g[1] - g[0])
    mid = ((longest[0] + longest[1]) / 2 - lo) / 1e9     # s into the window
    t0 = 500.0                                           # anchor's own clock
    spans = [("bench:window", t0, t0 + 40.0),
             ("outer", t0 + mid - 1.0, t0 + mid + 1.0),
             ("inner", t0 + mid - 0.5, t0 + mid + 0.5),
             ("elsewhere", t0 + mid + 2.0, t0 + mid + 3.0)]
    s = reduce_trace(TRACE, anchor_t0=t0, spans=spans)
    assert s.gaps[0] == ["inner", pytest.approx(9.671853094, abs=1e-9)]


def test_helpers():
    assert strip_hash("jit__psi_weights(1349810)") == "jit__psi_weights"
    assert strip_hash("jit_f") == "jit_f"
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert idle_gaps([(2, 3), (5, 6)], 0, 10) == [(0, 2), (3, 5), (6, 10)]
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    assert roofline_share(1.0, peaks, nbytes=819e9) == pytest.approx(100.0)
    assert roofline_share(2.0, peaks, flops=197e12) == pytest.approx(50.0)
    assert roofline_share(0.0, peaks, nbytes=1.0) is None
