"""``bench/idle_split.py``: device-idle time split by the program span
open, on hand-made intervals and on the recorded ``agg_filter`` trace
(one chip, four ``jit__segsum_padded`` programs inside ``bench:window``,
and no other program span)."""

import json
from pathlib import Path

import pytest

from bench.idle_split import (idle_by_span, is_container, is_program_span,
                              main, split_idle)
from bench.trace_reduce import reduce_trace

TRACE = str(Path(__file__).resolve().parents[1] / "testdata"
            / "agg_filter.xplane.pb")
S = 1e9                                              # ns per second


def test_the_split_adds_up_to_the_window_idle_time():
    window, split = idle_by_span(TRACE)
    s = reduce_trace(TRACE)
    assert window == pytest.approx(s.window_s, abs=1e-9)
    assert list(split) == ["bench:window"]
    assert split["bench:window"] == pytest.approx(s.window_s - s.busy_s,
                                                  abs=1e-6 * 5)


def test_a_span_gets_the_idle_time_under_it_and_the_inner_span_wins():
    idle = [(1 * S, 3 * S), (5 * S, 9 * S)]
    spans = [("bench:window", 0, 10 * S),
             ("gfjs:emit:2", 1 * S, 3 * S),          # exactly the first gap
             ("service:frame", 5 * S, 9 * S),
             ("service:wrap", 7 * S, 9 * S)]         # its second half
    split = split_idle(idle, spans)
    assert split == pytest.approx({"gfjs:emit": 2.0, "service:frame": 2.0,
                                   "service:wrap": 2.0})


def test_spans_outside_the_idle_time_get_nothing():
    idle = [(2 * S, 4 * S)]
    spans = [("before", 0, 1 * S), ("after", 5 * S, 6 * S),
             ("half", 3 * S, 7 * S)]
    assert split_idle(idle, spans) == pytest.approx({"host": 1.0,
                                                     "half": 1.0})


@pytest.mark.parametrize("name,program", [
    ("bench:window", True), ("gfjs:emit:2", True), ("server:plan", True),
    ("algebra:count_distinct", True), ("kernel:mul_segsum", True),
    ("tpu::System::Execute", False), ("H2D Dispatch", False),
    ("PjitFunction(_segsum_padded)", False)])
def test_program_span_names(name, program):
    assert is_program_span(name) is program


def test_containers_name_no_work():
    assert all(map(is_container, ["bench:window", "phase:summarize",
                                  "gfjs:level", "host", "service:frame"]))
    assert not any(map(is_container, ["gfjs:emit", "service:wrap",
                                      "segsum:wait", "server:plan"]))


def test_the_command_prints_the_split(capsys):
    assert main([TRACE]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["idle_s"] == pytest.approx(
        out["by_span"]["bench:window"], abs=1e-12)
    assert out["named_percent"] == 0.0
    assert main([]) == 2
