"""The trace reduction on a small recorded trace: an extract of a
``--trace 1``-style profile of one TPU v5e running the evaluator and the
timing program (``data/trace_v5e.json``: plane, line, name, start and
duration of each event)."""
import json
import os

import pytest

from bench.trace import Trace, program_patterns

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace_v5e.json")


@pytest.fixture(scope="module")
def events():
    with open(DATA) as f:
        return json.load(f)


def test_device_operations_and_programs_are_found(events):
    t = Trace.from_events(events, window_s=10.0)
    assert t.ops[0] and t.modules[0]
    assert 0 < t.busy_s() < 10.0
    assert t.op_seconds(program_patterns("lut_eval6")["ops"]) > 0
    assert t.module_seconds(program_patterns("eval")["modules"]) > 0
    assert t.module_seconds(program_patterns("timing")["modules"]) > 0


def test_busy_time_is_no_more_than_the_summed_operations(events):
    t = Trace.from_events(events, window_s=10.0)
    summed = sum(e - s for s, e, _ in t.ops[0]) / 1e9
    assert t.busy_s() <= summed + 1e-12
    assert t.op_seconds(program_patterns("lut_eval6")["ops"]) <= summed


def test_breakdown_names_program_and_opcode(events):
    bd = Trace.from_events(events, window_s=10.0).breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    names = [n for n, _ in bd["device_ops"]]
    assert "eval/custom-call:tpu_custom_call" in names
    assert not any(n.endswith("/while") for n in names)
    assert all(s > 0 for _, s in bd["device_ops"])
