"""The trace reduction on a small trace recorded on a TPU v5e.

tests/data/small.xplane.pb: one jitted tanh(x @ x).sum(0) on 512 x 512
f32, called four times inside a `bench.window` annotation, each call under
`prepare` and its wait under `resolve` (with a 2 ms host sleep), as
tests/data/fixture.py records it.
"""
import os

import pytest

from benchmark import trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.reduce_file(TRACE, "bench.window",
                                    {"prepare", "resolve"})


def test_busy_and_idle_partition_the_window(trace):
    assert trace.devices == 1
    assert 0.0 < trace.busy_s < trace.window_s
    gaps = sum(s for _label, s in trace.gaps)
    assert gaps + trace.busy_s == pytest.approx(trace.window_s, abs=1e-9)
    assert 0.0 < trace.idle_share < 1.0


def test_ops_and_modules_account_for_the_busy_time(trace):
    # ops may overlap, so their sum bounds the union from above
    assert sum(trace.op_s.values()) >= trace.busy_s - 1e-12
    assert trace.module_time("jit__lambda") > 0.0
    assert all(not k.count(" = ") for k in trace.op_s)


def test_idle_gaps_carry_the_engine_span_open_in_them(trace):
    labels = {label for label, _s in trace.gaps}
    assert labels <= {"prepare", "resolve", "-"}
    by_label = dict(trace.gaps_by_label())
    # four 2 ms host sleeps inside `resolve`: a gap that spans one and the
    # next `prepare` takes the label of the span open at its middle
    assert by_label["resolve"] >= 0.004
    assert by_label["resolve"] + by_label["prepare"] >= 0.008
    bd = trace.breakdown()
    assert bd["device_ops"] and bd["idle_gaps"]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.reduce_file(TRACE, "no.such.span")
