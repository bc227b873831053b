"""The trace reduction, on a small trace recorded on a TPU v5e by
``testdata/record_trace.py`` (five runs of a jitted 1024 x 1024 bf16
product, each followed by a 20 ms wait, inside ``chipbench.window``),
and on hand-built planes."""
from types import SimpleNamespace as NS

import pytest

import trace_reduce
from conftest import BENCH

TRACE = BENCH / "testdata" / "small_trace.xplane.pb"


@pytest.fixture(scope="module")
def small():
    return trace_reduce.reduce_file(str(TRACE))


def test_recorded_trace_programs_and_busy(small):
    runs = small.module_seconds("small_step")
    assert len(runs) == 5, runs
    assert all(r > 0 for r in runs)
    assert small.devices == 1
    assert 0 < small.busy_s < small.window_s
    # the product is busy at least its module time, and the waits keep
    # the device idle for at least 5 x 20 ms
    assert small.busy_s >= 0.9 * sum(runs)
    assert small.window_s - small.busy_s >= 5 * 0.02


def test_recorded_trace_idle_is_labelled_by_the_waits(small):
    assert small.idle["chipbench.wait"] >= 5 * 0.02 * 0.95
    assert small.idle["chipbench.wait"] == max(small.idle.values())
    assert sum(small.idle.values()) == pytest.approx(
        small.window_s - small.busy_s, rel=1e-6)


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _planes(ops, modules, spans):
    return [
        NS(name="/host:CPU", lines=[NS(name="python", events=spans)]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=modules),
            NS(name="XLA Ops", events=ops)]),
    ]


def test_hand_built_planes():
    spans = [_ev("chipbench.window", 0, 1000),
             _ev("chipbench.step", 0, 600), _ev("chipbench.wait", 600, 400)]
    ops = [_ev("a", 100, 200), _ev("b", 250, 100), _ev("a", 900, 200)]
    modules = [_ev("jit_step(1)", 100, 250)]
    r = trace_reduce.reduce_planes(_planes(ops, modules, spans))
    assert r.window_s == pytest.approx(1000e-9)
    # union [100, 350) + [900, 1000) clipped: 350 ns busy
    assert r.busy_s == pytest.approx(350e-9)
    assert r.ops["a"] == pytest.approx(300e-9)     # second run clipped
    assert r.module_seconds("step") == [pytest.approx(250e-9)]
    assert r.idle["chipbench.step"] == pytest.approx(100e-9 + 250e-9)
    assert r.idle["chipbench.wait"] == pytest.approx(300e-9)


def test_no_window_or_no_device_work_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes(_planes([], [], []))
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes(
            _planes([], [], [_ev("chipbench.window", 0, 10)]))
