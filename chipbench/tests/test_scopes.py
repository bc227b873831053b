"""The reader of the program's own instrumentation, on a trace recorded
on a TPU v5e by ``testdata/record_scoped_trace.py`` (five runs of a
scoped jitted step inside ``repro.*`` spans, each with a 5 ms host-only
``repro.adapter.accept``, a 2 ms ``repro.engine.prefill`` and a 20 ms
``chipbench.wait``), and on hand-built planes."""
import pytest
from jax.profiler import ProfileData

import scopes
from conftest import BENCH
from scopes import Event, Plane

TRACE = BENCH / "testdata" / "scoped_trace.xplane.pb"
SMALL = BENCH / "testdata" / "small_trace.xplane.pb"
RUNS = 5
STEP = "scoped_step"


@pytest.fixture(scope="module")
def planes():
    return scopes.read_planes(str(TRACE))


@pytest.fixture(scope="module")
def recorded(planes):
    return scopes.reduce_planes(planes)


@pytest.mark.parametrize("path", [TRACE, SMALL], ids=["scoped", "small"])
def test_wire_format_reads_what_profile_data_reads(path):
    ours = scopes.read_planes(str(path))
    theirs = list(ProfileData.from_file(str(path)).planes)
    assert [p.name for p in ours] == [p.name for p in theirs]
    for mine, plane in zip(ours, theirs):
        for line in plane.lines:
            evs = list(line.events)
            assert len(mine.lines[line.name]) == len(evs)
            for a, b in zip(mine.lines[line.name], evs):
                assert a.name == b.name
                assert a.start_ns == pytest.approx(b.start_ns, abs=1.0)
                assert a.end_ns - a.start_ns == pytest.approx(
                    b.duration_ns, abs=1.0)


def test_recorded_scope_times(planes, recorded):
    assert recorded.runs_of(STEP) == RUNS
    attn = recorded.seconds(STEP, "attention")
    ffn = recorded.seconds(STEP, "ffn")
    scan = recorded.seconds(STEP, "layers", ("attention", "ffn"))
    assert attn > 0 and ffn > 0 and scan >= 0
    # the product pair of the FFN is four times the attention's work
    assert ffn > attn
    # every op's self time is counted once: the scoped and unscoped
    # times of the program fill its runs, up to the gaps between ops
    total = sum(s for (p, _), s in recorded.device_s.items() if STEP in p)
    device = next(p for p in planes if scopes.DEVICE_PLANE.match(p.name))
    module = sum(ev.end_ns - ev.start_ns
                 for ev in device.lines[scopes.MODULES_LINE]) * 1e-9
    assert 0.8 * module <= total <= module * (1 + 1e-6)
    assert attn + ffn + scan >= 0.8 * total


def test_recorded_idle_is_labelled_by_the_program_spans(recorded):
    idle = recorded.idle
    assert recorded.idle_under("repro.adapter.accept") >= RUNS * 0.005 * 0.9
    assert recorded.idle_under("repro.engine.prefill") >= RUNS * 0.002 * 0.9
    assert idle[("chipbench.wait", "")] >= RUNS * 0.02 * 0.95
    accept = ("chipbench.step",
              "repro.scheduler.step/repro.adapter.accept")
    assert idle[accept] == pytest.approx(
        recorded.idle_under("repro.adapter.accept"))
    assert recorded.idle_under("repro.scheduler.step") >= idle[accept]
    assert sum(idle.values()) < recorded.window_s


def test_recorded_span_arguments(recorded):
    assert recorded.args("repro.engine.prefill") == [
        {"computed_tokens": 5, "padded_tokens": 64, "bucket": 8}] * RUNS
    assert recorded.args("repro.scheduler.step") == [
        {"width": 3, "active": 8}] * RUNS
    assert [a["rids"] for a in recorded.args("repro.scheduler.admit")] == [
        f"{i} 9" for i in range(RUNS)]


def test_recorded_clock_offset(planes, recorded):
    off = recorded.offset
    assert off.pairs >= RUNS
    assert off.lo_ns <= off.ns <= off.hi_ns
    # after the shift each run sits inside its host span: it starts once
    # dispatched and ends before the readback returns
    device = next(p for p in planes if scopes.DEVICE_PLANE.match(p.name))
    runs = sorted(device.lines[scopes.MODULES_LINE], key=lambda e: e.start_ns)
    decode = [s for s in recorded.spans if s.name == "repro.engine.decode"]
    back = [s for s in recorded.spans if s.name == "repro.adapter.readback"]
    for run, d, b in zip(runs, decode, back):
        assert d.start_ns <= run.start_ns + off.ns
        assert run.end_ns + off.ns <= b.end_ns


# ---------------------------------------------------------------------------
# hand-built planes
# ---------------------------------------------------------------------------
def _ev(name, start, end, stats=None, meta=None):
    return Event(name, float(start), float(end), stats or {}, meta or {})


def _op(name, start, end, tf_op=None):
    meta = {"program_id": 7}
    if tf_op is not None:
        meta["tf_op"] = tf_op
    return _ev(f"%{name} = f32[] op()", start, end, meta=meta)


def _planes(spans, ops, modules, host_runs=()):
    return [
        Plane("/host:CPU", {"python": spans, "runtime": list(host_runs)}),
        Plane("/device:TPU:0", {scopes.MODULES_LINE: modules,
                                scopes.OPS_LINE: ops}),
    ]


def test_scope_label():
    assert scopes.scope_label(
        "jit(f)/layers/while/body/closed_call/attention/jit(k)/k/dot:") \
        == "layers/attention"
    assert scopes.scope_label("jit(f)/layers/while:") == "layers"
    assert scopes.scope_label("jit(f)/dot_general:") == ""


def test_hand_built_planes():
    body = "jit(step)/layers/while/body/"
    spans = [
        _ev("chipbench.window", 0, 1000),
        _ev("chipbench.step", 0, 600),
        _ev("repro.scheduler.step", 50, 600, {"width": 2, "active": 4}),
        _ev("repro.adapter.draft", 50, 100),
        _ev("repro.engine.prefill", 620, 700,
            {"computed_tokens": 3, "padded_tokens": 16, "bucket": 8}),
    ]
    # a while op holding two body ops, then an op under no scope
    ops = [_op("while", 100, 400, "jit(step)/layers/while:"),
           _op("fusion", 120, 220, body + "attention/dot:"),
           _op("fusion.1", 250, 390, body + "ffn/dot:"),
           _op("copy", 900, 1100)]
    modules = [_ev("jit_step(7)", 100, 400)]
    sc = scopes.reduce_planes(_planes(spans, ops, modules))
    assert sc.offset.pairs == 0 and sc.offset.ns == 0
    assert sc.runs == {"jit_step": 1}
    assert sc.seconds("step", "attention") == pytest.approx(100e-9)
    assert sc.seconds("step", "ffn") == pytest.approx(140e-9)
    # the loop's own time is its span less its body's ops
    assert sc.seconds("step", "layers", ("attention", "ffn")) == \
        pytest.approx(60e-9)
    assert sc.ops[("jit_step", "", "copy")] == pytest.approx(100e-9)
    assert sc.ops[("jit_step", "layers", "while")] == pytest.approx(60e-9)
    # idle: [0, 100) and [400, 900), cut at span edges
    assert sc.idle == {
        ("chipbench.step", ""): pytest.approx(50e-9),
        ("chipbench.step", "repro.scheduler.step/repro.adapter.draft"):
            pytest.approx(50e-9),
        ("chipbench.step", "repro.scheduler.step"): pytest.approx(200e-9),
        ("", ""): pytest.approx(220e-9),
        ("", "repro.engine.prefill"): pytest.approx(80e-9),
    }
    assert sc.idle_under("repro.scheduler.step") == pytest.approx(250e-9)
    assert sc.args("repro.engine.prefill") == [
        {"computed_tokens": 3, "padded_tokens": 16, "bucket": 8}]


def test_hand_built_clock_offset_shifts_the_device():
    spans = [_ev("chipbench.window", 0, 1000),
             _ev("repro.adapter.readback", 200, 500)]
    # the device clock runs 100 ns behind the host's: the run at [50, 250)
    # was enqueued at 120 and its completion noticed at 420
    ops = [_op("fusion", 50, 250, "jit(step)/dot:")]
    modules = [_ev("jit_step(7)", 50, 250, {"run_id": 3})]
    host = [_ev("DoEnqueueProgram", 120, 130, {"run_id": 3}),
            _ev("CompleteCallbacks", 420, 430, {"run_id": 3})]
    sc = scopes.reduce_planes(_planes(spans, ops, modules, host))
    assert sc.offset.pairs == 1
    assert (sc.offset.lo_ns, sc.offset.hi_ns) == (70.0, 170.0)
    assert sc.offset.ns == 120.0
    # busy [170, 370) on the host's clock: the readback span holds
    # [370, 500) of idle, the rest of the window none
    assert sc.idle_under("repro.adapter.readback") == pytest.approx(130e-9)
    assert sum(sc.idle.values()) == pytest.approx(800e-9)


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        scopes.reduce_planes(_planes([], [], []))
