"""The slot step's phase readers and the host-time reader on a synthetic
traced window: a hand-written compiled module, device seconds per HLO
instruction, and the program's span buffer."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]

import harness  # noqa: E402

PHASE_METRICS = {
    "service_us_per_slot.sweep": ["svc.1"],
    "schedule_us_per_slot.sweep": ["sched.1"],
    "arrivals_us_per_slot.sweep": ["cls.1"],
    "candidates_us_per_slot.sweep": ["rw.1", "cs.1"],
    "route_layout_us_per_slot.sweep": ["pad.1"],
    "accumulate_us_per_slot.sweep": ["acc.1"],
}

_SCOPE = 'metadata={op_name="jit(_sweep_cells)/while/body/slot.%s/op"}'
MODULE = "\n".join([
    "HloModule jit__sweep_cells, is_scheduled=true",
    "",
    "%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {",
    "  %arg = (s32[], f32[8]{0}) parameter(0)",
    "  %x = f32[8]{0} get-tuple-element(%arg), index=1",
    "  %svc.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f0, "
    + _SCOPE % "service",
    "  %sched.1 = f32[8]{0} fusion(%svc.1), kind=kLoop, calls=%f1, "
    + _SCOPE % "schedule",
    "  %cls.1 = s32[8]{0} fusion(%sched.1), kind=kLoop, calls=%f2, "
    + _SCOPE % "arrivals",
    "  %rw.1 = s32[8]{0} reduce-window(%cls.1, %z), window={size=8}, "
    "to_apply=%add",
    "  %cs.1 = s32[8]{0} fusion(%rw.1), kind=kLoop, calls=%f3, "
    + _SCOPE % "candidates",
    "  %pad.1 = s32[8]{0} fusion(%cs.1), kind=kLoop, calls=%f4, "
    + _SCOPE % "route",
    "  %route_commit.7 = (s32[8]{0}) custom-call(%pad.1), "
    'custom_call_target="tpu_custom_call"',
    "  %acc.1 = f32[8]{0} fusion(%route_commit.7), kind=kLoop, calls=%f5, "
    + _SCOPE % "accumulate",
    "  %inner.1 = (s32[]) while(%acc.1), condition=%c, body=%b, "
    + _SCOPE % "arrivals",
    "  ROOT %t = (s32[], f32[8]{0}) tuple(%acc.1)",
    "}",
    "",
    "ENTRY %main (a: f32[8]) -> f32[8] {",
    "  %a = f32[8]{0} parameter(0)",
    "  %while.3 = (s32[], f32[8]{0}) while(%a), condition=%cond, "
    'body=%body, metadata={op_name="jit(_sweep_cells)/while"}',
    "  ROOT %r = f32[8]{0} get-tuple-element(%while.3), index=1",
    "}",
    ""])

LAUNCHES, CELLS = 100, 8


def _ctx(op_s, launches=LAUNCHES):
    op_s = dict(op_s)
    op_n = {n: 1 for n in op_s}
    if launches:
        op_s["route_commit.7"] = 0.5
        op_n["route_commit.7"] = launches
    return dict(trace=dict(op_s=op_s, op_n=op_n),
                window=dict(cells_per_launch=CELLS, spans=[(10.0, 20.0)]))


@pytest.fixture
def program_map(monkeypatch):
    """The program's phase map, read from MODULE."""
    from repro.core import simulator
    from repro.telemetry.spans import op_phases
    phases = op_phases(MODULE)
    monkeypatch.setattr(simulator, "sweep_op_phases", lambda: phases)
    return phases


def _read(name, ctx):
    return harness.load_module("metrics", name).read(ctx)


@pytest.mark.parametrize("name", sorted(PHASE_METRICS))
def test_each_phase_reads_its_own_instructions(program_map, name):
    # every instruction gets its own power of two of device time, so the
    # sum names exactly the instructions that were counted
    names = ["svc.1", "sched.1", "cls.1", "rw.1", "cs.1", "pad.1",
             "acc.1", "while.3", "inner.1", "copy.1"]
    op_s = {n: 2.0 ** i for i, n in enumerate(names)}
    got = _read(name, _ctx(op_s))
    want = sum(op_s[n] for n in PHASE_METRICS[name])
    assert got == pytest.approx(1e6 * want / (LAUNCHES * CELLS))


def test_containers_and_unknown_instructions_count_nowhere(program_map):
    assert program_map["while.3"] == program_map["inner.1"] == "container"
    ctx = _ctx({"while.3": 7.0, "inner.1": 3.0, "copy.1": 1.0})
    for name in PHASE_METRICS:
        assert _read(name, ctx) == 0.0


def test_route_layout_leaves_out_the_kernel_itself(program_map):
    assert program_map["route_commit.7"] == "route"
    ctx = _ctx({"pad.1": 0.25})
    assert _read("route_layout_us_per_slot.sweep", ctx) == pytest.approx(
        1e6 * 0.25 / (LAUNCHES * CELLS))


def test_no_kernel_launch_reads_none(program_map):
    ctx = _ctx({"svc.1": 1.0}, launches=0)
    for name in PHASE_METRICS:
        assert _read(name, ctx) is None


def test_a_program_without_a_phase_map_reads_none(monkeypatch):
    from repro.core import simulator
    monkeypatch.setattr(simulator, "sweep_op_phases", lambda: {})
    for name in PHASE_METRICS:
        assert _read(name, _ctx({"svc.1": 1.0})) is None


def _spans_module():
    """``repro.telemetry.spans`` (the package's ``spans`` is a function)."""
    import repro.telemetry  # noqa: F401
    return sys.modules["repro.telemetry.spans"]


class _Clock:
    """A perf_counter that returns the times it is given, in turn."""

    def __init__(self, times):
        self.times = list(times)

    def perf_counter(self):
        return self.times.pop(0)


def test_host_time_is_the_mean_of_the_calls_inside_the_window(monkeypatch):
    spans_mod = _spans_module()
    spans_mod.reset()
    # per call: sweep.call opens, grid, launch, summarize, sweep.call
    # closes; the first call is set-up, before the window (10 s to 20 s)
    times = [1.0, 1.1, 1.2, 1.3, 1.5, 1.6, 1.7, 9.0,
             11.0, 11.001, 11.003, 11.004, 11.010, 11.011, 11.012, 14.0,
             14.0, 14.002, 14.002, 14.003, 14.005, 14.006, 14.010, 17.0]
    monkeypatch.setattr(spans_mod, "time", _Clock(times))
    try:
        for _ in range(3):
            with spans_mod.span("sweep.call"):
                for part in ("sweep.grid", "sweep.launch",
                             "sweep.summarize"):
                    with spans_mod.span(part):
                        pass
        got = _read("sweep_host_ms_per_call.sweep", _ctx({}))
    finally:
        spans_mod.reset()
    # call 2: 2 + 6 + 1 ms; call 3: 0 + 2 + 4 ms
    assert got == pytest.approx((9.0 + 6.0) / 2)


def test_host_time_reads_none_without_program_spans():
    _spans_module().reset()
    assert _read("sweep_host_ms_per_call.sweep", _ctx({})) is None
