"""Phase scopes and host spans (``repro.telemetry.spans``): the span
buffer, the map from a compiled sweep's instructions to the slot step's
phases, and the jitted one-device sweep it reads (bit-identical to the
looped grid, one executable per signature)."""
import collections

import jax
import numpy as np
import pytest

from repro.core import Cluster, Rates, SimConfig, simulate_grid, \
    simulate_sweep, sweep_grid
from repro.core import simulator as sim
from repro.telemetry import spans as buffered_spans
from repro.telemetry.spans import (
    CONTAINER,
    CONTAINER_OPS,
    MAX_SPANS,
    PHASES,
    PLUMBING_OPS,
    op_phases,
    phase,
    reset,
    span,
    top_level,
    totals,
)
from repro.scenarios import canonical_pad
from repro.trace import ReplayEngine, synth_trace

CLUSTER = Cluster(M=20, K=4)
RATES = Rates(0.05, 0.025, 0.01)
# shapes of their own, so no other test's jit cache entry is reused
CFG = SimConfig(T=8, warmup=2, route_mode="batched", s_max=20)
SCENARIOS = ["uniform", "slow_rack"]
LOADS = [0.5, 0.9]


@pytest.fixture(autouse=True)
def _empty_buffer():
    reset()
    yield
    reset()


# ---------------------------------------------------------------------------
# the span buffer
# ---------------------------------------------------------------------------


def test_spans_nest_with_their_parents_and_times():
    with span("outer"):
        with span("inner"):
            pass
        with span("inner"):
            pass
    got = buffered_spans()
    assert [(s.name, s.parent) for s in got] == [
        ("inner", "outer"), ("inner", "outer"), ("outer", None)]
    outer = got[-1]
    for s in got[:2]:
        assert outer.start <= s.start <= s.end <= outer.end
    assert [s.name for s in buffered_spans("inner")] == ["inner", "inner"]


def test_span_closes_when_the_block_raises():
    with pytest.raises(RuntimeError):
        with span("failing"):
            raise RuntimeError("boom")
    with span("after"):
        pass
    assert [(s.name, s.parent) for s in buffered_spans()] == [
        ("failing", None), ("after", None)]


def test_buffer_is_bounded_but_totals_count_every_span():
    for _ in range(MAX_SPANS + 5):
        with span("tick"):
            pass
    assert len(buffered_spans()) == MAX_SPANS
    count, total = totals()["tick"]
    assert count == MAX_SPANS + 5
    assert total >= sum(s.end - s.start for s in buffered_spans())


def test_reset_drops_spans_and_totals():
    with span("a"):
        pass
    assert totals()["a"][0] == 1
    reset()
    assert buffered_spans() == [] and totals() == {}


def test_phase_is_a_named_scope_of_known_phases_only():
    with pytest.raises(ValueError, match="unknown phase"):
        phase("routing")
    text = jax.jit(lambda x: _scoped_double(x)).lower(
        np.float32(1.0)).as_text(debug_info=True)
    assert "slot.service" in text


def _scoped_double(x):
    with phase("service"):
        return x * 2.0


# ---------------------------------------------------------------------------
# op_phases on the compiled one-device sweep
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module", params=["balanced_pandas_pod",
                                        "balanced_pandas"])
def swept(request):
    """(algo, compiled text of the sweep that ran, its phase map)."""
    algo = request.param
    simulate_sweep(algo, CLUSTER, RATES, LOADS, 2, CFG, scenarios=SCENARIOS)
    rec = sim._LAST_SWEEP
    text = rec["fn"].lower(*rec["args"], **rec["kw"]).compile().as_text()
    return algo, text, sim.sweep_op_phases()


def _scan_body(instrs):
    """The slot scan's body: the largest computation a while of the
    entry computation runs."""
    entry = instrs[0].computation
    sizes = collections.Counter(i.computation for i in instrs)
    callees = [c for i in instrs if i.computation == entry
               and i.opcode == "while" for c in i.callees]
    return max(callees, key=lambda c: sizes[c])


def test_every_leaf_of_the_scan_body_gets_a_phase(swept):
    algo, text, phases = swept
    instrs = top_level(text)
    body = _scan_body(instrs)
    leaves = [i for i in instrs if i.computation == body
              and i.opcode not in CONTAINER_OPS
              and i.opcode not in PLUMBING_OPS]
    assert leaves
    assert [i.name for i in leaves if phases.get(i.name) not in PHASES] \
        == []
    seen = {phases[i.name] for i in leaves}
    assert {"service", "schedule", "arrivals", "candidates", "route",
            "accumulate"} <= seen, (algo, seen)


def test_containers_are_marked_and_only_they(swept):
    _, text, phases = swept
    for i in top_level(text):
        assert (phases.get(i.name) == CONTAINER) == (
            i.opcode in CONTAINER_OPS), i
    assert any(v == CONTAINER for v in phases.values())


def test_phase_map_is_the_program_that_ran(swept):
    """Lowering the recorded signature gives the text of lowering the
    call's own arguments: the map describes the dispatched program."""
    algo, text, _ = swept
    _, scen, lam, a_max = sweep_grid(CLUSTER, RATES, CFG, LOADS,
                                     scenarios=SCENARIOS)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    pod = sim._pod_for(algo, None)
    direct = sim._sweep_jit.lower(
        keys, lam, scen, algo=algo, cluster=CLUSTER, rates=RATES, cfg=CFG,
        pod=pod, a_max=a_max, tcfg=None).compile().as_text()
    assert direct == text


# ---------------------------------------------------------------------------
# op_phases on hand-written module text
# ---------------------------------------------------------------------------

_MODULE = """\
HloModule m, is_scheduled=true

%fused (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %n = f32[4]{0} negate(%p), metadata={op_name="jit(f)/slot.route/neg"}
}

%body (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[4]{0} get-tuple-element(%arg), index=1
  %rw = f32[4]{0} reduce-window(%x, %z), window={size=4 pad=3_0}, to_apply=%add
  %cs = f32[4]{0} fusion(%rw), kind=kLoop, calls=%fused, metadata={op_name="jit(f)/while/body/slot.candidates/cumsum"}
  %route_commit.3 = (f32[4]{0}) custom-call(%cs), custom_call_target="tpu_custom_call"
  %out = f32[4]{0} get-tuple-element(%route_commit.3), index=0
  %tail = f32[4]{0} copy(%out)
  ROOT %t = (s32[], f32[4]{0}) tuple(%i, %tail)
}

%cond (arg: (s32[], f32[4])) -> pred[] {
  %arg.1 = (s32[], f32[4]{0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%arg.1), index=0
  ROOT %lt = pred[] compare(%i.1, %i.1), direction=LT
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %svc = f32[4]{0} multiply(%a, %a), metadata={op_name="jit(f)/slot.service/mul"}
  %w = (s32[], f32[4]{0}) while(%svc), condition=%cond, body=%body, metadata={op_name="jit(f)/while"}
  ROOT %r = f32[4]{0} get-tuple-element(%w), index=1
}
"""


def test_hand_written_module_follows_the_rules():
    phases = op_phases(_MODULE)
    names = {i.name for i in top_level(_MODULE)}
    assert "n" not in names and "p" not in names     # a fusion body
    assert phases["w"] == CONTAINER
    assert phases["svc"] == "service"                # its own scope
    assert phases["route_commit.3"] == "route"       # the kernel, by name
    assert phases["rw"] == "candidates"              # from its consumer
    assert phases["tail"] == "route"                 # from its operand
    assert "lt" not in phases                        # no phased neighbour


def test_nested_scopes_take_the_innermost_phase():
    line = ('  %f = f32[] add(%a, %a), metadata={op_name='
            '"jit(f)/slot.candidates/jit(g)/slot.route/add"}')
    text = "ENTRY %main (a: f32[]) -> f32[] {\n" + line + "\n}\n"
    assert op_phases(text) == {"f": "route"}


# ---------------------------------------------------------------------------
# the jitted one-device sweep
# ---------------------------------------------------------------------------


def test_full_bp_sweep_is_bit_identical_to_the_looped_grid():
    """As tests/test_sweep.py's BP-Pod guard, for full Balanced-Pandas and
    every field: one canonical pad and one a_max for both paths."""
    pad = canonical_pad(CLUSTER)
    _, _, _, a_max = sweep_grid(CLUSTER, RATES, CFG, LOADS,
                                scenarios=SCENARIOS, pad=pad)
    _, res, _ = simulate_sweep("balanced_pandas", CLUSTER, RATES, LOADS, 2,
                               CFG, scenarios=SCENARIOS, pad=pad,
                               a_max=a_max)
    for s, name in enumerate(SCENARIOS):
        looped = simulate_grid("balanced_pandas", CLUSTER, RATES, LOADS, 2,
                               CFG, scenario=name, pad=pad, a_max=a_max)
        for field in res._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(res, field))[s]
                if np.ndim(getattr(res, field)) else getattr(res, field),
                np.asarray(getattr(looped, field)), err_msg=field)


def test_sweep_records_its_host_spans_and_reads_no_trace_twice():
    simulate_sweep("balanced_pandas_pod", CLUSTER, RATES, LOADS, 2, CFG,
                   scenarios=SCENARIOS)
    traces = sim.trace_count()
    assert sim.sweep_op_phases() is sim.sweep_op_phases()   # kept
    assert sim.trace_count() == traces       # the map traced nothing new
    got = buffered_spans()
    call = [s for s in got if s.name == "sweep.call"]
    assert len(call) == 1 and call[0].parent is None
    parts = [s for s in got if s.parent == "sweep.call"]
    assert [s.name for s in parts] == ["sweep.grid", "sweep.launch",
                                       "sweep.summarize"]
    for s in parts:
        assert call[0].start <= s.start <= s.end <= call[0].end


def test_replay_run_records_put_chunk_and_sync_spans():
    log = synth_trace(name="small", n_tasks=300, seed=3, churn_t=(0.5,),
                      n_tenants=2, n_chunks=64)
    eng = ReplayEngine(log, Cluster(M=8, K=2), Rates(),
                       cfg=SimConfig(T=128, warmup=32), chunk_slots=32)
    eng.run(seed=0)
    n = eng.n_chunks
    count = {k: v[0] for k, v in totals().items()}
    assert count == {"replay.put": n, "replay.chunk": n, "replay.sync": 1}
