"""The four-chip scenario cell (``m500-sweep-4chip``) on the CPU: its
reference (``reference_scen.py``) against the program at a small fleet
for the outage and Zipf scenarios, two faults planted under the timed
path that the check must catch, and the per-device cell count read off a
synthetic four-device trace."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]

import harness  # noqa: E402
import reference_scen  # noqa: E402

CELL = "m500-sweep-4chip"
SEED = 2 ** 33 + 17


def _small_spec():
    """The cell at a fleet of 40 servers, its outage and Zipf scenarios at
    load 0.9, with enough seeds and reference runs to average the noise
    of so small a fleet."""
    spec = harness.cell_spec(
        harness.load_json(os.path.join(ROOT, "BENCHMARK.json")), CELL)
    spec["config"].update(M=40, K=4)
    traffic = spec["traffic"]
    traffic.update(T=3000, warmup=750, reference_runs=12, seeds=12,
                   loads=[0.9], scenarios=[
                       s for s in traffic["scenarios"]
                       if s["name"] in ("rack_outage", "zipf_hotspot")])
    return spec


def _run(spec):
    import jax
    return harness.run_cell(spec, SEED, 0.0, False, jax.devices(), 0.0)


def test_reference_agrees_with_the_program_at_a_small_fleet():
    spec = _small_spec()
    out = _run(spec)
    assert out["correct"], out["checks"]
    assert out["checks"]["edge_gap"]["value"] < 1e-4
    limits = spec["traffic"]["limits"]
    for name in ("tasks_gap", "locality_gap"):
        assert out["checks"][name]["value"] <= limits[name]


def _outage_dropped(monkeypatch):
    """The program's ``rack_outage`` without its window."""
    from repro.scenarios.spec import SCENARIOS, Scenario
    monkeypatch.setitem(SCENARIOS, "rack_outage", Scenario("rack_outage"))


def _uniform_placement(monkeypatch):
    """The program draws every task's replicas uniformly, catalog or
    not."""
    from repro.core import simulator as sim
    from repro.core.cluster import sample_locals
    monkeypatch.setattr(
        sim, "sample_locals_scenario",
        lambda key, cluster, scen, batch, pe=0: sample_locals(key, cluster,
                                                              batch))


@pytest.mark.parametrize("fault", [_outage_dropped, _uniform_placement])
def test_a_fault_under_the_timed_path_is_not_correct(fault, monkeypatch):
    import jax
    fault(monkeypatch)
    jax.clear_caches()
    try:
        out = _run(_small_spec())
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert out["correct"] is False, out["checks"]


def test_a_result_on_fewer_devices_than_chips_fails_the_run(monkeypatch):
    import types

    import jax.numpy as jnp
    entry = harness.load_module("entries", "sweep_mesh")
    one_device = types.SimpleNamespace(mean_tasks_in_system=jnp.zeros(4))
    monkeypatch.setattr(entry, "_sweep_call", lambda state, seed0:
                        one_device)
    with pytest.raises(RuntimeError, match="4 chips"):
        entry._call(dict(devices=["tpu"] * 4), 7)
    assert entry._call(dict(devices=["tpu"]), 7) is one_device


def test_reference_refuses_a_catalog_row_that_repeats_a_server():
    with pytest.raises(ValueError):
        reference_scen.checked_catalog([[0, 1, 2], [3, 3, 4]], 40)
    with pytest.raises(ValueError):
        reference_scen.checked_catalog([[0, 1, 40]], 40)


def test_reference_closed_edge_averages_the_outage_over_the_run():
    segs = reference_scen.speed_segments(
        500, 10, 20000, (), [dict(rack=0, t0=0.45, t1=0.55, mult=0.0)])
    assert [(lo, hi) for lo, hi, _ in segs] == [(0, 9000), (9000, 11000),
                                                (11000, 20000)]
    assert reference_scen.closed_edge((0.01, 0.005, 0.002), segs, 20000) \
        == pytest.approx(4.95, rel=1e-12)


# ---------------------------------------------------------------------------
# per-device cell count on a synthetic trace
# ---------------------------------------------------------------------------

PHASE_US = dict(service=6.1, schedule=4.4, arrivals=7.6, candidates=1.5,
                route=0.2, accumulate=0.2)      # per cell-slot
KERNEL_US = 5.8


def _trace(chips: int, cells: int, slots: int) -> dict:
    """A reduced trace (``trace_reduce.reduce_profile``'s keys) of
    ``slots`` slots on ``chips`` devices whose launches serve ``cells``
    grid cells each, every phase and the kernel taking the same time per
    cell-slot whatever the split."""
    cell_slots = chips * cells * slots
    op_s = {f"{p}_fusion.1": PHASE_US[p] * 1e-6 * cell_slots
            for p in PHASE_US}
    op_s["route_commit.7"] = KERNEL_US * 1e-6 * cell_slots
    op_n = {name: chips * slots for name in op_s}
    busy_total = sum(op_s.values())
    return dict(window_s=busy_total / chips * 1.01,
                busy_s=busy_total / chips, op_s=op_s, op_n=op_n,
                n_device_events=sum(op_n.values()), truncated=False)


def test_per_device_cells_read_the_same_us_per_cell_slot(monkeypatch):
    import phase_time
    entry = harness.load_module("entries", "sweep_mesh")
    monkeypatch.setattr(phase_time, "phase_map", lambda: dict(
        {f"{p}_fusion.1": p for p in PHASE_US}, **{"route_commit.7":
                                                   "route"}))
    spec = harness.cell_spec(
        harness.load_json(os.path.join(ROOT, "BENCHMARK.json")), CELL)
    S, grid, slots = 4, 16, 1000
    readings = {}
    for chips in (1, 4):
        cells = entry.per_device_cells(S, grid, chips)
        assert cells == grid // chips
        win = dict(cells_per_launch=cells, chips=chips, M=500,
                   cell_slots=grid * slots, routed=2.2 * grid * slots)
        ctx = dict(trace=_trace(chips, cells, slots), window=win,
                   config=spec["config"], traffic=spec["traffic"],
                   device_kind="TPU v5 lite")
        readings[chips] = {
            m["name"]: harness.load_module("metrics", m["name"]).read(ctx)
            for m in spec["per_layer"] if m["source"] == "device_trace"}
    assert readings[1]["route_commit_us_per_slot.sweep"] == \
        pytest.approx(KERNEL_US)
    assert readings[1]["service_us_per_slot.sweep"] == \
        pytest.approx(PHASE_US["service"])
    for name, one in readings[1].items():
        assert one is not None, name
        assert readings[4][name] == pytest.approx(one, rel=1e-9), name


def test_per_device_cells_with_a_block_padded():
    entry = harness.load_module("entries", "sweep_mesh")
    # 3 scenarios on 2 devices: each device runs a block of 2 (the last
    # repeats a scenario), so a launch serves 2 of 3 scenarios' cells
    assert entry.per_device_cells(3, 12, 2) == 8
    assert entry.per_device_cells(4, 16, 8) == 4
    assert entry.per_device_cells(4, 16, 1) == 16
