"""One-program mega-sweep (core.simulate_sweep): stacking contract,
single-compile guard across the FULL registry grid, bit-identical
equivalence with the looped path, per-cell telemetry views, and the
shard_map fallback."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import (
    Cluster,
    Rates,
    SimConfig,
    reset_trace_count,
    simulate_grid,
    simulate_sweep,
    sweep_grid,
    trace_count,
)
from repro.scenarios import (
    SCENARIOS,
    canonical_pad,
    scenario_names,
    stack_scenarios,
)
from repro.telemetry import TelemetryConfig, cell_view

CLUSTER = Cluster(M=16, K=4)
RATES = Rates(0.05, 0.025, 0.01)
# distinctive shapes so these tests cannot ride (or pollute) another
# test's jit cache entry — a collision would hide a retrace
CFG = SimConfig(T=112, warmup=32, route_mode="batched", s_max=16)


# ---------------------------------------------------------------------------
# stacking
# ---------------------------------------------------------------------------


def test_stack_scenarios_shapes_and_caps():
    names = ["uniform", "slow_rack", "zipf_hotspot"]
    stacked, caps = stack_scenarios(names, CLUSTER, RATES, CFG.T)
    assert caps.shape == (3,)
    assert np.all(caps > 0)
    for leaf in jax.tree_util.tree_leaves(stacked):
        assert leaf.shape[0] == 3
    # stacked rows == individually realized scenarios (same pad)
    from repro.scenarios import realize
    pad = canonical_pad(CLUSTER)
    single, _ = realize(SCENARIOS["slow_rack"], CLUSTER, RATES, CFG.T,
                        pad=pad)
    for got, want in zip(jax.tree_util.tree_leaves(stacked),
                         jax.tree_util.tree_leaves(single)):
        np.testing.assert_array_equal(np.asarray(got)[1], np.asarray(want))


def test_stack_scenarios_rejects_undersized_pad():
    pad = canonical_pad(CLUSTER)
    small = pad._replace(n_windows=1)   # straggler_wave needs 4
    with pytest.raises(ValueError, match="pad"):
        stack_scenarios(["uniform", "straggler_wave"], CLUSTER, RATES,
                        CFG.T, pad=small)


def test_sweep_grid_axes():
    names, stacked, lam, a_max = sweep_grid(CLUSTER, RATES, CFG,
                                            [0.4, 0.8])
    assert names == list(scenario_names())
    assert lam.shape == (len(names), 2)
    assert a_max >= 1
    # load axis scales the absolute rate per scenario capacity
    np.testing.assert_allclose(np.asarray(lam[:, 1]) / np.asarray(lam[:, 0]),
                               2.0, rtol=1e-5)


# ---------------------------------------------------------------------------
# the tentpole guards: one compile for the whole grid; cells bit-identical
# to the looped path
# ---------------------------------------------------------------------------


def test_full_registry_grid_is_one_program_per_policy():
    """trace_count advances by EXACTLY 1 per policy for the entire
    registry x loads x seeds grid — the mega-sweep's defining property."""
    loads = [0.4, 0.8]
    for algo in ("balanced_pandas_pod", "jsq_maxweight_pod"):
        reset_trace_count()
        names, res, _ = simulate_sweep(algo, CLUSTER, RATES, loads, 2, CFG)
        t = np.asarray(res.mean_completion_norm)
        assert t.shape == (len(SCENARIOS), 2, 2)
        assert np.isfinite(t).all()
        assert trace_count() == 1, \
            f"{algo}: grid retraced {trace_count()}x"


def test_sweep_cells_bit_identical_to_looped_grid():
    """Every cell of the one-program sweep equals the corresponding
    looped simulate_grid cell bit-for-bit.  The shared a_max matters:
    a different arrival-buffer width changes the PRNG draw shapes, so
    the looped baseline must be given the sweep's a_max."""
    names = ["uniform", "hetero_storm"]
    loads = [0.45, 0.85]
    pad = canonical_pad(CLUSTER)
    _, _, _, a_max = sweep_grid(CLUSTER, RATES, CFG, loads,
                                scenarios=names, pad=pad)
    _, res, _ = simulate_sweep("balanced_pandas_pod", CLUSTER, RATES,
                               loads, 2, CFG, scenarios=names, pad=pad,
                               a_max=a_max)
    swept = np.asarray(res.mean_completion_norm)          # [2, 2, 2]
    for s, name in enumerate(names):
        looped = simulate_grid("balanced_pandas_pod", CLUSTER, RATES,
                               loads, 2, CFG, scenario=name, pad=pad,
                               a_max=a_max)
        want = np.asarray(looped.mean_completion_norm)    # [seeds, loads]
        np.testing.assert_array_equal(swept[s], want, err_msg=name)


def test_sweep_telemetry_has_cell_leading_dims():
    tcfg = TelemetryConfig(sojourns=False)
    names = ["uniform", "slow_rack"]
    loads = [0.5]
    _, res, tele = simulate_sweep("balanced_pandas_pod", CLUSTER, RATES,
                                  loads, 2, CFG, scenarios=names,
                                  telemetry=tcfg)
    assert tele is not None
    assert np.asarray(tele.win).shape[:3] == (2, 2, 1)
    cell = cell_view(tele, (1, slice(None), 0))
    # the cell slab keeps the seed axis and drops scenario/load
    assert np.asarray(cell.win).shape[0] == 2
    np.testing.assert_array_equal(np.asarray(cell.win),
                                  np.asarray(tele.win)[1, :, 0])


def test_sweep_rejects_empty_scenarios():
    with pytest.raises(ValueError, match="empty"):
        stack_scenarios([], CLUSTER, RATES, CFG.T)


# ---------------------------------------------------------------------------
# shard_map path (forced multi-device CPU in a subprocess)
# ---------------------------------------------------------------------------

_SHARD_SCRIPT = r"""
import numpy as np
import jax
assert jax.device_count() == 2, jax.devices()
from repro.core import Cluster, Rates, SimConfig, simulate_sweep
cluster, rates = Cluster(M=16, K=4), Rates(0.05, 0.025, 0.01)
cfg = SimConfig(T=112, warmup=32, route_mode="batched", s_max=16)
# 3 scenarios on 2 devices: exercises the pad-and-drop uneven split
names = ["uniform", "slow_rack", "zipf_hotspot"]
_, sharded, _ = simulate_sweep("balanced_pandas_pod", cluster, rates,
                               [0.5], 2, cfg, scenarios=names)
_, single, _ = simulate_sweep("balanced_pandas_pod", cluster, rates,
                              [0.5], 2, cfg, scenarios=names,
                              devices=jax.devices()[:1])
a = np.asarray(sharded.mean_completion_norm)
b = np.asarray(single.mean_completion_norm)
assert a.shape == (3, 2, 1), a.shape
np.testing.assert_array_equal(a, b)
print("SHARD_OK")
"""


_REUSE_SCRIPT = r"""
import jax
from jax import monitoring
assert jax.device_count() == 2, jax.devices()
from repro.core import Cluster, Rates, SimConfig, simulate_sweep
cluster, rates = Cluster(M=16, K=4), Rates(0.05, 0.025, 0.01)
cfg = SimConfig(T=112, warmup=32, route_mode="batched", s_max=16)
run = lambda: jax.block_until_ready(simulate_sweep(
    "balanced_pandas", cluster, rates, [0.5], 2, cfg,
    scenarios=["uniform", "slow_rack"])[1])
run()
compiles = []
monitoring.register_event_duration_secs_listener(
    lambda event, secs, **kw: compiles.append(event)
    if event == "/jax/core/compile/backend_compile_duration" else None)
run()
assert not compiles, compiles
print("REUSE_OK")
"""


_MESH4_SCRIPT = r"""
import numpy as np
import jax
assert jax.device_count() == 4, jax.devices()
from repro.core import (Cluster, Rates, SimConfig, simulate_sweep,
                        sweep_devices)
from repro.core.simulator import sweep_op_phases
from repro.telemetry.spans import reset, spans
# the four scenarios of the m500-sweep-4chip cell at a small fleet
cluster, rates = Cluster(M=40, K=4), Rates(0.01, 0.005, 0.002)
cfg = SimConfig(T=400, warmup=100, route_mode="batched")
names = ["uniform", "slow_rack", "rack_outage", "zipf_hotspot"]
run = lambda devices: simulate_sweep(
    "balanced_pandas_pod", cluster, rates, [0.5, 0.9], 2, cfg, seed0=11,
    scenarios=names, devices=devices)[1]
reset()
sharded = run(None)
assert sweep_devices() == 4
assert len(spans("sweep.place")) == 1
assert len(sharded.mean_tasks_in_system.sharding.device_set) == 4
# the phase map is read from the sharded program just dispatched
assert {"service", "schedule", "arrivals", "candidates", "route",
        "accumulate"} <= set(sweep_op_phases().values())
single = run(jax.devices()[:1])
assert sweep_devices() == 1
assert len(spans("sweep.place")) == 1        # one device: nothing placed
for a, b in zip(sharded, single):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
assert np.asarray(sharded.mean_tasks_in_system).shape == (4, 2, 2)
print("MESH4_OK")
"""


def _run_on_host_devices(script: str, n: int = 2):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n}")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    return subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, env=env,
                          timeout=600)


def test_sharded_sweep_reuses_its_program():
    """A second sharded sweep over the same grid compiles nothing: the
    shard_mapped program is cached like the single-device one."""
    proc = _run_on_host_devices(_REUSE_SCRIPT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "REUSE_OK" in proc.stdout


def test_shard_map_matches_single_device():
    """With 2 forced host devices, the scenario axis shard_maps across
    them and the result is bit-identical to the single-device vmap —
    including the uneven (3 scenarios on 2 devices) pad-and-drop."""
    proc = _run_on_host_devices(_SHARD_SCRIPT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHARD_OK" in proc.stdout


def test_cell_scenarios_on_four_devices_match_one_device():
    """The m500-sweep-4chip grid's scenarios, the rack outage and the Zipf
    catalog among them, one per device of 4: every cell is bit-identical
    to the one-device sweep, the mesh is 4 wide, the call places its grid
    on it once (``sweep.place``), and the sharded program has a phase
    map."""
    proc = _run_on_host_devices(_MESH4_SCRIPT, 4)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "MESH4_OK" in proc.stdout
