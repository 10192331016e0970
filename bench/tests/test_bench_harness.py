"""The benchmark harness on the CPU: files found by name, the window loop
at a tiny fleet, the result line's keys, and faults planted under the
timed path that the check must catch."""
import json
import os
import re
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]

import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "checks"]


def _bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _tiny_spec(workload="m500-bppod-sweep", **traffic):
    """The cell's spec at a fleet small enough for the CPU."""
    spec = harness.cell_spec(_bench(), workload)
    spec["config"].update(M=40, K=4)
    spec["traffic"].update(dict(T=3000, warmup=750, reference_runs=12,
                                scenarios=[{"name": "uniform",
                                            "rack_speeds": []}],
                                loads=[0.5], seeds=12), **traffic)
    return spec


def test_every_cell_resolves_its_files_by_name():
    bench = _bench()
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        assert NAME.match(cell["name"]) and len(cell["why"]) <= 200
        config = harness.load_json(os.path.join(ROOT,
                                            configs[cell["config"]]["file"]))
        assert config["name"] == cell["config"]
        traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             cell["traffic"] + ".json"))
        assert traffic["name"] == cell["traffic"]
        entry = harness.load_module("entries", traffic["entry"])
        for fn in ("setup", "window", "check", "compile_count"):
            assert callable(getattr(entry, fn))
        spec = harness.cell_spec(bench, cell["name"])
        assert any(m["name"] == "setup_s" for m in spec["end_to_end"])
        assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]
        for m in spec["per_layer"]:
            assert callable(harness.load_module("metrics", m["name"]).read)


def test_benchmark_json_keeps_the_contract_shapes():
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert set(m["workloads"]) <= cells
    for c in bench["configs"]:
        assert c["file"].startswith("bench/")
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert len(json.dumps(bench)) < 64 * 1024


def _tiny_traffic(name):
    """A traffic file of the benchmark, cut to a fleet of 40 servers."""
    traffic = harness.load_json(
        os.path.join(BENCH, "traffic", name + ".json"))
    if traffic["entry"] == "sweep":
        traffic.update(T=300, warmup=100, seeds=2)
    else:
        traffic.update(T=600, warmup=100, chunk_slots=100)
        traffic["day"] = dict(traffic["day"], n_tasks=108)
    return traffic


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))))
def test_window_loop_runs_at_a_tiny_fleet(name):
    import jax
    config = dict(harness.load_json(os.path.join(BENCH, "configs",
                                             "paper_m500.json")), M=40, K=4)
    traffic = _tiny_traffic(name)
    entry = harness.load_module("entries", traffic["entry"])
    state = entry.setup(config, traffic, 3, jax.devices())
    p0 = entry.compile_count()
    win = entry.window(state, 0.5, 3)
    assert entry.compile_count() == p0          # nothing traced again
    n = win["mean_tasks"].shape[0]
    assert n >= 1 and win["locality"].shape[0] == n
    assert win["attempted"] > 0 and win["failed"] == 0
    assert all(v > 0 for v in win["end_to_end"].values())
    np.testing.assert_allclose(win["locality"].sum(-1), 1.0, rtol=1e-5)


@pytest.fixture(scope="module")
def sound():
    import jax
    jax.clear_caches()
    return harness.run_cell(_tiny_spec(), 2 ** 33 + 17, 0.0, False,
                        jax.devices(), 0.0)


def test_result_line_has_the_contract_keys(sound):
    assert list(sound) == RESULT_KEYS
    assert set(sound["metrics"]) == {"sim_slots_per_s", "setup_s"}
    assert set(sound["device"]) == {"platform", "kind", "count",
                                    "memory_peak_bytes"}
    assert json.loads(json.dumps(sound)) == sound
    for c in sound["checks"].values():
        assert set(c) == {"value", "limit"}


def test_sound_run_at_a_tiny_fleet_is_correct(sound):
    assert sound["correct"], sound["checks"]


def _state_unchanged(sim, monkeypatch):
    orig = sim._bp_step

    def step(state, sums, key, **kw):
        _, sums, tele = orig(state, sums, key, **kw)
        return state, sums, tele
    monkeypatch.setattr(sim, "_bp_step", step)


def _half_batch(sim, monkeypatch):
    import jax.numpy as jnp
    orig = sim.kernel_route_commit

    def kernel(Q, valid, inv, **kw):
        keep = jnp.arange(valid.shape[-1]) % 2 == 0
        return orig(Q, valid & keep, inv, **kw)
    monkeypatch.setattr(sim, "kernel_route_commit", kernel)


def _answer_altered(sim, monkeypatch):
    import jax.numpy as jnp
    orig = sim.kernel_route_commit

    def kernel(Q, valid, inv, **kw):
        _, W, sel, cls, val = orig(Q, valid, inv, **kw)
        cls = (cls + 1) % 3
        Q = Q.at[sel, cls].add(valid.astype(jnp.int32))
        return Q, W, sel, cls, val
    monkeypatch.setattr(sim, "kernel_route_commit", kernel)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
def test_a_fault_under_the_timed_path_is_not_correct(fault, monkeypatch):
    import jax
    from repro.core import simulator as sim
    fault(sim, monkeypatch)
    jax.clear_caches()
    try:
        out = harness.run_cell(_tiny_spec(), 2 ** 33 + 17, 0.0, False,
                           jax.devices(), 0.0)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert out["correct"] is False, out["checks"]
