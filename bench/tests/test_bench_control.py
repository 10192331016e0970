"""Each cell's control (the reference with one stated guarantee broken, in
the program's place) reads above a limit, and the reference in the
program's place reads within every limit, at the cell's own fleet and
length, on its first scenario at its highest load."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import checks  # noqa: E402
import control  # noqa: E402
import harness  # noqa: E402


def _cells():
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    return [w["name"] for w in bench["workloads"]]


def _high_load(workload):
    spec = harness.cell_spec(harness.load_json(os.path.join(
        harness.ROOT, "BENCHMARK.json")), workload)
    tr = spec["traffic"]
    tr.update(scenarios=tr["scenarios"][:1], loads=[max(tr["loads"])])
    return spec["config"], tr


def _over(checks):
    return [n for n, c in checks.items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("workload", _cells())
def test_control_fails_a_limit(workload):
    config, traffic = _high_load(workload)
    assert _over(control.readings(config, traffic, 4242))


@pytest.mark.parametrize("workload", _cells())
def test_reference_in_the_programs_place_passes(workload):
    config, traffic = _high_load(workload)
    entry = harness.load_module("entries", traffic["entry"])
    pk, pj = entry.reference_jobs(config, traffic, 4242, traffic["seeds"],
                                  first=1000)
    rk, rj = entry.reference_jobs(config, traffic, 4242,
                                  traffic["reference_runs"])
    outs = checks.run_jobs(pj + rj)
    win = checks.as_window(traffic, pk, outs[:len(pj)])
    assert not _over(checks.compare(traffic, win, rk, outs[len(pj):]))
