"""Entry: ``core.simulator.simulate_sweep`` with the grid's scenario axis
over the cell's chips, one block of scenarios per chip.

Set-up and window are ``sweep.py``'s, loaded from that file: the same
warm call, the same back-to-back calls, the same ``sim_slots_per_s`` over
the whole grid.  Three things differ:

- Every call's result must live on as many devices as the cell has
  chips, read from the sharding of its arrays; a call whose result lives
  on fewer fails the run.  (The loaded copy of ``sweep.py`` calls the
  sweep through this module's ``_call``, which checks it.)
- ``cells_per_launch`` is per device: each chip launches ``route_commit``
  once a slot for its own scenarios, ``ceil(S / chips) * seeds * loads``
  cells, so the per-cell-slot readers divide a launch's time by those.
- The check runs ``reference_scen.py``, which knows speed windows, the
  Zipf catalog and the capacity edge (and hands the scenarios without
  either to ``reference.py``).  The program's catalog rows (replica
  triples) are handed to it as data; its edge it computes itself.  Besides ``checks.compare``'s ``tasks_gap`` and
  ``locality_gap`` it returns ``edge_gap``: |program edge - reference
  edge| / reference edge, the worst over the scenarios, where the
  program's edge is the one its sweep took the loads of.
"""
from __future__ import annotations

import importlib.util
import math
import multiprocessing
import os

import numpy as np

import checks
import reference
import reference_scen


def _load_sweep():
    """A private copy of ``entries/sweep.py``."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "sweep.py")
    spec = importlib.util.spec_from_file_location("bench_sweep_for_mesh",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_sweep = _load_sweep()
_sweep_call = _sweep._call
compile_count = _sweep.compile_count


def _call(state, seed0: int):
    """``sweep.py``'s call, failed unless its result lives on every chip
    of the cell."""
    res = _sweep_call(state, seed0)
    on = len(res.mean_tasks_in_system.sharding.device_set)
    if on != len(state["devices"]):
        raise RuntimeError(f"the sweep's result lives on {on} device(s), "
                           f"the cell has {len(state['devices'])} chips")
    return res


_sweep._call = _call


def _catalogs(config: dict, traffic: dict, stacked) -> list:
    """Per scenario, the program's catalog rows as numpy, or None where
    the traffic file gives the scenario uniform placement."""
    out = []
    for si, scen in enumerate(traffic["scenarios"]):
        if "zipf_s" not in scen:
            out.append(None)
            continue
        C = scen["chunks_per_server"] * config["M"]
        out.append(np.asarray(stacked.chunk_locals[si, :C]))
    return out


def _program_grid(state: dict):
    """The program's stacked grid for the cell's scenarios, and the
    capacity edge of each, as ``simulate_sweep`` builds them."""
    from repro.scenarios import stack_scenarios
    return stack_scenarios(state["scenarios"], state["cluster"],
                           state["rates"], state["cfg"].T)


def setup(config: dict, traffic: dict, seed: int, devices) -> dict:
    """``sweep.py``'s set-up (its warm call checked as above), then the
    program's edges and catalogs for the check."""
    state = _sweep.setup(config, traffic, seed, devices)
    stacked, edges = _program_grid(state)
    state["edges"] = [float(e) for e in edges]
    state["catalogs"] = _catalogs(config, traffic, stacked)
    return state


def per_device_cells(S: int, cells: int, chips: int) -> int:
    """Grid cells one device's launch serves when ``cells`` cells of ``S``
    scenarios are split by scenario over ``chips`` devices (the sweep
    repeats trailing scenarios to fill the last device's block)."""
    return math.ceil(S / min(chips, S)) * (cells // S)


def window(state: dict, seconds: float, seed: int) -> dict:
    """``sweep.py``'s window, with ``cells_per_launch`` per device and the
    program's edges and catalogs."""
    win = _sweep.window(state, seconds, seed)
    win["cells_per_launch"] = per_device_cells(
        len(state["scenarios"]), win["cells_per_launch"], win["chips"])
    win["edges"] = state["edges"]
    win["catalogs"] = state["catalogs"]
    return win


def reference_jobs(config: dict, traffic: dict, seed: int, runs: int,
                   first: int = 0, catalogs=None,
                   **override) -> tuple[list, list]:
    """Reference runs for every (scenario, load) of the grid, as
    ``sweep.reference_jobs`` lays them out; ``catalogs`` defaults to the
    program's (for ``control.py``, which runs no window)."""
    if catalogs is None:
        from repro.core import Cluster, Rates, SimConfig
        stacked, _ = _program_grid(dict(
            scenarios=[s["name"] for s in traffic["scenarios"]],
            cluster=Cluster(M=config["M"], K=config["K"],
                            n_replicas=config["n_replicas"]),
            rates=Rates(config["alpha"], config["beta"], config["gamma"]),
            cfg=SimConfig(T=traffic["T"])))
        catalogs = _catalogs(config, traffic, stacked)
    keys, jobs = [], []
    for si, scen in enumerate(traffic["scenarios"]):
        for li, load in enumerate(traffic["loads"]):
            for r in range(runs):
                keys.append((si, li, r))
                job = dict(
                    M=config["M"], K=config["K"],
                    n_replicas=config["n_replicas"],
                    rates=(config["alpha"], config["beta"], config["gamma"]),
                    load=load, T=traffic["T"], warmup=traffic["warmup"],
                    seed=(seed * 7919 + first + len(jobs)) % 2 ** 63,
                    d_rack=traffic["d_rack"], d_remote=traffic["d_remote"])
                job.update(_scenario_kw(config, scen, catalogs[si]))
                jobs.append(dict(job, **override))
    return keys, jobs


def _scenario_kw(config: dict, scen: dict, catalog) -> dict:
    """A scenario's reference arguments: ``reference.py``'s fixed
    ``speed`` where it has no window and no catalog, else
    ``reference_scen.py``'s rack speeds, windows and catalog."""
    if "windows" not in scen and catalog is None:
        return dict(speed=reference.server_speed(
            config["M"], config["K"], scen.get("rack_speeds", ())))
    return dict(rack_speeds=scen.get("rack_speeds", ()),
                windows=scen.get("windows", ()), catalog=catalog,
                zipf_s=scen.get("zipf_s", 0.0))


def run_jobs(jobs: list) -> list:
    """Run reference jobs in a pool of processes (numpy only, no chip)."""
    workers = max(1, min(len(jobs), (os.cpu_count() or 2) - 1))
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(workers) as pool:
        return pool.map(reference_scen.simulate_kw, jobs)


def check(config: dict, traffic: dict, win: dict, seed: int) -> dict:
    """Program vs reference for every (scenario, load) of the grid, and
    the program's capacity edges vs the reference's."""
    keys, jobs = reference_jobs(config, traffic, seed,
                                traffic["reference_runs"],
                                catalogs=win["catalogs"])
    got = checks.compare(traffic, win, keys, run_jobs(jobs))
    edge_gap = 0.0
    for si, scen in enumerate(traffic["scenarios"]):
        ref = reference_scen.capacity_edge(
            M=config["M"], K=config["K"], n_replicas=config["n_replicas"],
            rates=(config["alpha"], config["beta"], config["gamma"]),
            T=traffic["T"], rack_speeds=scen.get("rack_speeds", ()),
            windows=scen.get("windows", ()), catalog=win["catalogs"][si],
            zipf_s=scen.get("zipf_s", 0.0))
        edge_gap = max(edge_gap, float(abs(win["edges"][si] - ref) / ref))
    got["edge_gap"] = dict(value=edge_gap,
                           limit=traffic["limits"]["edge_gap"])
    return got
