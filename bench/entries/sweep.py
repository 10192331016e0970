"""Entry: ``core.simulator.simulate_sweep``, the one-program policy sweep.

Set-up builds the fleet and warms the program with one call of the cell's
own grid (a seed outside the window's).  The window calls the sweep back
to back, each call with a fresh ``seed0`` drawn from ``--seed`` and the
call's index, and blocks on each result.  ``sim_slots_per_s`` is every
cell-slot (scenarios x seeds x loads x T) of the calls run, over the time
from the window's start to the end of the last call.

The check compares, for every (scenario, load) of the grid, the program's
mean tasks in system and locality mix of service starts, averaged over all
seeds of all calls in the window, with the plain reference
(``bench/reference.py``) run ``reference_runs`` times at the same fleet,
policy, scenario, load, T and warmup.
"""
from __future__ import annotations

import time

import numpy as np

import checks
import reference

_SEED_STRIDE = 1_000_003


def _seed0(seed: int, i: int) -> int:
    """Sweep ``seed0`` of call ``i`` (-1: the set-up call); fits int32."""
    return (seed * _SEED_STRIDE + i + 1) % (2 ** 31 - 1)


def compile_count() -> int:
    """Traces of the simulator's jitted step so far (the program's own
    counter)."""
    from repro.core.simulator import trace_count
    return trace_count()


def _call(state, seed0: int):
    """One sweep, blocked on; the host spans name its two parts."""
    import jax
    from repro.core.simulator import simulate_sweep
    with jax.profiler.TraceAnnotation("bench.sweep.dispatch"):
        names, res, _ = simulate_sweep(
            state["algo"], state["cluster"], state["rates"], state["loads"],
            state["seeds"], cfg=state["cfg"], pod=state["pod"], seed0=seed0,
            scenarios=state["scenarios"], devices=state["devices"])
    if names != state["scenarios"]:
        raise RuntimeError(f"sweep returned scenarios {names}")
    with jax.profiler.TraceAnnotation("bench.sweep.wait"):
        return jax.block_until_ready(res)


def setup(config: dict, traffic: dict, seed: int, devices) -> dict:
    """Build the fleet and the grid; warm the program with one call."""
    from repro.core import Cluster, Rates, SimConfig
    from repro.core.policies import PodSpec

    pod = (PodSpec(traffic["d_rack"], traffic["d_remote"])
           if traffic["algo"] == "balanced_pandas_pod" else None)
    state = dict(
        algo=traffic["algo"], pod=pod, devices=list(devices),
        cluster=Cluster(M=config["M"], K=config["K"],
                        n_replicas=config["n_replicas"]),
        rates=Rates(config["alpha"], config["beta"], config["gamma"]),
        cfg=SimConfig(T=traffic["T"], warmup=traffic["warmup"],
                      route_mode=traffic["route_mode"]),
        loads=tuple(traffic["loads"]), seeds=traffic["seeds"],
        scenarios=[s["name"] for s in traffic["scenarios"]])
    _call(state, _seed0(seed, -1))
    return state


def window(state: dict, seconds: float, seed: int) -> dict:
    """Back-to-back sweeps for ``seconds``; returns what ``check`` and the
    metric readers need, on the host."""
    S, L = len(state["scenarios"]), len(state["loads"])
    per_call = S * state["seeds"] * L * state["cfg"].T
    results, spans = [], []
    t0 = time.perf_counter()
    i = 0
    while True:
        ts = time.perf_counter()
        res = _call(state, _seed0(seed, i))
        te = time.perf_counter()
        results.append(res)
        spans.append((ts, te))
        i += 1
        if te - t0 >= seconds:
            break
    wall = spans[-1][1] - t0

    def stack(field):
        return np.stack([np.asarray(getattr(r, field)) for r in results])

    cfg = state["cfg"]
    measured = cfg.T - cfg.warmup
    arrivals = stack("arrival_rate_hat") * measured          # [n, S, K, L]
    clip = stack("clip_fraction")
    offered = arrivals / np.maximum(1.0 - clip, 1e-12)
    return dict(
        cell_slots=per_call * len(results),
        cells_per_launch=per_call // cfg.T, chips=len(state["devices"]),
        # valid arrivals routed, the measured rate taken over all T slots
        routed=float((stack("arrival_rate_hat") * cfg.T).sum()),
        spans=spans,
        end_to_end=dict(sim_slots_per_s=per_call * len(results) / wall),
        attempted=int(round(float(offered.sum()))),
        failed=int(round(float((offered - arrivals).sum()))),
        mean_tasks=stack("mean_tasks_in_system"),             # [n, S, K, L]
        locality=stack("locality_fractions"),                 # [n, S, K, L, 3]
        M=state["cluster"].M)


def reference_jobs(config: dict, traffic: dict, seed: int, runs: int,
                   first: int = 0, **override) -> tuple[list, list]:
    """Reference runs for every (scenario, load) of the grid: returns
    ((scenario index, load index, run) per job, keyword arguments per
    job).  ``override`` replaces reference arguments (a control)."""
    keys, jobs = [], []
    for si, scen in enumerate(traffic["scenarios"]):
        speed = reference.server_speed(config["M"], config["K"],
                                       scen.get("rack_speeds", ()))
        for li, load in enumerate(traffic["loads"]):
            for r in range(runs):
                keys.append((si, li, r))
                jobs.append(dict(dict(
                    M=config["M"], K=config["K"],
                    n_replicas=config["n_replicas"],
                    rates=(config["alpha"], config["beta"], config["gamma"]),
                    speed=speed, load=load, T=traffic["T"],
                    warmup=traffic["warmup"],
                    seed=(seed * 7919 + first + len(jobs)) % 2 ** 63,
                    d_rack=traffic.get("d_rack", 0),
                    d_remote=traffic.get("d_remote", 0),
                    full=traffic["algo"] == "balanced_pandas"), **override))
    return keys, jobs


def check(config: dict, traffic: dict, win: dict, seed: int) -> dict:
    """Program vs reference for every (scenario, load) of the grid."""
    keys, jobs = reference_jobs(config, traffic, seed,
                                traffic["reference_runs"])
    return checks.compare(traffic, win, keys, checks.run_jobs(jobs))
