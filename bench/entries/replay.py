"""Entry: ``trace.replay.ReplayEngine.run``, a recorded day replayed through
the fused router, with its host loop of chunk dispatches and
double-buffered host-to-device copies.

Set-up makes the day from ``--seed`` (``bench/daygen.py`` with the traffic
file's parameters; every seed gives the same number of tasks), builds the
engine (ingest, binning, packing) and warms it with one run.  The window
calls ``run`` back to back, each with its own seed; ``replay_tasks_per_s``
is the tasks of every run in the window over the time from the window's
start to the end of the last run.

The check replays the same day in the plain reference
(``bench/reference.py``) ``reference_runs`` times, each with its own
placement of the day's chunks, and compares the mean tasks in the system
and the locality mix with the program's, averaged over its runs.
"""
from __future__ import annotations

import time

import numpy as np

import checks
import daygen

_SEED_STRIDE = 1_000_003


def _seed(seed: int, i: int) -> int:
    """Run seed of window run ``i`` (-1: the set-up run); fits int32."""
    return (seed * _SEED_STRIDE + i + 1) % (2 ** 31 - 1)


def compile_count() -> int:
    """Traces of the replay's jitted chunk step so far (the program's own
    counter)."""
    from repro.trace import replay_trace_count
    return replay_trace_count()


def _log(traffic: dict, seed: int):
    from repro.trace import ArrivalLog
    d = daygen.day(traffic["day"], seed)
    return ArrivalLog(name=traffic["name"], horizon=d["horizon"], t=d["t"],
                      chunk=d["chunk"], size=d["size"], tenant=d["tenant"],
                      churn_t=d["churn_t"])


def setup(config: dict, traffic: dict, seed: int, devices) -> dict:
    """Make the day, build the engine, warm it with one run."""
    import jax
    from repro.core import Cluster, Rates, SimConfig
    from repro.trace import ReplayEngine

    if list(devices) != jax.devices()[:1]:
        raise ValueError("the replay engine runs on the default device")
    log = _log(traffic, seed)
    eng = ReplayEngine(
        log, Cluster(M=config["M"], K=config["K"],
                     n_replicas=config["n_replicas"]),
        Rates(config["alpha"], config["beta"], config["gamma"]),
        cfg=SimConfig(T=traffic["T"], warmup=traffic["warmup"]),
        algo=traffic["algo"], chunk_slots=traffic["chunk_slots"],
        chunks_per_server=traffic["chunks_per_server"])
    eng.run(seed=_seed(seed, -1))
    return dict(eng=eng, log=log)


def window(state: dict, seconds: float, seed: int) -> dict:
    """Back-to-back replays for ``seconds``."""
    import jax

    eng = state["eng"]
    results = []
    t0 = time.perf_counter()
    i = 0
    while True:
        with jax.profiler.TraceAnnotation("bench.replay.run"):
            r = eng.run(seed=_seed(seed, i))
        results.append(r.result)
        i += 1
        if time.perf_counter() - t0 >= seconds:
            break
    wall = time.perf_counter() - t0
    n = state["log"].n_tasks * len(results)
    return dict(
        cells_per_launch=1, chips=1,
        end_to_end=dict(replay_tasks_per_s=n / wall),
        attempted=n, failed=0,
        mean_tasks=np.asarray([float(r.mean_tasks_in_system)
                               for r in results]).reshape(-1, 1, 1, 1),
        locality=np.asarray([np.asarray(r.locality_fractions)
                             for r in results]).reshape(-1, 1, 1, 1, 3))


def reference_jobs(config: dict, traffic: dict, seed: int, runs: int,
                   first: int = 0, **override) -> tuple[list, list]:
    """Reference replays of the day of ``traffic``/``seed``: returns
    ((0, 0, run) per job, keyword arguments per job)."""
    d = daygen.day(traffic["day"], seed)
    T = traffic["T"]
    slots = np.clip(np.floor(d["t"] / d["horizon"] * T).astype(np.int64),
                    0, T - 1)
    # the chunk -> data mapping changes at each churn boundary: a chunk id
    # in another epoch is other data
    key = d["epoch"] * traffic["day"]["n_chunks"] + d["chunk"]
    sigma = float(np.std(np.log(np.asarray(d["size"], np.float64))))
    keys, jobs = [], []
    for r in range(runs):
        keys.append((0, 0, r))
        jobs.append(dict(dict(
            M=config["M"], K=config["K"], n_replicas=config["n_replicas"],
            rates=(config["alpha"], config["beta"], config["gamma"]),
            slots=slots, chunk=key, size_sigma=sigma, T=T,
            warmup=traffic["warmup"], seed=(seed * 7919 + first + r) % 2 ** 63,
            d_rack=traffic.get("d_rack", 0),
            d_remote=traffic.get("d_remote", 0),
            full=traffic["algo"] == "balanced_pandas"), **override))
    return keys, jobs


def check(config: dict, traffic: dict, win: dict, seed: int) -> dict:
    """Program vs reference on the day of ``seed``."""
    keys, jobs = reference_jobs(config, traffic, seed,
                                traffic["reference_runs"])
    return checks.compare(traffic, win, keys, checks.run_jobs(jobs))
