"""What the entries share for ``correct``: reference runs in a pool of
processes, and the comparison of the program's statistics with the
reference's, each number beside its limit from the traffic file.

The numbers compared, each the worst over the (scenario, load) groups:

- ``tasks_gap``: |program - reference| / reference of the mean number of
  tasks in the system, each side averaged over all its runs;
- ``locality_gap``: the largest absolute difference of the locality mix of
  service starts (local, rack-local, remote fractions), averaged alike.
"""
from __future__ import annotations

import numpy as np

import reference


def run_jobs(jobs: list) -> list:
    """Run reference jobs in a pool of processes (numpy only, no chip)."""
    import multiprocessing
    import os

    workers = max(1, min(len(jobs), (os.cpu_count() or 2) - 1))
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(workers) as pool:
        return pool.map(reference.simulate_kw, jobs)


def as_window(traffic: dict, keys, outs) -> dict:
    """Reference outputs laid out as a window's arrays (one call, the runs
    as its seeds), so that a control takes the program's place."""
    S, L = len(traffic["scenarios"]), len(traffic["loads"])
    R = 1 + max(k[2] for k in keys)
    n = np.zeros((1, S, R, L))
    loc = np.zeros((1, S, R, L, 3))
    for (si, li, r), o in zip(keys, outs):
        n[0, si, r, li] = o["mean_tasks"]
        loc[0, si, r, li] = o["locality"]
    return dict(mean_tasks=n, locality=loc)


def compare(traffic: dict, win: dict, keys, outs) -> dict:
    """The numbers compared (module docstring) with their limits.  ``win``
    holds the program's ``mean_tasks`` [n, S, K, L] and ``locality``
    [n, S, K, L, 3]; ``keys`` names each reference output's (s, l, run)."""
    ref_n, ref_loc = {}, {}
    for (si, li, _), o in zip(keys, outs):
        ref_n.setdefault((si, li), []).append(o["mean_tasks"])
        ref_loc.setdefault((si, li), []).append(o["locality"])
    tasks_gap = locality_gap = 0.0
    for (si, li), ns in ref_n.items():
        rn = float(np.mean(ns))
        pn = float(np.mean(win["mean_tasks"][:, si, :, li]))
        rl = np.mean(ref_loc[(si, li)], axis=0)
        pl = np.mean(win["locality"][:, si, :, li], axis=(0, 1))
        tasks_gap = max(tasks_gap, abs(pn - rn) / rn)
        locality_gap = max(locality_gap, float(np.max(np.abs(pl - rl))))
    lim = traffic["limits"]
    return dict(tasks_gap=dict(value=tasks_gap, limit=lim["tasks_gap"]),
                locality_gap=dict(value=locality_gap,
                                  limit=lim["locality_gap"]))
