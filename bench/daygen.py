"""Generator of a production-shaped arrival day, from a traffic file's
parameters and a seed (a copy of the program's ``repro.trace.synth``
recipe, kept with the benchmark so that no later change to the program
changes the traffic).

The day layers a diurnal base, flash crowds (short multiplicative spikes),
placement churn (at each boundary the popularity rank -> chunk id mapping
is reshuffled), Zipf chunk popularity and mean-1 lognormal task sizes.
Timestamps come from inverse-CDF sampling of the integrated intensity.
Returns plain arrays: sorted times in [0, horizon), chunk ids, sizes and
the tenant of each task.
"""
from __future__ import annotations

import numpy as np

_GRID = 4096          # intensity-integration resolution


def day(p: dict, seed: int) -> dict:
    """One day from the parameters ``p`` (see ``bench/traffic/*.json``)."""
    rng = np.random.default_rng(seed)
    n, horizon = int(p["n_tasks"]), float(p["horizon"])

    x = (np.arange(_GRID) + 0.5) / _GRID
    lam = 1.0 + p["diurnal_amp"] * np.sin(
        2.0 * np.pi * p["diurnal_cycles"] * x - 0.5 * np.pi)
    for _ in range(p["n_flash"]):
        f0 = rng.uniform(0.05, 0.95 - p["flash_frac"])
        lam = np.where((x >= f0) & (x < f0 + p["flash_frac"]),
                       lam * p["flash_peak"], lam)
    lam = np.maximum(lam, 0.02)
    cdf = np.concatenate([[0.0], np.cumsum(lam)])
    cdf /= cdf[-1]
    u = np.sort(rng.random(n))
    t = np.interp(u, cdf, np.arange(_GRID + 1) / _GRID) * horizon
    t = np.minimum(t, np.nextafter(horizon, 0.0))

    n_chunks = p["n_chunks"]
    pop = np.arange(1, n_chunks + 1, dtype=np.float64) ** (-p["zipf_s"])
    pop /= pop.sum()
    ranks = rng.choice(n_chunks, size=n, p=pop)
    churn_t = tuple(float(c) for c in p["churn_t"])
    bounds = np.asarray((0.0, *churn_t, 1.0)) * horizon
    epoch = np.clip(np.searchsorted(bounds, t, side="right") - 1,
                    0, len(churn_t))
    chunk = np.empty(n, np.int64)
    for e in range(len(churn_t) + 1):
        perm = rng.permutation(n_chunks)
        m = epoch == e
        chunk[m] = perm[ranks[m]]

    sigma = p["size_sigma"]
    z = rng.standard_normal(n)
    size = np.exp(sigma * z - 0.5 * sigma ** 2).astype(np.float32)
    tp = np.arange(1, p["n_tenants"] + 1, dtype=np.float64) ** -1.0
    tenant = rng.choice(p["n_tenants"], size=n,
                        p=tp / tp.sum()).astype(np.int32)
    return dict(t=t, chunk=chunk, size=size, tenant=tenant, epoch=epoch,
                horizon=horizon, churn_t=churn_t)
