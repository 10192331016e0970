"""Plain numpy reference of the slotted model under a failure and data
scenario: ``reference.py`` with speed windows, a Zipf catalog and a
capacity edge of its own.

Independent of the program under test: it imports nothing from ``repro``.
It keeps ``reference.py``'s semantics (racks, the three sub-queues, the
slot order, Balanced-Pandas-Pod's candidates and sequential commits) and
its candidate draws, and adds:

- **Speed windows.**  Rack r runs at ``rack_speeds[r]`` (1 where not
  given), times the ``mult`` of every window ``{"rack", "t0", "t1",
  "mult"}`` active at slot t, a window covering slots
  ``[round(t0 * T), round(t1 * T))``.  Speed is a server's own for all
  three classes.  A busy server does ``speed`` work units a slot; a
  server at speed 0 makes no progress and starts nothing.
- **Dead servers in routing.**  A server's inverse rate for a class is
  ``1 / (speed * rate)``, or +inf at speed 0.  A candidate's score is
  ``W * inv`` where ``inv`` is finite and +inf where not (never
  ``0 * inf``), and a queued task at a dead server adds 0 to its
  workload.  The best score wins; exact ties go to the lowest class and
  then to the lowest candidate slot (locals in their replica order, then
  the rack-local draws, then the remote draws), so a dead server is
  chosen only when every candidate is dead.
- **A Zipf catalog.**  ``catalog`` [C, n_replicas] is the data's
  placement, handed in as data: each row must hold distinct servers.  A
  task reads chunk c with probability proportional to ``(c + 1) **
  -zipf_s``, computed here, and its replicas are that chunk's row.
  Without a catalog each task's replicas are distinct uniform servers.
- **The capacity edge** that ``load`` is a share of: under uniform
  placement the time-averaged closed form ``alpha * mean_t sum_m
  speed_t[m]``; under a catalog the optimum of a fluid LP of its own
  (``lp_edge``).  Arrivals are Poisson(``load * edge``) a slot.

A scenario with neither windows nor a catalog is ``reference.py``'s own
(fixed per-rack speeds, uniform placement): ``simulate_kw`` hands such a
job to ``reference.simulate``, whose ties go to a random one of the
lowest class; with uniform placement the candidate slots are
exchangeable, so the two tie rules give the same law.
"""
from __future__ import annotations

import numpy as np

import reference
from reference import LOCAL, RACK, REMOTE


def speed_segments(M: int, K: int, T: int, rack_speeds=(),
                   windows=()) -> list:
    """``[(first slot, end slot, speed [M]), ...]`` covering ``[0, T)``:
    speed is constant within each segment."""
    R = M // K
    base = np.ones(K)
    base[:len(rack_speeds)] = rack_speeds
    cuts = sorted({0, T} | {int(round(w[k] * T)) for w in windows
                            for k in ("t0", "t1")})
    out = []
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        rack = base.copy()
        for w in windows:
            if int(round(w["t0"] * T)) <= lo < int(round(w["t1"] * T)):
                rack[w["rack"]] *= w["mult"]
        out.append((lo, hi, np.repeat(rack, R)))
    return out


def zipf_popularity(C: int, zipf_s: float) -> np.ndarray:
    """[C] probability of each catalog row: ``(c + 1) ** -zipf_s``,
    normalised."""
    p = np.arange(1, C + 1, dtype=np.float64) ** -float(zipf_s)
    return p / p.sum()


def checked_catalog(catalog, M: int) -> np.ndarray:
    """The catalog as int64 [C, n]; ValueError unless every row holds
    distinct servers of the fleet."""
    cat = np.asarray(catalog, np.int64)
    if cat.ndim != 2 or cat.shape[0] == 0:
        raise ValueError(f"catalog of shape {cat.shape}")
    if cat.min() < 0 or cat.max() >= M:
        raise ValueError("catalog names a server outside the fleet")
    srt = np.sort(cat, axis=1)
    if np.any(srt[:, 1:] == srt[:, :-1]):
        raise ValueError("a catalog row repeats a server")
    return cat


def closed_edge(rates, segments, T: int) -> float:
    """``alpha`` times the fleet's speed summed over servers, averaged
    over the run."""
    return rates[0] * sum((hi - lo) * s.sum() for lo, hi, s in segments) / T


def lp_edge(M: int, K: int, rates, segments, T: int, catalog,
            popularity) -> float:
    """The largest arrival rate whose chunk demand the fleet can serve.

    Variables, per segment and chunk: the time each of its replica
    servers spends on it (local rate), and the time each rack's servers
    together spend on it (rack-local rate in the racks holding a replica,
    remote elsewhere).  Constraints: each chunk's service, averaged over
    the segments, covers ``lam * popularity``; each server's local time
    is at most 1; each rack's time in all is at most its size.  Pooling a
    rack is exact here because speed is a rack's own: its servers serve a
    chunk's rack-local or remote work at one rate, and a share of rack
    time that can only land on the chunk's own replica serves it
    locally, at least as fast."""
    from scipy import sparse
    from scipy.optimize import linprog

    cat = np.asarray(catalog, np.int64)
    C, n = cat.shape
    R = M // K
    r_loc, r_rack, r_rem = rates
    held = np.zeros((C, K), bool)
    held[np.repeat(np.arange(C), n), (cat // R).ravel()] = True
    A_rows, A_cols, A_vals, caps = [], [], [], []
    n_var = 1                                   # variable 0 is lam
    row0 = C                                    # rows below C: demand
    for lo, hi, speed in segments:
        w = (hi - lo) / T
        rack_speed = speed[::R]
        # local time of each (chunk, replica)
        rate = r_loc * speed[cat]                               # [C, n]
        c_i, j_i = np.nonzero(rate > 0)
        v = n_var + np.arange(c_i.size)
        srv = cat[c_i, j_i]
        A_rows += [c_i, row0 + srv, row0 + M + srv // R]
        A_cols += [v, v, v]
        A_vals += [-w * rate[c_i, j_i], np.ones(v.size), np.ones(v.size)]
        n_var += v.size
        # pooled rack time of each (chunk, rack)
        rate = np.where(held, r_rack, r_rem) * rack_speed[None, :]  # [C, K]
        c_i, k_i = np.nonzero(rate > 0)
        v = n_var + np.arange(c_i.size)
        A_rows += [c_i, row0 + M + k_i]
        A_cols += [v, v]
        A_vals += [-w * rate[c_i, k_i], np.ones(v.size)]
        n_var += v.size
        caps += [np.ones(M), np.full(K, float(R))]
        row0 += M + K
    A_rows.append(np.arange(C))
    A_cols.append(np.zeros(C, np.int64))
    A_vals.append(np.asarray(popularity, np.float64))
    A = sparse.csr_matrix((np.concatenate(A_vals),
                           (np.concatenate(A_rows), np.concatenate(A_cols))),
                          shape=(row0, n_var))
    b = np.concatenate([np.zeros(C)] + caps)
    cost = np.zeros(n_var)
    cost[0] = -1.0
    res = linprog(cost, A_ub=A, b_ub=b, bounds=(0, None), method="highs")
    if res.status != 0:
        raise RuntimeError(f"capacity LP: {res.message}")
    return float(res.x[0])


def capacity_edge(*, M: int, K: int, n_replicas: int, rates, T: int,
                  rack_speeds=(), windows=(), catalog=None,
                  zipf_s: float = 0.0) -> float:
    """The scenario's capacity edge (module docstring)."""
    segments = speed_segments(M, K, T, rack_speeds, windows)
    if catalog is None:
        return closed_edge(rates, segments, T)
    catalog = checked_catalog(catalog, M)
    if catalog.shape[1] != n_replicas:
        raise ValueError(f"catalog rows hold {catalog.shape[1]} servers, "
                         f"not {n_replicas}")
    return lp_edge(M, K, rates, segments, T, catalog,
                   zipf_popularity(catalog.shape[0], zipf_s))


def simulate_kw(kw: dict) -> dict:
    """For a process pool: ``simulate(**kw)`` for a job with speed windows
    or a catalog, ``reference.simulate_kw(kw)`` for one that
    ``reference.py`` states (fixed speeds, uniform placement)."""
    if "windows" in kw or "catalog" in kw:
        return simulate(**kw)
    return reference.simulate_kw(kw)


def simulate(*, M: int, K: int, n_replicas: int, rates, load: float, T: int,
             warmup: int, seed: int, d_rack: int, d_remote: int,
             rack_speeds=(), windows=(), catalog=None,
             zipf_s: float = 0.0) -> dict:
    """One Balanced-Pandas-Pod run of the scenario (module docstring);
    returns ``reference.py``'s post-warmup means."""
    rng = np.random.default_rng(seed)
    segments = speed_segments(M, K, T, rack_speeds, windows)
    edge = capacity_edge(M=M, K=K, n_replicas=n_replicas, rates=rates, T=T,
                         rack_speeds=rack_speeds, windows=windows,
                         catalog=catalog, zipf_s=zipf_s)
    if catalog is not None:
        catalog = np.asarray(catalog, np.int64)
        popularity = zipf_popularity(catalog.shape[0], zipf_s)
    counts = rng.poisson(load * edge, T)
    total = int(counts.sum())
    if catalog is None:
        locs = reference._draw_locals(rng, M, n_replicas, total)
    else:
        locs = catalog[rng.choice(catalog.shape[0], size=total,
                                  p=popularity)]
    return _run(rng, counts, locs, M=M, K=K, rates=rates, segments=segments,
                warmup=warmup, d_rack=d_rack, d_remote=d_remote)


def _run(rng, counts, locs, *, M, K, rates, segments, warmup, d_rack,
         d_remote) -> dict:
    """``reference._run``'s slot loop for pod routing, with the speed of
    the slot's segment and the dead-server rules of the module
    docstring."""
    T = counts.shape[0]
    n_replicas = locs.shape[1]
    R = M // K
    rates = np.asarray(rates, np.float64)
    racks = locs // R
    cand = np.concatenate([
        locs, reference._draw_in_racks(rng, racks, R, locs, d_rack),
        reference._draw_outside_racks(rng, racks, K, R, d_remote)], axis=1)
    ccls = np.array([LOCAL] * n_replicas + [RACK] * d_rack
                    + [REMOTE] * d_remote)
    u_dur = rng.random((T, M))

    Q = np.zeros((M, 3), np.int64)
    busy = np.zeros(M, bool)
    rem = np.zeros(M)
    log1mp = np.log1p(-rates)
    ar = np.arange(M)
    sum_n = arrivals = completions = 0.0
    starts = np.zeros(3)
    j = 0
    for lo, hi, speed in segments:
        rate_m = speed[:, None] * rates[None, :]
        live = rate_m > 0
        # inverse rates, 0 where dead: a dead server's queue adds nothing
        # to its workload, and as a candidate it scores +inf
        inv_w = np.where(live, 1.0 / np.where(live, rate_m, 1.0), 0.0)
        servable = speed > 0
        for t in range(lo, hi):
            measure = t >= warmup
            # service progress and completions
            rem = np.where(busy, rem - speed, 0.0)
            done = busy & (rem <= 0)
            busy &= ~done
            # scheduling: idle live servers start their first non-empty
            # class
            has = (Q > 0) & servable[:, None]
            start = ~busy & has.any(1)
            pick = np.argmax(has, 1)
            Q[ar[start], pick[start]] -= 1
            u = np.clip(u_dur[t], 1e-7, 1.0 - 1e-7)
            dur = np.maximum(np.ceil(np.log1p(-u) / log1mp[pick]), 1.0)
            rem = np.where(start, dur, rem)
            busy |= start
            # arrivals, routed in order, each seeing the commits before it
            W = (Q * inv_w).sum(1)
            n_t = int(counts[t])
            for i in range(j, j + n_t):
                ok = live[cand[i], ccls]
                sc = np.where(ok, W[cand[i]] * inv_w[cand[i], ccls], np.inf)
                k = int(np.argmin(sc))       # first best: lowest class, slot
                m, c = int(cand[i, k]), int(ccls[k])
                Q[m, c] += 1
                W[m] += inv_w[m, c]
            j += n_t
            if measure:
                sum_n += Q.sum() + busy.sum()
                arrivals += n_t
                completions += done.sum()
                starts += np.bincount(pick[start], minlength=3)
    slots = max(T - warmup, 1)
    return dict(mean_tasks=sum_n / slots, arrival_rate=arrivals / slots,
                throughput=completions / slots,
                locality=starts / max(starts.sum(), 1.0))
