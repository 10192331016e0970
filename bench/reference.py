"""Plain numpy reference of the Balanced-Pandas slotted model (paper §III-IV).

Independent of the program under test: it imports nothing from ``repro``
and takes nothing the program made.  The semantics it restates:

- M servers in K racks of M/K, contiguous by rack.  A task's data lives on
  ``n_replicas`` distinct servers chosen uniformly ("local"); other servers
  of those racks are "rack-local", the rest "remote".
- Each server keeps three sub-queues (local, rack-local, remote) counted in
  tasks.  A slot runs, in order: service progress (a busy server does
  ``speed[m]`` work units; a task whose remaining work reaches 0 completes),
  scheduling (an idle server starts the head of its first non-empty
  sub-queue, local > rack > remote, its work a geometric duration at the
  class's base rate), arrivals (Poisson(lam) tasks, lam = load * alpha *
  sum(speed)), and routing.  The tasks in the system are counted at the
  end of each slot.
- Routing (Balanced-Pandas): the workload of server m is
  ``W[m] = sum_c Q[m, c] / rate[m, c]`` with ``rate[m, c] = speed[m] *
  rate_c``; a task goes to the candidate minimising ``W[m] / rate[m, cls]``
  for its locality class there.  Candidates are every server (full) or the
  locals plus ``d_rack`` rack-local and ``d_remote`` remote servers drawn
  uniformly with replacement (pod).  Arrivals route one after another, so
  each sees the commits of the ones before it in the slot.  Exact ties go
  to the faster class, then uniformly at random: full routing takes the
  lowest of a random priority drawn for every server once per slot, pod
  routing the first of its randomly drawn candidates.

Only the counts that the program's summary also reports are kept: the mean
number of tasks in the system, completions and arrivals per slot, and the
locality mix of service starts, all over the slots after ``warmup``.

``snapshot=True`` is a control, not the model: every arrival of a slot
scores against the workloads at the start of the slot, as a router that
skips the in-slot commit would; full routing then sends the arrivals of a
slot that tie on their best score to one server.
"""
from __future__ import annotations

import numpy as np

LOCAL, RACK, REMOTE = 0, 1, 2


def server_speed(M: int, K: int, rack_speeds=()) -> np.ndarray:
    """[M] speed multipliers: rack i runs at ``rack_speeds[i]``, the rest
    at 1."""
    speed = np.ones(M)
    R = M // K
    for r, s in enumerate(rack_speeds):
        speed[r * R:(r + 1) * R] = s
    return speed


def _draw_locals(rng, M: int, n: int, size: int) -> np.ndarray:
    """[size, n] distinct uniform servers per task (sequential skip)."""
    out = np.empty((size, n), np.int64)
    for i in range(n):
        d = rng.integers(0, M - i, size)
        prev = np.sort(out[:, :i], axis=1)
        for j in range(i):
            d = d + (d >= prev[:, j])
        out[:, i] = d
    return out


def _draw_in_racks(rng, racks: np.ndarray, R: int, exclude: np.ndarray,
                   k: int) -> np.ndarray:
    """k uniform draws per row from the servers of ``racks`` [N, n] (a
    rack repeated in a row counts once) other than ``exclude`` [N, n]."""
    N, n = racks.shape
    out = np.empty((N, k), np.int64)
    for j in range(k):
        todo = np.arange(N)
        while todo.size:
            r = racks[todo, rng.integers(0, n, todo.size)]
            # a rack listed twice would be drawn twice as often: redraw
            # so each distinct rack carries equal weight
            dup = (racks[todo] == r[:, None]).sum(1)
            keep = rng.random(todo.size) < 1.0 / dup
            s = r * R + rng.integers(0, R, todo.size)
            ok = keep & ~(exclude[todo] == s[:, None]).any(1)
            out[todo[ok], j] = s[ok]
            todo = todo[~ok]
    return out


def _draw_outside_racks(rng, racks: np.ndarray, K: int, R: int,
                        k: int) -> np.ndarray:
    """k uniform draws per row from the servers outside ``racks``."""
    N = racks.shape[0]
    out = np.empty((N, k), np.int64)
    for j in range(k):
        todo = np.arange(N)
        while todo.size:
            r = rng.integers(0, K, todo.size)
            ok = ~(racks[todo] == r[:, None]).any(1)
            out[todo[ok], j] = r[ok] * R + rng.integers(0, R, ok.sum())
            todo = todo[~ok]
    return out


def _route_full(W, sc_r, rack_min, inv_m, loc, racks, R: int, prio):
    """Full Balanced-Pandas decision (server, class) over every server:
    the locals, the rest of their racks, and the remote servers, whose
    best score is found through the per-rack minima ``rack_min``.  Ties
    within the best class go to the lowest ``prio``."""
    zero = [x for x in loc.tolist()
            if W[x] == 0.0 and inv_m[x, LOCAL] < np.inf]
    if zero:                            # no score is below 0: a local wins
        return min(zero, key=lambda x: prio[x]), LOCAL
    own = sorted(set(racks.tolist()))
    sc_l = W[loc] * inv_m[loc, LOCAL]
    options = [(sc_l.min(), LOCAL, loc[sc_l == sc_l.min()])]
    in_rack = np.concatenate([np.arange(r * R, (r + 1) * R) for r in own])
    sc_k = W[in_rack] * inv_m[in_rack, RACK]
    for x in loc:                       # a local is not rack-local
        sc_k[own.index(x // R) * R + x % R] = np.inf
    options.append((sc_k.min(), RACK, in_rack[sc_k == sc_k.min()]))
    rm = rack_min.copy()
    rm[own] = np.inf
    best_r = rm.min()
    if np.isfinite(best_r):
        tied = np.flatnonzero(sc_r == best_r)
        keep = np.ones(tied.size, bool)
        for r in own:
            keep &= tied // R != r
        options.append((best_r, REMOTE, tied[keep]))
    best = min(o[0] for o in options)
    _, c, tied = next(o for o in options if o[0] == best)
    return int(tied[np.argmin(prio[tied])]), c


def simulate_kw(kw: dict) -> dict:
    """``simulate(**kw)`` or, with a ``slots`` key, ``replay(**kw)``: for a
    process pool."""
    return replay(**kw) if "slots" in kw else simulate(**kw)


def simulate(*, M: int, K: int, n_replicas: int, rates, speed, load: float,
             T: int, warmup: int, seed: int, d_rack: int = 0,
             d_remote: int = 0, full: bool = False,
             snapshot: bool = False) -> dict:
    """One run under Poisson(load * alpha * sum(speed)) arrivals with
    uniform placement; returns the post-warmup means (module docstring)."""
    rng = np.random.default_rng(seed)
    lam = load * rates[0] * np.sum(speed)
    counts = rng.poisson(lam, T)
    locs = _draw_locals(rng, M, n_replicas, int(counts.sum()))
    return _run(rng, counts, locs, M=M, K=K, rates=rates, speed=speed,
                warmup=warmup, d_rack=d_rack, d_remote=d_remote, full=full,
                snapshot=snapshot)


def replay(*, M: int, K: int, n_replicas: int, rates, slots, chunk,
           size_sigma: float, T: int, warmup: int, seed: int,
           d_rack: int = 0, d_remote: int = 0, full: bool = False,
           snapshot: bool = False) -> dict:
    """One run over recorded arrivals: task i arrives in slot ``slots[i]``
    (ascending) and reads data chunk ``chunk[i]``; each distinct chunk id
    is placed on its own ``n_replicas`` distinct uniform servers, drawn
    from ``seed``.  Each task's work is its geometric duration times a
    mean-1 lognormal of log-std ``size_sigma``."""
    rng = np.random.default_rng(seed)
    ids, row = np.unique(np.asarray(chunk), return_inverse=True)
    triples = _draw_locals(rng, M, n_replicas, ids.size)
    counts = np.bincount(np.asarray(slots), minlength=T)
    return _run(rng, counts, triples[row], M=M, K=K, rates=rates,
                speed=np.ones(M), warmup=warmup, d_rack=d_rack,
                d_remote=d_remote, full=full, snapshot=snapshot,
                size_sigma=size_sigma)


def _run(rng, counts, locs, *, M, K, rates, speed, warmup, d_rack,
         d_remote, full, snapshot, size_sigma=0.0) -> dict:
    """The slot loop over ``counts[t]`` arrivals in slot t whose replica
    servers are the next rows of ``locs``."""
    T = counts.shape[0]
    n_replicas = locs.shape[1]
    R = M // K
    rates = np.asarray(rates, np.float64)
    speed = np.asarray(speed, np.float64)
    rate_m = speed[:, None] * rates[None, :]
    inv_m = np.where(rate_m > 0, 1.0 / np.where(rate_m > 0, rate_m, 1.0),
                     np.inf)
    inv_w = np.where(np.isfinite(inv_m), inv_m, 0.0)

    # the candidates do not depend on the queues: draw them all
    racks = locs // R
    if not full:
        cand = np.concatenate([
            locs, _draw_in_racks(rng, racks, R, locs, d_rack),
            _draw_outside_racks(rng, racks, K, R, d_remote)], axis=1)
        ccls = np.array([LOCAL] * n_replicas + [RACK] * d_rack
                        + [REMOTE] * d_remote)
        cinv = inv_m[cand, ccls[None, :]]
        tie_u = rng.random(cand.shape)
    u_dur = rng.random((T, M))

    Q = np.zeros((M, 3), np.int64)
    busy = np.zeros(M, bool)
    rem = np.zeros(M)
    log1mp = np.log1p(-rates)
    servable = speed > 0
    ar = np.arange(M)
    sum_n = arrivals = completions = 0.0
    starts = np.zeros(3)
    j = 0
    for t in range(T):
        measure = t >= warmup
        # service progress and completions
        rem = np.where(busy, rem - speed, 0.0)
        done = busy & (rem <= 0)
        busy &= ~done
        # scheduling: idle servers start their first non-empty class
        has = (Q > 0) & servable[:, None]
        start = ~busy & has.any(1)
        pick = np.argmax(has, 1)
        Q[ar[start], pick[start]] -= 1
        u = np.clip(u_dur[t], 1e-7, 1.0 - 1e-7)
        dur = np.maximum(np.ceil(np.log1p(-u) / log1mp[pick]), 1.0)
        if size_sigma:
            dur = dur * np.exp(size_sigma * rng.standard_normal(M)
                               - 0.5 * size_sigma ** 2)
        rem = np.where(start, dur, rem)
        busy |= start
        # arrivals, routed in order
        W = (Q * inv_w).sum(1)
        W0 = W.copy() if snapshot else W
        if full:
            # remote score of every server, its least value per rack, and
            # the slot's tie priorities
            sc_r = W0 * inv_m[:, REMOTE]
            rack_min = sc_r.reshape(K, R).min(1)
            prio = rng.permutation(M)
        n_t = int(counts[t])
        for i in range(j, j + n_t):
            if full:
                m, c = _route_full(W0, sc_r, rack_min, inv_m, locs[i],
                                   racks[i], R, prio)
            else:
                sc = W0[cand[i]] * cinv[i]
                best = sc.min()
                tied = np.flatnonzero(sc == best)
                tied = tied[ccls[tied] == ccls[tied].min()]
                k = int(tied[np.argmin(tie_u[i, tied])])
                m, c = int(cand[i, k]), int(ccls[k])
            Q[m, c] += 1
            W[m] += inv_w[m, c]
            if full and not snapshot:
                sc_r[m] = W[m] * inv_m[m, REMOTE]
                r = m // R
                rack_min[r] = sc_r[r * R:(r + 1) * R].min()
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
