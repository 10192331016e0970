"""Discrete-time slotted simulator for the paper's system model (§III).

Three queue-structure families cover the paper's six algorithms:

  BP family      (3 sub-queues per server: local / rack-local / remote)
      - balanced_pandas        routing: argmin weighted workload over all M
      - balanced_pandas_pod    routing: argmin over 3 locals + d sampled
      scheduling (both): serve own local queue, then rack-local, then remote.

  SQ family      (one queue per server; queued tasks are local to it)
      - jsq_maxweight          routing: shortest local queue (O(1) already);
                               scheduling: argmax over all M of
                               {alpha*Q_own, beta*Q_rack, gamma*Q_other}.
      - jsq_maxweight_pod      scheduling: argmax over own + d' sampled.
      - jsq_priority           scheduling: own queue first, else longest
                               queue in own rack, else longest anywhere.

  FCFS           (single central queue; idle servers grab the head task)

Time is slotted; service durations are sampled once at service start
(geometric == the paper's discrete-time model / memoryless; log-normal ==
the paper's heavy-tail simulations) and counted down.  Within a slot the
order is completions -> scheduling -> arrivals, and the task-in-system count
N is read at slot end, so Little's law (E[T] = E[N]/lambda) gives the mean
task completion time without per-task bookkeeping.  A numpy event-accurate
reference with per-task sojourns (refsim.py) validates this in tests.

Routing modes:
  sequential — each arrival sees the workload left by the previous one
               (faithful to the paper's per-arrival routing; inner scan of
               plain-JAX ops, random tie-breaks).
  batched    — the slot's whole arrival batch routes through ONE fused
               Pallas launch (kernels.route_commit): score -> route ->
               queue-commit with in-kernel sequential conflict resolution,
               so arrival b+1 scores against workloads that already
               include arrival b's commit (a W-delta accumulator in VMEM).
               This preserves the paper's per-arrival semantics — a burst
               spreads instead of herding onto one snapshot argmin — at
               one launch per slot; it is the same [M, 3]-rate kernel
               the production PodRouter runs, traced inline into the
               jit'd step (Mosaic on TPU, the interpreter on CPU).
               Exact score ties
               resolve by locality class (LOCAL < RACK < REMOTE), then
               lowest server index / candidate slot — an exact integer
               rank lane in-kernel, valid at any workload magnitude —
               where the sequential path uses shared random priorities.
               The SQ family's batched routing rides the same kernel with
               unit rates (queue length == workload).

Scenarios (repro.scenarios): every run is parameterized by a ScenarioData
pytree — a [T] arrival-intensity shape, per-server speed multipliers with
time-indexed event windows, and optionally Zipf-skewed replica placement.
Speed is per locality CLASS: speed_t is an [M, 3] matrix (whole-server
events carry equal columns; per-class windows — network-tier degradation,
ToR cascades — scale beta/gamma independently).  Durations are sampled in
speed-1 work units at the class rate; a busy server completes
speed_t[m, c] units per slot for its in-flight class-c task, so a
straggler slows its in-flight task and a drained server (speed 0) freezes
and starts nothing — and a server whose beta tier is down can still start
local work.  The BP workload metric divides each sub-queue by the server's
own current [M, 3] rates, with drained (zero-rate) entries carried as
+inf inverse rates: they contribute 0 workload and score +inf in routing
(policies.weighted_score), so an empty dead server is never selected.
The default `uniform` scenario reproduces the symmetric model exactly.
For sweeps, ``simulate(..., pad=scenarios.canonical_pad(cluster),
a_max=scenarios.canonical_a_max(...))`` realizes every scenario to one
canonical pytree signature so the jit'd step compiles exactly once for the
whole registry (``trace_count`` instruments this; a regression test in
tests/test_scenarios.py guards it).

Scheduling is batched per slot: all idle servers act against the same
snapshot, with steal conflicts resolved by weight priority and queue-length
caps.  ``SimConfig.s_max`` bounds scheduling attempts per slot (capped
servers retry next slot); set s_max >= M for the exact uncapped dynamics
(tests do) — the default 64 only matters in transients where >64 servers
try to steal simultaneously.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .cluster import (
    GEOMETRIC,
    LOCAL,
    RACK,
    REMOTE,
    Cluster,
    Rates,
    inv_rate_matrix,
    locality_class,
    safe_inv_rates,
    sample_durations,
)
from ..kernels import ref as kernel_ref
from ..kernels import route_commit as kernel_route_commit
from ..telemetry import collectors as tlm
from ..scenarios.build import (
    ScenarioData,
    placement_epoch_at,
    realize,
    sample_locals_scenario,
    speed_at,
    stack_scenarios,
)
from ..scenarios.spec import get_scenario, scenario_names
from .policies import (
    PodSpec,
    bp_candidates_per_route,
    inv_rate_for,
    jsqmw_candidates_per_schedule,
    lex_argmax,
    lex_argmin,
    pod_candidates,
    route_balanced_pandas_full,
    route_jsq_local,
    route_pod_candidates,
    sample_rack_peer,
    sample_remote_peer,
    weighted_score,
)

_INF = jnp.inf


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Static simulation parameters (hashable: safe as a jit static arg)."""

    T: int = 20_000               # total slots
    warmup: int = 4_000           # slots discarded before measuring
    a_max: int = 0                # max arrivals per slot (0 = auto from load)
    s_max: int = 64               # max scheduling attempts per slot
    route_mode: str = "sequential"  # "sequential" | "batched"
    service_dist: str = GEOMETRIC   # "geometric" | "lognormal"
    sigma: float = 1.0              # log-normal shape

    def resolve_a_max(self, lam: float, shape_peak: float = 1.0) -> int:
        """Arrival-buffer width from the PEAK slot intensity.

        ``lam`` is the mean arrival rate; ``shape_peak`` the maximum of the
        scenario's mean-1 intensity shape (flash / diurnal traces spike
        well above the mean — sizing the Poisson tail bound from the mean
        clips arrivals exactly in the scenarios the clip warnings exist
        for).  The bound is peak + 6*sqrt(peak) + 4: P(clip) per slot is
        ~1e-9 at the peak intensity.
        """
        if self.a_max > 0:
            return self.a_max
        import math
        peak = lam * shape_peak
        return int(math.ceil(peak + 6.0 * math.sqrt(peak) + 4))


class RawSums(NamedTuple):
    """Per-run accumulators."""

    slots: jnp.ndarray
    sum_N: jnp.ndarray
    sum_N_h1: jnp.ndarray
    sum_N_h2: jnp.ndarray
    arrivals: jnp.ndarray
    clipped: jnp.ndarray
    completions: jnp.ndarray
    starts: jnp.ndarray        # [3] service starts by locality class
    routed: jnp.ndarray        # [3] routing decisions by chosen class (BP family)
    busy: jnp.ndarray
    route_decisions: jnp.ndarray
    sched_decisions: jnp.ndarray
    final_N: jnp.ndarray

    @staticmethod
    def zero() -> "RawSums":
        """All-zero accumulator (scan carry init)."""
        z = jnp.float32(0.0)
        return RawSums(z, z, z, z, z, z, z, jnp.zeros(3, jnp.float32),
                       jnp.zeros(3, jnp.float32), z, z, z, z)


class SimResult(NamedTuple):
    """Per-run summary statistics (``summarize``); under ``simulate_grid``
    every leaf gains leading [seeds, loads] dims, under ``simulate_sweep``
    [scenarios, seeds, loads]."""
    mean_tasks_in_system: jnp.ndarray
    mean_completion_slots: jnp.ndarray
    mean_completion_norm: jnp.ndarray   # units of mean local service time
    arrival_rate_hat: jnp.ndarray
    throughput: jnp.ndarray
    utilization: jnp.ndarray
    locality_fractions: jnp.ndarray     # [3] of service starts
    routed_fractions: jnp.ndarray       # [3] of routing choices (BP family)
    drift: jnp.ndarray                  # mean_N(2nd half) / mean_N(1st half);
    #                                     NaN when the 1st half saw no mass
    #                                     (drift UNMEASURABLE — consumers must
    #                                     treat NaN as "not converged", never
    #                                     as "converged"; see telemetry.
    #                                     export.auto_extend_warmup)
    clip_fraction: jnp.ndarray
    route_decisions: jnp.ndarray
    sched_decisions: jnp.ndarray
    route_candidates_per_decision: jnp.ndarray
    sched_candidates_per_decision: jnp.ndarray


# ---------------------------------------------------------------------------
# Shared slot plumbing
# ---------------------------------------------------------------------------


def _speed_of_class(speed, cls):
    """[M] per-server speed for class ``cls[m]``; speed: [M, 3]."""
    return jnp.take_along_axis(speed, cls[:, None], axis=1)[:, 0]


def _progress_service(busy, rem, speed, cls, homo: bool = False):
    """Busy servers complete ``speed[m, cls[m]]`` work units this slot
    (cls = class of the in-flight task); rem is float32 work remaining.
    homo=True: speed is statically all-ones (no per-server gather).
    Return (busy', rem', completed_mask)."""
    rem = jnp.where(busy, rem - (1.0 if homo
                                 else _speed_of_class(speed, cls)), 0.0)
    completed = busy & (rem <= 0)
    busy = busy & ~completed
    rem = jnp.where(busy, rem, 0.0)
    return busy, rem, completed


def _arrival_batch(key, cluster, scen, lam_t, a_max, need_cls: bool, pe=0):
    """Poisson(lam_t) arrival count (clipped to a_max) + per-arrival
    locality under the scenario's placement law (``pe`` = the slot's
    churn-epoch index, see scenarios.placement_epoch_at)."""
    k_n, k_loc = jax.random.split(key)
    raw = jax.random.poisson(k_n, lam_t)
    n = jnp.minimum(raw, a_max)
    mask = jnp.arange(a_max) < n
    locals_ = sample_locals_scenario(k_loc, cluster, scen, a_max, pe=pe)
    cls = locality_class(cluster, locals_) if need_cls else None
    return mask, locals_, cls, (raw - n).astype(jnp.float32)


def _relation_rows(cluster: Cluster, rows: jnp.ndarray) -> jnp.ndarray:
    """[S, M] locality class of server rows[s] serving a task queued at
    (= local to) server n."""
    rack_of = cluster.rack_of
    n = jnp.arange(cluster.M, dtype=jnp.int32)
    same = rack_of[rows][:, None] == rack_of[None, :]
    own = rows[:, None] == n[None, :]
    return jnp.where(own, LOCAL, jnp.where(same, RACK, REMOTE)).astype(jnp.int32)


def _acc(sums: RawSums, *, in_half2, N, arr, clipped, comp, starts, routed,
         busy_n, routes, scheds, measure) -> RawSums:
    f = jnp.float32
    w = f(measure)
    return RawSums(
        slots=sums.slots + w,
        sum_N=sums.sum_N + w * N,
        sum_N_h1=sums.sum_N_h1 + w * N * (1.0 - f(in_half2)),
        sum_N_h2=sums.sum_N_h2 + w * N * f(in_half2),
        arrivals=sums.arrivals + w * arr,
        clipped=sums.clipped + w * clipped,
        completions=sums.completions + w * comp,
        starts=sums.starts + w * starts,
        routed=sums.routed + w * routed,
        busy=sums.busy + w * busy_n,
        route_decisions=sums.route_decisions + w * routes,
        sched_decisions=sums.sched_decisions + w * scheds,
        final_N=N,
    )


_SIZE_SALT = 7  # fold_in salt deriving the size-multiplier PRNG stream


def _task_work(key, dur, scen) -> jnp.ndarray:
    """Float32 work units for freshly started tasks: the sampled duration
    times the scenario's per-task size multiplier, exp(size_mu +
    size_sigma * z) — a mean-1 lognormal (realize sets mu = -sigma^2/2).
    size_sigma == 0 (every non-trace scenario) is the exact identity:
    the multiplier is exp(0.0) == 1.0 and the f32 product returns ``dur``
    bit-for-bit.  The normal draw comes from a salted fold of the duration
    key, so the legacy duration/arrival PRNG streams are untouched."""
    work = dur.astype(jnp.float32)
    if scen is None or scen.size_mu is None:
        return work
    z = jax.random.normal(jax.random.fold_in(key, _SIZE_SALT), work.shape)
    return work * jnp.exp(scen.size_mu + scen.size_sigma * z)


# ---------------------------------------------------------------------------
# BP family: Balanced-Pandas and Balanced-Pandas-Pod
# ---------------------------------------------------------------------------


class BPState(NamedTuple):
    """Balanced-Pandas family state: per-server 3-class sub-queues."""
    Q: jnp.ndarray          # int32 [M, 3] sub-queue lengths
    busy: jnp.ndarray       # bool  [M]
    rem: jnp.ndarray        # f32   [M] remaining service work units
    cls: jnp.ndarray        # int32 [M] class of in-service task

    @staticmethod
    def zero(M: int) -> "BPState":
        """Empty cluster of M servers."""
        return BPState(
            jnp.zeros((M, 3), jnp.int32), jnp.zeros(M, bool),
            jnp.zeros(M, jnp.float32), jnp.zeros(M, jnp.int32),
        )


def _bp_workload(Q: jnp.ndarray, inv_rates: jnp.ndarray) -> jnp.ndarray:
    """Paper §IV-A: W_m = Q^l/alpha_m + Q^k/beta_m + Q^r/gamma_m.

    inv_rates: [3] (homogeneous) or per-server [M, 3] (heterogeneous).
    Non-finite entries (drained servers, +inf inverse rate) contribute 0 —
    the queue_update kernel's semantics; routing masks dead servers by
    their rate (weighted_score), never by their W."""
    if inv_rates.ndim == 1:
        inv_rates = inv_rates[None, :]
    finite = jnp.where(jnp.isfinite(inv_rates), inv_rates, 0.0)
    return (Q.astype(jnp.float32) * finite).sum(axis=-1)


def _bp_schedule(key, Q, busy, rem, cls, rates, service_dist, sigma,
                 servable, scen=None):
    """Idle servers start their own head-of-class *servable* task:
    local > rack > remote among classes whose tier is up.  Purely local
    information — no cross-server messages (paper §IV-A).
    servable: bool [M, 3] (speed > 0) — a drained server starts nothing;
    a server whose beta tier is down skips rack-local work but still
    starts local/remote tasks.  None = statically all-servable (the
    homogeneous fast path).  Also returns (pick, start) so the
    telemetry sojourn ring can mirror the queue pops."""
    has = Q > 0 if servable is None else (Q > 0) & servable
    pick = jnp.argmax(has, axis=1).astype(jnp.int32)   # first servable class
    start = (~busy) & has.any(axis=1)
    Q = Q - (jax.nn.one_hot(pick, 3, dtype=jnp.int32) * start[:, None].astype(jnp.int32))
    dur = sample_durations(key, pick, rates, service_dist, sigma)
    busy = busy | start
    rem = jnp.where(start, _task_work(key, dur, scen), rem)
    cls = jnp.where(start, pick, cls)
    starts_by_class = (jax.nn.one_hot(pick, 3, dtype=jnp.float32)
                       * start[:, None].astype(jnp.float32)).sum(axis=0)
    return (Q, busy, rem, cls, starts_by_class,
            start.sum().astype(jnp.float32), pick, start)


def _full_bp_scores(W, cls_arr, inv_rates):
    """[..., M] weighted-workload score of EVERY server for each arrival —
    what the O(M) policy would examine (telemetry probe-quality oracle)."""
    m = jnp.arange(cls_arr.shape[-1], dtype=jnp.int32)
    return weighted_score(W, inv_rate_for(inv_rates, m, cls_arr))


def _bp_route_batch(key, cluster, Q, cls_arr, locals_, mask, inv_rates, pod,
                    sequential: bool, class_tiebreak: bool = True,
                    tcfg=None):
    """Route a slot's arrival batch; returns (Q', sel [A], sel_cls [A],
    probe) where probe = (rank_sum, regret_sum, n_decisions) telemetry
    (zeros when ``tcfg`` is None or probe collection is off).

    sequential: per-arrival plain-JAX routing, each arrival seeing the
    previous one's queues (the paper's model; random tie-breaks).
    batched: ONE fused kernels.route_commit launch — score, route, and
    queue-commit with in-kernel sequential conflict resolution, so each
    arrival still sees the previous one's commit (no snapshot herding).
    Exact ties break by locality class, then a per-slot random priority
    permutation (full BP; pod candidate slots are already randomly
    sampled), then index (class_tiebreak is a sequential-path knob; the
    kernel's class lane is always on).  Probe
    telemetry replays the evolving pre-commit workloads each arrival
    actually routed against (ref.route_commit_wseq), so batched probe
    ranks are measured against the same O(M) oracle the decision saw."""
    k_tie, k_pod, k_seq = jax.random.split(key, 3)
    tie_rnd = jax.random.uniform(k_tie, (cluster.M,))
    collect = tcfg is not None and tcfg.probes
    probe = tlm.ZERO_PROBE

    if sequential:
        def route_one(Qc, xs):
            cls_a, loc_a, valid, kr = xs
            W = _bp_workload(Qc, inv_rates)
            if pod is None:
                sel, sc = route_balanced_pandas_full(W, cls_a, inv_rates,
                                                     tie_rnd, class_tiebreak)
            else:
                kc, kt = jax.random.split(kr)
                ci, cc, cv = pod_candidates(kc, cluster, loc_a, cls_a, pod)
                sel, sc = route_pod_candidates(kt, W, ci, cc, cv, inv_rates)
            Qc = Qc.at[sel, sc].add(valid.astype(jnp.int32))
            if collect:
                full = _full_bp_scores(W, cls_a, inv_rates)
                return Qc, (sel, sc, full[sel], jnp.min(full),
                            (full < full[sel]).sum())
            return Qc, (sel, sc)
        keys = jax.random.split(k_seq, mask.shape[0])
        Q, ys = jax.lax.scan(route_one, Q, (cls_arr, locals_, mask, keys))
        if collect:
            sel, sel_cls, chosen, best, rank = ys
            regret = jnp.where(jnp.isfinite(chosen - best), chosen - best,
                               0.0)
            v = mask.astype(jnp.float32)
            probe = ((rank * v).sum(), (jnp.maximum(regret, 0.0) * v).sum(),
                     v.sum())
        else:
            sel, sel_cls = ys
    else:
        Q0 = Q
        if pod is None:
            # same tie semantics as the sequential path: class, then a
            # per-slot random priority (W is lattice-valued, ties are
            # routine; always-lowest-index ties hotspot low-index servers)
            Q, _W, sel, sel_cls, _val = kernel_route_commit(
                Q, mask, inv_rates, cls=cls_arr,
                prio=jax.random.permutation(k_tie, cluster.M))
        else:
            kc, _ = jax.random.split(k_pod)
            ci, cc, cv = pod_candidates(kc, cluster, locals_, cls_arr, pod)
            Q, _W, sel, sel_cls, _val = kernel_route_commit(
                Q, mask, inv_rates, cand_idx=ci, cand_cls=cc, cand_valid=cv)
        if collect:
            # rank each decision against the evolving O(M) oracle: the
            # pre-commit workload row arrival b actually routed against
            W_seq = kernel_ref.route_commit_wseq(Q0, sel, sel_cls, mask,
                                                 inv_rates)       # [A, M]
            full = _full_bp_scores(W_seq, cls_arr, inv_rates)
            chosen = jnp.take_along_axis(full, sel[:, None], axis=1)[:, 0]
            probe = tlm.probe_stats_min(full, chosen, mask)
    return Q, sel, sel_cls, probe


def _bp_step(state: BPState, sums: RawSums, key, *, cluster, rates, cfg,
             lam_t, scen, speed, inv_rate_m, pod, a_max, measure, in_half2,
             homo=False, class_tiebreak=True, t=None, tele=None, tcfg=None):
    k_sched, k_arr, k_route = jax.random.split(key, 3)

    busy, rem, completed = _progress_service(state.busy, state.rem, speed,
                                             state.cls, homo=homo)
    if tcfg is not None:
        # sojourn = completion slot - arrival slot of the in-service task
        tele = tlm.record_sojourns(tele, tcfg, t, cfg.warmup, completed)
    Q, busy, rem, cls_serv, starts, n_started, pick, start = _bp_schedule(
        k_sched, state.Q, busy, rem, state.cls, rates, cfg.service_dist,
        cfg.sigma, servable=None if homo else speed > 0, scen=scen)
    if tcfg is not None:
        m = jnp.arange(cluster.M, dtype=jnp.int32)
        tele = tlm.ring_pop(tele, tcfg, m * 3 + pick, start, m)

    mask, locals_, cls_arr, clipped = _arrival_batch(
        k_arr, cluster, scen, lam_t, a_max, need_cls=True,
        pe=placement_epoch_at(scen, t))
    Q, sel, sel_cls, probe = _bp_route_batch(
        k_route, cluster, Q, cls_arr, locals_, mask, inv_rate_m, pod,
        sequential=(cfg.route_mode == "sequential"),
        class_tiebreak=class_tiebreak, tcfg=tcfg)
    if tcfg is not None:
        tele = tlm.ring_push(tele, tcfg, sel * 3 + sel_cls, mask, t)

    routed = (jax.nn.one_hot(sel_cls, 3, dtype=jnp.float32)
              * mask[:, None].astype(jnp.float32)).sum(axis=0)

    N = Q.sum().astype(jnp.float32) + busy.sum().astype(jnp.float32)
    sums = _acc(sums, in_half2=in_half2, N=N,
                arr=mask.sum().astype(jnp.float32), clipped=clipped,
                comp=completed.sum().astype(jnp.float32), starts=starts,
                routed=routed, busy_n=busy.sum().astype(jnp.float32),
                routes=mask.sum().astype(jnp.float32), scheds=n_started,
                measure=measure)
    if tcfg is not None:
        tele = tlm.collect_step(
            tele, tcfg, t=t, T=cfg.T, N=N, q_mass=Q.sum(axis=0),
            qlen=Q.sum(axis=1), workload=_bp_workload(Q, inv_rate_m),
            arrivals=mask.sum(), clipped=clipped,
            completions=completed.sum(), busy_n=busy.sum(), probe=probe)
    return BPState(Q, busy, rem, cls_serv), sums, tele


# ---------------------------------------------------------------------------
# SQ family: JSQ-MaxWeight(-Pod) and JSQ-Priority
# ---------------------------------------------------------------------------


class SQState(NamedTuple):
    """JSQ family state: one scalar queue per server."""
    Q: jnp.ndarray          # int32 [M] queue lengths (tasks local to server)
    busy: jnp.ndarray
    rem: jnp.ndarray
    cls: jnp.ndarray

    @staticmethod
    def zero(M: int) -> "SQState":
        """Empty cluster of M servers."""
        return SQState(jnp.zeros(M, jnp.int32), jnp.zeros(M, bool),
                       jnp.zeros(M, jnp.float32), jnp.zeros(M, jnp.int32))


def _grant_conflicts(tgt, prio, has, Q, key, M):
    """Resolve batched steal conflicts among S claimants: at most Q[n] grants
    to queue n, higher-priority claimants first (prio = ascending-sort keys,
    random-uniform final tie-break).  Returns bool [S] granted.

    Claimant i is granted iff its priority rank among same-target claimants
    is below Q[tgt[i]].  The rank is a pairwise count — [S, S] staged
    lexicographic compares + a row sum — which is cheaper per slot than the
    old lexsort/searchsorted/scatter chain at scheduler batch sizes."""
    S = tgt.shape[0]
    rnd = jax.random.uniform(key, (S,))
    # beats[i, j]: claimant j precedes i in (prio..., rnd) ascending order
    beats = jnp.zeros((S, S), bool)
    eq = jnp.ones((S, S), bool)
    for k in tuple(prio) + (rnd,):
        beats = beats | (eq & (k[None, :] < k[:, None]))
        eq = eq & (k[None, :] == k[:, None])
    same = (tgt[None, :] == tgt[:, None]) & has[None, :] & has[:, None]
    rank = jnp.sum(same & beats, axis=1)
    return has & (rank < Q[tgt])


def _sq_schedule(key, cluster, Q, busy, rem, cls, rates, cfg, variant,
                 pod: Optional[PodSpec], speed, homo: bool = False,
                 tcfg=None, scen=None):
    """Batched scheduling for the single-queue family (see module docstring).

    variant: "maxweight" (argmax of rate-weighted queue lengths — the serving
    server's own per-class rates, so a fast server outbids a slow one for the
    same queue — over all M or over 1+d' Pod samples) or "priority" (own >
    longest-in-rack > longest-anywhere).  speed: [M, 3] current per-class
    multipliers; a (server, queue) pair whose locality-class tier is down
    (speed 0) is ineligible, and a fully drained server schedules nothing.
    homo=True asserts (statically — see _rates_homogeneous) that speed is
    identically 1, so the per-pair speed gathers and drain checks drop out
    of the slot loop with identical results.

    Also returns (rows, tgt, granted) for the telemetry sojourn rings and
    probe = (rank_sum, regret_sum, n) probe-quality stats: for the Pod
    variant the full [S, M] weight matrix the O(M) MaxWeight would have
    examined is recomputed and the pod pick ranked against it."""
    M = cluster.M
    S = min(cfg.s_max, M)
    k_rows, k_cand, k_tie, k_grant, k_dur = jax.random.split(key, 5)

    idle = ~busy
    anyq = (Q > 0).any()
    eligible = idle & ((Q > 0) | anyq)
    if not homo:
        eligible = eligible & (speed > 0).any(axis=1)
    if S == M:
        # every server is its own scheduling attempt: no subset to sample,
        # and row order is immaterial (grants tie-break on explicit rnd)
        rows = jnp.arange(M, dtype=jnp.int32)
    else:
        # up to S eligible servers (random priority; the rest retry next slot)
        rkey = jnp.where(eligible, jax.random.uniform(k_rows, (M,)), _INF)
        order = jnp.argsort(rkey)
        rows = order[:S]
    act = eligible if S == M else eligible[rows]

    collect = tcfg is not None and tcfg.probes
    probe = tlm.ZERO_PROBE
    qf = Q.astype(jnp.float32)
    if variant == "maxweight" and pod is None:
        rel = _relation_rows(cluster, rows)              # [S, M]
        if homo:
            w = qf[None, :] * rates.as_array()[rel]
            cand = jnp.broadcast_to((Q > 0)[None, :], (S, M))
        else:
            sp = speed[rows[:, None], rel]               # serving server's
            w = qf[None, :] * rates.as_array()[rel] * sp  # per-class speed
            cand = (Q > 0)[None, :] & (sp > 0)
        rnd = jax.random.uniform(k_tie, (S, M))
        tgt = lex_argmax(w, rnd, mask=cand)
        val = jnp.take_along_axis(w, tgt[:, None], axis=1)[:, 0]
        has = cand.any(axis=1) & act
        prio = (-val,)
        if collect:  # full MaxWeight = the O(M) oracle itself: rank 0
            probe = tlm.probe_stats_max(w, val, has, cand)
    elif variant == "maxweight":
        # one fused randint for the rack + remote probes (one PRNG sweep
        # per slot instead of two; same per-column uniform law)
        R = cluster.rack_size
        start = (rows // R) * R
        hi = jnp.concatenate([
            jnp.full((pod.d_rack,), max(R - 1, 1), jnp.int32),
            jnp.full((pod.d_remote,), max(M - R, 1), jnp.int32)])
        u = jax.random.randint(k_cand, (S, pod.d_rack + pod.d_remote), 0,
                               hi[None, :])
        x = u[:, :pod.d_rack]
        rack = start[:, None] + x + (x >= (rows - start)[:, None])
        y = u[:, pod.d_rack:]
        remote = y + jnp.where(y >= start[:, None], R, 0)
        cand_idx = jnp.concatenate([rows[:, None], rack, remote], axis=1)
        rel = jnp.concatenate([
            jnp.full((S, 1), LOCAL, jnp.int32),
            jnp.full((S, pod.d_rack), RACK, jnp.int32),
            jnp.full((S, pod.d_remote), REMOTE, jnp.int32)], axis=1)
        qc = Q[cand_idx]
        if homo:
            w = qc.astype(jnp.float32) * rates.as_array()[rel]
            cand = qc > 0
        else:
            sp = speed[rows[:, None], rel]
            w = qc.astype(jnp.float32) * rates.as_array()[rel] * sp
            cand = (qc > 0) & (sp > 0)
        rnd = jax.random.uniform(k_tie, cand_idx.shape)
        c = lex_argmax(w, rnd, mask=cand)
        tgt = jnp.take_along_axis(cand_idx, c[:, None], axis=1)[:, 0]
        val = jnp.take_along_axis(w, c[:, None], axis=1)[:, 0]
        has = cand.any(axis=1) & act
        prio = (-val,)
        if collect:  # rank the 1+d' pod pick against the full [S, M] oracle
            rel_f = _relation_rows(cluster, rows)
            sp_f = speed[rows[:, None], rel_f]
            w_f = qf[None, :] * rates.as_array()[rel_f] * sp_f
            elig = (Q > 0)[None, :] & (sp_f > 0)
            probe = tlm.probe_stats_max(w_f, val, has, elig)
    elif variant == "priority":
        rel = _relation_rows(cluster, rows)              # [S, M]
        if homo:
            nonempty = jnp.broadcast_to((Q > 0)[None, :], (S, M))
            own_has = Q[rows] > 0
        else:
            sp = speed[rows[:, None], rel]
            nonempty = (Q > 0)[None, :] & (sp > 0)
            own_has = (Q[rows] > 0) & (speed[rows, LOCAL] > 0)
        rack_set = (rel == RACK) & nonempty
        glob_set = (rel == REMOTE) & nonempty
        rnd = jax.random.uniform(k_tie, (S, M))
        wq = jnp.broadcast_to(qf[None, :], (S, M))
        rack_tgt = lex_argmax(wq, rnd, mask=rack_set)
        glob_tgt = lex_argmax(wq, rnd, mask=glob_set)
        rack_any = rack_set.any(axis=1)
        glob_any = glob_set.any(axis=1)
        tgt = jnp.where(own_has, rows,
                        jnp.where(rack_any, rack_tgt, glob_tgt))
        has = (own_has | rack_any | glob_any) & act
        class_rank = jnp.where(own_has, 0.0, jnp.where(rack_any, 1.0, 2.0))
        prio = (class_rank, -qf[tgt])
    else:
        raise ValueError(variant)

    granted = _grant_conflicts(tgt, prio, has, Q, k_grant, M)
    Q = Q.at[tgt].add(-granted.astype(jnp.int32))
    # locality class of (server rows[s], queue tgt[s]) — pairwise, O(S)
    rack_of = cluster.rack_of
    start_cls = jnp.where(rows == tgt, LOCAL,
                          jnp.where(rack_of[rows] == rack_of[tgt],
                                    RACK, REMOTE)).astype(jnp.int32)
    dur = sample_durations(k_dur, start_cls, rates, cfg.service_dist, cfg.sigma)
    work = _task_work(k_dur, dur, scen)

    if S == M:
        # rows == arange(M): the per-row scatters are identity placements
        busy = busy | granted
        rem = jnp.where(granted, work, rem)
        cls = jnp.where(granted, start_cls, cls)
    else:
        busy = busy.at[rows].set(busy[rows] | granted)
        rem = rem.at[rows].set(jnp.where(granted, work, rem[rows]))
        cls = cls.at[rows].set(jnp.where(granted, start_cls, cls[rows]))
    starts = (jax.nn.one_hot(start_cls, 3, dtype=jnp.float32)
              * granted[:, None].astype(jnp.float32)).sum(axis=0)
    n_dec = has.sum().astype(jnp.float32)
    return Q, busy, rem, cls, starts, n_dec, rows, tgt, granted, probe


def _sq_step(state: SQState, sums: RawSums, key, *, cluster, rates, cfg,
             lam_t, scen, speed, inv_rate_m, variant, pod, a_max, measure,
             in_half2, homo=False, t=None, tele=None, tcfg=None):
    k_sched, k_arr, k_route = jax.random.split(key, 3)

    busy, rem, completed = _progress_service(state.busy, state.rem, speed,
                                             state.cls, homo=homo)
    if tcfg is not None:
        tele = tlm.record_sojourns(tele, tcfg, t, cfg.warmup, completed)
    Q, busy, rem, cls_serv, starts, n_sched, rows, tgt, granted, probe = \
        _sq_schedule(k_sched, cluster, state.Q, busy, rem, state.cls, rates,
                     cfg, variant, pod, speed, homo=homo, tcfg=tcfg,
                     scen=scen)
    if tcfg is not None:
        tele = tlm.ring_pop(tele, tcfg, tgt, granted, rows)

    mask, locals_, _cls, clipped = _arrival_batch(
        k_arr, cluster, scen, lam_t, a_max, need_cls=False,
        pe=placement_epoch_at(scen, t))
    if cfg.route_mode == "sequential":
        def route_one(Qc, xs):
            loc, valid, kr = xs
            sel = route_jsq_local(kr, Qc, loc)
            return Qc.at[sel].add(valid.astype(jnp.int32)), sel
        keys = jax.random.split(k_route, a_max)
        Q, sel = jax.lax.scan(route_one, Q, (locals_, mask, keys))
    else:
        # fused route_commit with unit rates: queue length == workload, so
        # shortest-local-queue = the kernel's candidate argmin, and each
        # arrival sees the previous one's commit (no snapshot herding).
        # Ties break by replica slot order (vs the sequential path's
        # random pick) — a documented batched-mode contract difference.
        Q3 = jnp.zeros((cluster.M, 3), jnp.int32).at[:, 0].set(Q)
        Q3, _W, sel, _scls, _val = kernel_route_commit(
            Q3, mask, jnp.ones(3, jnp.float32), cand_idx=locals_,
            cand_cls=jnp.zeros_like(locals_),
            cand_valid=jnp.ones_like(locals_))
        Q = Q3[:, 0]
    if tcfg is not None:
        tele = tlm.ring_push(tele, tcfg, sel, mask, t)

    N = Q.sum().astype(jnp.float32) + busy.sum().astype(jnp.float32)
    sums = _acc(sums, in_half2=in_half2, N=N,
                arr=mask.sum().astype(jnp.float32), clipped=clipped,
                comp=completed.sum().astype(jnp.float32), starts=starts,
                routed=jnp.zeros(3, jnp.float32),
                busy_n=busy.sum().astype(jnp.float32),
                routes=mask.sum().astype(jnp.float32), scheds=n_sched,
                measure=measure)
    if tcfg is not None:
        # workload proxy: queued work at the local rate (JSQ queues are
        # local to their server); drained servers contribute 0
        inv_l = inv_rate_m[:, LOCAL] if inv_rate_m.ndim == 2 \
            else jnp.full((cluster.M,), inv_rate_m[LOCAL])
        inv_l = jnp.where(jnp.isfinite(inv_l), inv_l, 0.0)
        tele = tlm.collect_step(
            tele, tcfg, t=t, T=cfg.T, N=N,
            q_mass=jnp.stack([Q.sum().astype(jnp.float32),
                              jnp.float32(0.0), jnp.float32(0.0)]),
            qlen=Q, workload=Q.astype(jnp.float32) * inv_l,
            arrivals=mask.sum(), clipped=clipped,
            completions=completed.sum(), busy_n=busy.sum(), probe=probe)
    else:
        del inv_rate_m  # JSQ routing is workload-metric-free
    return SQState(Q, busy, rem, cls_serv), sums, tele


# ---------------------------------------------------------------------------
# FCFS: central queue, idle servers grab the head task
# ---------------------------------------------------------------------------


class FCFSState(NamedTuple):
    """FCFS state: a single central queue feeding all servers."""
    C: jnp.ndarray          # int32 scalar: central queue length
    busy: jnp.ndarray
    rem: jnp.ndarray
    cls: jnp.ndarray

    @staticmethod
    def zero(M: int) -> "FCFSState":
        """Empty cluster of M servers."""
        return FCFSState(jnp.zeros((), jnp.int32), jnp.zeros(M, bool),
                         jnp.zeros(M, jnp.float32), jnp.zeros(M, jnp.int32))


def _fcfs_step(state: FCFSState, sums: RawSums, key, *, cluster, rates, cfg,
               lam_t, scen, speed, inv_rate_m, a_max, measure, in_half2,
               homo=False, t=None, tele=None, tcfg=None):
    del inv_rate_m  # FCFS is workload-metric-free
    M = cluster.M
    G = min(cfg.s_max, M)
    k_rank, k_loc, k_dur, k_arr = jax.random.split(key, 4)

    busy, rem, completed = _progress_service(state.busy, state.rem, speed,
                                             state.cls, homo=homo)
    idle = ~busy if homo else (~busy) & (speed > 0).any(axis=1)
    r = jnp.where(idle, jax.random.uniform(k_rank, (M,)), _INF)
    rows = jnp.argsort(r)[:G]
    # locality of the grabbed task relative to the grabbing server: the task's
    # replica triple is iid (uniform or chunk-skewed) and independent of
    # everything else, so sampling it at dequeue time is distributionally
    # identical.
    pe = placement_epoch_at(scen, t)
    locals_g = sample_locals_scenario(k_loc, cluster, scen, G,
                                      pe=pe)  # [G, n_rep]
    rack_of = cluster.rack_of
    is_local = (locals_g == rows[:, None]).any(axis=1)
    in_rack = (rack_of[locals_g] == rack_of[rows][:, None]).any(axis=1)
    start_cls = jnp.where(is_local, LOCAL,
                          jnp.where(in_rack, RACK, REMOTE)).astype(jnp.int32)
    # a server whose tier for this task's class is down leaves it queued
    grant = (idle[rows] & (jnp.arange(G) < state.C)
             & (speed[rows, start_cls] > 0))
    dur = sample_durations(k_dur, start_cls, rates, cfg.service_dist, cfg.sigma)
    C = state.C - grant.sum().astype(jnp.int32)
    busy = busy.at[rows].set(busy[rows] | grant)
    rem = rem.at[rows].set(jnp.where(grant, _task_work(k_dur, dur, scen),
                                     rem[rows]))
    cls = state.cls.at[rows].set(jnp.where(grant, start_cls, state.cls[rows]))
    starts = (jax.nn.one_hot(start_cls, 3, dtype=jnp.float32)
              * grant[:, None].astype(jnp.float32)).sum(axis=0)

    mask, _, _, clipped = _arrival_batch(k_arr, cluster, scen, lam_t, a_max,
                                         need_cls=False, pe=pe)
    C = C + mask.sum().astype(jnp.int32)

    N = C.astype(jnp.float32) + busy.sum().astype(jnp.float32)
    sums = _acc(sums, in_half2=in_half2, N=N,
                arr=mask.sum().astype(jnp.float32), clipped=clipped,
                comp=completed.sum().astype(jnp.float32), starts=starts,
                routed=jnp.zeros(3, jnp.float32),
                busy_n=busy.sum().astype(jnp.float32),
                routes=jnp.float32(0.0), scheds=grant.sum().astype(jnp.float32),
                measure=measure)
    if tcfg is not None:
        # central queue: windows only — no per-task identity to ring-track
        tele = tlm.collect_step(
            tele, tcfg, t=t, T=cfg.T, N=N,
            q_mass=jnp.stack([C.astype(jnp.float32), jnp.float32(0.0),
                              jnp.float32(0.0)]),
            qlen=C[None].astype(jnp.float32), workload=None,
            arrivals=mask.sum(), clipped=clipped,
            completions=completed.sum(), busy_n=busy.sum(),
            probe=tlm.ZERO_PROBE)
    return FCFSState(C, busy, rem, cls), sums, tele


# ---------------------------------------------------------------------------
# Algorithm registry + entry point
# ---------------------------------------------------------------------------

# paper §V parameters: d = 8 = (2 rack-local + 6 remote) for BP-Pod routing;
# d' = 12 = (6 + 6) for JSQ-MW-Pod scheduling.
BP_POD_DEFAULT = PodSpec(d_rack=2, d_remote=6)
JSQMW_POD_DEFAULT = PodSpec(d_rack=6, d_remote=6)

ALGORITHMS = (
    "fcfs",
    "jsq_priority",
    "jsq_maxweight",
    "jsq_maxweight_pod",
    "balanced_pandas",
    "balanced_pandas_pod",
)


def _pod_for(algo: str, pod: Optional[PodSpec]) -> Optional[PodSpec]:
    if pod is not None:
        return pod
    if algo == "balanced_pandas_pod":
        return BP_POD_DEFAULT
    if algo == "jsq_maxweight_pod":
        return JSQMW_POD_DEFAULT
    return None


# -- jit trace-count instrumentation ----------------------------------------
# The body of a jit'd function executes (as Python) exactly once per compiled
# signature, so a plain counter bumped inside `_run` counts cache misses.
# The one-compile scenario sweep (canonical ScenarioData padding + shared
# a_max) is guarded by a regression test asserting this stays at 1 across
# the whole registry (tests/test_scenarios.py).

_TRACE_COUNTS: dict = {"_run": 0}


def trace_count() -> int:
    """Number of times the jit'd simulator step has been (re)traced."""
    return _TRACE_COUNTS["_run"]


def reset_trace_count() -> None:
    """Zero the ``_run`` retrace counter (test isolation helper)."""
    _TRACE_COUNTS["_run"] = 0


def _family(algo: str) -> str:
    if algo in ("balanced_pandas", "balanced_pandas_pod",
                "balanced_pandas_randomtie"):
        return "bp"
    if algo == "fcfs":
        return "fcfs"
    if algo in ("jsq_maxweight", "jsq_maxweight_pod", "jsq_priority"):
        return "sq"
    raise ValueError(f"unknown algorithm {algo!r}")


def _rates_homogeneous(scen: ScenarioData) -> bool:
    """Host-side static check: does this realized scenario leave every
    server at the symmetric base rates for the whole run?  True only for
    window-free realizations with unit base speeds — then the simulator can
    thread the homogeneous ``[3]`` inverse-rate vector instead of the
    ``[M, 3]`` matrix, and the route_commit kernel skips its per-candidate
    rate gather (statically, via ``ndim``).  Bit-identical either way:
    every consumer branches on ndim and a gather of identical rows returns
    exactly the shared row.  Canonically padded sweeps always carry window
    rows, so the one-compile contract is untouched (one signature, with
    this False)."""
    import numpy as _np
    return (scen.win_start.shape[0] == 0
            and bool(_np.all(_np.asarray(scen.base_speed) == 1.0)))


@functools.partial(
    jax.jit,
    static_argnames=("algo", "cluster", "rates", "cfg", "pod", "a_max",
                     "homo_rates", "tcfg"))
def _run(key, lam, scen: ScenarioData, *, algo: str, cluster: Cluster,
         rates: Rates, cfg: SimConfig, pod: Optional[PodSpec], a_max: int,
         homo_rates: bool = False, tcfg=None):
    _TRACE_COUNTS["_run"] += 1        # executes only on a jit cache miss
    half2_from = cfg.warmup + (cfg.T - cfg.warmup) // 2
    family = _family(algo)

    def step(carry, t):
        state, sums, tele = carry
        k = jax.random.fold_in(key, t)
        measure = t >= cfg.warmup
        in_half2 = t >= half2_from
        speed = speed_at(scen, t)                       # [M, 3] per-class
        kw = dict(cluster=cluster, rates=rates, cfg=cfg,
                  lam_t=lam * scen.lam_shape[t], scen=scen, speed=speed,
                  inv_rate_m=(safe_inv_rates(rates.as_array()) if homo_rates
                              else inv_rate_matrix(rates, speed)),
                  homo=homo_rates, a_max=a_max, measure=measure,
                  in_half2=in_half2, t=t, tele=tele, tcfg=tcfg)
        if family == "bp":
            state, sums, tele = _bp_step(
                state, sums, k, pod=pod,
                class_tiebreak=(algo != "balanced_pandas_randomtie"), **kw)
        elif family == "sq":
            variant = "priority" if algo == "jsq_priority" else "maxweight"
            state, sums, tele = _sq_step(state, sums, k, variant=variant,
                                         pod=pod, **kw)
        elif family == "fcfs":
            state, sums, tele = _fcfs_step(state, sums, k, **kw)
        else:
            raise ValueError(f"unknown algorithm {algo!r}")
        return (state, sums, tele), None

    if family == "bp":
        state0 = BPState.zero(cluster.M)
    elif family == "fcfs":
        state0 = FCFSState.zero(cluster.M)
    else:
        state0 = SQState.zero(cluster.M)
    tele0 = (tlm.zero_telemetry(tcfg, cluster.M, family)
             if tcfg is not None else None)

    (state, sums, tele), _ = jax.lax.scan(
        step, (state0, RawSums.zero(), tele0), jnp.arange(cfg.T))
    return sums, tele


def simulate(algo: str, cluster: Cluster, rates: Rates, load: float,
             key: jax.Array, cfg: SimConfig = SimConfig(),
             pod: Optional[PodSpec] = None, scenario=None,
             pad=None, a_max: Optional[int] = None) -> SimResult:
    """Run one simulation and return derived metrics.

    load: fraction of the (scenario-aware, time-averaged) capacity boundary;
    for the default `uniform` scenario that is lambda = load * M * alpha.
    scenario: a registered scenario name, a scenarios.Scenario, or None.
    pad / a_max: canonical sweep controls (scenarios.canonical_pad /
    scenarios.canonical_a_max) — realizing every scenario with the same pad
    and sharing one a_max keeps the whole sweep on a single compiled
    signature (see trace_count).
    """
    scen, lam_cap = realize(get_scenario(scenario), cluster, rates, cfg.T,
                            pad=pad)
    lam = float(load) * lam_cap
    pod = _pod_for(algo, pod)
    if a_max is None:
        a_max = cfg.resolve_a_max(lam, float(jnp.max(scen.lam_shape)))
    sums, _ = _run(key, jnp.float32(lam), scen, algo=algo, cluster=cluster,
                   rates=rates, cfg=cfg, pod=pod, a_max=a_max,
                   homo_rates=_rates_homogeneous(scen))
    return summarize(sums, algo, cluster, rates, pod)


def simulate_with_telemetry(
        algo: str, cluster: Cluster, rates: Rates, load: float,
        key: jax.Array, cfg: SimConfig = SimConfig(),
        pod: Optional[PodSpec] = None, scenario=None, pad=None,
        a_max: Optional[int] = None,
        telemetry: tlm.TelemetryConfig = tlm.TelemetryConfig()):
    """``simulate`` + in-jit collectors; returns (SimResult, Telemetry).

    The SimResult is bit-identical to ``simulate``'s (collectors never
    consume PRNG keys — tests/test_telemetry.py enforces it).  Host-side
    consumers live in repro.telemetry.export (JSONL events, windowed
    drift, sojourn percentiles, probe summaries)."""
    scen, lam_cap = realize(get_scenario(scenario), cluster, rates, cfg.T,
                            pad=pad)
    lam = float(load) * lam_cap
    pod = _pod_for(algo, pod)
    if a_max is None:
        a_max = cfg.resolve_a_max(lam, float(jnp.max(scen.lam_shape)))
    sums, tele = _run(key, jnp.float32(lam), scen, algo=algo,
                      cluster=cluster, rates=rates, cfg=cfg, pod=pod,
                      a_max=a_max, homo_rates=_rates_homogeneous(scen),
                      tcfg=telemetry)
    return summarize(sums, algo, cluster, rates, pod), tele


def simulate_auto_warmup(
        algo: str, cluster: Cluster, rates: Rates, load: float,
        key: jax.Array, cfg: SimConfig = SimConfig(),
        pod: Optional[PodSpec] = None, scenario=None, pad=None,
        a_max: Optional[int] = None,
        telemetry: tlm.TelemetryConfig = tlm.TelemetryConfig(),
        policy=None):
    """``simulate_with_telemetry`` + drift-aware auto-extend warmup.

    Runs ONCE at full ``cfg.T``, then lets
    ``telemetry.export.auto_extend_warmup`` push the measurement boundary
    forward window-by-window until the windowed drift of the surviving
    tail drops below ``policy.threshold`` (or the cap/min-tail guards
    fire).  Window sums are exact per-slot sums, so the re-derived tail
    statistics equal a run measured with the longer warmup — nothing is
    re-run or retraced (the one-compile sweep invariant holds; a
    fast-mixing run costs zero extensions).

    Returns ``(SimResult, Telemetry, WarmupReport)``.  The SimResult is
    the run's own (configured-warmup) summary — bit-identical to
    ``simulate_with_telemetry``; the report carries the realized warmup,
    convergence verdict, and the tail's mean_N / lam_hat /
    mean_completion / throughput.  A NaN drift is reported as NOT
    converged, loudly (see ``WarmupReport.note``)."""
    from ..telemetry.export import WarmupPolicy, auto_extend_warmup
    if policy is None:
        policy = WarmupPolicy()
    res, tele = simulate_with_telemetry(
        algo, cluster, rates, load, key, cfg=cfg, pod=pod,
        scenario=scenario, pad=pad, a_max=a_max, telemetry=telemetry)
    report = auto_extend_warmup(tele, telemetry, cfg.T, cfg.warmup,
                                policy=policy)
    return res, tele, report


def simulate_grid(algo: str, cluster: Cluster, rates: Rates, loads,
                  n_seeds: int, cfg: SimConfig = SimConfig(),
                  pod: Optional[PodSpec] = None, seed0: int = 0,
                  scenario=None, pad=None,
                  a_max: Optional[int] = None) -> SimResult:
    """Vectorized sweep: one compile, vmapped over loads x seeds.
    Returns SimResult with leading dims [n_seeds, n_loads].
    pad / a_max as in ``simulate``."""
    import numpy as _np
    scen, lam_cap = realize(get_scenario(scenario), cluster, rates, cfg.T,
                            pad=pad)
    lam = jnp.array([l * lam_cap for l in loads], jnp.float32)
    pod = _pod_for(algo, pod)
    if a_max is None:
        a_max = cfg.resolve_a_max(float(_np.max(_np.asarray(lam))),
                                  float(jnp.max(scen.lam_shape)))
    keys = jax.random.split(jax.random.PRNGKey(seed0), n_seeds)

    def one(key, l):
        sums, _ = _run(key, l, scen, algo=algo, cluster=cluster, rates=rates,
                       cfg=cfg, pod=pod, a_max=a_max,
                       homo_rates=_rates_homogeneous(scen))
        return sums

    sums = jax.vmap(lambda k: jax.vmap(lambda l: one(k, l))(lam))(keys)
    return summarize(sums, algo, cluster, rates, pod)


def simulate_grid_with_telemetry(
        algo: str, cluster: Cluster, rates: Rates, loads, n_seeds: int,
        cfg: SimConfig = SimConfig(), pod: Optional[PodSpec] = None,
        seed0: int = 0, scenario=None, pad=None,
        a_max: Optional[int] = None,
        telemetry: tlm.TelemetryConfig = tlm.TelemetryConfig()):
    """``simulate_grid`` + collectors; returns (SimResult, Telemetry) with
    leading dims [n_seeds, n_loads] on every leaf.  Aggregate over the
    batch axes with ``repro.telemetry.export.aggregate`` (sums add, maxima
    max), or index a single (seed, load) cell for per-run windows."""
    import numpy as _np
    scen, lam_cap = realize(get_scenario(scenario), cluster, rates, cfg.T,
                            pad=pad)
    lam = jnp.array([l * lam_cap for l in loads], jnp.float32)
    pod = _pod_for(algo, pod)
    if a_max is None:
        a_max = cfg.resolve_a_max(float(_np.max(_np.asarray(lam))),
                                  float(jnp.max(scen.lam_shape)))
    keys = jax.random.split(jax.random.PRNGKey(seed0), n_seeds)

    def one(key, l):
        return _run(key, l, scen, algo=algo, cluster=cluster, rates=rates,
                    cfg=cfg, pod=pod, a_max=a_max,
                    homo_rates=_rates_homogeneous(scen), tcfg=telemetry)

    sums, tele = jax.vmap(lambda k: jax.vmap(lambda l: one(k, l))(lam))(keys)
    return summarize(sums, algo, cluster, rates, pod), tele


# ---------------------------------------------------------------------------
# Batched mega-sweep: ONE compiled program per policy for the whole
# scenario x load x seed grid
# ---------------------------------------------------------------------------


def sweep_grid(cluster: Cluster, rates: Rates, cfg: SimConfig, loads,
               scenarios=None, pad=None, a_max: Optional[int] = None):
    """Host-side grid construction for ``simulate_sweep``.

    Realizes + stacks the scenarios (``scenarios.build.stack_scenarios``)
    and resolves the grid's shared arrival-buffer width.  Returns
    ``(names, stacked ScenarioData with leading [S], lam [S, L] float32
    absolute arrival rates, a_max)``.  ``scenarios`` is an iterable of
    registered names and/or Scenario objects (default: the full registry);
    ``a_max`` defaults to the maximum ``resolve_a_max`` over every
    (scenario, load) cell, sized from each scenario's peak slot intensity
    — one static width for the whole grid, so the grid shares one
    compiled signature.
    """
    import numpy as _np
    names = list(scenarios) if scenarios is not None \
        else list(scenario_names())
    stacked, caps = stack_scenarios(names, cluster, rates, cfg.T, pad=pad)
    loads = [float(l) for l in loads]
    lam = _np.asarray(caps)[:, None] * _np.asarray(loads)[None, :]
    if a_max is None:
        peaks = _np.max(_np.asarray(stacked.lam_shape), axis=1)
        a_max = max(cfg.resolve_a_max(float(c) * max(loads), float(p))
                    for c, p in zip(caps, peaks))
    labels = [getattr(n, "name", n) for n in names]
    return labels, stacked, jnp.asarray(lam, jnp.float32), int(a_max)


def _sweep_cells(keys, lam, scen, *, algo, cluster, rates, cfg, pod, a_max,
                 tcfg):
    """vmap the jit'd ``_run`` over the stacked grid.

    keys: [K] PRNG keys (one per Monte-Carlo seed, shared across cells the
    way ``simulate_grid`` shares them across loads); lam: [S, L]; scen:
    ScenarioData with leading [S].  Returns (sums, tele) with leading
    [S, K, L] on every leaf.  The jit boundary stays on ``_run``, so the
    whole grid lowers to ONE batched executable per policy signature and
    ``trace_count`` advances by exactly 1.
    """
    def one(key, l, sc):
        return _run(key, l, sc, algo=algo, cluster=cluster, rates=rates,
                    cfg=cfg, pod=pod, a_max=a_max, homo_rates=False,
                    tcfg=tcfg)

    def per_scen(lam_row, sc):
        def per_seed(k):
            return jax.vmap(lambda l: one(k, l, sc))(lam_row)
        return jax.vmap(per_seed)(keys)

    return jax.vmap(per_scen)(lam, scen)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "algo", "cluster", "rates", "cfg", "pod",
                     "a_max", "tcfg"))
def _sweep_sharded(keys, lam, scen, *, mesh, algo, cluster, rates, cfg, pod,
                   a_max, tcfg):
    """``_sweep_cells`` with the scenario axis shard_mapped over ``mesh``.
    Jitted on the static grid description, so a repeated sweep over the
    same mesh and shapes reuses its program instead of compiling again."""
    from jax.sharding import PartitionSpec as P
    kw = dict(algo=algo, cluster=cluster, rates=rates, cfg=cfg, pod=pod,
              a_max=a_max, tcfg=tcfg)
    return jax.shard_map(
        lambda k, l, sc: _sweep_cells(k, l, sc, **kw), mesh=mesh,
        in_specs=(P(), P("scen"), P("scen")), out_specs=P("scen"),
        check_vma=False)(keys, lam, scen)


def simulate_sweep(algo: str, cluster: Cluster, rates: Rates, loads,
                   n_seeds: int, cfg: SimConfig = SimConfig(),
                   pod: Optional[PodSpec] = None, seed0: int = 0,
                   scenarios=None, pad=None, a_max: Optional[int] = None,
                   telemetry=None, devices=None):
    """The whole scenario x load x seed grid as ONE program per policy.

    Stacks canonically-padded scenario pytrees along a leading axis
    (``scenarios.build.stack_scenarios``), vmaps the jit'd simulator over
    scenario x seed x load, and — when more than one device is visible —
    shard_maps the scenario axis across devices (single-device hosts, e.g.
    CPU CI, fall back to the plain vmap; pass ``devices`` to restrict the
    mesh).  The policy (``algo``, ``pod``) is a static branch: each policy
    is its own compiled program, and ``trace_count`` advances by exactly 1
    per policy for the entire grid (tests/test_sweep.py guards this).

    Per-cell PRNG: seed k of every (scenario, load) cell uses key
    ``jax.random.split(PRNGKey(seed0), n_seeds)[k]`` — exactly the keys
    ``simulate_grid`` uses, so each cell of the one-program sweep is
    BIT-IDENTICAL to the corresponding looped ``simulate_grid`` cell
    (also guarded by tests/test_sweep.py).

    Returns ``(names, SimResult, telemetry)`` where every SimResult leaf
    carries leading dims ``[n_scenarios, n_seeds, n_loads]`` and
    ``telemetry`` is None unless a TelemetryConfig is passed (then its
    leaves carry the same leading dims; reduce per cell with
    ``repro.telemetry.export.cell_view`` — never aggregate across cells).
    """
    import numpy as _np
    names, scen, lam, a_max = sweep_grid(cluster, rates, cfg, loads,
                                         scenarios=scenarios, pad=pad,
                                         a_max=a_max)
    pod = _pod_for(algo, pod)
    keys = jax.random.split(jax.random.PRNGKey(seed0), n_seeds)
    kw = dict(algo=algo, cluster=cluster, rates=rates, cfg=cfg, pod=pod,
              a_max=a_max, tcfg=telemetry)

    devs = list(devices) if devices is not None else jax.devices()
    S = lam.shape[0]
    D = min(len(devs), S)
    if D > 1:
        from jax.sharding import Mesh

        pad_s = (-S) % D
        if pad_s:
            # repeat trailing scenarios so the scenario axis divides the
            # mesh evenly; the duplicate rows are dropped below
            rep = lambda x: jnp.concatenate([x, x[-pad_s:]], axis=0)
            scen = jax.tree_util.tree_map(rep, scen)
            lam = rep(lam)
        mesh = Mesh(_np.asarray(devs[:D]), ("scen",))
        sums, tele = _sweep_sharded(keys, lam, scen, mesh=mesh, **kw)
        if pad_s:
            drop = lambda x: x[:S]
            sums = jax.tree_util.tree_map(drop, sums)
            tele = jax.tree_util.tree_map(drop, tele)
    else:
        sums, tele = _sweep_cells(keys, lam, scen, **kw)
    return names, summarize(sums, algo, cluster, rates, pod), tele


def summarize(s: RawSums, algo: str, cluster: Cluster, rates: Rates,
              pod: Optional[PodSpec]) -> SimResult:
    """Reduce raw scan sums to a ``SimResult`` (Little's-law mean delay,
    locality fractions, drift, clip fraction, probe complexity)."""
    slots = jnp.maximum(s.slots, 1.0)
    mean_N = s.sum_N / slots
    lam_hat = s.arrivals / slots
    mean_T = mean_N / jnp.maximum(lam_hat, 1e-9)       # Little's law, slots
    h = jnp.maximum(slots / 2.0, 1.0)
    starts_total = jnp.maximum(s.starts.sum(-1, keepdims=True), 1.0)
    routed_total = jnp.maximum(s.routed.sum(-1, keepdims=True), 1.0)
    if algo in ("balanced_pandas", "balanced_pandas_pod",
                "balanced_pandas_randomtie"):
        route_cand = bp_candidates_per_route(cluster, pod)
        sched_cand = 1  # own sub-queues only — purely local information
    elif algo in ("jsq_maxweight", "jsq_maxweight_pod"):
        route_cand = cluster.n_replicas
        sched_cand = jsqmw_candidates_per_schedule(cluster, pod)
    elif algo == "jsq_priority":
        route_cand = cluster.n_replicas
        sched_cand = cluster.M
    else:  # fcfs
        route_cand = 0
        sched_cand = 1
    return SimResult(
        mean_tasks_in_system=mean_N,
        mean_completion_slots=mean_T,
        mean_completion_norm=mean_T * rates.alpha,
        arrival_rate_hat=lam_hat,
        throughput=s.completions / slots,
        utilization=s.busy / (slots * cluster.M),
        locality_fractions=s.starts / starts_total,
        routed_fractions=s.routed / routed_total,
        # NaN-explicit: an empty first half (e.g. warmup >= T, or a system
        # that never held a task) means drift is UNMEASURABLE — the old
        # 1e-9 guard silently turned that into a huge finite ratio that
        # drift<1.05 convergence checks mistook for "wildly diverging"
        # (or, with sum_N_h2 also 0, for a perfectly-converged 0/1e-9=0)
        drift=jnp.where(s.sum_N_h1 > 0,
                        (s.sum_N_h2 / h) / jnp.maximum(s.sum_N_h1 / h, 1e-30),
                        jnp.nan),
        clip_fraction=s.clipped / jnp.maximum(s.arrivals + s.clipped, 1.0),
        route_decisions=s.route_decisions,
        sched_decisions=s.sched_decisions,
        route_candidates_per_decision=jnp.float32(route_cand),
        sched_candidates_per_decision=jnp.float32(sched_cand),
    )
