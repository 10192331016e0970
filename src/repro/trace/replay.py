"""High-throughput trace replay over the fused route_commit megakernel.

``ReplayEngine`` serves a recorded arrival log through the Balanced-Pandas
family's fused router at sustained rates well above the per-slot simulator
path.  The speed comes from moving everything that is *known before the
run* out of the slot loop:

  host prep (once)   timestamps are binned to the slot grid, every task's
                     catalog row is resolved (compile.arrival_rows), and
                     the per-slot arrival tensors ([T, A, 3] replica
                     triples + validity mask) are packed contiguously.
  chunk prep (jit)   per chunk of S slots, pod candidate lists
                     ([S, A, C], drawn from the replica triples) or, for
                     full BP only, locality classes ([S, A, M]) are
                     computed in one vectorized shot — the slot scan
                     then runs only
                     service progress, local scheduling, the fused
                     route_commit launch, and the accumulators.  No
                     Poisson sampling, no categorical catalog draws, no
                     window-speed machinery (trace realizations are
                     window-free: the homogeneous fast path).
  double buffering   the host->device transfer of chunk c+1 is issued
                     before chunk c's computation is awaited, so H2D
                     copies overlap compute; arrival buffers are donated
                     to the chunk step, so steady-state device memory is
                     two chunks regardless of trace length.

Dynamics are the simulator's own: the chunk step reuses
``_progress_service`` / ``_bp_schedule`` / ``kernel_route_commit`` /
``_acc`` and the same per-task size law, so ``summarize`` yields a
SimResult directly comparable to ``simulate`` on the trace-lowered
scenario (tests/test_trace.py holds mean delay within 5%).  The chunk
step compiles once per engine — ``replay_trace_count`` mirrors the
simulator's one-compile instrumentation."""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.cluster import Cluster, Rates, locality_class, safe_inv_rates
from ..core.simulator import (
    BPState,
    RawSums,
    SimConfig,
    SimResult,
    _acc,
    _bp_schedule,
    _bp_workload,
    _pod_for,
    _progress_service,
    summarize,
)
from ..core.policies import PodSpec, pod_candidates
from ..kernels import route_commit as kernel_route_commit
from ..telemetry import collectors as tlm
from ..telemetry.spans import phase, span
from ..scenarios.build import realize
from .compile import arrival_rows, scenario_from_trace
from .format import ArrivalLog, ensure_valid

_REPLAY_TRACE_COUNTS: dict = {"chunk": 0}


def replay_trace_count() -> int:
    """Times the jit'd replay chunk step has been (re)traced."""
    return _REPLAY_TRACE_COUNTS["chunk"]


def reset_replay_trace_count() -> None:
    """Zero the replay-chunk retrace counter (test isolation helper)."""
    _REPLAY_TRACE_COUNTS["chunk"] = 0


class _SizeLaw(NamedTuple):
    """Duck-types ScenarioData for simulator._task_work (size fields only)."""

    size_mu: jnp.ndarray
    size_sigma: jnp.ndarray


class ReplayResult(NamedTuple):
    """One replay run: summary stats + sustained routing throughput."""
    result: SimResult               # summarize() over the replayed run
    sums: RawSums
    telemetry: Optional[object]     # Telemetry pytree (None if off)
    routed_tasks: int               # total trace arrivals routed
    wall_s: float
    tasks_per_s: float              # routed_tasks / wall_s (sustained)
    trace_count: int                # chunk-step traces during this run


@functools.partial(
    jax.jit,
    static_argnames=("cluster", "rates", "cfg", "pod", "full_bp", "tcfg",
                     "t_pad"),
    donate_argnames=("locals_c", "mask_c"))
def _replay_chunk(state: BPState, sums: RawSums, tele, locals_c, mask_c,
                  t0, sizes: _SizeLaw, key, *, cluster: Cluster,
                  rates: Rates, cfg: SimConfig, pod: Optional[PodSpec],
                  full_bp: bool, tcfg, t_pad: int):
    """Advance the replay by one chunk of S slots.

    locals_c: int32 [S, A, 3] replica triples; mask_c: bool [S, A] arrival
    validity (both donated — freed after the chunk).  t0: first global
    slot of the chunk (traced scalar: chunks share one compile)."""
    _REPLAY_TRACE_COUNTS["chunk"] += 1
    S, A = mask_c.shape
    inv_rates = safe_inv_rates(rates.as_array())
    half2_from = cfg.warmup + (cfg.T - cfg.warmup) // 2

    # vectorized chunk prep: everything per-arrival that does not depend
    # on queue state happens once, outside the slot scan
    if full_bp:
        with phase("arrivals"):
            cls_c = locality_class(cluster, locals_c)      # [S, A, M]
    else:
        k_cand, key = jax.random.split(key)
        with phase("candidates"):
            ci, cc, cv = pod_candidates(k_cand, cluster, locals_c, pod)
            cv = cv & mask_c[..., None]

    def slot_step(carry, s):
        state, sums, tele = carry
        t = t0 + s
        k = jax.random.fold_in(key, s)
        k_sched, k_tie = jax.random.split(k)
        measure = (t >= cfg.warmup) & (t < cfg.T)
        with phase("service"):
            busy, rem, completed = _progress_service(
                state.busy, state.rem, None, state.cls, homo=True)
        with phase("schedule"):
            Q, busy, rem, cls_serv, starts, n_started, _pick, _start = \
                _bp_schedule(k_sched, state.Q, busy, rem, state.cls, rates,
                             cfg.service_dist, cfg.sigma, servable=None,
                             scen=sizes)
        mask_t = mask_c[s]
        if full_bp:
            with phase("candidates"):
                prio = jax.random.permutation(k_tie, cluster.M)
            with phase("route"):
                Q, _W, sel, sel_cls, _val = kernel_route_commit(
                    Q, mask_t, inv_rates, cls=cls_c[s], prio=prio)
        else:
            with phase("route"):
                Q, _W, sel, sel_cls, _val = kernel_route_commit(
                    Q, mask_t, inv_rates, cand_idx=ci[s], cand_cls=cc[s],
                    cand_valid=cv[s])
        with phase("accumulate"):
            routed = (jax.nn.one_hot(sel_cls, 3, dtype=jnp.float32)
                      * mask_t[:, None].astype(jnp.float32)).sum(axis=0)
            N = Q.sum().astype(jnp.float32) + busy.sum().astype(jnp.float32)
            sums = _acc(sums, in_half2=(t >= half2_from), N=N,
                        arr=mask_t.sum().astype(jnp.float32),
                        clipped=jnp.float32(0.0),   # replay never clips
                        comp=completed.sum().astype(jnp.float32),
                        starts=starts, routed=routed,
                        busy_n=busy.sum().astype(jnp.float32),
                        routes=mask_t.sum().astype(jnp.float32),
                        scheds=n_started, measure=measure)
            if tcfg is not None:
                tele = tlm.collect_step(
                    tele, tcfg, t=t, T=t_pad, N=N, q_mass=Q.sum(axis=0),
                    qlen=Q.sum(axis=1), workload=_bp_workload(Q, inv_rates),
                    arrivals=mask_t.sum(), clipped=jnp.float32(0.0),
                    completions=completed.sum(), busy_n=busy.sum(),
                    probe=tlm.ZERO_PROBE)
        return (BPState(Q, busy, rem, cls_serv), sums, tele), None

    (state, sums, tele), _ = jax.lax.scan(
        slot_step, (state, sums, tele), jnp.arange(S))
    return state, sums, tele


class ReplayEngine:
    """Replay an ArrivalLog through the fused router (see module docstring).

    algo: "balanced_pandas" (full O(M) routing) or "balanced_pandas_pod"
    (power-of-d candidate routing) — the BP family the fused kernel
    serves.  cfg.T sets the slot grid the trace is binned into;
    cfg.route_mode is ignored (replay is always the fused batched path).
    telemetry: a TelemetryConfig for per-window collection (sojourn rings
    and probe replay are forced off — they are per-slot-cost features the
    replay path exists to avoid)."""

    def __init__(self, log: ArrivalLog, cluster: Cluster, rates: Rates,
                 *, cfg: SimConfig = SimConfig(),
                 algo: str = "balanced_pandas_pod",
                 pod: Optional[PodSpec] = None, chunk_slots: int = 500,
                 chunks_per_server: int = 4,
                 telemetry: Optional[tlm.TelemetryConfig] = None):
        if algo not in ("balanced_pandas", "balanced_pandas_pod"):
            raise ValueError(f"replay serves the BP family, not {algo!r}")
        self.log = ensure_valid(log)
        self.cluster, self.rates, self.cfg = cluster, rates, cfg
        self.algo = algo
        self.pod = _pod_for(algo, pod)
        self.chunk_slots = int(chunk_slots)
        self.tcfg = (dataclasses.replace(telemetry, sojourns=False,
                                         probes=False)
                     if telemetry is not None else None)

        # -- lower + realize (unpadded: window-free == homogeneous path) --
        self.scenario = scenario_from_trace(
            log, name=f"replay:{log.name}",
            chunks_per_server=chunks_per_server)
        self.scen, self.lam_cap = realize(self.scenario, cluster, rates,
                                          cfg.T)
        self.load = float(log.n_tasks / (cfg.T * self.lam_cap))
        self._sizes = _SizeLaw(self.scen.size_mu, self.scen.size_sigma)

        # -- host prep: pack per-slot arrival tensors ---------------------
        T = cfg.T
        rows = arrival_rows(log, cluster.M
                            * self.scenario.placement.chunks_per_server)
        triples = np.asarray(self.scen.chunk_locals)[rows]     # [N, 3]
        slots = log.slot_of(T)
        counts = np.bincount(slots, minlength=T)
        self.a_cap = int(max(counts.max(), 1))
        offsets = np.concatenate([[0], np.cumsum(counts)])
        within = np.arange(log.n_tasks) - offsets[slots]
        S = self.chunk_slots
        self.n_chunks = -(-T // S)
        t_pad = self.n_chunks * S
        locals_pad = np.zeros((t_pad, self.a_cap, cluster.n_replicas),
                              np.int32)
        locals_pad[:, :, :] = np.arange(cluster.n_replicas, dtype=np.int32)
        mask_pad = np.zeros((t_pad, self.a_cap), bool)
        locals_pad[slots, within] = triples
        mask_pad[slots, within] = True
        self._t_pad = t_pad
        self._chunks = [(locals_pad[c * S:(c + 1) * S],
                         mask_pad[c * S:(c + 1) * S])
                        for c in range(self.n_chunks)]

    def _step_kwargs(self) -> dict:
        return dict(cluster=self.cluster, rates=self.rates, cfg=self.cfg,
                    pod=self.pod, full_bp=(self.algo == "balanced_pandas"),
                    tcfg=self.tcfg, t_pad=self._t_pad)

    def run(self, seed: int = 0) -> ReplayResult:
        """One full replay pass; wall time covers transfer + compute (the
        sustained rate), not compilation — call ``benchmark`` for the
        warm-compile protocol."""
        key = jax.random.PRNGKey(seed)
        state = BPState.zero(self.cluster.M)
        sums = RawSums.zero()
        tele = (tlm.zero_telemetry(self.tcfg, self.cluster.M, "bp")
                if self.tcfg is not None else None)
        kw = self._step_kwargs()
        traces0 = replay_trace_count()
        def put(c):
            with span("replay.put"):
                return (jax.device_put(self._chunks[c][0]),
                        jax.device_put(self._chunks[c][1]))
        t_start = time.perf_counter()
        nxt = put(0)
        with warnings.catch_warnings():
            # backends without donation support (CPU interpret runs) warn
            # once per compile that the donated arrival buffers went unused
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            for c in range(self.n_chunks):
                cur = nxt
                if c + 1 < self.n_chunks:
                    nxt = put(c + 1)  # H2D for c+1 overlaps chunk c compute
                with span("replay.chunk"):
                    state, sums, tele = _replay_chunk(
                        state, sums, tele, cur[0], cur[1],
                        jnp.int32(c * self.chunk_slots), self._sizes,
                        jax.random.fold_in(key, c), **kw)
        with span("replay.sync"):
            jax.block_until_ready(sums)
        wall = time.perf_counter() - t_start
        n = self.log.n_tasks
        return ReplayResult(
            result=summarize(sums, self.algo, self.cluster, self.rates,
                             self.pod),
            sums=sums, telemetry=tele, routed_tasks=n, wall_s=wall,
            tasks_per_s=n / max(wall, 1e-9),
            trace_count=replay_trace_count() - traces0)

    def benchmark(self, seed: int = 0) -> ReplayResult:
        """Compile-and-warm pass, then a timed pass (router_bench protocol);
        returns the timed pass's result."""
        self.run(seed)
        return self.run(seed)

    def telemetry_events(self, res: ReplayResult, **manifest_extra) -> list:
        """Flatten a replay's telemetry into schema-v1 JSONL events."""
        from ..telemetry import export
        if res.telemetry is None:
            raise ValueError("engine was built without telemetry")
        manifest = export.run_manifest(
            kind="trace_replay", trace=self.log.name, algo=self.algo,
            M=self.cluster.M, K=self.cluster.K, T=self.cfg.T,
            warmup=self.cfg.warmup, load=self.load,
            tasks=res.routed_tasks, wall_s=res.wall_s,
            tasks_per_s=res.tasks_per_s, trace_count=res.trace_count,
            **manifest_extra)
        return export.to_events(res.telemetry, self.tcfg, T=self._t_pad,
                                warmup=self.cfg.warmup, manifest=manifest)
