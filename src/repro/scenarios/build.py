"""Realize a Scenario spec into arrays the jit'd simulator scans over.

Contract
--------
``realize(scenario, cluster, rates, T, pad=None)`` turns a declarative
:class:`~repro.scenarios.spec.Scenario` into a :class:`ScenarioData`
pytree of concrete arrays (shapes documented on the class) plus the
scenario's capacity-region edge ``lam_cap`` (tasks/slot at load 1).
Realization is deterministic in ``scenario.seed`` and host-side only —
nothing here runs under jit; the simulator scans over the returned
arrays.

Single-compile invariants
-------------------------
Two knobs keep a whole sweep on ONE compiled simulator signature:

* ``pad`` (:class:`ScenarioPad`, usually :func:`canonical_pad`): pads
  window/catalog/epoch arrays to registry-wide maxima and switches the
  placement law to data-selection (``placement_on``), so every scenario
  shares one pytree structure and one set of leaf shapes.
* ``canonical_a_max``: one arrival-buffer width (a static jit argument)
  sized from the PEAK slot intensity over the whole sweep.

``stack_scenarios`` builds on both: it realizes many scenarios against
one pad and stacks them along a leading ``[S]`` axis — the input the
batched sweep engine (``core.simulate_sweep``) vmaps and shard_maps over.

All float arrays are float32 (except host-side capacity integration,
float64); index arrays are int32.
"""
from __future__ import annotations

import math
from typing import TYPE_CHECKING, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .spec import (
    COMPOSE_DEPTH,
    SCENARIOS,
    FleetSpec,
    PlacementSpec,
    Scenario,
    TrafficSpec,
    WindowSpec,
    get_scenario,
    registry_limits,
)

if TYPE_CHECKING:  # runtime import would cycle: core.simulator imports us
    from ..core.cluster import Cluster, Rates


class ScenarioData(NamedTuple):
    """Pytree of realized scenario arrays (dynamic jit operands).

    lam_shape     [T]  arrival-intensity shape, mean ~1 (multiplies lambda)
    base_speed    [M]  persistent per-server speed multipliers
    win_start/end [E]  event-window slot bounds (E may be 0)
    win_mult      [E, M, 3] per-window, per-locality-class speed multiplier
                  (1.0 = unaffected).  Whole-server windows carry equal
                  columns; per-class windows (network-tier degradation,
                  ToR cascades) scale the beta/gamma columns independently.
    chunk_logits  [C]  log chunk popularity, or None for uniform placement
    chunk_locals  [C, n_replicas] each chunk's replica triple, or None
    size_mu       scalar lognormal log-mean of the per-task service-size
                  multiplier (None on directly-constructed pytrees; realize
                  always emits it, with mu = -sigma^2/2 so the multiplier
                  has mean exactly 1 and lam_cap is size-law invariant)
    size_sigma    scalar lognormal log-std; 0.0 (the registry default)
                  leaves sampled durations untouched bit-for-bit
    placement_on  scalar 0/1 selector, or None.  Canonical (padded)
                  realizations always carry the chunk arrays and choose the
                  placement law by DATA instead of pytree structure:
                  1.0 -> draw from the chunk catalog, 0.0 -> uniform
                  sample_locals.  That keeps every scenario on one compiled
                  signature (the one-compile sweep).  None preserves the
                  unpadded behavior, where structure picks the law.
    epoch_logits  [P, C] per-churn-epoch chunk popularity (trace-lowered
                  placements: row e is the CONDITIONAL popularity while
                  epoch e is active, so per-instant skew is not diluted by
                  mixing epochs), or None (single-epoch placements; the
                  global ``chunk_logits`` law applies at every slot).
                  Canonical realizations always carry it — row 0 mirrors
                  chunk_logits, pad rows get ~ -inf.
    placement_epoch  [T] int32 slot -> churn-epoch index into epoch_logits
                  (zeros for single-epoch placements), or None.
    epoch_cdf     [P, C] float32 cumulative popularity of each row of
                  epoch_logits (of chunk_logits as one row where a
                  placement has no epochs), built on the host by
                  ``catalog_cdf``: the table ``sample_locals_scenario``
                  searches by inverse CDF.  None without a catalog.
    """

    lam_shape: jnp.ndarray
    base_speed: jnp.ndarray
    win_start: jnp.ndarray
    win_end: jnp.ndarray
    win_mult: jnp.ndarray
    chunk_logits: Optional[jnp.ndarray]
    chunk_locals: Optional[jnp.ndarray]
    placement_on: Optional[jnp.ndarray] = None
    size_mu: Optional[jnp.ndarray] = None
    size_sigma: Optional[jnp.ndarray] = None
    epoch_logits: Optional[jnp.ndarray] = None
    placement_epoch: Optional[jnp.ndarray] = None
    epoch_cdf: Optional[jnp.ndarray] = None

    @property
    def M(self) -> int:
        """Number of servers this realization was built for."""
        return self.base_speed.shape[0]


class ScenarioPad(NamedTuple):
    """Canonical array shapes every realized scenario is padded to.

    n_windows: event-window slots (inactive pads: start == end == 0,
    mult == 1).  n_chunks: placement-catalog rows (pads get ~ -inf logits,
    so they are never drawn).  Realizing every scenario of a sweep with the
    same ScenarioPad makes all ScenarioData pytrees share one structure and
    one set of leaf shapes — the jit'd simulator then traces exactly once
    for the whole sweep.
    """

    n_windows: int
    n_chunks: int
    n_epochs: int = 1


def canonical_pad(cluster: "Cluster", scenarios=None,
                  compose_depth: Optional[int] = None) -> ScenarioPad:
    """The registry-wide ScenarioPad (or for an explicit scenario subset).

    compose_depth widens the event-window budget for deeper-than-pairwise
    ``compose()`` products (default: spec.COMPOSE_DEPTH = 2).  A 3-way
    product of window-carrying scenarios overflows the default pad —
    ``realize`` / ``stack_scenarios`` reject it with a ValueError naming
    ``canonical_pad(..., compose_depth=3)`` as the fix."""
    n_windows, chunks_per_server, n_epochs = registry_limits(
        scenarios, compose_depth=compose_depth)
    return ScenarioPad(n_windows=max(n_windows, 1),
                       n_chunks=max(chunks_per_server * cluster.M, 1),
                       n_epochs=max(n_epochs, 1))


def canonical_a_max(cluster: "Cluster", rates: "Rates", cfg, load: float,
                    scenarios=None) -> int:
    """One arrival-batch width valid for every scenario in the sweep.

    ``a_max`` is a static jit argument of the simulator, so a per-scenario
    value (peak intensity x scenario capacity) would force one recompile per
    scenario even with canonical array padding.  This resolves the maximum
    over the registry (or an explicit subset), sizing each scenario's
    buffer from its PEAK slot intensity (mean rate x max of the mean-1
    intensity shape — flash/diurnal shapes spike well above the mean); cfg
    is any object with ``T`` and ``resolve_a_max(lam, shape_peak)`` (i.e.
    a core.SimConfig — duck-typed to avoid an import cycle).
    """
    specs = tuple(scenarios) if scenarios is not None else tuple(
        SCENARIOS.values())
    a_max = 1
    for s in specs:
        scen, lam_cap = realize(get_scenario(s), cluster, rates, cfg.T)
        shape_peak = float(np.max(np.asarray(scen.lam_shape)))
        a_max = max(a_max, cfg.resolve_a_max(float(load) * lam_cap,
                                             shape_peak))
    return a_max


def speed_at(scen: ScenarioData, t) -> jnp.ndarray:
    """[M, 3] effective per-class speed at slot ``t`` (jit-safe; t may be
    traced).  Column c scales the class-c service rate; whole-server
    windows carry equal columns.  Windows compose multiplicatively when
    they overlap."""
    active = (scen.win_start <= t) & (t < scen.win_end)          # [E]
    mult = jnp.where(active[:, None, None], scen.win_mult, 1.0)  # [E, M, 3]
    return scen.base_speed[:, None] * jnp.prod(mult, axis=0)


def speed_trace(scen: ScenarioData, T: int) -> np.ndarray:
    """[T, M, 3] host-side speed trace (tests / plots; not the hot path)."""
    start = np.asarray(scen.win_start)[None, :]                  # [1, E]
    end = np.asarray(scen.win_end)[None, :]
    t = np.arange(T)[:, None]                                    # [T, 1]
    active = (start <= t) & (t < end)                            # [T, E]
    mult = np.where(active[:, :, None, None],
                    np.asarray(scen.win_mult)[None], 1.0)        # [T, E, M, 3]
    return np.asarray(scen.base_speed)[None, :, None] * mult.prod(axis=1)


# ---------------------------------------------------------------------------
# Fleet axis
# ---------------------------------------------------------------------------


def _check_rack(r: int, cluster: "Cluster", w: WindowSpec) -> None:
    # loud, not silent: an out-of-range rack would otherwise realize as an
    # all-False mask — an inert window, i.e. a failure event that never
    # happens (generators hard-code rack counts; see generators.py)
    if not 0 <= r < cluster.K:
        raise ValueError(f"window {w} targets rack {r}, but the cluster "
                         f"has K={cluster.K} racks")


def _window_mask(w: WindowSpec, cluster: "Cluster") -> np.ndarray:
    m = np.arange(cluster.M)
    if w.rack is not None:
        _check_rack(w.rack, cluster, w)
        return (m // cluster.rack_size) == w.rack
    if w.servers is not None:
        lo, hi = w.servers
        return (m >= lo) & (m < hi)
    if w.every is not None:
        return (m % w.every) == w.phase
    if w.rack_member is not None:
        r, i = w.rack_member
        _check_rack(r, cluster, w)
        return m == r * cluster.rack_size + (i % cluster.rack_size)
    raise ValueError(f"window {w} selects no servers")


def _fleet_arrays(fleet: FleetSpec, cluster: "Cluster", T: int,
                  rng: np.random.Generator):
    M = cluster.M
    base = np.ones(M, np.float32)
    for r, s in enumerate(fleet.rack_speeds):
        base[r * cluster.rack_size:(r + 1) * cluster.rack_size] = s
    for frac, s_mult in fleet.cohorts():
        k = max(1, int(round(frac * M)))
        base[rng.choice(M, size=k, replace=False)] *= s_mult
    E = len(fleet.windows)
    start = np.zeros(E, np.int32)
    end = np.zeros(E, np.int32)
    mult = np.ones((E, M, 3), np.float32)
    for e, w in enumerate(fleet.windows):
        start[e] = int(round(w.t0 * T))
        end[e] = int(round(w.t1 * T))
        mult[e, _window_mask(w, cluster)] = np.asarray(w.class_mult,
                                                      np.float32)
    return base, start, end, mult


def capacity_scale(scen: ScenarioData, T: int) -> float:
    """Time-averaged sum_m local_speed_t[m] / M: the heterogeneous capacity
    region edge relative to the symmetric M * alpha.  At the boundary every
    task is served locally, so only the LOCAL (alpha, class-0) column of the
    window multipliers matters — beta/gamma-only degradation leaves the
    edge untouched.  Exact: windows make speed piecewise-constant, so
    integrate over the boundary segments."""
    start = np.asarray(scen.win_start)
    end = np.asarray(scen.win_end)
    bounds = np.unique(np.clip(np.concatenate(
        [[0, T], start, end]), 0, T)).astype(np.int64)
    total = 0.0
    base = np.asarray(scen.base_speed, np.float64)
    mult = np.asarray(scen.win_mult, np.float64)[:, :, 0]   # local tier
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        if hi <= lo:
            continue
        active = (start <= lo) & (lo < end)                      # [E]
        seg = base * np.where(active[:, None], mult, 1.0).prod(axis=0)
        total += float(seg.sum()) * (hi - lo)
    return total / (T * scen.M)


# ---------------------------------------------------------------------------
# Traffic axis
# ---------------------------------------------------------------------------


def _shape_one(spec: TrafficSpec, T: int,
               rng: np.random.Generator) -> np.ndarray:
    """[T] float64 raw intensity shape of a single factor, clamped >= 0."""
    if hasattr(spec, "realize_shape"):
        # duck-typed extension hook: trace-backed traffic (repro.trace)
        # bins recorded arrival timestamps instead of evaluating a formula
        return np.maximum(
            np.asarray(spec.realize_shape(T, rng), np.float64), 0.0)
    t = np.arange(T, dtype=np.float64)
    if spec.kind == "stationary":
        shape = np.ones(T)
    elif spec.kind == "diurnal":
        shape = 1.0 + spec.amp * np.sin(2.0 * math.pi * spec.cycles * t / T)
    elif spec.kind == "flash":
        shape = np.ones(T)
        shape[int(spec.t0 * T):int(spec.t1 * T)] = spec.peak
    elif spec.kind == "mmpp":
        # 2-state Markov chain simulated host-side; start from the
        # stationary distribution so warmup statistics are unbiased.
        p01, p10 = spec.p_enter, spec.p_exit
        pi_burst = p01 / max(p01 + p10, 1e-12)
        state = 1 if rng.random() < pi_burst else 0
        shape = np.empty(T)
        u = rng.random(T)
        for i in range(T):
            shape[i] = spec.burst if state else 1.0
            if state == 0 and u[i] < p01:
                state = 1
            elif state == 1 and u[i] < p10:
                state = 0
    else:
        raise ValueError(f"unknown traffic kind {spec.kind!r}")
    # clamp before multiplying/normalizing: amp > 1 diurnals would otherwise
    # produce negative intensities (invalid Poisson rates) instead of dead
    # zones — and two negative factors must not multiply into spurious load
    return np.maximum(shape, 0.0)


def traffic_shape(spec, T: int, rng: np.random.Generator) -> np.ndarray:
    """[T] float32 intensity shape, normalized to mean 1 over the run.

    ``spec`` is a TrafficSpec or a TrafficProduct (the compose() merge of
    several non-trivial shapes): factors are realized left to right against
    the shared rng and multiplied pointwise, then normalized to mean 1
    once.  Deterministic factors (stationary / diurnal / flash) therefore
    compose order-invariantly; stochastic ones (mmpp) consume rng draws in
    factor order."""
    shape = np.ones(T, np.float64)
    for part in (spec.parts or (spec,)):
        shape = shape * _shape_one(part, T, rng)
    shape = shape / max(shape.mean(), 1e-12)
    return shape.astype(np.float32)


def arrival_counts(spec, T: int, mean_per_tick: float,
                   seed: int = 0) -> np.ndarray:
    """[T] int64 Poisson arrival counts following the traffic shape — the
    scenario-driven arrival trace the serve engine replays."""
    rng = np.random.default_rng(seed)
    return rng.poisson(mean_per_tick * traffic_shape(spec, T, rng))


# ---------------------------------------------------------------------------
# Placement axis
# ---------------------------------------------------------------------------


def _placement_arrays(spec: PlacementSpec, cluster: "Cluster",
                      rng: np.random.Generator):
    """(chunk_logits [C], chunk_locals [C, n_rep], epoch_logits [P, C]) —
    the last is None for single-epoch placements."""
    if hasattr(spec, "realize_catalog"):
        # duck-typed extension hook: trace-backed placement (repro.trace)
        # derives the catalog from observed chunk ids + churn episodes
        logits, locals_, epoch_logits = spec.realize_catalog(cluster, rng)
        return (jnp.asarray(logits), jnp.asarray(locals_),
                None if epoch_logits is None else jnp.asarray(epoch_logits))
    if spec.kind == "uniform":
        return None, None, None
    if spec.kind != "zipf":
        raise ValueError(f"unknown placement kind {spec.kind!r}")
    C = spec.chunks_per_server * cluster.M
    popularity = np.arange(1, C + 1, dtype=np.float64) ** (-spec.zipf_s)
    logits = np.log(popularity / popularity.sum()).astype(np.float32)
    # each chunk's replica triple: distinct servers, uniform placement —
    # the *popularity* is skewed, not the placement itself (HDFS-style)
    order = np.argsort(rng.random((C, cluster.M)), axis=1)
    locals_ = order[:, :cluster.n_replicas].astype(np.int32)
    if spec.hot_rack is not None:
        # adversarial placement: the hot head of the catalog (Zipf rows are
        # already popularity-ordered) lives entirely inside one rack
        R = cluster.rack_size
        if not 0 <= spec.hot_rack < cluster.K:
            raise ValueError(f"hot_rack {spec.hot_rack} out of range for "
                             f"K={cluster.K} racks")
        if R < cluster.n_replicas:
            raise ValueError(f"rack_size {R} cannot host "
                             f"{cluster.n_replicas} distinct replicas")
        n_hot = max(1, min(C, math.ceil(spec.hot_frac * C)))
        members = spec.hot_rack * R + np.arange(R)
        horder = np.argsort(rng.random((n_hot, R)), axis=1)
        locals_[:n_hot] = members[
            horder[:, :cluster.n_replicas]].astype(np.int32)
    return jnp.asarray(logits), jnp.asarray(locals_), None


def placement_epoch_at(scen: Optional[ScenarioData], t):
    """Scalar churn-epoch index at slot ``t`` (jit-safe; 0 when the
    scenario has no time-varying placement)."""
    if scen is None or scen.placement_epoch is None:
        return 0
    return scen.placement_epoch[t]


def catalog_cdf(logits) -> jnp.ndarray:
    """[..., C] float32 cumulative popularity of the categorical law
    ``softmax(logits)`` along the last axis: the table
    ``sample_locals_scenario`` searches with one uniform per task.

    Host-side, in float64 from logits shifted by their row maximum (the law
    is shift-invariant), then normalised: the last entry with positive mass,
    and every entry after it, is exactly 1.0, so a uniform in [0, 1) always
    lands inside the catalog, and an entry with no mass repeats the one
    before it and is never drawn.  A row whose logits are all equal (the
    uniform scenarios' dummy catalog, unused epoch rows) gets the uniform
    law, as ``jax.random.categorical`` would give it."""
    lg = np.asarray(logits, np.float64)
    cdf = np.cumsum(np.exp(lg - lg.max(axis=-1, keepdims=True)), axis=-1)
    return jnp.asarray((cdf / cdf[..., -1:]).astype(np.float32))


# pivots per level of the catalog search: the 128 lanes of a TPU vector
# register
SEARCH_FANOUT = 128


def catalog_levels(C: int) -> int:
    """Levels of the k-ary catalog search over C rows (k = SEARCH_FANOUT):
    ceil(log_k C), at least 1.  One level compares every task with every
    row."""
    levels = 1
    while SEARCH_FANOUT ** levels < C:
        levels += 1
    return levels


def catalog_index(cdf: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """The row of the sorted cumulative table ``cdf`` [C] each uniform of
    ``u`` [B] falls in: the count of entries <= u, as
    ``jnp.searchsorted(cdf, u, side="right")`` gives it.

    A k-ary search (k = SEARCH_FANOUT): each of ``catalog_levels(C)``
    levels gathers one row of k pivots per task (the last entry under
    each of k subtrees) and counts those <= u, which is the next base-k
    digit of the answer.  O(k log_k C) comparisons a task in a few
    dependent steps, where a binary search takes log2 C steps of one
    latency-bound gather each.  Pivots past the end read 1.0, which no
    uniform in [0, 1) reaches."""
    k, C = SEARCH_FANOUT, cdf.shape[-1]
    levels = catalog_levels(C)
    idx = jnp.zeros(u.shape, jnp.int32)
    for level in range(levels):
        s = k ** (levels - 1 - level)          # catalog rows per subtree
        rows = -(-C // (s * k))
        piv = cdf[s - 1::s]
        table = jnp.pad(piv, (0, rows * k - piv.shape[0]),
                        constant_values=1.0).reshape(rows, k)
        idx = idx * k + (table[idx] <= u[:, None]).sum(-1, dtype=jnp.int32)
    return idx


def sample_locals_scenario(key: jax.Array, cluster: "Cluster",
                           scen: ScenarioData, batch: int,
                           pe=0) -> jnp.ndarray:
    """Replica triples for ``batch`` tasks under the scenario's placement.

    Uniform placement defers to core.cluster.sample_locals.  A catalog
    placement draws each task's chunk by inverse CDF (one uniform, searched
    in the popularity table ``epoch_cdf`` by ``catalog_index``: O(log C)
    per task) and returns the chunk's fixed triple.  ``pe`` (scalar, may
    be traced — see placement_epoch_at) selects the active churn epoch's
    conditional popularity row; single-epoch placements carry one row.
    The chunk draws from the first half of ``split(key)``; canonical
    (padded) realizations carry ``placement_on`` and also draw the uniform
    law from the second half, and a scalar jnp.where picks one by data, so
    uniform and skewed scenarios share one trace."""
    from ..core.cluster import sample_locals

    if scen.chunk_locals is None:
        return sample_locals(key, cluster, batch)
    if scen.epoch_cdf is None:
        raise ValueError("a catalog placement draws from epoch_cdf: build "
                         "the ScenarioData with realize(), or set epoch_cdf "
                         "to catalog_cdf of its logits")
    k_cat, k_uni = jax.random.split(key)
    cdf = scen.epoch_cdf[pe]
    u = jax.random.uniform(k_cat, (batch,))
    cidx = catalog_index(cdf, u)
    skewed = scen.chunk_locals[jnp.minimum(cidx, cdf.shape[-1] - 1)]
    if scen.placement_on is None:
        return skewed
    uniform = sample_locals(k_uni, cluster, batch)
    return jnp.where(scen.placement_on > 0, skewed, uniform)


# ---------------------------------------------------------------------------
# Scenario stacking (the batched mega-sweep's input)
# ---------------------------------------------------------------------------


def stack_scenarios(scenarios, cluster: "Cluster", rates: "Rates", T: int,
                    pad: Optional[ScenarioPad] = None):
    """Realize every scenario against ONE canonical pad and stack the
    resulting pytrees along a new leading axis.

    Returns ``(stacked, lam_caps)`` where ``stacked`` is a ScenarioData
    whose every leaf carries a leading ``[S]`` scenario axis and
    ``lam_caps`` is a float64 ``[S]`` array of capacity-region edges
    (tasks/slot at load 1) in the same order.  This is the input contract
    of ``core.simulate_sweep``: because all S realizations share one
    canonical signature (same ScenarioPad, hence identical leaf shapes and
    pytree structure), the whole stack can be vmapped over — and
    shard_mapped across devices — by a single compiled program.

    ``scenarios`` is an iterable of registered names and/or Scenario
    objects; ``pad`` defaults to the registry-wide ``canonical_pad`` so a
    stacked sweep shares its compiled signature with per-scenario
    canonical runs.  Raises if a realization escapes the shared structure
    (e.g. an ad-hoc composition exceeding the pad's window headroom).
    """
    if pad is None:
        pad = canonical_pad(cluster)
    scens, caps = [], []
    for s in scenarios:
        scen, cap = realize(get_scenario(s), cluster, rates, T, pad=pad)
        scens.append(scen)
        caps.append(cap)
    if not scens:
        raise ValueError("stack_scenarios: empty scenario list")
    ref = jax.tree_util.tree_structure(scens[0])
    shapes = [l.shape for l in jax.tree_util.tree_leaves(scens[0])]
    for s, scen in zip(scenarios, scens[1:]):
        st = jax.tree_util.tree_structure(scen)
        sh = [l.shape for l in jax.tree_util.tree_leaves(scen)]
        if st != ref or sh != shapes:
            raise ValueError(
                f"stack_scenarios: scenario {getattr(s, 'name', s)!r} does "
                f"not realize to the shared canonical signature {pad} — "
                "widen the pad, e.g. canonical_pad(cluster, "
                "compose_depth=3) for 3-way compose() products "
                "(see registry_limits)")
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *scens)
    return stacked, np.asarray(caps, np.float64)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


_PAD_LOGIT = -1e30  # effectively -inf popularity: pad chunks get no mass in
#                     catalog_cdf (finite, so an all-pad row shifts to zeros)


def _pad_placement(chunk_logits, chunk_locals, epoch_logits,
                   cluster: "Cluster", n_chunks: int, n_epochs: int):
    """Canonicalize the placement axis to ``n_chunks`` catalog rows and
    ``n_epochs`` churn-epoch popularity rows.

    Uniform scenarios get a dummy catalog (never drawn: placement_on = 0);
    skewed ones are padded with _PAD_LOGIT rows.  Pad triples are the first
    n_replicas server ids — valid, but they carry no mass in epoch_cdf.
    epoch_logits is always emitted canonically: single-epoch placements
    mirror the global law in row 0 (identical values, so the draw reads
    the same law); unused epoch rows are all-_PAD_LOGIT and never indexed
    by placement_epoch."""
    n_rep = cluster.n_replicas
    dummy_row = np.arange(n_rep, dtype=np.int32)[None, :]
    if chunk_logits is None:
        logits = np.full(n_chunks, _PAD_LOGIT, np.float32)
        locals_ = np.repeat(dummy_row, n_chunks, axis=0)
        on = 0.0
    else:
        logits = np.asarray(chunk_logits, np.float32)
        locals_ = np.asarray(chunk_locals, np.int32)
        C = logits.shape[0]
        assert C <= n_chunks, (C, n_chunks)
        logits = np.pad(logits, (0, n_chunks - C),
                        constant_values=_PAD_LOGIT)
        locals_ = np.concatenate(
            [locals_, np.repeat(dummy_row, n_chunks - C, axis=0)], axis=0)
        on = 1.0
    if epoch_logits is None:
        elog = np.full((n_epochs, n_chunks), _PAD_LOGIT, np.float32)
        elog[0] = logits
    else:
        elog = np.asarray(epoch_logits, np.float32)
        E, C = elog.shape
        assert E <= n_epochs and C <= n_chunks, (elog.shape, n_epochs,
                                                 n_chunks)
        elog = np.pad(elog, ((0, n_epochs - E), (0, n_chunks - C)),
                      constant_values=_PAD_LOGIT)
    return (jnp.asarray(logits), jnp.asarray(locals_), jnp.float32(on),
            jnp.asarray(elog))


def realize(scenario: Scenario, cluster: "Cluster", rates: "Rates",
            T: int, pad: Optional[ScenarioPad] = None
            ) -> tuple[ScenarioData, float]:
    """Build the ScenarioData arrays + the capacity-region edge (tasks/slot
    at load = 1) for this scenario.  Deterministic in ``scenario.seed``.

    ``pad`` canonicalizes the pytree: window arrays are padded to
    pad.n_windows (inactive rows), the placement catalog to pad.n_chunks,
    and ``placement_on`` selects the placement law by data — so every
    scenario realized with the same pad shares one jit signature (the
    one-compile sweep; see canonical_pad / tests/test_scenarios.py's
    recompile-count guard).  pad=None reproduces the unpadded pytrees
    exactly."""
    rng = np.random.default_rng(scenario.seed)
    base, wstart, wend, wmult = _fleet_arrays(scenario.fleet, cluster, T, rng)
    lam_shape = traffic_shape(scenario.traffic, T, rng)
    chunk_logits, chunk_locals, epoch_logits = _placement_arrays(
        scenario.placement, cluster, rng)
    # slot -> churn-epoch map (trace-backed placements re-derive their
    # catalog per episode; everything else is single-epoch)
    placement_epoch = (
        jnp.asarray(np.asarray(scenario.placement.realize_epochs(T),
                               np.int32))
        if hasattr(scenario.placement, "realize_epochs") else None)
    # per-task size-multiplier law: lognormal normalized to mean exactly 1
    # (mu = -sigma^2/2), so lam_cap below needs no size correction; always
    # concrete scalars so every realization shares one pytree structure
    sigma = float(scenario.sizes.sigma)
    placement_on = None
    if pad is not None:
        E = wstart.shape[0]
        if E > pad.n_windows:
            raise ValueError(
                f"scenario {scenario.name!r} has {E} event windows but the "
                f"pad reserves only {pad.n_windows} (the default budget "
                f"covers {COMPOSE_DEPTH}-way compose() products).  Widen "
                f"it explicitly: canonical_pad(cluster, "
                f"compose_depth={max(2, -(-E // max(pad.n_windows // COMPOSE_DEPTH, 1)))}) "
                f"— or pad._replace(n_windows={E}) for a one-off")
        wstart = np.pad(wstart, (0, pad.n_windows - E))
        wend = np.pad(wend, (0, pad.n_windows - E))      # start == end: inert
        wmult = np.pad(wmult, ((0, pad.n_windows - E), (0, 0), (0, 0)),
                       constant_values=1.0)
        chunk_logits, chunk_locals, placement_on, epoch_logits = \
            _pad_placement(chunk_logits, chunk_locals, epoch_logits,
                           cluster, pad.n_chunks, pad.n_epochs)
        if placement_epoch is None:
            placement_epoch = jnp.zeros(T, jnp.int32)
    epoch_cdf = None
    if chunk_logits is not None:
        epoch_cdf = catalog_cdf(epoch_logits if epoch_logits is not None
                                else np.asarray(chunk_logits)[None])
    scen = ScenarioData(
        lam_shape=jnp.asarray(lam_shape),
        base_speed=jnp.asarray(base),
        win_start=jnp.asarray(wstart),
        win_end=jnp.asarray(wend),
        win_mult=jnp.asarray(wmult),
        chunk_logits=chunk_logits,
        chunk_locals=chunk_locals,
        placement_on=placement_on,
        size_mu=jnp.float32(-0.5 * sigma * sigma),
        size_sigma=jnp.float32(sigma),
        epoch_logits=epoch_logits,
        placement_epoch=placement_epoch,
        epoch_cdf=epoch_cdf,
    )
    # placement-aware capacity edge: uniform placement keeps the closed
    # form bit-for-bit; skewed catalogs get the fluid-LP optimum (local
    # import — capacity.py imports capacity_scale from this module)
    from .capacity import capacity_edge
    lam_cap = capacity_edge(scen, cluster, rates, T)
    return scen, lam_cap
