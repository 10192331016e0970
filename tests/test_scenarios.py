"""Scenario engine: spec realization, placement skew, per-server rates,
refsim-vs-JAX agreement on a heterogeneous fleet, canonical padding
(one-compile sweep guard), and PodRouter-vs-refsim end-to-end agreement
on the heterogeneous kernel path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Cluster,
    PodSpec,
    Rates,
    SimConfig,
    inv_rate_matrix,
    locality_class,
    rate_matrix,
    reset_trace_count,
    route_balanced_pandas_full,
    simulate,
    trace_count,
)
from repro.core.refsim import simulate_bp_ref
from repro.scenarios import (
    SCENARIOS,
    FleetSpec,
    PlacementSpec,
    Scenario,
    TrafficProduct,
    TrafficSpec,
    WindowSpec,
    arrival_counts,
    canonical_a_max,
    canonical_pad,
    capacity_scale,
    cascading_stragglers,
    compose,
    correlated_outages,
    get_scenario,
    realize,
    sample_locals_scenario,
    speed_at,
    speed_trace,
    traffic_shape,
)

CLUSTER = Cluster(M=24, K=4)
RATES = Rates(0.05, 0.025, 0.01)


def test_registry_has_named_scenarios():
    assert len(SCENARIOS) >= 5
    for required in ("uniform", "slow_rack", "straggler_wave",
                     "diurnal_burst", "zipf_hotspot"):
        assert required in SCENARIOS
    with pytest.raises(KeyError, match="unknown scenario"):
        get_scenario("no_such_scenario")


# ---------------------------------------------------------------------------
# fleet axis
# ---------------------------------------------------------------------------


def test_speed_windows_compose_and_capacity_is_exact():
    spec = Scenario(
        "w", fleet=FleetSpec(rack_speeds=(0.5,), windows=(
            WindowSpec(t0=0.25, t1=0.75, mult=0.5, rack=0),
            WindowSpec(t0=0.50, t1=0.75, mult=0.0, rack=1),
        )))
    T = 1000
    scen, lam_cap = realize(spec, CLUSTER, RATES, T)
    R = CLUSTER.rack_size
    s0 = np.asarray(speed_at(scen, 0))            # [M, 3] per-class speeds
    assert s0[0] == pytest.approx([0.5] * 3) and s0[R] == pytest.approx([1.0] * 3)
    s_mid = np.asarray(speed_at(scen, 600))       # both windows active
    assert s_mid[0] == pytest.approx([0.25] * 3)  # 0.5 base * 0.5 window
    assert s_mid[R] == pytest.approx([0.0] * 3)   # rack 1 drained
    s_end = np.asarray(speed_at(scen, 900))       # recovered
    assert s_end[0] == pytest.approx([0.5] * 3)
    assert s_end[R] == pytest.approx([1.0] * 3)

    # capacity_scale integrates the piecewise-constant LOCAL trace exactly
    tr = speed_trace(scen, T)                     # [T, M, 3] host oracle
    assert capacity_scale(scen, T) == pytest.approx(tr[..., 0].mean(),
                                                    rel=1e-9)
    assert lam_cap == pytest.approx(RATES.alpha * CLUSTER.M
                                    * tr[..., 0].mean())


def test_uniform_scenario_is_the_seed_model():
    scen, lam_cap = realize(get_scenario(None), CLUSTER, RATES, 100)
    assert np.asarray(scen.base_speed).tolist() == [1.0] * CLUSTER.M
    assert scen.chunk_locals is None
    np.testing.assert_allclose(np.asarray(scen.lam_shape), 1.0)
    assert lam_cap == pytest.approx(CLUSTER.M * RATES.alpha)


# ---------------------------------------------------------------------------
# traffic axis
# ---------------------------------------------------------------------------


def test_traffic_shapes_are_mean_one_and_shaped():
    rng = np.random.default_rng(0)
    T = 4000
    for kind in ("stationary", "diurnal", "flash", "mmpp"):
        shape = traffic_shape(TrafficSpec(kind=kind), T, rng)
        assert shape.shape == (T,)
        assert shape.mean() == pytest.approx(1.0, rel=1e-5)
        assert (shape >= 0).all()
    flash = traffic_shape(TrafficSpec(kind="flash", t0=0.5, t1=0.6,
                                      peak=2.5), T, rng)
    assert flash[int(0.55 * T)] / flash[0] == pytest.approx(2.5, rel=1e-6)


def test_arrival_counts_deterministic_and_calibrated():
    spec = TrafficSpec(kind="mmpp")
    a = arrival_counts(spec, 5000, mean_per_tick=2.0, seed=7)
    b = arrival_counts(spec, 5000, mean_per_tick=2.0, seed=7)
    np.testing.assert_array_equal(a, b)
    assert a.mean() == pytest.approx(2.0, rel=0.15)


# ---------------------------------------------------------------------------
# placement axis
# ---------------------------------------------------------------------------


def test_zipf_placement_distribution_and_determinism():
    spec = get_scenario("zipf_hotspot")
    scen, _ = realize(spec, CLUSTER, RATES, 100)
    scen2, _ = realize(spec, CLUSTER, RATES, 100)
    # realization is deterministic in the scenario seed
    np.testing.assert_array_equal(np.asarray(scen.chunk_locals),
                                  np.asarray(scen2.chunk_locals))
    np.testing.assert_array_equal(np.asarray(scen.chunk_logits),
                                  np.asarray(scen2.chunk_logits))

    key = jax.random.PRNGKey(0)
    loc = np.asarray(sample_locals_scenario(key, CLUSTER, scen, 8000))
    loc2 = np.asarray(sample_locals_scenario(key, CLUSTER, scen, 8000))
    np.testing.assert_array_equal(loc, loc2)      # same key -> same draws

    # triples are valid server ids, distinct within a task
    assert loc.min() >= 0 and loc.max() < CLUSTER.M
    assert all(len(set(row)) == CLUSTER.n_replicas for row in loc)

    # distribution sanity: triple frequencies follow the Zipf law -> the
    # hottest triple appears ~p_0 of the time and far more often than under
    # uniform placement over the chunk catalog
    triples = [tuple(sorted(r)) for r in loc]
    top_frac = max(np.unique([hash(t) for t in triples],
                             return_counts=True)[1]) / len(triples)
    probs = np.exp(np.asarray(scen.chunk_logits))
    assert top_frac == pytest.approx(float(probs.max()), rel=0.2)
    C = probs.shape[0]
    assert top_frac > 5.0 / C                     # >> uniform 1/C


def test_pod_candidates_membership_under_zipf_placement():
    """Interval pod sampling stays class-consistent when the locals come
    from the skewed placement law."""
    from repro.core import PodSpec, pod_candidates

    scen, _ = realize(get_scenario("zipf_hotspot"), CLUSTER, RATES, 100)
    key = jax.random.PRNGKey(3)
    locals_ = sample_locals_scenario(key, CLUSTER, scen, 64)
    cls = locality_class(CLUSTER, locals_)
    ci, cc, cv = pod_candidates(key, CLUSTER, locals_, PodSpec(2, 4))
    ci, cc, cv = map(np.asarray, (ci, cc, cv))
    cls_np = np.asarray(cls)
    for b in range(64):
        for j in range(ci.shape[1]):
            if cv[b, j]:
                assert cls_np[b, ci[b, j]] == cc[b, j]


# ---------------------------------------------------------------------------
# per-server workload metric
# ---------------------------------------------------------------------------


def test_per_server_workload_routing_matches_numpy_oracle():
    rng = np.random.default_rng(1)
    M = CLUSTER.M
    speed = rng.uniform(0.25, 2.0, M).astype(np.float32)
    inv_m = np.asarray(inv_rate_matrix(RATES, jnp.asarray(speed)))
    # oracle: 1 / (speed_m * rate_c)
    want = 1.0 / (speed[:, None] * np.array(
        [RATES.alpha, RATES.beta, RATES.gamma])[None, :])
    np.testing.assert_allclose(inv_m, want, rtol=1e-5)

    Q = rng.integers(0, 12, (M, 3))
    W = (Q * inv_m).sum(axis=1).astype(np.float32)
    locals_ = sample_locals_scenario(jax.random.PRNGKey(4), CLUSTER,
                                     realize(get_scenario("uniform"),
                                             CLUSTER, RATES, 10)[0], 32)
    cls = locality_class(CLUSTER, locals_)
    tie = jax.random.uniform(jax.random.PRNGKey(5), (M,))
    sel, sel_cls = route_balanced_pandas_full(
        jnp.asarray(W), cls, jnp.asarray(inv_m), tie)
    sel, sel_cls = np.asarray(sel), np.asarray(sel_cls)
    cls_np = np.asarray(cls)
    scores = W[None, :] * inv_m[np.arange(M)[None, :], cls_np]    # [B, M]
    np.testing.assert_allclose(W[sel] * inv_m[sel, sel_cls],
                               scores.min(axis=1), rtol=1e-6)


# ---------------------------------------------------------------------------
# canonical padding: semantics preserved, one compile for the whole registry
# ---------------------------------------------------------------------------


def test_canonical_padding_preserves_scenario_semantics():
    """Padded realization == unpadded realization on everything observable:
    speed traces, capacity edge, traffic shape; pad chunks are never drawn."""
    pad = canonical_pad(CLUSTER)
    for name in ("uniform", "straggler_wave", "zipf_hotspot", "hetero_storm",
                 "network_degraded", "cascade_flash"):
        spec = get_scenario(name)
        T = 400
        raw, cap_raw = realize(spec, CLUSTER, RATES, T)
        can, cap_can = realize(spec, CLUSTER, RATES, T, pad=pad)
        assert cap_can == pytest.approx(cap_raw, rel=1e-9)
        np.testing.assert_array_equal(np.asarray(raw.lam_shape),
                                      np.asarray(can.lam_shape))
        np.testing.assert_allclose(speed_trace(can, T), speed_trace(raw, T))
        assert can.win_start.shape == (pad.n_windows,)
        assert can.chunk_logits.shape == (pad.n_chunks,)
        assert float(can.placement_on) == (
            1.0 if spec.placement.kind != "uniform" else 0.0)
        if spec.placement.kind != "uniform":
            # draws come from the real catalog only (pads have ~ -inf logits)
            loc = np.asarray(sample_locals_scenario(
                jax.random.PRNGKey(1), CLUSTER, can, 4000))
            real = {tuple(r) for r in np.asarray(raw.chunk_locals)}
            assert all(tuple(r) in real for r in loc)


def test_scenario_sweep_shares_one_compiled_signature():
    """The recompile-count regression guard: all 9 registry scenarios,
    realized with the registry-wide canonical pad and a shared a_max, must
    run the jit'd simulator on ONE compiled signature — the property that
    makes the scenario sweep's wall-clock kernel-bound instead of
    compile-bound."""
    cluster = Cluster(M=16, K=4)
    rates = Rates(0.05, 0.025, 0.01)
    # distinctive cfg so this test cannot collide with another test's
    # identically-shaped jit cache entry (which would hide a retrace)
    cfg = SimConfig(T=96, warmup=32, route_mode="batched", s_max=16)
    pad = canonical_pad(cluster)
    a_max = canonical_a_max(cluster, rates, cfg, 0.5)
    reset_trace_count()
    for name in SCENARIOS:
        r = simulate("balanced_pandas", cluster, rates, 0.5,
                     jax.random.PRNGKey(0), cfg, scenario=name,
                     pad=pad, a_max=a_max)
        assert np.isfinite(float(r.mean_tasks_in_system)), name
    assert trace_count() == 1, f"registry sweep retraced: {trace_count()}"
    # an unpadded window scenario changes the pytree shapes -> retrace;
    # this is exactly what the canonical pad removes
    simulate("balanced_pandas", cluster, rates, 0.5, jax.random.PRNGKey(0),
             cfg, scenario="rack_outage")
    assert trace_count() == 2


# ---------------------------------------------------------------------------
# refsim vs JAX on a heterogeneous fleet
# ---------------------------------------------------------------------------


def test_refsim_and_jax_agree_on_heterogeneous_scenario():
    """Event-accurate numpy oracle vs the vectorized simulator on a
    slow-rack fleet: mean task count within 5% (acceptance criterion)."""
    slow = Scenario("slow_rack_test", fleet=FleetSpec(rack_speeds=(0.5,)))
    speed = np.ones(CLUSTER.M)
    speed[:CLUSTER.rack_size] = 0.5

    # load 0.55 keeps queue autocorrelation (and so seed-to-seed spread)
    # small enough that the 5% bar is ~4 sigma for these seed counts
    T, warmup, load = 16_000, 4_000, 0.55
    ref = np.mean([simulate_bp_ref(CLUSTER, RATES, load, T=T, warmup=warmup,
                                   seed=s, speed=speed).mean_tasks_in_system
                   for s in range(3)])
    cfg = SimConfig(T=T, warmup=warmup)
    jaxN = np.mean([float(simulate("balanced_pandas", CLUSTER, RATES, load,
                                   jax.random.PRNGKey(s), cfg,
                                   scenario=slow).mean_tasks_in_system)
                    for s in range(6)])
    assert abs(jaxN - ref) / ref < 0.05, (jaxN, ref)


# ---------------------------------------------------------------------------
# PodRouter end-to-end on the heterogeneous kernel path
# ---------------------------------------------------------------------------


def _podrouter_closed_loop(rate_m, speed, load, T, warmup, seed,
                           d_rack=2, d_remote=6):
    """Drive PodRouter through refsim's slotted loop: per-arrival routing
    (each arrival sees the previous one's queues, like refsim), own-queue
    local>rack>remote service at per-server speed, Q decremented at service
    start (router.complete mirrors refsim's bookkeeping).  Returns the
    post-warmup mean tasks in system."""
    from repro.sched import FleetTopology, PodRouter

    M, R = CLUSTER.M, CLUSTER.rack_size
    fleet = FleetTopology(n_replicas=M, n_pods=CLUSTER.K)
    router = PodRouter(fleet, RATES, policy="pod",
                       pod=PodSpec(d_rack, d_remote), seed=seed,
                       rate_matrix=rate_m)
    assert (router.heterogeneous == (rate_m is not None))
    rng = np.random.default_rng(seed)
    class_p = np.array([RATES.alpha, RATES.beta, RATES.gamma])
    lam = load * RATES.alpha * speed.sum()
    counts = np.zeros((M, 3), np.int64)       # queued-only, mirrors router.Q
    busy = np.zeros(M, bool)
    rem = np.zeros(M)
    sum_N, slots = 0.0, 0
    for t in range(T):
        rem[busy] -= speed[busy]
        done = busy & (rem <= 0)
        busy &= ~done
        starts_m, starts_c = [], []
        for m in np.where(~busy & (speed > 0))[0]:
            for c in range(3):
                if counts[m, c] > 0:
                    counts[m, c] -= 1
                    starts_m.append(m)
                    starts_c.append(c)
                    busy[m] = True
                    rem[m] = rng.geometric(class_p[c])   # speed-1 work units
                    break
        if starts_m:
            router.complete(np.array(starts_m), np.array(starts_c))
        for _ in range(rng.poisson(lam)):
            locals_ = rng.choice(M, size=CLUSTER.n_replicas, replace=False)
            sel = int(router.route(locals_[None, :])[0])
            c = (0 if sel in locals_
                 else 1 if (locals_ // R == sel // R).any() else 2)
            counts[sel, c] += 1
        if t >= warmup:
            sum_N += counts.sum() + busy.sum()
            slots += 1
    return sum_N / slots


def test_podrouter_hetero_kernel_path_matches_refsim():
    """Acceptance criterion: PodRouter with a slow-rack [M, 3] rate matrix —
    now routed through the Pallas kernels, no plain-JAX fallback — must
    reproduce the event-accurate refsim's completion-time stats (mean tasks
    in system, i.e. mean completion time via Little's law) within the
    existing 5% tolerance."""
    speed = np.ones(CLUSTER.M)
    speed[:CLUSTER.rack_size] = 0.5
    rm = np.asarray(rate_matrix(RATES, jnp.asarray(speed)))

    # load 0.45: BP-Pod on a slow rack mixes slowly at higher loads
    # (per-seed means of the refsim are heavy-tailed at 0.55), so run where
    # relaxation is fast enough that the 5% bar is well clear of seed noise
    T, warmup, load = 10_000, 2_500, 0.45
    router_N = np.mean([
        _podrouter_closed_loop(rm, speed, load, T, warmup, seed=s)
        for s in range(3)])
    ref_N = np.mean([
        simulate_bp_ref(CLUSTER, RATES, load, T=T, warmup=warmup, seed=s,
                        d_rack=2, d_remote=6, pod=True,
                        speed=speed).mean_tasks_in_system
        for s in range(8)])
    assert abs(router_N - ref_N) / ref_N < 0.05, (router_N, ref_N)


def test_podrouter_hetero_path_equals_homogeneous_on_identical_rows():
    """With identical rate-matrix rows the unified kernel path must be
    bit-identical to the homogeneous router: same selections, same Q, same
    workloads, for both policies."""
    from repro.sched import FleetTopology, PodRouter

    M = CLUSTER.M
    fleet = FleetTopology(n_replicas=M, n_pods=CLUSTER.K)
    rm = np.asarray(rate_matrix(RATES, jnp.ones(M)))     # rows == class rates
    rng = np.random.default_rng(7)
    for policy in ("pod", "full"):
        het = PodRouter(fleet, RATES, policy=policy, seed=3, rate_matrix=rm)
        hom = PodRouter(fleet, RATES, policy=policy, seed=3)
        assert het.heterogeneous and not hom.heterogeneous
        for _ in range(12):
            locals_ = rng.integers(0, M, (8, 3)).astype(np.int32)
            np.testing.assert_array_equal(het.route(locals_),
                                          hom.route(locals_.copy()))
        np.testing.assert_array_equal(np.asarray(het.Q), np.asarray(hom.Q))
        np.testing.assert_allclose(np.asarray(het.W), np.asarray(hom.W))
        assert het.stats.probes == hom.stats.probes


def test_heterogeneous_simulation_is_stable_at_moderate_load():
    """JAX-side sanity on slow_rack: BP-Pod is stable at 60% of the
    (speed-scaled) capacity region and throughput tracks arrivals."""
    cfg = SimConfig(T=12_000, warmup=4_000)   # slow rack lengthens warmup
    r = simulate("balanced_pandas_pod", CLUSTER, RATES, 0.6,
                 jax.random.PRNGKey(0), cfg, scenario="slow_rack")
    assert np.isfinite(float(r.mean_completion_slots))
    assert float(r.drift) < 1.6
    assert abs(float(r.throughput) / float(r.arrival_rate_hat) - 1) < 0.1


# ---------------------------------------------------------------------------
# compose() algebra
# ---------------------------------------------------------------------------


def _assert_scenario_data_equal(a, b):
    for x, y in zip(a, b):
        if x is None:
            assert y is None
        else:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_compose_with_uniform_is_identity():
    """compose(uniform, s) realizes to exactly s's arrays (every axis merge
    has `uniform` as its identity, and the XOR'd seed preserves s's rng)."""
    for name in ("slow_rack", "straggler_wave", "mmpp_bursty",
                 "zipf_hotspot", "network_degraded", "pod_flap"):
        s = get_scenario(name)
        c = compose("uniform", s)
        T = 500
        a, cap_a = realize(s, CLUSTER, RATES, T)
        b, cap_b = realize(c, CLUSTER, RATES, T)
        assert cap_b == pytest.approx(cap_a, rel=1e-12), name
        _assert_scenario_data_equal(a, b)
        # and from the left too (all merges treat uniform as identity)
        d, _ = realize(compose(s, "uniform"), CLUSTER, RATES, T)
        _assert_scenario_data_equal(a, d)


def test_compose_order_invariance_on_deterministic_axes():
    """Fleet merge (window union, speed product) and deterministic traffic
    products are order-invariant through realization."""
    T = 600
    pairs = [("slow_rack", "straggler_wave"),      # speeds x windows
             ("slow_rack", "flash_crowd"),         # fleet x traffic
             ("diurnal_burst", "flash_crowd"),     # deterministic product
             ("network_degraded", "rack_outage")]  # per-class x outage
    for na, nb in pairs:
        ab, cap_ab = realize(compose(na, nb), CLUSTER, RATES, T)
        ba, cap_ba = realize(compose(nb, na), CLUSTER, RATES, T)
        assert cap_ab == pytest.approx(cap_ba, rel=1e-9), (na, nb)
        np.testing.assert_allclose(np.asarray(ab.lam_shape),
                                   np.asarray(ba.lam_shape), rtol=1e-6)
        np.testing.assert_allclose(speed_trace(ab, T), speed_trace(ba, T),
                                   rtol=1e-6)


def test_compose_merges_every_axis():
    c = compose("slow_rack", "flash_crowd", "zipf_hotspot")
    assert c.name == "slow_rack+flash_crowd+zipf_hotspot"
    assert c.fleet.rack_speeds == (0.5,)
    assert c.placement.kind == "zipf"
    T = 1000
    scen, lam_cap = realize(c, CLUSTER, RATES, T)
    lam = np.asarray(scen.lam_shape, np.float64)
    assert lam.mean() == pytest.approx(1.0, rel=1e-5)
    # the flash step survives composition (single non-trivial factor)
    assert lam[int(0.55 * T)] / lam[0] == pytest.approx(2.5, rel=1e-5)
    assert scen.chunk_locals is not None
    R = CLUSTER.rack_size
    want_scale = (0.5 * R + (CLUSTER.M - R)) / CLUSTER.M
    closed = RATES.alpha * CLUSTER.M * want_scale
    # the composition carries zipf_hotspot's skewed catalog, so lam_cap is
    # the fluid-LP edge: at most the fleet-only closed form, and strictly
    # below it when the hot chunks' local tier binds (which it does here)
    assert 0 < lam_cap < closed

    # persistent speeds multiply elementwise on double composition
    cc = compose("slow_rack", "slow_rack")
    assert cc.fleet.rack_speeds == (0.25,)


def test_compose_traffic_product_is_renormalized_product():
    c = compose("diurnal_burst", "flash_crowd")
    assert isinstance(c.traffic, TrafficProduct)
    T = 2000
    rng = np.random.default_rng(0)
    d = traffic_shape(get_scenario("diurnal_burst").traffic, T, rng)
    f = traffic_shape(get_scenario("flash_crowd").traffic, T, rng)
    want = (d.astype(np.float64) * f)
    want = want / want.mean()
    got = traffic_shape(c.traffic, T, np.random.default_rng(1))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert got.mean() == pytest.approx(1.0, rel=1e-5)


def test_compose_rightmost_nonuniform_placement_wins():
    z15 = Scenario("z15", placement=PlacementSpec(kind="zipf", zipf_s=1.5))
    assert compose("zipf_hotspot", z15).placement.zipf_s == 1.5
    assert compose(z15, "zipf_hotspot").placement.zipf_s == 1.2
    assert compose(z15, "uniform").placement.zipf_s == 1.5  # uniform: no-op


def test_mixed_base_and_composed_sweep_shares_one_signature():
    """Acceptance: compose() of any two registry scenarios realizes to the
    canonical pytree signature (registry_limits reserves pairwise window
    headroom), and a mixed base+composed sweep compiles exactly once."""
    cluster = Cluster(M=16, K=4)
    rates = Rates(0.05, 0.025, 0.01)
    cfg = SimConfig(T=88, warmup=24, route_mode="batched", s_max=16)
    pad = canonical_pad(cluster)

    # worst-case pairwise window union fits the canonical shapes
    widest = max(SCENARIOS.values(), key=lambda s: len(s.fleet.windows))
    worst = compose(widest, widest, name="worst_case")
    uni, _ = realize(get_scenario("uniform"), cluster, rates, cfg.T, pad=pad)
    com, _ = realize(worst, cluster, rates, cfg.T, pad=pad)
    assert (jax.tree_util.tree_structure(uni)
            == jax.tree_util.tree_structure(com))
    for u, c in zip(uni, com):
        assert u.shape == c.shape and u.dtype == c.dtype

    composed = [compose("slow_rack", "flash_crowd"),
                compose("network_degraded", "zipf_hotspot"),
                compose("straggler_wave", "tor_cascade", name="wave_cascade")]
    sweep = list(SCENARIOS) + composed
    a_max = canonical_a_max(cluster, rates, cfg, 0.5,
                            scenarios=list(SCENARIOS.values()) + composed)
    reset_trace_count()
    for s in sweep:
        r = simulate("balanced_pandas", cluster, rates, 0.5,
                     jax.random.PRNGKey(0), cfg, scenario=s,
                     pad=pad, a_max=a_max)
        assert np.isfinite(float(r.mean_tasks_in_system)), s
    assert trace_count() == 1, f"mixed sweep retraced: {trace_count()}"


# ---------------------------------------------------------------------------
# per-class (network-tier) windows + correlated-failure generators
# ---------------------------------------------------------------------------


def test_network_degraded_scales_only_beta_gamma():
    T = 1000
    scen, lam_cap = realize(get_scenario("network_degraded"), CLUSTER,
                            RATES, T)
    s = np.asarray(speed_at(scen, T // 2))        # inside the window
    np.testing.assert_allclose(s[:, 0], 1.0)
    np.testing.assert_allclose(s[:, 1], 0.4, rtol=1e-6)
    np.testing.assert_allclose(s[:, 2], 0.25, rtol=1e-6)
    s_out = np.asarray(speed_at(scen, 0))         # outside
    np.testing.assert_allclose(s_out, 1.0)
    # the capacity edge is local-service-bound: beta/gamma-only degradation
    # must not move it
    _, lam_uni = realize(get_scenario("uniform"), CLUSTER, RATES, T)
    assert lam_cap == pytest.approx(lam_uni, rel=1e-12)


def test_out_of_range_rack_selector_is_loud():
    """A window targeting a rack the cluster doesn't have must raise at
    realization, not silently become an inert no-op event."""
    for w in (WindowSpec(t0=0.1, t1=0.2, mult=0.0, rack=CLUSTER.K),
              WindowSpec(t0=0.1, t1=0.2, mult=0.5,
                         rack_member=(CLUSTER.K, 0))):
        with pytest.raises(ValueError, match="targets rack"):
            realize(Scenario("bad", fleet=FleetSpec(windows=(w,))),
                    CLUSTER, RATES, 100)


def test_correlated_outages_generator():
    ws = correlated_outages(n_events=5, n_racks=4, seed=7)
    assert ws == correlated_outages(n_events=5, n_racks=4, seed=7)
    assert ws != correlated_outages(n_events=5, n_racks=4, seed=8)
    assert len(ws) == 5
    for w in ws:
        assert w.mult == 0.0 and 0 <= w.rack < 4
        assert 0.0 <= w.t0 < w.t1 <= 1.0
        assert w.class_mult == (0.0, 0.0, 0.0)    # whole-pod drain
        assert 0.02 * 0.999 <= w.t1 - w.t0 <= 0.20 + 1e-9


def test_cascading_stragglers_generator_and_realization():
    ws = cascading_stragglers(n_events=3, n_racks=4, seed=5)
    assert len(ws) == 6                            # straggler + ToR per event
    scen, _ = realize(Scenario("cg", fleet=FleetSpec(windows=ws)),
                      CLUSTER, RATES, 1000)
    wm = np.asarray(scen.win_mult)                 # [6, M, 3]
    R = CLUSTER.rack_size
    for e, (a, b) in enumerate(zip(ws[::2], ws[1::2])):
        assert a.rack_member is not None and a.rack_member[0] == b.rack
        assert (a.t0, a.t1) == (b.t0, b.t1)
        # straggler window: exactly one server, all tiers slowed
        hit = np.where((wm[2 * e] != 1.0).any(axis=1))[0]
        assert len(hit) == 1 and hit[0] // R == b.rack
        np.testing.assert_allclose(wm[2 * e, hit[0]], 0.25)
        # cascade window: the whole rack's beta tier only
        hit2 = np.where((wm[2 * e + 1] != 1.0).any(axis=1))[0]
        assert len(hit2) == R and (hit2 // R == b.rack).all()
        np.testing.assert_allclose(wm[2 * e + 1, hit2[0]], [1.0, 0.5, 1.0])


# ---------------------------------------------------------------------------
# +inf zero-rate contract (the old finite sentinel absorbed tasks)
# ---------------------------------------------------------------------------


def test_drained_empty_server_scores_inf_not_zero():
    from repro.core import pod_candidates, route_pod_candidates, weighted_score

    speed = np.ones((CLUSTER.M, 3), np.float32)
    speed[0] = 0.0                                 # server 0 fully drained
    inv_m = inv_rate_matrix(RATES, jnp.asarray(speed))
    assert not np.isfinite(np.asarray(inv_m)[0]).any()

    # the contract primitive: 0 workload x inf inverse rate -> inf, not NaN
    s = np.asarray(weighted_score(jnp.zeros(3), np.asarray(inv_m)[0]))
    assert np.isinf(s).all() and not np.isnan(s).any()

    # full-BP routing over an EMPTY fleet: a task local to the dead server
    # must route to a live replica (the ROADMAP bug: the finite sentinel
    # made the dead server score 0 and absorb one task per outage window)
    W = jnp.zeros(CLUSTER.M)
    locals_ = jnp.asarray([[0, 1, 2]], jnp.int32)
    cls = locality_class(CLUSTER, locals_)
    tie = jax.random.uniform(jax.random.PRNGKey(0), (CLUSTER.M,))
    sel, sel_cls = route_balanced_pandas_full(W, cls, inv_m, tie)
    assert int(sel[0]) in (1, 2)                   # live locals win
    assert int(sel_cls[0]) == 0

    # pod routing with the dead server in the candidate list
    key = jax.random.PRNGKey(1)
    ci, cc, cv = pod_candidates(key, CLUSTER, locals_, PodSpec(2, 4))
    sel_p, _ = route_pod_candidates(key, W, ci, cc, cv, inv_m)
    assert int(sel_p[0]) != 0


def test_outage_window_does_not_absorb_tasks_end_to_end():
    """During a whole-rack drain the dead rack's queues must stay empty
    under BP routing (no task is ever routed to a drained server)."""
    cfg = SimConfig(T=2_000, warmup=200)
    spec = Scenario("drain", fleet=FleetSpec(windows=(
        WindowSpec(t0=0.0, t1=1.0, mult=0.0, rack=0),)))
    r = simulate("balanced_pandas", CLUSTER, RATES, 0.4,
                 jax.random.PRNGKey(2), cfg, scenario=spec)
    # Little's-law N stays finite and the run is stable: the drained rack
    # absorbed nothing (absorbed tasks would never complete -> drift >> 1)
    assert np.isfinite(float(r.mean_tasks_in_system))
    assert float(r.drift) < 1.5
    assert float(r.throughput) / float(r.arrival_rate_hat) > 0.9


# ---------------------------------------------------------------------------
# refsim vs JAX on a per-class-window scenario
# ---------------------------------------------------------------------------


def test_refsim_and_jax_agree_on_per_class_windows():
    """Event-accurate numpy oracle vs the vectorized simulator with beta
    and gamma tiers at half speed fleet-wide (a full-run per-class window
    on the JAX side, a constant [M, 3] speed matrix on the refsim side):
    mean task count within 5%."""
    spec = Scenario("nd_const", fleet=FleetSpec(windows=(
        WindowSpec(t0=0.0, t1=1.0, mult=(1.0, 0.5, 0.5), every=1),)))
    speed = np.ones((CLUSTER.M, 3))
    speed[:, 1:] = 0.5

    # load 0.45: with halved beta/gamma the chain mixes slowly above ~0.5
    # (stationary N is large and warmup-dominated on both sides); at 0.45
    # relaxation is fast and the 5% bar is several sigma for these seeds
    T, warmup, load = 12_000, 3_000, 0.45
    ref = np.mean([simulate_bp_ref(CLUSTER, RATES, load, T=T, warmup=warmup,
                                   seed=s, speed=speed).mean_tasks_in_system
                   for s in range(3)])
    cfg = SimConfig(T=T, warmup=warmup)
    jaxN = np.mean([float(simulate("balanced_pandas", CLUSTER, RATES, load,
                                   jax.random.PRNGKey(s), cfg,
                                   scenario=spec).mean_tasks_in_system)
                    for s in range(6)])
    assert abs(jaxN - ref) / ref < 0.05, (jaxN, ref)


# ---------------------------------------------------------------------------
# batched BP path through the Pallas kernels
# ---------------------------------------------------------------------------


def test_batched_kernel_path_agrees_with_sequential_on_hetero():
    """The route_mode="batched" BP path runs the fused route_commit
    megakernel; on a slow-rack fleet it must agree with the sequential
    plain-JAX path at the same tolerance the homogeneous
    batched-vs-sequential test uses."""
    cfg_s = SimConfig(T=6_000, warmup=1_500)
    cfg_b = SimConfig(T=6_000, warmup=1_500, route_mode="batched")
    for algo in ("balanced_pandas", "balanced_pandas_pod"):
        a = float(simulate(algo, CLUSTER, RATES, 0.6, jax.random.PRNGKey(3),
                           cfg_s, scenario="slow_rack").mean_completion_slots)
        b = float(simulate(algo, CLUSTER, RATES, 0.6, jax.random.PRNGKey(3),
                           cfg_b, scenario="slow_rack").mean_completion_slots)
        assert abs(a - b) / a < 0.25, (algo, a, b)


def test_batched_fused_path_agrees_with_sequential_under_flash():
    """The snapshot-herding regression, end to end: flash_crowd drives
    large multi-arrival slots (2.5x peak), exactly where the old batched
    path routed a whole burst against one workload snapshot and herded it
    onto the argmin server (inflating completion times far beyond the
    sequential path).  With in-kernel sequential commits the batched and
    sequential paths must agree for every batched algorithm — BP, BP-Pod,
    and JSQ-MW-Pod.  clip_fraction == 0 also locks the peak-aware
    resolve_a_max sizing: the flash peak must fit the arrival buffer."""
    cfg_s = SimConfig(T=6_000, warmup=1_500)
    cfg_b = SimConfig(T=6_000, warmup=1_500, route_mode="batched")
    for algo in ("balanced_pandas", "balanced_pandas_pod",
                 "jsq_maxweight_pod"):
        rs = simulate(algo, CLUSTER, RATES, 0.6, jax.random.PRNGKey(5),
                      cfg_s, scenario="flash_crowd")
        rb = simulate(algo, CLUSTER, RATES, 0.6, jax.random.PRNGKey(5),
                      cfg_b, scenario="flash_crowd")
        assert float(rs.clip_fraction) == 0.0, algo
        assert float(rb.clip_fraction) == 0.0, algo
        a = float(rs.mean_completion_slots)
        b = float(rb.mean_completion_slots)
        assert abs(a - b) / a < 0.25, (algo, a, b)


def test_batched_fused_path_agrees_with_refsim():
    """Acceptance criterion: the fused batched path vs the event-accurate
    numpy refsim oracle, which routes every arrival against queues that
    include all earlier arrivals in the slot — the semantics the megakernel
    now implements in-kernel.  At load 0.5 multi-arrival slots are routine,
    so snapshot herding would push N well past the 5% bar."""
    T, warmup, load = 12_000, 3_000, 0.5
    ref = np.mean([simulate_bp_ref(CLUSTER, RATES, load, T=T, warmup=warmup,
                                   seed=s).mean_tasks_in_system
                   for s in range(3)])
    cfg = SimConfig(T=T, warmup=warmup, route_mode="batched")
    jaxN = np.mean([float(simulate("balanced_pandas", CLUSTER, RATES, load,
                                   jax.random.PRNGKey(s),
                                   cfg).mean_tasks_in_system)
                    for s in range(6)])
    assert abs(jaxN - ref) / ref < 0.05, (jaxN, ref)


# ---------------------------------------------------------------------------
# peak-aware arrival-buffer sizing
# ---------------------------------------------------------------------------


def test_resolve_a_max_sizes_from_peak_intensity():
    """resolve_a_max bounds the Poisson tail at the PEAK slot intensity
    (lam * shape_peak), not the mean — sizing from the mean clipped
    arrivals in exactly the flash/diurnal scenarios the clip warnings
    exist for."""
    cfg = SimConfig(T=100, warmup=10)
    assert cfg.resolve_a_max(10.0, 5.0) == cfg.resolve_a_max(50.0)
    assert cfg.resolve_a_max(10.0, 5.0) > cfg.resolve_a_max(10.0)
    assert cfg.resolve_a_max(10.0, 1.0) == cfg.resolve_a_max(10.0)
    # explicit a_max still overrides the auto sizing
    assert dataclasses.replace(cfg, a_max=7).resolve_a_max(10.0, 5.0) == 7
    # the shared canonical width covers every registry scenario's peak:
    # at least as wide as the peakiest shape demands
    cluster = Cluster(M=16, K=4)
    am = canonical_a_max(cluster, RATES, cfg, 0.5)
    lam_cap = 0.5 * RATES.alpha * cluster.M
    peaks = []
    for spec in SCENARIOS.values():
        scen, _ = realize(spec, cluster, RATES, cfg.T)
        peaks.append(float(np.max(np.asarray(scen.lam_shape))))
    assert am >= cfg.resolve_a_max(lam_cap, max(peaks))


# ---------------------------------------------------------------------------
# the catalog draw: inverse CDF over epoch_cdf
# ---------------------------------------------------------------------------

CAT_CLUSTER = Cluster(M=40, K=4)


def _index_catalog(scen):
    """``scen`` with row c's triple replaced by (c, c, c): a draw then
    returns the drawn chunk's index itself."""
    C = scen.chunk_locals.shape[0]
    rows = jnp.repeat(jnp.arange(C, dtype=jnp.int32)[:, None],
                      CAT_CLUSTER.n_replicas, axis=1)
    return scen._replace(chunk_locals=rows)


def _drawn_indices(scen, n, key=7, pe=0):
    loc = sample_locals_scenario(jax.random.PRNGKey(key), CAT_CLUSTER,
                                 _index_catalog(scen), n, pe=pe)
    return np.asarray(loc)[:, 0]


def _assert_follows(idx, probs, n_sigma=5.0):
    """Every chunk's frequency within n_sigma binomial deviations of its
    probability (plus float32's resolution of the table)."""
    n = idx.shape[0]
    freq = np.bincount(idx, minlength=probs.shape[0]) / n
    tol = n_sigma * np.sqrt(probs * (1 - probs) / n) + 1e-6
    bad = np.nonzero(np.abs(freq - probs) > tol)[0]
    assert bad.size == 0, [(int(c), freq[c], probs[c]) for c in bad[:5]]


@pytest.mark.parametrize("padded", [False, True])
def test_uniform_scenario_draws_equal_sample_locals_on_the_second_key(
        padded):
    """A uniform scenario's triples are core.cluster.sample_locals on the
    second half of the key's split (canonical) or on the key itself
    (unpadded), bit for bit, whatever the catalog table holds."""
    from repro.core.cluster import sample_locals
    pad = canonical_pad(CAT_CLUSTER) if padded else None
    scen, _ = realize(get_scenario("uniform"), CAT_CLUSTER, RATES, 50,
                      pad=pad)
    for seed in (0, 1, 2 ** 31 + 5):
        for batch in (1, 22, 90):
            key = jax.random.PRNGKey(seed)
            got = sample_locals_scenario(key, CAT_CLUSTER, scen, batch,
                                         pe=jnp.int32(0))
            want = sample_locals(jax.random.split(key)[1] if padded
                                 else key, CAT_CLUSTER, batch)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("padded", [False, True])
def test_zipf_catalog_draw_follows_the_popularity_law(padded):
    pad = canonical_pad(CAT_CLUSTER) if padded else None
    scen, _ = realize(get_scenario("zipf_hotspot"), CAT_CLUSTER, RATES, 50,
                      pad=pad)
    probs = np.exp(np.asarray(scen.chunk_logits, np.float64))
    assert probs.sum() == pytest.approx(1.0, abs=1e-5)
    _assert_follows(_drawn_indices(scen, 200_000), probs)


def test_churn_epoch_row_draw_follows_its_conditional_law():
    """A trace-backed placement: each epoch row of epoch_cdf is that
    epoch's conditional popularity, and a draw at epoch e stays on e's
    rows."""
    from repro.trace import scenario_from_trace, synth_trace
    log = synth_trace(name="churn", n_tasks=2000, seed=3, churn_t=(0.5,),
                      n_tenants=2, n_chunks=64)
    scen, _ = realize(scenario_from_trace(log, seed=1), CAT_CLUSTER, RATES,
                      64, pad=canonical_pad(CAT_CLUSTER))
    elog = np.asarray(scen.epoch_logits, np.float64)
    assert elog.shape[0] >= 2
    for e in (0, 1):
        probs = np.exp(elog[e] - elog[e].max())
        probs /= probs.sum()
        idx = _drawn_indices(scen, 120_000, key=11 + e, pe=jnp.int32(e))
        _assert_follows(idx, probs)
        assert probs[idx].min() > 1e-9          # only the epoch's own rows


def test_catalog_rows_without_mass_are_never_drawn():
    """Pad rows and _PAD_LOGIT rows carry no mass; an all-_PAD_LOGIT row
    (the uniform scenarios' dummy catalog) still draws in range."""
    from repro.scenarios.build import _PAD_LOGIT, catalog_cdf
    pad = canonical_pad(CAT_CLUSTER)
    raw, _ = realize(get_scenario("zipf_hotspot"), CAT_CLUSTER, RATES, 50)
    C_real = raw.chunk_logits.shape[0]
    can, _ = realize(get_scenario("zipf_hotspot"), CAT_CLUSTER, RATES, 50,
                     pad=pad)
    C = can.chunk_logits.shape[0]
    cdf = np.asarray(can.epoch_cdf)
    assert cdf.dtype == np.float32 and cdf.shape[1] == C
    assert np.all(np.diff(cdf[0]) >= 0) and np.all(cdf[0, C_real - 1:] == 1.0)

    # mass on every third row only, the rest _PAD_LOGIT
    logits = np.full(C, _PAD_LOGIT, np.float32)
    live = np.arange(0, C, 3)
    logits[live] = np.log(np.linspace(1.0, 2.0, live.size))
    gappy = can._replace(epoch_cdf=catalog_cdf(logits[None]))
    idx = _drawn_indices(gappy, 50_000)
    assert set(np.unique(idx)) <= set(live.tolist())
    assert np.asarray(gappy.epoch_cdf)[0, live[-1]] == 1.0

    # an all-_PAD_LOGIT row: uniform over the catalog, always in range
    dead = can._replace(epoch_cdf=catalog_cdf(
        np.full((1, C), _PAD_LOGIT, np.float32)))
    idx = _drawn_indices(dead, 50_000)
    assert idx.min() >= 0 and idx.max() < C
    _assert_follows(idx, np.full(C, 1.0 / C))

    # the uniform scenario's dummy catalog and its unused epoch rows
    uni, _ = realize(get_scenario("uniform"), CAT_CLUSTER, RATES, 50,
                     pad=pad)
    for e in range(uni.epoch_cdf.shape[0]):
        idx = _drawn_indices(uni._replace(placement_on=None), 4_000,
                             pe=jnp.int32(e))
        assert idx.min() >= 0 and idx.max() < C
    # a hand-built catalog without its table is refused, not misdrawn
    with pytest.raises(ValueError, match="epoch_cdf"):
        sample_locals_scenario(jax.random.PRNGKey(0), CAT_CLUSTER,
                               can._replace(epoch_cdf=None), 4)


@pytest.mark.parametrize("C", [1, 2, 127, 128, 129, 2000, 16384, 16385,
                               20000])
@pytest.mark.parametrize("fanout", [2, 3, 128])
def test_catalog_index_equals_searchsorted(monkeypatch, C, fanout):
    """The k-ary search finds the row jnp.searchsorted finds, at uniforms
    drawn at random and at every table entry and the float just below
    it, with rows of no mass and a heavy row among them."""
    from repro.scenarios import build
    monkeypatch.setattr(build, "SEARCH_FANOUT", fanout)
    p = np.random.default_rng(C).random(C)
    p[1::3] = 0.0
    p[C // 2] += p.sum()
    cdf = build.catalog_cdf(np.where(p > 0, np.log(np.where(p > 0, p, 1.0)),
                                     build._PAD_LOGIT))
    top = np.float32(1) - np.float32(2 ** -24)
    u = jnp.clip(jnp.concatenate([
        jax.random.uniform(jax.random.PRNGKey(C), (4000,)), cdf,
        jnp.nextafter(cdf, 0.0)]), 0.0, top)
    np.testing.assert_array_equal(
        np.asarray(build.catalog_index(cdf, u)),
        np.asarray(jnp.searchsorted(cdf, u, side="right")))


def test_sweep_builds_no_catalog_wide_draw(monkeypatch):
    """A BP-Pod sweep over uniform and zipf_hotspot traces no O(B*C)
    catalog draw; a one-level search, which compares every task with
    every row, counts one."""
    from repro.core import catalog_draw_count, simulate_sweep
    from repro.scenarios import build
    grid = dict(loads=(0.5, 0.9), n_seeds=2, seed0=3,
                scenarios=["uniform", "zipf_hotspot"])
    names, res, _ = simulate_sweep(
        "balanced_pandas_pod", CAT_CLUSTER, RATES,
        cfg=SimConfig(T=120, warmup=40, route_mode="batched"),
        pod=PodSpec(2, 6), **grid)
    assert catalog_draw_count() == 0
    assert np.all(np.isfinite(np.asarray(res.mean_tasks_in_system)))
    monkeypatch.setattr(build, "SEARCH_FANOUT",
                        canonical_pad(CAT_CLUSTER).n_chunks)
    simulate_sweep("balanced_pandas_pod", CAT_CLUSTER, RATES,
                   cfg=SimConfig(T=121, warmup=40, route_mode="batched"),
                   pod=PodSpec(2, 6), **grid)
    assert catalog_draw_count() == 1
