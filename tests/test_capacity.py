"""Placement-aware capacity edge (scenarios.capacity) + auto-extend warmup.

Covers the fluid-LP edge's contract from every side:

* exactness — LP == the hand-computable edge of a single-hot-triple
  catalog, and == the closed form on a disjoint uniform catalog (the
  regression identity);
* dispatch — uniform scenarios keep the closed form BIT-FOR-BIT, skewed
  registry scenarios get a strictly smaller honest edge, padded == raw;
* ground truth — a brute-force refsim stability bracket at small M
  confirms the true edge lies within 2% of the LP optimum;
* the drift-aware auto-extend warmup loop (telemetry.export): slow-mixing
  runs extend and converge below threshold, fast-mixing runs never extend,
  unmeasurable (NaN) drift is loudly NOT converged;
* the 3+-way compose() pad overflow: a helpful ValueError naming
  ``canonical_pad(..., compose_depth=...)``, and that override working.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Cluster, Rates, SimConfig, simulate, simulate_auto_warmup
from repro.core.refsim import simulate_bp_ref
from repro.scenarios import SCENARIOS, canonical_pad, compose, realize
from repro.scenarios import capacity
from repro.scenarios.build import ScenarioData
from repro.scenarios.capacity import (
    capacity_edge,
    chunk_demand,
    fluid_edge,
    speed_segments,
    uniform_edge,
)
from repro.telemetry import (
    TelemetryConfig,
    WarmupPolicy,
    auto_extend_warmup,
    windowed_drift,
)

RATES = Rates(0.05, 0.025, 0.01)


def _scen(M, T, logits, locals_):
    """Minimal ScenarioData with an explicit placement catalog."""
    return ScenarioData(
        lam_shape=jnp.ones(T, jnp.float32),
        base_speed=jnp.ones(M, jnp.float32),
        win_start=jnp.zeros(0, jnp.int32),
        win_end=jnp.zeros(0, jnp.int32),
        win_mult=jnp.ones((0, M, 3), jnp.float32),
        chunk_logits=jnp.asarray(logits, jnp.float32),
        chunk_locals=jnp.asarray(locals_, jnp.int32),
    )


# ---------------------------------------------------------------------------
# LP exactness
# ---------------------------------------------------------------------------


def test_lp_matches_hand_computed_single_triple_edge():
    # every task lands on chunk 0 with replicas {0,1,2} = all of rack 0
    # (M=6, K=2, rack_size=3): at the edge the 3 local servers serve at
    # alpha and the 3 remote servers at gamma -> lam* = 3a + 3g exactly.
    cl = Cluster(M=6, K=2)
    scen = _scen(6, 1000, [0.0], [[0, 1, 2]])
    want = 3 * RATES.alpha + 3 * RATES.gamma
    got = fluid_edge(scen, cl, RATES, 1000)
    assert got == pytest.approx(want, rel=1e-9)


def test_lp_regression_identity_uniform_catalog():
    # a disjoint catalog spreading equal demand over all servers is
    # placement-uniform in effect: the LP must reproduce the closed form
    # alpha * M (every server busy on local work at the edge).
    cl = Cluster(M=12, K=3)
    locals_ = [[3 * i, 3 * i + 1, 3 * i + 2] for i in range(4)]
    scen = _scen(12, 1000, [0.0] * 4, locals_)
    got = fluid_edge(scen, cl, RATES, 1000)
    assert got == pytest.approx(uniform_edge(scen, RATES, 1000), rel=1e-9)
    assert got == pytest.approx(RATES.alpha * 12, rel=1e-9)


@pytest.mark.parametrize("M,K", [(24, 4), (60, 10)])
@pytest.mark.parametrize("parts", [
    ("zipf_hotspot",), ("adversarial_placement",),
    ("rack_outage", "zipf_hotspot"), ("slow_rack", "zipf_hotspot"),
    ("network_degraded", "zipf_hotspot")])
def test_rack_pooled_lp_equals_the_server_lp(parts, M, K, monkeypatch):
    """Where a rack's servers share their rack-local and remote speeds,
    the LP that pools those two classes per rack has the per-server
    LP's optimum."""
    cl = Cluster(M=M, K=K)
    scen, _ = realize(compose(*parts), cl, RATES, 1000)
    segs = capacity.speed_segments(scen, 1000)
    rates = np.array([RATES.alpha, RATES.beta, RATES.gamma])
    assert capacity._rack_homogeneous(segs, cl, rates)
    pooled = fluid_edge(scen, cl, RATES, 1000)
    monkeypatch.setattr(capacity, "_rack_homogeneous", lambda *a: False)
    assert fluid_edge(scen, cl, RATES, 1000) == pytest.approx(pooled,
                                                              rel=1e-9)


def test_per_server_speeds_take_the_server_lp():
    scen, _ = realize(compose("straggler_wave", "zipf_hotspot"), CLUSTER,
                      RATES, 1000)
    rates = np.array([RATES.alpha, RATES.beta, RATES.gamma])
    assert not capacity._rack_homogeneous(
        capacity.speed_segments(scen, 1000), CLUSTER, rates)


def test_lp_segments_and_demand_helpers():
    cl = Cluster(M=6, K=2)
    scen = _scen(6, 100, [0.0, np.log(3.0)], [[0, 1, 2], [3, 4, 5]])
    segs = speed_segments(scen, 100)
    assert len(segs) == 1 and segs[0][0] == 100          # no windows: one seg
    pbar, locals_ = chunk_demand(scen, 100)
    assert pbar == pytest.approx([0.25, 0.75])
    assert locals_.shape == (2, 3)


# ---------------------------------------------------------------------------
# dispatch: uniform bit-for-bit, skewed strictly smaller, padded == raw
# ---------------------------------------------------------------------------

CLUSTER = Cluster(M=24, K=4)


@pytest.mark.parametrize("name", ["uniform", "slow_rack", "straggler_wave",
                                  "network_degraded", "flash_crowd"])
def test_uniform_placement_keeps_closed_form_bit_for_bit(name):
    T = 2000
    scen, cap = realize(SCENARIOS[name], CLUSTER, RATES, T)
    assert cap == uniform_edge(scen, RATES, T)           # exact, not approx
    scen_p, cap_p = realize(SCENARIOS[name], CLUSTER, RATES, T,
                            pad=canonical_pad(CLUSTER))
    assert cap_p == cap


@pytest.mark.parametrize("name", ["zipf_hotspot", "adversarial_placement",
                                  "hetero_storm"])
def test_skewed_placement_edge_strictly_below_closed_form(name):
    T = 2000
    scen, cap = realize(SCENARIOS[name], CLUSTER, RATES, T)
    closed = uniform_edge(scen, RATES, T)
    assert 0 < cap < closed
    # padded realization must agree with the raw one (the LP sees through
    # pad rows: they carry exactly zero popularity)
    _, cap_p = realize(SCENARIOS[name], CLUSTER, RATES, T,
                       pad=canonical_pad(CLUSTER))
    assert cap_p == pytest.approx(cap, rel=1e-9)


def test_capacity_edge_is_memoized():
    T = 2000
    scen, _ = realize(SCENARIOS["zipf_hotspot"], CLUSTER, RATES, T)
    a = capacity_edge(scen, CLUSTER, RATES, T)
    b = capacity_edge(scen, CLUSTER, RATES, T)
    assert a == b                                        # cache hit, same value


# ---------------------------------------------------------------------------
# ground truth: refsim stability bracket at small M
# ---------------------------------------------------------------------------


def _half_ratio(cl, load, T, seed, placement):
    """h2/h1 growth statistic from two deterministic refsim runs of the
    SAME seed (refsim is deterministic per seed): warmup=0 gives the full
    mean, warmup=T/2 the second-half mean; h1 = 2*full - h2."""
    full = simulate_bp_ref(cl, RATES, load, T, warmup=0, seed=seed,
                           placement=placement)
    tail = simulate_bp_ref(cl, RATES, load, T, warmup=T // 2, seed=seed,
                           placement=placement)
    h2 = tail.mean_tasks_in_system
    h1 = 2.0 * full.mean_tasks_in_system - h2
    return h2 / max(h1, 1e-9)


def test_refsim_stability_bracket_agrees_with_lp_within_2pct():
    # brute-force oracle: probe the single-hot-triple system 2% below and
    # 2% above the LP edge.  Below: tasks-in-system levels off (half-ratio
    # ~1).  Above: it grows linearly (half-ratio >> 1).  Both classifying
    # correctly brackets the true edge within 2% of the LP optimum.
    cl = Cluster(M=6, K=2)
    T = 50_000
    scen = _scen(6, T, [0.0], [[0, 1, 2]])
    edge = fluid_edge(scen, cl, RATES, T)
    placement = (np.array([1.0]), np.array([[0, 1, 2]]))
    fleet = RATES.alpha * cl.M                 # refsim load is vs fleet edge
    seeds = (0, 1)
    lo = np.mean([_half_ratio(cl, 0.98 * edge / fleet, T, s, placement)
                  for s in seeds])
    hi = np.mean([_half_ratio(cl, 1.02 * edge / fleet, T, s, placement)
                  for s in seeds])
    assert lo < 1.6, f"0.98x edge looks unstable (ratio {lo:.2f})"
    assert hi > 1.6, f"1.02x edge looks stable (ratio {hi:.2f})"


# ---------------------------------------------------------------------------
# auto-extend warmup (telemetry.export.auto_extend_warmup)
# ---------------------------------------------------------------------------

SMALL = Cluster(M=12, K=3)
TCFG = TelemetryConfig()


def test_auto_extend_fast_mixing_run_never_extends():
    _, _, rep = simulate_auto_warmup(
        "balanced_pandas", SMALL, RATES, 0.6, jax.random.PRNGKey(1),
        cfg=SimConfig(T=6000, warmup=1500), telemetry=TCFG)
    assert rep.extensions == 0
    assert rep.converged
    assert rep.warmup == rep.warmup0 == 1500
    assert rep.drift == rep.drift0 < 1.05


def test_auto_extend_slow_mixing_run_extends_and_converges():
    # high load, no configured warmup: the transient ramp-up contaminates
    # the head windows (drift >= threshold), so the loop must move the
    # boundary and land below 1.05
    _, _, rep = simulate_auto_warmup(
        "balanced_pandas", SMALL, RATES, 0.93, jax.random.PRNGKey(1),
        cfg=SimConfig(T=6000, warmup=0), telemetry=TCFG)
    assert rep.drift0 >= 1.05
    assert rep.extensions >= 1
    assert rep.converged
    assert rep.drift < 1.05
    assert rep.warmup > 0
    # tail stats are re-derived and finite
    assert np.isfinite(rep.mean_N) and np.isfinite(rep.mean_completion)


def test_auto_extend_gives_up_loudly_at_cap():
    _, _, rep = simulate_auto_warmup(
        "balanced_pandas", SMALL, RATES, 0.9, jax.random.PRNGKey(0),
        cfg=SimConfig(T=8000, warmup=0), telemetry=TCFG)
    assert not rep.converged
    assert rep.note and "NOT converged" in rep.note
    assert rep.warmup <= int(0.75 * 8000)
    f = rep.fields()
    assert f["warmup_converged"] is False and "warmup_note" in f


def test_nan_drift_is_never_converged():
    # warmup >= T leaves zero measured windows: windowed_drift is NaN and
    # the auto-extend report must say NOT converged, loudly — satellite 2:
    # NaN is "unmeasured", never "converged"
    from repro.core import simulate_with_telemetry
    _, tele = simulate_with_telemetry(
        "balanced_pandas", SMALL, RATES, 0.5, jax.random.PRNGKey(0),
        cfg=SimConfig(T=500, warmup=200), telemetry=TCFG)
    d = windowed_drift(tele, TCFG, 500, 500)
    assert d != d                                        # NaN
    rep = auto_extend_warmup(tele, TCFG, 500, 500)
    assert not rep.converged
    assert "UNMEASURABLE" in rep.note


def test_simresult_drift_nan_when_unmeasurable():
    # satellite 1: warmup >= T means the half-ratio has no first half; the
    # old 1e-9 guard produced a huge finite number (or 0.0), silently
    # misread by drift-threshold consumers
    r = simulate("balanced_pandas", SMALL, RATES, 0.5, jax.random.PRNGKey(0),
                 cfg=SimConfig(T=100, warmup=100))
    assert np.isnan(float(r.drift))


def test_warmup_policy_knobs_respected():
    _, tele = None, None
    from repro.core import simulate_with_telemetry
    _, tele = simulate_with_telemetry(
        "balanced_pandas", SMALL, RATES, 0.93, jax.random.PRNGKey(1),
        cfg=SimConfig(T=6000, warmup=0), telemetry=TCFG)
    # an impossible threshold forces the loop to the cap
    rep = auto_extend_warmup(tele, TCFG, 6000, 0,
                             policy=WarmupPolicy(threshold=0.0,
                                                 max_warmup_frac=0.5))
    assert not rep.converged
    assert rep.warmup <= 3000


# ---------------------------------------------------------------------------
# 3+-way compose(): pad overflow is explicit and fixable (satellite 3)
# ---------------------------------------------------------------------------


def test_three_way_compose_overflow_names_the_fix():
    tri = compose("straggler_wave", "tor_cascade", "cascade_flash")
    with pytest.raises(ValueError, match="compose_depth"):
        realize(tri, CLUSTER, RATES, 2000, pad=canonical_pad(CLUSTER))


def test_three_way_compose_with_widened_pad_matches_raw():
    tri = compose("straggler_wave", "tor_cascade", "cascade_flash")
    pad3 = canonical_pad(CLUSTER, compose_depth=3)
    scen, cap = realize(tri, CLUSTER, RATES, 2000, pad=pad3)
    _, cap_raw = realize(tri, CLUSTER, RATES, 2000)
    assert cap == pytest.approx(cap_raw, rel=1e-12)
    assert scen.win_start.shape[0] == pad3.n_windows


def test_registry_limits_rejects_bad_depth():
    from repro.scenarios.spec import registry_limits
    with pytest.raises(ValueError, match="compose_depth"):
        registry_limits(compose_depth=0)
