"""Property-based tests (hypothesis) for the routing primitives, and the
interval pod draws against their O(M) reference, alone and in the sweep."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    RACK,
    REMOTE,
    Cluster,
    PodSpec,
    Rates,
    SimConfig,
    class_tensor_count,
    lex_argmin,
    locality_class,
    masked_draws,
    pod_candidates,
    route_pod_candidates,
    sample_locals,
    sample_rack_peer,
    sample_remote_peer,
    simulate_sweep,
)
from repro.core import simulator as sim
from repro.scenarios import get_scenario, realize, sample_locals_scenario
from repro.telemetry import TelemetryConfig

SMALL = settings(max_examples=25, deadline=None)


@given(st.integers(0, 2**31 - 1), st.integers(2, 6), st.integers(2, 8))
@SMALL
def test_lex_argmin_matches_numpy_lexsort(seed, b, m):
    rng = np.random.default_rng(seed)
    vals = rng.integers(0, 4, (b, m)).astype(np.float32)   # force ties
    tb1 = rng.integers(0, 3, (b, m)).astype(np.float32)
    mask = rng.random((b, m)) < 0.8
    mask[:, 0] = True                                       # non-empty rows
    got = np.asarray(lex_argmin(jnp.asarray(vals), jnp.asarray(tb1),
                                mask=jnp.asarray(mask)))
    for i in range(b):
        keys = [(vals[i, j], tb1[i, j], j) for j in range(m) if mask[i, j]]
        want = min(keys)[2]
        assert got[i] == want


@given(st.integers(0, 2**31 - 1), st.integers(1, 6))
@SMALL
def test_masked_draws_land_in_set(seed, k):
    rng = np.random.default_rng(seed)
    mask = rng.random((4, 20)) < 0.4
    idx, valid = masked_draws(jax.random.PRNGKey(seed),
                              jnp.asarray(mask), k)
    idx, valid = np.asarray(idx), np.asarray(valid)
    for b in range(4):
        if mask[b].any():
            assert valid[b].all()
            assert mask[b][idx[b]].all()
        else:
            assert not valid[b].any()


@given(st.integers(0, 2**31 - 1))
@SMALL
def test_pod_candidates_classes_and_membership(seed):
    c = Cluster(M=24, K=4)
    key = jax.random.PRNGKey(seed)
    locals_ = sample_locals(key, c, 8)
    cls = locality_class(c, locals_)
    ci, cc, cv = pod_candidates(key, c, locals_, PodSpec(2, 4))
    ci, cc, cv = map(np.asarray, (ci, cc, cv))
    cls_np = np.asarray(cls)
    for b in range(8):
        for j in range(ci.shape[1]):
            if cv[b, j]:
                assert cls_np[b, ci[b, j]] == cc[b, j]


@given(st.integers(0, 2**31 - 1))
@SMALL
def test_route_pod_picks_min_weighted_workload(seed):
    c = Cluster(M=24, K=4)
    key = jax.random.PRNGKey(seed)
    W = jax.random.uniform(key, (c.M,)) * 10
    locals_ = sample_locals(key, c, 8)
    inv = jnp.array([10.0, 20.0, 50.0])
    ci, cc, cv = pod_candidates(key, c, locals_, PodSpec(2, 4))
    sel, sel_cls = route_pod_candidates(key, W, ci, cc, cv, inv)
    scores = np.where(np.asarray(cv),
                      np.asarray(W)[np.asarray(ci)] * np.asarray(inv)[np.asarray(cc)],
                      np.inf)
    sel_score = np.asarray(W)[np.asarray(sel)] * np.asarray(inv)[np.asarray(sel_cls)]
    assert np.allclose(sel_score, scores.min(axis=1), rtol=1e-6)


@given(st.integers(0, 2**31 - 1), st.integers(1, 8))
@SMALL
def test_rack_and_remote_peer_samplers(seed, k):
    c = Cluster(M=24, K=4)
    servers = jnp.arange(c.M, dtype=jnp.int32)
    rack = np.asarray(sample_rack_peer(jax.random.PRNGKey(seed), c, servers, k))
    rem = np.asarray(sample_remote_peer(jax.random.PRNGKey(seed), c, servers, k))
    rack_of = np.arange(c.M) // c.rack_size
    for m in range(c.M):
        assert (rack_of[rack[m]] == rack_of[m]).all()
        assert (rack[m] != m).all()
        assert (rack_of[rem[m]] != rack_of[m]).all()
        assert ((rem[m] >= 0) & (rem[m] < c.M)).all()


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_queue_conservation_one_slot(seed):
    """Tasks are conserved slot-to-slot: dN = arrivals - completions."""
    import jax as j
    from repro.core import Rates, SimConfig, simulate
    c = Cluster(M=20, K=4)
    cfg = SimConfig(T=300, warmup=0)
    r = simulate("balanced_pandas_pod", c, Rates(0.1, 0.05, 0.02), 0.6,
                 j.random.PRNGKey(seed), cfg)
    # final N equals cumulative arrivals - completions (exact integers)
    # mean over run can't be checked this way; use totals:
    total_in = float(r.arrival_rate_hat) * float(cfg.T)
    total_out = float(r.throughput) * float(cfg.T)
    # final_N isn't exposed in SimResult; conservation holds if in-out>=0
    assert total_in - total_out > -1e-3


# ---------------------------------------------------------------------------
# interval pod draws == the masked_draws reference, draw for draw
# ---------------------------------------------------------------------------


def _masked_pod_candidates(key, cluster, locals_, pod):
    """The reference pod draws: ``masked_draws`` over the [..., M] class
    tensor's RACK and REMOTE masks, laid out as ``pod_candidates``."""
    cls = locality_class(cluster, locals_)
    k_rack, k_rem = jax.random.split(key)
    rack_idx, rack_ok = masked_draws(k_rack, cls == RACK, pod.d_rack)
    rem_idx, rem_ok = masked_draws(k_rem, cls == REMOTE, pod.d_remote)
    shp = locals_.shape[:-1]
    n_rep = locals_.shape[-1]
    cand_cls = jnp.concatenate([
        jnp.zeros(shp + (n_rep,), jnp.int32),
        jnp.full(shp + (pod.d_rack,), RACK, jnp.int32),
        jnp.full(shp + (pod.d_remote,), REMOTE, jnp.int32)], axis=-1)
    return (jnp.concatenate([locals_, rack_idx, rem_idx], axis=-1), cand_cls,
            jnp.concatenate([jnp.ones(shp + (n_rep,), bool), rack_ok,
                             rem_ok], axis=-1))


def _shared_rack_triples(c: Cluster, n: int, rng) -> np.ndarray:
    """Two replicas in one rack and the third in another (the same rack
    when K=1), columns shuffled so the triple arrives unsorted."""
    R = c.rack_size
    out = np.empty((n, 3), np.int32)
    for b in range(n):
        a = rng.integers(c.K)
        two = a * R + rng.choice(R, 2, replace=False)
        if c.K > 1:
            other = (a + 1 + rng.integers(c.K - 1)) % c.K
            third = other * R + rng.integers(R)
        else:
            third = rng.choice(np.setdiff1d(np.arange(R), two - a * R)) + a * R
        out[b] = rng.permutation([two[0], two[1], third])
    return out


def _triples(c: Cluster, placement: str, seed: int, n: int = 400):
    key = jax.random.PRNGKey(seed)
    if placement == "sample_locals":
        return sample_locals(key, c, n)
    if placement == "shared_rack":
        return jnp.asarray(_shared_rack_triples(
            c, n, np.random.default_rng(seed)))
    scen, _ = realize(get_scenario(placement), c, Rates(), 100)
    return sample_locals_scenario(key, c, scen, n)


FLEETS = [(500, 10), (5000, 100), (20, 2), (30, 10), (12, 1)]
PLACEMENTS = ["sample_locals", "shared_rack", "zipf_hotspot",
              "adversarial_placement"]
# a catalog placement solves its capacity LP over C = 4M chunks when it
# is realized: kept to the small fleets
DRAW_CASES = [(M, K, p) for M, K in FLEETS for p in PLACEMENTS
              if M <= 30 or p in ("sample_locals", "shared_rack")]


@pytest.mark.parametrize("M,K,placement", DRAW_CASES)
def test_interval_pod_draws_equal_masked_draws(M, K, placement):
    """Same keys, same candidates: the interval draws return the index and
    validity ``masked_draws`` finds in the class tensor, element for
    element (K=1 has no remote server: every remote draw is invalid)."""
    c = Cluster(M=M, K=K)
    pod = PodSpec(2, 6)
    for seed in range(3):
        locals_ = _triples(c, placement, seed)
        key = jax.random.PRNGKey(1000 + seed)
        got = pod_candidates(key, c, locals_, pod)
        want = _masked_pod_candidates(key, c, locals_, pod)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        if K == 1:
            assert not np.asarray(got[2])[:, -pod.d_remote:].any()
        # one task at a time, as the sequential route scan calls it
        one = pod_candidates(key, c, locals_[seed], pod)
        ref = _masked_pod_candidates(key, c, locals_[seed], pod)
        for g, w in zip(one, ref):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# the sweep on the interval draws == the sweep on the reference draws
# ---------------------------------------------------------------------------

SWEEP_CLUSTER = Cluster(M=16, K=4)
SWEEP_RATES = Rates(0.05, 0.025, 0.01)
SWEEP_SCENARIOS = ["uniform", "adversarial_placement"]


def _sweep_sums(monkeypatch, algo, route_mode, telemetry, oracle: bool):
    """RawSums and telemetry of a small sweep, traced afresh; ``oracle``
    routes BP-Pod on the reference draws instead."""
    captured = {}
    summarize = sim.summarize

    def keep(s, *a):
        captured["sums"] = s
        return summarize(s, *a)

    with monkeypatch.context() as mp:
        mp.setattr(sim, "summarize", keep)
        if oracle:
            mp.setattr(sim, "pod_candidates", _masked_pod_candidates)
        jax.clear_caches()
        cfg = SimConfig(T=104, warmup=24, route_mode=route_mode, s_max=16)
        _, _, tele = simulate_sweep(algo, SWEEP_CLUSTER, SWEEP_RATES,
                                    [0.5, 0.9], 2, cfg=cfg, seed0=5,
                                    scenarios=SWEEP_SCENARIOS,
                                    telemetry=telemetry)
        n_cls = class_tensor_count()
        jax.clear_caches()
    return captured["sums"], tele, n_cls


@pytest.mark.parametrize("route_mode,telemetry", [
    ("batched", None), ("sequential", None), ("batched", TelemetryConfig())])
def test_pod_sweep_on_interval_draws_equals_masked_draws_oracle(
        monkeypatch, route_mode, telemetry):
    """BP-Pod sweeps are bit-identical with the interval draws and with
    the masked_draws reference, probe telemetry included; without probes
    the BP-Pod trace builds no class tensor."""
    sums, tele, n_cls = _sweep_sums(monkeypatch, "balanced_pandas_pod",
                                    route_mode, telemetry, oracle=False)
    want, want_tele, _ = _sweep_sums(monkeypatch, "balanced_pandas_pod",
                                     route_mode, telemetry, oracle=True)
    for g, w in zip(jax.tree_util.tree_leaves((sums, tele)),
                    jax.tree_util.tree_leaves((want, want_tele))):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert n_cls == (0 if telemetry is None else 1)


@pytest.mark.parametrize("route_mode", ["batched", "sequential"])
def test_full_bp_trace_builds_one_class_tensor(monkeypatch, route_mode):
    _, _, n_cls = _sweep_sums(monkeypatch, "balanced_pandas", route_mode,
                              None, oracle=False)
    assert n_cls == 1
