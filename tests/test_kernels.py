"""Pallas kernels vs pure-jnp oracles: shape & dtype sweeps, interpret mode.

The heterogeneous-rate battery at the bottom checks the kernels against an
independent *numpy* oracle (not ref.py) over randomized [M, 3] inverse-rate
matrices — log-uniform rates spanning 1e-3..1e3, deliberate exact ties,
f32/bf16 workloads, and zero-rate (+inf inverse-rate) servers/columns —
via the hypothesis replay harness (tests/_hypothesis_stub.py when the real
package is absent).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import (pod_route, queue_update, ref, route_commit,
                           weighted_argmin)

SHAPES = [(64, 3, 5), (128, 8, 8), (500, 37, 11), (1000, 130, 19), (129, 9, 16)]
INV = jnp.array([25.0, 50.0, 125.0], jnp.float32)


@pytest.mark.parametrize("M,B,C", SHAPES)
@pytest.mark.parametrize("w_dtype", [jnp.float32, jnp.bfloat16])
def test_weighted_argmin_matches_oracle(M, B, C, w_dtype):
    key = jax.random.PRNGKey(M * 1000 + B)
    ks = jax.random.split(key, 2)
    W = (jax.random.uniform(ks[0], (M,)) * 100).astype(w_dtype)
    cls = jax.random.randint(ks[1], (B, M), 0, 3)
    sel, val = weighted_argmin(W, cls, INV)
    rsel, rval = ref.weighted_argmin_ref(W, cls, INV)
    assert (sel == rsel).all()
    np.testing.assert_allclose(np.asarray(val), np.asarray(rval), rtol=1e-5)


@pytest.mark.parametrize("M,B,C", SHAPES)
def test_pod_route_matches_oracle(M, B, C):
    key = jax.random.PRNGKey(M + B)
    ks = jax.random.split(key, 4)
    W = jax.random.uniform(ks[0], (M,)) * 100
    ci = jax.random.randint(ks[1], (B, C), 0, M)
    cc = jax.random.randint(ks[2], (B, C), 0, 3)
    cv = jax.random.bernoulli(ks[3], 0.85, (B, C))
    cv = cv.at[:, 0].set(True)          # at least one valid candidate
    sel, val = pod_route(W, ci, cc, cv, INV)
    rsel, rval = ref.pod_route_ref(W, ci, cc, cv, INV)
    assert (sel == rsel).all()
    np.testing.assert_allclose(np.asarray(val), np.asarray(rval), rtol=1e-5)


@pytest.mark.parametrize("M,B,C", SHAPES)
def test_queue_update_matches_oracle(M, B, C):
    key = jax.random.PRNGKey(M * 7 + B)
    ks = jax.random.split(key, 4)
    Q = jax.random.randint(ks[0], (M, 3), 0, 50)
    sel = jax.random.randint(ks[1], (B,), 0, M)
    scl = jax.random.randint(ks[2], (B,), 0, 3)
    valid = jax.random.bernoulli(ks[3], 0.8, (B,))
    q2, w2 = queue_update(Q, sel, scl, valid, INV)
    rq2, rw2 = ref.queue_update_ref(Q, sel, scl, valid, INV)
    assert (q2 == rq2).all()
    np.testing.assert_allclose(np.asarray(w2), np.asarray(rw2), rtol=1e-5)


# ---------------------------------------------------------------------------
# Heterogeneous [M, 3] inverse-rate battery vs an independent numpy oracle.
# ---------------------------------------------------------------------------

# Small fixed shape pool so the property replays share compiled kernels
# (fresh shapes would recompile the interpret-mode kernels per example).
HETERO_SHAPES = [(64, 3, 5), (128, 8, 8), (129, 9, 16), (96, 17, 11)]


def _np_weighted_argmin(W32, cls, inv_m):
    """Numpy oracle: argmin_m W[m] * inv_m[m, cls[b, m]]; non-finite
    inverse rates score +inf (masked after the multiply); first-index ties."""
    factor = inv_m[np.arange(cls.shape[1])[None, :], cls]          # [B, M]
    with np.errstate(invalid="ignore"):
        scores = np.where(np.isfinite(factor), W32[None, :] * factor, np.inf)
    return np.argmin(scores, axis=1), np.min(scores, axis=1)


def _np_pod_route(W32, ci, cc, cv, inv_m):
    """Numpy oracle for candidate-list routing; first-slot ties."""
    factor = inv_m[ci, cc]                                         # [B, C]
    with np.errstate(invalid="ignore"):
        scores = np.where(cv & np.isfinite(factor), W32[ci] * factor, np.inf)
    c = np.argmin(scores, axis=1)
    return np.take_along_axis(ci, c[:, None], axis=1)[:, 0], np.min(scores, axis=1)


def _hetero_case(seed: int):
    """Randomized heterogeneous routing instance.

    Rates span 1e-3..1e3 log-uniform; some examples draw W and the rate rows
    from tiny discrete pools so exact score ties are dense (including at the
    min); some examples kill whole servers or a single rate column
    (inverse rate +inf); workloads are f32 or bf16.
    """
    rng = np.random.default_rng(seed)
    M, B, C = HETERO_SHAPES[rng.integers(len(HETERO_SHAPES))]
    inv_m = np.exp(rng.uniform(np.log(1e-3), np.log(1e3),
                               (M, 3))).astype(np.float32)
    if rng.random() < 0.5:           # dense exact ties: few distinct rows
        pool = inv_m[:4]
        inv_m = pool[rng.integers(4, size=M)]
    if rng.random() < 0.6:           # dead servers (outage / drain)
        inv_m[rng.choice(M, size=max(1, M // 8), replace=False)] = np.inf
    if rng.random() < 0.4:           # a zero-rate column slice
        inv_m[rng.random(M) < 0.3, rng.integers(3)] = np.inf
    if rng.random() < 0.5:           # few distinct workloads: ties at the min
        W = rng.choice(np.array([0.0, 1.0, 2.5, 77.0], np.float32), size=M)
    else:
        W = rng.uniform(0, 100, M).astype(np.float32)
    dtype = jnp.bfloat16 if rng.random() < 0.4 else jnp.float32
    W_j = jnp.asarray(W).astype(dtype)
    W32 = np.asarray(W_j.astype(jnp.float32))    # what the kernel computes on
    return rng, M, B, C, inv_m, W_j, W32


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_weighted_argmin_hetero_property(seed):
    rng, M, B, C, inv_m, W_j, W32 = _hetero_case(seed)
    cls = rng.integers(0, 3, (B, M)).astype(np.int32)
    sel, val = weighted_argmin(W_j, jnp.asarray(cls), jnp.asarray(inv_m))
    nsel, nval = _np_weighted_argmin(W32, cls, inv_m)
    np.testing.assert_array_equal(np.asarray(sel), nsel)
    np.testing.assert_allclose(np.asarray(val), nval, rtol=1e-5)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_pod_route_hetero_property(seed):
    rng, M, B, C, inv_m, W_j, W32 = _hetero_case(seed)
    ci = rng.integers(0, M, (B, C)).astype(np.int32)
    if rng.random() < 0.5:           # duplicate candidates: exact slot ties
        ci[:, 1::2] = ci[:, 0::2][:, :ci[:, 1::2].shape[1]]
    cc = rng.integers(0, 3, (B, C)).astype(np.int32)
    cv = rng.random((B, C)) < 0.85
    cv[:, 0] = True
    sel, val = pod_route(W_j, jnp.asarray(ci), jnp.asarray(cc),
                         jnp.asarray(cv), jnp.asarray(inv_m))
    nsel, nval = _np_pod_route(W32, ci, cc, cv, inv_m)
    np.testing.assert_array_equal(np.asarray(sel), nsel)
    np.testing.assert_allclose(np.asarray(val), nval, rtol=1e-5)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_queue_update_hetero_property(seed):
    rng, M, B, C, inv_m, W_j, W32 = _hetero_case(seed)
    Q = rng.integers(0, 50, (M, 3)).astype(np.int32)
    sel = rng.integers(0, M, B).astype(np.int32)
    scl = rng.integers(0, 3, B).astype(np.int32)
    valid = rng.random(B) < 0.8
    q2, w2 = queue_update(jnp.asarray(Q), jnp.asarray(sel), jnp.asarray(scl),
                          jnp.asarray(valid), jnp.asarray(inv_m))
    nq = Q.copy()
    np.add.at(nq, (sel[valid], scl[valid]), 1)
    inv_f = np.where(np.isfinite(inv_m), inv_m, 0.0)
    nw = (nq * inv_f).sum(axis=1, dtype=np.float32)
    np.testing.assert_array_equal(np.asarray(q2), nq)
    np.testing.assert_allclose(np.asarray(w2), nw, rtol=1e-5)


@pytest.mark.parametrize("M,B,C", SHAPES)
def test_weighted_argmin_hetero_matches_jnp_ref(M, B, C):
    """ref.py (the jnp oracle) and the kernel agree on [M, 3] operands too."""
    rng = np.random.default_rng(M * 31 + B)
    inv_m = rng.uniform(1e-2, 1e2, (M, 3)).astype(np.float32)
    inv_m[:: max(M // 7, 1)] = np.inf
    W = rng.uniform(0, 100, M).astype(np.float32)
    cls = rng.integers(0, 3, (B, M)).astype(np.int32)
    sel, val = weighted_argmin(jnp.asarray(W), jnp.asarray(cls),
                               jnp.asarray(inv_m))
    rsel, rval = ref.weighted_argmin_ref(jnp.asarray(W), jnp.asarray(cls),
                                         jnp.asarray(inv_m))
    np.testing.assert_array_equal(np.asarray(sel), np.asarray(rsel))
    np.testing.assert_allclose(np.asarray(val), np.asarray(rval), rtol=1e-5)


def test_hetero_lowest_index_ties_survive_onehot_formulation():
    """All-equal scores: the one-hot gather/argmin must keep the lowest
    server index (weighted_argmin) / lowest candidate slot (pod_route)."""
    M, B, C = 96, 11, 9
    W = jnp.full((M,), 3.0, jnp.float32)
    inv_m = jnp.broadcast_to(jnp.float32(2.0), (M, 3))
    cls = jnp.zeros((B, M), jnp.int32)
    sel, val = weighted_argmin(W, cls, inv_m)
    assert (np.asarray(sel) == 0).all()
    np.testing.assert_allclose(np.asarray(val), 6.0)

    rng = np.random.default_rng(0)
    ci = jnp.asarray(rng.integers(0, M, (B, C)).astype(np.int32))
    cc = jnp.ones((B, C), jnp.int32)
    cv = jnp.ones((B, C), bool)
    sel, _ = pod_route(W, ci, cc, cv, inv_m)
    np.testing.assert_array_equal(np.asarray(sel), np.asarray(ci)[:, 0])


def test_hetero_zero_rate_never_selected_over_live_candidate():
    """A drained (zero-rate, +inf inverse-rate) server with an EMPTY queue
    must score +inf — not 0 * inf = NaN — so a live candidate always wins."""
    M, B = 64, 8
    rng = np.random.default_rng(1)
    inv_m = np.full((M, 3), 10.0, np.float32)
    dead = rng.choice(M, size=M // 2, replace=False)
    inv_m[dead] = np.inf
    W = np.zeros(M, np.float32)          # every queue empty: the NaN hazard
    cls = rng.integers(0, 3, (B, M)).astype(np.int32)
    sel, val = weighted_argmin(jnp.asarray(W), jnp.asarray(cls),
                               jnp.asarray(inv_m))
    assert not np.isin(np.asarray(sel), dead).any()
    assert np.isfinite(np.asarray(val)).all()


# ---------------------------------------------------------------------------
# Fused route_commit megakernel: in-kernel sequential-commit semantics vs an
# independent numpy oracle (python loop), the class tie-break lane at large
# workload offsets, and the anti-herding burst contract.
# ---------------------------------------------------------------------------


def _np_route_commit(Q, valid, inv_m, cls=None, ci=None, cc=None, cv=None,
                     prio=None):
    """Independent numpy sequential-commit oracle: a python loop over
    arrivals.  Arrival b scores against W0 + dW (dW = f32-accumulated
    commits of arrivals 0..b-1, +finite inv_rate each); exact ties break by
    locality class, then the full variant's optional ``prio`` lane, then
    server index (full) / candidate slot (pod, with invalid slots losing
    every tie); dead (+inf) entries mask to +inf after the multiply and
    commit 0 workload."""
    M = Q.shape[0]
    inv_f = np.where(np.isfinite(inv_m), inv_m, 0.0).astype(np.float32)
    dead = ~np.isfinite(inv_m)
    W0 = (Q.astype(np.float32) * inv_f).sum(-1).astype(np.float32)
    dw = np.zeros(M, np.float32)
    Qn = Q.copy()
    B = valid.shape[0]
    sel = np.zeros(B, np.int32)
    scls = np.zeros(B, np.int32)
    val = np.zeros(B, np.float32)
    m = np.arange(M)
    p = m if prio is None else np.asarray(prio)
    for b in range(B):
        if cls is not None:
            factor = inv_f[m, cls[b]]
            ok = ~dead[m, cls[b]]
            scores = np.full(M, np.inf, np.float32)
            scores[ok] = ((W0 + dw) * factor)[ok]
            rank = np.where(scores == scores.min(),
                            (cls[b] * M + p) * M + m, 2**30)
            rb = rank.min()
            s, c = rb % M, rb // (M * M)
            amt = inv_f[s, cls[b, s]]
        else:
            C = ci.shape[1]
            slot = np.arange(C)
            factor = inv_f[ci[b], cc[b]]
            ok = cv[b] & ~dead[ci[b], cc[b]]
            scores = np.full(C, np.inf, np.float32)
            scores[ok] = ((W0 + dw)[ci[b]] * factor)[ok]
            rank = np.where(scores == scores.min(),
                            cc[b] * C + slot + (~cv[b]) * 4 * C, 2**30)
            sl = rank.min() % C
            s, c = ci[b, sl], cc[b, sl]
            amt = factor[sl]
        sel[b], scls[b], val[b] = s, c, scores.min()
        if valid[b]:
            dw[s] = np.float32(dw[s] + amt)
            Qn[s, c] += 1
    return Qn, W0 + dw, sel, scls, val


def _assert_route_commit_equal(out_k, out_np):
    qk, wk, sk, ck, vk = (np.asarray(x) for x in out_k)
    qn, wn, sn, cn, vn = out_np
    np.testing.assert_array_equal(sk, sn)
    np.testing.assert_array_equal(ck, cn)
    np.testing.assert_array_equal(qk, qn)
    np.testing.assert_allclose(wk, wn, rtol=1e-6)
    np.testing.assert_allclose(vk, vn, rtol=1e-6)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_route_commit_full_hetero_property(seed):
    rng, M, B, C, inv_m, _W_j, _W32 = _hetero_case(seed)
    Q = rng.integers(0, 30, (M, 3)).astype(np.int32)
    cls = rng.integers(0, 3, (B, M)).astype(np.int32)
    valid = rng.random(B) < 0.85
    prio = (rng.permutation(M).astype(np.int32)
            if rng.random() < 0.5 else None)
    out = route_commit(jnp.asarray(Q), jnp.asarray(valid), jnp.asarray(inv_m),
                       cls=jnp.asarray(cls),
                       prio=None if prio is None else jnp.asarray(prio))
    _assert_route_commit_equal(
        out, _np_route_commit(Q, valid, inv_m, cls=cls, prio=prio))


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_route_commit_pod_hetero_property(seed):
    rng, M, B, C, inv_m, _W_j, _W32 = _hetero_case(seed)
    Q = rng.integers(0, 30, (M, 3)).astype(np.int32)
    ci = rng.integers(0, M, (B, C)).astype(np.int32)
    if rng.random() < 0.5:           # duplicate candidates: exact slot ties
        ci[:, 1::2] = ci[:, 0::2][:, :ci[:, 1::2].shape[1]]
    cc = rng.integers(0, 3, (B, C)).astype(np.int32)
    cv = rng.random((B, C)) < 0.85
    cv[:, 0] = True
    valid = rng.random(B) < 0.85
    out = route_commit(jnp.asarray(Q), jnp.asarray(valid), jnp.asarray(inv_m),
                       cand_idx=jnp.asarray(ci), cand_cls=jnp.asarray(cc),
                       cand_valid=jnp.asarray(cv))
    _assert_route_commit_equal(
        out, _np_route_commit(Q, valid, inv_m, ci=ci, cc=cc, cv=cv))


@pytest.mark.parametrize("M,B,C", SHAPES)
def test_route_commit_matches_jnp_ref(M, B, C):
    """Both variants agree with ref.route_commit_ref (the jnp oracle the
    simulator's telemetry replay shares) across the full shape pool."""
    rng = np.random.default_rng(M * 13 + B)
    inv_m = rng.uniform(1e-2, 1e2, (M, 3)).astype(np.float32)
    inv_m[:: max(M // 7, 1)] = np.inf
    Q = jnp.asarray(rng.integers(0, 40, (M, 3)), jnp.int32)
    valid = jnp.asarray(rng.random(B) < 0.9)
    inv = jnp.asarray(inv_m)

    cls = jnp.asarray(rng.integers(0, 3, (B, M)), jnp.int32)
    prio = jnp.asarray(rng.permutation(M), jnp.int32)
    out_k = route_commit(Q, valid, inv, cls=cls, prio=prio)
    out_r = ref.route_commit_ref(Q, valid, inv, cls=cls, prio=prio)
    for a, b in zip(out_k, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    ci = jnp.asarray(rng.integers(0, M, (B, C)), jnp.int32)
    cc = jnp.asarray(rng.integers(0, 3, (B, C)), jnp.int32)
    cv = jnp.asarray(rng.random((B, C)) < 0.85, jnp.int32)
    out_k = route_commit(Q, valid, inv, cand_idx=ci, cand_cls=cc,
                         cand_valid=cv)
    out_r = ref.route_commit_ref(Q, valid, inv, cand_idx=ci, cand_cls=cc,
                                 cand_valid=cv)
    for a, b in zip(out_k, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


@pytest.mark.parametrize("offset", [0, 333])
def test_route_commit_class_tiebreak_survives_large_workload(offset):
    """Regression for the deleted _BP_TIE_EPS lift: with every sub-queue at
    ``offset`` and unit rates, every score ties EXACTLY at W = 3*offset
    (999 at offset=333 — where the old host-side ``W + 1e-6`` lift was
    silently absorbed by f32 addition, ulp(999) ~ 6e-5, so ties fell back
    to lowest server index).  The in-kernel integer rank lane must still
    route every arrival to its LOCAL server, never server 0."""
    M, B = 64, 8
    Q = np.full((M, 3), offset, np.int32)
    inv = jnp.ones(3, jnp.float32)
    rng = np.random.default_rng(5)
    local_at = rng.choice(np.arange(1, M), size=B, replace=False)  # never 0
    cls = np.full((B, M), 2, np.int32)
    cls[np.arange(B), local_at] = 0
    _, _, sel, scls, _ = route_commit(jnp.asarray(Q), jnp.ones(B, bool), inv,
                                      cls=jnp.asarray(cls))
    np.testing.assert_array_equal(np.asarray(sel), local_at)
    assert (np.asarray(scls) == 0).all()

    # pod variant: local candidate deliberately NOT in slot 0
    C = 5
    ci = np.stack([rng.choice(M, size=C, replace=False) for _ in range(B)])
    cc = np.tile(np.array([2, 1, 0, 1, 2], np.int32), (B, 1))
    out = route_commit(jnp.asarray(Q), jnp.ones(B, bool), inv,
                       cand_idx=jnp.asarray(ci), cand_cls=jnp.asarray(cc),
                       cand_valid=jnp.ones((B, C), jnp.int32))
    np.testing.assert_array_equal(np.asarray(out[2]), ci[:, 2])
    assert (np.asarray(out[3]) == 0).all()


def test_route_commit_burst_spreads_one_task_per_server():
    """The anti-herding contract: a burst of B arrivals into an all-empty
    equal-rate fleet lands one task per server (each arrival sees the
    previous commits), where snapshot routing would have piled all B onto
    the single argmin server."""
    M, B = 64, 48
    Q0 = jnp.zeros((M, 3), jnp.int32)
    q, w, sel, _, _ = route_commit(Q0, jnp.ones(B, bool), jnp.ones(3),
                                   cls=jnp.zeros((B, M), jnp.int32))
    assert int(np.asarray(q).max()) == 1
    assert len(np.unique(np.asarray(sel))) == B

    # pod variant with every server a candidate: same spread
    ci = jnp.broadcast_to(jnp.arange(M, dtype=jnp.int32)[None, :], (B, M))
    q, _, sel, _, _ = route_commit(Q0, jnp.ones(B, bool), jnp.ones(3),
                                   cand_idx=ci,
                                   cand_cls=jnp.zeros((B, M), jnp.int32),
                                   cand_valid=jnp.ones((B, M), jnp.int32))
    assert int(np.asarray(q).max()) == 1
    assert len(np.unique(np.asarray(sel))) == B


def test_route_commit_wseq_replays_decision_workloads():
    """ref.route_commit_wseq row b == the pre-commit workload arrival b
    scored against (the telemetry probe replay contract): row 0 is W0, and
    re-scoring each arrival against its replayed row reproduces the
    kernel's chosen score."""
    rng = np.random.default_rng(9)
    M, B = 96, 17
    Q = jnp.asarray(rng.integers(0, 20, (M, 3)), jnp.int32)
    inv_m = rng.uniform(0.1, 10.0, (M, 3)).astype(np.float32)
    inv_m[5] = np.inf
    inv = jnp.asarray(inv_m)
    cls = jnp.asarray(rng.integers(0, 3, (B, M)), jnp.int32)
    valid = jnp.asarray(rng.random(B) < 0.8)
    _, W_new, sel, scls, val = route_commit(Q, valid, inv, cls=cls)
    wseq = np.asarray(ref.route_commit_wseq(Q, sel, scls, valid, inv))
    inv_f = np.where(np.isfinite(inv_m), inv_m, 0.0)
    np.testing.assert_allclose(
        wseq[0], (np.asarray(Q) * inv_f).sum(-1), rtol=1e-6)
    clsn, seln = np.asarray(cls), np.asarray(sel)
    replayed = wseq[np.arange(B), seln] * inv_f[
        seln, clsn[np.arange(B), seln]]
    np.testing.assert_allclose(replayed, np.asarray(val), rtol=1e-6)


def test_kernels_compose_as_router_pipeline():
    """classes -> pod_route -> queue_update: one routing tick end-to-end."""
    from repro.core import Cluster, PodSpec, pod_candidates, sample_locals
    c = Cluster(M=128, K=8)
    key = jax.random.PRNGKey(0)
    locals_ = sample_locals(key, c, 32)
    ci, cc, cv = pod_candidates(key, c, locals_, PodSpec(2, 6))
    Q = jnp.zeros((c.M, 3), jnp.int32)
    W = jnp.zeros((c.M,), jnp.float32)
    for _ in range(3):
        sel, _ = pod_route(W, ci, cc, cv, INV)
        take = (ci == sel[:, None]).argmax(axis=1)
        sel_cls = jnp.take_along_axis(cc, take[:, None], axis=1)[:, 0]
        Q, W = queue_update(Q, sel, sel_cls, jnp.ones((32,), bool), INV)
    assert int(Q.sum()) == 96
    np.testing.assert_allclose(
        np.asarray(W),
        np.asarray((Q.astype(jnp.float32) * INV[None, :]).sum(-1)), rtol=1e-6)
