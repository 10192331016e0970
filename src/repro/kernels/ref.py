"""Pure-jnp oracles for the scheduler kernels.

These define the exact semantics the Pallas kernels must reproduce
(tests/test_kernels.py sweeps shapes & dtypes and asserts allclose / exact
index equality).  Tie-breaking contract: lowest index wins for the three
legacy snapshot kernels; the fused ``route_commit`` megakernel instead
breaks exact score ties by locality class first (LOCAL < RACK < REMOTE),
then lowest server index / candidate slot — see ``route_commit_ref``.

Inverse-rate operand (all three oracles): either the homogeneous ``[3]``
vector (every server identical) or a per-server ``[M, 3]`` matrix
(heterogeneous fleets).  A zero-rate (drained / failed) server has
reciprocal rate ``+inf``; the routing oracles mask such entries to a
``+inf`` score AFTER the multiply — so a zero workload on a dead server
scores ``+inf``, never ``0 * inf = NaN`` — and queue_update counts them as
contributing 0 workload (routing never consults a dead server's W).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _rate_factor(inv_rates: jnp.ndarray, idx: jnp.ndarray,
                 cls: jnp.ndarray) -> jnp.ndarray:
    """inv_rates[idx, cls] for the [M, 3] form, inv_rates[cls] for [3].
    idx/cls broadcast together."""
    inv = jnp.asarray(inv_rates, jnp.float32)
    if inv.ndim == 1:
        return inv[cls]
    return inv[idx, cls]


def _guarded_scores(w: jnp.ndarray, factor: jnp.ndarray,
                    valid: jnp.ndarray) -> jnp.ndarray:
    """w * factor with invalid slots and non-finite factors -> +inf (the
    dead-server mask lands after the multiply: no 0 * inf NaNs)."""
    return jnp.where(valid & jnp.isfinite(factor),
                     w.astype(jnp.float32) * factor, jnp.inf)


def weighted_argmin_ref(W: jnp.ndarray, cls: jnp.ndarray,
                        inv_rates: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Balanced-Pandas O(M) routing: full argmin of weighted workload.

    W: [M] workloads; cls: [B, M] int32 locality classes (0/1/2);
    inv_rates: [3] = 1/(alpha,beta,gamma), or [M, 3] per-server.
    Returns (sel [B] int32, val [B] float32): argmin_m W[m]*inv_rates[m,cls]
    (first index on ties; zero-rate entries score +inf) and the winning
    score.
    """
    m = jnp.arange(cls.shape[-1], dtype=jnp.int32)[None, :]
    factor = _rate_factor(inv_rates, m, cls)                 # [B, M]
    scores = _guarded_scores(W[None, :], factor, jnp.ones(cls.shape, bool))
    sel = jnp.argmin(scores, axis=1).astype(jnp.int32)
    val = jnp.min(scores, axis=1)
    return sel, val


def pod_route_ref(W: jnp.ndarray, cand_idx: jnp.ndarray, cand_cls: jnp.ndarray,
                  valid: jnp.ndarray, inv_rates: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Balanced-Pandas-Pod O(d) routing: argmin over an explicit candidate list.

    W: [M]; cand_idx/cand_cls: [B, C] int32; valid: [B, C] bool;
    inv_rates: [3] or [M, 3].  Returns (sel [B] int32 server index,
    val [B] score).  Invalid candidate slots and zero-rate (non-finite
    inverse-rate) candidates never win (score +inf); ties -> lowest slot c,
    and the returned server is cand_idx[b, c*].
    """
    w = W.astype(jnp.float32)[cand_idx]                      # [B, C]
    factor = _rate_factor(inv_rates, cand_idx, cand_cls)
    scores = _guarded_scores(w, factor, valid)
    c = jnp.argmin(scores, axis=1)
    sel = jnp.take_along_axis(cand_idx, c[:, None], axis=1)[:, 0].astype(jnp.int32)
    val = jnp.min(scores, axis=1)
    return sel, val


def queue_update_ref(Q: jnp.ndarray, sel: jnp.ndarray, sel_cls: jnp.ndarray,
                     valid: jnp.ndarray, inv_rates: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused post-routing queue scatter + workload recompute.

    Q: [M, 3] int32 sub-queue lengths; sel/sel_cls: [B] int32; valid: [B] bool.
    inv_rates: [3] or [M, 3].  Returns (Q_new [M,3] int32, W [M] float32)
    where Q_new = Q + scatter_add(one_hot(sel) x one_hot(sel_cls) * valid)
    and W = (Q_new * inv_rates).sum(-1) (paper's W_m = Q^l/a + Q^k/b + Q^r/g,
    with each server's own rates in the [M, 3] form; non-finite entries
    contribute 0).
    """
    upd = jnp.zeros_like(Q).at[sel, sel_cls].add(valid.astype(Q.dtype))
    Q_new = Q + upd
    inv = jnp.asarray(inv_rates, jnp.float32)
    if inv.ndim == 1:
        inv = inv[None, :]
    inv = jnp.where(jnp.isfinite(inv), inv, 0.0)
    W = (Q_new.astype(jnp.float32) * inv).sum(-1)
    return Q_new, W


def _finite_dead(inv_rates: jnp.ndarray, M: int):
    """(finite reciprocal rates [M, 3], dead mask [M, 3]) — the oracle-side
    mirror of the kernels' invrates encoding."""
    inv = jnp.asarray(inv_rates, jnp.float32)
    if inv.ndim == 1:
        inv = jnp.broadcast_to(inv[None, :], (M, 3))
    finite = jnp.isfinite(inv)
    return jnp.where(finite, inv, 0.0), ~finite


_RANK_BIG = jnp.int32(2**30)


def _w0(Q: jnp.ndarray, finite: jnp.ndarray) -> jnp.ndarray:
    """W0 [M] = Q[:, 0] * r0 + Q[:, 1] * r1 + Q[:, 2] * r2, added in that
    order (route_commit sums in the same order, so W matches bit for bit
    on every backend)."""
    p = Q.astype(jnp.float32) * finite
    return (p[:, 0] + p[:, 1]) + p[:, 2]


def route_commit_ref(Q: jnp.ndarray, valid: jnp.ndarray,
                     inv_rates: jnp.ndarray, *,
                     cls: Optional[jnp.ndarray] = None,
                     prio: Optional[jnp.ndarray] = None,
                     cand_idx: Optional[jnp.ndarray] = None,
                     cand_cls: Optional[jnp.ndarray] = None,
                     cand_valid: Optional[jnp.ndarray] = None):
    """Sequential-commit routing oracle for the route_commit megakernel.

    Routes arrivals IN ORDER: arrival b scores against ``W0 + dW`` where
    ``dW`` holds the commits of arrivals ``0..b-1`` (``+inv_rates[sel,
    cls]`` each, 0 for dead servers) — the paper's per-arrival model, not
    a shared snapshot.  Scores are ``(W0 + dW) * inv_rates[m, cls]`` with
    dead / invalid entries masked to ``+inf`` after the multiply.  Exact
    ties break by locality class first, then the optional per-server
    ``prio`` lane (full variant; lower wins — a random permutation gives
    the unbiased ties the sequential path uses), then lowest server index
    (full variant, ``cls [B, M]``) or lowest candidate slot (pod variant,
    ``cand_idx``/``cand_cls``/``cand_valid [B, C]``; invalid slots lose
    every tie and can only win when every slot scores ``+inf``).  Arrivals
    with ``valid[b]`` False still receive a routing decision but commit
    nothing.

    Returns (Q_new [M, 3] int32, W_new [M] f32, sel [B] int32,
    sel_cls [B] int32, val [B] f32).
    """
    M = Q.shape[0]
    finite, dead = _finite_dead(inv_rates, M)
    W0 = _w0(Q, finite)

    if cls is not None:
        m = jnp.arange(M, dtype=jnp.int32)
        p = (m if prio is None else prio.astype(jnp.int32))

        def step(dw, xs):
            cls_b, v_b = xs
            factor = finite[m, cls_b]
            ok = (cls_b < 3) & ~dead[m, cls_b]
            scores = jnp.where(ok, (W0 + dw) * factor, jnp.inf)
            best = jnp.min(scores)
            rank = jnp.where(scores == best,
                             (cls_b * M + p) * M + m, _RANK_BIG)
            rb = jnp.min(rank)
            sel = (rb % M).astype(jnp.int32)
            scls = (rb // (M * M)).astype(jnp.int32)
            amt = finite[sel, jnp.minimum(scls, 2)] * (scls < 3)
            dw = dw + jnp.where((m == sel) & v_b, amt, 0.0)
            return dw, (sel, scls, best)

        xs = (cls.astype(jnp.int32), jnp.asarray(valid, bool))
    else:
        assert cand_idx is not None and cand_cls is not None \
            and cand_valid is not None
        C = cand_idx.shape[1]
        slot = jnp.arange(C, dtype=jnp.int32)
        m = jnp.arange(M, dtype=jnp.int32)

        def step(dw, xs):
            ci, cc, cv, v_b = xs
            factor = finite[ci, cc]
            ok = (cv > 0) & (cc < 3) & ~dead[ci, cc]
            scores = jnp.where(ok, (W0 + dw)[ci] * factor, jnp.inf)
            best = jnp.min(scores)
            rank = jnp.where(scores == best,
                             cc * C + slot + (1 - cv) * 4 * C, _RANK_BIG)
            s = (jnp.min(rank) % C).astype(jnp.int32)
            sel = ci[s]
            scls = cc[s]
            dw = dw + jnp.where((m == sel) & v_b, factor[s], 0.0)
            return dw, (sel, scls, best)

        xs = (cand_idx.astype(jnp.int32), cand_cls.astype(jnp.int32),
              jnp.asarray(cand_valid, jnp.int32),
              jnp.asarray(valid, bool))

    dw, (sel, scls, val) = jax.lax.scan(step, jnp.zeros(M, jnp.float32), xs)
    v = jnp.asarray(valid, bool)
    Q_new = Q + jnp.zeros_like(Q).at[sel, jnp.minimum(scls, 2)].add(
        (v & (scls < 3)).astype(Q.dtype))
    return Q_new, W0 + dw, sel, scls, val


def route_commit_wseq(Q: jnp.ndarray, sel: jnp.ndarray, sel_cls: jnp.ndarray,
                      valid: jnp.ndarray, inv_rates: jnp.ndarray) -> jnp.ndarray:
    """Replay the PRE-commit workload each arrival routed against: [B, M].

    Row b is ``W0 + (commits of arrivals 0..b-1)`` — exactly what
    route_commit scored arrival b with.  Used by the telemetry probe hooks
    to rank batched decisions against the evolving O(M) oracle instead of
    a stale slot-start snapshot.
    """
    M = Q.shape[0]
    finite, _ = _finite_dead(inv_rates, M)
    W0 = _w0(Q, finite)
    m = jnp.arange(M, dtype=jnp.int32)

    def step(dw, xs):
        s, c, v = xs
        wpre = W0 + dw
        amt = finite[s, jnp.minimum(c, 2)] * (c < 3)
        dw = dw + jnp.where((m == s) & v, amt, 0.0)
        return dw, wpre

    _, wseq = jax.lax.scan(
        step, jnp.zeros(M, jnp.float32),
        (sel.astype(jnp.int32), sel_cls.astype(jnp.int32),
         jnp.asarray(valid, bool)))
    return wseq
