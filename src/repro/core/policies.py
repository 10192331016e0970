"""Routing / scheduling policies from the paper (§IV), as pure JAX functions.

Routing policies map (workloads, task locality, rng) -> chosen server.
Scheduling policies are embedded in the per-family simulators (simulator.py)
because they operate on the family's queue structure; this module provides
the shared primitives: exact lexicographic arg-min/max with masking, and
power-of-d candidate sampling.

Complexity accounting: the *simulation* of a policy is vectorized (that is
what makes it a JAX program), but the *algorithm's* message complexity — how
many queue-length/workload values the central scheduler must fetch per
decision — is the candidate-set size.  Each policy exposes
``candidates_per_decision`` so benchmarks report the paper's O(M) vs O(1)
comparison from first principles (paper §IV-C: (d+3)/M, 2.2% for M=500, d=8).

Sampling model: Pod candidates are drawn uniformly **with replacement** from
the rack-local / remote sets (the standard Mitzenmacher power-of-d model;
the collision probability for d=8 out of hundreds is <3% and only ever
*shrinks* the effective d, i.e. it is conservative for the paper's claims).
Servers are contiguous by rack, so both sets are unions of index
intervals fixed by the task's replica triple: rack-local is the triple's
1-3 distinct racks minus the replicas, remote is every other rack.  A draw
picks u uniformly below the set's size and maps it to the (u+1)-th member
by O(n_replicas) integer skips over the sorted triple — no per-server
mask, O(1) per task as the paper's complexity claim has it.
``masked_draws`` (cumulative-count inversion, O(M) per task) is the
reference these draws are tested against: same keys, same indices.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .cluster import LOCAL, RACK, REMOTE, Cluster

_INF = jnp.inf


def lex_argmin(values: jnp.ndarray, *tiebreaks: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Exact staged arg-min: minimize ``values`` over ``mask``; break ties by
    each ``tiebreaks`` array in turn (lower wins); final ties -> lowest index.

    Exact (no epsilon hacks): comparisons are staged, so float resolution
    never mixes keys.  Inputs [..., M]; reduction over the last axis.
    """
    v = jnp.where(mask, values, _INF)
    best = jnp.min(v, axis=-1, keepdims=True)
    tie = (v == best) & mask
    for tb in tiebreaks:
        t = jnp.where(tie, tb, _INF)
        tbest = jnp.min(t, axis=-1, keepdims=True)
        tie = tie & (t == tbest)
    return jnp.argmax(tie, axis=-1).astype(jnp.int32)


def lex_argmax(values: jnp.ndarray, *tiebreaks: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """``lex_argmin`` on negated values: masked argmax with tie lanes."""
    return lex_argmin(-values, *tiebreaks, mask=mask)


def masked_draws(key: jax.Array, set_mask: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """k uniform-with-replacement draws from each row of ``set_mask``.

    set_mask: bool [..., M].  Returns (idx int32 [..., k], valid bool [..., k]);
    rows with an empty set yield valid=False and index M-1.  Inversion
    sampling: the (u+1)-th set member is the first index where
    cumsum(mask) > u.  O(M) per row: the reference ``pod_candidates``'
    interval draws are tested against (identical for the same key).
    """
    csum = jnp.cumsum(set_mask.astype(jnp.int32), axis=-1)
    total = csum[..., -1]
    u = jax.random.randint(key, set_mask.shape[:-1] + (k,), 0,
                           jnp.maximum(total, 1)[..., None])
    # searchsorted(csum, u, 'right') == #(csum <= u): one fused counting op
    # over [..., k, M] instead of a vmapped binary search
    idx = jnp.sum((csum[..., None, :] <= u[..., :, None]).astype(jnp.int32),
                  axis=-1)
    valid = jnp.broadcast_to((total > 0)[..., None], idx.shape)
    return jnp.minimum(idx, set_mask.shape[-1] - 1), valid


def _interval_draws(key: jax.Array, size: jnp.ndarray, k: int, skip,
                    M: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """k draws u ~ U[0, size) per row, mapped to server ids by ``skip``.

    size: int32 [...] set sizes.  The draw and the empty-set contract
    (valid=False, index M-1) are ``masked_draws``' own, so the two agree
    draw for draw wherever ``skip`` maps u to the (u+1)-th set member."""
    u = jax.random.randint(key, size.shape + (k,), 0,
                           jnp.maximum(size, 1)[..., None])
    ok = jnp.broadcast_to((size > 0)[..., None], u.shape)
    return jnp.where(ok, skip(u), M - 1), ok


def weighted_score(W: jnp.ndarray, inv: jnp.ndarray) -> jnp.ndarray:
    """``W * inv`` under the +inf zero-rate contract (kernels/invrates.py):
    a non-finite inverse rate (drained / failed server) scores ``+inf``
    AFTER the multiply — never ``0 * inf = NaN``, and never the 0 a finite
    sentinel produced for an empty dead server (which then absorbed one
    task per outage window).  Mirrors the kernels' dead-flag mask."""
    return jnp.where(jnp.isfinite(inv), W * inv, jnp.inf)


def inv_rate_for(inv_rates: jnp.ndarray, idx: jnp.ndarray,
                 cls: jnp.ndarray) -> jnp.ndarray:
    """Reciprocal service rate of server ``idx`` for a task of class ``cls``.

    inv_rates is either the homogeneous [3] vector (every server identical —
    the seed model) or a per-server [M, 3] matrix (heterogeneous fleets,
    scenarios); the two forms are distinguished statically by ndim so jit
    traces stay branch-free.  idx/cls broadcast together.
    """
    if inv_rates.ndim == 1:
        return inv_rates[cls]
    return inv_rates[idx, cls]


@dataclasses.dataclass(frozen=True)
class PodSpec:
    """Power-of-d sampling spec: how many rack-local / remote servers to probe
    in addition to the task's local servers.  The paper's §V uses d=8 split as
    (2 rack-local, 6 remote) for Balanced-Pandas-Pod and d'=12 as (6, 6) for
    JSQ-MaxWeight-Pod scheduling."""

    d_rack: int
    d_remote: int

    @property
    def d(self) -> int:
        """Total probe budget (rack + remote candidates)."""
        return self.d_rack + self.d_remote


def pod_candidates(
    key: jax.Array,
    cluster: Cluster,
    locals_: jnp.ndarray,
    pod: PodSpec,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Candidate lists for Balanced-Pandas-Pod routing.

    locals_: int32 [..., n_rep], distinct servers per task.  Returns
    (cand_idx, cand_cls, valid), each [..., C] with C = n_rep + d_rack +
    d_remote, ordered [locals | rack draws | remote draws] so that
    first-index tie-breaking prefers faster classes — the ordering the
    paper's ArgMin notation implies.  The draws equal
    ``masked_draws(k, locality_class(cluster, locals_) == RACK / REMOTE, d)``
    for the two halves of ``key``, computed from the sorted triple alone.
    """
    k_rack, k_rem = jax.random.split(key)
    n_rep = locals_.shape[-1]
    R, M = cluster.rack_size, cluster.M
    srt = jnp.sort(locals_, axis=-1)
    rack = srt // R                                   # ascending
    first = jnp.concatenate(                          # first of its rack
        [jnp.ones_like(rack[..., :1], bool),
         rack[..., 1:] != rack[..., :-1]], axis=-1)
    # distinct racks before each replica's own, unrolled: even over three
    # lanes a cumsum lowers to a reduce-window of its own on the TPU
    ranks = [jnp.zeros_like(rack[..., 0])]
    for i in range(1, n_rep):
        ranks.append(ranks[-1] + first[..., i].astype(rack.dtype))
    rank = jnp.stack(ranks, axis=-1)
    n_racks = rank[..., -1] + 1

    def rack_skip(u):
        # u-th non-replica position of the racks laid end to end, then the
        # rank-th distinct rack's server at that offset
        pos = rank * R + srt % R                      # ascending, distinct
        for i in range(n_rep):
            u = u + (u >= pos[..., i:i + 1])
        j = u // R
        base = sum(jnp.where(first[..., i:i + 1] & (rank[..., i:i + 1] == j),
                             rack[..., i:i + 1], 0) for i in range(n_rep))
        return base * R + u % R

    def remote_skip(u):
        # step over each distinct replica rack, lowest first
        for i in range(n_rep):
            u = u + jnp.where(first[..., i:i + 1]
                              & (u >= rack[..., i:i + 1] * R), R, 0)
        return u

    rack_idx, rack_ok = _interval_draws(k_rack, n_racks * R - n_rep,
                                        pod.d_rack, rack_skip, M)
    rem_idx, rem_ok = _interval_draws(k_rem, M - n_racks * R,
                                      pod.d_remote, remote_skip, M)
    cand_idx = jnp.concatenate([locals_, rack_idx, rem_idx], axis=-1)
    shp = locals_.shape[:-1]
    cand_cls = jnp.concatenate([
        jnp.broadcast_to(jnp.int32(LOCAL), shp + (n_rep,)),
        jnp.broadcast_to(jnp.int32(RACK), shp + (pod.d_rack,)),
        jnp.broadcast_to(jnp.int32(REMOTE), shp + (pod.d_remote,)),
    ], axis=-1)
    valid = jnp.concatenate(
        [jnp.ones(shp + (n_rep,), bool), rack_ok, rem_ok], axis=-1)
    return cand_idx, cand_cls, valid


def route_pod_candidates(
    key: jax.Array,
    W: jnp.ndarray,
    cand_idx: jnp.ndarray,
    cand_cls: jnp.ndarray,
    valid: jnp.ndarray,
    inv_rates: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Argmin of weighted workload over an explicit candidate list.

    Semantics shared with kernels/pod_route.py (which accelerates exactly
    this on TPU).  Ties: faster class first (candidate ordering), then
    uniformly at random.  Returns (server, class) for each task.
    inv_rates: [3] or per-server [M, 3] (see inv_rate_for).
    """
    scores = weighted_score(W[cand_idx],
                            inv_rate_for(inv_rates, cand_idx, cand_cls))
    rnd = jax.random.uniform(key, cand_idx.shape)
    c = lex_argmin(scores, cand_cls.astype(jnp.float32), rnd, mask=valid)
    sel = jnp.take_along_axis(cand_idx, c[..., None], axis=-1)[..., 0]
    sel_cls = jnp.take_along_axis(cand_cls, c[..., None], axis=-1)[..., 0]
    return sel, sel_cls


def route_balanced_pandas_full(
    W: jnp.ndarray,
    cls: jnp.ndarray,
    inv_rates: jnp.ndarray,
    tie_rnd: jnp.ndarray,
    class_tiebreak: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Balanced-Pandas O(M) routing: argmin over all M of the weighted
    workload (paper §IV-A).  Ties -> faster class (the ArgMin term ordering;
    class_tiebreak=False ablates to uniform-random ties — the variant that
    reproduces the paper's BP-Pod>BP medium-load ordering, see EXPERIMENTS
    §Paper-claims), then ``tie_rnd`` (a [M] random priority, shared within a
    slot — unbiased across slots).  inv_rates: [3] or per-server [M, 3]."""
    m = jnp.arange(cls.shape[-1], dtype=jnp.int32)
    ww = weighted_score(W, inv_rate_for(inv_rates, m, cls))
    mask = jnp.ones(cls.shape, bool)
    keys = ((cls.astype(jnp.float32),) if class_tiebreak else ())
    sel = lex_argmin(ww, *keys,
                     jnp.broadcast_to(tie_rnd, cls.shape), mask=mask)
    sel_cls = jnp.take_along_axis(cls, sel[..., None], axis=-1)[..., 0]
    return sel, sel_cls


def route_jsq_local(
    key: jax.Array,
    Q: jnp.ndarray,
    locals_: jnp.ndarray,
) -> jnp.ndarray:
    """JSQ-MaxWeight(-Pod) / JSQ-Priority routing: join the shortest *local*
    queue (paper §IV-B).  Q: [M]; locals_: int32 [..., R].  Already O(1):
    only the n_replicas local queues are examined."""
    qloc = Q[locals_]
    rnd = jax.random.uniform(key, locals_.shape)
    mask = jnp.ones(locals_.shape, dtype=bool)
    pick = lex_argmin(qloc.astype(jnp.float32), rnd, mask=mask)
    return jnp.take_along_axis(locals_, pick[..., None], axis=-1)[..., 0]


# ----------------------------------------------------------------------------
# O(1) in-rack / out-of-rack draws (server ids are contiguous by rack, so both
# sets are index intervals — no cumsum needed).  Used by JSQ-MW-Pod scheduling.
# ----------------------------------------------------------------------------


def sample_rack_peer(key: jax.Array, cluster: Cluster, server: jnp.ndarray,
                     k: int) -> jnp.ndarray:
    """k uniform draws (with replacement) from ``server``'s rack, excluding
    itself.  server: int32 [...]; returns int32 [..., k]."""
    R = cluster.rack_size
    start = (server // R) * R
    off = server - start
    x = jax.random.randint(key, server.shape + (k,), 0, max(R - 1, 1))
    x = x + (x >= off[..., None])
    return start[..., None] + x


def sample_remote_peer(key: jax.Array, cluster: Cluster, server: jnp.ndarray,
                       k: int) -> jnp.ndarray:
    """k uniform draws (with replacement) from outside ``server``'s rack."""
    R = cluster.rack_size
    start = (server // R) * R
    u = jax.random.randint(key, server.shape + (k,), 0, max(cluster.M - R, 1))
    return u + jnp.where(u >= start[..., None], R, 0)


# ----------------------------------------------------------------------------
# Message/complexity accounting (paper §IV-C / abstract): values the central
# scheduler must fetch per decision.
# ----------------------------------------------------------------------------


def bp_candidates_per_route(cluster: Cluster, pod: Optional[PodSpec]) -> int:
    """Servers BP(-Pod) scores per routing decision (complexity table)."""
    if pod is None:
        return cluster.M
    return cluster.n_replicas + pod.d


def jsqmw_candidates_per_schedule(cluster: Cluster, pod: Optional[PodSpec]) -> int:
    """Queues JSQ-MW(-Pod) scans per scheduling decision."""
    if pod is None:
        return cluster.M
    return 1 + pod.d
