"""PodRouter — the paper's Balanced-Pandas-Pod as a production request
router, backed by the Pallas kernels.

The router keeps the paper's per-replica 3-sub-queue bookkeeping (Q[m, c]
counts of requests queued at replica m in locality class c) and its
workload metric W_m = Q^l/alpha + Q^k/beta + Q^r/gamma, and routes each
request batch with ONE fused kernels.route_commit launch — score, route,
and queue-commit with in-kernel sequential conflict resolution, so request
b+1 in a batch scores against workloads that already include request b's
commit (no snapshot herding under bursts):

  policy="pod"  -> route_commit pod variant  (O(d) probes per request —
                   paper §IV-C candidate lists)
  policy="full" -> route_commit full variant (O(M) baseline Balanced-Pandas)

The kernel also updates Q and W in the same launch (the old three-call
pod_route/weighted_argmin + queue_update sequence is gone), and breaks
exact score ties by locality class then index — no epsilon lifts.  The
complexity counter the benchmarks report (probes per decision) is exactly
the candidate-set width handed to the kernel.

Heterogeneous fleets: pass ``rate_matrix`` ([M, 3] per-replica per-class
service rates, e.g. from repro.core.rate_matrix with scenario speeds).  The
workload metric and routing scores then divide by each replica's *own*
rates — and the SAME Pallas kernels serve both forms: their inverse-rate
operand is [3] or [M, 3] (the per-candidate rate gather rides the same
one-hot select as the workload gather), so the router never leaves the
kernel.  A
zero-rate replica (drained / outage) carries a ``+inf`` inverse rate; the
kernels mask it to a ``+inf`` score after the multiply, so it is never
selected while any live candidate exists.  With identical rate-matrix rows
the heterogeneous path is bit-identical to the homogeneous one
(tests/test_scenarios.py asserts this).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.cluster import LOCAL, RACK, REMOTE, Rates
from ..core.policies import PodSpec
from ..kernels import route_commit
from .locality import FleetTopology


@dataclasses.dataclass
class RouterStats:
    decisions: int = 0
    probes: int = 0
    routed_by_class: Optional[np.ndarray] = None

    def __post_init__(self):
        if self.routed_by_class is None:
            self.routed_by_class = np.zeros(3, np.int64)


class PodRouter:
    def __init__(self, fleet: FleetTopology, rates: Rates,
                 policy: str = "pod", pod: PodSpec = PodSpec(2, 6),
                 seed: int = 0,
                 rate_matrix: Optional[np.ndarray] = None):
        assert policy in ("pod", "full")
        self.fleet = fleet
        self.rates = rates
        self.policy = policy
        self.pod = pod
        self.M = fleet.n_replicas
        self.Q = jnp.zeros((self.M, 3), jnp.int32)
        self.W = jnp.zeros((self.M,), jnp.float32)
        self.inv_rates = 1.0 / rates.as_array()
        if rate_matrix is not None:
            rm = np.asarray(rate_matrix, np.float32)
            assert rm.shape == (self.M, 3), rm.shape
            # zero-rate (drained) replicas -> +inf inverse rate; the kernels
            # mask these to +inf scores (never 0 * inf = NaN).
            rmj = jnp.asarray(rm)
            self.inv_rate_m = jnp.where(rmj > 0, 1.0 / rmj, jnp.inf)
        else:
            self.inv_rate_m = None
        self.key = jax.random.PRNGKey(seed)
        self.stats = RouterStats()
        R = self.M // fleet.n_pods
        self._pod_of = np.arange(self.M) // R

    @property
    def heterogeneous(self) -> bool:
        return self.inv_rate_m is not None

    @property
    def _inv(self) -> jnp.ndarray:
        """The kernels' inverse-rate operand: [M, 3] when heterogeneous,
        the homogeneous [3] vector otherwise."""
        return self.inv_rate_m if self.heterogeneous else self.inv_rates

    # -- locality classes for a request batch ------------------------------

    def _classes(self, locals_: np.ndarray) -> np.ndarray:
        """locals_: [B, r] replica ids holding each request's prefix.
        Returns [B, M] class matrix."""
        B = locals_.shape[0]
        cls = np.full((B, self.M), REMOTE, np.int32)
        for b in range(B):
            pods = np.unique(self._pod_of[locals_[b]])
            cls[b, np.isin(self._pod_of, pods)] = RACK
            cls[b, locals_[b]] = LOCAL
        return cls

    def _sample_candidates(self, cls: np.ndarray, locals_: np.ndarray):
        """3 locals + d_rack + d_remote uniform samples per request."""
        B = cls.shape[0]
        rng = np.random.default_rng(int(jax.random.randint(
            self._next_key(), (), 0, 2**31 - 1)))
        C = locals_.shape[1] + self.pod.d
        idx = np.zeros((B, C), np.int32)
        ccls = np.zeros((B, C), np.int32)
        valid = np.zeros((B, C), bool)
        r = locals_.shape[1]
        idx[:, :r] = locals_
        ccls[:, :r] = LOCAL
        valid[:, :r] = True
        for b in range(B):
            for j, (want, k0, kn) in enumerate(
                    [(RACK, r, r + self.pod.d_rack),
                     (REMOTE, r + self.pod.d_rack, C)]):
                pool = np.where(cls[b] == want)[0]
                if len(pool):
                    take = rng.choice(pool, size=kn - k0)
                    idx[b, k0:kn] = take
                    ccls[b, k0:kn] = want
                    valid[b, k0:kn] = True
        return idx, ccls, valid

    def _next_key(self):
        self.key, sub = jax.random.split(self.key)
        return sub

    # -- the routing call ----------------------------------------------------

    def route(self, locals_: np.ndarray) -> np.ndarray:
        """Route a batch of requests; locals_: [B, r] replica ids holding
        each request's prefix.  Returns chosen replica ids [B].

        One fused route_commit launch per batch: request b+1 scores
        against workloads including request b's commit, and Q/W come back
        updated from the same kernel."""
        B = locals_.shape[0]
        cls = self._classes(locals_)
        inv = self._inv
        valid_b = jnp.ones((B,), bool)
        if self.policy == "full":
            # random tie priority per batch: W is lattice-valued, exact
            # ties are routine, and index-order ties hotspot low replicas
            self.Q, self.W, sel, sel_cls, _ = route_commit(
                self.Q, valid_b, inv, cls=jnp.asarray(cls),
                prio=jax.random.permutation(self._next_key(), self.M))
            self.stats.probes += B * self.M
        else:
            idx, ccls, valid = self._sample_candidates(cls, locals_)
            self.Q, self.W, sel, sel_cls, _ = route_commit(
                self.Q, valid_b, inv, cand_idx=jnp.asarray(idx),
                cand_cls=jnp.asarray(ccls), cand_valid=jnp.asarray(valid))
            self.stats.probes += B * idx.shape[1]
        self.stats.decisions += B
        np.add.at(self.stats.routed_by_class, np.asarray(sel_cls), 1)
        return np.asarray(sel)

    def complete(self, replica_ids: np.ndarray, classes: np.ndarray):
        """Mark requests finished (dequeue bookkeeping)."""
        dec = jnp.zeros((self.M, 3), jnp.int32).at[
            jnp.asarray(replica_ids), jnp.asarray(classes)].add(1)
        self.Q = jnp.maximum(self.Q - dec, 0)
        inv = self._inv
        if inv.ndim == 1:
            inv = inv[None, :]
        # same W semantics as kernels.queue_update: dead (non-finite) entries
        # contribute 0 — routing masks dead replicas by rate, never by W.
        self.W = (self.Q.astype(jnp.float32)
                  * jnp.where(jnp.isfinite(inv), inv, 0.0)).sum(-1)
