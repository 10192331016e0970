"""Pallas TPU kernels for the scheduler's compute hot spots.

route_commit    — THE batched hot path: fused score -> route -> queue-commit
                  of a whole arrival batch per launch, with in-kernel
                  sequential conflict resolution (arrival b+1 sees arrival
                  b's commit via a VMEM W-delta accumulator) and an exact
                  class-priority tie-break lane.  Full-BP and pod variants
                  behind one wrapper.
weighted_argmin — O(M) Balanced-Pandas snapshot routing (the baseline the
                  paper improves on); pod_route — O(d) power-of-d snapshot
                  routing; queue_update — fused scatter + workload
                  recompute.  These three remain the per-arrival
                  (sequential route_mode) building blocks.

ref.py holds the pure-jnp oracles.  ``interpret=None`` resolves per
backend (``invrates.resolve_interpret``): Mosaic on TPU, the interpreter on
CPU, an error on anything else.

All kernels take their inverse-rate operand as either the homogeneous
``[3]`` vector or a per-server ``[M, 3]`` matrix (heterogeneous fleets);
zero-rate servers carry ``+inf`` inverse rates and are masked to ``+inf``
scores after the multiply (invrates.py documents the finite encoding).
"""
from . import ref
from .pod_route import pod_route
from .queue_update import queue_update
from .route_commit import route_commit
from .weighted_argmin import weighted_argmin

__all__ = ["ref", "pod_route", "queue_update", "route_commit",
           "weighted_argmin"]
