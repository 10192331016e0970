"""Shared inverse-rate operand encoding for the scheduler kernels.

All three kernels (weighted_argmin, pod_route, queue_update) take the same
logical operand: per-(server, class) reciprocal service rates.  Callers may
pass either the homogeneous ``[3]`` vector (every server identical — the
paper's symmetric model) or a per-server ``[M, 3]`` matrix (heterogeneous
fleets — GB-PANDAS's motivating asymmetry).  A zero-rate entry (drained /
failed server) has reciprocal rate ``+inf``.

``inf`` cannot ride through the kernels directly: pod_route gathers the
matrix with a one-hot matmul (``0 * inf = NaN`` on every non-selected row)
and a zero workload on a dead server would score ``0 * inf = NaN`` instead
of ``+inf``.  So the host-side encoding splits the operand into lanes the
kernels can consume safely:

  cols 0..2   finite reciprocal rates  (non-finite entries -> 0.0)
  col  3      zero padding
  cols 4..6   dead flags (1.0 where the reciprocal rate was non-finite)
  col  7      zero padding

The kernels multiply workloads by cols 0..2 (never NaN) and mask any
(server, class) whose dead flag is set to ``+inf`` *after* the multiply —
the same guard already applied to pad lanes.  queue_update consumes only
cols 0..2: dead entries contribute 0 to the workload metric, which is safe
because routing masks dead servers by their flag, never by their W.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

CLASSES = 3
WIDTH = 8          # padded lane width: [rates 0..2 | 0 | flags 4..6 | 0]
FLAG_BASE = 4


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Shared ``interpret`` auto-default for every kernel in this package.

    None -> Mosaic on a TPU backend, the Pallas interpreter on the CPU
    backend (tests), and an error on any other backend: a kernel never
    falls back to the interpreter silently on an accelerator.  Explicit
    True/False pass through."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"no Pallas path for backend {backend!r}: the "
                       "kernels compile for TPU and interpret on CPU")


def as_matrix(inv_rates: jnp.ndarray, M: int) -> jnp.ndarray:
    """Broadcast a ``[3]`` homogeneous vector to ``[M, 3]``; pass ``[M, 3]``
    through.  Always float32."""
    inv = jnp.asarray(inv_rates, jnp.float32)
    if inv.ndim == 1:
        inv = jnp.broadcast_to(inv[None, :], (M, CLASSES))
    return inv


def encode(inv_rates: jnp.ndarray, M: int, flags: bool = True) -> jnp.ndarray:
    """Finite [M, 8] encoding of a [3] or [M, 3] inverse-rate operand.

    flags=False leaves cols 4..6 zero (queue_update, which only needs the
    finite rates and treats dead entries as contributing no workload).
    """
    inv = as_matrix(inv_rates, M)
    finite = jnp.isfinite(inv)
    enc = jnp.zeros((M, WIDTH), jnp.float32)
    enc = enc.at[:, :CLASSES].set(jnp.where(finite, inv, 0.0))
    if flags:
        enc = enc.at[:, FLAG_BASE:FLAG_BASE + CLASSES].set(
            (~finite).astype(jnp.float32))
    return enc
