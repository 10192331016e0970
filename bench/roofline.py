"""Published peaks of each chip, and the algorithmic cost of ``route_commit``.

Peaks are keyed by ``device.device_kind``; a kind that is not in the table
is an error, never a default.

``route_commit_cost`` counts what the routing algorithm needs, from its
sizes alone: the fleet size ``M``, the launches (one per simulated slot and
cell), the valid arrivals routed, and the candidates per arrival ``C``
(0 for full Balanced-Pandas, which scores every server).  It never reads
the kernel's operands or their padding, so a change to the kernel's
layout leaves the count as it is.

Per launch, the workload recompute reads the [M, 3] queue counts and the
[M, 3] inverse rates and does 3 multiplies and 2 adds per server; the
commit writes the [M, 3] counts and the [M] workloads.  Per valid arrival,
full routing scores and compares every server (2M) and pod routing its C
candidates (2C), plus one workload update and one queue increment.  The
inputs per arrival are its replica triple (full) or its C candidate ids
(pod); the outputs its server and class.  Counts are 4-byte words.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip,
    # 16 GB HBM at 819 GB/s.  No peak is published for the vector unit.
    "TPU v5 lite": dict(flops_per_s=197e12, hbm_bytes_per_s=819e9),
}


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; KeyError if unknown."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def route_commit_cost(M: int, launches: float, arrivals: float, C: int,
                      n_replicas: int = 3) -> tuple[float, float]:
    """(operations, bytes) of ``launches`` launches routing ``arrivals``
    valid arrivals in all (see module docstring)."""
    per_launch_ops = 5 * M
    per_launch_bytes = 4 * (3 * M + 3 * M + 3 * M + M)
    scored = M if C == 0 else C
    per_arrival_ops = 2 * scored + 2
    per_arrival_bytes = 4 * ((n_replicas if C == 0 else C) + 2)
    return (launches * per_launch_ops + arrivals * per_arrival_ops,
            launches * per_launch_bytes + arrivals * per_arrival_bytes)


def roofline_share(ops: float, nbytes: float, seconds: float,
                   device_kind: str) -> tuple[float, str]:
    """(percent of the roofline, the bound: "compute" or "memory"): the
    least time the chip could take over the time taken."""
    p = peaks(device_kind)
    t_ops = ops / p["flops_per_s"]
    t_mem = nbytes / p["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
