"""Telemetry: in-jit windowed metrics, distribution collectors, and
probe-quality instrumentation — the reporting spine of the repro.

Why
---
The paper's claims are distributional (BP-Pod ~ BP at low/medium load;
BP-Pod far less sensitive to d than JSQ-MW-Pod), but a run-level scalar
mean cannot show *when* a scenario destabilizes, *which* servers absorb
the imbalance, or *how good* the d sampled probes actually were.  This
package adds those observables without leaving the jit'd slot scan: all
collectors are pytree state threaded through ``jax.lax.scan`` — no
recompiles (static shapes from ``TelemetryConfig``), no host round-trips,
and **zero dynamics perturbation** (collectors never consume PRNG keys;
telemetry-off runs are bit-identical — tests/test_telemetry.py enforces
both).

The three layers
----------------
**Windowed time series** (``collectors.Telemetry.win`` / ``win_max``):
the run's T slots are split into ``n_windows`` equal windows
(``window_len = ceil(T / n_windows)``; the ragged last window is
narrower).  Per window, SUM channels (``collectors.WINDOW_SUMS``): slot
count, tasks-in-system, per-class queue mass, completions, busy servers,
arrivals + clipped arrivals, mean/max per-server workload, probe
rank/regret/decision counts; MAX channels (``WINDOW_MAXES``): peak N and
peak workload.  Export derives means (``export.window_records``) and the
windowed drift diagnostic (``export.windowed_drift``).

**Distribution collectors**: per-window log-spaced histograms of
per-server queue length and per-server workload, plus a whole-run
per-task sojourn histogram.  The bin convention lives in ``hist.py``
(shared with the serve engine): value v -> bin
``floor(bins_per_octave * log2(v + 1))``, so bin b covers
``[2^(b/bpo) - 1, 2^((b+1)/bpo) - 1)`` — constant ~9% relative width at
the default 8 bins/octave, which is what lets ``hist.percentiles`` read
p50/p95/p99 within a few percent (validated <5% against refsim's exact
per-task sojourns).  Sojourns are tracked refsim-style: each sub-queue
carries a static-shape FIFO ring of arrival slots (push at routing, pop
at service start, histogram record at completion); ring overflow drops
the *record*, never the task, and is counted in ``sojourn_dropped``.

**Probe quality** (Pod policies): per pod decision, the rank of the
chosen server's score among all M (0 = the probe set contained the global
optimum) and the score regret vs the O(M) argmin/argmax.  This is the
paper's d-sensitivity claim as a direct observable: BP-Pod's regret stays
flat as d shrinks; JSQ-MW-Pod's grows.

Sinks
-----
``export`` converts collected pytrees to a JSONL event stream (schema in
``export.__doc__``: run manifest -> window rows -> histograms ->
percentiles) consumed by ``benchmarks/scenarios.py --metrics-out=FILE``
and validated by ``scripts/validate_telemetry.py`` in CI.
``benchmarks/router_bench.py`` appends routing-throughput datapoints to
``BENCH_router.json`` for a PR-over-PR perf trajectory.

Entry points: ``core.simulate_with_telemetry`` /
``core.simulate_grid_with_telemetry`` return ``(SimResult, Telemetry)``.

Time, not statistics: ``spans``
-------------------------------
The collectors above record what the simulated fleet did; ``spans``
records where the program's time went.  ``phase(name)`` scopes the slot
step's six phases (``PHASES``) in the compiled program's metadata, and
``op_phases`` maps a compiled module's instructions back to them
(``core.simulator.sweep_op_phases()`` for the last sweep).  ``span(name)``
marks host work in the profiler trace and in an in-memory buffer read by
``spans()`` and ``span_totals()``.  (The function ``spans`` shadows the
submodule of the same name here: import from ``repro.telemetry.spans``
for the module's other names.)
"""
from .collectors import (
    WINDOW_MAXES,
    WINDOW_SUMS,
    Telemetry,
    TelemetryConfig,
    ZERO_PROBE,
    collect_step,
    probe_stats_max,
    probe_stats_min,
    record_sojourns,
    ring_pop,
    ring_push,
    zero_telemetry,
)
from .export import (
    SCHEMA_VERSION,
    WarmupPolicy,
    WarmupReport,
    aggregate,
    auto_extend_warmup,
    cell_view,
    format_clip_warning,
    probe_summary,
    read_jsonl,
    run_manifest,
    sojourn_percentiles,
    tail_stats,
    to_events,
    validate_events,
    window_records,
    windowed_drift,
    write_jsonl,
)
from .spans import (
    CONTAINER,
    PHASES,
    Span,
    op_phases,
    phase,
    span,
    spans,
)
from .spans import reset as reset_spans
from .spans import totals as span_totals
from .hist import (
    BINS_PER_OCTAVE,
    N_BINS,
    bin_edges,
    bin_index,
    np_hist,
    percentiles,
)

__all__ = [n for n in dir() if not n.startswith("_")]
