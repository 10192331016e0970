"""Benchmark orchestrator — one harness per paper figure/table + the
framework's complexity/roofline reports + the scenario sweeps.  Prints a
``name,seconds,headline`` CSV summary at the end.

``--help`` output is generated from the suite registry (``SUITES``), so
it can never drift from what ``--only=`` accepts — CI smoke-checks that
every registered suite is named in it (tests/test_benchmarks_cli.py).
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import balls_and_bins
import complexity
import fig2_exponential
import fig3_highload_exp
import fig4_fixedload_exp
import fig5_lognormal
import fig6_highload_logn
import fig7_fixedload_logn
import locality
import roofline_table
import router_bench
import scenarios as scenarios_suite
import trace_replay
from common import preset_from_argv
from repro.compile_cache import enable_compile_cache

# The suite registry: (name, entry point taking a Preset, one-line help).
# --only= names, --help text, and the summary CSV all come from here.
SUITES = [
    ("fig2_exponential", fig2_exponential.main,
     "paper Fig 2: mean completion vs load, exponential service"),
    ("fig3_highload_exp", fig3_highload_exp.main,
     "paper Fig 3: high-load zoom, exponential service"),
    ("fig4_fixedload_exp", fig4_fixedload_exp.main,
     "paper Fig 4: fixed load, completion time vs d, exponential"),
    ("fig5_lognormal", fig5_lognormal.main,
     "paper Fig 5: mean completion vs load, heavy-tailed lognormal"),
    ("fig6_highload_logn", fig6_highload_logn.main,
     "paper Fig 6: high-load zoom, lognormal service"),
    ("fig7_fixedload_logn", fig7_fixedload_logn.main,
     "paper Fig 7: fixed load, completion time vs d, lognormal"),
    ("locality", locality.main,
     "local/rack/remote service-fraction table per algorithm"),
    ("scenarios", scenarios_suite.main,
     "registry scenario sweep at fixed load + BP-Pod d-sensitivity"),
    ("grid", scenarios_suite.grid_main,
     "one-program mega-sweep: scenario x load x seed grid per policy, "
     "mean +/- CI columns -> BENCH_sweep.json"),
    ("trace_replay", trace_replay.main,
     "production-day trace replay throughput vs the per-slot path"),
    ("router_bench", router_bench.main,
     "routing throughput + probe-quality d-sweep -> BENCH_router.json"),
    ("complexity", complexity.main,
     "probe-count complexity table (Pod probes vs full-sweep O(M))"),
    ("balls_and_bins", balls_and_bins.main,
     "power-of-d balls-and-bins sanity check vs theory"),
    ("roofline", roofline_table.main,
     "kernel roofline / occupancy table (TPU; skips cells on CPU)"),
]

FLAGS = [
    ("--preset=smoke|quick|paper",
     "cluster scale + run length (default quick; CI uses smoke)"),
    ("--only=s1,s2", "run only the named suites (see list above)"),
    ("--grid", "shorthand for --only=grid"),
    ("--scenarios=n1,n2", "scenarios/grid: restrict the scenario set; "
     "'a+b' composes registry entries ad hoc"),
    ("--metrics-out=FILE", "scenarios/grid: collect in-jit telemetry and "
     "write the JSONL event stream to FILE"),
    ("--grid-loads=0.45,0.9", "grid: override the preset's load axis"),
    ("--grid-seeds=N", "grid: override the preset's Monte-Carlo seeds"),
    ("--policies=p1,p2", "grid: override the policy set"),
    ("--loop-baseline=K", "grid: loop K scenarios on the pre-sweep path "
     "for the wall-clock comparison (0 skips; default 3)"),
]


def usage() -> str:
    """--help text generated from SUITES + FLAGS (cannot drift)."""
    lines = [
        "usage: PYTHONPATH=src python -m benchmarks.run [flags]",
        "",
        "Runs the registered benchmark suites (all of them by default)",
        "and prints a name,seconds,headline CSV summary.",
        "",
        "suites:",
    ]
    for name, _, help_line in SUITES:
        lines.append(f"  {name:20s} {help_line}")
    lines.append("")
    lines.append("flags:")
    for flag, help_line in FLAGS:
        lines.append(f"  {flag:24s} {help_line}")
    return "\n".join(lines)


def _headline(name, out):
    try:
        if name.startswith("fig"):
            algos = out["algos"]
            bp = algos["balanced_pandas"]["mean"]
            pod = algos["balanced_pandas_pod"]["mean"]
            import numpy as np
            gain = float(np.nanmean((np.array(bp) - np.array(pod))
                                    / np.array(bp)))
            return f"BP-Pod vs BP mean-completion gain {gain:+.1%}"
        if name == "complexity":
            r = out["probes"][1]
            return (f"M={r['M']}: Pod probes {r['ratio']:.1%} of full "
                    f"(paper: 2.2%)")
        if name == "roofline":
            done = [r for r in out if isinstance(r, dict)
                    and "skipped" not in r]
            return f"{len(done)} cells"
        if name == "trace_replay":
            tp = out["throughput"]["trace_replay"]["tasks_per_s"]
            return (f"replay {tp:.0f} routed tasks/s = "
                    f"{out['speedup_vs_per_slot']:.1f}x per-slot path; "
                    f"trace_count {out['trace_count']}")
        if name == "router_bench":
            tp = out["throughput"]["balanced_pandas_pod"]
            bp_f = out["probe_quality"]["balanced_pandas_pod"]["flatness"]
            mw_f = out["probe_quality"]["jsq_maxweight_pod"]["flatness"]
            return (f"BP-Pod {tp['slots_per_s']:.0f} slots/s; regret "
                    f"flatness BP-Pod {bp_f:.2f} vs JSQ-MW-Pod {mw_f:.2f}")
        if name == "grid":
            op = next(iter(out["one_program"].values()))
            head = (f"{len(out['scenarios'])}x{len(out['loads'])}x"
                    f"{out['seeds']} grid; {op['cells']} cells/policy; "
                    f"trace_count +{op['trace_count']}")
            if out.get("speedup_per_cell"):
                head += f"; {out['speedup_per_cell']:.1f}x vs looped"
            return head
        if name == "scenarios":
            import numpy as np
            rows = out["scenarios"]
            gaps = {n: (r["algos"]["balanced_pandas_pod"]["mean"]
                        - r["algos"]["balanced_pandas"]["mean"])
                    / max(r["algos"]["balanced_pandas"]["mean"], 1e-9)
                    for n, r in rows.items()}
            worst = max(rows, key=lambda n: rows[n]["sensitivity_d"])
            return (f"{len(rows)} scenarios; BP-Pod vs BP gap "
                    f"{np.mean(list(gaps.values())):+.1%} mean; "
                    f"d-sensitivity peaks at {worst} "
                    f"({rows[worst]['sensitivity_d']:+.1%})")
    except Exception:
        pass
    return ""


def main() -> int:
    """Parse flags, run the selected suites, print the CSV summary.
    Returns 1 if any suite failed (the others still run), else 0."""
    if "--help" in sys.argv[1:] or "-h" in sys.argv[1:]:
        print(usage())
        return 0
    preset = preset_from_argv()
    enable_compile_cache()
    print(f"[benchmarks] preset={preset.name} M={preset.cluster.M} "
          f"K={preset.cluster.K} T={preset.cfg.T}")
    only = [a.split("=", 1)[1] for a in sys.argv[1:]
            if a.startswith("--only=")]
    if "--grid" in sys.argv[1:]:
        only.append("grid")
    suites = [(n, fn) for n, fn, _ in SUITES]
    if only:
        wanted = {n for o in only for n in o.split(",") if n}
        unknown = wanted - {n for n, _ in suites}
        if unknown:
            raise SystemExit(f"--only: unknown suites {sorted(unknown)}; "
                             f"see --help")
        suites = [(n, fn) for n, fn in suites if n in wanted]
    summary = []
    failed = []
    for name, fn in suites:
        t0 = time.time()
        try:
            out = fn(preset)
            summary.append((name, time.time() - t0, _headline(name, out)))
        except Exception as e:  # run the other suites, then fail below
            failed.append(name)
            summary.append((name, time.time() - t0, f"FAILED: {e}"))
            print(f"[benchmarks] {name} FAILED: {e}", file=sys.stderr)
    print("\nname,seconds,headline")
    for name, dt, head in summary:
        print(f"{name},{dt:.1f},{head}")
    if failed:
        print(f"[benchmarks] failed suites: {','.join(failed)}",
              file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
