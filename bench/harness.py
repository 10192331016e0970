#!/usr/bin/env python3
"""Run one benchmark cell of ``BENCHMARK.json`` on the chips of this machine.

Usage (from the root of a checkout):

  python3 bench/harness.py --workload NAME --seed N --seconds S --trace 0|1

A cell names a configuration (``bench/configs/<config>.json``, the fleet)
and a traffic mix (``bench/traffic/<traffic>.json``).  The traffic names
its entry (``bench/entries/<entry>.py``), which builds and warms the
program in set-up, drives it back to back for ``--seconds`` and then
checks what the window produced against the plain reference.  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries
the cell's per-layer metrics, each read by ``bench/metrics/<name>.py``.

The last line of standard output is the JSON result; the numbers compared
for ``correct`` end standard error and the result's ``checks`` key.  With
no TPU, or fewer chips than the cell asks for, the run exits 1 and prints
no result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

T_PROCESS = time.perf_counter()   # set-up is counted from here

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(msg: str) -> None:
    """A progress line on standard error."""
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    """Parse one JSON file."""
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` by its path (names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(bench: dict, workload: str) -> dict:
    """The cell, its configuration and traffic, and the metrics it reports."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))

    def applies(metric, reported=None):
        if "workloads" in metric:
            return workload in metric["workloads"]
        return reported is None or metric["moves"] in reported

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if applies(m, names)]
    return dict(cell=cell, config=config, traffic=traffic, end_to_end=e2e,
                per_layer=layer)


def tpu_devices(chips: int):
    """The first ``chips`` TPU devices, or None (with the reason on
    standard error) when JAX finds no TPU or too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: needs a TPU, JAX found {devs[0].platform!r}")
        return None
    if len(devs) < chips:
        log(f"bench: the cell needs {chips} chips, JAX found {len(devs)}")
        return None
    return devs[:chips]


class CompileCounter:
    """Counts executables that JAX builds (``n``: each XLA compilation and
    each load from the persistent cache, both of which miss the in-memory
    cache) and the loads among them (``hits``)."""

    def __init__(self):
        import jax
        self.n = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, devices,
             t_start: float) -> dict:
    """Set up, measure, check; return the result object (see module doc).

    Everything but the look for a chip happens here, so a test can drive a
    whole run on the CPU."""
    import jax
    import trace_reduce
    from repro.compile_cache import enable_compile_cache

    log(f"bench: compile cache {enable_compile_cache()}")
    # every program the run builds goes to the cache, the small ones too,
    # so that a second run of the cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    entry = load_module("entries", traffic["entry"])
    log(f"bench: entry set-up starts at {time.perf_counter() - t_start:.3f} s")
    state = entry.setup(config, traffic, seed, devices)
    setup_s = time.perf_counter() - t_start
    log(f"bench: setup_s={setup_s:.6f} executables_built={compiles.n} "
        f"of_them_from_cache={compiles.hits}")

    c0, p0 = compiles.n, entry.compile_count()
    if trace:
        # the trace of a window is some hundreds of MB: it goes to TMPDIR
        # and is deleted once reduced
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        win = entry.window(state, seconds, seed)
    if trace:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"bench: stop_trace_s={time.perf_counter() - t0:.3f}")
    if "spans" in win:
        log("bench: call_s="
            + ",".join(f"{b - a:.6f}" for a, b in win["spans"]))
    # an earlier line than the checks: what compiled inside the window
    log(f"bench: compiles_in_window={compiles.n - c0} "
        f"program_traces_in_window={entry.compile_count() - p0}")
    peak = memory_peak(devices)
    device = dict(platform=devices[0].platform, kind=devices[0].device_kind,
                  count=len(devices), memory_peak_bytes=peak)

    metrics, breakdown = {}, None
    if trace:
        t0 = time.perf_counter()
        try:
            red = trace_reduce.reduce_dir(trace_dir, devices)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"bench: trace_reduce_s={time.perf_counter() - t0:.3f}")
        log(f"bench: trace window_s={red['window_s']:.6f} "
            f"busy_s={red['busy_s']:.6f} truncated={red['truncated']} "
            f"dropped_events={red['dropped_events']} "
            f"device_events={red['n_device_events']}")
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        ctx = dict(trace=red, window=win, config=config, traffic=traffic,
                   device_kind=devices[0].device_kind)
        for m in spec["per_layer"]:
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = dict(value=value, unit=m["unit"])
        breakdown = dict(device_ops=red["device_ops"],
                         idle_gaps=red["idle_gaps"])
    else:
        values = dict(win["end_to_end"], setup_s=setup_s)
        for m in spec["end_to_end"]:
            metrics[m["name"]] = dict(value=values[m["name"]],
                                      unit=m["unit"])

    del state
    t0 = time.perf_counter()
    checks = entry.check(config, traffic, win, seed)
    log(f"bench: reference_s={time.perf_counter() - t0:.3f}")
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for name, c in checks.items():
        log(f"check {name} value={c['value']!r} limit={c['limit']!r}")
    out = dict(correct=correct, attempted=win["attempted"],
               failed=win["failed"], metrics=metrics, device=device)
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    """Parse arguments, find the chips, run the cell, print the result."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        log(f"bench: the program is missing ({src}/repro)")
        return 1
    sys.path[:0] = [src, BENCH]
    spec = cell_spec(load_json(os.path.join(ROOT, "BENCHMARK.json")),
                     args.workload)
    devices = tpu_devices(spec["cell"]["chips"])
    if devices is None:
        return 1
    log(f"bench: platform={devices[0].platform} "
        f"device_kind={devices[0].device_kind} count={len(devices)} "
        f"found after {time.perf_counter() - T_PROCESS:.3f} s")
    out = run_cell(spec, args.seed, args.seconds, bool(args.trace), devices,
                   T_PROCESS)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
