"""Reduce a JAX profiler trace (``*.xplane.pb``) to the benchmark's numbers.

- The traced window is the host span ``bench.window`` that the harness
  opens around the measured window.  The chip's tracer keeps a bounded
  number of events and drops the rest (the plane's ``dropped_traces``
  stat); where it dropped any, the window is cut at the end of the last
  operation recorded on the chips, so that what follows covers only time
  in which every device operation was recorded.  ``window_s`` is the
  length of that window, and ``truncated`` says whether it was cut.
- ``busy_s``: per chip, the union of the intervals in which an operation
  ran (the ``XLA Ops`` line of ``/device:TPU:<id>``) inside the window;
  averaged over the chips.
- ``op_s`` / ``op_n``: device seconds and event count per operation, by
  its HLO instruction name (``route_commit.12``, ``fusion.317``), summed
  over the chips.
- ``device_ops``: the ten operations that took most device time.
- ``idle_gaps``: the ten longest gaps between busy intervals of the first
  chip, each named by the innermost ``bench.*`` host span around the
  gap's midpoint (what the benchmark's host loop was doing then).

Host and device events of one trace share one clock (the profiler's).
"""
from __future__ import annotations

import glob
import os

import numpy as np

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def op_name(event_name: str) -> str:
    """``%fusion.317 = f32[...] fusion(...)`` -> ``fusion.317``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _union(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """[n, 2] disjoint sorted intervals covering the given ones."""
    if starts.size == 0:
        return np.zeros((0, 2), np.int64)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return np.stack([s[first], reach[last]], axis=1)


def reduce_profile(pd, device_ids) -> dict:
    """Reduce a ``jax.profiler.ProfileData`` (see module docstring)."""
    host_spans, dev, dropped = [], {}, 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            try:
                d = int(plane.name.rsplit(":", 1)[1])
            except ValueError:
                continue
            if d not in device_ids:
                continue
            dropped += int(dict(plane.stats or {}).get("dropped_traces", 0))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    short, names, st, en = {}, [], [], []
                    for e in line.events:
                        n = e.name
                        if n not in short:
                            short[n] = op_name(n)
                        names.append(short[n])
                        st.append(e.start_ns)
                        en.append(e.end_ns)
                    dev[d] = (np.asarray(names, object),
                              np.asarray(st, np.float64),
                              np.asarray(en, np.float64))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host_spans.append((e.name, e.start_ns, e.end_ns))
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} host span")
    w0, w1 = windows[0]
    truncated = dropped > 0 and any(v[2].size for v in dev.values())
    if truncated:
        w1 = min(w1, min(float(v[2].max()) for v in dev.values()
                         if v[2].size))
    busy, op_s, op_n, gaps = [], {}, {}, []
    for d in sorted(dev):
        names, st, en = dev[d]
        st, en = np.maximum(st, w0), np.minimum(en, w1)
        keep = en > st
        names, st, en = names[keep], st[keep], en[keep]
        for n, dt in zip(names.tolist(), (en - st).tolist()):
            op_s[n] = op_s.get(n, 0.0) + dt * 1e-9
            op_n[n] = op_n.get(n, 0) + 1
        merged = _union(st, en)
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()) * 1e-9)
        if d == min(dev):
            edges = np.concatenate([[w0], merged.ravel(), [w1]])
            gaps = [(a, b) for a, b in edges.reshape(-1, 2) if b > a]

    def label(mid):
        inside = [(e - s, n) for n, s, e in host_spans
                  if s <= mid < e and n != WINDOW_SPAN]
        return min(inside)[1] if inside else "outside bench spans"

    gaps.sort(key=lambda g: g[0] - g[1])
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    return dict(
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(busy) / max(len(device_ids), 1),
        truncated=bool(truncated), dropped_events=dropped,
        op_s=op_s, op_n=op_n,
        n_device_events=sum(v[0].size for v in dev.values()),
        device_ops=[[n, s] for n, s in top],
        idle_gaps=[[label((a + b) / 2), float(b - a) * 1e-9]
                   for a, b in gaps[:10]])


def reduce_dir(trace_dir: str, devices) -> dict:
    """Read the trace under ``trace_dir`` and reduce it for ``devices``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(trace_dir))
    return reduce_profile(pd, {d.id for d in devices})


def kernel(red: dict, prefix: str) -> tuple[float, int]:
    """(device seconds, launches) of the operations named ``prefix.N``."""
    hit = [n for n in red["op_s"] if n.split(".")[0] == prefix]
    return (sum(red["op_s"][n] for n in hit),
            sum(red["op_n"][n] for n in hit))
