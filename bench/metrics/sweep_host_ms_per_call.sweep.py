"""The program's host time per sweep call in the window, in ms: the mean,
over the ``sweep.call`` spans inside the window's calls, of the time in
its ``sweep.grid``, ``sweep.launch`` and ``sweep.summarize`` spans (the
program's span buffer, ``repro.telemetry.spans``; the same
``time.perf_counter`` clock as the window's call spans).  None where the
program records no such spans."""
PARTS = ("sweep.grid", "sweep.launch", "sweep.summarize")


def read(ctx):
    try:
        from repro.telemetry.spans import spans
    except ImportError:
        return None
    calls = ctx["window"].get("spans") or []
    recorded = spans()
    per_call = []
    for c in recorded:
        if c.name != "sweep.call" or not any(
                a <= c.start and c.end <= b for a, b in calls):
            continue
        per_call.append(sum(
            s.end - s.start for s in recorded
            if s.name in PARTS and s.parent == "sweep.call"
            and c.start <= s.start and s.end <= c.end))
    if not per_call:
        return None
    return 1e3 * sum(per_call) / len(per_call)
