"""The program's host time per sweep call in ``sweep.place`` in the
window, in ms: the mean, over the ``sweep.call`` spans inside the
window's calls, of the time in the ``sweep.place`` spans inside each
(putting the grid on the mesh; ``repro.telemetry.spans``).  None where
the program records no such span: a sweep on one device, or a program
that places nothing itself."""


def read(ctx):
    try:
        from repro.telemetry.spans import spans
    except ImportError:
        return None
    calls = ctx["window"].get("spans") or []
    recorded = spans()
    places = [s for s in recorded if s.name == "sweep.place"]
    per_call = []
    for c in recorded:
        if c.name != "sweep.call" or not any(
                a <= c.start and c.end <= b for a, b in calls):
            continue
        inside = [s.end - s.start for s in places
                  if c.start <= s.start and s.end <= c.end]
        if inside:
            per_call.append(sum(inside))
    if not per_call:
        return None
    return 1e3 * sum(per_call) / len(per_call)
