"""Device time of the ``route_commit`` kernel per replayed slot, in us: the
summed duration of the kernel's events over its launches (one a slot)."""
import trace_reduce


def read(ctx):
    s, n = trace_reduce.kernel(ctx["trace"], "route_commit")
    if n == 0:
        return None
    return 1e6 * s / (n * ctx["window"]["cells_per_launch"])
