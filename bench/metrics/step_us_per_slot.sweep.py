"""Device busy time outside ``route_commit`` per simulated cell-slot, in us:
the slot step's service, scheduling, arrival sampling, locality classes,
candidate draws and accumulators.  The cell-slots are the kernel's
launches in the traced window times the grid cells each serves."""
import trace_reduce


def read(ctx):
    tr = ctx["trace"]
    s, n = trace_reduce.kernel(tr, "route_commit")
    if n == 0:
        return None
    chips = ctx["window"]["chips"]
    return (1e6 * (tr["busy_s"] * chips - s)
            / (n * ctx["window"]["cells_per_launch"]))
