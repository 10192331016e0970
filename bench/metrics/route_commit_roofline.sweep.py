"""Share of the roofline reached by ``route_commit`` in the sweep, in %: the
least time the chip could take for the algorithm's operations and bytes
(``roofline.route_commit_cost`` over the traced launches, with the mean
valid arrivals of a cell-slot in the window) over the kernel's time."""
import roofline
import trace_reduce


def read(ctx):
    s, n = trace_reduce.kernel(ctx["trace"], "route_commit")
    if n == 0:
        return None
    w, tr, cfg = ctx["window"], ctx["traffic"], ctx["config"]
    launches = n * w["cells_per_launch"]
    arrivals = launches * w["routed"] / w["cell_slots"]
    C = (0 if tr["algo"] == "balanced_pandas"
         else cfg["n_replicas"] + tr["d_rack"] + tr["d_remote"])
    ops, nbytes = roofline.route_commit_cost(w["M"], launches, arrivals, C,
                                             cfg["n_replicas"])
    return roofline.roofline_share(ops, nbytes, s, ctx["device_kind"])[0]
