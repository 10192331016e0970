"""Device time of the slot step's ``candidates`` phase per simulated cell-slot,
in us: what the kernel is handed: the pod candidate draws (masked_draws), or
full Balanced-Pandas' random tie permutation.  See ``phase_time``."""
import phase_time


def read(ctx):
    return phase_time.us_per_slot(ctx, "candidates")
