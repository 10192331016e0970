"""Device time of the slot step's ``arrivals`` phase per simulated cell-slot,
in us: the Poisson count, the replica draws and the [A, M] locality classes.
See ``phase_time``."""
import phase_time


def read(ctx):
    return phase_time.us_per_slot(ctx, "arrivals")
