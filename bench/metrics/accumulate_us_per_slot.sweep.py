"""Device time of the slot step's ``accumulate`` phase per simulated cell-slot,
in us: the run statistics (and the collectors, when on).  See
``phase_time``."""
import phase_time


def read(ctx):
    return phase_time.us_per_slot(ctx, "accumulate")
