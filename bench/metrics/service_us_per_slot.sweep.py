"""Device time of the slot step's ``service`` phase per simulated cell-slot, in
us: busy servers working down their in-flight task.  See ``phase_time``."""
import phase_time


def read(ctx):
    return phase_time.us_per_slot(ctx, "service")
