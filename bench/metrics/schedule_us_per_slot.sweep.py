"""Device time of the slot step's ``schedule`` phase per simulated cell-slot,
in us: idle servers starting a queued task, with the duration draws.  See
``phase_time``."""
import phase_time


def read(ctx):
    return phase_time.us_per_slot(ctx, "schedule")
