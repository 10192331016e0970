"""Device time of the slot step's ``route`` phase outside the
``route_commit`` kernel, per simulated cell-slot, in us: the pads and
transposes into the kernel's lane layout and back.  See ``phase_time``."""
import phase_time


def read(ctx):
    return phase_time.us_per_slot(ctx, "route", with_kernel=False)
