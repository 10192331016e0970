"""Device time of each phase of the slot step, per simulated cell-slot.

The program maps the instructions of the sweep's compiled module to the
slot step's phases (``core.simulator.sweep_op_phases()``, built from its
``jax.named_scope`` phases).  A phase's time is the device time of the
traced window's operations (``trace["op_s"]``, by HLO instruction name)
that the map gives that phase; loop containers, whose events span their
bodies, are in no phase.  The cell-slots are those of
``step_us_per_slot.sweep``: the kernel's launches times the grid cells
each serves.  ``op_s`` adds up instructions of one name from every
program in the window; the small eager programs of a call add
microseconds to a name they share with the sweep.

Where the program has no phase map (an older program), every reader gives
None.
"""
import trace_reduce

KERNEL = "route_commit"


def phase_map():
    """The program's ``{instruction: phase}`` for the last sweep, or None."""
    try:
        from repro.core.simulator import sweep_op_phases
    except ImportError:
        return None
    return sweep_op_phases() or None


def us_per_slot(ctx, phase: str, with_kernel: bool = True):
    """Device us per cell-slot of ``phase``; ``with_kernel=False`` leaves
    out the ``route_commit`` instruction (it has metrics of its own)."""
    tr = ctx["trace"]
    _, n = trace_reduce.kernel(tr, KERNEL)
    if n == 0:
        return None
    phases = phase_map()
    if phases is None:
        return None
    s = sum(t for name, t in tr["op_s"].items()
            if phases.get(name) == phase
            and (with_kernel or name.split(".")[0] != KERNEL))
    return 1e6 * s / (n * ctx["window"]["cells_per_launch"])
