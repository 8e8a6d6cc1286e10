"""Time the fluid engine's advance spent off the CPU per decision: the
wall time of the program's ``fluid/advance`` spans on the service's
worker less the thread's CPU time in them (waiting for the interpreter
lock, or descheduled), in ms per decision of the window."""

from benchmarks.chip import program_spans as ps


def read(run):
    recs = ps.window(run)
    if recs is None:
        return None
    cpu_ms = ps.cpu_ms(recs, "fluid/advance")
    return (ps.wall_ms(recs, ("fluid/advance",)) - cpu_ms) / run["decisions"]
