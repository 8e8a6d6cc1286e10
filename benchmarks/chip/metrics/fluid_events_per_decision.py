"""Fluid engine event steps per decision: the ``events`` of the program's
``fluid/advance`` spans on the service's worker, over the window's
decisions."""

from benchmarks.chip import program_spans as ps


def read(run):
    recs = ps.window(run)
    if recs is None:
        return None
    return ps.attr_sum(recs, "fluid/advance", "events") / run["decisions"]
