"""Host time of the fluid engine's water-filling solves (its alloc-cache
misses) per decision: the ``solve_ns`` of the program's ``fluid/advance``
spans on the service's worker, in ms per decision of the window."""

from benchmarks.chip import program_spans as ps


def read(run):
    recs = ps.window(run)
    if recs is None:
        return None
    return ps.attr_sum(recs, "fluid/advance", "solve_ns") / 1e6 / run["decisions"]
