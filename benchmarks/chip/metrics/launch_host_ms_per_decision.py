"""Host time of the kernel launches per decision, on the service's worker:
the program's ``launch/prep`` (checks, bucketing, padding, schedule),
``launch/put`` (the uploads), ``launch/dispatch`` and ``accept/dispatch``
spans, in ms per decision of the window."""

from benchmarks.chip import program_spans as ps


def read(run):
    recs = ps.window(run)
    if recs is None or not ps.launched(recs):
        return None
    return ps.wall_ms(recs, ps.LAUNCH_HOST) / run["decisions"]
