"""Bytes uploaded host to device by the kernel launches per decision, on
every thread: the ``bytes`` of the program's ``launch/put`` and
``accept/dispatch`` spans, over the window's decisions."""

from benchmarks.chip import program_spans as ps


def read(run):
    recs = ps.window(run)
    if recs is None or not ps.launched(recs):
        return None
    return sum(ps.attr_sum(recs, name, "bytes", thread=None)
               for name in ps.UPLOADS) / run["decisions"]
