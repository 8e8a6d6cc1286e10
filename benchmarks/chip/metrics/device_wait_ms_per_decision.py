"""Time the service's worker waited for kernel results per decision: the
program's ``launch/fetch`` and ``accept/fetch`` spans, and the wait a
traced run's kernel probe moves next to them
(``program_spans.device_wait_ms``), in ms per decision of the window."""

from benchmarks.chip import program_spans as ps


def read(run):
    recs = ps.window(run)
    if recs is None or not ps.launched(recs):
        return None
    return ps.device_wait_ms(recs) / run["decisions"]
