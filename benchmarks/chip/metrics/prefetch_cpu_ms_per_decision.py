"""CPU time of the epoch prefetch per decision: the thread CPU time of the
program's ``prefetch/warm`` spans, which run on the service's prefetch
thread (``serve-prefetch_0``: Allocate, Propose and Score of the
predicted next epoch, contending with the worker for the interpreter
lock), in ms per decision of the window."""

from benchmarks.chip import program_spans as ps


def read(run):
    recs = ps.window(run)
    if recs is None:
        return None
    return ps.cpu_ms(recs, "prefetch/warm", thread=None) / run["decisions"]
