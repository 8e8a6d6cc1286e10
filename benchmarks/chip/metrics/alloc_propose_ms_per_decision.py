"""Host scheduler stages per decision: the pipeline's Allocate and
Propose stages on the service's worker (the prefetch thread's are left
out), ms per decision."""

from benchmarks.chip.probes import span_ms


def read(run):
    return span_ms(run["spans"], {"allocate", "propose"}) / run["decisions"]
