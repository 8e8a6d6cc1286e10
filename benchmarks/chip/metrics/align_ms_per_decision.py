"""Alignment per decision: the pipeline's Align stage (ranking and
Algorithm 1) on the service's worker, ms per decision."""

from benchmarks.chip.probes import span_ms


def read(run):
    return span_ms(run["spans"], {"align"}) / run["decisions"]
