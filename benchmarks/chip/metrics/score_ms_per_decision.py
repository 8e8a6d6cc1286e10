"""Scoring per decision: the pipeline's Score stage on the service's
worker (affinity graphs, link cache, batched solves with their kernel
launches and waits), ms per decision."""

from benchmarks.chip.probes import span_ms


def read(run):
    return span_ms(run["spans"], {"score"}) / run["decisions"]
