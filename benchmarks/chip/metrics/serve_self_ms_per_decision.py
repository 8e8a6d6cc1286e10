"""Serve loop self time per decision: the worker's request handling
(``SchedulerService._handle``) less the decisions and the fluid engine
calls inside it, in ms per decision of the window."""

from benchmarks.chip.probes import span_ms


def read(run):
    spans = run["spans"]
    total = span_ms(spans, {"serve.handle"})
    inner = span_ms(spans, {"decision", "fluid.advance", "fluid.configure"})
    return (total - inner) / run["decisions"]
