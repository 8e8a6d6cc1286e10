"""Fluid engine time per decision: ``FluidNetworkSim.advance`` and
``configure_incremental`` on the service's worker, ms per decision."""

from benchmarks.chip.probes import span_ms


def read(run):
    return span_ms(run["spans"], {"fluid.advance", "fluid.configure"}) / run["decisions"]
