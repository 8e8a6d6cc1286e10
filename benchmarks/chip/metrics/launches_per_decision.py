"""Kernel launches per decision: ``BatchStats.launches`` summed over
every batched solve in the window, over the window's decisions."""


def read(run):
    return run["batch"].launches / run["decisions"]
