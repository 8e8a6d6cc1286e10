"""Link problems solved per decision: ``BatchStats.problems`` summed
over every batched solve in the window (the prefetch thread's included),
over the window's decisions."""


def read(run):
    return run["batch"].problems / run["decisions"]
