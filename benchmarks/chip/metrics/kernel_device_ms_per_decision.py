"""Device time of the circle_score kernels and the accept scan per
decision, from the profiler trace of the window."""


def read(run):
    t = run["trace"]
    if not t["kernel_events"]:
        return None
    return t["kernel_s"] * 1e3 / run["decisions"]
