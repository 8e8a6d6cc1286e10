"""Share of its roofline the circle_score argmin kernel reached: the mean
least time of the window's launches on this chip (``roofline.least_time``,
from each launch's rows, angle counts and needed shifts, recorded on the
host) over their mean device time in the trace (the
``circle_score_argmin_pallas`` custom calls).  Means, so that a launch
that straddles the window's edge on one side only shifts nothing.
Nothing when no launch ran."""

from benchmarks.chip.roofline import least_time


def read(run):
    t = run["trace"]
    n, secs = t["argmin_events"], t["argmin_s"]
    if not run["launches"] or not n or secs <= 0:
        return None
    least, _ = least_time(run["launches"], run["device_kind"])
    return 100.0 * (least / len(run["launches"])) / (secs / n)
